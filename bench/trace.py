"""From a profiler trace to device busy time, per-executable device time,
the device operations that took most time, and idle gaps labelled with
the host span they fall in.

``read_xplane`` turns the profiler's ``.xplane.pb`` into plain event
tuples; ``summarize`` works on those tuples alone, so that a small
recorded trace checks it.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict

#: device lines: one event per executable run, one per device operation
MODULES_LINE, OPS_LINE = "XLA Modules", "XLA Ops"
#: the harness's host spans (``jax.profiler.TraceAnnotation`` names)
SPAN_PREFIX = "bench."


def read_xplane(path) -> dict:
    """``{"device": [(plane, line, name, start_ns, dur_ns)], "host":
    [(thread, name, start_ns, dur_ns)]}``; host events are the harness's
    own spans only."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    device, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name not in (MODULES_LINE, OPS_LINE):
                    continue
                for ev in line.events:
                    device.append((plane.name, line.name, ev.name,
                                   ev.start_ns, ev.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        host.append((line.name, ev.name, ev.start_ns,
                                     ev.duration_ns))
    return {"device": device, "host": host}


#: control-flow ops whose events span the ops of their bodies
CONTAINERS = ("%while", "%conditional", "%call")


def op_label(name: str) -> str:
    """``%fusion.2 = f32[48,16]{1,0:T(8,128)} fusion(...)`` ->
    ``%fusion.2 f32[48,16]``: the instruction and its shape."""
    head, _, rest = name.partition(" = ")
    shape = re.match(r"\(?[a-z0-9]+\[[0-9,]*\]", rest)
    return f"{head} {shape.group(0).lstrip('(')}" if shape else head


def module_base(name: str) -> str:
    """``jit_wave(12)`` / ``jit_wave.3`` -> ``jit_wave``."""
    return re.split(r"[(.]", name, maxsplit=1)[0]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, d, lo, hi):
    return max(s, lo), min(s + d, hi)


def summarize(events: dict, start_ns: float, window_ns: float,
              top: int = 10) -> dict | None:
    """Reduce the events inside ``[start_ns, start_ns + window_ns)``.

    Busy time is the union of the device operations' intervals (of the
    executables' where a plane has no operation line), averaged over the
    device planes. Returns None where no device plane has an event."""
    lo, hi = start_ns, start_ns + window_ns
    planes = defaultdict(lambda: {MODULES_LINE: [], OPS_LINE: []})
    for plane, line, name, s, d in events["device"]:
        a, b = _clip(s, d, lo, hi)
        if b > a:
            planes[plane][line].append((name, a, b))
    planes = {p: v for p, v in planes.items()
              if v[MODULES_LINE] or v[OPS_LINE]}
    if not planes:
        return None
    busy, modules, ops, gaps = 0.0, defaultdict(float), defaultdict(float), []
    for lines in planes.values():
        spans = lines[OPS_LINE] or lines[MODULES_LINE]
        merged = _union([(a, b) for _, a, b in spans])
        busy += sum(b - a for a, b in merged)
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        for name, a, b in lines[MODULES_LINE]:
            modules[module_base(name)] += b - a
        for name, a, b in lines[OPS_LINE]:
            label = op_label(name)
            if not label.startswith(CONTAINERS):
                ops[label] += b - a
    n = len(planes)
    idle = defaultdict(float)
    label = host_labeller(events["host"])
    for a, b in gaps:
        idle[label((a + b) / 2)] += b - a
    rank = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": busy / n * 1e-9,
        "window_s": window_ns * 1e-9,
        "modules_s": {k: v / n * 1e-9 for k, v in modules.items()},
        "device_ops": [[k, v / n * 1e-9] for k, v in rank],
        "idle_gaps": [[k, v / n * 1e-9] for k, v in
                      sorted(idle.items(), key=lambda kv: -kv[1])[:top]],
    }


def host_labeller(host):
    """``label(t_ns)``: the harness span covering ``t_ns``, looked up on
    the thread that runs the waves first and then on the others (the
    prefill thread), else ``"host.other"`` (the stream's own scheduling
    and waits)."""
    threads = defaultdict(list)
    for thread, name, s, d in host:
        threads[thread].append((s, s + d, name))
    order = sorted(threads, key=lambda th: not any(
        n == SPAN_PREFIX + "wave" for _, _, n in threads[th]))
    tables = []
    for th in order:
        spans = sorted(threads[th])
        tables.append(([s for s, _, _ in spans], spans))

    def label(t_ns: float) -> str:
        for starts, spans in tables:
            # the latest-starting span that still covers t: the innermost
            i = bisect.bisect_right(starts, t_ns) - 1
            for j in range(i, max(i - 16, -1), -1):
                if spans[j][1] > t_ns:
                    return spans[j][2]
        return "host.other"

    return label


def marker(events: dict, name: str) -> float | None:
    """Start of the first host span called ``name``."""
    starts = [s for _, n, s, _ in events["host"] if n == name]
    return min(starts) if starts else None
