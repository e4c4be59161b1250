"""The program's named scopes and ``serve.*`` host spans in a profiler
trace: the wave executable's operation time split by scope, the time of
each host span, and idle gaps labelled by the innermost ``serve.*`` span
(the harness's ``bench.*`` spans as the fallback).

An operation's scope path (``jit(wave)/while/body/.../attn/kv_write/
scatter``) is the ``tf_op`` stat of its event metadata, which
``jax.profiler.ProfileData`` does not expose; ``read_xplane`` parses the
``.xplane.pb`` itself, with a descriptor of the few fields of
``tsl/profiler/protobuf/xplane.proto`` it reads (the parser skips the
rest). ``summarize`` works on the tuples alone, so a small recorded
trace checks it. Busy time and gaps are those of ``bench/trace.py``:
the union of the operations' intervals on each device plane.
"""

from __future__ import annotations

import bisect
from collections import defaultdict

from bench import trace as tr

#: ``jax.named_scope`` names in the model step and the wave body; an
#: operation belongs to the innermost one on its path, or to ``""``
SCOPES = ("kv_write", "kv_gather", "attn", "mlp", "ssm", "head")
#: the program's host spans (``jax.profiler.TraceAnnotation`` names)
SERVE = "serve."
#: the executable whose operation time the scopes split
WAVE = "jit_wave"

_I64, _U64, _STR, _MSG = 3, 4, 9, 11       # FieldDescriptorProto types
#: message -> fields (name, number, type, repeated)
_XPLANE = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("name", 2, _STR, False), ("lines", 3, "XLine", True),
               ("event_metadata", 4, "EventEntry", True),
               ("stat_metadata", 5, "StatEntry", True)],
    "EventEntry": [("key", 1, _I64, False),
                   ("value", 2, "XEventMetadata", False)],
    "StatEntry": [("key", 1, _I64, False),
                  ("value", 2, "XStatMetadata", False)],
    "XLine": [("id", 1, _I64, False), ("name", 2, _STR, False),
              ("timestamp_ns", 3, _I64, False),
              ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, _I64, False),
               ("offset_ps", 2, _I64, False),
               ("duration_ps", 3, _I64, False)],
    "XEventMetadata": [("id", 1, _I64, False), ("name", 2, _STR, False),
                       ("stats", 5, "XStat", True)],
    "XStatMetadata": [("id", 1, _I64, False), ("name", 2, _STR, False)],
    "XStat": [("metadata_id", 1, _I64, False),
              ("uint64_value", 3, _U64, False),
              ("int64_value", 4, _I64, False),
              ("str_value", 5, _STR, False),
              ("ref_value", 7, _U64, False)],
}


def _xspace_class():
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    F = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(name="bench_xplane.proto",
                                           package="bench_xplane",
                                           syntax="proto3")
    for msg, fields in _XPLANE.items():
        m = f.message_type.add(name=msg)
        for name, number, typ, repeated in fields:
            field = m.field.add(
                name=name, number=number,
                label=F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL)
            if isinstance(typ, str):
                field.type, field.type_name = _MSG, f".bench_xplane.{typ}"
            else:
                field.type = typ
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def read_xplane(path) -> dict:
    """``{"ops": [(plane, module, scope_path, op, start_ns, dur_ns)],
    "host": [(thread, name, start_ns, dur_ns)]}``: every operation on the
    device planes' ``XLA Ops`` lines, with its executable's base name
    (``jit_wave``), its scope path and its ``op_label``; host events are
    the ``serve.*`` and ``bench.*`` spans, each thread named
    ``<line name>/<line id>``; times in whole nanoseconds, as
    ``jax.profiler.ProfileData`` gives them to ``bench/trace.py``."""
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    ops, host = [], []
    for plane in space.planes:
        meta = {e.key: e.value for e in plane.event_metadata}
        if plane.name.startswith("/device:"):
            stat = {e.key: e.value.name for e in plane.stat_metadata}
            program, scope = {}, {}
            for i, m in meta.items():
                for s in m.stats:
                    name = stat.get(s.metadata_id)
                    if name == "program_id":
                        program[i] = str(s.uint64_value or s.int64_value)
                    elif name == "tf_op":
                        value = (stat.get(s.ref_value, "") if s.ref_value
                                 else s.str_value)
                        scope[i] = value.rstrip(":")
            lines = {ln.name: ln for ln in plane.lines}
            modules = {}
            for ev in getattr(lines.get(tr.MODULES_LINE), "events", ()):
                name = meta[ev.metadata_id].name
                base, _, pid = name.partition("(")
                modules[pid.rstrip(")")] = tr.module_base(base)
            op = {i: (plane.name, modules.get(program.get(i), ""),
                      scope.get(i, ""), tr.op_label(m.name))
                  for i, m in meta.items()}
            line = lines.get(tr.OPS_LINE)
            for ev in getattr(line, "events", ()):
                ops.append(op[ev.metadata_id]
                           + (line.timestamp_ns + ev.offset_ps // 1000,
                              ev.duration_ps // 1000))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                thread = f"{line.name}/{line.id}"
                for ev in line.events:
                    name = meta[ev.metadata_id].name
                    if name.startswith((SERVE, tr.SPAN_PREFIX)):
                        host.append((thread, name,
                                     line.timestamp_ns
                                     + ev.offset_ps // 1000,
                                     ev.duration_ps // 1000))
    return {"ops": ops, "host": host}


def scope_of(path: str) -> str:
    """The innermost of :data:`SCOPES` on an operation's scope path (the
    first path, where XLA joined a fusion's with ``;``), else ``""``."""
    for part in reversed(path.split(";")[0].split("/")):
        if part in SCOPES:
            return part
    return ""


def summarize(events: dict, start_ns: float, window_ns: float
              ) -> dict | None:
    """Reduce the events inside ``[start_ns, start_ns + window_ns)``.

    ``scopes_s``: :data:`WAVE`'s operation time split by :func:`scope_of`
    (control-flow containers left out, as ``bench/trace.py`` leaves them
    out of ``device_ops``), so the values add up to that operation time;
    ``scope_ops``: its ten operations that took most time, with their
    scope;
    ``spans_s`` and ``span_counts``: each ``serve.*`` span's time inside
    the window and how many start there; ``idle_gaps``: idle device time
    by the innermost ``serve.*`` span covering the gap, else the
    ``bench.*`` one, else ``host.other``. Device times are averaged over
    the device planes; None where no plane has an operation."""
    lo, hi = start_ns, start_ns + window_ns
    planes = defaultdict(list)
    scopes, by_op = defaultdict(float), defaultdict(float)
    for plane, mod, path, op, s, d in events["ops"]:
        a, b = max(s, lo), min(s + d, hi)
        if b <= a:
            continue
        planes[plane].append((a, b))
        if mod == WAVE and not op.startswith(tr.CONTAINERS):
            scope = scope_of(path)
            scopes[scope] += b - a
            by_op[(scope, op)] += b - a
    if not planes:
        return None
    n = len(planes)
    label = labeller(events["host"])
    idle = defaultdict(float)
    for spans in planes.values():
        merged = tr._union(spans)
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                idle[label((a + b) / 2)] += b - a
    spans_s, counts = defaultdict(float), defaultdict(int)
    for _, name, s, d in events["host"]:
        if name.startswith(SERVE):
            spans_s[name] += max(0.0, min(s + d, hi) - max(s, lo)) * 1e-9
            counts[name] += lo <= s < hi
    rank = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    return {
        "scopes_s": {k: v / n * 1e-9 for k, v in scopes.items()},
        "scope_ops": [[sc, op, v / n * 1e-9] for (sc, op), v in rank],
        "spans_s": dict(spans_s),
        "span_counts": dict(counts),
        "idle_gaps": [[k, v / n * 1e-9] for k, v in
                      sorted(idle.items(), key=lambda kv: -kv[1])],
    }


def labeller(host):
    """``label(t_ns)``: the innermost ``serve.*`` span covering ``t_ns``,
    looked up on the thread that runs the waves first, then on the
    others; else the ``bench.*`` span ``bench/trace.py`` would give."""
    threads = defaultdict(list)
    for thread, name, s, d in host:
        if name.startswith(SERVE):
            threads[thread].append((s, s + d, name))
    order = sorted(threads, key=lambda th: not any(
        n == SERVE + "wave" for _, _, n in threads[th]))
    tables = [sorted(threads[th]) for th in order]
    starts = [[s for s, _, _ in spans] for spans in tables]
    fallback = tr.host_labeller([h for h in host
                                 if h[1].startswith(tr.SPAN_PREFIX)])

    def label(t_ns: float) -> str:
        for st, spans in zip(starts, tables):
            # spans nest on a thread: the latest-starting one that still
            # covers t is the innermost
            i = bisect.bisect_right(st, t_ns) - 1
            for j in range(i, max(i - 16, -1), -1):
                if spans[j][1] > t_ns:
                    return spans[j][2]
        return fallback(t_ns)

    return label
