"""One run of one benchmark cell.

Everything that belongs to a configuration, a traffic mix, a cell or a
per-layer metric is a file found by name:

* ``BENCHMARK.json`` — cells, configurations (their ``file``), metrics;
* ``bench/models/<architecture>.py`` — weights, reference, work counts;
* ``bench/traffic/<traffic>.json`` — the mix and the engine it fills;
* ``bench/cells/<workload>.json`` — the limits ``correct`` is held to;
* ``bench/metrics/<metric>.py`` — one per-layer metric's reader;
* ``bench/peaks.json`` — the chips' peaks, by ``device_kind``.

A run serves the mix through the program's own path, ``ServeStream.run``
over one ``DecodeEngine``, with more requests than the window can
finish. Set-up (weights, executables, a warm pass over every prompt
bucket, and the ramp until as many requests as slots have been
admitted) ends where the window opens; the window closes ``seconds``
later through the requests' own ``deadline_s`` (the stream's clock
starts at the window's opening), so what is still queued or live then
ends ``expired``: cut, not failed.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import sys
import tempfile
import time
from dataclasses import dataclass
from functools import partial
from pathlib import Path
from types import ModuleType

import numpy as np

from bench import traffic
from bench import trace as tr

#: terminal statuses that count as failed (``expired`` is the window's cut)
FAILED = ("quarantined", "shed")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """No accelerator, too few chips, or a chip with no peaks entry."""


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def load_module(path: Path) -> ModuleType:
    name = "bench_file_" + "_".join(path.with_suffix("").parts[-2:]).replace(
        ".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    entry: dict           # the cell's entry in BENCHMARK.json
    spec: dict            # the configuration file
    mix: dict             # the traffic file
    limits: dict          # bench/cells/<name>.json
    model: ModuleType     # bench/models/<architecture>.py
    end_to_end: list      # names of this cell's end-to-end metrics
    readers: dict         # per-layer metric name -> read(window)


def load_cell(root: Path, name: str) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    spec = load_json(root / conf["file"])
    d = root / "bench"

    def mine(metric):
        return name in metric.get("workloads", [name])

    return Cell(
        name=name, entry=entry, spec=spec,
        mix=load_json(d / "traffic" / f"{entry['traffic']}.json"),
        limits=load_json(d / "cells" / f"{name}.json"),
        model=load_module(d / "models" / f"{spec['architecture']}.py"),
        end_to_end=[m["name"] for m in bench["end_to_end"] if mine(m)],
        readers={m["name"]: load_module(d / "metrics" / f"{m['name']}.py").read
                 for m in bench["per_layer"] if mine(m)})


def seed_key(seed: int):
    """A PRNG key from all bits of ``seed`` (``PRNGKey`` keeps 32)."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def use_compile_cache(root: Path) -> str:
    """JAX's persistent cache at ``<checkout>/.jax_cache``, every
    executable kept (the default skips those compiled in under 1 s)."""
    import jax

    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def chip(root: Path, chips: int) -> tuple[dict, dict]:
    """The device record and its peaks; NoChip where there is no TPU,
    too few of them, or no peaks entry for its kind."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devs[0].platform!r}; "
                     "the benchmark measures a TPU and does not fall back")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    peaks = load_json(root / "bench" / "peaks.json")["devices"]
    kind = devs[0].device_kind
    if kind not in peaks:
        raise NoChip(f"device kind {kind!r} has no entry in "
                     "bench/peaks.json")
    return ({"platform": devs[0].platform, "kind": kind,
             "count": len(devs)}, peaks[kind])


# --------------------------------------------------------------------- #
# the served window
# --------------------------------------------------------------------- #
class Recorder:
    """Wraps one engine's host protocol, on the instance, to timestamp
    deliveries, waves and prefills and to open the window. Reads only
    host-side bookkeeping the program keeps (``emitted_prev``); no device
    read of its own. Each wrapper is also a host span in the trace."""

    def __init__(self, eng):
        import jax

        self.slots = eng.slots
        self.t_open: float | None = None
        self.admitted: set = set()
        self.deliveries: list = []  # (t, handle, prompt_len, before, m)
        self.waves: list = []       # {"t0", "t1", "rows": [(len, m)]}
        self.prefills: list = []    # (t, prompt_len)
        self.compiles: list = []    # times of executables built
        self.traces_open = 0
        span = jax.profiler.TraceAnnotation
        o_admit, o_run, o_commit = eng.admit, eng.run_wave, eng.commit_wave
        o_prefill, o_evict = eng.prefill, eng.evict

        def admit(req, pre=None, handle=None):
            with span("bench.admit"):
                slot = o_admit(req, pre, handle=handle)
            if slot is not None:
                self.admitted.add(handle)
                if self.t_open is None and len(self.admitted) >= self.slots:
                    self._open()
            return slot

        def prefill(req):
            with span("bench.prefill"):
                out = o_prefill(req)
            self.prefills.append((time.monotonic(), out["T"]))
            return out

        def run_wave(wave_len=8, *, crash_hook=None):
            t0 = time.monotonic()
            with span("bench.wave"):
                o_run(wave_len, crash_hook=crash_hook)
            self.waves.append({"t0": t0, "t1": time.monotonic()})

        def commit_wave():
            before = {s: (h["handle"], h["prompt_len"], h["emitted_prev"])
                      for s, h in eng._live.items()}
            with span("bench.commit"):
                out = o_commit()
            t = time.monotonic()
            fin = {s: res.emitted for s, _, res in out[0]}
            rows = []
            for s, (handle, T, e0) in before.items():
                m = (fin[s] if s in fin else eng._live[s]["emitted_prev"]) - e0
                rows.append((T + e0, m))
                if m:
                    self.deliveries.append((t, handle, T, e0, m))
            self.waves[-1].update(t_commit=t, rows=rows)
            return out

        def evict(slot, status="expired"):
            with span("bench.evict"):
                return o_evict(slot, status)

        eng.admit, eng.prefill, eng.evict = admit, prefill, evict
        eng.run_wave, eng.commit_wave = run_wave, commit_wave

    def _open(self):
        import jax

        from repro.runtime.serve import trace_total

        with jax.profiler.TraceAnnotation("bench.window_open"):
            self.t_open = time.monotonic()
        self.traces_open = trace_total()

    def clock(self) -> float:
        """The stream's clock: 0 until the window opens, then seconds
        since, so a request's ``deadline_s`` is the window's close."""
        return 0.0 if self.t_open is None else time.monotonic() - self.t_open

    def on_event(self, event: str, secs: float, **kw):
        if event == COMPILE_EVENT:
            self.compiles.append((time.monotonic(), kw.get("fun_name")))


@dataclass
class Window:
    """What one served window did; what the per-layer readers read."""

    seconds: float
    t_open: float
    slots: int
    spec: dict
    model: ModuleType
    peaks: dict
    deliveries: list
    waves: list            # recorder's waves joined with wave_stats
    prefills: list
    results: list
    admitted: set
    retraces: int
    compiles: list         # executables built in the window, by name
    queued_at_close: int = 0
    trace: dict | None = None

    @property
    def t_close(self) -> float:
        return self.t_open + self.seconds

    def inside(self, t: float) -> bool:
        return self.t_open < t <= self.t_close

    def overlap(self, t0: float, t1: float) -> float:
        """Seconds of ``[t0, t1]`` inside the window."""
        return max(0.0, min(t1, self.t_close) - max(t0, self.t_open))

    def share(self, t0: float, t1: float) -> float:
        """Fraction of ``[t0, t1]`` inside the window."""
        return self.overlap(t0, t1) / (t1 - t0) if t1 > t0 else \
            float(self.inside(t1))


def make_params(cell: Cell, seed: int):
    import jax

    init = jax.jit(partial(cell.model.init_params, cell.spec))
    return jax.block_until_ready(init(seed_key(seed)))


def program_config(cell: Cell):
    from repro.configs import get_config

    return get_config(cell.spec["program_config"]).replace(
        **cell.model.program_fields(cell.spec))


def warm_up(eng, mix: dict) -> None:
    """Run every executable the window uses once, at this cell's shapes:
    a prefill and an admission per prompt bucket, a wave (with its
    snapshot), an eviction."""
    import jax

    from repro.runtime.serve import Request

    wave_len = mix["engine"]["wave_len"]
    for T in traffic.buckets(mix):
        if not eng.has_free_slot:
            eng.wave(wave_len)       # one token each: every slot frees
        req = Request(prompt=np.zeros(T, np.int32), max_new=1)
        if eng.admit(req, eng.prefill(req)) is None:
            raise RuntimeError(f"warm-up: no pages for a {T}-token prompt")
    eng.wave(wave_len)
    eng.evict(eng.admit(req, eng.prefill(req)))
    jax.block_until_ready(eng.st)


def serve_window(cell: Cell, seed: int, seconds: float, *, peaks: dict,
                 trace: bool) -> tuple[Window, object]:
    """Serve one window; returns it and the weights (for the check)."""
    import jax

    from repro.runtime.serve import (DecodeEngine, Request, ServeStream,
                                     trace_total)

    e = cell.mix["engine"]
    params = make_params(cell, seed)
    eng = DecodeEngine(program_config(cell), params, slots=e["slots"],
                       page_size=e["page_size"], max_ctx=e["max_ctx"],
                       max_new_cap=traffic.max_output(cell.mix),
                       name=cell.entry["config"])
    warm_up(eng, cell.mix)
    used = max((d.memory_stats() or {}).get("bytes_in_use", 0)
               for d in jax.devices())
    log(f"bench: device bytes in use after the warm-up {used}")
    rec = Recorder(eng)
    stream = ServeStream(eng, wave_len=e["wave_len"], clock=rec.clock)
    reqs = [Request(prompt=p, max_new=n, seed=i, deadline_s=seconds)
            for i, (p, n) in enumerate(traffic.make_requests(
                cell.mix, seed, cell.spec["vocab_size"]))]
    jax.monitoring.register_event_duration_secs_listener(rec.on_event)
    tdir = tempfile.TemporaryDirectory() if trace else None
    try:
        if tdir:
            jax.profiler.start_trace(tdir.name)
        try:
            results = stream.run(reqs)
        finally:
            if tdir:
                jax.profiler.stop_trace()
        if rec.t_open is None:
            raise RuntimeError("the window never opened: fewer requests "
                               "than slots were admitted")
        rep = stream.last_report
        for w, (_, wall, steps, _, live) in zip(rec.waves, rep.wave_stats):
            w.update(wall=wall, steps=steps, live=live)
        win = Window(
            seconds=seconds, t_open=rec.t_open, slots=eng.slots,
            spec=cell.spec, model=cell.model, peaks=peaks,
            deliveries=rec.deliveries, waves=rec.waves,
            prefills=rec.prefills, results=results,
            admitted=rec.admitted, retraces=trace_total() - rec.traces_open,
            compiles=[f for t, f in rec.compiles
                      if rec.t_open < t <= rec.t_open + seconds])
        win.queued_at_close = sum(
            1 for i, r in enumerate(results)
            if r.status == "expired" and i not in rec.admitted)
        if win.queued_at_close == 0:
            raise RuntimeError("the queue ran dry before the window closed: "
                               "the traffic file needs more requests")
        if tdir:
            files = sorted(Path(tdir.name).rglob("*.xplane.pb"))
            events = tr.read_xplane(files[-1])
            start = tr.marker(events, "bench.window_open")
            win.trace = tr.summarize(events, start, seconds * 1e9)
    finally:
        jax.monitoring.unregister_event_duration_listener(rec.on_event)
        if tdir:
            tdir.cleanup()
    return win, params


# --------------------------------------------------------------------- #
# metrics
# --------------------------------------------------------------------- #
def end_to_end(win: Window, setup_s: float) -> dict:
    """``serve_tok_s``: tokens delivered inside the window over its
    length, all the work over all the time; ``setup_s``: process start
    to the window's opening."""
    tokens = sum(m for t, _, _, _, m in win.deliveries if win.inside(t))
    return {"serve_tok_s": (tokens / win.seconds, "tokens/s"),
            "setup_s": (setup_s, "s")}


def outcome(win: Window) -> dict:
    """Requests attempted (admitted to a slot), failed, cut, finished."""
    status = {}
    for r in win.results:
        status[r.status] = status.get(r.status, 0) + 1
    failed = sum(1 for r in win.results if r.status in FAILED)
    cut = sum(1 for i, r in enumerate(win.results)
              if r.status == "expired" and i in win.admitted)
    return {"attempted": len(win.admitted), "failed": failed, "cut": cut,
            "finished": sum(1 for r in win.results if r.ok),
            "status": status}


# --------------------------------------------------------------------- #
# correct: the served tokens against the plain reference
# --------------------------------------------------------------------- #
def sample(results, n: int, seed: int) -> list:
    """The finished request with most served tokens, and ``n - 1`` more
    drawn from the seed."""
    done = sorted((r for r in results if r.ok and r.emitted),
                  key=lambda r: (-r.emitted, r.index))
    if not done:
        return []
    rng = np.random.default_rng([seed, 1])
    rest = [done[i] for i in sorted(rng.choice(
        np.arange(1, len(done)), min(n, len(done)) - 1, replace=False))]
    return [done[0]] + rest


def served_gaps(cell: Cell, params, picked: list, *, control: bool = False
                ) -> dict:
    """The widest gap by which a served token's logit lies below the
    reference's best, over every served token of ``picked``. With
    ``control``, also the widest gap of the token that the fp8 control
    puts first at the same positions."""
    import jax
    import jax.numpy as jnp

    cap = cell.mix["engine"]["max_ctx"]
    spec, model = cell.spec, cell.model
    hidden = {f: jax.jit(partial(model.hidden, spec, fp8=f))
              for f in (False, True)}
    head = {f: jax.jit(partial(model.head, spec, fp8=f))
            for f in (False, True)}
    out = {"program": 0.0, "tokens": 0}
    if control:
        out["control"] = 0.0
    with jax.default_matmul_precision("highest"):
        for r in picked:
            seq = np.zeros(cap + 1, np.int32)
            seq[:len(r.tokens)] = r.tokens
            inp, tgt = jnp.asarray(seq[:-1]), jnp.asarray(seq[1:])
            lo, hi = r.prompt_len - 1, len(r.tokens) - 1
            h = hidden[False](params, inp)
            gap = np.asarray(head[False](params, h, tgt)[0])[lo:hi]
            out["program"] = max(out["program"], float(gap.max()))
            out["tokens"] += hi - lo
            if control:
                best8 = head[True](params, hidden[True](params, inp), tgt)[1]
                gap8 = np.asarray(head[False](params, h, best8)[0])[lo:hi]
                out["control"] = max(out["control"], float(gap8.max()))
    return out


def free_program_state(win: Window) -> None:
    """Keep only the finished results, and collect the engine (unreachable
    once ``serve_window`` has returned, but held in reference cycles by
    the recorder's wrappers) so that its device state is freed before
    the reference runs."""
    win.results = [r for r in win.results if r.ok]
    gc.collect()


# --------------------------------------------------------------------- #
def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run(root: Path, name: str, seed: int, seconds: float, trace: bool, *,
        t_start: float) -> dict:
    """One run of cell ``name``: the result line as a dict."""
    import jax

    use_compile_cache(root)
    cell = load_cell(root, name)
    device, peaks = chip(root, cell.entry["chips"])
    cache = {"hits": 0, "misses": 0}

    def on_cache(event, **_):
        for k in cache:
            if event == f"/jax/compilation_cache/cache_{k}":
                cache[k] += 1

    jax.monitoring.register_event_listener(on_cache)
    try:
        win, params = serve_window(cell, seed, seconds, peaks=peaks,
                                   trace=trace)
    finally:
        jax.monitoring.unregister_event_listener(on_cache)
    setup_s = win.t_open - t_start
    log(f"bench: compile cache {cache['hits']} hits, {cache['misses']} "
        "misses while serving")
    stats = [d.memory_stats() or {} for d in jax.devices()]
    device["memory_peak_bytes"] = max(s.get("peak_bytes_in_use", 0)
                                      for s in stats)
    res = outcome(win)
    log(f"bench: {name} seed {seed}: set-up {setup_s:.3f} s; "
        f"{res['attempted']} requests admitted, {res['finished']} finished, "
        f"{res['cut']} cut at the close, {res['failed']} failed; "
        f"{win.queued_at_close} still queued; statuses "
        f"{res['status']}")
    log(f"bench: retraces in the window {win.retraces}, executables built "
        f"in the window {len(win.compiles)} {win.compiles}")

    if trace:
        if win.trace is None:
            raise RuntimeError("the trace has no device events in the window")
        metrics = {}
        for mname, read in cell.readers.items():
            value = read(win)
            if value is not None:
                metrics[mname] = {"value": float(value[0]), "unit": value[1]}
        device["busy_s"] = win.trace["busy_s"]
        device["window_s"] = win.trace["window_s"]
    else:
        e2e = end_to_end(win, setup_s)
        metrics = {k: {"value": float(v), "unit": u}
                   for k, (v, u) in e2e.items() if k in cell.end_to_end}

    free_program_state(win)
    picked = sample(win.results, cell.mix["check"]["requests"], seed)
    gaps = served_gaps(cell, params, picked)
    limit = cell.limits["logit_gap"]
    checks = {
        "logit_gap": {"value": gaps["program"], "limit": limit},
        "served_tokens_compared": {"value": gaps["tokens"], "limit": 1},
        "retraces": {"value": win.retraces, "limit": 0},
        "builds_in_window": {"value": len(win.compiles), "limit": 0},
    }
    correct = (gaps["tokens"] >= 1 and gaps["program"] <= limit
               and win.retraces == 0 and not win.compiles)
    line = {"correct": correct, "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": device}
    if trace:
        line["breakdown"] = {"device_ops": win.trace["device_ops"],
                             "idle_gaps": win.trace["idle_gaps"]}
    line["checks"] = checks
    log(f"bench: {len(picked)} finished requests compared with the float32 "
        "reference (logit_gap: widest gap of a served token's logit below "
        "the reference's best; at most the limit. served_tokens_compared: "
        "at least the limit. retraces, builds_in_window: at most the limit)")
    for k, c in checks.items():
        log(f"check {k} {c['value']} limit {c['limit']}")
    return line
