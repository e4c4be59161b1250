"""The program's named scopes and ``serve.*`` spans, and their reduction
(``bench/scopes.py``): the scopes reach the compiled wave, the wave's
operation time splits into them without a remainder, idle gaps take the
innermost ``serve.*`` span, a recorded chip trace reduces to known
totals, and the delivery reader reads what the harness's reads."""

import json
import re
from pathlib import Path

import numpy as np
import pytest

from bench import harness
from bench import scopes as sc
from bench import trace as tr
from bench_tiny import ROOT, tiny_root

DATA = Path(__file__).parent / "data"
D = "/device:TPU:0"
W = "jit(wave)/while/body/while/body/closed_call/checkpoint"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("scopes"))


@pytest.mark.parametrize("name, want", [
    ("tiny-dense.tiny", {"kv_write", "kv_gather", "attn", "mlp", "head"}),
    ("tiny-ssd.tiny", {"ssm", "head"})])
def test_compiled_wave_carries_the_scopes(root, name, want):
    import jax.numpy as jnp

    from repro.runtime.serve import DecodeEngine

    cell = harness.load_cell(root, name)
    e = cell.mix["engine"]
    eng = DecodeEngine(harness.program_config(cell),
                       harness.make_params(cell, 3), slots=e["slots"],
                       page_size=e["page_size"], max_ctx=e["max_ctx"],
                       max_new_cap=8)
    text = eng._wave_fn.lower(eng.params, eng.st,
                              jnp.int32(e["wave_len"])).compile().as_text()
    paths = re.findall(r'op_name="(jit\(wave\)/[^"]*)"', text)
    assert {sc.scope_of(p) for p in paths} == want | {""}


def test_scope_of_takes_the_innermost_on_the_first_path():
    assert sc.scope_of(f"{W}/attn/kv_write/scatter") == "kv_write"
    assert sc.scope_of(f"{W}/attn/dot_general") == "attn"
    assert sc.scope_of(f"{W}/mlp/add;checkpoint/attn/add") == "mlp"
    assert sc.scope_of("jit(wave)/while/body/while/body/"
                       "dynamic_update_slice") == ""
    assert sc.scope_of("") == ""


def made_trace():
    """A window [0, 1000) ns. A wave 0-600 with scoped ops, an unscoped
    copy, an op under none of the scopes, and its ``%while`` container;
    a prefill 800-900. Host spans on the scheduler thread: serve.wave
    (with bench.wave inside it, and serve.block), then bench.commit
    around serve.commit around serve.sync, then only bench.wave."""
    ops = [
        (D, "jit_wave", "jit(wave)/while", "%while.3 s32[3]", 0, 600),
        (D, "jit_wave", f"{W}/attn/kv_write/scatter", "%scatter.1 bf16[8]",
         0, 50),
        (D, "jit_wave", f"{W}/attn/kv_gather/gather", "%fusion.1 bf16[8]",
         50, 100),
        (D, "jit_wave", f"{W}/attn/dot_general", "%fusion.2 bf16[8]",
         150, 100),
        (D, "jit_wave", f"{W}/mlp/dot_general;{W}/attn/add",
         "%fusion.3 bf16[8]", 250, 150),
        (D, "jit_wave", "jit(wave)/while/body/head/scatter",
         "%fusion.4 s32[3]", 400, 20),
        (D, "jit_wave", "", "%copy.5 bf16[40,8]", 440, 120),
        (D, "jit_wave", "jit(wave)/while/cond/lt", "%lt.6 pred[]", 560, 40),
        (D, "jit_pf", "jit(pf)/while/body/mlp/dot_general",
         "%fusion.7 bf16[8]", 800, 100),
        (D, "jit_wave", f"{W}/mlp/dot_general", "%fusion.3 bf16[8]",
         -100, 90),                         # before the window
    ]
    host = [
        ("main/1", "serve.wave", -10, 620),
        ("main/1", "bench.wave", -5, 610),
        ("main/1", "serve.block", 5, 600),
        ("main/1", "bench.commit", 600, 150),
        ("main/1", "serve.commit", 610, 130),
        ("main/1", "serve.sync", 620, 100),
        ("main/1", "bench.wave", 760, 40),
        ("pool/2", "serve.prefill", 700, 200),
        ("main/1", "serve.prefill_wait", 905, 90),
    ]
    return {"ops": ops, "host": host}


def test_scopes_partition_the_wave_op_time_and_gaps_take_serve_spans():
    s = sc.summarize(made_trace(), 0, 1000)
    ns = pytest.approx
    assert s["scopes_s"] == {"kv_write": ns(50e-9), "kv_gather": ns(100e-9),
                             "attn": ns(100e-9), "mlp": ns(150e-9),
                             "head": ns(20e-9), "": ns(160e-9)}
    # every op of jit_wave inside the window but the container, once
    assert sum(s["scopes_s"].values()) == ns(580e-9)
    assert s["scope_ops"][0] == ["mlp", "%fusion.3 bf16[8]", ns(150e-9)]
    # gaps: 600-800 (serve.sync at its middle, 700), 900-1000 (950:
    # prefill_wait on the scheduler thread, before the prefill thread)
    assert dict(s["idle_gaps"]) == {"serve.sync": ns(200e-9),
                                    "serve.prefill_wait": ns(100e-9)}
    assert s["spans_s"]["serve.wave"] == ns(610e-9)
    assert s["spans_s"]["serve.sync"] == ns(100e-9)
    assert s["span_counts"] == {"serve.wave": 0, "serve.block": 1,
                                "serve.commit": 1, "serve.sync": 1,
                                "serve.prefill": 1,
                                "serve.prefill_wait": 1}


def test_gaps_fall_back_to_bench_spans_then_host_other():
    ev = made_trace()
    ev["host"] = [h for h in ev["host"] if h[1] != "serve.sync"
                  and not h[1].startswith("serve.prefill")]
    s = sc.summarize(ev, 0, 1000)
    # 700 lies in serve.commit; 950 in no span at all
    assert dict(s["idle_gaps"]) == pytest.approx(
        {"serve.commit": 200e-9, "host.other": 100e-9})
    ev["host"] = [h for h in ev["host"] if h[1].startswith("bench.")]
    s = sc.summarize(ev, 0, 1000)
    assert dict(s["idle_gaps"]) == pytest.approx(
        {"bench.commit": 200e-9, "host.other": 100e-9})


def test_no_device_events_reads_nothing():
    ev = made_trace()
    ev["ops"] = [o for o in ev["ops"] if o[4] + o[5] <= 0]
    assert sc.summarize(ev, 0, 1000) is None


def test_recorded_chip_trace_reduces_to_known_scope_totals():
    """A wave's end, its commit and the next wave's first step on a
    v5e: the whole-pool copies lie under no scope, the MLP's matrix
    products under ``mlp``, and the device waits while the host blocks
    on and then syncs the finished wave."""
    ev = json.loads((DATA / "trace_v5e_granite_scoped.json").read_text())
    s = sc.summarize(ev, 0, 19e6)
    assert s["scopes_s"] == pytest.approx({
        "": 0.009914494, "attn": 0.000237942, "kv_gather": 0.00028479,
        "kv_write": 0.000005966, "mlp": 0.000682193, "head": 0.000019263},
        rel=1e-9)
    wave = sum(min(o[4] + o[5], 19e6) - max(o[4], 0) for o in ev["ops"]
               if o[1] == "jit_wave" and not o[3].startswith(tr.CONTAINERS))
    assert sum(s["scopes_s"].values()) == pytest.approx(wave * 1e-9)
    top = {op: scope for scope, op, _ in s["scope_ops"]}
    assert top["%copy.57 bf16[40,769,8,16,64]"] == ""
    assert top["%bitcast_add_fusion.4 bf16[3,1,2048]"] == "mlp"
    assert s["idle_gaps"][0] == ["serve.block", pytest.approx(0.004315816)]
    assert s["spans_s"]["serve.sync"] == pytest.approx(0.00214896)
    # the same events, as bench/trace.py reads them, are as busy
    old = {"device": [(o[0], tr.OPS_LINE, o[3], o[4], o[5])
                      for o in ev["ops"]],
           "host": [tuple(h) for h in ev["host"]]}
    busy = tr.summarize(old, 0, 19e6)["busy_s"]
    assert busy + sum(v for _, v in s["idle_gaps"]) == pytest.approx(19e-3)


def write_xplane(path):
    """A two-plane trace written with the reader's own descriptor: a
    device plane (an executable run, two ops, one with its scope path
    as a shared ``ref_value`` string) and a host plane with spans."""
    msg = sc._xspace_class()()
    dev = msg.planes.add(name=D)
    dev.stat_metadata.add(key=1).value.name = "program_id"
    dev.stat_metadata.add(key=2).value.name = "tf_op"
    dev.stat_metadata.add(key=3).value.name = f"{W}/attn/kv_write/scatter:"
    mod = dev.event_metadata.add(key=10).value
    mod.name = "jit_wave(77)"
    for key, name, ref, tf_op in ((11, "%scatter.1 = bf16[8]{0} scatter()",
                                   3, ""),
                                  (12, "%copy.2 = bf16[4]{0} copy()", 0,
                                   "jit(wave)/while:")):
        m = dev.event_metadata.add(key=key).value
        m.name = name
        m.stats.add(metadata_id=1, uint64_value=77)
        m.stats.add(metadata_id=2, ref_value=ref, str_value=tf_op)
    line = dev.lines.add(id=1, name=tr.MODULES_LINE, timestamp_ns=1000)
    line.events.add(metadata_id=10, offset_ps=0, duration_ps=500_000)
    line = dev.lines.add(id=2, name=tr.OPS_LINE, timestamp_ns=1000)
    line.events.add(metadata_id=11, offset_ps=10_000, duration_ps=200_000)
    line.events.add(metadata_id=12, offset_ps=250_000, duration_ps=100_000)
    host = msg.planes.add(name="/host:CPU")
    for key, name in ((1, "serve.sync"), (2, "bench.commit"),
                      (3, "$threading.py:1 run")):
        host.event_metadata.add(key=key).value.name = name
    line = host.lines.add(id=5, name="python", timestamp_ns=2000)
    for key, off in ((2, 0), (1, 5_000), (3, 9_000)):
        line.events.add(metadata_id=key, offset_ps=off, duration_ps=1_000)
    path.write_bytes(msg.SerializeToString())


def test_read_xplane_finds_modules_scopes_and_spans(tmp_path):
    f = tmp_path / "t.xplane.pb"
    write_xplane(f)
    ev = sc.read_xplane(f)
    assert ev["ops"] == [
        (D, "jit_wave", f"{W}/attn/kv_write/scatter", "%scatter.1 bf16[8]",
         1010.0, 200.0),
        (D, "jit_wave", "jit(wave)/while", "%copy.2 bf16[4]", 1250.0,
         100.0)]
    assert ev["host"] == [("python/5", "bench.commit", 2000.0, 1.0),
                          ("python/5", "serve.sync", 2005.0, 1.0)]
    # the profiler's own reader sees the same planes, names and times
    old = tr.read_xplane(f)
    assert [(n, s, d) for _, line, n, s, d in old["device"]
            if line == tr.OPS_LINE] == [
        ("%scatter.1 = bf16[8]{0} scatter()", 1010.0, 200.0),
        ("%copy.2 = bf16[4]{0} copy()", 1250.0, 100.0)]
    assert old["host"] == [("python", "bench.commit", 2000.0, 1.0)]


class Res:
    def __init__(self, deliveries):
        self.deliveries = deliveries


def test_delivery_reader_reads_the_harness_statistic():
    """The program's deliveries of two requests give the same per-token
    times as the harness's records of the same commits."""
    c = harness.load_cell(ROOT, "granite-3-2b.longctx")
    base = dict(seconds=10.0, t_open=100.0, slots=8, spec=c.spec,
                model=c.model, peaks={}, waves=[], prefills=[],
                admitted=set(), retraces=0, compiles=[])
    # request 7: admitted and first delivery before the window, two
    # inside; request 8: first delivery inside, one after the close
    results = [Res([(98.0, 0), (99.0, 8), (100.5, 8), (101.5, 4)]),
               Res([(101.0, 0), (102.0, 8), (103.0, 8), (111.0, 8)]),
               Res([])]
    d = [(99.0, 7, 100, 0, 8), (100.5, 7, 100, 8, 8), (101.5, 7, 100, 16, 4),
         (102.0, 8, 50, 0, 8), (103.0, 8, 50, 8, 8), (111.0, 8, 50, 16, 8)]
    w = harness.Window(deliveries=d, results=results, **base)
    got = c.readers["delivery_gap_p95_ms.serve"](w)
    per = [1.5 / 8] * 8 + [0.25] * 4 + [0.125] * 8
    assert got == (pytest.approx(np.percentile(per, 95) * 1e3), "ms")
    assert got == c.readers["tpot_p95_ms.serve"](w)


def test_delivery_reader_reads_nothing_without_deliveries():
    """A program whose results keep no deliveries (or keep only first
    ones) gives no reading, and raises nothing."""
    c = harness.load_cell(ROOT, "granite-3-2b.longctx")
    base = dict(seconds=10.0, t_open=100.0, slots=8, spec=c.spec,
                model=c.model, peaks={}, deliveries=[], waves=[],
                prefills=[], admitted=set(), retraces=0, compiles=[])
    read = c.readers["delivery_gap_p95_ms.serve"]
    assert read(harness.Window(results=[object()], **base)) is None
    w = harness.Window(results=[Res([(101.0, 0), (102.0, 4)])], **base)
    assert read(w) is None
