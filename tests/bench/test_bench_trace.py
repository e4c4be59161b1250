"""The trace reduction on small traces with known busy, idle and
per-executable times."""

import json
from pathlib import Path

import pytest

from bench import trace as tr

DATA = Path(__file__).parent / "data"
D, M, O = "/device:TPU:0", tr.MODULES_LINE, tr.OPS_LINE


def made_trace():
    """A window [1000, 2000) ns: a wave 1000-1400 with ops 1000-1100,
    1050-1200 (overlapping) and 1300-1400; a prefill 1500-1700 (one op);
    a wave from 1900 running past the close (op 1900-2100). Host spans:
    commit 1400-1500 on the main thread, prefill 1450-1700 on another."""
    return {
        "device": [
            (D, M, "jit_wave(3)", 1000, 400), (D, O, "fusion.1", 1000, 100),
            (D, O, "fusion.2", 1050, 150), (D, O, "fusion.1", 1300, 100),
            (D, M, "jit_pf.12", 1500, 200), (D, O, "dot.4", 1500, 200),
            (D, M, "jit_wave(3)", 1900, 300), (D, O, "fusion.1", 1900, 200),
            (D, M, "jit_wave(3)", 500, 100), (D, O, "fusion.1", 500, 100),
        ],
        "host": [
            ("main", "bench.window_open", 1000, 1),
            ("main", "bench.wave", 990, 420),
            ("main", "bench.commit", 1400, 100),
            ("pool", "bench.prefill", 1450, 250),
            ("main", "bench.wave", 1890, 200),
        ],
    }


def test_known_busy_idle_and_executables():
    ev = made_trace()
    start = tr.marker(ev, "bench.window_open")
    s = tr.summarize(ev, start, 1000)
    # busy: 1000-1200, 1300-1400, 1500-1700, 1900-2000 = 600 ns
    assert s["busy_s"] == pytest.approx(600e-9)
    assert s["window_s"] == pytest.approx(1000e-9)
    assert s["modules_s"] == pytest.approx({"jit_wave": 500e-9,
                                            "jit_pf": 200e-9})
    assert s["device_ops"][0] == ["fusion.1", pytest.approx(300e-9)]
    # gaps: 1200-1300 (in wave), 1400-1500 (commit), 1700-1900 (none)
    gaps = dict((k, v) for k, v in s["idle_gaps"])
    assert gaps == pytest.approx({"bench.wave": 100e-9,
                                  "bench.commit": 100e-9,
                                  "host.other": 200e-9})


def test_no_device_events_reads_nothing():
    ev = made_trace()
    ev["device"] = [e for e in ev["device"] if e[3] < 900]
    assert tr.summarize(ev, 1000, 1000) is None


def test_op_labels_and_containers():
    assert tr.op_label("%fusion.2 = f32[48,16]{1,0:T(8,128)} fusion(x)") \
        == "%fusion.2 f32[48,16]"
    assert tr.op_label("%copy.9 = (bf16[4]{0}, s32[]) copy(y)") == \
        "%copy.9 bf16[4]"
    assert tr.op_label("fusion.1") == "fusion.1"
    ev = {"device": [(D, O, "%while.3 = (s32[]) while(x)", 0, 100),
                     (D, O, "%fusion.4 = f32[8]{0} fusion(x)", 10, 30)],
          "host": []}
    s = tr.summarize(ev, 0, 100)
    assert s["busy_s"] == pytest.approx(100e-9)
    assert s["device_ops"] == [["%fusion.4 f32[8]", pytest.approx(30e-9)]]


def test_module_base_names():
    assert tr.module_base("jit_wave(12)") == "jit_wave"
    assert tr.module_base("jit_admit.3") == "jit_admit"
    assert tr.module_base("jit_pf") == "jit_pf"


def test_recorded_chip_trace_against_a_timeline():
    """6 ms of a TPU v5e trace of the SSD cell: busy time from the
    reduction equals a nanosecond timeline's count, and each executable's
    time equals its events' clipped durations summed by hand."""
    import numpy as np

    rec = json.loads((DATA / "trace_v5e_mamba2_6ms.json").read_text())
    ev = {"device": [tuple(e) for e in rec["device"]],
          "host": [tuple(e) for e in rec["host"]]}
    window = 6_000_000
    s = tr.summarize(ev, 0, window)
    line = np.zeros(window, bool)
    modules = {}
    for _, ln, name, a, d in ev["device"]:
        lo, hi = max(a, 0), min(a + d, window)
        if hi <= lo:
            continue
        if ln == O:
            line[lo:hi] = True
        else:
            base = name.split("(")[0]
            modules[base] = modules.get(base, 0) + (hi - lo)
    assert s["busy_s"] == pytest.approx(line.sum() * 1e-9)
    assert s["window_s"] == pytest.approx(window * 1e-9)
    assert s["modules_s"] == pytest.approx(
        {k: v * 1e-9 for k, v in modules.items()})
    assert 0 < s["busy_s"] <= s["window_s"]
    assert sum(v for _, v in s["idle_gaps"]) == pytest.approx(
        s["window_s"] - s["busy_s"])
    assert set(modules) >= {"jit_snap", "jit_pf"}
