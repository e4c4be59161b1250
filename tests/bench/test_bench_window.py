"""The window's accounting and the per-layer readers, on a window built
by hand: cut requests count as cut and not as failed, each end-to-end
metric is all the work over all the time of the window, each reader
reads what its docstring says."""

import numpy as np
import pytest

from bench import harness
from bench_tiny import ROOT


class Res:
    def __init__(self, status, emitted=0):
        self.status, self.emitted = status, emitted

    @property
    def ok(self):
        return self.status in ("ok", "retried_ok")


def window(**kw):
    c = harness.load_cell(ROOT, "granite-3-2b.longctx")
    peaks = harness.load_json(ROOT / "bench" / "peaks.json")["devices"][
        "TPU v5 lite"]
    base = dict(seconds=10.0, t_open=100.0, slots=8, spec=c.spec,
                model=c.model, peaks=peaks, deliveries=[], waves=[],
                prefills=[], results=[], admitted=set(), retraces=0,
                compiles=[])
    base.update(kw)
    return harness.Window(**base), c


def test_cut_requests_are_cut_not_failed():
    results = [Res("ok", 5), Res("expired", 3), Res("expired", 0),
               Res("expired", 0), Res("quarantined", 2), Res("shed")]
    w, _ = window(results=results, admitted={0, 1, 2, 4})
    out = harness.outcome(w)
    assert out["attempted"] == 4
    assert out["cut"] == 2          # expired while live (1) or admitted (2)
    assert out["failed"] == 2       # quarantined + shed
    assert out["finished"] == 1
    assert out["status"] == {"ok": 1, "expired": 3, "quarantined": 1,
                             "shed": 1}


def test_end_to_end_counts_only_the_window():
    # request 7: first delivery before the window, then two inside;
    # request 8: first delivery inside (left out of tpot), one after close
    d = [(99.0, 7, 100, 0, 8), (100.5, 7, 100, 8, 8), (101.5, 7, 100, 16, 4),
         (102.0, 8, 50, 0, 8), (103.0, 8, 50, 8, 8), (111.0, 8, 50, 16, 8)]
    w, c = window(deliveries=d)
    assert harness.end_to_end(w, setup_s=12.5) == {
        "serve_tok_s": (28 / 10.0, "tokens/s"), "setup_s": (12.5, "s")}
    # per-token times: 8 x 1.5/8, 4 x 1.0/4, 8 x 1.0/8
    per = [1.5 / 8] * 8 + [0.25] * 4 + [0.125] * 8
    got = c.readers["tpot_p95_ms.serve"](w)
    assert got == (pytest.approx(np.percentile(per, 95) * 1e3), "ms")
    w, c = window(deliveries=d[:1])
    assert c.readers["tpot_p95_ms.serve"](w) is None


def readers():
    c = harness.load_cell(ROOT, "granite-3-2b.longctx")
    return c.readers


def test_readers_on_a_hand_made_window():
    r = readers()
    waves = [  # one wave half inside the window, two inside
        {"t0": 99.5, "t1": 100.5, "wall": 1.0, "steps": 8, "live": 4,
         "rows": [(1000, 8)] * 4},
        {"t0": 101.0, "t1": 102.0, "wall": 1.0, "steps": 8, "live": 8,
         "rows": [(2000, 8)] * 8},
        {"t0": 103.0, "t1": 104.0, "wall": 1.0, "steps": 4, "live": 8,
         "rows": [(3000, 4)] * 8},
    ]
    trace = {"busy_s": 7.5, "window_s": 10.0,
             "modules_s": {"jit_wave": 2.0, "jit_pf": 0.4,
                           "jit_admit": 0.1, "jit_snap": 0.3}}
    w, c = window(waves=waves, trace=trace,
                  prefills=[(99.0, 2048), (100.2, 3072)],
                  deliveries=[(101.0, 1, 3072, 0, 8)])
    occ = (0.5 * 8 * 4 + 8 * 8 + 4 * 8) / (0.5 * 8 * 8 + 8 * 8 + 4 * 8)
    assert r["occupancy.serve"](w) == (pytest.approx(100 * occ), "%")
    assert r["host_gap_share.serve"](w) == (pytest.approx(100 * 0.75), "%")
    steps = 0.5 * 8 + 8 + 4
    assert r["decode_step_ms.serve"](w) == (pytest.approx(2000 / steps),
                                            "ms")
    assert r["prefill_share.serve"](w) == (pytest.approx(5.0), "%")
    assert r["idle_share.serve"](w) == (pytest.approx(25.0), "%")
    m = c.model
    need = (0.5 * m.decode_bytes(c.spec, 8, waves[0]["rows"])
            + m.decode_bytes(c.spec, 8, waves[1]["rows"])
            + m.decode_bytes(c.spec, 4, waves[2]["rows"]))
    assert r["decode_hbm_roofline.serve"](w) == (
        pytest.approx(100 * need / (2.0 * 819e9)), "%")
    flops = m.prefill_flops(c.spec, 3072) + sum(
        m.token_flops(c.spec, 3072 + j + 1) for j in range(8))
    assert r["mfu.serve"](w) == (pytest.approx(100 * flops / (10 * 197e12)),
                                 "%")


def test_device_readers_read_nothing_without_a_trace():
    r = readers()
    w, _ = window(waves=[{"t0": 101.0, "t1": 102.0, "wall": 1.0,
                          "steps": 8, "live": 8, "rows": [(10, 8)]}])
    for name in ("decode_step_ms.serve", "prefill_share.serve",
                 "decode_hbm_roofline.serve", "idle_share.serve"):
        assert r[name](w) is None
