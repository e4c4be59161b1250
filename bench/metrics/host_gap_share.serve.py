"""Engine layer: share of the window spent outside device waves, that is
1 - (sum of ``ServeReport.wave_stats`` wall times inside the window) /
window. The rest is commit, admission and waits on prefill. Moves
``serve_tok_s`` (and the streamed time per token, ``tpot_p95_ms.serve``)."""


def read(w):
    waves = sum(w.overlap(x["t1"] - x["wall"], x["t1"]) for x in w.waves)
    return 100.0 * (1.0 - waves / w.seconds), "%"
