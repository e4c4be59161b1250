"""Engine layer: share of batch slots that held a live request over the
decode steps of the window (``ServeReport.wave_stats``: steps and live
slots of each wave, a wave that straddles an edge counted by the share
of it inside). Moves ``serve_tok_s``."""


def read(w):
    used = cap = 0.0
    for x in w.waves:
        f = w.share(x["t0"], x["t1"])
        used += f * x["steps"] * x["live"]
        cap += f * x["steps"] * w.slots
    return (100.0 * used / cap, "%") if cap else None
