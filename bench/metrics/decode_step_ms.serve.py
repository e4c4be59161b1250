"""Model-step layer: device time of the wave executable (``jit_wave``,
the jitted ``lax.while_loop`` around ``lm.decode_step``) inside the
traced window, over the decode steps the waves ran there. Moves
``serve_tok_s``."""


def read(w):
    if w.trace is None:
        return None
    steps = sum(w.share(x["t0"], x["t1"]) * x["steps"] for x in w.waves)
    device = w.trace["modules_s"].get("jit_wave", 0.0)
    return (1e3 * device / steps, "ms") if steps and device else None
