"""Engine layer: device time of the wave-boundary snapshot (``jit_snap``,
the jitted copy of the whole wave state, SSM state and conv windows
included, that ``DecodeEngine.run_wave`` takes before every wave) over
the traced window. Moves ``serve_tok_s``."""


def read(w):
    if w.trace is None:
        return None
    device = w.trace["modules_s"].get("jit_snap", 0.0)
    return (100.0 * device / w.trace["window_s"], "%") if device else None
