"""Engine layer: device time of admission (``jit_admit``,
``lm.admit_prefill`` inside ``DecodeEngine.admit``: a prompt's K/V pages
written into the pool and its SSM state and conv window rows into the
slot, in one executable) over the traced window. Reads nothing where the
trace holds no admission. Moves ``serve_tok_s``."""


def read(w):
    if w.trace is None:
        return None
    device = w.trace["modules_s"].get("jit_admit", 0.0)
    return (100.0 * device / w.trace["window_s"], "%") if device else None
