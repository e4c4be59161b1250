"""Model-step layer, the whole decode step against the HBM peak: the
bytes the algorithm needs for the window's decode steps (every weight
once a step, plus each live row's valid keys and values, or its f32
state read and written, once a token; from the configuration's shapes,
``decode_bytes`` of the cell's model file) over the wave executable's
device time times the peak bytes/s. Moves ``serve_tok_s``."""


def read(w):
    if w.trace is None:
        return None
    device = w.trace["modules_s"].get("jit_wave", 0.0)
    need = sum(w.share(x["t0"], x["t1"])
               * w.model.decode_bytes(w.spec, x["steps"], x["rows"])
               for x in w.waves)
    if not device or not need:
        return None
    return 100.0 * need / (device * w.peaks["hbm_bytes_per_s"]), "%"
