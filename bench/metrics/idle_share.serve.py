"""Device layer: 1 - (union of the intervals in which an operation ran
on the device) / traced window, averaged over the chips. Moves
``serve_tok_s``."""


def read(w):
    if w.trace is None:
        return None
    return 100.0 * (1.0 - w.trace["busy_s"] / w.trace["window_s"]), "%"
