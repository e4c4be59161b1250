"""Engine layer: 95th percentile, over every token delivered in the
window, of its streamed time. A delivery is one wave's commit; its m
tokens each get the gap since the same request's previous delivery over
m; a request's first delivery is left out. A per-layer metric because
the cell is saturated: whether about one wave in twenty waits behind a
prefill puts the percentile in one mode or the other. Moves
``serve_tok_s``."""

import numpy as np


def read(w):
    per_token, last = [], {}
    for t, handle, _, _, m in w.deliveries:
        if w.inside(t) and handle in last:
            per_token += [(t - last[handle]) / m] * m
        last[handle] = t
    if not per_token:
        return None
    return float(np.percentile(per_token, 95)) * 1e3, "ms"
