"""Engine layer: the statistic of ``tpot_p95_ms.serve`` read from the
program's own record instead of the harness's ``commit_wave`` wrapper.
``ServeResult.deliveries`` holds, after the admission ``(t, 0)``, one
``(t, m)`` per commit that gave the request ``m`` tokens; each of those
tokens gets the gap since the request's previous delivery over ``m``,
for deliveries inside the window; a request's first delivery is left
out. Reads nothing where the program keeps no deliveries. Moves
``serve_tok_s``."""

import numpy as np


def read(w):
    per_token = []
    for r in w.results:
        d = getattr(r, "deliveries", None) or []
        for (t0, _), (t, m) in zip(d[1:], d[2:]):
            if w.inside(t):
                per_token += [(t - t0) / m] * m
    if not per_token:
        return None
    return float(np.percentile(per_token, 95)) * 1e3, "ms"
