"""Model-step layer: device time of the prefill and admission
executables (``jit_pf``: ``lm.prefill``; ``jit_admit``:
``lm.admit_prefill``) over the traced window. Moves ``serve_tok_s``
(and ``tpot_p95_ms.serve``: a wave waits behind each prefill)."""


def read(w):
    if w.trace is None:
        return None
    m = w.trace["modules_s"]
    device = m.get("jit_pf", 0.0) + m.get("jit_admit", 0.0)
    return (100.0 * device / w.trace["window_s"], "%") if device else None
