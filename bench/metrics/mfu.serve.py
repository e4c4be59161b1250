"""The whole step against the bf16 peak: the operations that the
window's prefills (dispatched inside it) and delivered tokens require,
from the configuration's shapes (``prefill_flops``, ``token_flops`` of
the cell's model file), over window x peak FLOP/s. Moves
``serve_tok_s``."""


def read(w):
    spec, model = w.spec, w.model
    flops = sum(model.prefill_flops(spec, T)
                for t, T in w.prefills if w.inside(t))
    for t, _, T, before, m in w.deliveries:
        if w.inside(t):
            flops += sum(model.token_flops(spec, T + before + j + 1)
                         for j in range(m))
    return 100.0 * flops / (w.seconds * w.peaks["bf16_flops_per_s"]), "%"
