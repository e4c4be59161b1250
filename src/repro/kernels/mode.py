"""Where a Pallas kernel runs: compiled by Mosaic on a TPU, interpreted
everywhere else (CPU/GPU have no Mosaic lowering for these kernels)."""

from __future__ import annotations

import jax

__all__ = ["resolve_interpret"]


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> compiled on a TPU backend, the interpreter off it; an
    explicit ``True``/``False`` is honoured as given."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
