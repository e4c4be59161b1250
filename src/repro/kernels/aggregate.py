"""Pallas TPU kernel: the α-combiner (batched segment-sum of map outputs).

The Map phase ends by aggregating intermediate values that share a
(function, batch) key — the paper's "compression" step that all three
shuffle stages rely on. On TPU we express the segment-sum as a sequence of
one-hot matmuls so the MXU does the reduction:

    out[S, d] += onehot(ids_block) @ values_block     [S, bn] @ [bn, bd]

Tiling: grid (d-blocks, n-blocks) with the n axis innermost so the output
tile stays resident in VMEM and accumulates across n-blocks. The segment
ids are one ``[1, n]`` lane row, so ``block_n`` is a multiple of 128 (or
all of ``n``) when compiled for the chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .mode import resolve_interpret

__all__ = ["aggregate"]

_BLOCK_N = 256
_BLOCK_D = 512


def _agg_kernel(v_ref, ids_ref, o_ref, *, num_segments: int,
                block_n: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    ids = ids_ref[...]                               # [1, block_n]
    onehot = (jax.lax.broadcasted_iota(
        jnp.int32, (num_segments, block_n), 0) == ids).astype(
            jnp.bfloat16)                            # [S, block_n], exact
    # the MXU multiplies bf16: split each f32 value into three parts of
    # 8 significand bits each and sum their one-hot products hi-first,
    # so a one-row segment comes back bit-exact. A part is cut by
    # masking the low 16 bits (truncation), not by a rounding
    # conversion: every part is then exactly a bf16 value with the sign
    # of the value and bits disjoint from the other parts, so each
    # residual and each partial sum is exact in f32.
    rest = v_ref[...].astype(jnp.float32)            # [block_n, block_d]
    acc = None
    for _ in range(3):
        part = jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(rest, jnp.uint32)
            & jnp.uint32(0xFFFF0000), jnp.float32)
        rest = rest - part
        prod = jnp.dot(onehot, part.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
        acc = prod if acc is None else acc + prod
    o_ref[...] += acc


@functools.partial(jax.jit,
                   static_argnames=("num_segments", "block_n", "block_d",
                                    "interpret"))
def aggregate(values: jnp.ndarray, segment_ids: jnp.ndarray,
              num_segments: int, *, block_n: int = _BLOCK_N,
              block_d: int = _BLOCK_D, interpret: bool | None = None
              ) -> jnp.ndarray:
    """Segment-sum ``values: [n, d]`` by ``segment_ids: [n] -> [S, d]``.

    Out-of-range ids (used for padding) contribute nothing.
    ``interpret=None`` compiles the kernel on TPU backends and runs it
    in Pallas interpret mode elsewhere (same policy as every kernel
    here). When every segment holds exactly one row (the trainer's
    gamma=1 map lane) the one-hot matmul is an exact gather — the three
    bf16 parts of a value sum back to it, and adding 0-products cannot
    perturb finite f32 values — so the combiner is bit-transparent
    there, for every value of magnitude at least 2^-102 (below that
    the last part is subnormal, and the chip flushes it to zero). Like
    any sum, and like ``ref.aggregate_ref``, it returns -0.0 as +0.0.
    """
    n, d = values.shape
    n_pad = -(-n // block_n) * block_n
    d_pad = -(-d // block_d) * block_d
    v = jnp.pad(values, ((0, n_pad - n), (0, d_pad - d)))
    # ids ride as one lane-major row: a 1-D block clashes with the
    # layout XLA gives 1-D arrays on TPU (T(1024) vs the block's tile)
    ids = jnp.pad(segment_ids.astype(jnp.int32), (0, n_pad - n),
                  constant_values=-1)[None]  # -1 never matches the iota
    out = pl.pallas_call(
        functools.partial(_agg_kernel, num_segments=num_segments,
                          block_n=block_n),
        grid=(d_pad // block_d, n_pad // block_n),
        in_specs=[
            pl.BlockSpec((block_n, block_d), lambda i, j: (j, i)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((num_segments, block_d),
                               lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((num_segments, d_pad), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(v, ids)
    return out[:, :d].astype(values.dtype)
