"""Pallas TPU kernel: Mamba2 SSD (state-space duality) chunked scan.

Dominant op of the mamba2/zamba2 architectures. The recurrence
    h_t = a_t * h_{t-1} + b_t x_t^T,   y_t = c_t^T h_t
is evaluated chunk-wise (the SSD trick, arXiv:2405.21060): within a chunk
of length C everything is dense matmuls on the MXU —

    y_intra = (tril(C B^T) * decay ratio) X          [C, C] @ [C, P]
    y_state = decay * (C h_prev)                     [C, S] @ [S, P]
    h_next  = decay_end * h_prev + (B * ratio)^T X   [S, C] @ [C, P]

Tiling: grid (B, H, n_chunks) with the chunk axis innermost/sequential;
the [S, P] state is carried across chunks in f32 VMEM scratch. This is
the TPU-native adaptation of the paper-aggregation idea: intermediate
per-timestep values are combined into per-chunk aggregates before they
ever leave the compute unit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .mode import resolve_interpret

__all__ = ["ssd_scan"]

_CHUNK = 256


def _ssd_kernel(x_ref, a_ref, b_ref, c_ref, y_ref, h_scr, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0, 0].astype(jnp.float32)          # [C, P]
    a = a_ref[...].astype(jnp.float32)           # [1, C] (log decay)
    b = b_ref[0, 0].astype(jnp.float32)          # [C, S]
    c = c_ref[0, 0].astype(jnp.float32)          # [C, S]

    # log prod_{s<=t} a_s, as a column [C, 1] and a row [1, C]: two
    # triangular matmuls, so no lane<->sublane relayout is needed
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    trif = tri.astype(jnp.float32)
    exact = dict(precision=jax.lax.Precision.HIGHEST,
                 preferred_element_type=jnp.float32)
    cum_col = jax.lax.dot_general(trif, a, (((1,), (1,)), ((), ())),
                                  **exact)        # [C, 1]
    cum_row = jax.lax.dot_general(a, trif, (((1,), (1,)), ((), ())),
                                  **exact)        # [1, C]
    cum_end = jnp.sum(a)                         # scalar: a splat, not a
    # [1, 1] slice that Mosaic cannot broadcast over both axes
    h_prev = h_scr[...]                          # [S, P]

    # inter-chunk: y_state[t] = decay[t] * c_t . h_prev
    y_state = jnp.exp(cum_col) * jax.lax.dot_general(
        c, h_prev, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)      # [C, P]

    # intra-chunk: M[t, s] = (c_t . b_s) * exp(cum[t] - cum[s]), s <= t
    ratio = jnp.exp(cum_col - cum_row)           # [C, C]
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    M = jnp.where(tri, cb * ratio, 0.0)
    y_intra = jax.lax.dot_general(M, x, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    y_ref[0, 0] = (y_state + y_intra).astype(y_ref.dtype)

    # state update: h = decay[-1] h_prev + sum_s (decay[-1]/decay[s]) b_s x_s^T
    bw = b * jnp.exp(cum_end - cum_col)          # [C, S]
    h_scr[...] = jnp.exp(cum_end) * h_prev + jax.lax.dot_general(
        bw, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray,
             c: jnp.ndarray, *, chunk: int = _CHUNK,
             interpret: bool | None = None) -> jnp.ndarray:
    """SSD scan. x: [B, T, H, P], a: [B, T, H] (log-decay),
    b, c: [B, T, H, S] -> y: [B, T, H, P]. T must divide by ``chunk``
    (padded otherwise; padding uses a = 0 -> decay 1, x = 0).
    ``interpret=None`` compiles on TPU and interprets elsewhere."""
    B, T, H, Pd = x.shape
    S = b.shape[-1]
    C = min(chunk, T)
    t_pad = -(-T // C) * C
    if t_pad != T:
        pad = ((0, 0), (0, t_pad - T), (0, 0), (0, 0))
        x = jnp.pad(x, pad)
        b = jnp.pad(b, pad)
        c = jnp.pad(c, pad)
        a = jnp.pad(a, ((0, 0), (0, t_pad - T), (0, 0)))
    # layout: [B, H, T, *] so (batch, head) are leading grid axes
    xt = jnp.moveaxis(x, 2, 1)                   # [B, H, T, P]
    bt = jnp.moveaxis(b, 2, 1)
    ct = jnp.moveaxis(c, 2, 1)
    at = jnp.moveaxis(a, 2, 1)[:, :, None]       # [B, H, 1, T]

    grid = (B, H, t_pad // C)
    y = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=C),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, C, Pd), lambda i, j, k: (i, j, k, 0)),
            pl.BlockSpec((None, None, 1, C), lambda i, j, k: (i, j, 0, k)),
            pl.BlockSpec((1, 1, C, S), lambda i, j, k: (i, j, k, 0)),
            pl.BlockSpec((1, 1, C, S), lambda i, j, k: (i, j, k, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, C, Pd), lambda i, j, k: (i, j, k, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, t_pad, Pd), x.dtype),
        scratch_shapes=[pltpu.VMEM((S, Pd), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(xt, at, bt, ct)
    return jnp.moveaxis(y, 1, 2)[:, :T]
