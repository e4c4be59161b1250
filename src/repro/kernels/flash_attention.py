"""Pallas TPU kernel: blockwise flash attention (online softmax).

Dominant train/prefill FLOPs of every LM architecture in the zoo. Supports
the features the assigned archs need: causal masking, GQA (grouped KV
heads), sliding windows (mixtral/gemma2 local layers), gemma2 logit
soft-capping, and right-aligned decode (Tq << Tk against a KV cache).

Tiling: grid (B, Hq, nq, nk), nk innermost. Q/O tiles [BLOCK_Q, D] stay in
VMEM with f32 running (m, l, acc) scratch across the nk loop; K/V stream
through VMEM in [BLOCK_K, D] tiles. Fully-masked K blocks are skipped via
pl.when on the block indices (causal upper triangle / outside the window).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .mode import resolve_interpret

__all__ = ["flash_attention"]

_BLOCK_Q = 256
_BLOCK_K = 256
_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int | None,
                  softcap: float | None, block_q: int, block_k: int,
                  tq: int, tk: int):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # absolute positions (right-aligned queries for decode)
    q_pos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0) + (tk - tq)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    # block-level skip: is any (q, k) pair in this tile visible?
    q_blk_last = iq * block_q + block_q - 1 + (tk - tq)
    q_blk_first = iq * block_q + (tk - tq)
    k_blk_first = ik * block_k
    k_blk_last = ik * block_k + block_k - 1
    live = True
    if causal:
        live = k_blk_first <= q_blk_last
    if window is not None:
        live = jnp.logical_and(live, k_blk_last > q_blk_first - window)

    @pl.when(live)
    def _():
        q = q_ref[0, 0].astype(jnp.float32) * scale      # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)              # [bk, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        mask = k_pos < tk  # padded keys (positions >= tk) are never valid
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[...]                              # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                           # [bq, bk]
        v = v_ref[0, 0].astype(jnp.float32)              # [bk, D]
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _():
        l = l_scr[...]
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "softcap", "scale",
                              "block_q", "block_k", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int | None = None,
                    softcap: float | None = None,
                    scale: float | None = None,
                    block_q: int = _BLOCK_Q, block_k: int = _BLOCK_K,
                    interpret: bool | None = None) -> jnp.ndarray:
    """q: [B, Hq, Tq, D]; k, v: [B, Hkv, Tk, D] -> [B, Hq, Tq, D].

    ``interpret=None`` compiles on TPU and interprets elsewhere."""
    B, Hq, Tq, D = q.shape
    _, Hkv, Tk, _ = k.shape
    if Hq % Hkv:
        raise ValueError(f"Hq={Hq} must be a multiple of Hkv={Hkv}")
    rep = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    bq = min(block_q, Tq)
    bk = min(block_k, Tk)
    tq_pad = -(-Tq // bq) * bq
    tk_pad = -(-Tk // bk) * bk
    # pad queries on the LEFT (keep right alignment), keys on the right;
    # padded key rows are masked because padded q rows only ADD rows whose
    # outputs are dropped, and key padding is handled by the causal/window
    # mask against real positions when causal; for non-causal we mask via
    # l==0 guard + explicit key validity below.
    if tk_pad != Tk:
        # appended keys get positions >= Tk and are masked in-kernel
        k = jnp.pad(k, ((0, 0), (0, 0), (0, tk_pad - Tk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, tk_pad - Tk), (0, 0)))
    if tq_pad != Tq:
        q = jnp.pad(q, ((0, 0), (0, 0), (tq_pad - Tq, 0), (0, 0)))

    grid = (B, Hq, tq_pad // bq, tk_pad // bk)
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, causal=causal, window=window,
            softcap=softcap, block_q=bq, block_k=bk, tq=tq_pad, tk=Tk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j: (b, h // rep, j, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j: (b, h // rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, tq_pad, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
    return out[:, :, tq_pad - Tq:, :]
