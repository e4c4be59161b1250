"""Jit'd dispatch wrappers: Pallas kernel on TPU, pure-jnp path elsewhere.

Models call these entry points; the ``use_pallas`` switch lives in the
arch config (``ModelConfig.use_pallas``). On the CPU host (dry-run, smoke
tests) the jnp path lowers to plain XLA HLO — same math, honest
cost_analysis. On TPU the Pallas kernels take over, compiled; off it they
run in the Pallas interpreter (``kernels.mode.resolve_interpret``).
Interpret-mode execution of the kernels is exercised by tests/test_kernels
against the ref oracles.
"""

from __future__ import annotations

import jax.numpy as jnp

from . import ref
from .aggregate import aggregate as aggregate_pallas
from .flash_attention import flash_attention as flash_attention_pallas
from .ssd_scan import ssd_scan as ssd_scan_pallas
from .xor_code import xor_encode as xor_encode_pallas

__all__ = ["attention", "ssd", "combine_aggregates", "xor_fold"]


_CHUNK_THRESHOLD = 2 ** 21  # Tq*Tk above which the XLA path chunks


def attention(q, k, v, *, causal=True, window=None, softcap=None,
              scale=None, valid_len=None, use_pallas=False,
              block_q=1024, block_k=1024, unroll=False):
    """Unified attention entry (see flash_attention / ref docstrings).

    Routing: Pallas kernel on TPU (or interpret in kernel tests); on the
    XLA lane, long sequences use the chunked flash (block-skipping)
    implementation, short ones the materialized oracle. ``valid_len``
    (partial-cache decode, Tq ~ 1) uses the materialized path — its
    score matrix is only [B, H, Tq, Tk].
    """
    if use_pallas and valid_len is None:
        return flash_attention_pallas(
            q, k, v, causal=causal, window=window, softcap=softcap,
            scale=scale)
    Tq, Tk = q.shape[2], k.shape[2]
    if valid_len is None and Tq * Tk > _CHUNK_THRESHOLD:
        return ref.flash_attention_chunked(
            q, k, v, causal=causal, window=window, softcap=softcap,
            scale=scale, block_q=block_q, block_k=block_k, unroll=unroll)
    return ref.flash_attention_ref(
        q, k, v, causal=causal, window=window, softcap=softcap, scale=scale,
        valid_len=valid_len)


def ssd(x, a, b, c, *, use_pallas=False, chunk=256, unroll=False):
    """Unified Mamba2 SSD entry (chunked matmul form on the XLA lane).

    ``b``/``c`` may be group-shared [B, T, S] (preferred — smaller
    activations) or per-head [B, T, H, S] (broadcast for the Pallas
    kernel / oracle)."""
    if use_pallas:
        if b.ndim == 3:
            H = x.shape[2]
            b = jnp.broadcast_to(b[:, :, None], (*b.shape[:2], H,
                                                 b.shape[-1]))
            c = jnp.broadcast_to(c[:, :, None], (*c.shape[:2], H,
                                                 c.shape[-1]))
        return ssd_scan_pallas(x, a, b, c, chunk=chunk)
    if b.ndim == 4:  # per-head inputs: fall back to the oracle
        return ref.ssd_scan_ref(x, a, b, c)
    return ref.ssd_chunked(x, a, b, c, chunk=chunk, unroll=unroll)


def combine_aggregates(values, segment_ids, num_segments, *,
                       use_pallas=False):
    """α-combiner used by the CAMR map phase."""
    if use_pallas:
        return aggregate_pallas(values, segment_ids, num_segments)
    return ref.aggregate_ref(values, segment_ids, num_segments)


def xor_fold(packets, *, use_pallas=False):
    """Algorithm-2 Δ encoder."""
    if use_pallas:
        return xor_encode_pallas(packets)
    return ref.xor_encode_ref(packets)
