"""Pallas TPU kernels: XOR packet codec for the coded shuffle.

Algorithm-2 hot loop, both directions:

* encode — a server's coded broadcast Δ is the XOR of the ``m = k-1``
  packets assigned to it (u32 bit patterns of the aggregates).
* decode — a receiver strips a round's broadcast down to its own packet
  by XOR-ing back the ``m`` cancellation packets it can recompute
  locally (the Lemma-2 storage condition); a boolean mask selects which
  ones apply.

At production scale these run once per (stage, round) over multi-MB
gradient shards, so the fold is fused into a single VMEM pass instead of
m-1 separate HLO xors over HBM. The batched variants carry one row per
coded group — the ShuffleProgram executors call them with the whole
per-round packet table at once.

Two kernel families (DESIGN.md §10):

* ``xor_fold`` / ``xor_decode`` — dense variants over pre-gathered
  packet tables. These are the CPU/GPU-oracle building blocks: the
  caller pays separate HBM passes to gather/replicate the packets
  before the fold ever runs.
* ``xor_encode_gather`` / ``xor_decode_gather`` — FUSED variants that
  read packets straight out of the flat chunk buffer via
  scalar-prefetched index tables (``PrefetchScalarGridSpec``). The
  gather happens in the BlockSpec index map, so each packet word moves
  HBM→VMEM exactly once and no ``[n, k, d]`` / ``[n·(k-1), k, pk]``
  intermediate is ever materialized. The decode variant additionally
  scatters each decoded round packet into its final chunk-slot row via
  a precomputed receive-selector table — the post-hoc
  ``argsort``/gather of the multipass path is baked into the schedule
  lowering.

* ``xor_encode_gather16`` / ``xor_decode_gather16`` — the PACKED
  low-precision lane (DESIGN.md §12): the same fused gathers running
  natively on the u16 view of a bf16/f16 chunk buffer (two lanes per
  u32 wire word). XOR commutes with the bit partition, so folding u16
  lane pairs is bit-identical to folding the packed u32 words; pack
  (encode output) and unpack (decode output) are same-width bitcasts —
  no 16-bit value ever widens to a 4-byte word in HBM.

Tiling (DESIGN.md §10): every packet of ``width`` words is viewed as
``[rows, 128]`` lane rows (a row-major reshape of the zero-padded row),
and a block is a whole number of the chip's native ``(8, 128)`` u32 /
``(16, 128)`` u16 tiles, or the whole of a short packet. The packet-row
gather rides the leading (squeezed) block dimension, which the TPU
compiler leaves unconstrained — a ``(1, BLOCK)`` row block breaks its
(8, 128) rule. Grid over (row, word-block[, source]); for the gather
kernels the source axis is innermost, so the output tile stays resident
in VMEM across the whole fold (one write-back per (row, block)).
Masked-off sources are selected to the XOR identity in VMEM by a
scalar-prefetched 0/1 flag, never branched on.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .mode import resolve_interpret

__all__ = ["xor_encode", "xor_fold", "xor_decode",
           "xor_encode_gather", "xor_decode_gather",
           "xor_encode_gather16", "xor_decode_gather16"]

_BLOCK = 1024  # words per block: one (8, 128) u32 tile
_LANE = 128


def _tile(width: int, block: int, dtype) -> tuple[int, int]:
    """``(rows per block, padded rows)`` of a ``width``-word packet laid
    out as ``[rows, 128]``: a block is a whole number of native sublane
    tiles (8 rows of u32, 16 of u16) or, for a short packet, all of it."""
    sub = 32 // jnp.dtype(dtype).itemsize
    rows = -(-width // _LANE)
    br = max(sub, block // _LANE // sub * sub)
    if rows <= br:
        return rows, rows
    return br, -(-rows // br) * br


def _rows(x: jnp.ndarray, rows: int) -> jnp.ndarray:
    """``[..., width]`` -> zero-padded lane-row view ``[..., rows, 128]``
    (XOR identity is 0, so padding never leaks into real words)."""
    pad = rows * _LANE - x.shape[-1]
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x.reshape(*x.shape[:-1], rows, _LANE)


def _unrows(y: jnp.ndarray, width: int) -> jnp.ndarray:
    """Inverse of :func:`_rows`: ``[..., rows, 128]`` -> ``[..., width]``."""
    return y.reshape(*y.shape[:-2], -1)[..., :width]


def _masked(tile, flag):
    """``tile`` where ``flag`` else the XOR identity — a select, no branch."""
    return jnp.where(flag != 0, tile, jnp.zeros_like(tile))


def _xor_kernel(p_ref, o_ref, *, m: int):
    acc = p_ref[0]
    for i in range(1, m):  # m = k-1 is small and static: unrolled VPU xors
        acc = acc ^ p_ref[i]
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def xor_encode(packets: jnp.ndarray, *, block: int = _BLOCK,
               interpret: bool | None = None) -> jnp.ndarray:
    """XOR-fold ``packets: u32[m, n]`` over axis 0 -> ``u32[n]``.

    ``n`` is padded to a multiple of ``block`` (XOR identity is 0, so
    padding never leaks into real words).
    """
    if packets.dtype != jnp.uint32:
        raise TypeError("xor_encode expects uint32")
    interpret = resolve_interpret(interpret)
    m, n = packets.shape
    n_pad = -(-n // block) * block
    x = jnp.pad(packets, ((0, 0), (0, n_pad - n)))
    out = pl.pallas_call(
        functools.partial(_xor_kernel, m=m),
        grid=(n_pad // block,),
        in_specs=[pl.BlockSpec((m, block), lambda i: (0, i))],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), jnp.uint32),
        interpret=interpret,
    )(x)
    return out[:n]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def xor_fold(packets: jnp.ndarray, *, block: int = _BLOCK,
             interpret: bool | None = None) -> jnp.ndarray:
    """Batched encode: ``u32[R, m, n]`` -> ``u32[R, n]`` (fold axis 1).

    Row ``r`` is one coded group's packet set; the grid runs one program
    per (row, word-block) so every fold is a single VMEM pass.
    """
    if packets.dtype != jnp.uint32:
        raise TypeError("xor_fold expects uint32")
    interpret = resolve_interpret(interpret)
    R, m, n = packets.shape
    br, rows = _tile(n, block, jnp.uint32)
    out = pl.pallas_call(
        functools.partial(_xor_kernel, m=m),
        grid=(R, rows // br),
        in_specs=[pl.BlockSpec((None, m, br, _LANE),
                               lambda i, j: (i, 0, j, 0))],
        out_specs=pl.BlockSpec((None, br, _LANE), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((R, rows, _LANE), jnp.uint32),
        interpret=interpret,
    )(_rows(packets, rows))
    return _unrows(out, n)


def _decode_kernel(msk_ref, r_ref, p_ref, o_ref, *, m: int):
    i = pl.program_id(0)
    acc = r_ref[...]
    for t in range(m):
        acc = acc ^ _masked(p_ref[t], msk_ref[i, t])
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def xor_decode(recv: jnp.ndarray, packets: jnp.ndarray,
               mask: jnp.ndarray, *, block: int = _BLOCK,
               interpret: bool | None = None) -> jnp.ndarray:
    """Batched decode: ``recv ^ XOR_i(packets[:, i] where mask[:, i])``.

    ``recv: u32[R, n]`` round broadcasts, ``packets: u32[R, m, n]``
    locally recomputed cancellation packets, ``mask: bool[R, m]``
    selects the ones that participate. Returns ``u32[R, n]`` — the
    receiver's own packet per row (Lemma 2 decode).
    """
    if recv.dtype != jnp.uint32 or packets.dtype != jnp.uint32:
        raise TypeError("xor_decode expects uint32")
    interpret = resolve_interpret(interpret)
    R, m, n = packets.shape
    if recv.shape != (R, n):
        raise ValueError(f"recv shape {recv.shape} != {(R, n)}")
    if mask.shape != (R, m):
        raise ValueError(f"mask shape {mask.shape} != {(R, m)}")
    br, rows = _tile(n, block, jnp.uint32)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, m=m),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(R, rows // br),
            in_specs=[
                pl.BlockSpec((None, br, _LANE), lambda i, j, _: (i, j, 0)),
                pl.BlockSpec((None, m, br, _LANE),
                             lambda i, j, _: (i, 0, j, 0)),
            ],
            out_specs=pl.BlockSpec((None, br, _LANE),
                                   lambda i, j, _: (i, j, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((R, rows, _LANE), jnp.uint32),
        interpret=interpret,
    )(mask.astype(jnp.int32), _rows(recv, rows), _rows(packets, rows))
    return _unrows(out, n)


# --------------------------------------------------------------------- #
# fused gather-XOR codec (single-pass encode/decode, DESIGN.md §10)
# --------------------------------------------------------------------- #
def _encode_gather_kernel(idx_ref, msk_ref, chunk_ref, o_ref):
    i = pl.program_id(0)
    j = pl.program_id(2)
    term = _masked(chunk_ref[...], msk_ref[i, j])

    @pl.when(j == 0)
    def _init():
        o_ref[...] = term

    @pl.when(j > 0)
    def _fold():
        o_ref[...] ^= term


def _encode_gather(chunks, idx, mask, *, block, interpret):
    """Shared body of the u32 and u16 fused encodes (dtype-generic)."""
    n, m = idx.shape
    if mask.shape != (n, m):
        raise ValueError(f"mask shape {mask.shape} != {(n, m)}")
    width = chunks.shape[1]
    br, rows = _tile(width, block, chunks.dtype)
    out = pl.pallas_call(
        _encode_gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n, rows // br, m),
            in_specs=[
                pl.BlockSpec((None, br, _LANE), lambda i, b, j, idx_r, _:
                             (idx_r[i, j], b, 0)),
            ],
            out_specs=pl.BlockSpec((None, br, _LANE),
                                   lambda i, b, j, *_: (i, b, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n, rows, _LANE), chunks.dtype),
        interpret=resolve_interpret(interpret),
    )(idx.astype(jnp.int32), mask.astype(jnp.int32), _rows(chunks, rows))
    return _unrows(out, width)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def xor_encode_gather(chunks: jnp.ndarray, idx: jnp.ndarray,
                      mask: jnp.ndarray, *, block: int = _BLOCK,
                      interpret: bool | None = None) -> jnp.ndarray:
    """Fused gather + XOR-fold encode:
    ``out[i] = XOR_j { chunks[idx[i, j]] : mask[i, j] }``.

    ``chunks: u32[P, pk]`` is the flat packet view of the local chunk
    buffer (``u32.reshape(-1, pk)``); ``idx: i32[n, m]`` holds
    flat packet-row sources (``enc_src`` of the schedule lowering) and
    ``mask: bool[n, m]`` their validity. Invalid entries must carry an
    in-range index (the lowering bakes 0) — they are selected to the
    XOR identity inside VMEM, never branched on.

    The gather IS the block index map (scalar-prefetched tables), so
    encode reads each needed chunk word from HBM exactly once and
    writes Δ once: one pass, vs gather → reshape → take_along_axis →
    fold (3 HBM round trips) in the multipass path.
    """
    if chunks.dtype != jnp.uint32:
        raise TypeError("xor_encode_gather expects uint32")
    return _encode_gather(chunks, idx, mask, block=block,
                          interpret=interpret)


def _decode_gather_kernel(rsel_ref, idx_ref, msk_ref, recv_ref, chunk_ref,
                          o_ref):
    i = pl.program_id(0)
    j = pl.program_id(2)
    term = _masked(chunk_ref[...], msk_ref[i, j])

    @pl.when(j == 0)
    def _init():
        o_ref[...] = recv_ref[...] ^ term

    @pl.when(j > 0)
    def _fold():
        o_ref[...] ^= term


def _decode_gather(recv, chunks, rsel, idx, mask, *, block, interpret):
    """Shared body of the u32 and u16 fused decodes (dtype-generic)."""
    R, m = idx.shape
    width = chunks.shape[1]
    if recv.shape[1] != width:
        raise ValueError(f"recv width {recv.shape[1]} != chunks width "
                         f"{width}")
    if rsel.shape != (R,):
        raise ValueError(f"rsel shape {rsel.shape} != {(R,)}")
    if mask.shape != (R, m):
        raise ValueError(f"mask shape {mask.shape} != {(R, m)}")
    br, rows = _tile(width, block, chunks.dtype)
    out = pl.pallas_call(
        _decode_gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(R, rows // br, m),
            in_specs=[
                pl.BlockSpec((None, br, _LANE), lambda i, b, j, rsel_r, *_:
                             (rsel_r[i], b, 0)),
                pl.BlockSpec((None, br, _LANE),
                             lambda i, b, j, rsel_r, idx_r, _:
                             (idx_r[i, j], b, 0)),
            ],
            out_specs=pl.BlockSpec((None, br, _LANE),
                                   lambda i, b, j, *_: (i, b, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((R, rows, _LANE), chunks.dtype),
        interpret=resolve_interpret(interpret),
    )(rsel.astype(jnp.int32), idx.astype(jnp.int32), mask.astype(jnp.int32),
      _rows(recv, rows), _rows(chunks, rows))
    return _unrows(out, width)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def xor_decode_gather(recv: jnp.ndarray, chunks: jnp.ndarray,
                      rsel: jnp.ndarray, idx: jnp.ndarray,
                      mask: jnp.ndarray, *, block: int = _BLOCK,
                      interpret: bool | None = None) -> jnp.ndarray:
    """Fused gather + XOR decode + chunk-slot scatter:
    ``out[i] = recv[rsel[i]] ^ XOR_j { chunks[idx[i, j]] : mask[i, j] }``.

    Output row ``i`` is a CHUNK SLOT (row-major ``(group, slot)``), not
    a broadcast round: ``rsel: i32[R]`` (``dec_recv`` of the schedule
    lowering) selects which received round packet lands in each slot —
    the lowering bakes ``argsort(dec_gather)`` into it, so the
    multipass path's per-trace argsort + post-hoc ``take_along_axis``
    disappear. ``idx/mask: [R, m]`` name the cancellation packets as
    flat rows of ``chunks: u32[P, pk]`` (the same flat chunk buffer the
    encode reads — the ``[n, k, k-1, pk]`` packet table and the
    ``(k-1)×``-replicated ``[n, k-1, k, k-1, pk]`` cancellation buffer
    of the multipass path are never built).

    Single pass: every cancellation word moves HBM→VMEM once via the
    scalar-prefetched index maps, each output row is written once, in
    final chunk order.
    """
    if recv.dtype != jnp.uint32 or chunks.dtype != jnp.uint32:
        raise TypeError("xor_decode_gather expects uint32")
    return _decode_gather(recv, chunks, rsel, idx, mask, block=block,
                          interpret=interpret)


# --------------------------------------------------------------------- #
# packed 16-bit lane (pack/unpack-fused gather kernels, DESIGN.md §12)
#
# bf16/f16 payloads ride the codec as PAIRS of 16-bit lanes per u32
# wire word. XOR commutes with any bit partition, so the fold can run
# natively on the u16 view of the half-precision chunk buffer — the
# "pack" into wire words is a same-width bitcast of the kernel output,
# never a widening: no value ever occupies 4 bytes in HBM on this lane
# (the unpacked-u32 transient a cast-to-f32 shuffle would pay).
# Tables are the SAME d-independent packet-row indices as the u32
# kernels; only the lane count per packet doubles, and a block holds
# (16, 128) u16 tiles — the same bytes as the u32 lane's (8, 128).
# --------------------------------------------------------------------- #
@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def xor_encode_gather16(chunks: jnp.ndarray, idx: jnp.ndarray,
                        mask: jnp.ndarray, *, block: int = _BLOCK,
                        interpret: bool | None = None) -> jnp.ndarray:
    """Packed-lane fused encode: :func:`xor_encode_gather` over a u16
    chunk buffer ``u16[P, 2*pk]`` (the bitcast view of the padded
    bf16/f16 contributions — see ``collective._wire_buffer``).

    Returns ``u16[n, 2*pk]``; the caller bitcasts lane pairs to the
    ``u32[n, pk]`` wire Δ (a same-width reinterpretation — the pack is
    fused in the sense that no widened per-value word is ever
    materialized).
    """
    if chunks.dtype != jnp.uint16:
        raise TypeError("xor_encode_gather16 expects uint16")
    if chunks.shape[1] % 2:
        raise ValueError(f"packed packet lane count must be even, got "
                         f"{chunks.shape[1]}")
    return _encode_gather(chunks, idx, mask, block=block,
                          interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def xor_decode_gather16(recv: jnp.ndarray, chunks: jnp.ndarray,
                        rsel: jnp.ndarray, idx: jnp.ndarray,
                        mask: jnp.ndarray, *, block: int = _BLOCK,
                        interpret: bool | None = None) -> jnp.ndarray:
    """Packed-lane fused decode: :func:`xor_decode_gather` with the
    received wire words viewed as u16 lane pairs (``recv: u16[R,
    2*pk]``) and cancellation packets read straight from the u16 chunk
    buffer. Output rows are chunk slots in 16-bit lanes — the caller's
    unpack is a slice + same-width bitcast, so the decoded payload
    never round-trips through a widened word buffer.
    """
    if recv.dtype != jnp.uint16 or chunks.dtype != jnp.uint16:
        raise TypeError("xor_decode_gather16 expects uint16")
    return _decode_gather(recv, chunks, rsel, idx, mask, block=block,
                          interpret=interpret)
