"""TPU-native CAMR coded shuffle on a JAX mesh axis (shard_map executor
of the compiled :class:`~repro.core.schedule.ShuffleProgram`).

This is the production counterpart of :mod:`repro.core.engine`: the same
3-stage schedule — the same IR tables — expressed as SPMD collectives on
a device axis of size ``K = k*q``. See DESIGN.md §3/§4 for the
multicast -> collective mapping and the bus-vs-p2p accounting.

Semantics
---------
``J = q**(k-1)`` *jobs* (simultaneously-trained model replicas, or
gradient-accumulation groups). Each job's gradient is split into ``K``
function shards of width ``d``; device ``s`` reduces shard ``s`` of every
job (Q = K). The placement assigns device ``s`` the map work of ``k-1``
batches for each of its ``q**(k-2)`` owned jobs; its input here is the
*per-batch gradient aggregates* it computed locally:

    contribs : f32[J_own, k-1, K, d]
        contribs[a, b] = gradient of batch ``stored_batches[s, a, b]`` of
        job ``owned_jobs[s, a]``, split into K shards of width d.

Output per device: ``out : [J, d]`` — the fully-aggregated shard ``s`` of
every job (reduce-scatter semantics, the paper's Reduce phase).

Routers
-------
Each of the ``k-1`` broadcast rounds of stages 1 and 2 runs as ONE
grouped collective over every group at once — ``2*(k-1)`` batched
collectives total, independent of ``J``:

* ``router="all_to_all"`` — one ``lax.all_to_all`` per round (a single
  ppermute cannot carry a round: each device must reach ``q`` peers,
  see DESIGN.md §4).
* ``router="ppermute"`` — ``q`` value-shift sub-permutations per round
  (``2*(k-1)*q`` ppermutes, every byte on the wire useful).

Multi-wave streaming (DESIGN.md §9) is :class:`ShuffleStream`: async,
double-buffered dispatch of this executor with same-shaped waves
stacked along ``d`` into a single program execution.

XOR encode/decode default to the FUSED single-pass gather-XOR codec
(``codec="fused"``, DESIGN.md §10): packet words are read straight out
of the flat chunk buffer through the schedule's precomputed index
tables — via the scalar-prefetch Pallas kernels of
:mod:`repro.kernels.xor_code` when ``use_kernels`` is true (default: on
TPU backends), via one jnp gather otherwise. ``codec="multipass"``
keeps the original gather → take_along_axis → fold pipeline as the
CPU/GPU oracle (bit-identical, tests/test_codec_fused.py).

Payload dtypes take one of two wire lanes (DESIGN.md §12): 4-byte
dtypes (f32/u32) bitcast one value per u32 word; 16-bit floats
(bf16/f16) PACK two values per word, halving bytes-on-wire for the
coded stages and shipping stage-3 unicasts at native width — the
shuffle itself stays a lossless bit transport on either lane.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .designs import ResolvableDesign
from .placement import Placement
from .schedule import (EXEC_CACHE, SCHEDULE_CACHE, HostTables,
                       ShuffleProgram, StageTables, Topology,
                       _normalize_topology, payload_words)

__all__ = ["CAMRPlan", "make_plan", "camr_shuffle", "scatter_contributions",
           "camr_shuffle_reference", "uncoded_reduce_scatter",
           "camr_collective_bytes", "camr_edge_bytes",
           "expected_collective_calls",
           "ShuffleStream", "CODEC_DTYPES", "PACKED_DTYPES",
           "check_codec_dtype"]


# --------------------------------------------------------------------- #
# plan — a thin handle on the compiled program
# --------------------------------------------------------------------- #
@dataclass(frozen=True, eq=False)
class CAMRPlan:
    q: int
    k: int
    d: int                       # function-shard width (elements)
    program: ShuffleProgram = field(repr=False)

    @property
    def design(self) -> ResolvableDesign:
        return self.program.design

    @property
    def placement(self) -> Placement:
        return self.program.placement

    @property
    def owned_jobs(self) -> np.ndarray:
        return self.program.owned_jobs

    @property
    def stored_batches(self) -> np.ndarray:
        return self.program.stored_batches

    @property
    def s3_perms(self) -> tuple:
        return self.program.s3_perms

    @property
    def s2_groups(self) -> tuple:
        """Stage-2 groups as member tuples (rank order)."""
        return tuple(self.program.group_members(int(r))
                     for r in self.program.s2_rows)

    @property
    def K(self) -> int:
        return self.q * self.k

    @property
    def J(self) -> int:
        return self.q ** (self.k - 1)

    @property
    def J_own(self) -> int:
        return self.q ** (self.k - 2)

    @property
    def packet_len(self) -> int:
        return self.d // (self.k - 1)

    @property
    def topology(self) -> Topology | None:
        """The topology the program was lowered for (None == flat)."""
        return self.program.topology


def make_plan(q: int, k: int, d: int,
              topology: Topology | None = None, *,
              gateway_avoid=frozenset()) -> CAMRPlan:
    """Lower the full SPMD schedule for a (q, k) CAMR cluster.

    Served from the structural :data:`~repro.core.schedule.SCHEDULE_CACHE`
    — all shard widths of one (q, k) share the same base lowering.

    ``topology=None`` (or flat) lowers the exact schedules every prior
    PR lowered; a two-level :class:`Topology` additionally lowers the
    host-aware relay overlay (DESIGN.md §16) that the executor uses to
    deduplicate inter-host packet copies (an :class:`AutoTopology`
    marker resolves via the cost model first). ``gateway_avoid``
    re-homes phase-A gateways away from the named devices (straggler
    failover, DESIGN.md §17). Outputs are bitwise identical for every
    topology and gateway assignment.
    """
    if k < 3:
        # k = 2 degenerates (single-packet chunks, blocks of size 1);
        # supported by the engine but not worth a coded TPU path.
        raise ValueError("TPU collective path requires k >= 3")
    if d % (k - 1):
        raise ValueError(f"shard width d={d} must be divisible by k-1={k - 1}")
    program = SCHEDULE_CACHE.program(q, k, Q=q * k, d=d,
                                     topology=topology,
                                     gateway_avoid=gateway_avoid)
    return CAMRPlan(q=q, k=k, d=d, program=program)


# --------------------------------------------------------------------- #
# bit helpers
# --------------------------------------------------------------------- #
#: payload dtypes the XOR codec can move, and (implicitly) the wire
#: lane each takes: 4-byte dtypes bitcast one value per u32 word;
#: :data:`PACKED_DTYPES` pack two 16-bit values per word at half the
#: bytes-on-wire (DESIGN.md §12). This tuple is the single source of
#: truth for codec dtype support — the JobStream entry guard
#: (:mod:`repro.runtime.jobstream`) consumes it rather than keeping a
#: second hand-rolled list.
CODEC_DTYPES = ("float32", "uint32", "bfloat16", "float16")

#: the 16-bit members of :data:`CODEC_DTYPES` — the packed wire lane.
PACKED_DTYPES = ("bfloat16", "float16")


def check_codec_dtype(dtype, where: str) -> None:
    """Entry guard: fail fast, with a fix, instead of a bare TypeError
    from ``_wire_buffer`` deep inside the shard_map trace."""
    if jnp.dtype(dtype).name not in CODEC_DTYPES:
        raise TypeError(
            f"{where}: the CAMR XOR codec moves 32-bit wire words; "
            f"supported payload dtypes are {', '.join(CODEC_DTYPES)} "
            "(bf16/f16 ride the packed 16-bit lane, two values per "
            f"word — DESIGN.md §12), got {jnp.dtype(dtype).name}. Cast "
            "the contributions to a supported dtype first (e.g. "
            "contribs.astype(jnp.float32)).")


def _to_u32(x):
    if x.dtype == jnp.float32:
        return lax.bitcast_convert_type(x, jnp.uint32)
    if x.dtype == jnp.uint32:
        return x
    raise TypeError(f"XOR word lane expects f32/u32, got {x.dtype}")


def _from_u32(x, dtype):
    return lax.bitcast_convert_type(x, dtype) if dtype != jnp.uint32 else x


def _u16_pairs_to_u32(x):
    """u16 ``[..., 2*m]`` lane pairs -> u32 ``[..., m]`` wire words
    (little-endian: lane ``2i`` is the low half of word ``i`` — the
    byte order of :func:`repro.core.schedule.pack_payload`)."""
    return lax.bitcast_convert_type(
        x.reshape(*x.shape[:-1], x.shape[-1] // 2, 2), jnp.uint32)


def _wire_buffer(x, *, wp: int, codec: str, use_kernels: bool):
    """Contributions -> the codec's chunk buffer (DESIGN.md §12).

    32-bit dtypes bitcast straight to u32. 16-bit dtypes are viewed as
    u16 lanes, zero-padded per shard from ``d`` to ``2*wp`` lanes (the
    deterministic trailing-lane pad rule), and either packed to u32
    words (jnp / multipass lanes) or handed to the Pallas gather
    kernels as the u16 view itself — the kernels fold lane pairs
    natively, so the pack is a same-width bitcast of their half-width
    output and no value ever widens to 4 bytes in HBM.
    """
    if jnp.dtype(x.dtype).itemsize != 2:
        return _to_u32(x)
    u16 = lax.bitcast_convert_type(x, jnp.uint16)
    pad = 2 * wp - x.shape[-1]
    if pad:
        u16 = jnp.pad(u16, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    if codec == "fused" and use_kernels:
        return u16
    return _u16_pairs_to_u32(u16)


def _from_wire(words, dtype, d: int):
    """Decoded u32 wire words ``[n, wp]`` -> payload values ``[n, d]``
    (inverse of :func:`_wire_buffer`'s per-shard packing)."""
    if jnp.dtype(dtype).itemsize == 2:
        u16 = lax.bitcast_convert_type(words, jnp.uint16)
        u16 = u16.reshape(words.shape[0], -1)[:, :d]
        return lax.bitcast_convert_type(u16, dtype)
    return _from_u32(words, dtype)


def _xor_reduce(x, axis):
    return lax.reduce(x, np.uint32(0), lax.bitwise_xor, (axis,))


def _resolve_kernels(use_kernels) -> bool:
    if use_kernels is None:  # Pallas on TPU; plain-jnp fold on CPU/GPU
        return jax.default_backend() == "tpu"
    return bool(use_kernels)


def _fold(pkts, use_kernels: bool):
    """XOR-fold ``u32[n, m, pk]`` over axis 1 -> ``u32[n, pk]``."""
    if use_kernels:
        from repro.kernels.xor_code import xor_fold
        return xor_fold(pkts)
    return _xor_reduce(pkts, axis=1)


def _decode(recv, pkts, mask, use_kernels: bool):
    """``recv ^ fold(pkts where mask)`` — Lemma-2 receiver decode."""
    if use_kernels:
        from repro.kernels.xor_code import xor_decode
        return xor_decode(recv, pkts, mask)
    return recv ^ _xor_reduce(jnp.where(mask[..., None], pkts, 0), axis=1)


def _gather_fold(flat, idx, mask, use_kernels: bool):
    """Fused encode primitive: ``XOR_j flat[idx[:, j]] where mask``.

    The jnp lane is ONE XLA gather of exactly the needed packet words
    plus a masked fold — memory scales like the Pallas kernel (no
    ``[n, k, d]`` chunk table, no replication)."""
    if use_kernels:
        from repro.kernels.xor_code import xor_encode_gather
        return xor_encode_gather(flat, idx, mask)
    return _xor_reduce(jnp.where(mask[..., None], flat[idx], 0), axis=1)


def _gather_decode(recv_flat, flat, rsel, idx, mask, use_kernels: bool):
    """Fused decode primitive: ``recv[rsel] ^ XOR_j flat[idx] where
    mask`` — rows come out in final chunk-slot order (``rsel`` bakes the
    round→slot scatter)."""
    if use_kernels:
        from repro.kernels.xor_code import xor_decode_gather
        return xor_decode_gather(recv_flat, flat, rsel, idx, mask)
    return recv_flat[rsel] ^ _xor_reduce(
        jnp.where(mask[..., None], flat[idx], 0), axis=1)


# --------------------------------------------------------------------- #
# the coded exchange (stages 1 and 2 share everything; the flat and
# two-level executors differ ONLY in how a round's packets move).
#
# Two codecs execute the same tables (DESIGN.md §10):
#
# * ``fused`` (default) — Δ and the decode read packet words straight
#   out of the flat chunk buffer via the schedule's precomputed flat
#   index tables (enc_src / dec_src / dec_recv): encode+decode touch
#   HBM twice total, and the largest transient is the [n, k-1, pk]
#   recv buffer the exchange produces anyway.
# * ``multipass`` — the original gather → reshape → take_along_axis →
#   fold pipeline, kept as the CPU/GPU oracle the fused path must match
#   bit-for-bit (tests/test_codec_fused.py).
# --------------------------------------------------------------------- #
def _encode_stage(wire, T: StageTables, me, *, k, pk, codec, use_kernels):
    """Prologue shared by both executors: the sender-side
    Δ = XOR_p pkt(G[p], pos(me, G[p])) (self-row zero).

    ``wire`` is the chunk buffer :func:`_wire_buffer` built — u32 wire
    words, or the u16 lane view on the packed-kernel lane. Returns
    ``(ctx, delta [n, pk])`` (delta always in u32 wire words) where
    ``ctx`` is whatever the matching :func:`_decode_stage` needs to
    cancel packets — the flat ``[·, pk]`` chunk-buffer view (fused) or
    the materialized packet table ``u32[n, k, k-1, pk]`` (multipass)."""
    def dev(tab):
        return jnp.take(jnp.asarray(tab), me, axis=0)

    n = T.n
    if codec == "fused":
        if wire.dtype == jnp.uint16:   # packed lane, Pallas kernels
            from repro.kernels.xor_code import xor_encode_gather16
            flat = wire.reshape(-1, 2 * pk)
            delta16 = xor_encode_gather16(flat, dev(T.enc_src),
                                          dev(T.src_ok))
            return flat, _u16_pairs_to_u32(delta16)
        flat = wire.reshape(-1, pk)    # free view: packets are contiguous
        delta = _gather_fold(flat, dev(T.enc_src), dev(T.src_ok),
                             use_kernels)
        return flat, delta
    chunks = wire[dev(T.src_jslot), dev(T.src_bslot), jnp.asarray(T.shard)]
    chunks = jnp.where(dev(T.src_ok)[:, :, None], chunks, 0)  # [n, k, d]
    packets = chunks.reshape(n, k, k - 1, pk)
    my_pkts = jnp.take_along_axis(
        packets, dev(T.delta_pos)[:, :, None, None], axis=2)[:, :, 0]
    return packets, _fold(my_pkts, use_kernels)


def _decode_stage(recv, ctx, T: StageTables, me, *, k, pk, codec,
                  use_kernels):
    """Epilogue shared by both executors: pkt(me, pos(m_r, me)) =
    recv[r] XOR XOR_{p: G[p] not in {m_r, me}} pkt(G[p], pos(m_r, G[p])),
    decoded words landing in their chunk-slot positions."""
    def dev(tab):
        return jnp.take(jnp.asarray(tab), me, axis=0)

    n = T.n
    if codec == "fused":
        if ctx.dtype == jnp.uint16:    # packed lane, Pallas kernels
            from repro.kernels.xor_code import xor_decode_gather16
            recv16 = lax.bitcast_convert_type(
                recv.reshape(n * (k - 1), pk),
                jnp.uint16).reshape(n * (k - 1), 2 * pk)
            dec16 = xor_decode_gather16(
                recv16, ctx,
                dev(T.dec_recv).reshape(n * (k - 1)),
                dev(T.dec_src).reshape(n * (k - 1), k),
                dev(T.dec_mask).reshape(n * (k - 1), k))
            return _u16_pairs_to_u32(dec16).reshape(n, (k - 1) * pk)
        dec = _gather_decode(
            recv.reshape(n * (k - 1), pk), ctx,
            dev(T.dec_recv).reshape(n * (k - 1)),
            dev(T.dec_src).reshape(n * (k - 1), k),
            dev(T.dec_mask).reshape(n * (k - 1), k),
            use_kernels)
        return dec.reshape(n, (k - 1) * pk)
    # broadcast (not .repeat) the round axis: XLA folds the replication
    # into the gather, so oracle memory stays ~[n, k-1, k, pk]
    canc = jnp.take_along_axis(
        jnp.broadcast_to(ctx[:, None], (n, k - 1, k, k - 1, pk)),
        dev(T.cancel_pos)[:, :, :, None, None], axis=3)[:, :, :, 0]
    cmask = dev(T.cancel_mask)
    dec = _decode(recv.reshape(n * (k - 1), pk),
                  canc.reshape(n * (k - 1), k, pk),
                  cmask.reshape(n * (k - 1), k),
                  use_kernels).reshape(n, k - 1, pk)
    order = jnp.argsort(dev(T.dec_gather), axis=1)
    chunk = jnp.take_along_axis(dec, order[:, :, None], axis=1)
    return chunk.reshape(n, (k - 1) * pk)


def _corrupt_delta(delta, me, corrupt):
    """Flip ``bits`` in one wire word of one device's outgoing Δ —
    the deterministic single-word fault model of the integrity lane
    (DESIGN.md §17). Injected AFTER the checksum fold, so it lands on
    the wire exactly as a transit bit-flip would: the sender's local
    decode context stays clean and every receiver of the tampered
    packet sees a checksum mismatch."""
    if corrupt is None:
        return delta
    cdev, crow, cword, cbits = corrupt
    bump = jnp.where(me == cdev, jnp.uint32(cbits), jnp.uint32(0))
    return delta.at[crow, cword].set(delta[crow, cword] ^ bump)


def _stage_coded_batched(axis_name, wire, T: StageTables, me, *,
                         q, k, K, pk, router, codec, use_kernels,
                         corrupt=None):
    """One coded stage as ``k-1`` grouped collectives (DESIGN.md §4).

    Returns decoded chunks ``u32[n, wp]`` — row order = the stage's
    group rank order (stage 1: job order; stage 2: ``s2_ord``
    ordinals).
    """
    def dev(tab):
        return jnp.take(jnp.asarray(tab), me, axis=0)

    R = int(T.R)
    ctx, delta = _encode_stage(wire, T, me, k=k, pk=pk, codec=codec,
                               use_kernels=use_kernels)
    delta = _corrupt_delta(delta, me, corrupt)
    recv = []
    for r in range(1, k):
        if router == "all_to_all":
            idx = dev(T.a2a_send[r - 1])                       # [K, R]
            buf = jnp.where((idx >= 0)[:, :, None],
                            delta[jnp.clip(idx, 0)], 0)        # [K, R, pk]
            got = lax.all_to_all(buf, axis_name, split_axis=0,
                                 concat_axis=0, tiled=True)
            flat = got.reshape(K * R, pk)
            slot = dev(T.a2a_recv[r - 1])                      # [n]
        elif router == "ppermute":
            parts = []
            for dd in range(q):
                idx = dev(T.pp_send[r - 1, dd])                # [R]
                buf = jnp.where((idx >= 0)[:, None],
                                delta[jnp.clip(idx, 0)], 0)
                parts.append(lax.ppermute(
                    buf, axis_name, perm=list(T.pp_perms[r - 1][dd])))
            flat = jnp.concatenate(parts, axis=0)              # [q*R, pk]
            slot = dev(T.pp_recv[r - 1])
        else:
            raise ValueError(f"unknown router {router!r}")
        recv.append(flat[slot])                                # [n, pk]
    recv = jnp.stack(recv, axis=1)                             # [n, k-1, pk]
    return _decode_stage(recv, ctx, T, me, k=k, pk=pk, codec=codec,
                         use_kernels=use_kernels)


def _stage_coded_two_level(axis_name, wire, T: StageTables,
                           X: HostTables, me, *, q, k, K, pk, router,
                           codec, use_kernels, corrupt=None):
    """One coded stage on a two-level topology (DESIGN.md §16).

    Phase A is :func:`_stage_coded_batched`'s round exchange driven by
    the PRIMARY-masked send tables: the only packet copies that cross a
    host boundary are the per-host gateway copies; masked slots arrive
    as zero blocks. Phase B then relays each gateway's copy to the
    other same-host receivers with intra-host cyclic-shift ppermutes
    (every hop stays on the fast edge), filling exactly the recv slots
    phase A zeroed. The reconstructed receive buffer is word-identical
    to the flat exchange's, so decode — and the shuffle output — stays
    bitwise equal to the flat schedule and the serial engine oracle.
    """
    def dev(tab):
        return jnp.take(jnp.asarray(tab), me, axis=0)

    R = int(T.R)
    n = T.n
    ctx, delta = _encode_stage(wire, T, me, k=k, pk=pk, codec=codec,
                               use_kernels=use_kernels)
    delta = _corrupt_delta(delta, me, corrupt)
    # ---- phase A: flat round exchange, primary deliveries only ------- #
    recv = []
    for r in range(1, k):
        if router == "all_to_all":
            idx = dev(X.a2a_send[r - 1])                      # [K, R]
            buf = jnp.where((idx >= 0)[:, :, None],
                            delta[jnp.clip(idx, 0)], 0)       # [K, R, pk]
            got = lax.all_to_all(buf, axis_name, split_axis=0,
                                 concat_axis=0, tiled=True)
            flat = got.reshape(K * R, pk)
            slot = dev(T.a2a_recv[r - 1])                     # [n]
        elif router == "ppermute":
            parts = []
            for dd in range(q):
                idx = dev(X.pp_send[r - 1, dd])               # [R]
                buf = jnp.where((idx >= 0)[:, None],
                                delta[jnp.clip(idx, 0)], 0)
                parts.append(lax.ppermute(
                    buf, axis_name, perm=list(T.pp_perms[r - 1][dd])))
            flat = jnp.concatenate(parts, axis=0)             # [q*R, pk]
            slot = dev(T.pp_recv[r - 1])
        else:
            raise ValueError(f"unknown router {router!r}")
        recv.append(flat[slot])                               # [n, pk]
    recv_a = jnp.stack(recv, axis=1)                          # [n, k-1, pk]

    # ---- phase B: intra-host gateway relay --------------------------- #
    if int(X.Rb):
        Rb = int(X.Rb)
        src_a = recv_a.reshape(n * (k - 1), pk)   # gateway slots only:
        # a relay source is always a PRIMARY (phase-A-filled) slot, so
        # gathering from the phase-A buffer can never read a slot that
        # phase B itself fills
        rounds = []
        for r in range(1, k):
            live = X.b_live[r - 1]
            if not live:
                rounds.append(recv_a[:, r - 1])
                continue
            parts = []
            for di in live:
                idx = dev(X.b_send[r - 1, di])                # [Rb]
                buf = jnp.where((idx >= 0)[:, None],
                                src_a[jnp.clip(idx, 0)], 0)
                parts.append(lax.ppermute(
                    buf, axis_name, perm=list(X.b_perms[di])))
            relay = jnp.concatenate(parts, axis=0)   # [len(live)*Rb, pk]
            slot = dev(X.b_recv[r - 1])                       # [n]
            mask = dev(X.b_mask[r - 1])                       # [n]
            rounds.append(jnp.where(mask[:, None], relay[slot],
                                    recv_a[:, r - 1]))
        recv_a = jnp.stack(rounds, axis=1)

    return _decode_stage(recv_a, ctx, T, me, k=k, pk=pk, codec=codec,
                         use_kernels=use_kernels)


# --------------------------------------------------------------------- #
# the SPMD shuffle body (runs inside shard_map over `axis_name`)
# --------------------------------------------------------------------- #
def camr_shuffle(plan: CAMRPlan, contribs: jnp.ndarray, *,
                 axis_name: str, router: str = "all_to_all",
                 codec: str = "fused", use_kernels=None,
                 verify_wire: bool = False, corrupt=None):
    """3-stage CAMR coded shuffle: contribs [J_own, k-1, K, d] -> [J, d].

    ``codec="fused"`` (default) runs the single-pass gather-XOR codec
    over the schedule's flat index tables; ``codec="multipass"`` is the
    original multi-pass pipeline, kept as the oracle (DESIGN.md §10).

    bf16/f16 contributions take the packed wire lane (DESIGN.md §12):
    two values per u32 word through stages 1+2 and native-width stage-3
    unicasts — half the bytes-on-wire of an f32 shuffle of the same
    ``d``, with the decoded bit patterns exactly equal to the inputs'
    (the XOR transport never does arithmetic on either lane).

    Per-device outputs are BITWISE equal to the numpy engine's reduce
    results for the same contributions: XOR delivery is lossless and
    the assembly folds batch aggregates in the engine's canonical
    combine order (DESIGN.md §11) — the contract the training path's
    cross-mode parameter identity rests on.

    ``verify_wire=True`` runs the self-verifying wire (DESIGN.md §17):
    every coded packet carries one extra u32 checksum word — the XOR
    of its payload words — folded through the SAME codec (checksums of
    XOR-combined packets XOR-combine, so coded data verifies without
    decoding first). Returns ``(out, bad)`` where ``bad`` is this
    device's count of decoded rows whose recomputed checksum
    mismatches: 0 on every healthy wave (valid rows decode to exact
    packets), and ANY single corrupted wire word in stages 1+2 —
    payload or checksum, flat or relay edge — is counted (a one-word
    delta cannot cancel between a payload fold and its checksum).
    ``corrupt=(stage, device, row, word, bits)`` XORs ``bits`` into
    one outgoing Δ word post-encode — the deterministic fault the
    chaos layer replays. Requires the fused codec; the index
    tables are row-oriented, so the widened rows reuse the same tables,
    and 16-bit payloads pack to u32 words before widening (the u32
    kernels carry them; the u16 kernels assume unaugmented packets).
    """
    prog = plan.program
    q, k, K, J, J_own, d = (plan.q, plan.k, plan.K, plan.J, plan.J_own,
                            plan.d)
    dtype = contribs.dtype
    check_codec_dtype(dtype, "camr_shuffle")
    if contribs.shape != (J_own, k - 1, K, d):
        raise ValueError(f"contribs shape {contribs.shape} != "
                         f"{(J_own, k - 1, K, d)}")
    if codec not in ("fused", "multipass"):
        raise ValueError(f"unknown codec {codec!r}")
    two_level = prog.topology is not None
    if verify_wire:
        if codec != "fused":
            raise ValueError("verify_wire requires codec='fused' (the "
                             "checksum word rides the row-oriented "
                             "fused index tables)")
    elif corrupt is not None:
        raise ValueError("corrupt injection without verify_wire "
                         "would silently mis-reduce — exactly the "
                         "failure mode the integrity lane exists "
                         "to rule out")
    use_kernels = _resolve_kernels(use_kernels)
    me = lax.axis_index(axis_name)
    # wire lane (DESIGN.md §12): wp u32 words per shard — d for 4-byte
    # dtypes, ceil(d/2) (+ pad to a packet multiple) for packed 16-bit
    wp = payload_words(d, jnp.dtype(dtype).itemsize, k)
    pk = wp // (k - 1)

    def dev(tab):
        return jnp.take(jnp.asarray(tab), me, axis=0)

    # [J_own, k-1, K, wp]; the integrity lane widens u32 words, so
    # 16-bit payloads pack to u32 first and ride the u32 kernels
    wire = _wire_buffer(contribs, wp=wp, codec=codec,
                        use_kernels=use_kernels and not verify_wire)
    pkv = pk
    if verify_wire:
        # widen every packet row from pk to pk+1 u32 words: payload +
        # its XOR checksum. The fused tables index packet ROWS, so the
        # same enc_src/dec_src/dec_recv drive the widened buffer; row
        # ids are unchanged by the reshape below.
        w4 = wire.reshape(*wire.shape[:-1], k - 1, pk)
        csum = _xor_reduce(w4, axis=w4.ndim - 1)
        wire = jnp.concatenate([w4, csum[..., None]], axis=-1)
        wire = wire.reshape(*wire.shape[:-2], (k - 1) * (pk + 1))
        pkv = pk + 1
    if corrupt is not None:
        cst, cdev, crow, cword, cbits = (int(x) for x in corrupt)
        if not 0 <= cword < pkv:
            raise ValueError(f"corrupt word {cword} outside packet "
                             f"[0, {pkv})")
        if not cbits:
            raise ValueError("corrupt bits must be nonzero")

    # ========== stages 1 + 2: one shared coded-exchange machine ======== #
    stage_vals = {}
    bad = jnp.zeros((), dtype=jnp.int32)
    for stage in (1, 2):
        T = prog.stage_tables(stage)
        spec = ((cdev, crow, cword, cbits)
                if corrupt is not None and cst == stage else None)
        if two_level:
            decoded = _stage_coded_two_level(
                axis_name, wire, T, prog.host_tables(stage), me, q=q,
                k=k, K=K, pk=pkv, router=router, codec=codec,
                use_kernels=use_kernels, corrupt=spec)
        else:
            decoded = _stage_coded_batched(
                axis_name, wire, T, me, q=q, k=k, K=K, pk=pkv,
                router=router, codec=codec, use_kernels=use_kernels,
                corrupt=spec)
        if verify_wire:
            # recompute each decoded row's checksum; non-member rows
            # decode garbage by design and are masked out (T.valid)
            dec3 = decoded.reshape(-1, k - 1, pkv)
            calc = _xor_reduce(dec3[:, :, :pk], axis=2)
            bad_rows = (calc != dec3[:, :, pk]) & dev(T.valid)[:, None]
            bad = bad + jnp.sum(bad_rows.astype(jnp.int32))
            # strip checksum words: the payload words are bit-for-bit
            # the unverified decode's output
            decoded = dec3[:, :, :pk].reshape(-1, (k - 1) * pk)
        stage_vals[stage] = _from_wire(decoded, dtype, d)
    stage1_val = stage_vals[1]   # [J, d]; row j valid where I own job j
    stage2_val = stage_vals[2]   # [n_s2, d]; rows at my s2_ord ordinals

    # sequential ascending left fold over the stored-batch axis — the
    # canonical combine order of CAMREngine.reduce_phase (stored_batches
    # rows are ascending), so the SPMD output is BITWISE equal to the
    # engine's, not merely allclose (a plain .sum() would let XLA pick
    # its own reduction tree).
    def _fold_stored(x):                                    # [J_own, k-1, d]
        acc = x[:, 0]
        for b in range(1, k - 1):
            acc = acc + x[:, b]
        return acc                                          # [J_own, d]

    # ========== stage 3: intra-class unicasts (q-1 full ppermutes) ===== #
    cls_base = (me // q) * q
    s3_out = jnp.zeros((q - 1, J_own, d), dtype=dtype)
    for o in range(1, q):
        dst = cls_base + (me % q + o) % q
        pay = _fold_stored(jnp.take(contribs, dst, axis=2))  # [J_own, d]
        got = lax.ppermute(pay, axis_name, perm=list(prog.s3_perms[o - 1]))
        s3_out = s3_out.at[o - 1].set(got)

    # ========== assemble (reduce-side tables of the program) ========== #
    # value = delivered batch + fold of the other k-1 (owners fold their
    # own aggregates; non-owners get the sender-side fold via stage 3)
    own_sum = _fold_stored(jnp.take(contribs, me, axis=2))  # [J_own, d]
    d_isown = dev(prog.is_own)
    d_slot = dev(prog.own_slot)
    d_s2 = dev(prog.s2_ord)
    d_s3 = dev(prog.s3_off)

    owner_val = stage1_val + own_sum[d_slot]      # [J, d]
    s2_sel = stage2_val[d_s2]
    s3_sel = s3_out[d_s3, d_slot]
    nonowner_val = s2_sel + s3_sel
    out = jnp.where(d_isown[:, None], owner_val, nonowner_val)
    if verify_wire:
        return out, bad
    return out


def expected_collective_calls(plan: CAMRPlan,
                              router: str = "all_to_all") -> dict[str, int]:
    """Collectives per shuffle — what each router traces (tested against
    the jaxpr in tests/test_collective.py). On a two-level topology the
    phase-B relay adds one intra-host ppermute per live (round, shift)
    lane of each coded stage."""
    q, k = plan.q, plan.k
    s12 = 2 * (k - 1) if router == "all_to_all" else 2 * (k - 1) * q
    if plan.topology is not None:
        s12 += sum(len(live) for X in (plan.program.hx1, plan.program.hx2)
                   for live in X.b_live)
    return dict(stage12=s12, stage3=q - 1, total=s12 + q - 1)


# --------------------------------------------------------------------- #
# helpers for drivers & tests
# --------------------------------------------------------------------- #
def scatter_contributions(plan: CAMRPlan,
                          batch_grads: np.ndarray) -> np.ndarray:
    """batch_grads [J, k, K, d] -> per-device contribs [K, J_own, k-1, K, d]
    per the placement (device s gets the batches it stores)."""
    K, J_own, k = plan.K, plan.J_own, plan.k
    out = np.zeros((K, J_own, k - 1, K, plan.d), dtype=batch_grads.dtype)
    for s in range(K):
        for a, j in enumerate(plan.owned_jobs[s]):
            for b, t in enumerate(plan.stored_batches[s, a]):
                out[s, a, b] = batch_grads[j, t]
    return out


def camr_shuffle_reference(plan: CAMRPlan,
                           batch_grads: np.ndarray) -> np.ndarray:
    """Oracle: out[s, j] = sum over batches of shard s of job j."""
    total = batch_grads.sum(axis=1)               # [J, K, d]
    return np.transpose(total, (1, 0, 2))         # [K, J, d]


def uncoded_reduce_scatter(contribs: jnp.ndarray, *, axis_name: str,
                           plan: CAMRPlan) -> jnp.ndarray:
    """Baseline: mask duplicate batch copies, psum, slice my shard."""
    me = lax.axis_index(axis_name)
    K, J, J_own = plan.K, plan.J, plan.J_own
    first = np.zeros((K, J_own, plan.k - 1), dtype=bool)
    seen = set()
    for s in range(K):
        for a, j in enumerate(plan.owned_jobs[s]):
            for b, t in enumerate(plan.stored_batches[s, a]):
                if (j, t) not in seen:
                    seen.add((j, t))
                    first[s, a, b] = True
    mask = jnp.take(jnp.asarray(first), me, axis=0)
    jl = jnp.take(jnp.asarray(plan.owned_jobs), me, axis=0)
    masked = jnp.where(mask[:, :, None, None], contribs, 0)
    dense = jnp.zeros((J, K, plan.d), contribs.dtype)
    dense = dense.at[jl].add(masked.sum(axis=1))
    total = lax.psum(dense, axis_name)            # [J, K, d]
    return jnp.take(total, me, axis=1)


# --------------------------------------------------------------------- #
# async / double-buffered multi-wave execution (DESIGN.md §9)
# --------------------------------------------------------------------- #
class ShuffleStream:
    """Async, double-buffered multi-wave driver of :func:`camr_shuffle`.

    The SPMD half of the JobStream runtime
    (:class:`repro.runtime.jobstream.JobStream` is the host-side,
    bit-exact reference). Two mechanisms, both byte-preserving:

    * **wave batching** — ``wave_batch`` same-shaped waves are stacked
      along the value axis ``d`` and run as ONE shuffle of width
      ``W*d``. Every step of the codec (packet split, XOR fold,
      cancellation, reassembly) is elementwise per value column, so
      stacking commutes with the whole pipeline and the split outputs
      are exactly the per-wave outputs.
    * **async dispatch with double buffering** — :meth:`submit` issues
      the jitted shard_map computation WITHOUT blocking (jax async
      dispatch); at most ``depth`` dispatched waves keep device buffers
      alive (default 2 = classic double buffering, memory cost model in
      DESIGN.md §9). The oldest in-flight wave is materialized only
      when the window is full, so host-side map/aggregate work for
      wave ``t+1`` overlaps the on-device shuffle of wave ``t``.

    Usage::

        stream = ShuffleStream(q, k, d, mesh=mesh, wave_batch=2)
        outs = stream.run_waves(contribs_list)   # [W][K, J, d]
    """

    def __init__(self, q: int, k: int, d: int, *, mesh,
                 axis_name: str = "camr", depth: int = 2,
                 wave_batch: int = 1, router: str = "all_to_all",
                 codec: str = "fused", use_kernels=None,
                 topology: Topology | None = None,
                 gateway_avoid=frozenset(), verify_wire: bool = False,
                 max_replays: int = 2):
        if k < 3:
            raise ValueError("TPU collective path requires k >= 3")
        if d % (k - 1):
            # validated here, not at dispatch: every stacked width W*d
            # inherits divisibility from d, so a stream can never fail
            # mid-flight on a partial trailing batch
            raise ValueError(f"shard width d={d} must be divisible by "
                             f"k-1={k - 1}")
        if depth < 1:
            raise ValueError("depth must be >= 1")
        if wave_batch < 1:
            raise ValueError("wave_batch must be >= 1")
        self.q, self.k, self.d = q, k, d
        self.K = q * k
        self.mesh = mesh
        self.axis_name = axis_name
        self.depth = depth
        self.wave_batch = wave_batch
        self.router = router
        if codec not in ("fused", "multipass"):
            raise ValueError(f"unknown codec {codec!r}")
        self.codec = codec
        self.use_kernels = use_kernels
        from .schedule import resolve_topology
        self.topology = resolve_topology(topology, q, k)
        if self.topology is not None:
            self.topology.check(q, k)
        self._gateway_avoid = frozenset(int(x)
                                        for x in (gateway_avoid or ()))
        if any(not 0 <= x < self.K for x in self._gateway_avoid):
            raise ValueError(f"gateway_avoid "
                             f"{sorted(self._gateway_avoid)} has "
                             f"devices outside [0, {self.K})")
        self.verify_wire = bool(verify_wire)
        if self.verify_wire and codec != "fused":
            raise ValueError("verify_wire requires codec='fused'")
        if max_replays < 0:
            raise ValueError("max_replays must be >= 0")
        self.max_replays = max_replays
        self._jitted: dict = {}                # executor key -> compiled
        self._pending: list = []               # waves awaiting dispatch
        self._in_flight: deque = deque()       # (out, W, t0, buf)
        self._done: list = []                  # host [K, J, d] outputs
        self._corrupt = None                   # one-shot fault spec
        self.dispatches = 0                    # program executions issued
        self.compiles = 0                      # executors traced (per key)
        self.degraded_compiles = 0             # degraded execs built (§15)
        self._failed: frozenset = frozenset()  # current survivor-set gap
        self.swaps = 0                         # degrade/restore events
        self.host_swaps = 0                    # topology re-homings (§17)
        self.wire_faults = 0                   # checksum-flagged waves
        self.wire_replays = 0                  # bitwise replays issued
        self.wave_times: list[float] = []      # dispatch->collect wall s

    # -- compiled executor per (width, topology, gateways, fault) ------- #
    def _gw(self) -> frozenset:
        """Gateway preference in effect — flat has no gateways."""
        return (self._gateway_avoid if self.topology is not None
                else frozenset())

    def _fn(self, W: int, corrupt=None):
        key = (W,
               None if self.topology is None else self.topology.key(),
               tuple(sorted(self._gw())), self.verify_wire, corrupt)
        if key not in self._jitted:
            from jax.sharding import PartitionSpec as P

            from repro.compat import shard_map
            prog = SCHEDULE_CACHE.program(self.q, self.k, Q=self.K,
                                          d=W * self.d,
                                          topology=self.topology,
                                          gateway_avoid=self._gw())
            plan = CAMRPlan(q=self.q, k=self.k, d=W * self.d,
                            program=prog)
            verify = self.verify_wire

            def body(c):
                r = camr_shuffle(plan, c[0], axis_name=self.axis_name,
                                 router=self.router, codec=self.codec,
                                 use_kernels=self.use_kernels,
                                 verify_wire=verify, corrupt=corrupt)
                if verify:
                    out, bad = r
                    return out[None], bad[None]
                return r[None]

            self.compiles += 1
            self._jitted[key] = jax.jit(shard_map(
                body, mesh=self.mesh, in_specs=P(self.axis_name),
                out_specs=P(self.axis_name)))
        return self._jitted[key]

    # -- fault domains & gateway failover (DESIGN.md §17) --------------- #
    @property
    def gateway_avoid(self) -> frozenset:
        return self._gw()

    def set_topology(self, topology) -> None:
        """Re-home subsequent dispatches onto ``topology`` — the
        whole-host recovery path: after ``HostMembership.kill_host``,
        pass its ``current_topology()`` here. Purely a re-keying:
        executors compiled for other topologies stay resident (a later
        rejoin swaps back retrace-free) and the schedule comes from the
        warm cache — zero cold lowerings after
        :meth:`warm_host_survivors`. Waves already in flight were
        dispatched under the old topology and complete unchanged;
        outputs are bitwise identical across topologies (§16)."""
        from .schedule import resolve_topology
        t = resolve_topology(topology, self.q, self.k)
        if t is not None:
            t.check(self.q, self.k)
        if t != self.topology:
            self.topology = t
            self.host_swaps += 1

    def set_gateway_avoid(self, avoid) -> None:
        """Prefer phase-A gateways OUTSIDE ``avoid`` for subsequent
        dispatches (straggler failover — feed it
        ``Membership.gateway_avoid()``). Joins the executor and
        schedule-cache keys; outputs are bitwise identical for every
        assignment, so this is pure routing policy."""
        fs = frozenset(int(x) for x in (avoid or ()))
        if any(not 0 <= x < self.K for x in fs):
            raise ValueError(f"gateway_avoid {sorted(fs)} has devices "
                             f"outside [0, {self.K})")
        self._gateway_avoid = fs

    def warm_host_survivors(self, *, max_host_failures: int = 1) -> int:
        """Pre-pay the surviving-topology lowering of every loss of up
        to ``max_host_failures`` hosts (ScheduleCache
        .warm_host_survivors), so a later :meth:`set_topology` on the
        kill path is a pure cache hit. Returns survivor topologies
        warmed."""
        if self.topology is None:
            raise ValueError("warm_host_survivors needs a two-level "
                             "stream (flat has no hosts to lose)")
        prog = SCHEDULE_CACHE.program(self.q, self.k, Q=self.K,
                                      d=self.d, topology=self.topology,
                                      gateway_avoid=self._gw())
        return SCHEDULE_CACHE.warm_host_survivors(
            prog, max_host_failures=max_host_failures)

    def inject_corruption(self, *, stage: int = 1, device: int = 0,
                          row=None, word: int = 0, bits: int = 1) -> None:
        """Arm a ONE-SHOT deterministic wire fault: the next dispatched
        wave XORs ``bits`` into outgoing Δ word ``(row, word)`` of
        ``device`` in coded stage ``stage`` (the chaos layer's
        ``CorruptPacket``). The supervisor detects it via the checksum
        word and replays the wave bitwise through the clean executor —
        the transient-fault model. ``row=None`` picks the device's
        first participating group row so the tampered packet is always
        actually sent."""
        if not self.verify_wire:
            raise ValueError("inject_corruption needs verify_wire=True "
                             "— corrupting an unverified wire would "
                             "silently mis-reduce")
        if stage not in (1, 2):
            raise ValueError(f"stage must be 1 or 2, got {stage}")
        if not 0 <= device < self.K:
            raise ValueError(f"device {device} outside [0, {self.K})")
        if not 0 < int(bits) < 2 ** 32:
            raise ValueError("bits must be a nonzero u32 pattern")
        prog = SCHEDULE_CACHE.program(self.q, self.k, Q=self.K,
                                      d=self.d, topology=self.topology,
                                      gateway_avoid=self._gw())
        T = prog.stage_tables(stage)
        if row is None:
            rows = np.flatnonzero(np.asarray(T.valid)[device])
            if not len(rows):
                raise ValueError(f"device {device} participates in no "
                                 f"stage-{stage} group")
            row = int(rows[0])
        if not 0 <= int(row) < T.n:
            raise ValueError(f"row {row} outside [0, {T.n})")
        self._corrupt = (int(stage), int(device), int(row), int(word),
                         int(bits))

    def _take_corrupt(self):
        spec, self._corrupt = self._corrupt, None
        return spec

    def _verified(self, res, bad, buf, W: int):
        """Supervisor half of the integrity lane: block on the per-
        device mismatch counts; on any fault, replay the SAME wave
        through the clean executor (transient-fault model) up to
        ``max_replays`` times, then raise ``WireCorruptionError``.
        Replays are bitwise — the payload words a clean pass decodes
        are exactly the unverified lane's (DESIGN.md §17)."""
        total = int(np.asarray(jax.block_until_ready(bad)).sum())
        if total:
            self.wire_faults += 1
        replays = 0
        while total:
            if replays >= self.max_replays:
                from repro.runtime.fault import WireCorruptionError
                raise WireCorruptionError(
                    f"wave failed wire verification after {replays} "
                    f"bitwise replays ({total} corrupted packet rows "
                    "persist) — persistent corruption, not a "
                    "transient fault; quarantine the link")
            replays += 1
            self.wire_replays += 1
            self.dispatches += 1
            res, bad = self._fn(W)(buf)
            total = int(np.asarray(jax.block_until_ready(bad)).sum())
        return res

    # -- live elasticity (DESIGN.md §14) -------------------------------- #
    @property
    def failed(self) -> frozenset:
        return self._failed

    def degrade(self, failed) -> None:
        """Swap subsequent dispatches to the survivor set ``failed``.

        Validates recoverability up front (unrecoverable sets raise
        ``ValueError`` exactly as :func:`~repro.core.schedule
        .lower_degraded` does) and pulls the re-lowering from the warm
        :data:`SCHEDULE_CACHE`. Waves already in flight were dispatched
        healthy and complete unchanged — a real survivor set only
        affects exchanges issued after the membership change. Degraded
        waves run a COMPILED dense survivor-set executor on device
        (:func:`repro.runtime.fault.build_degraded_executor`, served
        from the process-wide EXEC_CACHE — zero retraces after
        :meth:`warm_degraded_execs`), bitwise-identical to the fault
        runtime's host interpreter
        (:func:`repro.runtime.fault.degraded_shuffle_host`, the
        reference the tests hold it to). The compiled healthy
        executors stay resident either way, so :meth:`restore` is
        retrace-free (``compiles`` flat).
        """
        failed = frozenset(int(s) for s in failed)
        if not failed:
            self.restore()
            return
        prog = SCHEDULE_CACHE.program(self.q, self.k, Q=self.K,
                                      d=self.d, topology=self.topology,
                                      gateway_avoid=self._gw())
        SCHEDULE_CACHE.degraded(prog, failed)   # validate + warm
        if failed != self._failed:
            self._failed = failed
            self.swaps += 1

    def restore(self) -> None:
        """Re-admit everyone: subsequent dispatches run the compiled
        healthy executor again (no retrace — the jitted cache never
        dropped)."""
        if self._failed:
            self._failed = frozenset()
            self.swaps += 1

    def _degraded_fn(self, W: int, dtype, failed=None):
        """The compiled dense degraded executor for stack width ``W``
        and value ``dtype``, AOT-built into the process-wide
        EXEC_CACHE (so a later stream of the same shape — or a
        :meth:`warm_degraded_execs` call before any failure — makes a
        mid-stream degrade completely build-free)."""
        failed = self._failed if failed is None else failed
        topo = None if self.topology is None else self.topology.key()
        key = ("spmd_degraded", self.q, self.k, self.K, W * self.d,
               str(jnp.dtype(dtype)), tuple(sorted(failed)), topo)

        def build():
            from repro.runtime.fault import build_degraded_executor
            prog = SCHEDULE_CACHE.program(self.q, self.k, Q=self.K,
                                          d=W * self.d,
                                          topology=self.topology,
                                          gateway_avoid=self._gw())
            self.degraded_compiles += 1
            return build_degraded_executor(prog, failed, W * self.d,
                                           dtype)

        return EXEC_CACHE.get(key, build)

    def warm_degraded_execs(self, *, max_failures: int = 1,
                            widths=(1,), dtype=np.float32) -> int:
        """Pre-compile the dense degraded executor of every recoverable
        survivor set with up to ``max_failures`` concurrent failures
        (x stack ``widths`` x ``dtype``), alongside the schedule
        warm-up of :meth:`~repro.core.schedule.ScheduleCache
        .warm_survivors` — after this, a mid-stream :meth:`degrade`
        pays neither a lowering nor a compile on the recovery critical
        path (DESIGN.md §15). Returns the number of executables now
        resident."""
        from itertools import combinations
        prog = SCHEDULE_CACHE.program(self.q, self.k, Q=self.K,
                                      d=self.d, topology=self.topology,
                                      gateway_avoid=self._gw())
        SCHEDULE_CACHE.warm_survivors(prog, max_failures=max_failures)
        warmed = 0
        for r in range(1, max_failures + 1):
            for combo in combinations(range(self.K), r):
                fs = frozenset(combo)
                try:
                    SCHEDULE_CACHE.degraded(prog, set(fs))
                except ValueError:
                    continue                   # unrecoverable: skip
                for W in widths:
                    self._degraded_fn(W, dtype, failed=fs)
                    warmed += 1
        return warmed

    def _degraded_exec(self, buf, W: int):
        """Degraded wave over the stacked [K, J_own, k-1, K, W*d]
        tensor through the compiled dense executor (async, output stays
        on device), bitwise-identical to the healthy executor's output
        (DESIGN.md §11), in logical slots."""
        dtype = getattr(buf, "dtype", None)
        if dtype is None:
            buf = np.asarray(buf)
            dtype = buf.dtype
        return self._degraded_fn(W, dtype)(jnp.asarray(buf))

    def _check_wave(self, contribs) -> None:
        shape = (self.K, self.q ** (self.k - 2), self.k - 1, self.K,
                 self.d)
        if tuple(np.shape(contribs)) != shape:
            raise ValueError(f"wave shape {np.shape(contribs)} != {shape}")
        # dtype guard here, not at dispatch: like the width check above,
        # a stream must never discover an uncodable wave mid-flight.
        # getattr, not np.asarray: a device-array wave must not be
        # synced/copied to host just to read its dtype (dtype-less
        # inputs still hit camr_shuffle's own entry guard at dispatch)
        dtype = getattr(contribs, "dtype", None)
        if dtype is not None:
            check_codec_dtype(dtype, "ShuffleStream")

    # -- streaming ------------------------------------------------------ #
    def submit(self, contribs) -> None:
        """Queue one wave ``[K, J_own, k-1, K, d]``; dispatches as soon
        as ``wave_batch`` waves are pending. Never blocks on compute
        unless the double buffer is full."""
        self._check_wave(contribs)
        self._pending.append(contribs)
        if len(self._pending) >= self.wave_batch:
            self._dispatch()

    # -- multi-step reuse (training grad-sync path) --------------------- #
    def sync(self, contribs):
        """Run ONE wave through the stream's compiled executor and
        return the ``[K, J, d]`` **device** output (async dispatch, no
        host copy) — the training grad-sync path: one lowered plan and
        one compiled executor reused across every step, with the output
        left on the mesh for the device-resident optimizer update
        (DESIGN.md §11). Independent of the submit/drain double buffer.
        """
        self._check_wave(contribs)
        self.dispatches += 1
        if self._failed:
            return self._degraded_exec(contribs, 1)
        if self.verify_wire:
            res, bad = self._fn(1, corrupt=self._take_corrupt())(contribs)
            return self._verified(res, bad, contribs, 1)
        return self._fn(1)(contribs)

    def stats(self) -> dict:
        """Executor-reuse counters (``compiles`` stays flat while
        ``dispatches`` grows on a steady-state stream — including
        across degrade/restore ``swaps`` and topology
        ``host_swaps``)."""
        return dict(dispatches=self.dispatches, compiles=self.compiles,
                    widths=sorted({key[0] for key in self._jitted}),
                    swaps=self.swaps,
                    failed=tuple(sorted(self._failed)),
                    degraded_compiles=self.degraded_compiles,
                    topology=(None if self.topology is None
                              else self.topology.key()),
                    gateway_avoid=tuple(sorted(self._gw())),
                    host_swaps=self.host_swaps,
                    verify_wire=self.verify_wire,
                    wire_faults=self.wire_faults,
                    wire_replays=self.wire_replays)

    def _dispatch(self) -> None:
        waves, self._pending = self._pending, []
        if not waves:
            return
        buf = (waves[0] if len(waves) == 1
               else np.concatenate([np.asarray(w) for w in waves],
                                   axis=-1))
        t0 = time.perf_counter()
        keep = None
        if self._failed:
            # degraded waves run the dense survivor-set executor — no
            # coded wire, nothing to checksum (host-oracle-gated lane)
            out = self._degraded_exec(buf, len(waves))
        elif self.verify_wire:
            out = self._fn(len(waves), corrupt=self._take_corrupt())(buf)
            keep = buf                  # retained for a bitwise replay
        else:
            out = self._fn(len(waves))(buf)    # async: returns immediately
        self.dispatches += 1
        self._in_flight.append((out, len(waves), t0, keep))
        while len(self._in_flight) > self.depth:
            self._collect_oldest()

    def _collect_oldest(self) -> None:
        out, W, t0, buf = self._in_flight.popleft()
        if isinstance(out, tuple):                     # integrity lane
            res = self._verified(out[0], out[1], buf, W)
            arr = np.asarray(jax.block_until_ready(res))
        else:
            arr = np.asarray(jax.block_until_ready(out))   # [K, J, W*d]
        self.wave_times.append(time.perf_counter() - t0)
        if W == 1:
            self._done.append(arr)
        else:
            self._done.extend(
                arr[..., w * self.d:(w + 1) * self.d] for w in range(W))

    def drain(self) -> list[np.ndarray]:
        """Flush pending waves, block on everything in flight, and
        return all completed ``[K, J, d]`` outputs in submission order."""
        self._dispatch()
        while self._in_flight:
            self._collect_oldest()
        done, self._done = self._done, []
        return done

    def run_waves(self, waves) -> list[np.ndarray]:
        """Convenience: submit every wave, then drain."""
        for w in waves:
            self.submit(w)
        return self.drain()


def camr_collective_bytes(plan: CAMRPlan, itemsize: int = 4,
                          dtype=None) -> dict[str, int]:
    """On-wire bytes per device-step of the SPMD schedule (p2p model),
    for the §Perf comparison against psum-based reduce-scatter.

    ``dtype`` selects the wire lane: 16-bit dtypes pack two values per
    u32 word through the coded stages 1+2 (plus at most ``k-2`` pad
    words per shard) and ship stage-3 unicasts at native width, so the
    total is ~half the f32 bytes for the same element payload ``d``
    (DESIGN.md §12).
    """
    if dtype is not None:
        check_codec_dtype(dtype, "camr_collective_bytes")
        itemsize = jnp.dtype(dtype).itemsize
    k, q, J, J_own, K, d = (plan.k, plan.q, plan.J, plan.J_own, plan.K,
                            plan.d)
    # coded packets move as u32 wire words regardless of payload dtype
    pk_b = (payload_words(d, itemsize, k) // (k - 1)) * 4
    s1 = J * (k - 1) * pk_b * k            # J groups, k-1 rounds, k senders
    s2 = plan.program.n_s2 * (k - 1) * pk_b * k
    s3 = (q - 1) * J_own * d * itemsize * K
    # uncoded alternative: psum of [J, K, d] dense gradient (ring):
    ring = 2 * (K - 1) * J * K * d * itemsize
    return dict(stage1=s1, stage2=s2, stage3=s3,
                camr_total=s1 + s2 + s3, psum_ring_total=ring)


def camr_edge_bytes(plan: CAMRPlan, itemsize: int = 4,
                    dtype=None) -> dict[str, int]:
    """Per-edge bytes of the flat vs two-level schedules, MEASURED from
    the lowered send tables (DESIGN.md §16) — not the closed form.

    Walks the actual routing tables the executor drives the wire with:
    every kept ``a2a_send`` entry is one packet delivery, classified by
    the host blocks of its sender and receiver under the plan's
    two-level topology; phase-B relay hops (``b_send``) are intra-host
    by construction. Stage-3 unicasts are intra-class and parallel
    classes sit inside host blocks (``hosts | k``), so stage 3 never
    crosses under either schedule.
    ``tests/test_topology.py::test_edge_bytes_match_hierarchical_loads``
    holds these measured counts to the analytic
    :func:`repro.core.loads.camr_load_hierarchical` prediction.

    Requires a plan lowered with a two-level topology (the flat plan
    has no host structure to classify against).
    """
    prog = plan.program
    topo = prog.topology
    if topo is None:
        raise ValueError("camr_edge_bytes needs a plan lowered with a "
                         "two-level topology (make_plan(..., topology="
                         "Topology.two_level(hosts)))")
    if dtype is not None:
        check_codec_dtype(dtype, "camr_edge_bytes")
        itemsize = jnp.dtype(dtype).itemsize
    k, q, K, d, J_own = plan.k, plan.q, plan.K, plan.d, plan.J_own
    pk_b = (payload_words(d, itemsize, k) // (k - 1)) * 4
    host = np.arange(K) // topo.devices_per_host(K)
    cross = host[:, None] != host[None, :]                  # [K, K]
    flat = dict(inter=0, intra=0)
    two = dict(inter=0, intra=0)
    for stage in (1, 2):
        T = prog.stage_tables(stage)
        X = prog.host_tables(stage)
        for tab, acc in ((T.a2a_send, flat), (X.a2a_send, two)):
            kept = (tab >= 0).sum(axis=3).sum(axis=0)       # [K, K]
            acc["inter"] += int(kept[cross].sum())
            acc["intra"] += int(kept[~cross].sum())
        two["intra"] += int((X.b_send >= 0).sum())          # relay hops
    s3_b = (q - 1) * J_own * d * itemsize * K               # intra-host
    return dict(
        hosts=topo.hosts, packet_bytes=pk_b,
        flat_inter_bytes=flat["inter"] * pk_b,
        flat_intra_bytes=flat["intra"] * pk_b + s3_b,
        two_level_inter_bytes=two["inter"] * pk_b,
        two_level_intra_bytes=two["intra"] * pk_b + s3_b,
        s3_inter_bytes=0)
