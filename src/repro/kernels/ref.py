"""Pure-jnp oracles for every Pallas kernel (the ground truth in tests).

These are deliberately simple, O(n^2)-where-natural implementations: the
kernels must match them bit-for-bit (xor/aggregate) or to fp tolerance
(attention/ssd) across shape/dtype sweeps.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["xor_encode_ref", "xor_fold_ref", "xor_decode_ref",
           "xor_encode_gather_ref", "xor_decode_gather_ref",
           "aggregate_ref", "flash_attention_ref", "ssd_scan_ref"]


def xor_encode_ref(packets: jnp.ndarray) -> jnp.ndarray:
    """XOR-fold ``packets[m, :]`` over axis 0. uint32 in/out.

    This is the Algorithm-2 Δ computation: a server's coded broadcast is
    the XOR of the m = k-1 packets assigned to it.
    """
    if packets.dtype != jnp.uint32:
        raise TypeError("xor_encode expects uint32 bit patterns")
    return lax.reduce(packets, jnp.uint32(0), lax.bitwise_xor, (0,))


def xor_fold_ref(packets: jnp.ndarray) -> jnp.ndarray:
    """Batched encode oracle: ``u32[R, m, n]`` -> ``u32[R, n]``."""
    if packets.dtype != jnp.uint32:
        raise TypeError("xor_fold expects uint32 bit patterns")
    return lax.reduce(packets, jnp.uint32(0), lax.bitwise_xor, (1,))


def xor_decode_ref(recv: jnp.ndarray, packets: jnp.ndarray,
                   mask: jnp.ndarray) -> jnp.ndarray:
    """Batched decode oracle: ``recv ^ fold(packets where mask)``."""
    masked = jnp.where(mask[..., None], packets, jnp.uint32(0))
    return recv ^ xor_fold_ref(masked)


def xor_encode_gather_ref(chunks: jnp.ndarray, idx: jnp.ndarray,
                          mask: jnp.ndarray) -> jnp.ndarray:
    """Fused-encode oracle: ``out[i] = XOR_j chunks[idx[i, j]] & mask``.

    ``chunks: u32[P, pk]``, ``idx: i32[n, m]``, ``mask: bool[n, m]`` —
    a plain XLA gather + masked fold (the memory-light jnp lane of the
    fused codec; the Pallas kernel must match it bit-for-bit).
    """
    gathered = chunks[idx]                       # [n, m, pk]
    return xor_fold_ref(jnp.where(mask[..., None], gathered,
                                  jnp.uint32(0)))


def xor_decode_gather_ref(recv: jnp.ndarray, chunks: jnp.ndarray,
                          rsel: jnp.ndarray, idx: jnp.ndarray,
                          mask: jnp.ndarray) -> jnp.ndarray:
    """Fused-decode oracle:
    ``out[i] = recv[rsel[i]] ^ XOR_j chunks[idx[i, j]] & mask``."""
    return recv[rsel] ^ xor_encode_gather_ref(chunks, idx, mask)


def aggregate_ref(values: jnp.ndarray, segment_ids: jnp.ndarray,
                  num_segments: int) -> jnp.ndarray:
    """The paper's α-combiner: sum values with the same (function, batch)
    key. values: [n, d] float; segment_ids: [n] int32 -> [num_segments, d].
    """
    return jax.ops.segment_sum(values, segment_ids,
                               num_segments=num_segments)


def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        *, causal: bool = True, window: int | None = None,
                        softcap: float | None = None,
                        scale: float | None = None,
                        valid_len=None) -> jnp.ndarray:
    """Materialized attention oracle.

    q: [B, Hq, Tq, D]; k, v: [B, Hkv, Tk, D] (GQA: Hq % Hkv == 0).
    ``window``: sliding-window size (attend to keys in (i-window, i]).
    ``softcap``: gemma2-style logit soft-capping: cap*tanh(x/cap).
    ``valid_len``: (traced) number of valid keys — queries are aligned so
    the last query sits at position valid_len-1 (partial KV-cache decode).
    A ``[B]`` vector gives each batch row its own valid length (ragged
    continuous-batching decode, DESIGN.md §13); scalar/None keep the
    original shared-length mask.
    """
    B, Hq, Tq, D = q.shape
    Hkv = k.shape[1]
    rep = Hq // Hkv
    # grouped form: never materialize the rep-fold K/V broadcast
    qg = q.reshape(B, Hkv, rep, Tq, D).astype(jnp.float32)
    scale = scale if scale is not None else D ** -0.5
    logits = jnp.einsum("bgrqd,bgkd->bgrqk", qg,
                        k.astype(jnp.float32)) * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    Tk = k.shape[2]
    if valid_len is not None and jnp.ndim(valid_len) == 1:
        # per-row valid lengths: mask [B, Tq, Tk], broadcast over heads
        endb = jnp.asarray(valid_len)[:, None, None]         # [B, 1, 1]
        qpos = jnp.arange(Tq)[None, :, None] + (endb - Tq)   # [B, Tq, 1]
        kpos = jnp.arange(Tk)[None, None, :]                 # [1, 1, Tk]
        mask = kpos < endb
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        logits = jnp.where(mask[:, None, None], logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bgrqk,bgkd->bgrqd", p, v.astype(jnp.float32))
        return out.reshape(B, Hq, Tq, D).astype(q.dtype)
    end = Tk if valid_len is None else valid_len
    qpos = jnp.arange(Tq)[:, None] + (end - Tq)  # right-aligned (decode ok)
    kpos = jnp.arange(Tk)[None, :]
    mask = kpos < end
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bgrqk,bgkd->bgrqd", p, v.astype(jnp.float32))
    return out.reshape(B, Hq, Tq, D).astype(q.dtype)


def flash_attention_chunked(q, k, v, *, causal=True, window=None,
                            softcap=None, scale=None, valid_len=None,
                            block_q: int = 1024, block_k: int = 1024,
                            unroll: bool = False):
    """Flash attention in pure jnp (the XLA lane for long sequences).

    Online-softmax over K/V blocks; queries are processed in python-
    unrolled blocks so causal/window scheduling SKIPS fully-masked K
    blocks at the HLO level (no 2x causal FLOP waste). Full-head layout
    (K/V broadcast over the GQA group) so the head axis stays tensor-
    parallel without resharding. ``unroll`` unrolls the inner K-block
    scan — used by the dry-run cost pass for trip-true HLO accounting.
    """
    B, Hq, Tq, D = q.shape
    _, Hkv, Tk, _ = k.shape
    rep = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    bq = min(block_q, Tq)
    bk = min(block_k, Tk)
    tq_pad = -(-Tq // bq) * bq
    tk_pad = -(-Tk // bk) * bk
    end = Tk if valid_len is None else valid_len
    # left-pad queries (keep right alignment), right-pad keys (masked)
    qp = jnp.pad(q, ((0, 0), (0, 0), (tq_pad - Tq, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, tk_pad - Tk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, tk_pad - Tk), (0, 0)))
    if rep > 1:  # broadcast KV to full heads (fuses into the einsum)
        kp = jnp.broadcast_to(kp[:, :, None],
                              (B, Hkv, rep, tk_pad, D)).reshape(
            B, Hq, tk_pad, D)
        vp = jnp.broadcast_to(vp[:, :, None],
                              (B, Hkv, rep, tk_pad, D)).reshape(
            B, Hq, tk_pad, D)
    qg = qp * jnp.asarray(scale, qp.dtype)
    kb = jnp.moveaxis(kp.reshape(B, Hq, tk_pad // bk, bk, D), 2, 0)
    vb = jnp.moveaxis(vp.reshape(B, Hq, tk_pad // bk, bk, D), 2, 0)

    outs = []
    for qi in range(tq_pad // bq):
        qblk = qg[:, :, qi * bq:(qi + 1) * bq]           # [B, Hq, bq, D]
        qpos = (qi * bq + jnp.arange(bq) + (end - tq_pad))  # absolute
        # static block schedule (conservative: uses Tk, not valid_len)
        q_last = qi * bq + bq - 1 + (Tk - tq_pad)
        q_first = qi * bq + (Tk - tq_pad)
        lo = 0
        hi = tk_pad // bk
        if causal:
            hi = min(hi, q_last // bk + 1)
        if window is not None:
            lo = max(lo, (q_first - window + 1) // bk)
        lo = max(min(lo, hi), 0)
        if hi <= lo:
            outs.append(jnp.zeros((B, Hq, bq, D), jnp.float32))
            continue

        def body(carry, xs):
            m, l, acc = carry
            kx, vx, start = xs
            s = jnp.einsum("bhqd,bhkd->bhqk", qblk, kx,
                           preferred_element_type=jnp.float32)
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            kpos = start + jnp.arange(bk)
            mask = kpos[None, :] < end
            if causal:
                mask = mask & (kpos[None, :] <= qpos[:, None])
            if window is not None:
                mask = mask & (kpos[None, :] > qpos[:, None] - window)
            s = jnp.where(mask[None, None], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(vx.dtype), vx,
                preferred_element_type=jnp.float32)
            return (m_new, l, acc), None

        m0 = jnp.full((B, Hq, bq, 1), -1e30, jnp.float32)
        l0 = jnp.zeros((B, Hq, bq, 1), jnp.float32)
        a0 = jnp.zeros((B, Hq, bq, D), jnp.float32)
        starts = (jnp.arange(lo, hi) * bk)
        # checkpoint the block body: backward recomputes the [bq, bk]
        # score/probability tensors instead of saving them per iteration
        # (flash-attention-style; O(T) instead of O(T^2) residuals)
        (m, l, acc), _ = lax.scan(
            jax.checkpoint(body), (m0, l0, a0),
            (kb[lo:hi], vb[lo:hi], starts),
            unroll=(hi - lo) if unroll else 1)
        outs.append(acc / jnp.where(l == 0.0, 1.0, l))

    out = jnp.concatenate(outs, axis=2)
    return out[:, :, tq_pad - Tq:].astype(q.dtype)


def ssd_chunked(x, a, b, c, *, chunk: int = 256, unroll: bool = False):
    """Chunked SSD for the XLA lane — the same matmul-form math as
    kernels/ssd_scan.py (MXU-friendly, O(T/C) sequential steps instead of
    O(T)). ``b``/``c`` are GROUP-SHARED projections [B, T, S] (Mamba2
    n_groups=1) — never broadcast over heads, which keeps the activation
    footprint at [B, T, S] instead of [B, T, H, S].
    ``unroll`` unrolls the chunk scan (dry-run cost pass)."""
    B, T, H, Pd = x.shape
    S = b.shape[-1]
    assert b.ndim == 3 and c.ndim == 3, "group-shared b/c: [B, T, S]"
    C = min(chunk, T)
    t_pad = -(-T // C) * C
    if t_pad != T:
        pad4 = ((0, 0), (0, t_pad - T), (0, 0), (0, 0))
        pad3 = ((0, 0), (0, t_pad - T), (0, 0))
        x = jnp.pad(x, pad4)
        b, c = jnp.pad(b, pad3), jnp.pad(c, pad3)
        a = jnp.pad(a, pad3)
    nc = t_pad // C

    def resh(z):  # [B, T, ...] -> [nc, B, C, ...]
        z2 = z.reshape(B, nc, C, *z.shape[2:])
        return jnp.moveaxis(z2, 1, 0)

    xs = (resh(x), resh(a), resh(b), resh(c))
    tri = (jnp.arange(C)[:, None] >= jnp.arange(C)[None, :])

    def body(h, inp):
        xc, ac, bc, cc = inp                   # [B,C,H,P] [B,C,H] [B,C,S]
        cum = jnp.cumsum(ac.astype(jnp.float32), axis=1)  # [B, C, H]
        decay = jnp.exp(cum)
        ccf = cc.astype(jnp.float32)
        bcf = bc.astype(jnp.float32)
        xcf = xc.astype(jnp.float32)
        y_state = decay[..., None] * jnp.einsum("bcs,bhsp->bchp", ccf, h)
        ratio = jnp.exp(cum[:, :, None] - cum[:, None])   # [B, C, C, H]
        cb = jnp.einsum("bcs,bks->bck", ccf, bcf)         # [B, C, C]
        M = jnp.where(tri[None, :, :, None],
                      cb[..., None] * ratio, 0.0)         # [B, C, C, H]
        y_intra = jnp.einsum("bckh,bkhp->bchp", M, xcf)
        w = jnp.exp(cum[:, -1:, :] - cum)                 # [B, C, H]
        h_new = (jnp.exp(cum[:, -1])[..., None, None] * h
                 + jnp.einsum("bcs,bch,bchp->bhsp", bcf, w, xcf))
        return h_new, (y_state + y_intra).astype(x.dtype)

    h0 = jnp.zeros((B, H, S, Pd), jnp.float32)
    # checkpoint: the [B, C, C, H] decay/mixing tensors are recomputed in
    # the backward instead of being saved per chunk (the SSD memory whale)
    _, ys = lax.scan(jax.checkpoint(body), h0, xs,
                     unroll=nc if unroll else 1)
    y = jnp.moveaxis(ys, 0, 1).reshape(B, t_pad, H, Pd)
    return y[:, :T].astype(x.dtype)


def ssd_scan_ref(x: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray,
                 c: jnp.ndarray) -> jnp.ndarray:
    """Mamba2 SSD (state-space dual) oracle — sequential recurrence.

    x: [B, T, H, P]   per-head inputs
    a: [B, T, H]      log-decay per step (a_t = exp(log_a_t) in (0, 1])
    b: [B, T, H, S]   input projection onto state
    c: [B, T, H, S]   output projection
    Returns y: [B, T, H, P] with state h_t = a_t * h_{t-1} + b_t x_t^T,
    y_t = c_t^T h_t  (h: [S, P] per head).
    """
    Bt, T, H, Pd = x.shape
    S = b.shape[-1]

    def step(h, inp):
        xt, at, bt, ct = inp
        h = at[..., None, None] * h + jnp.einsum("bhs,bhp->bhsp", bt, xt)
        y = jnp.einsum("bhs,bhsp->bhp", ct, h)
        return h, y

    h0 = jnp.zeros((Bt, H, S, Pd), dtype=jnp.float32)
    xs = (jnp.moveaxis(x, 1, 0).astype(jnp.float32),
          jnp.moveaxis(jnp.exp(a), 1, 0).astype(jnp.float32),
          jnp.moveaxis(b, 1, 0).astype(jnp.float32),
          jnp.moveaxis(c, 1, 0).astype(jnp.float32))
    _, ys = lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype)


def ssm_state_step_ref(state: jnp.ndarray, layer, da: jnp.ndarray,
                       xin: jnp.ndarray, b: jnp.ndarray, c: jnp.ndarray):
    """One Mamba2 decode step on layer ``layer`` of the stacked state
    ``f32[R, B, H, P, S]``, in XLA: ``h' = da * h + xin (x) b`` written in
    place, ``y = sum_S c * h'``. ``da: [B, H]``, ``xin: [B, H, P]``,
    ``b``, ``c: [B, G, S]`` (head ``h`` reads group ``h // (H // G)``).
    Returns ``(state, y: [B, H, P])``; the oracle of
    ``ssm_step.mamba2_state_step``."""
    B, H, P, S = state.shape[1:]
    G = b.shape[1]
    st = lax.dynamic_index_in_dim(state, layer, 0, False)
    st = st.reshape(B, G, H // G, P, S) * da.reshape(B, G, H // G, 1, 1) \
        + jnp.einsum("bgs,bghp->bghps", b, xin.reshape(B, G, H // G, P))
    state = lax.dynamic_update_index_in_dim(
        state, st.reshape(B, H, P, S), layer, 0)
    # read the row back from the written state: the old state then has no
    # reader after the write, so XLA keeps the write in place (at the
    # price of reading the layer's state a second time)
    st = lax.dynamic_index_in_dim(state, layer, 0, False)
    y = jnp.einsum("bgs,bghps->bghp", c, st.reshape(B, G, H // G, P, S))
    return state, y.reshape(B, H, P)
