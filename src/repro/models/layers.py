"""Layer primitives shared by all architectures (pure functions on pytrees).

Conventions
-----------
* params are nested dicts of jnp arrays; every init_* has a matching
  spec_* returning the same structure with *logical axis* tuples used by
  the partitioner (repro.launch.partitioning).
* activations: x [B, T, D]; attention uses [B, H, T, Dh] internally.
* all matmuls accumulate in f32 (preferred_element_type) regardless of the
  param/activation dtype.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import ops

Params = dict
Specs = dict

# logical axis names (mapped to mesh axes in launch/partitioning.py).
# NOTE: the d_model axis of *parameters* is the FSDP shard axis ('fsdp');
# the 'embed' name is reserved for activations (replicated over model).
EMBED, FFN, HEADS, KV, VOCAB, EXP, SSM_IN, STATE = (
    "fsdp", "ffn", "heads", "kv", "vocab", "experts", "ssm_in", "state")


# --------------------------------------------------------------------- #
# basics
# --------------------------------------------------------------------- #
def dense(x, w):
    return lax.dot_general(x, w, (((x.ndim - 1,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32
                           ).astype(x.dtype)


def rms_norm(x, scale, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layer_norm(x, scale, bias, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def rope(x, positions, theta=1e4):
    """x: [B, H, T, Dh]; positions: [B, T] or [T]."""
    B, H, T, Dh = x.shape
    half = Dh // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[:, None, :, None].astype(jnp.float32) * freq  # [B,1,T,h]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------- #
# attention (GQA + RoPE + window/softcap), with optional KV cache
# --------------------------------------------------------------------- #
def init_attention(key, cfg) -> Params:
    d, hq, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    k1, k2, k3, k4 = jax.random.split(key, 4)
    sc = d ** -0.5
    return {
        "wq": jax.random.normal(k1, (d, hq * dh), cfg.dtype) * sc,
        "wk": jax.random.normal(k2, (d, hkv * dh), cfg.dtype) * sc,
        "wv": jax.random.normal(k3, (d, hkv * dh), cfg.dtype) * sc,
        "wo": jax.random.normal(k4, (hq * dh, d), cfg.dtype) * sc,
    }


def spec_attention(cfg) -> Specs:
    return {"wq": (EMBED, HEADS), "wk": (EMBED, KV), "wv": (EMBED, KV),
            "wo": (HEADS, EMBED)}


def attention_block(p, x, positions, cfg, *, window=None, softcap=None,
                    causal=True, cache=None, cache_index=None,
                    memory=None, layer=None):
    """Self- (or cross-, when ``memory`` is set) attention.

    cache: optional dict(k=[B, Hkv, Tmax, Dh], v=...) -> returns updated.
    With ``layer`` (decode) the cache is the layer-stacked ``[R, ...]``
    array, written in place at ``layer`` and returned whole.
    """
    B, T, D = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = dense(x, p["wq"]).reshape(B, T, hq, dh).transpose(0, 2, 1, 3)
    src = x if memory is None else memory
    Ts = src.shape[1]
    k = dense(src, p["wk"]).reshape(B, Ts, hkv, dh).transpose(0, 2, 1, 3)
    v = dense(src, p["wv"]).reshape(B, Ts, hkv, dh).transpose(0, 2, 1, 3)
    if memory is None and cfg.rope_theta is not None:   # None: NoPE
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    new_cache = None
    valid_len = None
    if cache is not None and "pages" in cache:
        # paged slot-indexed layout (serving, DESIGN.md §13): k/v live in
        # a shared page pool [R, P, Hkv, page, Dh]; ``pages`` [R, B, npp]
        # maps each slot's logical pages to physical ones; ``cache_index``
        # is the per-row logical write position (-1 = finished row, its
        # write is routed to the reserved trash page 0 and its keys are
        # fully masked via valid_len 0).
        assert T == 1, "paged cache entries are decode-only (T == 1)"
        pt = cache["pages"][layer]                # [B, npp] int32
        ps = cache["k"].shape[3]                  # page size
        npp = pt.shape[1]
        rows = jnp.arange(B)
        idx = cache_index
        with jax.named_scope("kv_write"):
            safe = jnp.maximum(idx, 0)
            phys = jnp.where(idx < 0, 0, pt[rows, safe // ps])   # [B]
            off = safe % ps                                       # [B]
            kc, vc = cache["k"], cache["v"]
            # one in-place row write per batch row: a scatter at (layer,
            # phys, :, off) makes CPU layout assignment transpose (and
            # copy) the whole pool
            for b in range(B):
                at = (layer, phys[b], 0, off[b], 0)
                kc = lax.dynamic_update_slice(
                    kc, k[b, :, 0][None, None, :, None].astype(kc.dtype), at)
                vc = lax.dynamic_update_slice(
                    vc, v[b, :, 0][None, None, :, None].astype(vc.dtype), at)
        new_cache = {"k": kc, "v": vc, "pages": cache["pages"]}
        # gather the slot's pages back into logical order: the dense
        # per-row view the masked attention below consumes
        with jax.named_scope("kv_gather"):
            k = kc[layer, pt].transpose(0, 2, 1, 3, 4).reshape(
                B, hkv, npp * ps, dh)
            v = vc[layer, pt].transpose(0, 2, 1, 3, 4).reshape(
                B, hkv, npp * ps, dh)
        valid_len = idx + T                       # [B]; -1 -> all masked
    elif cache is not None:
        # write this step's k/v at cache_index; keep the updated cache in
        # its sharded layout (a resharded DUS would replicate it)
        from repro.launch.partitioning import constrain as _con
        lead = () if layer is None else (layer,)
        spec = (None,) * len(lead) + ("batch", None, "seq_kv", None)
        with jax.named_scope("kv_write"):
            at = lead + (0, 0, cache_index, 0)
            kc = lax.dynamic_update_slice(
                cache["k"], k.reshape((1,) * len(lead) + k.shape), at)
            vc = lax.dynamic_update_slice(
                cache["v"], v.reshape((1,) * len(lead) + v.shape), at)
            kc = _con(kc, spec)
            vc = _con(vc, spec)
        new_cache = {"k": kc, "v": vc}
        if T == 1:
            # decode: attend over the cache up to the current position
            k, v = (kc, vc) if layer is None else (kc[layer], vc[layer])
            valid_len = cache_index + T
        # else prefill: the T tokens just computed ARE the valid keys —
        # attend over (k, v) directly with the static causal mask (keeps
        # the O(T) chunked-flash path; the cache write is independent)

    # keep the head axis tensor-parallel through the attention einsums
    # (constrain drops axes that do not divide, e.g. gemma2's 8 heads)
    from repro.launch.partitioning import constrain
    q = constrain(q, ("batch", "heads", None, None))
    k = constrain(k, ("batch", "heads", None, None))
    v = constrain(v, ("batch", "heads", None, None))
    out = ops.attention(q, k, v, causal=causal and memory is None,
                        window=window, softcap=softcap, scale=cfg.attn_scale,
                        valid_len=valid_len,
                        use_pallas=cfg.use_pallas,
                        block_q=cfg.attn_block, block_k=cfg.attn_block,
                        unroll=cfg.scan_unroll)
    out = constrain(out, ("batch", "heads", None, None))
    out = out.transpose(0, 2, 1, 3).reshape(B, T, hq * dh)
    out = dense(out, p["wo"])
    return (out, new_cache) if cache is not None else (out, None)


# --------------------------------------------------------------------- #
# MLP: SwiGLU / GEGLU
# --------------------------------------------------------------------- #
def init_mlp(key, cfg) -> Params:
    d, f = cfg.d_model, cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": jax.random.normal(k1, (d, f), cfg.dtype) * d ** -0.5,
        "w_up": jax.random.normal(k2, (d, f), cfg.dtype) * d ** -0.5,
        "w_down": jax.random.normal(k3, (f, d), cfg.dtype) * f ** -0.5,
    }


def spec_mlp(cfg) -> Specs:
    return {"w_gate": (EMBED, FFN), "w_up": (EMBED, FFN),
            "w_down": (FFN, EMBED)}


def mlp_block(p, x, cfg):
    act = jax.nn.gelu if cfg.mlp_act == "geglu" else jax.nn.silu
    h = act(dense(x, p["w_gate"])) * dense(x, p["w_up"])
    return dense(h, p["w_down"])


# --------------------------------------------------------------------- #
# MoE (top-k routing, capacity-bounded sort-free dispatch)
# --------------------------------------------------------------------- #
def init_moe(key, cfg) -> Params:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "router": jax.random.normal(k1, (d, e), cfg.dtype) * d ** -0.5,
        "w_gate": jax.random.normal(k2, (e, d, f), cfg.dtype) * d ** -0.5,
        "w_up": jax.random.normal(k3, (e, d, f), cfg.dtype) * d ** -0.5,
        "w_down": jax.random.normal(k4, (e, f, d), cfg.dtype) * f ** -0.5,
    }


def spec_moe(cfg) -> Specs:
    if cfg.moe_shard_mode == "ep":
        w = (EXP, EMBED, None)
        wd = (EXP, None, EMBED)
    else:  # tensor-parallel experts (few big experts, e.g. mixtral)
        w = (None, EMBED, FFN)
        wd = (None, FFN, EMBED)
    return {"router": (EMBED, None), "w_gate": w, "w_up": w, "w_down": wd}


def _moe_dispatch_compute(p, xf, cfg, n_model: int = 1,
                          axis_name: str | None = None,
                          ep_replicated: bool = False):
    """Local dispatch + expert FFN on a flat token block [N, D].

    When running manually over a 'model' axis (axis_name set):
      - 'ep' mode: experts are sharded E/n_model per device; tokens are
        routed with a bidirectional all_to_all (the classic MoE a2a).
      - 'ep' + ``ep_replicated`` (tokens identical on every model shard,
        e.g. decode with T=1): each shard serves only its local experts
        and the partial token outputs are psum'd — no a2a, no duplicate
        expert work.
      - 'tp' mode: every expert's FFN dim is sharded; partial outputs
        are psum'd over the axis.
    Tokens beyond an expert's capacity are dropped (GShard behaviour).
    """
    N, D = xf.shape
    E, topk = cfg.n_experts, cfg.experts_per_token
    logits = dense(xf, p["router"]).astype(jnp.float32)       # [N, E]
    gates = jax.nn.softmax(logits, axis=-1)
    w, idx = lax.top_k(gates, topk)                           # [N, topk]
    w = w / jnp.sum(w, axis=-1, keepdims=True)

    cap = int(cfg.moe_capacity_factor * N * topk / E)
    cap = max(cap, 4)
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)          # [N, topk, E]
    flat = onehot.reshape(N * topk, E)
    pos = jnp.cumsum(flat, axis=0) - flat
    pos = jnp.sum(pos * flat, axis=-1)                        # [N*topk]
    eidx = idx.reshape(N * topk)
    keep = pos < cap
    src = jnp.repeat(xf, topk, axis=0)
    act = jax.nn.gelu if cfg.mlp_act == "geglu" else jax.nn.silu
    ep = axis_name is not None and cfg.moe_shard_mode == "ep" \
        and n_model > 1 and not ep_replicated
    ep_rep = axis_name is not None and cfg.moe_shard_mode == "ep" \
        and n_model > 1 and ep_replicated
    tp = axis_name is not None and cfg.moe_shard_mode == "tp" \
        and n_model > 1

    if ep_rep:
        e_loc = E // n_model
        e0 = lax.axis_index(axis_name) * e_loc
        mine = keep & (eidx >= e0) & (eidx < e0 + e_loc)
        e_sel = jnp.where(mine, eidx - e0, e_loc - 1)
        c_sel = jnp.where(mine, pos, cap - 1)
        buf = jnp.zeros((e_loc, cap, D), xf.dtype)
        buf = buf.at[e_sel, c_sel].add(jnp.where(mine[:, None], src, 0))
    else:
        e_sel = jnp.where(keep, eidx, E - 1)
        c_sel = jnp.where(keep, pos, cap - 1)
        buf = jnp.zeros((E, cap, D), xf.dtype)
        buf = buf.at[e_sel, c_sel].add(jnp.where(keep[:, None], src, 0))
        mine = keep
    if ep:
        # route tokens to the peers owning each expert block:
        # [E, cap, D] -> [E/n, n*cap, D] (tiled a2a, self-transposing)
        buf = lax.all_to_all(buf, axis_name, split_axis=0, concat_axis=1,
                             tiled=True)
    h = act(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"],
                       preferred_element_type=jnp.float32).astype(xf.dtype))
    h = h * jnp.einsum("ecd,edf->ecf", buf, p["w_up"],
                       preferred_element_type=jnp.float32).astype(xf.dtype)
    out_e = jnp.einsum("ecf,efd->ecd", h, p["w_down"],
                       preferred_element_type=jnp.float32).astype(xf.dtype)
    if ep:
        # route results back: [E/n, n*cap, D] -> [E, cap, D]
        out_e = lax.all_to_all(out_e, axis_name, split_axis=1,
                               concat_axis=0, tiled=True)
    if tp:
        out_e = lax.psum(out_e, axis_name)  # FFN-dim partial sums

    got = out_e[e_sel, c_sel]
    got = jnp.where(mine[:, None], got, 0)
    wflat = w.reshape(N * topk, 1).astype(xf.dtype)
    out = jnp.sum((got * wflat).reshape(N, topk, D), axis=1)
    if ep_rep:
        out = lax.psum(out, axis_name)     # combine expert-shard partials
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(jax.nn.one_hot(idx[:, 0], E, dtype=jnp.float32), axis=0)
    return out, (me, ce)


def moe_block(p, x, cfg):
    """Top-k MoE. With an active mesh, dispatch runs under shard_map so
    the scatter/gather stays LOCAL to each token shard (GSPMD cannot
    partition data-dependent scatters well) and the expert parallelism
    is an explicit all_to_all ('ep') or psum ('tp') on the model axis."""
    from repro.launch import partitioning as pt
    B, T, D = x.shape
    mesh = pt.current_mesh()
    E = cfg.n_experts
    if mesh is None:
        out, (me, ce) = _moe_dispatch_compute(p, x.reshape(B * T, D), cfg)
        return out.reshape(B, T, D), E * jnp.sum(me * ce)

    from jax.sharding import PartitionSpec as P
    ctx_rules = pt._state.ctx[1]
    daxes = tuple(ctx_rules["batch"])
    n_data = 1
    for a in daxes:
        n_data *= mesh.shape[a]
    n_model = mesh.shape["model"]
    batch_ax = daxes if B % n_data == 0 else None
    if batch_ax is not None and len(batch_ax) == 1:
        batch_ax = batch_ax[0]
    # EP splits tokens over 'model' (a2a regroups by expert); TP must NOT
    # (its psum reduces FFN partials of the SAME tokens)
    seq_ax = "model" if (cfg.moe_shard_mode == "ep"
                         and T % n_model == 0) else None
    xs = P(batch_ax, seq_ax, None)

    if cfg.moe_shard_mode == "ep":
        wspec = {"router": P(None, None), "w_gate": P("model", None, None),
                 "w_up": P("model", None, None),
                 "w_down": P("model", None, None)}
    else:
        wspec = {"router": P(None, None), "w_gate": P(None, None, "model"),
                 "w_up": P(None, None, "model"),
                 "w_down": P(None, "model", None)}

    ep_rep = cfg.moe_shard_mode == "ep" and seq_ax is None

    def body(p_loc, x_loc):
        b, t, _ = x_loc.shape
        out, (me, ce) = _moe_dispatch_compute(
            p_loc, x_loc.reshape(b * t, D), cfg, n_model=n_model,
            axis_name="model", ep_replicated=ep_rep)
        # aux loss: global token means FIRST (linear), then the product
        for ax in ("model",) + tuple(daxes):
            me, ce = lax.pmean(me, ax), lax.pmean(ce, ax)
        return out.reshape(b, t, D), E * jnp.sum(me * ce)

    from repro.compat import shard_map
    out, aux = shard_map(
        body, mesh=mesh,
        in_specs=(wspec, xs), out_specs=(xs, P()))(
        {k: p[k] for k in ("router", "w_gate", "w_up", "w_down")}, x)
    return out, aux


# --------------------------------------------------------------------- #
# Mamba2 block (SSD core + gating, simplified faithful structure)
# --------------------------------------------------------------------- #
def init_ssm(key, cfg) -> Params:
    d = cfg.d_model
    di = cfg.ssm_d_inner
    H = cfg.ssm_heads
    S = cfg.ssm_state
    ks = jax.random.split(key, 6)
    return {
        "w_in": jax.random.normal(ks[0], (d, di), cfg.dtype) * d ** -0.5,
        "w_gate": jax.random.normal(ks[1], (d, di), cfg.dtype) * d ** -0.5,
        # B/C are group-shared across heads (n_groups=1, as in Mamba2)
        "w_bc": jax.random.normal(ks[2], (d, 2 * S), cfg.dtype)
        * d ** -0.5,
        "w_dt": jax.random.normal(ks[3], (d, H), cfg.dtype) * d ** -0.5,
        "a_log": jnp.zeros((H,), jnp.float32),
        "skip": jnp.ones((H,), jnp.float32) * 0.1,   # D residual term
        "w_out": jax.random.normal(ks[5], (di, d), cfg.dtype) * di ** -0.5,
    }


def spec_ssm(cfg) -> Specs:
    return {"w_in": (EMBED, SSM_IN), "w_gate": (EMBED, SSM_IN),
            "w_bc": (EMBED, None), "w_dt": (EMBED, None),
            "a_log": (None,), "skip": (None,), "w_out": (SSM_IN, EMBED)}


def ssm_block(p, x, cfg, *, state=None, layer=None, return_state=False):
    """Mamba2 SSD block. Decode: ``state`` is the layer-stacked
    ``[R, B, H, S, P]`` state; returns it updated in place at ``layer``.

    ``return_state`` (prefill): also returns the final state, computed in
    closed form h_T = sum_s exp(cum_T - cum_s) b_s x_s^T (weights <= 1, so
    numerically stable for arbitrary T).
    """
    B, T, D = x.shape
    H, S = cfg.ssm_heads, cfg.ssm_state
    P = cfg.ssm_d_inner // H
    u = dense(x, p["w_in"]).reshape(B, T, H, P)
    z = dense(x, p["w_gate"])                                  # [B, T, di]
    bc = dense(x, p["w_bc"])                                   # [B, T, 2S]
    b, c = bc[..., :S], bc[..., S:]                            # [B, T, S]
    dt = jax.nn.softplus(dense(x, p["w_dt"]).astype(jnp.float32))  # [B,T,H]
    a = -jnp.exp(p["a_log"])[None, None, :] * dt               # log-decay <0
    xin = u * dt[..., None].astype(u.dtype)

    if state is None:
        y = ops.ssd(xin, a, b, c, use_pallas=cfg.use_pallas,
                    chunk=cfg.ssm_chunk, unroll=cfg.scan_unroll)
        new_state = None
        if return_state:
            cum = jnp.cumsum(a, axis=1)                        # [B, T, H]
            w = jnp.exp(cum[:, -1:, :] - cum)                  # [B, T, H]
            new_state = jnp.einsum(
                "bth,bts,bthp->bhsp", w,
                b.astype(jnp.float32), xin.astype(jnp.float32))
    else:
        # single-step recurrence (T == 1)
        at = jnp.exp(a[:, 0]).astype(jnp.float32)              # [B, H]
        st = lax.dynamic_index_in_dim(state, layer, 0, False)
        st = st * at[..., None, None] + jnp.einsum(
            "bs,bhp->bhsp", b[:, 0].astype(jnp.float32),
            xin[:, 0].astype(jnp.float32))
        new_state = lax.dynamic_update_index_in_dim(state, st, layer, 0)
        # read the row back from the written state: the old state then
        # has no reader after the write, so the write stays in place
        st = lax.dynamic_index_in_dim(new_state, layer, 0, False)
        y = jnp.einsum("bs,bhsp->bhp", c[:, 0].astype(jnp.float32),
                       st)[:, None].astype(x.dtype)
    y = y + xin * p["skip"][None, None, :, None].astype(u.dtype)
    y = y.reshape(B, T, H * P) * jax.nn.silu(z)
    return dense(y, p["w_out"]), new_state


# --------------------------------------------------------------------- #
# the published Mamba2 block (mamba_ssm's ``Mamba2``, arXiv:2405.21060 §7)
# --------------------------------------------------------------------- #
def init_mamba2(key, cfg) -> Params:
    """One fused input projection ``[z | xBC | dt]``, a depthwise causal
    conv over ``xBC`` (with bias), per-head ``dt_bias``, ``A = -exp(a_log)``
    and ``D``, the gated RMSNorm's scale (``1 + norm``) and ``out_proj``."""
    d, di, H = cfg.d_model, cfg.ssm_d_inner, cfg.ssm_heads
    conv = di + 2 * cfg.ssm_groups * cfg.ssm_state
    K = cfg.ssm_conv
    ks = jax.random.split(key, 4)
    lim = K ** -0.5
    return {
        "w_in": jax.random.normal(ks[0], (d, di + conv + H), cfg.dtype)
        * d ** -0.5,
        "conv_w": jax.random.uniform(ks[1], (K, conv), cfg.dtype, -lim, lim),
        "conv_b": jax.random.uniform(ks[2], (conv,), cfg.dtype, -lim, lim),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "a_log": jnp.zeros((H,), jnp.float32),
        "d_skip": jnp.ones((H,), jnp.float32),
        "norm": jnp.zeros((di,), jnp.float32),
        "w_out": jax.random.normal(ks[3], (di, d), cfg.dtype) * di ** -0.5,
    }


def spec_mamba2(cfg) -> Specs:
    return {"w_in": (EMBED, None), "conv_w": (None, None),
            "conv_b": (None,), "dt_bias": (None,), "a_log": (None,),
            "d_skip": (None,), "norm": (None,), "w_out": (SSM_IN, EMBED)}


def mamba2_block(p, x, cfg, *, cache=None, layer=None, return_state=False):
    """The Mamba2 mixer on normed ``x [B, T, D]``.

    Decode (``cache`` set, T == 1): ``cache`` holds the layer-stacked
    f32 SSM state ``[R, B, H, P, S]`` and conv window ``[R, B, K-1, C]``
    (the last K-1 pre-conv ``xBC`` rows); both are written in place at
    ``layer`` and returned whole. ``return_state`` (prefill): also
    returns this layer's final state and conv window (left-padded with
    zeros for prompts shorter than K-1)."""
    B, T, _ = x.shape
    H, S, G, K = cfg.ssm_heads, cfg.ssm_state, cfg.ssm_groups, cfg.ssm_conv
    di = cfg.ssm_d_inner
    P = di // H
    f32 = jnp.float32
    z, xbc, dt = jnp.split(dense(x, p["w_in"]), [di, 2 * di + 2 * G * S],
                           axis=-1)
    new_cache = None
    with jax.named_scope("conv"):
        w = p["conv_w"].astype(f32)
        if cache is None:
            pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
            xc = sum(pad[:, k:k + T].astype(f32) * w[k] for k in range(K))
            window = pad[:, T:]
        else:
            conv = cache["conv"]
            win = lax.dynamic_index_in_dim(conv, layer, 0, False)
            full = jnp.concatenate([win, xbc.astype(conv.dtype)], axis=1)
            conv = lax.dynamic_update_index_in_dim(conv, full[:, 1:], layer,
                                                   0)
            xc = jnp.sum(full.astype(f32) * w, axis=1, keepdims=True)
        xc = jax.nn.silu(xc + p["conv_b"].astype(f32))
    xs, b, c = jnp.split(xc, [di, di + G * S], axis=-1)
    xs = xs.reshape(B, T, G, H // G, P)
    b, c = b.reshape(B, T, G, S), c.reshape(B, T, G, S)
    dt = jax.nn.softplus(dt.astype(f32) + p["dt_bias"])           # [B,T,H]
    a = -jnp.exp(p["a_log"]) * dt                                  # log-decay
    xin = xs * dt.reshape(B, T, G, H // G, 1)
    with jax.named_scope("ssd"):
        if cache is None:
            if G == 1:
                bb, cc = b[:, :, 0], c[:, :, 0]                     # [B,T,S]
            else:
                bb, cc = (jnp.repeat(v, H // G, axis=2) for v in (b, c))
            y = ops.ssd(xin.reshape(B, T, H, P), a, bb, cc,
                        use_pallas=cfg.use_pallas, chunk=cfg.ssm_chunk,
                        unroll=cfg.scan_unroll)
            y = y.reshape(B, T, G, H // G, P)
            if return_state:
                cum = jnp.cumsum(a, axis=1)
                wt = jnp.exp(cum[:, -1:] - cum).reshape(B, T, G, H // G)
                st = jnp.einsum("btgh,btgs,btghp->bghps", wt, b, xin)
                new_cache = {"state": st.reshape(B, H, P, S),
                             "conv": window}
        else:
            state, y = ops.ssm_state_step(
                cache["state"], layer, jnp.exp(a[:, 0]),
                xin[:, 0].reshape(B, H, P), b[:, 0], c[:, 0])
            y = y.reshape(B, 1, G, H // G, P)
            new_cache = {"state": state, "conv": conv}
    y = y + xs * p["d_skip"].reshape(G, H // G, 1)
    with jax.named_scope("gate_norm"):
        g = y.reshape(B, T, di) * jax.nn.silu(z.astype(f32))
        g = g.reshape(B, T, G, di // G)
        g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                          + cfg.norm_eps)
        g = (g.reshape(B, T, di) * (1.0 + p["norm"])).astype(x.dtype)
    return dense(g, p["w_out"]), new_cache
