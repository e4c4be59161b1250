"""Model assembly: decoder-only / MoE / SSM / hybrid / enc-dec LMs.

One code path serves all eleven architectures, driven by
``ModelConfig.pattern`` (the repeating sublayer unit) with ``lax.scan``
over the ``repeats`` axis and optional per-unit remat. Entry points:

    init_params(cfg, key)                  -> params pytree
    param_specs(cfg)                       -> matching logical-axis pytree
    train_loss(cfg, params, batch)         -> (loss, metrics)
    prefill(cfg, params, batch)            -> (last_logits, cache)
    init_cache(cfg, B, T)                  -> zeroed cache pytree
    decode_step(cfg, params, cache, tokens, cache_index)
                                           -> (logits, new_cache)
    init_paged_cache(cfg, slots, n_pages, page_size, pages_per_slot)
                                           -> paged cache (DESIGN.md §13)
    admit_prefill(cfg, paged, prefill_cache, pages, slot)
                                           -> paged cache with the slot
                                              loaded from a B=1 prefill

``cache_index`` may be a scalar (dense cache, uniform position) or a
per-row ``[B]`` vector (paged cache, ragged positions; ``-1`` routes a
finished row's writes to the trash page).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs import ModelConfig
from repro.launch.partitioning import constrain
from . import layers as L

Params = Any


# --------------------------------------------------------------------- #
# structure helpers
# --------------------------------------------------------------------- #
def slot_names(cfg: ModelConfig) -> list[str]:
    return [f"{i}_{kind}" for i, kind in enumerate(cfg.pattern)]


def _init_ffn(key, cfg) -> Params:
    """``norm2`` and the MLP (or MoE) that follows a mixer."""
    ffn = L.init_moe(key, cfg) if cfg.n_experts else L.init_mlp(key, cfg)
    return {"norm2": jnp.zeros((cfg.d_model,), jnp.float32),
            "moe" if cfg.n_experts else "mlp": ffn}


def _spec_ffn(cfg) -> Any:
    if cfg.n_experts:
        return {"norm2": (None,), "moe": L.spec_moe(cfg)}
    return {"norm2": (None,), "mlp": L.spec_mlp(cfg)}


def _init_slot(key, cfg, kind: str) -> Params:
    d = cfg.d_model
    z = jnp.zeros((d,), jnp.float32)
    if kind in ("attn", "local", "shared_attn"):
        k1, k2, k3 = jax.random.split(key, 3)
        p = {"norm1": z, "attn": L.init_attention(k1, cfg),
             **_init_ffn(k2, cfg)}
        if cfg.family == "encdec" and kind == "attn":
            p["norm_x"] = z
            p["cross"] = L.init_attention(k3, cfg)
        return p
    if kind == "ssm":
        return {"norm": z, "ssm": L.init_ssm(key, cfg)}
    if kind == "mamba2":
        p = {"norm": z, "mixer": L.init_mamba2(key, cfg)}
        if cfg.d_ff:
            p.update(_init_ffn(jax.random.fold_in(key, 1), cfg))
        return p
    raise ValueError(f"unknown sublayer kind {kind!r}")


def _spec_slot(cfg, kind: str) -> Any:
    if kind in ("attn", "local", "shared_attn"):
        p = {"norm1": (None,), "attn": L.spec_attention(cfg),
             **_spec_ffn(cfg)}
        if cfg.family == "encdec" and kind == "attn":
            p["norm_x"] = (None,)
            p["cross"] = L.spec_attention(cfg)
        return p
    if kind == "ssm":
        return {"norm": (None,), "ssm": L.spec_ssm(cfg)}
    if kind == "mamba2":
        p = {"norm": (None,), "mixer": L.spec_mamba2(cfg)}
        if cfg.d_ff:
            p.update(_spec_ffn(cfg))
        return p
    raise ValueError(kind)


def init_params(cfg: ModelConfig, key) -> Params:
    keys = jax.random.split(key, 8)
    d, V = cfg.d_model, cfg.vocab_padded
    params: dict = {
        "embed": jax.random.normal(keys[0], (V, d), cfg.jdtype) * d ** -0.5,
        "norm_f": jnp.zeros((d,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["out"] = jax.random.normal(keys[1], (d, V),
                                          cfg.jdtype) * d ** -0.5
    blocks = {}
    for i, (name, kind) in enumerate(zip(slot_names(cfg), cfg.pattern)):
        if kind == "shared_attn":
            continue  # lives in params['shared']
        sub = jax.random.split(jax.random.fold_in(keys[2], i), cfg.repeats)
        blocks[name] = jax.vmap(
            lambda k: _init_slot(k, cfg, kind))(sub)
    params["blocks"] = blocks
    if "shared_attn" in cfg.pattern:
        params["shared"] = _init_slot(keys[3], cfg, "shared_attn")
    if cfg.n_enc_layers:
        enc_cfg = cfg
        sub = jax.random.split(keys[4], cfg.n_enc_layers)
        params["enc"] = {
            "blocks": jax.vmap(
                lambda k: _init_slot(k, enc_cfg, "attn")
                if cfg.family != "encdec"
                else {kk: vv for kk, vv in _init_slot(
                    k, enc_cfg.replace(family="dense"), "attn").items()}
            )(sub),
            "norm": jnp.zeros((d,), jnp.float32),
        }
    if cfg.frontend:
        params["front"] = {
            "w": jax.random.normal(keys[5], (cfg.frontend_dim, d),
                                   cfg.jdtype) * cfg.frontend_dim ** -0.5}
    return params


def param_specs(cfg: ModelConfig) -> Any:
    specs: dict = {"embed": (L.VOCAB, L.EMBED), "norm_f": (None,)}
    if not cfg.tie_embeddings:
        specs["out"] = (L.EMBED, L.VOCAB)
    blocks = {}
    for name, kind in zip(slot_names(cfg), cfg.pattern):
        if kind == "shared_attn":
            continue
        # leading scan axis is unsharded -> prepend None
        blocks[name] = jax.tree.map(
            lambda ax: (None,) + tuple(ax), _spec_slot(cfg, kind),
            is_leaf=lambda x: isinstance(x, tuple))
    specs["blocks"] = blocks
    if "shared_attn" in cfg.pattern:
        specs["shared"] = _spec_slot(cfg, "shared_attn")
    if cfg.n_enc_layers:
        specs["enc"] = {
            "blocks": jax.tree.map(
                lambda ax: (None,) + tuple(ax),
                _spec_slot(cfg.replace(family="dense"), "attn"),
                is_leaf=lambda x: isinstance(x, tuple)),
            "norm": (None,),
        }
    if cfg.frontend:
        specs["front"] = {"w": (None, L.EMBED)}
    return specs


# --------------------------------------------------------------------- #
# sublayer application
# --------------------------------------------------------------------- #
def _norm(cfg, x, scale):
    """RMSNorm at the config's epsilon, in the compute dtype (the
    residual stream ``x`` may be f32 under ``residual_in_fp32``)."""
    return L.rms_norm(x, scale, cfg.norm_eps).astype(cfg.jdtype)


def _add(cfg, x, h):
    """The residual add of a sublayer's output ``h``, scaled first by
    ``residual_multiplier`` where the config has one, and reduce-scattered
    into the sequence-parallel layout."""
    if cfg.residual_multiplier is not None:
        h = h * jnp.asarray(cfg.residual_multiplier, h.dtype)
    return x + constrain(h, ("batch", "seq", "embed"))


def _ffn(cfg, p, x):
    """``norm2`` and the MLP (or MoE) after a mixer: (x, aux)."""
    with jax.named_scope("mlp"):
        h = _norm(cfg, x, p["norm2"])
        if cfg.n_experts:
            h, aux = L.moe_block(p["moe"], h, cfg)
        else:
            h, aux = L.mlp_block(p["mlp"], h, cfg), jnp.zeros((), jnp.float32)
        return _add(cfg, x, h), aux


def _apply_slot(cfg, kind, p, x, positions, *, memory=None, cache=None,
                cache_index=None, layer=None, mode="train"):
    """Returns (x, new_cache_entry, aux).

    ``layer`` set (decode): ``cache`` is the slot's whole stacked
    ``[R, ...]`` entry, read and written in place at that index."""
    aux = jnp.zeros((), jnp.float32)
    if kind in ("attn", "local", "shared_attn"):
        window = cfg.local_window if kind == "local" else cfg.window
        # named scopes: a profile splits the step's device time by them
        with jax.named_scope("attn"):
            h = _norm(cfg, x, p["norm1"])
            attn_cache = cache.get("self") if cache else None
            h, new_self = L.attention_block(
                p["attn"], h, positions, cfg, window=window,
                softcap=cfg.attn_softcap, causal=(mode != "encoder"),
                cache=attn_cache, cache_index=cache_index, layer=layer)
            # reduce-scatter the row-parallel output into the SP layout
            x = _add(cfg, x, h)
            new_cross = None
            if (cfg.family == "encdec" and kind == "attn"
                    and mode != "encoder"):
                h = _norm(cfg, x, p["norm_x"])
                if cache is not None and "cross" in cache:
                    # decode: attend to the prefilled cross k/v directly
                    # (read-only: this layer's rows of the stacked cache)
                    ck = {n: c[layer] for n, c in cache["cross"].items()}
                    B = x.shape[0]
                    q = L.dense(h, p["cross"]["wq"]).reshape(
                        B, x.shape[1], cfg.n_heads, cfg.hd
                    ).transpose(0, 2, 1, 3)
                    from repro.kernels import ops
                    o = ops.attention(q, ck["k"], ck["v"], causal=False,
                                      use_pallas=cfg.use_pallas)
                    o = o.transpose(0, 2, 1, 3).reshape(B, x.shape[1], -1)
                    h = L.dense(o, p["cross"]["wo"])
                    new_cross = cache["cross"]
                else:
                    h, _ = L.attention_block(p["cross"], h, positions, cfg,
                                             causal=False, memory=memory)
                x = _add(cfg, x, h)
        x, aux = _ffn(cfg, p, x)
        new_cache = None
        if cache is not None:
            new_cache = {"self": new_self}
            if new_cross is not None:
                new_cache["cross"] = new_cross
        return x, new_cache, aux
    if kind == "ssm":
        with jax.named_scope("ssm"):
            h = _norm(cfg, x, p["norm"])
            if mode == "prefill":
                h, new_state = L.ssm_block(p["ssm"], h, cfg, state=None,
                                           return_state=True)
                new_cache = {"state": new_state}
            elif cache is None:
                h, _ = L.ssm_block(p["ssm"], h, cfg)
                new_cache = None
            else:
                h, new_state = L.ssm_block(p["ssm"], h, cfg,
                                           state=cache["state"], layer=layer)
                new_cache = {"state": new_state}
            return _add(cfg, x, h), new_cache, aux
    if kind == "mamba2":
        # scopes inside: conv, ssd, gate_norm (layers.mamba2_block)
        with jax.named_scope("ssm"):
            h, new_cache = L.mamba2_block(
                p["mixer"], _norm(cfg, x, p["norm"]), cfg, cache=cache,
                layer=layer, return_state=(mode == "prefill"))
            x = _add(cfg, x, h)
        if cfg.d_ff:
            x, aux = _ffn(cfg, p, x)
        return x, new_cache, aux
    raise ValueError(kind)


def _unit(cfg, params, shared, x, positions, *, cache=None,
          cache_index=None, layer=None, mode="train"):
    """Apply one repetition of the pattern. cache: dict slot->entry."""
    aux = jnp.zeros((), jnp.float32)
    new_cache = {} if cache is not None else None
    for name, kind in zip(slot_names(cfg), cfg.pattern):
        p = shared if kind == "shared_attn" else params[name]
        c = cache.get(name) if cache is not None else None
        x, nc, a = _apply_slot(cfg, kind, p, x, positions, cache=c,
                               cache_index=cache_index, layer=layer,
                               mode=mode)
        aux = aux + a
        if new_cache is not None:
            new_cache[name] = nc
    x = constrain(x, ("batch", "seq", "embed"))
    return x, new_cache, aux


def _scan_units(cfg, params, x, positions, *, cache=None, cache_index=None,
                mode="train"):
    """lax.scan over the pattern repetitions, optional per-unit remat.

    A decode cache rides the scan's carry whole (``[R, ...]``) and layer
    ``l`` writes its own rows in place at index ``l``: as the scan's
    ``xs``/``ys`` it would be sliced per layer and stacked back into a
    fresh array, rewriting (and XLA then copying) the whole cache every
    step to change one row per layer."""
    shared = params.get("shared")

    def body(carry, xs):
        x, aux, cache = carry
        blk, layer = xs
        x, cache, a = _unit(cfg, blk, shared, x, positions, cache=cache,
                            cache_index=cache_index, layer=layer, mode=mode)
        return (x, aux + a, cache), None

    if cfg.remat == "block" and cache is None:
        body = jax.checkpoint(body)      # training: no backward in decode
    (x, aux, cache), _ = lax.scan(
        body, (x, jnp.zeros((), jnp.float32), cache),
        (params["blocks"], jnp.arange(cfg.repeats)),
        unroll=cfg.repeats if cfg.scan_unroll else 1)
    return x, cache, aux


# --------------------------------------------------------------------- #
# embedding / logits / loss
# --------------------------------------------------------------------- #
def _embed_tokens(cfg, params, tokens):
    """Embedding rows of ``tokens``, scaled as the config says."""
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.scale_embed:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if cfg.embedding_multiplier is not None:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    return x


def _embed(cfg, params, batch):
    x = _embed_tokens(cfg, params, batch["tokens"])
    if cfg.frontend == "vit":
        patches = L.dense(batch["patches"], params["front"]["w"])
        pl_ = patches.shape[1]
        x = jnp.concatenate([patches.astype(x.dtype), x[:, pl_:]], axis=1)
    x = constrain(_residual(cfg, x), ("batch", "seq", "embed"))
    return x


def _residual(cfg, x):
    """The residual stream's dtype: f32 under ``residual_in_fp32``."""
    return x.astype(jnp.float32) if cfg.residual_in_fp32 else x


def _encoder(cfg, params, frames):
    """Bidirectional encoder over (stub-projected) frame features."""
    x = L.dense(frames, params["front"]["w"])
    positions = jnp.arange(x.shape[1])
    shared = None

    def body(carry, blk):
        h, _ = carry
        h, _, _ = _unit(cfg.replace(pattern=("attn",), family="dense"),
                        {"0_attn": blk}, shared, h, positions,
                        mode="encoder")
        return (h, jnp.zeros(())), None

    bodyf = jax.checkpoint(body) if cfg.remat == "block" else body
    (x, _), _ = lax.scan(bodyf, (x, jnp.zeros(())),
                         params["enc"]["blocks"],
                         unroll=cfg.n_enc_layers if cfg.scan_unroll else 1)
    return L.rms_norm(x, params["enc"]["norm"], cfg.norm_eps)


def _logits(cfg, params, x):
    out_w = params["embed"].T if cfg.tie_embeddings else params["out"]
    lg = L.dense(x, out_w).astype(jnp.float32)
    if cfg.logits_scaling is not None:
        lg = lg / cfg.logits_scaling
    if cfg.final_softcap:
        lg = cfg.final_softcap * jnp.tanh(lg / cfg.final_softcap)
    return lg


def _chunked_loss(cfg, params, x, labels):
    """Cross-entropy with seq-chunked logits (memory: O(chunk * vocab))."""
    B, T, D = x.shape
    C = min(cfg.loss_chunk, T)
    assert T % C == 0
    xc = x.reshape(B, T // C, C, D).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, T // C, C).transpose(1, 0, 2)

    def chunk(carry, xs):
        xi, li = xs
        lg = _logits(cfg, params, xi)
        # sharding-friendly: mask vocab padding (no uneven slice), gold
        # logit via one-hot contraction (no cross-shard gather) — both
        # keep the vocab axis sharded; only [B, C] scalars cross shards.
        vocab_ids = jax.lax.broadcasted_iota(jnp.int32, lg.shape,
                                             lg.ndim - 1)
        lg = jnp.where(vocab_ids < cfg.vocab, lg, -1e30)
        valid = li >= 0
        li = jnp.maximum(li, 0)
        m = jnp.max(lg, axis=-1)
        lse = m + jnp.log(jnp.sum(jnp.exp(lg - m[..., None]), axis=-1))
        gold = jnp.sum(jnp.where(vocab_ids == li[..., None], lg, 0.0),
                       axis=-1)
        nll = jnp.where(valid, lse - gold, 0.0)
        return (carry[0] + nll.sum(), carry[1] + valid.sum()), None

    # remat the chunk: recompute the [B, C, vocab] logits in the backward
    # instead of saving them (vocab-sized activations dominate otherwise)
    chunk_fn = jax.checkpoint(chunk) if cfg.remat == "block" else chunk
    (tot, cnt), _ = lax.scan(chunk_fn, (jnp.zeros((), jnp.float32),
                                        jnp.zeros((), jnp.int32)),
                             (xc, lc),
                             unroll=(T // C) if cfg.scan_unroll else 1)
    return tot / jnp.maximum(cnt, 1)


# --------------------------------------------------------------------- #
# public entry points
# --------------------------------------------------------------------- #
def train_loss(cfg: ModelConfig, params, batch):
    """batch: tokens, labels (+ patches/frames for vlm/audio)."""
    if cfg.family == "encdec":
        memory = _encoder(cfg, params, batch["frames"])
        x = _embed(cfg, params, batch)
        positions = jnp.arange(x.shape[1])

        # decoder units need the encoder memory for cross-attention: close
        # over it (memory is an invariant of the scan).
        def body_mem(carry, blk):
            h, aux = carry
            h2 = h
            for name, kind in zip(slot_names(cfg), cfg.pattern):
                h2, _, a = _apply_slot(cfg, kind, blk[name], h2, positions,
                                       memory=memory, mode="train")
                aux = aux + a
            h2 = constrain(h2, ("batch", "seq", "embed"))
            return (h2, aux), None

        bodyf = jax.checkpoint(body_mem) if cfg.remat == "block" \
            else body_mem
        (x, aux), _ = lax.scan(bodyf, (x, jnp.zeros(())), params["blocks"],
                               unroll=cfg.repeats if cfg.scan_unroll else 1)
    else:
        x = _embed(cfg, params, batch)
        positions = jnp.arange(x.shape[1])
        x, _, aux = _scan_units(cfg, params, x, positions, mode="train")
    x = _norm(cfg, x, params["norm_f"])
    loss = _chunked_loss(cfg, params, x, batch["labels"])
    if cfg.n_experts:
        loss = loss + 0.01 * aux / cfg.n_layers
    return loss, {"loss": loss, "moe_aux": aux}


def init_cache(cfg: ModelConfig, B: int, T: int):
    """Zeroed decode cache (also the dry-run ShapeDtypeStruct template)."""
    R, hkv, hd = cfg.repeats, cfg.n_kv_heads, cfg.hd
    cache = {}
    for name, kind in zip(slot_names(cfg), cfg.pattern):
        if kind in ("attn", "local", "shared_attn"):
            ent = {"self": {
                "k": jnp.zeros((R, B, hkv, T, hd), cfg.jdtype),
                "v": jnp.zeros((R, B, hkv, T, hd), cfg.jdtype)}}
            if cfg.family == "encdec" and kind == "attn":
                ent["cross"] = {
                    "k": jnp.zeros((R, B, hkv, T, hd), cfg.jdtype),
                    "v": jnp.zeros((R, B, hkv, T, hd), cfg.jdtype)}
            cache[name] = ent
        elif kind in ("ssm", "mamba2"):
            cache[name] = _recurrent_state(cfg, kind, R, B)
    return cache


def _recurrent_state(cfg, kind, R, B):
    """Zeroed recurrent state of ``B`` rows: the f32 SSM state, and for
    ``mamba2`` the conv window of the last ``ssm_conv - 1`` pre-conv
    ``xBC`` rows (DESIGN.md §18)."""
    H, S, P = cfg.ssm_heads, cfg.ssm_state, cfg.ssm_d_inner // cfg.ssm_heads
    # mamba2 keeps the state dim minor: [.., P, S] fills the chip's
    # 128-lane tiles where [.., S, P] pads P = 64 to 128
    shape = (R, B, H, S, P) if kind == "ssm" else (R, B, H, P, S)
    ent = {"state": jnp.zeros(shape, jnp.float32)}
    if kind == "mamba2":
        conv = cfg.ssm_d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
        ent["conv"] = jnp.zeros((R, B, cfg.ssm_conv - 1, conv), cfg.jdtype)
    return ent


def init_paged_cache(cfg: ModelConfig, slots: int, n_pages: int,
                     page_size: int, pages_per_slot: int):
    """Zeroed paged decode cache (DESIGN.md §13).

    Attention k/v live in ONE physical page pool ``[R, P, Hkv, page,
    Dh]`` shared by every batch slot; ``pages`` ``[R, slots, npp]`` is
    the per-slot page table (replicated over the layer axis, read by
    layer ``l`` as ``pages[l]``; int32, ~nothing).
    Physical page 0 is reserved as the trash page — finished rows write
    there and the allocator never hands it out. SSM state (and the
    ``mamba2`` conv window beside it) is recurrent (no sequence axis), so
    it stays a per-slot row ``[R, slots, ...]`` and is simply overwritten
    at admission.
    """
    if cfg.family == "encdec":
        raise NotImplementedError(
            "paged decode does not support enc-dec cross caches; use "
            "the legacy generate() path")
    R, hkv, hd = cfg.repeats, cfg.n_kv_heads, cfg.hd
    cache = {}
    for name, kind in zip(slot_names(cfg), cfg.pattern):
        if kind in ("attn", "local", "shared_attn"):
            # NOTE: each layer gets its OWN page-table buffer — sharing
            # one array across layers would put the same buffer in the
            # pytree twice and break jit argument donation
            cache[name] = {"self": {
                "k": jnp.zeros((R, n_pages, hkv, page_size, hd),
                               cfg.jdtype),
                "v": jnp.zeros((R, n_pages, hkv, page_size, hd),
                               cfg.jdtype),
                "pages": jnp.zeros((R, slots, pages_per_slot),
                                   jnp.int32)}}
        elif kind in ("ssm", "mamba2"):
            cache[name] = _recurrent_state(cfg, kind, R, slots)
    return cache


def admit_prefill(cfg: ModelConfig, paged, prefill_cache, pages, slot):
    """Scatter a ``B=1`` prefill cache into the paged pool (DESIGN.md
    §13).

    ``prefill_cache`` comes from :func:`prefill` with
    ``max_len = n * page_size`` (so its sequence axis splits into whole
    pages); ``pages`` is the slot's FULL page-table row ``[npp]`` whose
    first ``n`` entries are the allocated physical pages (the rest point
    at the trash page 0 and are never valid under the length mask);
    ``slot`` is the (traced) batch-slot index. Pure data movement —
    every cached byte lands bit-identical in its page.
    """
    new = {}
    for name, kind in zip(slot_names(cfg), cfg.pattern):
        if kind in ("attn", "local", "shared_attn"):
            ent, src = paged[name]["self"], prefill_cache[name]["self"]
            ps = ent["k"].shape[3]
            R, _, hkv, Tp, hd = src["k"].shape
            assert Tp % ps == 0, (Tp, ps)
            npg = Tp // ps
            out = {}
            for key in ("k", "v"):
                blocks = src[key][:, 0].reshape(R, hkv, npg, ps, hd)
                blocks = blocks.transpose(0, 2, 1, 3, 4)
                out[key] = ent[key].at[:, pages[:npg]].set(blocks)
            out["pages"] = ent["pages"].at[:, slot].set(pages)
            new[name] = {"self": out}
        elif kind in ("ssm", "mamba2"):
            new[name] = {k: v.at[:, slot].set(prefill_cache[name][k][:, 0])
                         for k, v in paged[name].items()}
    return new


def cache_specs(cfg: ModelConfig):
    """Logical axes for the cache: batch over data, cache SEQUENCE over
    model (flash-decode style — kv-head counts are often < the model
    axis, the sequence always divides it)."""
    spec = {}
    for name, kind in zip(slot_names(cfg), cfg.pattern):
        if kind in ("attn", "local", "shared_attn"):
            kv = {"k": (None, "batch", None, "seq_kv", None),
                  "v": (None, "batch", None, "seq_kv", None)}
            ent = {"self": kv}
            if cfg.family == "encdec" and kind == "attn":
                ent["cross"] = dict(kv)
            spec[name] = ent
        elif kind in ("ssm", "mamba2"):
            spec[name] = {"state": (None, "batch", "ssm_heads", None,
                                    None)}
            if kind == "mamba2":
                spec[name]["conv"] = (None, "batch", None, None)
    return spec


def prefill(cfg: ModelConfig, params, batch, max_len: int | None = None):
    """Forward pass that also writes the KV/state caches.

    Implemented as decode-mode scan with T-length writes at index 0.
    ``max_len`` sizes the cache for subsequent decode_step calls."""
    x = _embed(cfg, params, batch)
    B, T = x.shape[:2]
    positions = jnp.arange(T)
    cache = init_cache(cfg, B, max_len or T)
    memory = None
    if cfg.family == "encdec":
        memory = _encoder(cfg, params, batch["frames"])
        # fill cross k/v once per layer below via _apply_slot(memory=...)
    x, new_cache, _ = _prefill_scan(cfg, params, x, positions, cache,
                                    memory)
    x = _norm(cfg, x, params["norm_f"])
    logits = _logits(cfg, params, x[:, -1:])
    return logits, new_cache


def _prefill_scan(cfg, params, x, positions, cache, memory):
    shared = params.get("shared")

    def body(carry, xs):
        h = carry
        blk, cache_sl = xs
        new_c = {}
        for name, kind in zip(slot_names(cfg), cfg.pattern):
            p = shared if kind == "shared_attn" else blk[name]
            c = cache_sl.get(name)
            if kind in ("attn", "local", "shared_attn"):
                h, nc, _ = _apply_slot(
                    cfg, kind, p, h, positions, memory=memory,
                    cache={"self": c["self"]},
                    cache_index=jnp.zeros((), jnp.int32), mode="prefill")
                if cfg.family == "encdec" and kind == "attn":
                    # fill the cross k/v cache from the encoder memory
                    B, Ts = memory.shape[:2]
                    kx = L.dense(memory, p["cross"]["wk"]).reshape(
                        B, Ts, cfg.n_kv_heads, cfg.hd).transpose(0, 2, 1, 3)
                    vx = L.dense(memory, p["cross"]["wv"]).reshape(
                        B, Ts, cfg.n_kv_heads, cfg.hd).transpose(0, 2, 1, 3)
                    nc["cross"] = {"k": kx, "v": vx}
            else:
                h, nc, _ = _apply_slot(cfg, kind, p, h, positions,
                                       cache=None, mode="prefill")
            new_c[name] = nc
        h = constrain(h, ("batch", "seq", "embed"))
        return h, new_c

    bodyf = jax.checkpoint(body) if cfg.remat == "block" else body
    x, new_cache = lax.scan(bodyf, x, (params["blocks"], cache),
                            unroll=cfg.repeats if cfg.scan_unroll else 1)
    return x, new_cache, None


def decode_step(cfg: ModelConfig, params, cache, tokens, cache_index):
    """One serving step: tokens [B, 1] + cache -> logits [B, 1, V].

    ``cache_index`` is the write/attend position: a scalar (whole batch
    at one position — the classic right-aligned decode) or a ``[B]``
    vector of per-row positions for ragged continuous batching over a
    paged cache (DESIGN.md §13; -1 marks a finished/empty row whose
    write is routed to the trash page and whose keys are fully masked).
    """
    x = _residual(cfg, _embed_tokens(cfg, params, tokens))
    B = x.shape[0]
    ci = jnp.asarray(cache_index, jnp.int32)
    if ci.ndim == 1:
        positions = jnp.maximum(ci, 0)[:, None]          # [B, 1]
    else:
        positions = jnp.full((B, 1), ci, jnp.int32)
    x, new_cache, _ = _scan_units(cfg, params, x, positions, cache=cache,
                                  cache_index=ci, mode="decode")
    with jax.named_scope("head"):
        x = _norm(cfg, x, params["norm_f"])
        return _logits(cfg, params, x), new_cache


def poisoned_rows(logits, vocab: int):
    """Device-side poisoned-output sentinel (DESIGN.md §15).

    ``logits [..., V]`` -> bool ``[...]``: True where a row's next-token
    logits contain any non-finite value over the real (unpadded) vocab.
    Rows are independent through every decode op (attention, norms and
    sampling are all per-row), so a poisoned row never contaminates its
    batch siblings — the serving wave carries this mask to stop the bad
    slot exactly at its last clean token while the rest of the wave
    continues undisturbed.
    """
    return ~jnp.all(jnp.isfinite(logits[..., :vocab]), axis=-1)
