"""The Granite 4.0 hybrid decoder (transformers' ``granitemoehybrid`` with
no experts): Mamba2 and grouped-query attention layers in one stack, each
mixer followed by a SwiGLU MLP.

The same four things as ``dense_gqa``: the program's config fields,
random weights in the program's layout, the plain float32 reference
forward pass with its fp8 control, and the bytes and operations the
algorithm needs. On the residual stream ``x``:

    x = embed(tokens) * embedding_multiplier
    each layer (``layer_types``: "mamba" or "attention"):
        x += residual_multiplier * mixer(rmsnorm(x))
        x += residual_multiplier * swiglu(rmsnorm(x))
    logits = rmsnorm(x) E^T / logits_scaling

The attention mixer has no positional encoding (``nope``) and scores
``q k^T * attention_multiplier``; the Mamba2 mixer is
``bench/models/mamba2.py``'s (causal conv with bias, SSD with dt_bias, A
and D, RMSNorm gated by silu(z) in groups). Both are computed in blocks
of query rows, the SSD in its quadratic (masked attention-like) form,
which is exact for the recurrence. RMSNorm weights are ``1 + scale``, as
the program holds them. It imports nothing of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from bench.models.dense_gqa import BF16, F32, matmul, output_gap, rms_norm

#: query rows per block of the attention and the SSD (a [T, T, H] f32
#: decay tensor at T = 4,096 would be 4.3 GB)
BLOCK = 256
HIGHEST = lax.Precision.HIGHEST


def _period(kinds: tuple) -> tuple:
    """The shortest unit whose repetition gives ``kinds``."""
    n = len(kinds)
    for p in range(1, n + 1):
        if n % p == 0 and kinds == kinds[:p] * (n // p):
            return kinds[:p]
    return kinds


def dims(spec: dict) -> dict:
    d, hq = spec["hidden_size"], spec["num_attention_heads"]
    kinds = tuple("attn" if t == "attention" else "mamba2"
                  for t in spec["layer_types"])
    pattern = _period(kinds)
    di = spec["mamba_expand"] * d
    H, P = spec["mamba_n_heads"], spec["mamba_d_head"]
    assert H * P == di, (H, P, di)
    G, S = spec["mamba_n_groups"], spec["mamba_d_state"]
    conv = di + 2 * G * S
    return dict(d=d, L=len(kinds), kinds=kinds, pattern=pattern,
                R=len(kinds) // len(pattern), n_attn=kinds.count("attn"),
                n_mamba=kinds.count("mamba2"), hq=hq,
                hkv=spec["num_key_value_heads"],
                hd=spec.get("head_dim") or d // hq,
                f=spec["shared_intermediate_size"], di=di, H=H, P=P, S=S,
                G=G, K=spec["mamba_d_conv"], conv=conv, proj=di + conv + H,
                V=spec["vocab_size"], Vp=-(-spec["vocab_size"] // 128) * 128,
                tied=spec["tie_word_embeddings"])


def slot_names(m) -> list[str]:
    return [f"{i}_{kind}" for i, kind in enumerate(m["pattern"])]


def program_fields(spec: dict) -> dict:
    m = dims(spec)
    if spec["position_embedding_type"] != "nope":
        raise ValueError("the reference implements NoPE attention only")
    return dict(n_layers=m["L"], d_model=m["d"], n_heads=m["hq"],
                n_kv_heads=m["hkv"], head_dim=m["hd"], d_ff=m["f"],
                vocab=m["V"], pattern=m["pattern"], family="hybrid",
                rope_theta=None, attn_scale=spec["attention_multiplier"],
                embedding_multiplier=float(spec["embedding_multiplier"]),
                residual_multiplier=spec["residual_multiplier"],
                logits_scaling=float(spec["logits_scaling"]),
                tie_embeddings=m["tied"], dtype=spec["dtype"],
                residual_in_fp32=False, norm_eps=spec["rms_norm_eps"],
                ssm_state=m["S"], ssm_heads=m["H"], ssm_d_inner=m["di"],
                ssm_conv=m["K"], ssm_groups=m["G"])


# --------------------------------------------------------------------- #
# weights
# --------------------------------------------------------------------- #
def init_params(spec: dict, key):
    """Random weights in the program's layout, each slot of the pattern
    stacked over its ``R`` repeats. Projections normal with variance
    1/fan-in, the three that write into the residual stream (``wo``,
    ``w_out``, ``w_down``) by a further 1/sqrt(2 x layers), one residual
    add per mixer and per MLP, and by ``embedding_multiplier /
    residual_multiplier``: the scaled adds then stand to the scaled
    embedding as ``dense_gqa``'s adds to its embedding. Without that gain
    the tied head's logit of the current token dominates the rest, and
    the random model only repeats its input. The Mamba2 mixer as mamba_ssm
    initialises it (``A_log = log U[1, 16]``, dt log-uniform on [1e-3,
    1e-1] through ``dt_bias``, ``D = 1``, conv uniform within
    1/sqrt(d_conv), its norms at identity); the other norms ``1 + N(0,
    0.1^2)`` as ``dense_gqa``."""
    m = dims(spec)
    d, R, f, depth = m["d"], m["R"], m["f"], 2 * m["L"]
    gain = spec["embedding_multiplier"] / spec["residual_multiplier"]
    di, H, K, conv = m["di"], m["H"], m["K"], m["conv"]
    dt = jnp.dtype(spec["dtype"])
    k = iter(jax.random.split(key, 4 + 16 * len(m["pattern"])))

    def w(shape, fan_in, deep=1, g=1.0):  # drawn in the serving dtype
        return jax.random.normal(next(k), shape, dt) * jnp.asarray(
            g * (fan_in * deep) ** -0.5, dt)

    def norm(shape):
        return 0.1 * jax.random.normal(next(k), shape, jnp.float32)

    def uniform(shape, lo, hi, dtype=jnp.float32):
        return jax.random.uniform(next(k), shape, dtype, lo, hi)

    def mlp():
        return {"w_gate": w((R, d, f), d), "w_up": w((R, d, f), d),
                "w_down": w((R, f, d), f, depth, gain)}

    def attn():
        qw, kvw = m["hq"] * m["hd"], m["hkv"] * m["hd"]
        return {"wq": w((R, d, qw), d), "wk": w((R, d, kvw), d),
                "wv": w((R, d, kvw), d),
                "wo": w((R, qw, d), qw, depth, gain)}

    def mixer():
        step = jnp.exp(uniform((R, H), math.log(1e-3), math.log(1e-1)))
        lim = K ** -0.5
        return {"w_in": w((R, d, m["proj"]), d),
                "conv_w": uniform((R, K, conv), -lim, lim, dt),
                "conv_b": uniform((R, conv), -lim, lim, dt),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "a_log": jnp.log(uniform((R, H), 1.0, 16.0)),
                "d_skip": jnp.ones((R, H), jnp.float32),
                "norm": jnp.zeros((R, di), jnp.float32),
                "w_out": w((R, di, d), di, depth, gain)}

    blocks = {}
    for name, kind in zip(slot_names(m), m["pattern"]):
        if kind == "attn":
            blocks[name] = {"norm1": norm((R, d)), "attn": attn(),
                            "norm2": norm((R, d)), "mlp": mlp()}
        else:
            blocks[name] = {"norm": jnp.zeros((R, d), jnp.float32),
                            "mixer": mixer(), "norm2": norm((R, d)),
                            "mlp": mlp()}
    params = {"embed": w((m["Vp"], d), d), "norm_f": norm((d,)),
              "blocks": blocks}
    if not m["tied"]:
        params["out"] = w((d, m["Vp"]), d)
    return params


# --------------------------------------------------------------------- #
# the float32 reference and its fp8 control
# --------------------------------------------------------------------- #
def _blocks(T: int) -> int:
    blk = min(T, BLOCK)
    assert T % blk == 0, (T, blk)
    return blk


def _attention(q, k, v, scale):
    """Causal softmax attention of ``q [T, hq, hd]`` over GQA ``k``, ``v``
    ``[T, hkv, hd]``, queries in blocks of ``BLOCK`` rows."""
    T, hq, hd = q.shape
    rep = hq // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    blk = _blocks(T)

    def one(args):
        i, qi = args
        s = jnp.einsum("qhd,khd->hqk", qi, k, precision=HIGHEST) * scale
        qpos = i * blk + jnp.arange(blk)
        s = jnp.where(jnp.arange(T)[None, None, :] <= qpos[None, :, None],
                      s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                          precision=HIGHEST)

    out = lax.map(one, (jnp.arange(T // blk), q.reshape(T // blk, blk, hq,
                                                          hd)))
    return out.reshape(T, hq * hd)


def _ssd(xs, dt, a, b, c):
    """``y_t = sum_{s <= t} exp(cum_t - cum_s) (c_t . b_s) dt_s x_s`` for
    ``xs [T, H, P]``, ``dt``, ``a`` ``[T, H]`` (``a = A dt``), per-head
    ``b``, ``c`` ``[T, H, S]``; query rows in blocks of ``BLOCK``."""
    T, H, P = xs.shape
    blk = _blocks(T)
    cum = jnp.cumsum(a, axis=0)                                   # [T, H]
    u = xs * dt[..., None]

    def one(args):
        i, ci, cumi = args
        t = i * blk + jnp.arange(blk)
        diff = cumi[:, None, :] - cum[None, :, :]                 # [t, s, H]
        mask = (jnp.arange(T)[None, :] <= t[:, None])[..., None]
        decay = jnp.exp(jnp.where(mask, diff, -jnp.inf))
        cb = jnp.einsum("ths,khs->tkh", ci, b, precision=HIGHEST)
        return jnp.einsum("tkh,khp->thp", decay * cb, u, precision=HIGHEST)

    y = lax.map(one, (jnp.arange(T // blk),
                      c.reshape(T // blk, blk, H, -1),
                      cum.reshape(T // blk, blk, H)))
    return y.reshape(T, H, P)


def _mamba2(m, mx, h, eps, fp8):
    """The Mamba2 mixer on normed ``h [T, d]``."""
    di, H, P, S, G, K = m["di"], m["H"], m["P"], m["S"], m["G"], m["K"]
    T = h.shape[0]
    zxbcdt = matmul(h, mx["w_in"], fp8)
    z, xbc, dt = (zxbcdt[:, :di], zxbcdt[:, di:di + m["conv"]],
                  zxbcdt[:, di + m["conv"]:])
    pad = jnp.concatenate([jnp.zeros((K - 1, m["conv"])), xbc])
    cw = mx["conv_w"].astype(jnp.float32)
    xc = sum(pad[j:j + T] * cw[j] for j in range(K))
    xc = jax.nn.silu(xc + mx["conv_b"].astype(jnp.float32))
    group = jnp.arange(H) // (H // G)                             # head -> g
    xs = xc[:, :di].reshape(T, H, P)
    b = xc[:, di:di + G * S].reshape(T, G, S)[:, group]          # [T, H, S]
    c = xc[:, di + G * S:].reshape(T, G, S)[:, group]
    dt = jax.nn.softplus(dt + mx["dt_bias"])                      # [T, H]
    a = -jnp.exp(mx["a_log"])[None, :] * dt
    y = _ssd(xs, dt, a, b, c) + xs * mx["d_skip"][None, :, None]
    g = (y.reshape(T, di) * jax.nn.silu(z)).reshape(T, G, di // G)
    g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    g = g.reshape(T, di) * (1.0 + mx["norm"])
    return matmul(g, mx["w_out"], fp8)


def _attn(m, a, h, scale, fp8):
    """NoPE GQA attention on normed ``h [T, d]``."""
    T = h.shape[0]
    q = matmul(h, a["wq"], fp8).reshape(T, m["hq"], m["hd"])
    k = matmul(h, a["wk"], fp8).reshape(T, m["hkv"], m["hd"])
    v = matmul(h, a["wv"], fp8).reshape(T, m["hkv"], m["hd"])
    return matmul(_attention(q, k, v, scale), a["wo"], fp8)


def _mlp(g, h, fp8):
    u = jax.nn.silu(matmul(h, g["w_gate"], fp8)) * matmul(h, g["w_up"], fp8)
    return matmul(u, g["w_down"], fp8)


def hidden(spec: dict, params, tokens, fp8: bool = False):
    """Final normed hidden states ``[T, d]`` (float32) of one sequence
    ``tokens [T]``; T a multiple of ``BLOCK`` or at most it."""
    m = dims(spec)
    eps, rm = spec["rms_norm_eps"], spec["residual_multiplier"]
    scale = spec["attention_multiplier"]
    x = params["embed"][tokens].astype(jnp.float32) \
        * spec["embedding_multiplier"]

    def unit(x, p):
        for name, kind in zip(slot_names(m), m["pattern"]):
            q = p[name]
            if kind == "attn":
                mix = _attn(m, q["attn"], rms_norm(x, q["norm1"], eps),
                            scale, fp8)
            else:
                mix = _mamba2(m, q["mixer"], rms_norm(x, q["norm"], eps),
                              eps, fp8)
            x = x + rm * mix
            x = x + rm * _mlp(q["mlp"], rms_norm(x, q["norm2"], eps), fp8)
        return x, None

    x, _ = lax.scan(unit, x, params["blocks"])
    return rms_norm(x, params["norm_f"], eps)


def head(spec: dict, params, h, targets, fp8: bool = False):
    """``output_gap`` of the logits divided by ``logits_scaling`` (a
    positive scale: the best token is the same, the gap scales)."""
    m = dims(spec)
    gap, best = output_gap(params, h, targets, m["V"], m["tied"], fp8)
    return gap / spec["logits_scaling"], best


# --------------------------------------------------------------------- #
# the work a token needs
# --------------------------------------------------------------------- #
def _attn_matmul_params(m) -> int:
    return 2 * m["d"] * m["hq"] * m["hd"] + 2 * m["d"] * m["hkv"] * m["hd"]


def _mamba_matmul_params(m) -> int:
    return m["d"] * m["proj"] + m["di"] * m["d"]


def _mamba_conv_params(m) -> int:
    return (m["K"] + 1) * m["conv"]


def _mamba_vectors(m) -> int:
    """dt_bias, A_log, D and the gated norm's scale."""
    return 3 * m["H"] + m["di"]


def _matmul_params(m) -> int:
    """Every layer's projections and MLP (no head)."""
    return (m["n_attn"] * _attn_matmul_params(m)
            + m["n_mamba"] * _mamba_matmul_params(m)
            + m["L"] * 3 * m["d"] * m["f"])


def param_count(spec: dict) -> int:
    """Projections, MLPs, the mixers' conv and vectors, two norms a
    layer, the embedding (padded rows; tied head) and the final norm."""
    m = dims(spec)
    return (_matmul_params(m) + m["n_mamba"] * (_mamba_conv_params(m)
                                                + _mamba_vectors(m))
            + 2 * m["L"] * m["d"]
            + m["Vp"] * m["d"] * (1 if m["tied"] else 2) + m["d"])


def state_bytes_per_slot(spec: dict) -> int:
    """The f32 SSM state and the conv window (the last d_conv - 1
    pre-conv rows, in the serving dtype) of every Mamba2 layer."""
    m = dims(spec)
    return m["n_mamba"] * (m["H"] * m["S"] * m["P"] * F32
                           + (m["K"] - 1) * m["conv"] * BF16)


def kv_bytes_per_position(spec: dict) -> int:
    """Keys and values of one position in every attention layer."""
    m = dims(spec)
    return m["n_attn"] * 2 * m["hkv"] * m["hd"] * BF16


def weight_bytes_per_step(spec: dict) -> int:
    """Every matrix and conv once (bf16), the f32 vectors and norm
    scales, the output head over the real vocabulary; the embedding
    lookup's few rows are not counted."""
    m = dims(spec)
    mats = (_matmul_params(m) + m["n_mamba"] * _mamba_conv_params(m)
            + m["d"] * m["V"])
    vecs = m["n_mamba"] * _mamba_vectors(m) + (2 * m["L"] + 1) * m["d"]
    return mats * BF16 + vecs * F32


def decode_bytes(spec: dict, steps: int, rows) -> int:
    """Bytes the algorithm needs for ``steps`` decode steps over ``rows``
    of ``(length_before, tokens_emitted)``: the weights once a step; each
    live row's SSM state and conv window read and written once a token,
    and its valid keys and values once a token (its new k/v written
    included)."""
    tokens = sum(n for _, n in rows)
    keys = sum(n * L0 + n * (n + 1) // 2 for L0, n in rows)
    return (steps * weight_bytes_per_step(spec)
            + 2 * state_bytes_per_slot(spec) * tokens
            + kv_bytes_per_position(spec) * keys)


def _mixer_token_flops(m) -> int:
    """A Mamba2 layer's depthwise conv, state update (decay, outer
    product, add: 3HSP) and read-out (2HSP), beyond its projections."""
    return 2 * m["K"] * m["conv"] + 5 * m["H"] * m["S"] * m["P"]


def token_flops(spec: dict, ctx: int) -> int:
    """Operations of one decoded token that attends ``ctx`` positions."""
    m = dims(spec)
    return (2 * _matmul_params(m) + m["n_mamba"] * _mixer_token_flops(m)
            + 4 * m["n_attn"] * m["hq"] * m["hd"] * ctx
            + 2 * m["d"] * m["V"])


def prefill_flops(spec: dict, T: int) -> int:
    """Operations of a prefill of ``T`` tokens: projections, causal
    attention, the recurrence's operations; logits for the last one."""
    m = dims(spec)
    attn = 4 * m["n_attn"] * m["hq"] * m["hd"] * T * (T + 1) // 2
    return (T * (2 * _matmul_params(m)
                 + m["n_mamba"] * _mixer_token_flops(m))
            + attn + 2 * m["d"] * m["V"])
