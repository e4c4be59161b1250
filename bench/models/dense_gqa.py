"""Dense decoder with grouped-query attention (granite-3 style).

Four things the harness needs of an architecture, each from the
configuration's numbers alone:

* ``program_fields`` — the serving program's config fields for this file;
* ``init_params`` — random weights from a key, in the program's parameter
  layout and serving dtype, built in one jitted call;
* ``hidden`` / ``head`` — the plain float32 reference forward pass, and
  its fp8 control (``fp8=True``: every linear layer's operands rounded to
  float8_e4m3fn with per-channel / per-row scales, f32 accumulation);
* ``decode_bytes`` / ``token_flops`` / ``prefill_flops`` — the bytes and
  operations the algorithm needs, for roofline and MFU shares.

The reference implements the equations the program implements, which
leave the published model where the configuration's ``departures`` say.
It imports nothing of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

BF16, F32 = 2, 4


def dims(spec: dict) -> dict:
    d, hq = spec["hidden_size"], spec["num_attention_heads"]
    hd = spec.get("head_dim") or d // hq
    return dict(d=d, L=spec["num_hidden_layers"], hq=hq,
                hkv=spec["num_key_value_heads"], hd=hd,
                f=spec["intermediate_size"], V=spec["vocab_size"],
                Vp=-(-spec["vocab_size"] // 128) * 128,
                tied=spec["tie_word_embeddings"])


def program_fields(spec: dict) -> dict:
    m = dims(spec)
    return dict(n_layers=m["L"], d_model=m["d"], n_heads=m["hq"],
                n_kv_heads=m["hkv"], head_dim=m["hd"], d_ff=m["f"],
                vocab=m["V"], rope_theta=float(spec["rope_theta"]),
                tie_embeddings=m["tied"], dtype=spec["dtype"],
                pattern=("attn",), family="dense")


# --------------------------------------------------------------------- #
# weights
# --------------------------------------------------------------------- #
def init_params(spec: dict, key):
    """Random weights as a model at initialisation: normal with variance
    1/fan-in, the projections back into the residual stream (``wo``,
    ``w_down``) scaled by a further 1/sqrt(2 x layers) as in GPT-2 and
    Megatron, so that depth does not amplify rounding into a chaotic
    network; norm scales ``1 + N(0, 0.1^2)`` (the program's ``1 + scale``
    parametrisation)."""
    m = dims(spec)
    d, L, f, dt = m["d"], m["L"], m["f"], jnp.dtype(spec["dtype"])
    qw, kvw = m["hq"] * m["hd"], m["hkv"] * m["hd"]
    k = iter(jax.random.split(key, 16))

    def w(shape, fan_in, depth=1):  # drawn in the serving dtype
        return jax.random.normal(next(k), shape, dt) * jnp.asarray(
            (fan_in * depth) ** -0.5, dt)

    def norm(shape):
        return 0.1 * jax.random.normal(next(k), shape, jnp.float32)

    params = {
        "embed": w((m["Vp"], d), d),
        "norm_f": norm((d,)),
        "blocks": {"0_attn": {
            "norm1": norm((L, d)),
            "attn": {"wq": w((L, d, qw), d), "wk": w((L, d, kvw), d),
                     "wv": w((L, d, kvw), d), "wo": w((L, qw, d), qw, 2 * L)},
            "norm2": norm((L, d)),
            "mlp": {"w_gate": w((L, d, f), d), "w_up": w((L, d, f), d),
                    "w_down": w((L, f, d), f, 2 * L)},
        }},
    }
    if not m["tied"]:
        params["out"] = w((d, m["Vp"]), d)
    return params


# --------------------------------------------------------------------- #
# the float32 reference and its fp8 control
# --------------------------------------------------------------------- #
def _fp8(x, axis):
    """Round to float8_e4m3fn with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def matmul(x, w, fp8: bool):
    """``x [..., k] @ w [k, n]`` in float32; ``fp8`` rounds x per row and
    w per output column first."""
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.dot(x, w, precision=lax.Precision.HIGHEST)


def rms_norm(x, scale, eps):
    x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale.astype(jnp.float32))


def _rope(x, theta):
    """Rotate-half rotary embedding; ``x [T, H, hd]`` at positions 0..T-1."""
    T, _, hd = x.shape
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * freq
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _attention(q, k, v, block: int):
    """Causal softmax attention, queries in blocks of ``block`` rows so
    that the score matrix of a 4k context fits beside the weights."""
    T, hq, hd = q.shape
    rep = hq // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    qb = q.reshape(T // block, block, hq, hd)

    def one(args):
        i, qi = args
        s = jnp.einsum("qhd,khd->hqk", qi, k,
                       precision=lax.Precision.HIGHEST) * hd ** -0.5
        qpos = i * block + jnp.arange(block)
        s = jnp.where(jnp.arange(T)[None, None, :] <= qpos[None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v,
                          precision=lax.Precision.HIGHEST)

    out = lax.map(one, (jnp.arange(T // block), qb))
    return out.reshape(T, hq, hd)


def hidden(spec: dict, params, tokens, fp8: bool = False):
    """Final normed hidden states ``[T, d]`` (float32) of one sequence
    ``tokens [T]``; T a multiple of 128."""
    m = dims(spec)
    eps, theta = spec["rms_norm_eps"], float(spec["rope_theta"])
    x = params["embed"][tokens].astype(jnp.float32)
    T = x.shape[0]

    def layer(x, p):
        a = p["attn"]
        h = rms_norm(x, p["norm1"], eps)
        q = _rope(matmul(h, a["wq"], fp8).reshape(T, m["hq"], m["hd"]),
                  theta)
        k = _rope(matmul(h, a["wk"], fp8).reshape(T, m["hkv"], m["hd"]),
                  theta)
        v = matmul(h, a["wv"], fp8).reshape(T, m["hkv"], m["hd"])
        o = _attention(q, k, v, min(T, 512)).reshape(T, -1)
        x = x + matmul(o, a["wo"], fp8)
        h = rms_norm(x, p["norm2"], eps)
        g = p["mlp"]
        u = jax.nn.silu(matmul(h, g["w_gate"], fp8)) * matmul(
            h, g["w_up"], fp8)
        return x + matmul(u, g["w_down"], fp8), None

    x, _ = lax.scan(layer, x, params["blocks"]["0_attn"])
    return rms_norm(x, params["norm_f"], eps)


def head(spec: dict, params, h, targets, fp8: bool = False):
    m = dims(spec)
    return output_gap(params, h, targets, m["V"], m["tied"], fp8)


def output_gap(params, h, targets, vocab: int, tied: bool, fp8: bool):
    """Per position of ``h [T, d]``: the gap by which ``targets [T]``'s
    logit lies below the best, and the best token. Real vocabulary only."""
    w = params["embed"].T if tied else params["out"]
    lg = matmul(h, w, fp8)[:, :vocab]
    best = jnp.max(lg, axis=-1)
    got = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
    return best - got, jnp.argmax(lg, axis=-1).astype(jnp.int32)


# --------------------------------------------------------------------- #
# the work a token needs
# --------------------------------------------------------------------- #
def param_count(spec: dict) -> int:
    m = dims(spec)
    d, L = m["d"], m["L"]
    per = (2 * d * m["hq"] * m["hd"] + 2 * d * m["hkv"] * m["hd"]
           + 3 * d * m["f"] + 2 * d)
    return L * per + m["Vp"] * d * (1 if m["tied"] else 2) + d


def _matmul_params(m) -> int:
    """Weights one token multiplies: every layer's projections and the
    real-vocabulary output head."""
    d = m["d"]
    return m["L"] * (2 * d * m["hq"] * m["hd"] + 2 * d * m["hkv"] * m["hd"]
                     + 3 * d * m["f"]) + d * m["V"]


def weight_bytes_per_step(spec: dict) -> int:
    """Weights one decode step must read: every matrix once (bf16), the
    norm scales (f32); the embedding lookup's few rows are not counted."""
    m = dims(spec)
    return _matmul_params(m) * BF16 + (2 * m["L"] + 1) * m["d"] * F32


def kv_bytes_per_position(spec: dict) -> int:
    m = dims(spec)
    return m["L"] * 2 * m["hkv"] * m["hd"] * BF16


def decode_bytes(spec: dict, steps: int, rows) -> int:
    """Bytes the algorithm needs for ``steps`` decode steps over ``rows``
    of ``(length_before, tokens_emitted)``: the weights once a step, and
    each live row's valid keys and values once a token (its new k/v
    written included)."""
    kv = kv_bytes_per_position(spec)
    keys = sum(m * L0 + m * (m + 1) // 2 for L0, m in rows)
    return steps * weight_bytes_per_step(spec) + kv * keys


def token_flops(spec: dict, ctx: int) -> int:
    """Operations of one decoded token that attends ``ctx`` positions."""
    m = dims(spec)
    return 2 * _matmul_params(m) + 4 * m["L"] * m["hq"] * m["hd"] * ctx


def prefill_flops(spec: dict, T: int) -> int:
    """Operations of a causal prefill of ``T`` tokens; logits for the
    last one only."""
    m = dims(spec)
    d = m["d"]
    proj = m["L"] * (2 * d * m["hq"] * m["hd"] + 2 * d * m["hkv"] * m["hd"]
                     + 3 * d * m["f"])
    attn = 4 * m["L"] * m["hq"] * m["hd"] * T * (T + 1) // 2
    return 2 * proj * T + attn + 2 * d * m["V"]
