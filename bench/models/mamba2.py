"""The published Mamba2 decoder (mamba_ssm's ``MixerModel`` of ``Mamba2``
layers; arXiv:2405.21060 section 7).

The same four things as ``dense_gqa``: the program's config fields,
random weights in the program's layout, the plain float32 reference
forward pass with its fp8 control, and the bytes and operations the
algorithm needs. A layer, on the residual stream ``x`` (float32):

    h = rmsnorm(x);  [z, xBC, dt] = h W_in
    xBC = silu(causal_depthwise_conv(xBC) + conv_bias);  [x, B, C] = xBC
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    h_t = exp(A dt_t) h_{t-1} + B_t (dt_t x_t)^T;  y_t = C_t^T h_t + D x_t
    x += rmsnorm_g(y * silu(z)) W_out      (groups of d_inner / ngroups)

The SSD layer is computed in its quadratic (masked attention-like) form,
which is exact for the recurrence. RMSNorm weights are ``1 + scale``, as
the program holds them. It imports nothing of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from bench.models.dense_gqa import BF16, F32, matmul, output_gap, rms_norm


def dims(spec: dict) -> dict:
    ly = spec["mamba2_layer"]
    d = spec["d_model"]
    di = ly["expand"] * d
    G, S = ly["ngroups"], ly["d_state"]
    H = di // ly["headdim"]
    conv = di + 2 * G * S
    return dict(d=d, L=spec["n_layer"], di=di, H=H, P=ly["headdim"], S=S,
                G=G, K=ly["d_conv"], conv=conv, proj=di + conv + H,
                V=spec["vocab_size"], Vp=-(-spec["vocab_size"] // 128) * 128,
                tied=spec["tie_embeddings"])


def program_fields(spec: dict) -> dict:
    m = dims(spec)
    return dict(n_layers=m["L"], d_model=m["d"], vocab=m["V"],
                ssm_state=m["S"], ssm_heads=m["H"], ssm_d_inner=m["di"],
                ssm_conv=m["K"], ssm_groups=m["G"],
                tie_embeddings=m["tied"], dtype=spec["dtype"],
                residual_in_fp32=spec["residual_in_fp32"],
                norm_eps=spec["rms_norm_eps"],
                pattern=("mamba2",), family="ssm")


# --------------------------------------------------------------------- #
# weights
# --------------------------------------------------------------------- #
def init_params(spec: dict, key):
    """mamba_ssm's initialisation in the program's layout: projections
    normal with variance 1/fan-in, ``out_proj`` scaled by a further
    1/sqrt(layers) (mamba_ssm's prenorm rescale, one residual a layer);
    ``A_log = log U[1, 16]``; ``dt_bias`` the inverse softplus of dt
    drawn log-uniform on [1e-3, 1e-1]; ``D = 1``; conv weight and bias
    uniform within +-1/sqrt(d_conv); every norm at identity (scale 0)."""
    m = dims(spec)
    d, L, di, H, K, conv = m["d"], m["L"], m["di"], m["H"], m["K"], m["conv"]
    dt = jnp.dtype(spec["dtype"])
    k = iter(jax.random.split(key, 16))

    def w(shape, fan_in, depth=1):  # drawn in the serving dtype
        return jax.random.normal(next(k), shape, dt) * jnp.asarray(
            (fan_in * depth) ** -0.5, dt)

    def uniform(shape, lo, hi, dtype=jnp.float32):
        return jax.random.uniform(next(k), shape, dtype, lo, hi)

    step = jnp.exp(uniform((L, H), math.log(1e-3), math.log(1e-1)))
    lim = K ** -0.5
    params = {
        "embed": w((m["Vp"], d), d),
        "norm_f": jnp.zeros((d,), jnp.float32),
        "blocks": {"0_mamba2": {
            "norm": jnp.zeros((L, d), jnp.float32),
            "mixer": {
                "w_in": w((L, d, m["proj"]), d),
                "conv_w": uniform((L, K, conv), -lim, lim, dt),
                "conv_b": uniform((L, conv), -lim, lim, dt),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "a_log": jnp.log(uniform((L, H), 1.0, 16.0)),
                "d_skip": jnp.ones((L, H), jnp.float32),
                "norm": jnp.zeros((L, di), jnp.float32),
                "w_out": w((L, di, d), di, L)},
        }},
    }
    if not m["tied"]:
        params["out"] = w((d, m["Vp"]), d)
    return params


# --------------------------------------------------------------------- #
# the float32 reference and its fp8 control
# --------------------------------------------------------------------- #
def hidden(spec: dict, params, tokens, fp8: bool = False):
    """Final normed hidden states ``[T, d]`` (float32) of one sequence."""
    m = dims(spec)
    di, H, P, S, G, K = m["di"], m["H"], m["P"], m["S"], m["G"], m["K"]
    eps = spec["rms_norm_eps"]
    x = params["embed"][tokens].astype(jnp.float32)
    T = x.shape[0]
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    group = jnp.arange(H) // (H // G)                   # head -> group

    def layer(x, p):
        mx = p["mixer"]
        h = rms_norm(x, p["norm"], eps)
        zxbcdt = matmul(h, mx["w_in"], fp8)
        z, xbc, dt = zxbcdt[:, :di], zxbcdt[:, di:di + m["conv"]], \
            zxbcdt[:, di + m["conv"]:]
        pad = jnp.concatenate([jnp.zeros((K - 1, m["conv"])), xbc])
        cw = mx["conv_w"].astype(jnp.float32)
        xc = sum(pad[j:j + T] * cw[j] for j in range(K))
        xc = jax.nn.silu(xc + mx["conv_b"].astype(jnp.float32))
        xs = xc[:, :di].reshape(T, H, P)
        b = xc[:, di:di + G * S].reshape(T, G, S)[:, group]      # [T, H, S]
        c = xc[:, di + G * S:].reshape(T, G, S)[:, group]
        dt = jax.nn.softplus(dt + mx["dt_bias"])                  # [T, H]
        a = -jnp.exp(mx["a_log"])[None, :] * dt
        cum = jnp.cumsum(a, axis=0)                               # [T, H]
        diff = cum[:, None, :] - cum[None, :, :]                  # [t, s, H]
        decay = jnp.exp(jnp.where(causal[..., None], diff, -jnp.inf))
        cb = jnp.einsum("ths,khs->tkh", c, b,
                        precision=lax.Precision.HIGHEST)          # [t, s, H]
        y = jnp.einsum("tkh,khp->thp", decay * cb, xs * dt[..., None],
                       precision=lax.Precision.HIGHEST)
        y = y + xs * mx["d_skip"][None, :, None]
        g = (y.reshape(T, di) * jax.nn.silu(z)).reshape(T, G, di // G)
        g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
        g = g.reshape(T, di) * (1.0 + mx["norm"])
        return x + matmul(g, mx["w_out"], fp8), None

    x, _ = lax.scan(layer, x, params["blocks"]["0_mamba2"])
    return rms_norm(x, params["norm_f"], eps)


def head(spec: dict, params, h, targets, fp8: bool = False):
    m = dims(spec)
    return output_gap(params, h, targets, m["V"], m["tied"], fp8)


# --------------------------------------------------------------------- #
# the work a token needs
# --------------------------------------------------------------------- #
def _layer_matmul_params(m) -> int:
    return m["d"] * m["proj"] + m["di"] * m["d"]


def _layer_conv_params(m) -> int:
    return (m["K"] + 1) * m["conv"]


def _layer_vectors(m) -> int:
    """dt_bias, A_log, D, the gated norm's and the block norm's scales."""
    return 3 * m["H"] + m["di"] + m["d"]


def param_count(spec: dict) -> int:
    m = dims(spec)
    per = _layer_matmul_params(m) + _layer_conv_params(m) + _layer_vectors(m)
    return m["L"] * per + m["Vp"] * m["d"] * (1 if m["tied"] else 2) \
        + m["d"]


def state_bytes_per_slot(spec: dict) -> int:
    """The f32 SSM state and the conv window (last d_conv - 1 pre-conv
    rows, in the serving dtype) of every layer."""
    m = dims(spec)
    return m["L"] * (m["H"] * m["S"] * m["P"] * F32
                     + (m["K"] - 1) * m["conv"] * BF16)


def weight_bytes_per_step(spec: dict) -> int:
    """Every layer's matrices and conv (bf16) and f32 vectors once, the
    output head over the real vocabulary, the final norm."""
    m = dims(spec)
    layer = ((_layer_matmul_params(m) + _layer_conv_params(m)) * BF16
             + _layer_vectors(m) * F32)
    return m["L"] * layer + m["d"] * m["V"] * BF16 + m["d"] * F32


def decode_bytes(spec: dict, steps: int, rows) -> int:
    """Weights once a step; each live row's SSM state and conv window
    read and written once a token."""
    tokens = sum(n for _, n in rows)
    return (steps * weight_bytes_per_step(spec)
            + 2 * state_bytes_per_slot(spec) * tokens)


def _layer_token_flops(m) -> int:
    """Projections, the depthwise conv, the state update (decay, outer
    product, add: 3HSP) and the read-out (2HSP)."""
    return (2 * _layer_matmul_params(m) + 2 * m["K"] * m["conv"]
            + 5 * m["H"] * m["S"] * m["P"])


def token_flops(spec: dict, ctx: int) -> int:
    """One decoded token, independent of ctx: every layer and the head."""
    m = dims(spec)
    return m["L"] * _layer_token_flops(m) + 2 * m["d"] * m["V"]


def prefill_flops(spec: dict, T: int) -> int:
    """The recurrence's operations for T tokens; logits for the last."""
    m = dims(spec)
    return T * m["L"] * _layer_token_flops(m) + 2 * m["d"] * m["V"]
