"""Attention-free Mamba2 (SSD) decoder.

The same four things as ``dense_gqa``: the program's config fields,
random weights in the program's layout, the plain float32 reference
forward pass with its fp8 control, and the bytes and operations the
algorithm needs. The SSD layer is computed in its quadratic (masked
attention-like) form, which is exact for the recurrence
``h_t = exp(a_t) h_{t-1} + b_t x_t^T``, ``y_t = c_t^T h_t``.
It imports nothing of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from bench.models.dense_gqa import BF16, F32, matmul, output_gap, rms_norm


def dims(spec: dict) -> dict:
    ssm = spec["mamba2_layer"]
    d = spec["d_model"]
    di = ssm["expand"] * d
    return dict(d=d, L=spec["n_layer"], di=di, H=di // ssm["headdim"],
                P=ssm["headdim"], S=ssm["d_state"], V=spec["vocab_size"],
                Vp=-(-spec["vocab_size"] // 128) * 128,
                tied=spec["tie_embeddings"])


def program_fields(spec: dict) -> dict:
    m = dims(spec)
    return dict(n_layers=m["L"], d_model=m["d"], vocab=m["V"],
                ssm_state=m["S"], ssm_heads=m["H"], ssm_d_inner=m["di"],
                tie_embeddings=m["tied"], dtype=spec["dtype"],
                pattern=("ssm",), family="ssm")


def init_params(spec: dict, key):
    """Random weights as a model at initialisation: variance 1/fan-in;
    the output projection ``w_out`` scaled by a further 1/sqrt(2 x
    layers) (see ``dense_gqa.init_params``) and B, C by S^-1/4 each, so
    that c.b has unit variance; decay rates ``exp(a_log)`` log-uniform on
    [0.1, 1] per head."""
    m = dims(spec)
    d, L, di, H, S = m["d"], m["L"], m["di"], m["H"], m["S"]
    dt = jnp.dtype(spec["dtype"])
    k = iter(jax.random.split(key, 16))

    def w(shape, fan_in, depth=1):  # drawn in the serving dtype
        return jax.random.normal(next(k), shape, dt) * jnp.asarray(
            (fan_in * depth) ** -0.5, dt)

    def norm(shape):
        return 0.1 * jax.random.normal(next(k), shape, jnp.float32)

    a_log = jax.random.uniform(next(k), (L, H), jnp.float32,
                               math.log(0.1), 0.0)
    skip = 0.1 + 0.05 * jax.random.normal(next(k), (L, H), jnp.float32)
    params = {
        "embed": w((m["Vp"], d), d),
        "norm_f": norm((d,)),
        "blocks": {"0_ssm": {
            "norm": norm((L, d)),
            "ssm": {"w_in": w((L, d, di), d), "w_gate": w((L, d, di), d),
                    "w_bc": w((L, d, 2 * S), d, S ** 0.5),
                    "w_dt": w((L, d, H), d),
                    "a_log": a_log, "skip": skip,
                    "w_out": w((L, di, d), di, 2 * L)},
        }},
    }
    if not m["tied"]:
        params["out"] = w((d, m["Vp"]), d)
    return params


def hidden(spec: dict, params, tokens, fp8: bool = False):
    """Final normed hidden states ``[T, d]`` (float32) of one sequence."""
    m = dims(spec)
    H, P, S = m["H"], m["P"], m["S"]
    eps = spec["rms_norm_eps"]
    x = params["embed"][tokens].astype(jnp.float32)
    T = x.shape[0]
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]

    def layer(x, p):
        s = p["ssm"]
        h = rms_norm(x, p["norm"], eps)
        u = matmul(h, s["w_in"], fp8).reshape(T, H, P)
        z = matmul(h, s["w_gate"], fp8)
        bc = matmul(h, s["w_bc"], fp8)
        b, c = bc[:, :S], bc[:, S:]
        dt = jax.nn.softplus(matmul(h, s["w_dt"], fp8))           # [T, H]
        a = -jnp.exp(s["a_log"])[None, :] * dt
        xin = u * dt[..., None]
        cum = jnp.cumsum(a, axis=0)                                # [T, H]
        diff = cum[:, None, :] - cum[None, :, :]                   # [t, s, H]
        decay = jnp.exp(jnp.where(causal[..., None], diff, -jnp.inf))
        cb = jnp.dot(c, b.T, precision=lax.Precision.HIGHEST)      # [t, s]
        y = jnp.einsum("tsh,ts,shp->thp", decay, cb, xin,
                       precision=lax.Precision.HIGHEST)
        y = y + xin * s["skip"][None, :, None]
        y = y.reshape(T, H * P) * jax.nn.silu(z)
        return x + matmul(y, s["w_out"], fp8), None

    x, _ = lax.scan(layer, x, params["blocks"]["0_ssm"])
    return rms_norm(x, params["norm_f"], eps)


def head(spec: dict, params, h, targets, fp8: bool = False):
    m = dims(spec)
    return output_gap(params, h, targets, m["V"], m["tied"], fp8)


# --------------------------------------------------------------------- #
# the work a token needs
# --------------------------------------------------------------------- #
def _layer_matmul_params(m) -> int:
    d, di, S, H = m["d"], m["di"], m["S"], m["H"]
    return 2 * d * di + d * 2 * S + d * H + di * d


def param_count(spec: dict) -> int:
    m = dims(spec)
    per = _layer_matmul_params(m) + 2 * m["H"] + m["d"]
    return m["L"] * per + m["Vp"] * m["d"] * (1 if m["tied"] else 2) \
        + m["d"]


def state_bytes_per_slot(spec: dict) -> int:
    m = dims(spec)
    return m["L"] * m["H"] * m["S"] * m["P"] * F32


def weight_bytes_per_step(spec: dict) -> int:
    """Every layer's matrices (bf16) and f32 vectors once, the output head
    over the real vocabulary, the final norm."""
    m = dims(spec)
    layer = (_layer_matmul_params(m) * BF16
             + (2 * m["H"] + m["d"]) * F32)
    return m["L"] * layer + m["d"] * m["V"] * BF16 + m["d"] * F32


def decode_bytes(spec: dict, steps: int, rows) -> int:
    """Weights once a step; each live row's f32 state read and written
    once a token."""
    tokens = sum(m for _, m in rows)
    return (steps * weight_bytes_per_step(spec)
            + 2 * state_bytes_per_slot(spec) * tokens)


def token_flops(spec: dict, ctx: int) -> int:
    """Projections, the state update (decay, outer product, add: 3HSP)
    and the read-out (2HSP), and the output head; independent of ctx."""
    m = dims(spec)
    ssm = 5 * m["H"] * m["S"] * m["P"]
    return (m["L"] * (2 * _layer_matmul_params(m) + ssm)
            + 2 * m["d"] * m["V"])


def prefill_flops(spec: dict, T: int) -> int:
    """The recurrence's operations for T tokens; logits for the last."""
    m = dims(spec)
    ssm = 5 * m["H"] * m["S"] * m["P"]
    return T * m["L"] * (2 * _layer_matmul_params(m) + ssm) \
        + 2 * m["d"] * m["V"]
