"""Per-architecture smoke tests: REDUCED same-family configs on CPU.

One forward/train step asserting output shapes + finiteness, plus
prefill->decode consistency per family. Full configs are exercised only
by the dry-run (launch/dryrun.py, ShapeDtypeStructs — no allocation).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import (ARCHS, SHAPES, get_config, input_specs, reduced,
                           shape_supported)
from repro.models import lm


def _batch(cfg, B=2, T=32, seed=0):
    rng = np.random.default_rng(seed)
    b = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, T)),
                               jnp.int32),
         "labels": jnp.asarray(rng.integers(0, cfg.vocab, (B, T)),
                               jnp.int32)}
    if cfg.frontend == "vit":
        b["patches"] = jnp.asarray(
            rng.standard_normal((B, cfg.frontend_len, cfg.frontend_dim)),
            cfg.jdtype)
    if cfg.frontend == "audio":
        b["frames"] = jnp.asarray(
            rng.standard_normal((B, T, cfg.frontend_dim)), cfg.jdtype)
    return b


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_smoke(arch):
    cfg = reduced(get_config(arch))
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)

    def loss_fn(p):
        return lm.train_loss(cfg, p, batch)[0]

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    assert np.isfinite(float(loss))
    # gradients exist, are finite, and are not all zero
    leaves = jax.tree.leaves(grads)
    assert all(np.all(np.isfinite(np.asarray(l, np.float32)))
               for l in leaves)
    assert any(float(jnp.abs(l.astype(jnp.float32)).sum()) > 0
               for l in leaves)


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_consistency(arch):
    """decode_step(T) after prefill(:T) == prefill(:T+1) last logits."""
    cfg = reduced(get_config(arch))
    params = lm.init_params(cfg, jax.random.PRNGKey(1))
    B, T = 2, 32
    full = _batch(cfg, B=B, T=T + 1, seed=3)
    pre = dict(full)
    pre["tokens"] = full["tokens"][:, :T]
    lg_full, _ = jax.jit(lambda p, b: lm.prefill(cfg, p, b))(params, full)
    lg_pre, cache = jax.jit(
        lambda p, b: lm.prefill(cfg, p, b, max_len=T + 1))(params, pre)
    lg_dec, new_cache = jax.jit(
        lambda p, c, t: lm.decode_step(cfg, p, c, t, jnp.int32(T)))(
        params, cache, full["tokens"][:, T:T + 1])
    np.testing.assert_allclose(np.asarray(lg_full), np.asarray(lg_dec),
                               rtol=1e-3, atol=1e-3)
    assert jax.tree.structure(cache) == jax.tree.structure(new_cache)


@pytest.mark.parametrize("arch", ARCHS)
def test_param_specs_match_structure(arch):
    cfg = reduced(get_config(arch))
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    specs = lm.param_specs(cfg)
    flat_p = jax.tree.leaves(params)
    flat_s = jax.tree.leaves(specs,
                             is_leaf=lambda x: isinstance(x, tuple))
    assert len(flat_p) == len(flat_s)
    # every spec tuple matches the rank of its parameter
    def chk(p, s):
        assert len(s) == p.ndim, f"{s} vs {p.shape}"
    jax.tree.map(chk, params,
                 jax.tree.map(tuple, specs,
                              is_leaf=lambda x: isinstance(x, tuple)),
                 is_leaf=lambda x: hasattr(x, "ndim"))


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_param_count(arch):
    """The FULL config's analytic parameter count is in the advertised
    ballpark (catches config typos without allocating anything)."""
    cfg = get_config(arch)
    n = cfg.param_count()
    expected = {
        "internvl2-26b": 20e9,       # LM backbone only (ViT is a stub)
        "mixtral-8x7b": 47e9,
        # the assigned spec (48L x 64e x d_ff 1408) yields 28B total /
        # 3.97B active; the hf "16B" name counts a narrower layout — the
        # assignment numbers are the contract here.
        "moonshot-v1-16b-a3b": 28e9,
        "internlm2-20b": 20e9,
        "gemma2-2b": 2.6e9,
        "mistral-large-123b": 123e9,
        "granite-3-2b": 2.5e9,
        "zamba2-2.7b": 2.7e9,
        "mamba2-1.3b": 1.3e9,
        "seamless-m4t-large-v2": 2.3e9,
        "granite-4.0-h-micro": 3.2e9,
    }[cfg.name]
    assert 0.5 * expected < n < 1.7 * expected, (cfg.name, n, expected)


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("shape", list(SHAPES))
def test_input_specs_build(arch, shape):
    """input_specs must produce ShapeDtypeStructs for every supported
    (arch x shape) cell without allocating."""
    cfg = get_config(arch)
    sh = SHAPES[shape]
    ok, why = shape_supported(cfg, sh)
    if not ok:
        pytest.skip(why)
    specs = input_specs(cfg, sh)
    leaves = jax.tree.leaves(specs)
    assert leaves and all(isinstance(l, jax.ShapeDtypeStruct)
                          for l in leaves)


def test_long500k_skips_documented():
    """Exactly the SSM/hybrid archs run long_500k (DESIGN.md §6)."""
    runs = [a for a in ARCHS
            if shape_supported(get_config(a), SHAPES["long_500k"])[0]]
    assert sorted(runs) == ["granite_4_h_micro", "mamba2_1p3b",
                            "zamba2_2p7b"]


@pytest.mark.parametrize("arch", ["mixtral_8x7b", "moonshot_v1_16b_a3b"])
def test_moe_capacity_drops_tokens_gracefully(arch):
    """Production capacity factor may drop tokens; loss must stay finite."""
    cfg = reduced(get_config(arch)).replace(moe_capacity_factor=0.5)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    loss, _ = jax.jit(lambda p, b: lm.train_loss(cfg, p, b))(
        params, _batch(cfg))
    assert np.isfinite(float(loss))


def _granite_or_mamba2(arch):
    cfg = reduced(get_config("granite_3_2b" if arch == "granite_3_2b"
                             else "mamba2_1p3b"))
    if arch == "mamba2":
        cfg = cfg.replace(pattern=("mamba2",), residual_in_fp32=True,
                          norm_eps=1e-5)
    return cfg


def _prefill_and_step(cfg, params):
    """Prefill logits of 12 tokens and one decode step's after them."""
    toks = _batch(cfg, B=2, T=13, seed=7)["tokens"]
    lg, cache = jax.jit(lambda p, t: lm.prefill(cfg, p, {"tokens": t},
                                                max_len=13))(
        params, toks[:, :12])
    step, _ = jax.jit(lambda p, c, t: lm.decode_step(cfg, p, c, t,
                                                     jnp.int32(12)))(
        params, cache, toks[:, 12:])
    return np.asarray(lg), np.asarray(step)


@pytest.mark.parametrize("arch", ["granite_3_2b", "mamba2"])
@pytest.mark.parametrize("field", ["attn_scale", "embedding_multiplier",
                                   "residual_multiplier", "logits_scaling"])
def test_granite_scalars_at_their_defaults_change_nothing(arch, field):
    """Each scalar granite-4.0-h-micro added to ``ModelConfig`` is None by
    default, and then the logits of prefill and of a decode step are
    bitwise those of the scalar at its neutral value (the softmax scale
    hd ** -0.5, multipliers and divisor 1), which is what the path
    computed before the field existed. Set otherwise, it changes them
    wherever the model has the part it scales (no attention in mamba2)."""
    cfg = _granite_or_mamba2(arch)
    assert getattr(cfg, field) is None
    params = lm.init_params(cfg, jax.random.PRNGKey(4))
    neutral = {"attn_scale": cfg.hd ** -0.5}.get(field, 1.0)
    base = _prefill_and_step(cfg, params)
    same = _prefill_and_step(cfg.replace(**{field: neutral}), params)
    other = _prefill_and_step(cfg.replace(**{field: 0.5}), params)
    for a, b in zip(base, same):
        assert np.array_equal(a, b)
    scales_a_part = not (arch == "mamba2" and field == "attn_scale")
    assert any(not np.array_equal(a, c) for a, c in zip(base, other)) \
        == scales_a_part


@pytest.mark.parametrize("arch", ["granite_3_2b", "mamba2"])
def test_nope_drops_rotary_only_where_there_is_attention(arch):
    """``rope_theta=None`` (NoPE) changes granite's logits and leaves the
    attention-free mamba2's bitwise as they were."""
    cfg = _granite_or_mamba2(arch)
    params = lm.init_params(cfg, jax.random.PRNGKey(4))
    base = _prefill_and_step(cfg, params)
    nope = _prefill_and_step(cfg.replace(rope_theta=None), params)
    changed = any(not np.array_equal(a, b) for a, b in zip(base, nope))
    assert changed == (arch == "granite_3_2b")
