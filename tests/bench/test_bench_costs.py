"""The FLOP and byte functions against hand counts at both
configurations' widths, and the benchmark's weights against the
program's parameter layout."""

import json
import types

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench_tiny import ROOT


def cell(name):
    """A cell of BENCHMARK.json, or for the SSD configuration, which has
    no cell yet, its configuration file and model as a cell would hold
    them."""
    if name == "mamba2-1.3b.chat":
        spec = json.loads((ROOT / "bench/configs/mamba2-1.3b.json")
                          .read_text())
        model = harness.load_module(ROOT / "bench/models/mamba2_ssd.py")
        return types.SimpleNamespace(spec=spec, model=model)
    return harness.load_cell(ROOT, name)


def test_granite_hand_counts():
    c = cell("granite-3-2b.longctx")
    m, spec = c.model, c.spec
    # 40 x (2*2048*2048 + 2*2048*512 + 3*2048*8192 + 2*2048)
    #   + 49280*2048 (tied) + 2048
    assert m.param_count(spec) == 2_533_787_648
    # 40 layers x k,v x 8 heads x 64 x 2 bytes
    assert m.kv_bytes_per_position(spec) == 81_920
    # every matrix once (real vocabulary rows), norms in f32
    w = (40 * (2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 8192)
         + 2048 * 49155) * 2 + 81 * 2048 * 4
    assert m.weight_bytes_per_step(spec) == w
    # two steps, one row from length 100 emitting 2 tokens: keys 101 + 102
    assert m.decode_bytes(spec, 2, [(100, 2)]) == 2 * w + 203 * 81_920
    mat = 40 * (2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 8192) \
        + 2048 * 49155
    assert m.token_flops(spec, 3000) == 2 * mat + 4 * 40 * 2048 * 3000
    assert m.prefill_flops(spec, 2) == (
        2 * 2 * (mat - 2048 * 49155) + 4 * 40 * 2048 * 3
        + 2 * 2048 * 49155)


def test_mamba2_hand_counts():
    c = cell("mamba2-1.3b.chat")
    m, spec = c.model, c.spec
    layer = 2 * 2048 * 4096 + 2048 * 256 + 2048 * 64 + 4096 * 2048
    # the repository's own config (untied head, vocab 50280 -> 50304 rows)
    # holds 1,445,568,512 parameters; the benchmark runs the published
    # tied head and vocabulary 50,277 (also 50,304 rows)
    untied = dict(spec, tie_embeddings=False, vocab_size=50280)
    assert m.param_count(untied) == 1_445_568_512
    assert m.param_count(spec) == 48 * (layer + 2 * 64 + 2048) \
        + 50304 * 2048 + 2048
    # 48 layers x 64 heads x 128 x 64 x 4 bytes
    assert m.state_bytes_per_slot(spec) == 100_663_296
    w = 48 * (layer * 2 + (2 * 64 + 2048) * 4) + 2048 * 50277 * 2 + 2048 * 4
    assert m.weight_bytes_per_step(spec) == w
    assert m.decode_bytes(spec, 3, [(10, 2), (50, 3)]) == \
        3 * w + 2 * 100_663_296 * 5
    tok = 48 * (2 * layer + 5 * 64 * 128 * 64) + 2 * 2048 * 50277
    assert m.token_flops(spec, 1) == m.token_flops(spec, 700) == tok
    assert m.prefill_flops(spec, 4) == 4 * (tok - 2 * 2048 * 50277) \
        + 2 * 2048 * 50277


@pytest.mark.parametrize("name", ["granite-3-2b.longctx", "mamba2-1.3b.chat"])
def test_weights_match_program_layout(name):
    from repro.models import lm

    c = cell(name)
    cfg = harness.program_config(c)
    want = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.PRNGKey(0)))
    got = jax.eval_shape(lambda: c.model.init_params(
        c.spec, jax.random.PRNGKey(0)))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    assert sum(x.size for x in jax.tree.leaves(got)) == \
        c.model.param_count(c.spec)


def test_config_files_hold_what_runs():
    """The program runs the file's widths: the program config built from
    the file equals the repository's own where both state a width."""
    from repro.configs import get_config

    g = harness.program_config(cell("granite-3-2b.longctx"))
    assert g == get_config("granite_3_2b").replace(head_dim=64)
    s = harness.program_config(cell("mamba2-1.3b.chat"))
    base = get_config("mamba2_1p3b")
    for f in ("n_layers", "d_model", "ssm_state", "ssm_heads",
              "ssm_d_inner", "pattern", "family", "dtype", "ssm_chunk"):
        assert getattr(s, f) == getattr(base, f)
    assert (s.vocab, s.tie_embeddings) == (50277, True)
    assert s.vocab_padded == base.vocab_padded
    assert jnp.dtype(s.dtype) == jnp.bfloat16
