"""The Granite 4.0 hybrid (``bench/models/granite_hybrid.py``) against the
program's ``mamba2`` + ``attn`` pattern: the engine's logits after
prefill, admission into the paged pool and the SSM state and conv window
rows, and decoding through both, at small widths on the CPU; the
reference's counts at full width; the weights' layout; the engine's
cache against the counts; the ``.hybrid`` readers; a whole run."""

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench_tiny import ROOT, TINY_MIX, tiny_root
from repro.configs import get_config

CELL = "granite-4.0-h-micro.longctx16"
CONFIG = ROOT / "bench/configs/granite-4.0-h-micro.json"
STEPS = 11


def prompt_lens(chunk: int) -> tuple:
    """Prompts shorter than the conv window (1, 2, 3), one that crosses a
    page of 16 while decoding (23), and the program's SSD chunk and one
    past it."""
    return (1, 2, 3, 23, chunk, chunk + 1)


PROMPTS = prompt_lens(get_config("granite_4_h_micro").ssm_chunk)


def tiny_spec() -> dict:
    """The published file at small widths: two periods of its layer
    pattern (so each slot's stack holds two layers), float32."""
    spec = json.loads(CONFIG.read_text())
    period = spec["layer_types"][:10]
    spec.update(name="tiny-hybrid", dtype="float32", hidden_size=64,
                num_attention_heads=4, num_key_value_heads=2,
                intermediate_size=128, shared_intermediate_size=128,
                vocab_size=256, num_hidden_layers=20,
                layer_types=period * 2, mamba_n_heads=8, mamba_d_head=16,
                mamba_d_state=16)
    return spec


def tiny_hybrid_root(tmp):
    """``bench_tiny.tiny_root`` plus one tiny cell of the hybrid, added as
    data alone, held to the hybrid cell's limit."""
    root = tiny_root(tmp)
    spec = tiny_spec()
    name, cell = spec["name"], spec["name"] + ".tiny"
    (root / f"bench/configs/{name}.json").write_text(json.dumps(spec))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": name, "source": "test",
                             "file": f"bench/configs/{name}.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": cell, "config": name,
                               "traffic": "tiny", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / f"bench/cells/{cell}.json").write_text(
        (root / f"bench/cells/{CELL}.json").read_text())
    return root, cell


def reference_logits(cell, params, tokens, cap, fp8=False):
    """The reference's logits (divided by ``logits_scaling``) at every
    position of ``tokens`` padded to ``cap``."""
    seq = np.zeros(cap, np.int32)
    seq[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        h = cell.model.hidden(cell.spec, params, jnp.asarray(seq), fp8=fp8)
        lg = jnp.dot(h, params["embed"].T.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    lg = np.asarray(lg)[:, :cell.spec["vocab_size"]]
    return lg / cell.spec["logits_scaling"]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Six ragged prompts admitted into one engine, then ``STEPS`` decode
    steps: the carried logits after admission and after every step."""
    from repro.runtime.serve import DecodeEngine, Request

    root, name = tiny_hybrid_root(tmp_path_factory.mktemp("hybrid"))
    cell = harness.load_cell(root, name)
    cfg = harness.program_config(cell)
    V = cell.spec["vocab_size"]
    params = harness.make_params(cell, 2**35 + 19)
    # room for the longest prompt and its steps, in whole reference blocks
    blk = cell.model.BLOCK
    cap = max(TINY_MIX["engine"]["max_ctx"],
              -(-(max(PROMPTS) + STEPS + 1) // blk) * blk)
    eng = DecodeEngine(cfg, params, slots=len(PROMPTS), page_size=16,
                       max_ctx=cap, max_new_cap=16)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, V, n, dtype=np.int32) for n in PROMPTS]
    slots = []
    for p in prompts:
        req = Request(prompt=p, max_new=STEPS + 1)
        slots.append(eng.admit(req, eng.prefill(req)))
    seen = [np.asarray(eng.st["logits"])[:, :V]]
    for _ in range(STEPS):
        eng.run_wave(1)
        seen.append(np.asarray(eng.st["logits"])[:, :V])
        eng.commit_wave()
    buf = np.asarray(eng.st["buf"])
    return cell, cfg, params, cap, prompts, slots, np.stack(seen), buf


@pytest.mark.parametrize("i", range(len(PROMPTS)), ids=[
    "conv1", "conv2", "conv3", "page", "chunk", "chunk_plus1"])
def test_engine_logits_match_reference(served, i):
    """Prefill, admission and 11 decode steps through the paged pool and
    the carried SSM state and conv windows give the reference's logits to
    1e-4 of their scale (float32 throughout: what is left is summation
    order), and the greedy tokens are the reference's best; the fp8
    control misses that tolerance tenfold."""
    cell, cfg, params, cap, prompts, slots, seen, buf = served
    assert cfg.pattern == ("mamba2",) * 5 + ("attn",) + ("mamba2",) * 4
    p, s = prompts[i], slots[i]
    toks = np.concatenate([p, buf[s, :STEPS]])
    want = reference_logits(cell, params, toks, cap)[
        len(p) - 1:len(p) + STEPS]
    got = seen[:, s]
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 1e-4 * scale, (
        len(p), np.abs(got - want).max(), scale)
    assert np.array_equal(buf[s, :STEPS], want[:STEPS].argmax(-1)), len(p)
    fp8 = reference_logits(cell, params, toks, cap, fp8=True)[
        len(p) - 1:len(p) + STEPS]
    assert np.abs(fp8 - want).max() > 10 * 1e-4 * scale


def test_logits_are_not_degenerate(served):
    """Under the embedding's x12, the residuals' x0.22 and the logits'
    /8, the seeded weights give logits that spread over the vocabulary
    and a best token that changes from step to step."""
    cell, cfg, params, cap, prompts, slots, seen, buf = served
    lg = seen[:, slots[3]]
    assert 0.05 < lg.std(axis=-1).min() and lg.std(axis=-1).max() < 10
    assert len(set(lg.argmax(-1).tolist())) > STEPS // 2
    assert np.all(np.isfinite(lg))


def test_reference_initialisation():
    """The Mamba2 parts as mamba_ssm: A in [1, 16], dt = softplus(dt_bias)
    in [1e-3, 1e-1], D = 1, conv within 1/sqrt(d_conv), the mixer's norms
    at identity; the residual projections scaled for 2 x 20 adds and by
    the embedding's and the residual's multipliers (12 / 0.22)."""
    from bench.models import granite_hybrid as gh

    spec = tiny_spec()
    p = gh.init_params(spec, jax.random.PRNGKey(5))
    mx = p["blocks"]["0_mamba2"]["mixer"]
    a = np.exp(np.asarray(mx["a_log"]))
    assert a.min() >= 1.0 and a.max() <= 16.0
    dt = np.asarray(jax.nn.softplus(mx["dt_bias"]))
    assert dt.min() >= 1e-3 * (1 - 1e-5) and dt.max() <= 1e-1 * (1 + 1e-5)
    assert np.all(np.asarray(mx["d_skip"]) == 1.0)
    assert np.abs(np.asarray(mx["conv_w"])).max() <= 0.5
    assert not np.asarray(mx["norm"]).any()
    assert not np.asarray(p["blocks"]["0_mamba2"]["norm"]).any()
    wd = np.asarray(p["blocks"]["5_attn"]["mlp"]["w_down"])
    assert abs(wd.std() * np.sqrt(128 * 40) * 0.22 / 12 - 1) < 0.05


def published():
    spec = json.loads(CONFIG.read_text())
    return spec, harness.load_module(ROOT / "bench/models/granite_hybrid.py")


def test_hand_counts_at_full_width():
    spec, m = published()
    d, di, conv, H, f, V = 2048, 4096, 4096 + 2 * 128, 64, 8192, 100352
    mamba_mat = d * (di + conv + H) + di * d
    attn_mat = 2 * d * d + 2 * d * 512
    mlp = 3 * d * f
    # a Mamba2 layer: projections, conv weight and bias, dt_bias, A_log,
    # D, the gated norm, the block norm; then norm2 and the MLP
    mamba = mamba_mat + 5 * conv + 3 * H + di + d + d + mlp
    attn = attn_mat + 2 * d + mlp
    assert m.param_count(spec) == 36 * mamba + 4 * attn + V * d + d \
        == 3_191_396_096
    assert m.state_bytes_per_slot(spec) == 36 * (64 * 128 * 64 * 4
                                                 + 3 * 4352 * 2) \
        == 76_437_504
    # 4 attention layers x k, v x 8 heads x 64 x 2 bytes
    assert m.kv_bytes_per_position(spec) == 8_192
    mats = 36 * (mamba_mat + 5 * conv) + 4 * attn_mat + 40 * mlp + d * V
    vecs = 36 * (3 * H + di) + 81 * d
    w = mats * 2 + vecs * 4
    assert m.weight_bytes_per_step(spec) == w
    assert 6.3e9 < w < 6.4e9
    # one row at length 100 emitting 2 tokens: keys 101 + 102
    assert m.decode_bytes(spec, 2, [(100, 2)]) == \
        2 * w + 2 * 2 * 76_437_504 + 203 * 8_192
    mat = 36 * mamba_mat + 4 * attn_mat + 40 * mlp
    mixer = 2 * 4 * conv + 5 * 64 * 128 * 64
    assert m.token_flops(spec, 3000) == \
        2 * mat + 36 * mixer + 4 * 4 * 32 * 64 * 3000 + 2 * d * V
    assert m.prefill_flops(spec, 3) == \
        3 * (2 * mat + 36 * mixer) + 4 * 4 * 32 * 64 * 6 + 2 * d * V


def test_weights_match_program_layout():
    """The reference's weights have the program's tree, shapes and dtypes
    at full width, and as many parameters as counted."""
    from repro.models import lm

    spec, m = published()
    cell = type("Cell", (), {"spec": spec, "model": m})
    cfg = harness.program_config(cell)
    want = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.PRNGKey(0)))
    got = jax.eval_shape(lambda: m.init_params(spec, jax.random.PRNGKey(0)))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    assert sum(x.size for x in jax.tree.leaves(got)) == m.param_count(spec)


def test_config_file_holds_what_runs():
    """The cell runs the file's widths and scalars through the
    repository's own config: the same config but for the head width the
    file states."""
    cell = harness.load_cell(ROOT, CELL)
    assert cell.spec["architecture"] == "granite_hybrid"
    cfg = harness.program_config(cell)
    assert cfg == get_config("granite_4_h_micro").replace(head_dim=64)
    assert cfg.n_layers == 40 and cfg.repeats == 4
    kinds = [cfg.pattern[i % 10] for i in range(40)]
    assert [i for i, k in enumerate(kinds) if k == "attn"] == \
        [5, 15, 25, 35]
    assert ["attn" if t == "attention" else "mamba2"
            for t in cell.spec["layer_types"]] == kinds
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.d_ff) \
        == (2048, 32, 8, 64, 8192)
    assert (cfg.ssm_d_inner, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_conv,
            cfg.ssm_groups) == (4096, 64, 128, 4, 1)
    assert cfg.rope_theta is None and cfg.attn_scale == 0.015625
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling) == (12.0, 0.22, 8.0)
    assert (cfg.vocab, cfg.vocab_padded, cfg.tie_embeddings) == \
        (100352, 100352, True)
    assert cfg.norm_eps == 1e-5 and not cfg.residual_in_fp32
    assert jnp.dtype(cfg.dtype) == jnp.bfloat16
    e = cell.mix["engine"]
    assert (e["slots"], e["max_ctx"], e["page_size"]) == (16, 4096, 16)


def test_engine_cache_bytes_are_the_reference_counts():
    """``DecodeEngine.cache_bytes`` of the cell's engine at full width
    (shapes only, nothing allocated) is the reference's state and K/V
    counts: the bytes the roofline's ``decode_bytes`` reads are the
    program's real cache."""
    from repro.runtime.serve import DecodeEngine

    cell = harness.load_cell(ROOT, CELL)
    cfg, e = harness.program_config(cell), cell.mix["engine"]
    got = {}

    def build():
        eng = DecodeEngine(cfg, None, slots=e["slots"],
                           page_size=e["page_size"], max_ctx=e["max_ctx"],
                           max_new_cap=512)
        got.update(eng.cache_bytes())
        return eng.st

    jax.eval_shape(build)
    m, spec = cell.model, cell.spec
    assert got["recurrent_per_slot"] == m.state_bytes_per_slot(spec)
    assert got["recurrent"] == 16 * 76_437_504
    assert got["kv_per_position"] == m.kv_bytes_per_position(spec)
    # 1 trash page + 16 slots x 256 pages of 16 positions
    assert got["kv_pool"] == (1 + 16 * 256) * 16 * 8_192


def whole_run(root, name, monkeypatch):
    """A whole run of the tiny cell, the harness's look for a chip
    answered by the CPU and the chip's peaks (as test_bench_faults)."""
    import time

    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro.core.schedule import EXEC_CACHE

    peaks = harness.load_json(root / "bench" / "peaks.json")["devices"][
        "TPU v5 lite"]
    monkeypatch.setattr(harness, "chip", lambda root, chips: (
        {"platform": "cpu", "kind": "cpu", "count": 1}, peaks))
    EXEC_CACHE.clear()
    try:
        return harness.run(root, name, 2**34 + 7, 0.5, False,
                           t_start=time.monotonic())
    finally:
        EXEC_CACHE.clear()
        jax.config.update("jax_compilation_cache_dir", None)
        cc.reset_cache()


@pytest.mark.parametrize("carried", [True, False])
def test_whole_run_of_a_tiny_cell(tmp_path, monkeypatch, carried):
    """The harness serves the hybrid end to end and finds it correct; a
    decode step that hands back the cache it was given (no K/V, state or
    conv window carried from step to step) is not."""
    from repro.models import lm

    root, name = tiny_hybrid_root(tmp_path)
    if not carried:
        step = lm.decode_step
        monkeypatch.setattr(lm, "decode_step", lambda cfg, p, cache, t, i: (
            step(cfg, p, cache, t, i)[0], cache))
    line = whole_run(root, name, monkeypatch)
    assert line["correct"] is carried, line["checks"]
    assert line["checks"]["served_tokens_compared"]["value"] > 20
    assert line["failed"] == 0 and line["attempted"] >= 4


def test_compiled_wave_carries_the_scopes(tmp_path):
    """The hybrid wave's operations carry every scope a split of
    ``jit_wave`` reads: ``attn`` with ``kv_write`` and ``kv_gather``,
    ``mlp`` (after both mixers), ``ssm`` with ``conv``, ``ssd`` and
    ``gate_norm``, and ``head``."""
    from repro.runtime.serve import DecodeEngine

    root, name = tiny_hybrid_root(tmp_path)
    cell = harness.load_cell(root, name)
    e = cell.mix["engine"]
    eng = DecodeEngine(harness.program_config(cell),
                       harness.make_params(cell, 3), slots=e["slots"],
                       page_size=e["page_size"], max_ctx=e["max_ctx"],
                       max_new_cap=8)
    text = eng._wave_fn.lower(eng.params, eng.st,
                              jnp.int32(e["wave_len"])).compile().as_text()
    paths = re.findall(r'op_name="(jit\(wave\)/[^"]*)"', text)
    for scope in ("attn/kv_write", "attn/kv_gather", "ssm/conv", "ssm/ssd",
                  "ssm/gate_norm"):
        assert any(f"/{scope}/" in p for p in paths), scope
    for scope in ("attn", "mlp", "ssm", "head"):
        assert any(f"/{scope}/" in p for p in paths), scope


READERS = {"decode_step_ms.hybrid", "decode_hbm_roofline.hybrid",
           "prefill_share.hybrid", "mfu.hybrid", "snapshot_share.hybrid",
           "admit_share.hybrid"}


@pytest.mark.parametrize("model_of", ["granite-3-2b.longctx", CELL])
@pytest.mark.parametrize("traced", [True, False])
def test_hybrid_readers_read_a_value_or_nothing(model_of, traced):
    """The six ``.hybrid`` readers on a hand-made window of either model:
    each gives a value and its unit, or None, and never raises;
    ``admit_share.hybrid`` is ``jit_admit`` time over the window."""
    readers = harness.load_cell(ROOT, CELL).readers
    assert set(readers) == READERS
    c = harness.load_cell(ROOT, model_of)
    peaks = harness.load_json(ROOT / "bench" / "peaks.json")["devices"][
        "TPU v5 lite"]
    trace = {"busy_s": 9.0, "window_s": 10.0,
             "modules_s": {"jit_wave": 8.0, "jit_pf": 0.5, "jit_admit": 0.2,
                           "jit_snap": 0.4}} if traced else None
    w = harness.Window(
        seconds=10.0, t_open=100.0, slots=16, spec=c.spec, model=c.model,
        peaks=peaks, deliveries=[(101.0, 1, 2048, 0, 8)],
        waves=[{"t0": 101.0, "t1": 102.0, "wall": 1.0, "steps": 8,
                "live": 16, "rows": [(2048, 8)] * 16}],
        prefills=[(101.5, 2048)], results=[], admitted=set(), retraces=0,
        compiles=[], trace=trace)
    for name, read in readers.items():
        got = read(w)
        assert got is None or (got[0] >= 0 and isinstance(got[1], str)), name
    assert readers["admit_share.hybrid"](w) == (
        (pytest.approx(2.0), "%") if traced else None)
    assert readers["prefill_share.hybrid"](w) == (
        (pytest.approx(7.0), "%") if traced else None)
    assert (readers["decode_step_ms.hybrid"](w) is None) is not traced
    assert readers["mfu.hybrid"](w)[0] > 0
