"""The published Mamba2 block (``bench/models/mamba2.py``) against the
program's ``mamba2`` kind: the engine's logits after prefill, admission
into the slot's SSM state and conv window, and decoding through both, at
small widths on the CPU; the reference's counts at full width; the
weights' layout."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench_tiny import ROOT, TINY_MIX, tiny_root

CELL = "mamba2-1.3b.chat"
# prompts shorter than the conv window (1, 2, 3), and across the
# program's SSD chunk of 64 (64, 65)
PROMPTS = (1, 2, 3, 23, 64, 65)


def tiny_spec(ngroups: int) -> dict:
    spec = json.loads((ROOT / "bench/configs/mamba2-1.3b.published.json")
                      .read_text())
    spec.update(name=f"tiny-mamba2-g{ngroups}", dtype="float32", d_model=64,
                n_layer=2, vocab_size=256)
    spec["mamba2_layer"] = dict(spec["mamba2_layer"], d_state=16,
                                headdim=16, ngroups=ngroups)
    return spec


def tiny_mamba2_root(tmp, ngroups: int):
    """``bench_tiny.tiny_root`` plus one tiny cell of the published block,
    added as data alone."""
    root = tiny_root(tmp)
    spec = tiny_spec(ngroups)
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


def reference_logits(cell, params, tokens, cap):
    seq = np.zeros(cap, np.int32)
    seq[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        h = cell.model.hidden(cell.spec, params, jnp.asarray(seq))
        lg = jnp.dot(h, params["embed"].T.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    return np.asarray(lg)[:, :cell.spec["vocab_size"]]


@pytest.mark.parametrize("ngroups", [1, 2])
def test_engine_logits_match_reference(tmp_path, ngroups):
    """Prefill, admission and 11 decode steps through the carried SSM
    state and conv window give the reference's logits to 1e-4 of their
    scale (float32 throughout: what is left is summation order), and the
    greedy tokens are the reference's best."""
    from repro.runtime.serve import DecodeEngine, Request

    root, name = tiny_mamba2_root(tmp_path, ngroups)
    cell = harness.load_cell(root, name)
    cfg = harness.program_config(cell)
    assert cfg.pattern == ("mamba2",) and cfg.ssm_groups == ngroups
    V = cell.spec["vocab_size"]
    params = harness.make_params(cell, 2**35 + 17)
    cap = TINY_MIX["engine"]["max_ctx"]
    eng = DecodeEngine(cfg, params, slots=len(PROMPTS), page_size=16,
                       max_ctx=cap, max_new_cap=16)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, V, n, dtype=np.int32) for n in PROMPTS]
    slots = []
    for p in prompts:
        req = Request(prompt=p, max_new=12)
        slots.append(eng.admit(req, eng.prefill(req)))
    seen = [np.asarray(eng.st["logits"])[:, :V]]
    for _ in range(11):
        eng.run_wave(1)
        seen.append(np.asarray(eng.st["logits"])[:, :V])
        eng.commit_wave()
    buf = np.asarray(eng.st["buf"])
    for p, s in zip(prompts, slots):
        ref = reference_logits(cell, params, np.concatenate([p, buf[s, :11]]),
                               cap)
        got = np.stack([x[s] for x in seen])
        want = ref[len(p) - 1:len(p) + 11]
        scale = np.abs(want).max()
        assert np.abs(got - want).max() <= 1e-4 * scale, (
            len(p), np.abs(got - want).max(), scale)
        assert np.array_equal(buf[s, :11], want[:11].argmax(-1)), len(p)


def test_reference_initialisation_is_mamba_ssm_s():
    """A in [1, 16], dt = softplus(dt_bias) in [1e-3, 1e-1], D = 1, conv
    within 1/sqrt(d_conv), norms at identity."""
    from bench.models import mamba2

    spec = tiny_spec(1)
    p = mamba2.init_params(spec, jax.random.PRNGKey(5))
    mx = p["blocks"]["0_mamba2"]["mixer"]
    a = np.exp(np.asarray(mx["a_log"]))
    assert a.min() >= 1.0 and a.max() <= 16.0
    dt = np.asarray(jax.nn.softplus(mx["dt_bias"]))
    assert dt.min() >= 1e-3 * (1 - 1e-5) and dt.max() <= 1e-1 * (1 + 1e-5)
    assert np.all(np.asarray(mx["d_skip"]) == 1.0)
    assert np.abs(np.asarray(mx["conv_w"])).max() <= 0.5
    for n in (mx["norm"], p["blocks"]["0_mamba2"]["norm"], p["norm_f"]):
        assert not np.asarray(n).any()


def published():
    spec = json.loads((ROOT / "bench/configs/mamba2-1.3b.published.json")
                      .read_text())
    return spec, harness.load_module(ROOT / "bench/models/mamba2.py")


def test_hand_counts_at_full_width():
    spec, m = published()
    d, di, conv, H = 2048, 4096, 4096 + 2 * 128, 64
    # in_proj [z | xBC | dt], out_proj, conv weight and bias, dt_bias,
    # A_log, D, the gated norm's and the block norm's scales
    layer_mat = d * (di + conv + H) + di * d
    layer = layer_mat + 5 * conv + 3 * H + di + d
    assert layer == 25_849_280
    assert m.param_count(spec) == 48 * layer + 50304 * d + d \
        == 1_343_790_080
    # 48 x (64 heads x 128 x 64 f32 + 3 x 4352 bf16)
    assert m.state_bytes_per_slot(spec) == 48 * (64 * 128 * 64 * 4
                                                 + 3 * 4352 * 2) \
        == 101_916_672
    w = 48 * ((layer_mat + 5 * conv) * 2 + (3 * H + di + d) * 4) \
        + d * 50277 * 2 + d * 4
    assert m.weight_bytes_per_step(spec) == w
    # 16 slots a step: state 55% of the bytes
    step = m.decode_bytes(spec, 1, [(100, 1)] * 16)
    assert step == w + 16 * 2 * 101_916_672
    assert 0.54 < 16 * 2 * 101_916_672 / step < 0.56
    tok = 48 * (2 * layer_mat + 2 * 4 * conv + 5 * 64 * 128 * 64) \
        + 2 * d * 50277
    assert m.token_flops(spec, 1) == m.token_flops(spec, 700) == tok
    assert m.prefill_flops(spec, 4) == 4 * (tok - 2 * d * 50277) \
        + 2 * d * 50277


def test_weights_match_program_layout():
    """The reference's weights have the program's tree, shapes and
    dtypes at full width, and as many parameters as counted."""
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
    """The cell runs the published block at the file's widths: chosen by
    ``pattern`` alone, f32 residual, epsilon 1e-5, SSD chunk 64."""
    cell = harness.load_cell(ROOT, CELL)
    assert cell.spec["architecture"] == "mamba2"
    cfg = harness.program_config(cell)
    assert cfg.pattern == ("mamba2",) and cfg.n_layers == 48
    assert (cfg.d_model, cfg.ssm_d_inner, cfg.ssm_heads, cfg.ssm_state,
            cfg.ssm_conv, cfg.ssm_groups) == (2048, 4096, 64, 128, 4, 1)
    assert cfg.residual_in_fp32 and cfg.norm_eps == 1e-5
    assert (cfg.vocab, cfg.vocab_padded, cfg.tie_embeddings) == \
        (50277, 50304, True)
    assert cfg.ssm_chunk == 64 and jnp.dtype(cfg.dtype) == jnp.bfloat16
    # the config's count: real vocabulary rows, no final norm
    assert cfg.param_count() == 48 * 25_849_280 + 50277 * 2048


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
        return harness.run(root, name, 2**34 + 5, 0.5, False,
                           t_start=time.monotonic())
    finally:
        EXEC_CACHE.clear()
        jax.config.update("jax_compilation_cache_dir", None)
        cc.reset_cache()


@pytest.mark.parametrize("carried", [True, False])
def test_whole_run_of_a_tiny_cell(tmp_path, monkeypatch, carried):
    """The harness serves the published block end to end and finds it
    correct; a decode step that hands back the cache it was given (no
    state or conv window carried from step to step) is not."""
    from repro.models import lm

    root, name = tiny_mamba2_root(tmp_path, 1)
    if not carried:
        step = lm.decode_step
        monkeypatch.setattr(lm, "decode_step", lambda cfg, p, cache, t, i: (
            step(cfg, p, cache, t, i)[0], cache))
    line = whole_run(root, name, monkeypatch)
    assert line["correct"] is carried, line["checks"]
    assert line["checks"]["served_tokens_compared"]["value"] > 20
    assert line["failed"] == 0 and line["attempted"] >= 4


def test_compiled_wave_carries_the_block_scopes(tmp_path):
    """Inside ``ssm``, the wave's operations carry the block's scopes
    ``conv``, ``ssd`` and ``gate_norm``; ``bench/scopes.py``, which knows
    only its fixed ``SCOPES``, folds them into ``ssm``."""
    import re

    from bench import scopes as sc
    from repro.runtime.serve import DecodeEngine

    root, name = tiny_mamba2_root(tmp_path, 1)
    cell = harness.load_cell(root, name)
    e = cell.mix["engine"]
    eng = DecodeEngine(harness.program_config(cell),
                       harness.make_params(cell, 3), slots=e["slots"],
                       page_size=e["page_size"], max_ctx=e["max_ctx"],
                       max_new_cap=8)
    text = eng._wave_fn.lower(eng.params, eng.st,
                              jnp.int32(e["wave_len"])).compile().as_text()
    paths = re.findall(r'op_name="(jit\(wave\)/[^"]*)"', text)
    for scope in ("conv", "ssd", "gate_norm"):
        assert any(f"/ssm/{scope}/" in p for p in paths), scope
    assert {sc.scope_of(p) for p in paths} == {"ssm", "head", ""}


@pytest.mark.parametrize("model_of", ["granite-3-2b.longctx", CELL])
@pytest.mark.parametrize("traced", [True, False])
def test_ssm_readers_read_a_value_or_nothing(model_of, traced):
    """The five ``.ssm`` readers on a hand-made window of either model:
    each gives a value and its unit, or None, and never raises;
    ``snapshot_share.ssm`` is ``jit_snap`` time over the window."""
    readers = harness.load_cell(ROOT, CELL).readers
    assert set(readers) == {"decode_step_ms.ssm", "decode_hbm_roofline.ssm",
                            "mfu.ssm", "snapshot_share.ssm",
                            "prefill_share.ssm"}
    c = harness.load_cell(ROOT, model_of)
    peaks = harness.load_json(ROOT / "bench" / "peaks.json")["devices"][
        "TPU v5 lite"]
    trace = {"busy_s": 9.0, "window_s": 10.0,
             "modules_s": {"jit_wave": 8.0, "jit_pf": 0.5, "jit_admit": 0.1,
                           "jit_snap": 0.4}} if traced else None
    w = harness.Window(
        seconds=10.0, t_open=100.0, slots=16, spec=c.spec, model=c.model,
        peaks=peaks, deliveries=[(101.0, 1, 128, 0, 8)],
        waves=[{"t0": 101.0, "t1": 102.0, "wall": 1.0, "steps": 8,
                "live": 16, "rows": [(128, 8)] * 16}],
        prefills=[(101.5, 128)], results=[], admitted=set(), retraces=0,
        compiles=[], trace=trace)
    for name, read in readers.items():
        got = read(w)
        assert got is None or (got[0] >= 0 and isinstance(got[1], str)), name
    snap = readers["snapshot_share.ssm"](w)
    assert snap == ((pytest.approx(4.0), "%") if traced else None)
    assert (readers["decode_step_ms.ssm"](w) is None) is not traced
