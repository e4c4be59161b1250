"""Serving tests: the fixed host-loop oracle, the jit executable cache,
paged KV slots, and DecodeEngine/ServeStream parity (DESIGN.md §13)."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, get_config, reduced
from repro.core.schedule import EXEC_CACHE, ExecCache
from repro.kernels.ops import attention
from repro.models import lm
from repro.runtime.serve import (DecodeEngine, PagePool, Request,
                                 ServeStream, generate, trace_total)


@pytest.fixture(scope="module")
def gemma():
    cfg = reduced(get_config("gemma2_2b"))
    return cfg, lm.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def mamba():
    cfg = reduced(get_config("mamba2_1p3b"))
    return cfg, lm.init_params(cfg, jax.random.PRNGKey(0))


def _config(arch):
    """A reduced registry config; ``"mamba2"`` is mamba2-1.3b on the
    published Mamba2 block (conv window beside the SSM state, gated
    RMSNorm, f32 residual), chosen by ``pattern``."""
    if arch == "mamba2":
        return reduced(get_config("mamba2_1p3b")).replace(
            pattern=("mamba2",), residual_in_fp32=True, norm_eps=1e-5)
    return reduced(get_config(arch))


@pytest.fixture(scope="module")
def mamba2():
    cfg = _config("mamba2")
    return cfg, lm.init_params(cfg, jax.random.PRNGKey(0))


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, (t,)).astype(np.int32)
            for t in lens]


def _oracle_gen(cfg, params, req):
    """Per-request B=1 host-loop reference (the fixed generate)."""
    res = generate(cfg, params, np.asarray(req.prompt)[None],
                   max_new=req.max_new, eos=req.eos,
                   temperature=req.temperature, seed=req.seed,
                   pad=req.pad)
    return res.tokens[0, len(req.prompt):]


def _assert_parity(cfg, params, reqs, results):
    for req, res in zip(reqs, results):
        want = _oracle_gen(cfg, params, req)
        got = res.generated[:len(want)]
        assert np.array_equal(want, got), (
            f"plen={res.prompt_len}: oracle {want} != engine {got}")


# --------------------------------------------------------------------- #
# legacy generate fixes (the oracle itself)
# --------------------------------------------------------------------- #
def test_generate_post_eos_rows_emit_pad(gemma):
    cfg, params = gemma
    prompts = np.asarray(_prompts(cfg, [6, 6, 6])[0])[None].repeat(3, 0)
    # force a known eos: whatever token row 0 emits first becomes eos
    first = generate(cfg, params, prompts, max_new=1).tokens[0, -1]
    res = generate(cfg, params, prompts, max_new=8, eos=int(first))
    gen = res.tokens[:, prompts.shape[1]:]
    for row in gen:
        hit = np.where(row == int(first))[0]
        assert len(hit) > 0
        assert (row[hit[0]:] == int(first)).all(), \
            "rows past eos must emit the eos id, not sampled garbage"
    # custom pad id fills the tail instead
    res2 = generate(cfg, params, prompts, max_new=8, eos=int(first),
                    pad=0)
    gen2 = res2.tokens[:, prompts.shape[1]:]
    for row in gen2:
        hit = np.where(row == int(first))[0]
        assert (row[hit[0] + 1:] == 0).all()


def test_generate_second_call_zero_retrace(gemma):
    cfg, params = gemma
    prompts = np.stack(_prompts(cfg, [7, 7], seed=3))
    r1 = generate(cfg, params, prompts, max_new=5, eos=1)
    before = trace_total()
    r2 = generate(cfg, params, prompts, max_new=5, eos=1)
    assert trace_total() == before, \
        "same-shape generate must reuse the cached executables"
    assert np.array_equal(r1.tokens, r2.tokens)
    assert len(r1.step_times) == r1.steps


# --------------------------------------------------------------------- #
# executable cache
# --------------------------------------------------------------------- #
def test_exec_cache_hit_miss_and_lru():
    c = ExecCache(maxsize=2)
    built = []

    def mk(tag):
        def build():
            built.append(tag)
            return tag
        return build

    assert c.get("a", mk("a")) == "a"
    assert c.get("a", mk("a2")) == "a"          # hit: no rebuild
    assert built == ["a"]
    c.get("b", mk("b"))
    c.get("a", mk("a3"))                         # refresh a's recency
    c.get("c", mk("c"))                          # evicts b (LRU)
    c.get("b", mk("b2"))
    assert built == ["a", "b", "c", "b2"]
    s = c.stats()
    assert s["hits"] == 2 and s["misses"] == 4 and s["entries"] == 2


# --------------------------------------------------------------------- #
# paged KV plumbing
# --------------------------------------------------------------------- #
def test_page_pool_never_aliases():
    pool = PagePool(8)
    a = pool.alloc(0, 3)
    b = pool.alloc(1, 3)
    assert a is not None and b is not None
    assert 0 not in a + b, "trash page must never be handed out"
    assert not set(a) & set(b)
    pool.check_invariants()
    assert pool.alloc(2, 2) is None              # only 1 page left
    pool.free(0)
    c = pool.alloc(2, 3)
    assert set(c) == set(a), "freed pages are immediately reusable"
    pool.check_invariants()
    with pytest.raises(ValueError):
        pool.alloc(1, 1)                         # slot already owns pages


def test_attention_vector_valid_len_matches_scalar():
    rng = np.random.default_rng(0)
    B, H, Tq, Tk, D = 3, 2, 1, 12, 8
    q = jnp.asarray(rng.standard_normal((B, H, Tq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, H, Tk, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, H, Tk, D)), jnp.float32)
    lens = np.array([4, 9, 12], np.int32)
    out = attention(q, k, v, causal=True, valid_len=jnp.asarray(lens))
    for b, L in enumerate(lens):
        ref = attention(q[b:b + 1], k[b:b + 1], v[b:b + 1], causal=True,
                        valid_len=int(L))
        np.testing.assert_allclose(np.asarray(out[b]),
                                   np.asarray(ref[0]), atol=1e-5)


def test_paged_eviction_reuse_never_aliases_live_rows(gemma):
    """The aliasing trap: B finishes, its pages are re-used by C while A
    is still decoding — A's tokens must be unaffected."""
    cfg, params = gemma
    pa, pb, pc = _prompts(cfg, [6, 4, 5], seed=7)
    # B stops after 2 tokens (cap), A and C run long
    ra = Request(prompt=pa, max_new=10)
    rb = Request(prompt=pb, max_new=2)
    rc = Request(prompt=pc, max_new=10)
    # pool fits exactly two live requests -> C must recycle B's pages
    eng = DecodeEngine(cfg, params, slots=2, page_size=4, max_ctx=16,
                       n_pages=9, max_new_cap=10)
    stream = ServeStream(eng, wave_len=2)
    results = stream.run([ra, rb, rc])
    eng.pool.check_invariants()
    _assert_parity(cfg, params, [ra, rb, rc], results)


_HLO_TYPES = {"float32": "f32", "bfloat16": "bf16"}
_HLO_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%\S+ = (.+?) ([\w-]+)\(", re.M)


@pytest.mark.parametrize("arch", ["granite_3_2b", "mamba2_1p3b",
                                  "gemma2_2b", "mamba2",
                                  "granite_4_h_micro"])
def test_wave_never_copies_the_stacked_cache(arch):
    """The decode cache rides the layer scan's carry and each layer
    writes its rows in place: no ``copy`` or ``broadcast`` in the
    compiled wave produces an array of a stacked cache's full shape (a
    KV pool ``[R, P, Hkv, page, Dh]``, SSM state ``[R, slots, ...]`` or
    conv window ``[R, slots, K-1, C]``).
    """
    cfg = _config(arch)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    eng = DecodeEngine(cfg, params, slots=3, page_size=4, max_ctx=16,
                       max_new_cap=4)
    shapes = {f"{_HLO_TYPES[a.dtype.name]}[{','.join(map(str, a.shape))}]"
              for a in jax.tree.leaves(eng.st["cache"]) if a.ndim >= 4}
    hlo = eng._wave_fn.lower(params, eng.st,
                             jnp.int32(2)).compile().as_text()
    whole = [m.group(0).strip() for m in _HLO_INSTR.finditer(hlo)
             if m.group(2) in ("copy", "broadcast")
             and any(s in m.group(1) for s in shapes)]
    assert shapes and not whole, whole


# --------------------------------------------------------------------- #
# engine parity vs the host-loop oracle
# --------------------------------------------------------------------- #
def test_engine_greedy_parity_ragged_prompts(gemma):
    cfg, params = gemma
    reqs = [Request(prompt=p, max_new=8)
            for p in _prompts(cfg, [3, 11, 6, 9, 1, 5], seed=1)]
    eng = DecodeEngine(cfg, params, slots=3, page_size=4, max_ctx=24,
                       max_new_cap=8)
    results = ServeStream(eng, wave_len=4).run(reqs)
    _assert_parity(cfg, params, reqs, results)


def test_engine_early_eos_parity(gemma):
    cfg, params = gemma
    prompts = _prompts(cfg, [5, 5, 8, 8], seed=2)
    # pick each request's first greedy token as its eos: stops at step 1
    # in some slots while others keep decoding
    eos = [int(generate(cfg, params, p[None], max_new=1).tokens[0, -1])
           for p in prompts]
    reqs = [Request(prompt=p, max_new=6, eos=e if i % 2 == 0 else None)
            for i, (p, e) in enumerate(zip(prompts, eos))]
    eng = DecodeEngine(cfg, params, slots=4, page_size=4, max_ctx=16,
                       max_new_cap=6)
    results = ServeStream(eng, wave_len=3).run(reqs)
    _assert_parity(cfg, params, reqs, results)
    for req, res in zip(reqs, results):
        if req.eos is not None:
            assert res.emitted < req.max_new


def test_engine_temperature_parity_pinned_key(gemma):
    cfg, params = gemma
    reqs = [Request(prompt=p, max_new=6, temperature=0.8, seed=40 + i)
            for i, p in enumerate(_prompts(cfg, [4, 7, 6], seed=4))]
    eng = DecodeEngine(cfg, params, slots=2, page_size=4, max_ctx=16,
                       max_new_cap=6)
    results = ServeStream(eng, wave_len=4).run(reqs)
    _assert_parity(cfg, params, reqs, results)


def test_engine_parity_ssm_arch(mamba):
    cfg, params = mamba
    reqs = [Request(prompt=p, max_new=6)
            for p in _prompts(cfg, [5, 9, 3], seed=5)]
    eng = DecodeEngine(cfg, params, slots=2, page_size=4, max_ctx=16,
                       max_new_cap=6)
    results = ServeStream(eng, wave_len=3).run(reqs)
    _assert_parity(cfg, params, reqs, results)


SERVED = [a for a in ARCHS if get_config(a).family != "encdec"
          and not get_config(a).frontend]


@pytest.mark.parametrize("arch", SERVED + ["mamba2"])
def test_paged_decode_matches_prefill(arch):
    """After every decode step each live row's carried logits equal
    ``lm.prefill``'s last logits over the row's whole prefix (prompt
    plus the tokens it emitted). Prefill keeps no decode cache, so this
    checks the in-place paged writes and per-layer page gathers against
    an independent forward pass; the ragged prompts cross page
    boundaries at different steps."""
    cfg = _config(arch)
    params = lm.init_params(cfg, jax.random.PRNGKey(2))
    prompts = _prompts(cfg, [5, 6, 7], seed=16)
    eng = DecodeEngine(cfg, params, slots=3, page_size=4, max_ctx=16,
                       max_new_cap=4)
    slots = [eng.admit(Request(prompt=p, max_new=4), handle=i)
             for i, p in enumerate(prompts)]
    last = jax.jit(lambda p, t: lm.prefill(cfg, p, {"tokens": t})[0][0, 0])
    for _ in range(3):
        eng.run_wave(1)
        st = jax.device_get({k: eng.st[k]
                             for k in ("buf", "emitted", "done", "logits")})
        assert not st["done"][slots].any()
        for s, p in zip(slots, prompts):
            prefix = np.concatenate([p, st["buf"][s, :st["emitted"][s]]])
            want = last(params, jnp.asarray(prefix[None]))
            np.testing.assert_allclose(
                st["logits"][s, :cfg.vocab],
                np.asarray(want)[:cfg.vocab], rtol=1e-3, atol=1e-3)


def test_rollback_restores_the_conv_window(mamba2):
    """A rolled-back wave leaves the SSM state and the conv window
    bitwise as they were at the wave's boundary."""
    cfg, params = mamba2
    eng = DecodeEngine(cfg, params, slots=2, page_size=4, max_ctx=16,
                       max_new_cap=8)
    for i, p in enumerate(_prompts(cfg, [2, 6], seed=21)):
        eng.admit(Request(prompt=p, max_new=8), handle=i)
    eng.wave(2)
    before = jax.device_get(eng.st["cache"]["0_mamba2"])
    eng.run_wave(2)
    moved = jax.device_get(eng.st["cache"]["0_mamba2"])
    assert not np.array_equal(moved["conv"], before["conv"])
    eng.rollback()
    after = jax.device_get(eng.st["cache"]["0_mamba2"])
    for k in ("state", "conv"):
        assert np.array_equal(after[k], before[k]), k


def test_reused_slot_starts_fresh(mamba2):
    """A slot reused after an eviction gives a fresh engine's logits and
    tokens: admission overwrites both recurrent states, so nothing of the
    evicted request's SSM state or conv window leaks into the next."""
    cfg, params = mamba2
    first, second = _prompts(cfg, [7, 2], seed=22)

    def engine():
        return DecodeEngine(cfg, params, slots=1, page_size=4, max_ctx=16,
                            max_new_cap=6)

    def serve(eng):
        slot = eng.admit(Request(prompt=second, max_new=6))
        seen = [np.asarray(eng.st["logits"][slot])]
        for _ in range(3):
            eng.run_wave(1)
            seen.append(np.asarray(eng.st["logits"][slot]))
            eng.commit_wave()
        return np.stack(seen), np.asarray(eng.st["buf"][slot])

    used = engine()
    slot = used.admit(Request(prompt=first, max_new=6))
    used.wave(3)
    used.evict(slot)
    got, fresh = serve(used), serve(engine())
    assert np.array_equal(got[0], fresh[0])
    assert np.array_equal(got[1], fresh[1])


def test_engine_wave_length_invariance(gemma):
    """Tokens must not depend on the wave partitioning."""
    cfg, params = gemma
    reqs = [Request(prompt=p, max_new=8)
            for p in _prompts(cfg, [6, 4, 9], seed=6)]

    def run(wave):
        eng = DecodeEngine(cfg, params, slots=2, page_size=4,
                           max_ctx=24, max_new_cap=8)
        return ServeStream(eng, wave_len=wave).run(reqs)

    a, b = run(1), run(8)
    for ra, rb in zip(a, b):
        assert np.array_equal(ra.tokens, rb.tokens)


def test_engine_mid_stream_admission_zero_recompiles(gemma):
    """More requests than slots: admissions happen mid-stream, and after
    the first run has warmed the executables a second stream run with
    fresh prompt lengths drawn from the same set pays ZERO traces."""
    cfg, params = gemma
    lens = [3, 6, 9]
    mk = lambda seed: [Request(prompt=p, max_new=5)
                       for p in _prompts(cfg, lens * 2, seed=seed)]
    eng = DecodeEngine(cfg, params, slots=2, page_size=4, max_ctx=16,
                       max_new_cap=5)
    stream = ServeStream(eng, wave_len=3)
    r1 = stream.run(mk(8))                       # warmup traces allowed
    assert stream.last_report.admitted == 6
    before = trace_total()
    r2 = stream.run(mk(9))
    assert trace_total() == before, \
        "steady-state admission must not trigger recompilation"
    assert stream.last_report.traces == 0
    _assert_parity(cfg, params, mk(9), r2)


def test_engine_multi_tenant_stream(gemma, mamba):
    gcfg, gparams = gemma
    mcfg, mparams = mamba
    engines = {
        "gemma": DecodeEngine(gcfg, gparams, slots=2, page_size=4,
                              max_ctx=16, max_new_cap=5, name="gemma"),
        "mamba": DecodeEngine(mcfg, mparams, slots=2, page_size=4,
                              max_ctx=16, max_new_cap=5, name="mamba"),
    }
    jobs = []
    for i, p in enumerate(_prompts(gcfg, [4, 7, 5], seed=10)):
        jobs.append(("gemma", Request(prompt=p, max_new=5)))
    for i, p in enumerate(_prompts(mcfg, [6, 3, 8], seed=11)):
        jobs.append(("mamba", Request(prompt=p, max_new=5)))
    stream = ServeStream(engines, wave_len=3)
    results = stream.run(jobs)
    assert all(r is not None for r in results)
    for (name, req), res in zip(jobs, results):
        assert res.model == name
        cfg, params = (gcfg, gparams) if name == "gemma" else \
            (mcfg, mparams)
        want = _oracle_gen(cfg, params, req)
        assert np.array_equal(want, res.generated[:len(want)])


def test_engine_rejects_oversized_and_unsupported(gemma):
    cfg, params = gemma
    eng = DecodeEngine(cfg, params, slots=2, page_size=4, max_ctx=8,
                       max_new_cap=4)
    with pytest.raises(ValueError):
        eng.validate(Request(prompt=np.zeros(7, np.int32), max_new=4))
    with pytest.raises(ValueError):
        eng.validate(Request(prompt=np.zeros(2, np.int32), max_new=9))
    enc = get_config("seamless_m4t_large_v2")
    with pytest.raises(NotImplementedError):
        DecodeEngine(reduced(enc), None)


def test_serial_stream_matches_pipelined(gemma):
    cfg, params = gemma
    reqs = [Request(prompt=p, max_new=5)
            for p in _prompts(cfg, [5, 8, 4, 6], seed=12)]

    def run(pipeline):
        eng = DecodeEngine(cfg, params, slots=2, page_size=4,
                           max_ctx=16, max_new_cap=5)
        return ServeStream(eng, wave_len=3, pipeline=pipeline).run(reqs)

    for ra, rb in zip(run(True), run(False)):
        assert np.array_equal(ra.tokens, rb.tokens)


def test_deliveries_sum_to_emitted_finished_and_evicted(gemma):
    """Each result's deliveries start at its admission (0 tokens), their
    token counts sum to ``emitted`` and their times never decrease, for
    requests that finish and for one evicted mid-flight."""
    cfg, params = gemma
    reqs = [Request(prompt=p, max_new=n)
            for p, n in zip(_prompts(cfg, [5, 8, 4, 6], seed=14),
                            [5, 2, 4, 3])]
    eng = DecodeEngine(cfg, params, slots=2, page_size=4, max_ctx=16,
                       max_new_cap=5)
    results = ServeStream(eng, wave_len=2).run(reqs)
    evict = DecodeEngine(cfg, params, slots=2, page_size=4, max_ctx=16,
                         max_new_cap=5)
    slot = evict.admit(reqs[0], handle=0)
    evict.wave(2)
    _, cut = evict.evict(slot)
    assert 0 < cut.emitted < reqs[0].max_new
    for res in results + [cut]:
        times = [t for t, _ in res.deliveries]
        assert res.deliveries[0][1] == 0 and len(times) >= 2
        assert sum(m for _, m in res.deliveries) == res.emitted
        assert all(m > 0 for _, m in res.deliveries[1:])
        assert times == sorted(times)


def test_serving_spans_reach_the_profiler_trace(gemma, tmp_path):
    """A profiled stream run carries every ``serve.*`` span of the
    scheduler, engine and prefetch thread, with the request (``req``)
    and wave (``wave``) they belong to."""
    from jax.profiler import ProfileData

    cfg, params = gemma
    reqs = [Request(prompt=p, max_new=4)
            for p in _prompts(cfg, [5, 8, 4], seed=15)]
    eng = DecodeEngine(cfg, params, slots=2, page_size=4, max_ctx=16,
                       max_new_cap=4)
    stream = ServeStream(eng, wave_len=2)
    stream.run(reqs)                                 # compile untraced
    jax.profiler.start_trace(str(tmp_path))
    try:
        stream.run(reqs)
        slot = eng.admit(reqs[0], handle=7)
        eng.run_wave(2)          # an attempt the supervisor discards
        eng.rollback()
        eng.evict(slot)
    finally:
        jax.profiler.stop_trace()
    spans = {}
    for f in tmp_path.rglob("*.xplane.pb"):
        for plane in ProfileData.from_file(str(f)).planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("serve."):
                        spans.setdefault(ev.name, []).append(
                            {k: v for k, v in ev.stats})
    assert set(spans) == {
        "serve.sweep", "serve.wave", "serve.snapshot", "serve.dispatch",
        "serve.block", "serve.commit", "serve.sync", "serve.prefill",
        "serve.prefill_wait", "serve.admit", "serve.evict",
        "serve.rollback"}
    for name in ("serve.prefill", "serve.prefill_wait"):
        assert {int(s["req"]) for s in spans[name]} == {0, 1, 2}
    assert {int(s["req"]) for s in spans["serve.admit"]} == {0, 1, 2, 7}
    assert [int(s["req"]) for s in spans["serve.evict"]] == [7]
    waves = sorted(int(s["wave"]) for s in spans["serve.wave"])
    assert waves == list(range(len(waves))) and waves
    assert len(spans["serve.commit"]) == len(waves)
