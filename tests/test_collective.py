"""shard_map CAMR shuffle vs oracle — run in subprocesses with K host
devices (the main test process must keep seeing 1 device)."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.collective import (CAMRPlan, ShuffleStream,
                                   camr_collective_bytes, camr_shuffle,
                                   expected_collective_calls, make_plan)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_RUN = textwrap.dedent("""
    import numpy as np, jax
    from jax.sharding import PartitionSpec as P
    from repro.compat import make_mesh, shard_map
    from repro.core.collective import (make_plan, camr_shuffle,
        scatter_contributions, camr_shuffle_reference, uncoded_reduce_scatter,
        expected_collective_calls)
    q, k, d = {q}, {k}, {d}
    plan = make_plan(q, k, d); K = plan.K
    rng = np.random.default_rng(0)
    bg = rng.standard_normal((plan.J, k, K, d)).astype(np.float32)
    contribs = scatter_contributions(plan, bg)
    mesh = make_mesh((K,), ('camr',))
    ref = camr_shuffle_reference(plan, bg)

    def count_collectives(jaxpr):
        n = dict(ppermute=0, all_to_all=0)
        def walk(jx):
            for eqn in jx.eqns:
                if eqn.primitive.name in n:
                    n[eqn.primitive.name] += 1
                for v in eqn.params.values():
                    for sub in (v if isinstance(v, (list, tuple)) else [v]):
                        if hasattr(sub, 'eqns'):
                            walk(sub)
                        elif hasattr(sub, 'jaxpr'):
                            walk(sub.jaxpr)
        walk(jaxpr.jaxpr)
        return n

    first = None
    for router, codec in [('all_to_all', 'fused'), ('ppermute', 'fused'),
                          ('all_to_all', 'multipass')]:
        fn = shard_map(
            lambda c: camr_shuffle(plan, c[0], axis_name='camr',
                                   router=router, codec=codec)[None],
            mesh=mesh, in_specs=P('camr'), out_specs=P('camr'))
        out = np.asarray(jax.jit(fn)(contribs))
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-6)
        # fused and multipass codecs are BIT-identical, not just close
        first = out if first is None else first
        np.testing.assert_array_equal(out, first, err_msg=(router, codec))
        counts = count_collectives(jax.make_jaxpr(fn)(contribs))
        want = expected_collective_calls(plan, router)
        got12 = counts['all_to_all'] + counts['ppermute'] - (q - 1)
        assert got12 == want['stage12'], (router, counts, want)
        assert counts['ppermute'] + counts['all_to_all'] == want['total']

    g = jax.jit(shard_map(
        lambda c: uncoded_reduce_scatter(c[0], axis_name='camr',
                                         plan=plan)[None],
        mesh=mesh, in_specs=P('camr'), out_specs=P('camr')))
    np.testing.assert_allclose(np.asarray(g(contribs)), ref,
                               rtol=2e-5, atol=2e-6)
    print('OK')
""")

# seeded regression pinned to the ENGINE oracle: the SPMD collective and
# the numpy interpreter execute the same ShuffleProgram in the same
# canonical combine order (delivered batch + ascending fold), so their
# per-device outputs must be BITWISE equal — the contract the training
# integration's cross-mode parameter identity rests on (DESIGN.md §11).
_RUN_ENGINE = textwrap.dedent("""
    import numpy as np, jax
    from jax.sharding import PartitionSpec as P
    from repro.compat import make_mesh, shard_map
    from repro.core.collective import (make_plan, camr_shuffle,
        scatter_contributions)
    from repro.core.engine import CAMRConfig, CAMREngine
    q, k, d = {q}, {k}, {d}
    plan = make_plan(q, k, d); K = plan.K
    rng = np.random.default_rng({seed})
    bg = rng.standard_normal((plan.J, k, K, d)).astype(np.float32)

    # engine run: gamma=1, Q=K; map_fn(job, subfile t) = bg[job, t]
    cfg = CAMRConfig(q=q, k=k, gamma=1)
    eng = CAMREngine(cfg, lambda job, sf: sf)
    datasets = [[bg[j, t] for t in range(k)] for j in range(plan.J)]
    results = eng.run(datasets)
    eng.verify(datasets, results)

    contribs = scatter_contributions(plan, bg)
    mesh = make_mesh((K,), ('camr',))
    f = jax.jit(shard_map(
        lambda c: camr_shuffle(plan, c[0], axis_name='camr')[None],
        mesh=mesh, in_specs=P('camr'), out_specs=P('camr')))
    out = np.asarray(f(contribs))
    for s in range(K):
        for j in range(plan.J):
            np.testing.assert_array_equal(
                out[s, j], results[s][(j, s)],
                err_msg=f'device {{s}} job {{j}}')
    print('OK')
""")


# ShuffleStream (DESIGN.md §9): async double-buffered multi-wave
# dispatch, same-shaped waves stacked along d into ONE program
# execution. Per-wave outputs must be bit-identical to single-wave
# dispatch (the codec is elementwise per value column).
_RUN_STREAM = textwrap.dedent("""
    import numpy as np, jax
    from jax.sharding import PartitionSpec as P
    from repro.compat import make_mesh, shard_map
    from repro.core.collective import (ShuffleStream, make_plan,
        camr_shuffle, camr_shuffle_reference, scatter_contributions)
    q, k, d, waves = {q}, {k}, {d}, 5
    plan = make_plan(q, k, d); K = plan.K
    rng = np.random.default_rng(3)
    bgs = [rng.standard_normal((plan.J, k, K, d)).astype(np.float32)
           for _ in range(waves)]
    contribs = [scatter_contributions(plan, bg) for bg in bgs]
    mesh = make_mesh((K,), ('camr',))
    serial_fn = jax.jit(shard_map(
        lambda c: camr_shuffle(plan, c[0], axis_name='camr')[None],
        mesh=mesh, in_specs=P('camr'), out_specs=P('camr')))
    serial = [np.asarray(serial_fn(c)) for c in contribs]
    for wave_batch in (1, 2, 3):
        stream = ShuffleStream(q, k, d, mesh=mesh, wave_batch=wave_batch,
                               depth=2)
        outs = stream.run_waves(contribs)
        assert len(outs) == waves
        for out, bg, ser in zip(outs, bgs, serial):
            np.testing.assert_allclose(
                out, camr_shuffle_reference(plan, bg),
                rtol=2e-5, atol=2e-6)
            np.testing.assert_array_equal(out, ser)
    # incremental submit/drain keeps submission order
    stream = ShuffleStream(q, k, d, mesh=mesh, wave_batch=2, depth=1)
    for c in contribs[:3]:
        stream.submit(c)
    outs = stream.drain()
    for out, ser in zip(outs, serial):
        np.testing.assert_array_equal(out, ser)
    # sync(): the multi-step training path — one compiled executor
    # reused across calls, device-resident output, bit-identical to
    # the per-wave dispatch
    stream = ShuffleStream(q, k, d, mesh=mesh)
    for c, ser in zip(contribs, serial):
        got = stream.sync(c)
        assert isinstance(got, jax.Array)
        np.testing.assert_array_equal(np.asarray(got), ser)
    st = stream.stats()
    assert st['dispatches'] == len(contribs) and st['compiles'] == 1, st
    print('OK')
""")


def _run_subprocess(code: str, ndev: int) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    return res.stdout


@pytest.mark.parametrize("q,k,d", [(2, 3, 8), (4, 3, 16), (3, 4, 9),
                                   (2, 4, 6), (5, 3, 8)])
def test_camr_shuffle_multidevice(q, k, d):
    out = _run_subprocess(_RUN.format(q=q, k=k, d=d), ndev=q * k)
    assert "OK" in out


@pytest.mark.parametrize("q,k,d,seed", [(2, 3, 8, 7), (4, 3, 16, 11)])
def test_camr_shuffle_matches_engine_oracle(q, k, d, seed):
    """The SPMD executor and the numpy engine interpret the SAME
    ShuffleProgram: per-device outputs must match the engine's reduce
    results (seeded regression for the decode path)."""
    out = _run_subprocess(_RUN_ENGINE.format(q=q, k=k, d=d, seed=seed),
                          ndev=q * k)
    assert "OK" in out


@pytest.mark.parametrize("q,k,d", [(2, 3, 8), (3, 4, 9)])
def test_shuffle_stream_multidevice(q, k, d):
    """Async double-buffered ShuffleStream == per-wave serial dispatch,
    bit for bit, at every wave_batch width."""
    out = _run_subprocess(_RUN_STREAM.format(q=q, k=k, d=d), ndev=q * k)
    assert "OK" in out


def test_expected_collective_calls_model():
    plan = make_plan(4, 3, 16)
    want = expected_collective_calls(plan, "all_to_all")
    # the headline number: 2*(k-1) batched collectives for stages 1+2,
    # independent of J
    assert want["stage12"] == 2 * (plan.k - 1) == 4
    pp = expected_collective_calls(plan, "ppermute")
    assert pp["stage12"] == 2 * (plan.k - 1) * plan.q


def test_plan_validation():
    with pytest.raises(ValueError):
        make_plan(2, 2, 8)  # k >= 3 for the TPU path
    with pytest.raises(ValueError):
        make_plan(2, 3, 7)  # d not divisible by k-1


def test_codec_dtype_guard():
    """Uncodable dtypes fail AT ENTRY with an actionable message (not a
    bare TypeError from the wire packing deep inside the trace); the
    16-bit floats are NOT rejected — they ride the packed codec lane
    (DESIGN.md §12)."""
    import jax.numpy as jnp

    from repro.core.collective import CODEC_DTYPES, check_codec_dtype
    plan = make_plan(2, 3, 8)
    # numpy f64 (jnp.zeros would silently truncate to f32 without x64)
    bad = np.zeros((plan.J_own, plan.k - 1, plan.K, plan.d),
                   np.float64)
    with pytest.raises(TypeError, match="astype"):
        camr_shuffle(plan, bad, axis_name="camr")
    # the guard names the entry point, so users see WHERE to cast
    with pytest.raises(TypeError, match="camr_shuffle"):
        camr_shuffle(plan, bad, axis_name="camr")
    with pytest.raises(TypeError, match="int8"):
        check_codec_dtype(jnp.int8, "camr_shuffle")
    # bf16/f16 pass every entry guard (the packed 16-bit lane) — the
    # stale advice to cast them UP to f32 would double bytes-on-wire
    for name in ("bfloat16", "float16"):
        assert name in CODEC_DTYPES
        check_codec_dtype(jnp.dtype(name), "camr_shuffle")
    # ShuffleStream rejects uncodable waves at submit, never mid-flight
    stream = ShuffleStream(2, 3, 8, mesh=None)
    wave = np.zeros((stream.K, 2, 2, stream.K, 8), np.float64)
    with pytest.raises(TypeError, match="ShuffleStream"):
        stream.submit(wave)
    # ...and accepts a packed-lane wave (wave_batch=2: no dispatch, no
    # mesh needed — this asserts the GUARD, not the execution)
    stream16 = ShuffleStream(2, 3, 8, mesh=None, wave_batch=2)
    stream16.submit(np.zeros((stream16.K, 2, 2, stream16.K, 8),
                             jnp.bfloat16))


def test_codec_validation():
    import jax.numpy as jnp
    plan = make_plan(2, 3, 8)
    ok = jnp.zeros((plan.J_own, plan.k - 1, plan.K, plan.d), jnp.float32)
    with pytest.raises(ValueError, match="codec"):
        camr_shuffle(plan, ok, axis_name="camr", codec="nope")
    with pytest.raises(ValueError, match="codec"):
        ShuffleStream(2, 3, 8, mesh=None, codec="nope")


def test_shuffle_stream_validation():
    """Width/k checks fire at construction, never mid-stream (a partial
    trailing batch must not be able to fail after waves completed)."""
    with pytest.raises(ValueError):
        ShuffleStream(2, 2, 8, mesh=None)   # k >= 3
    with pytest.raises(ValueError):
        ShuffleStream(2, 3, 9, mesh=None)   # d % (k-1)
    with pytest.raises(ValueError):
        ShuffleStream(2, 3, 8, mesh=None, depth=0)


def test_plan_tables_consistent():
    plan = make_plan(3, 3, 8)
    K, J_own = plan.K, plan.J_own
    assert plan.owned_jobs.shape == (K, J_own)
    # each job appears in exactly k owner lists
    flat = plan.owned_jobs.ravel().tolist()
    for j in range(plan.J):
        assert flat.count(j) == plan.k
    # stage-3 permutations: q-1 full intra-class cyclic shifts
    assert len(plan.s3_perms) == plan.q - 1
    for perm in plan.s3_perms:
        assert len(perm) == K
        assert sorted(p[0] for p in perm) == list(range(K))
        assert sorted(p[1] for p in perm) == list(range(K))


def test_collective_bytes_model():
    """p2p byte counts: stages 1-2 carry k packets of d/(k-1) per group per
    round; totals beat a dense ring-psum of [J, K, d]."""
    plan = make_plan(2, 3, 8)
    b = camr_collective_bytes(plan, itemsize=4)
    K, J, d, k = plan.K, plan.J, plan.d, plan.k
    assert b["stage1"] == J * (k - 1) * (d // (k - 1)) * 4 * k
    assert b["stage3"] == (plan.q - 1) * plan.J_own * d * 4 * K
    assert b["camr_total"] < b["psum_ring_total"]
