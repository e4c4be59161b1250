"""Topology-parametric lowering (DESIGN.md §16): two-level host-aware
schedules — structure, cache keying, per-edge byte model, and bitwise
identity of the SPMD executor against the flat schedule and the engine
oracle."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.collective import (ShuffleStream, camr_edge_bytes,
                                   expected_collective_calls, make_plan)
from repro.core.loads import (camr_edge_loads, camr_load_hierarchical,
                              camr_load_p2p)
from repro.core.schedule import (SCHEDULE_CACHE, AutoTopology,
                                 ScheduleCache, Topology,
                                 _normalize_topology, _program_key,
                                 resolve_topology, surviving_topology)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CONFIGS = [(2, 4, 2), (3, 4, 2), (2, 6, 2), (2, 6, 3)]


def _run_subprocess(code: str, ndev: int) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    return res.stdout


# --------------------------------------------------------------------- #
# the Topology object
# --------------------------------------------------------------------- #
def test_topology_flat_normalizes_to_none():
    assert _normalize_topology(None) is None
    assert _normalize_topology(Topology.flat()) is None
    assert _normalize_topology(Topology(hosts=1, alpha=9.0)) is None
    t = Topology.two_level(2, alpha=3.0)
    assert _normalize_topology(t) is t
    assert t.key() == (2, 3.0)
    assert Topology.flat().key() is None


def test_topology_validation():
    with pytest.raises(ValueError):
        Topology(hosts=0)
    with pytest.raises(ValueError):
        Topology(hosts=2, alpha=0.0)
    with pytest.raises(ValueError):
        Topology.two_level(1)
    with pytest.raises(ValueError):          # hosts must divide k
        Topology.two_level(2).check(2, 3)
    Topology.two_level(3).check(2, 6)        # 3 | 6: fine
    t = Topology.two_level(2)
    assert t.devices_per_host(8) == 4
    assert [t.host_of(s, 8) for s in range(8)] == [0, 0, 0, 0, 1, 1, 1, 1]


# --------------------------------------------------------------------- #
# two-level lowering structure
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("q,k,hosts", CONFIGS)
def test_two_level_tables_conserve_deliveries(q, k, hosts):
    """Masked phase-A sends + phase-B relays == the flat delivery set:
    the overlay re-routes packets, it never drops or duplicates one."""
    plan = make_plan(q, k, 2 * (k - 1), topology=Topology.two_level(hosts))
    c = k // hosts
    for stage in (1, 2):
        T = plan.program.stage_tables(stage)
        X = plan.program.host_tables(stage)
        n = T.n
        flat_deliveries = n * k * (k - 1)
        kept = int((X.a2a_send >= 0).sum())
        assert kept + X.relay_intra == flat_deliveries
        assert int((X.pp_send >= 0).sum()) == kept
        assert int(X.b_mask.sum()) == X.relay_intra
        assert int((X.b_send >= 0).sum()) == X.relay_intra
        # closed-form per-edge counts (one member per class, c per host)
        assert X.flat_inter == n * k * (k - c)
        assert X.two_level_inter == n * k * (hosts - 1)
        assert X.intra == n * k * (c - 1)
        # round 1 can never relay: a gateway needs an earlier round
        assert X.b_live[0] == ()
        # every relay permutation stays inside a host block
        dph = X.dph
        for perm in X.b_perms:
            for src, dst in perm:
                assert src // dph == dst // dph


def test_two_level_requires_hosts_dividing_k():
    with pytest.raises(ValueError):
        make_plan(2, 3, 8, topology=Topology.two_level(2))


def test_flat_plan_has_no_overlay():
    plan = make_plan(2, 3, 8)
    assert plan.topology is None
    assert plan.program.hx1 is None and plan.program.hx2 is None
    with pytest.raises(ValueError):
        plan.program.host_tables(1)
    # explicit flat topology is the SAME program as no topology
    flat = make_plan(2, 3, 8, topology=Topology.flat())
    assert flat.program is plan.program


# --------------------------------------------------------------------- #
# cache keying (satellite: no flat/two-level aliasing)
# --------------------------------------------------------------------- #
def test_schedule_cache_no_topology_aliasing():
    """Flat and two-level lowerings of the same (q, k, gamma, Q) occupy
    distinct entries and never cross-hit."""
    cache = ScheduleCache()
    flat = cache.program(2, 4, Q=8, d=6)
    assert cache.stats()["misses"] == 2 and cache.stats()["hits"] == 0
    two = cache.program(2, 4, Q=8, d=6, topology=Topology.two_level(2))
    st = cache.stats()
    assert st["misses"] == 4 and st["hits"] == 0   # zero cross-hits
    assert two is not flat
    assert two.topology is not None and flat.topology is None
    # repeat lookups hit their own entries only
    assert cache.program(2, 4, Q=8, d=6) is flat
    assert cache.program(2, 4, Q=8, d=6,
                         topology=Topology.two_level(2)) is two
    st = cache.stats()
    assert st["hits"] == 4 and st["misses"] == 4
    # alpha is a cost parameter of the key too
    other = cache.program(2, 4, Q=8, d=6,
                          topology=Topology.two_level(2, alpha=8.0))
    assert other is not two
    # flat Topology object aliases the None entry (the identity case)
    assert cache.program(2, 4, Q=8, d=6, topology=Topology.flat()) is flat


def test_program_key_distinguishes_topology():
    flat = make_plan(2, 4, 6).program
    two = make_plan(2, 4, 6, topology=Topology.two_level(2)).program
    two8 = make_plan(2, 4, 6,
                     topology=Topology.two_level(2, alpha=8.0)).program
    keys = {_program_key(flat), _program_key(two), _program_key(two8)}
    assert len(keys) == 3
    # flat's key is the pre-topology tuple + None: stable across PRs
    assert _program_key(flat)[-1] is None


def test_degraded_cache_per_topology():
    """Degraded re-lowerings key per topology (warm_survivors pre-warms
    each topology's survivor sets independently)."""
    cache = ScheduleCache()
    flat = cache.program(2, 4, Q=8)
    two = cache.program(2, 4, Q=8, topology=Topology.two_level(2))
    n_flat = cache.warm_survivors(flat, max_failures=1)
    st = cache.stats()
    n_two = cache.warm_survivors(two, max_failures=1)
    assert n_flat == n_two == 8
    assert cache.stats()["degraded"] == st["degraded"] * 2
    # same failure, different topology: distinct entries, both valid
    d_flat = cache.degraded(flat, {0})
    d_two = cache.degraded(two, {0})
    assert d_flat is not d_two
    assert d_flat.coded_rows == d_two.coded_rows


# --------------------------------------------------------------------- #
# per-edge byte model: measured tables == analytic closed form
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("q,k,hosts", CONFIGS)
def test_edge_bytes_match_hierarchical_loads(q, k, hosts):
    """camr_edge_bytes (walked from the lowered send tables) must equal
    the camr_load_hierarchical / camr_edge_loads closed forms exactly —
    measured on the tables the executor drives the wire with."""
    d = 2 * (k - 1)
    J, K = q ** (k - 1), q * k
    B = d * 4
    plan = make_plan(q, k, d, topology=Topology.two_level(hosts))
    eb = camr_edge_bytes(plan)
    for sched in ("flat", "two_level"):
        intra, inter = camr_edge_loads(q, k, hosts, schedule=sched)
        assert eb[f"{sched}_inter_bytes"] == pytest.approx(
            inter * J * K * B, abs=1e-6)
        assert eb[f"{sched}_intra_bytes"] == pytest.approx(
            intra * J * K * B, abs=1e-6)
    # the headline: two-level cuts inter-host bytes by exactly hosts/k
    assert eb["two_level_inter_bytes"] * k == eb["flat_inter_bytes"] * hosts
    if hosts < k:
        assert eb["two_level_inter_bytes"] < eb["flat_inter_bytes"]
    # both schedules move the same total (the relay rides the fast edge)
    assert (eb["flat_inter_bytes"] + eb["flat_intra_bytes"] ==
            eb["two_level_inter_bytes"] + eb["two_level_intra_bytes"])
    # alpha=1 prices both schedules at camr_load_p2p-equivalent totals
    assert camr_load_hierarchical(q, k, hosts, 1.0) == pytest.approx(
        (eb["flat_inter_bytes"] + eb["flat_intra_bytes"]) / (J * K * B))


def test_edge_bytes_requires_two_level():
    with pytest.raises(ValueError):
        camr_edge_bytes(make_plan(2, 4, 6))


# --------------------------------------------------------------------- #
# SPMD executor: two-level == flat == engine oracle, bitwise
# --------------------------------------------------------------------- #
_RUN_TWO_LEVEL = textwrap.dedent("""
    import numpy as np, jax
    from jax.sharding import PartitionSpec as P
    from repro.compat import make_mesh, shard_map
    from repro.core.collective import (make_plan, camr_shuffle,
        scatter_contributions, expected_collective_calls)
    from repro.core.engine import CAMRConfig, CAMREngine
    from repro.core.schedule import Topology
    q, k, hosts, d, dtype = {q}, {k}, {hosts}, {d}, '{dtype}'
    plan_f = make_plan(q, k, d)
    plan_t = make_plan(q, k, d, topology=Topology.two_level(hosts))
    K = plan_f.K
    rng = np.random.default_rng(5)
    bg = rng.standard_normal((plan_f.J, k, K, d)).astype(np.float32)
    if dtype != 'float32':
        bg = np.asarray(jax.numpy.asarray(bg).astype(dtype))
    contribs = scatter_contributions(plan_f, bg)
    mesh = make_mesh((K,), ('camr',))

    def run(plan, router):
        fn = jax.jit(shard_map(
            lambda c: camr_shuffle(plan, c[0], axis_name='camr',
                                   router=router)[None],
            mesh=mesh, in_specs=P('camr'), out_specs=P('camr')))
        return np.asarray(jax.block_until_ready(fn(contribs)))

    flat = run(plan_f, 'all_to_all')
    bits = np.uint32 if flat.dtype.itemsize == 4 else np.uint16
    for router in ('all_to_all', 'ppermute'):
        two = run(plan_t, router)
        np.testing.assert_array_equal(two.view(bits), flat.view(bits),
                                      err_msg=router)

    def count_collectives(jaxpr):
        n = 0
        def walk(jx):
            nonlocal n
            for eqn in jx.eqns:
                if eqn.primitive.name in ('ppermute', 'all_to_all'):
                    n += 1
                for v in eqn.params.values():
                    for sub in (v if isinstance(v, (list, tuple)) else [v]):
                        if hasattr(sub, 'eqns'):
                            walk(sub)
                        elif hasattr(sub, 'jaxpr'):
                            walk(sub.jaxpr)
        walk(jaxpr.jaxpr)
        return n

    fn = shard_map(
        lambda c: camr_shuffle(plan_t, c[0], axis_name='camr')[None],
        mesh=mesh, in_specs=P('camr'), out_specs=P('camr'))
    got = count_collectives(jax.make_jaxpr(fn)(contribs))
    want = expected_collective_calls(plan_t)['total']
    assert got == want, (got, want)

    if dtype == 'float32':
        cfg = CAMRConfig(q=q, k=k, gamma=1)
        eng = CAMREngine(cfg, lambda job, sf: sf)
        datasets = [[bg[j, t] for t in range(k)] for j in range(plan_f.J)]
        results = eng.run(datasets)
        for s in range(K):
            for j in range(plan_f.J):
                np.testing.assert_array_equal(flat[s, j], results[s][(j, s)])
    print('OK')
""")


@pytest.mark.parametrize("q,k,hosts,dtype", [
    (2, 4, 2, "float32"),
    (3, 4, 2, "float32"),
    (2, 6, 3, "float32"),
    (2, 4, 2, "bfloat16"),
])
def test_two_level_bitwise_identity(q, k, hosts, dtype):
    """The two-level executor (both routers) produces BITWISE the flat
    schedule's output — which is itself bitwise the engine oracle's —
    and traces exactly the predicted collective count."""
    out = _run_subprocess(
        _RUN_TWO_LEVEL.format(q=q, k=k, hosts=hosts, d=2 * (k - 1),
                              dtype=dtype), ndev=q * k)
    assert "OK" in out


_RUN_STREAM_TOPO = textwrap.dedent("""
    import numpy as np, jax
    from repro.compat import make_mesh
    from repro.core.collective import (ShuffleStream, make_plan,
        scatter_contributions, camr_shuffle_reference)
    from repro.core.schedule import Topology
    q, k, d, hosts = {q}, {k}, {d}, {hosts}
    plan = make_plan(q, k, d); K = plan.K
    mesh = make_mesh((K,), ('camr',))
    rng = np.random.default_rng(11)
    bgs = [rng.standard_normal((plan.J, k, K, d)).astype(np.float32)
           for _ in range(4)]
    contribs = [scatter_contributions(plan, bg) for bg in bgs]
    flat = ShuffleStream(q, k, d, mesh=mesh)
    two = ShuffleStream(q, k, d, mesh=mesh,
                        topology=Topology.two_level(hosts))
    ref = flat.run_waves(contribs)
    got = two.run_waves(contribs)
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a, b)
    # degraded/survivor-set re-lowering on the two-level topology:
    # mid-stream degrade swaps to the survivor executor and stays
    # bitwise identical to the healthy oracle (values are transport-
    # independent; only the edge each packet rides changes)
    two.warm_degraded_execs(max_failures=1)
    for i, c in enumerate(contribs):
        if i == 1:
            two.degrade({{1}})
        if i == 3:
            two.restore()
        two.submit(c)
    churned = two.drain()
    assert two.stats()['degraded_compiles'] <= K  # all pre-warmed
    for out, bg, r in zip(churned, bgs, ref):
        np.testing.assert_allclose(out, camr_shuffle_reference(plan, bg),
                                   rtol=2e-5, atol=2e-6)
        np.testing.assert_array_equal(out, r)
    print('OK')
""")


def test_two_level_stream_and_degraded_relowering():
    """ShuffleStream on a two-level topology: healthy waves bitwise
    equal the flat stream's, and a mid-stream degrade re-lowers from
    the per-topology warm cache with bit-identical outputs."""
    out = _run_subprocess(
        _RUN_STREAM_TOPO.format(q=2, k=4, d=6, hosts=2), ndev=8)
    assert "OK" in out


def test_two_level_counts_relay_lanes():
    plan = make_plan(2, 4, 6, topology=Topology.two_level(2))
    calls = expected_collective_calls(plan)
    flat_calls = expected_collective_calls(make_plan(2, 4, 6))
    assert calls["total"] > flat_calls["total"]   # relay lanes counted
    assert calls["stage12"] > flat_calls["stage12"]
    assert calls["stage3"] == flat_calls["stage3"]  # stage 3 never relays


# --------------------------------------------------------------------- #
# gateway failover (DESIGN.md §17): avoid-set lowering stays conservative
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("q,k,hosts", CONFIGS)
def test_gateway_avoid_preserves_delivery_conservation(q, k, hosts):
    """Every gateway assignment re-routes the SAME delivery set: the
    per-edge conservation counts are invariant in the avoid set, only
    the relay tables move."""
    K = q * k
    dph = K // hosts
    base = make_plan(q, k, 2 * (k - 1), topology=Topology.two_level(hosts))
    # avoid one device per host, a whole host block, and a mixed set
    avoid_sets = [frozenset({h * dph for h in range(hosts)}),
                  frozenset(range(dph)),
                  frozenset({0, K - 1})]
    for avoid in avoid_sets:
        plan = make_plan(q, k, 2 * (k - 1),
                         topology=Topology.two_level(hosts),
                         gateway_avoid=avoid)
        assert plan.program.gateway_avoid == avoid
        moved = False
        for stage in (1, 2):
            B = base.program.host_tables(stage)
            X = plan.program.host_tables(stage)
            n = plan.program.stage_tables(stage).n
            c = k // hosts
            kept = int((X.a2a_send >= 0).sum())
            assert kept + X.relay_intra == n * k * (k - 1)
            assert int((X.pp_send >= 0).sum()) == kept
            assert int(X.b_mask.sum()) == X.relay_intra
            assert int((X.b_send >= 0).sum()) == X.relay_intra
            assert X.flat_inter == n * k * (k - c)
            assert X.two_level_inter == n * k * (hosts - 1)
            assert X.intra == n * k * (c - 1)
            for perm in X.b_perms:
                for src, dst in perm:
                    assert src // dph == dst // dph
            moved = moved or not np.array_equal(X.a2a_send, B.a2a_send)
        # a whole-host avoid set cannot move that host's gateways (the
        # fallback keeps the first receiver), but cross-host sets must
        if not any(set(range(h * dph, (h + 1) * dph)) <= avoid
                   for h in range(hosts)):
            assert moved, f"avoid={sorted(avoid)} left tables unchanged"


def test_gateway_avoid_joins_cache_and_program_key():
    """Gateway assignments never alias: default vs avoid-set lowerings
    occupy distinct cache entries, and the default keeps the pre-§17
    key shape."""
    cache = ScheduleCache()
    topo = Topology.two_level(2)
    base = cache.program(2, 4, Q=8, d=6, topology=topo)
    avoided = cache.program(2, 4, Q=8, d=6, topology=topo,
                            gateway_avoid={0})
    assert avoided is not base
    assert cache.stats()["misses"] == 4 and cache.stats()["hits"] == 0
    assert cache.program(2, 4, Q=8, d=6, topology=topo,
                         gateway_avoid={0}) is avoided
    assert cache.program(2, 4, Q=8, d=6, topology=topo) is base
    # key shape: default lowerings (flat or two-level) keep their
    # pre-gateway tuple; only non-empty avoid sets extend it
    assert _program_key(base) == _program_key(avoided)[:-1]
    assert _program_key(avoided)[-1] == (0,)
    # flat collapses the avoid set (no gateways to move): same entry
    flat = cache.program(2, 4, Q=8, d=6)
    assert cache.program(2, 4, Q=8, d=6, gateway_avoid={0}) is flat


def test_gateway_avoid_validation():
    with pytest.raises(ValueError, match="outside"):
        make_plan(2, 4, 6, topology=Topology.two_level(2),
                  gateway_avoid={99})
    with pytest.raises(ValueError, match="outside"):
        ShuffleStream(2, 4, 6, mesh=None, gateway_avoid={-1})


# --------------------------------------------------------------------- #
# alpha-driven auto-pick (DESIGN.md §17 satellite)
# --------------------------------------------------------------------- #
def test_auto_topology_resolution():
    auto = Topology.auto(2, alpha=4.0)
    assert isinstance(auto, AutoTopology)
    picked = auto.resolve(2, 4)
    assert picked == Topology.two_level(2, alpha=4.0)
    # alpha = 1: analytically equal costs — tie goes to flat
    assert Topology.auto(2, alpha=1.0).resolve(2, 4) is None
    # hosts = k: two-level degenerates to flat's inter traffic
    assert Topology.auto(4, alpha=4.0).resolve(2, 4) is None
    # non-dividing hosts: no class-aligned blocks, flat
    assert Topology.auto(3, alpha=16.0).resolve(2, 4) is None
    assert Topology.auto(1, alpha=16.0).resolve(2, 4) is None
    # the pick is exactly the cost-model argmin
    for hosts, alpha in [(2, 1.5), (2, 8.0), (3, 2.0), (3, 64.0)]:
        got = Topology.auto(hosts, alpha=alpha).resolve(2, 6)
        intra, inter = camr_edge_loads(2, 6, hosts, schedule="flat")
        flat_cost = intra + alpha * inter
        two_cost = camr_load_hierarchical(2, 6, hosts, alpha)
        if flat_cost - two_cost > 1e-9 * flat_cost:
            assert got == Topology.two_level(hosts, alpha=alpha)
        else:
            assert got is None
    # identity: alpha = 1 prices both schedules at camr_load_p2p
    assert camr_load_hierarchical(2, 6, 2, 1.0) == pytest.approx(
        camr_load_p2p(2, 6))


def test_auto_topology_resolves_through_cache_and_plan():
    """An AutoTopology marker is transparent everywhere a Topology is
    accepted — the cache keys the RESOLVED pick (no auto/concrete
    aliasing)."""
    cache = ScheduleCache()
    two = cache.program(2, 4, Q=8, topology=Topology.two_level(2))
    auto = cache.program(2, 4, Q=8, topology=Topology.auto(2, alpha=4.0))
    assert auto is two                       # resolved to the same entry
    flat = cache.program(2, 4, Q=8)
    assert cache.program(2, 4, Q=8,
                         topology=Topology.auto(2, alpha=1.0)) is flat
    plan = make_plan(2, 4, 6, topology=Topology.auto(2, alpha=4.0))
    assert plan.topology == Topology.two_level(2, alpha=4.0)
    assert resolve_topology(Topology.auto(2, alpha=1.0), 2, 4) is None


def test_surviving_topology():
    assert surviving_topology(2, 4) == Topology.two_level(2)
    assert surviving_topology(3, 4) is None          # 3 does not divide 4
    assert surviving_topology(1, 4) is None          # single host: flat
    assert surviving_topology(3, 6, alpha=8.0) == \
        Topology.two_level(3, alpha=8.0)
    with pytest.raises(ValueError):
        surviving_topology(0, 4)


def test_warm_host_survivors_prepays_every_host_loss():
    """After warm_host_survivors, every surviving-host re-lowering of
    up to max_host_failures losses is a pure cache hit."""
    cache = ScheduleCache()
    prog = cache.program(2, 6, Q=12, d=10, topology=Topology.two_level(3))
    n = cache.warm_host_survivors(prog, max_host_failures=2)
    assert n == 2                        # hosts 2 and 1 survivor layouts
    before = cache.stats()
    for lost in (1, 2):
        t = surviving_topology(3 - lost, 6)
        cache.program(2, 6, Q=12, d=10, topology=t)
    st = cache.stats()
    assert st["misses"] == before["misses"], "host recovery must be a " \
        "pure cache hit after warm_host_survivors"
    assert st["hits"] > before["hits"]
    # flat stream has no hosts to lose
    flat = cache.program(2, 6, Q=12, d=10)
    with pytest.raises(ValueError):
        cache.warm_host_survivors(flat)
    with pytest.raises(ValueError):
        cache.warm_host_survivors(prog, max_host_failures=3)


# --------------------------------------------------------------------- #
# SPMD executor: every gateway assignment bitwise == flat == oracle
# --------------------------------------------------------------------- #
_RUN_GATEWAY_SWEEP = textwrap.dedent("""
    import numpy as np, jax
    from jax.sharding import PartitionSpec as P
    from repro.compat import make_mesh, shard_map
    from repro.core.collective import (make_plan, camr_shuffle,
        scatter_contributions)
    from repro.core.schedule import Topology
    q, k, hosts, d = {q}, {k}, {hosts}, {d}
    plan_f = make_plan(q, k, d)
    K = plan_f.K
    dph = K // hosts
    rng = np.random.default_rng(7)
    bg = rng.standard_normal((plan_f.J, k, K, d)).astype(np.float32)
    contribs = scatter_contributions(plan_f, bg)
    mesh = make_mesh((K,), ('camr',))

    def run(plan, router='all_to_all'):
        fn = jax.jit(shard_map(
            lambda c: camr_shuffle(plan, c[0], axis_name='camr',
                                   router=router)[None],
            mesh=mesh, in_specs=P('camr'), out_specs=P('camr')))
        return np.asarray(jax.block_until_ready(fn(contribs)))

    flat = run(plan_f)
    # single-device avoids, one avoided-device-per-host, and a whole
    # host block (fallback keeps a gateway): all bitwise == flat
    sweeps = ([frozenset({{s}}) for s in range(K)]
              + [frozenset({{h * dph for h in range(hosts)}}),
                 frozenset(range(dph))])
    for avoid in sweeps:
        plan_a = make_plan(q, k, d, topology=Topology.two_level(hosts),
                           gateway_avoid=avoid)
        for router in ('all_to_all', 'ppermute'):
            got = run(plan_a, router)
            np.testing.assert_array_equal(
                got, flat, err_msg=f"avoid={{sorted(avoid)}} {{router}}")
    print('OK')
""")


@pytest.mark.parametrize("q,k,hosts", [(2, 4, 2), (2, 6, 3)])
def test_gateway_failover_bitwise_sweep(q, k, hosts):
    """Outputs are BITWISE equal to flat (hence to the engine oracle,
    test_two_level_bitwise_identity) for EVERY gateway assignment —
    gateway choice is pure routing policy."""
    out = _run_subprocess(
        _RUN_GATEWAY_SWEEP.format(q=q, k=k, hosts=hosts, d=2 * (k - 1)),
        ndev=q * k)
    assert "OK" in out
