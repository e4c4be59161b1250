"""Live elasticity under chaos (DESIGN.md §14).

The contract for every churn schedule — kills, rejoins, stragglers,
any wave boundary, even mid-flight: the elastic stream's output is
BITWISE identical to the healthy serial oracle, and with a warmed
schedule cache recovery never pays a lowering. The chaos harness
(tests/chaos.py) scripts deterministic FaultPlans; the sweep replays
them across configurations and both pipelining modes.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import chaos
from chaos import (ChaosController, FaultPlan, Kill, Rejoin, Straggle,
                   assert_bit_identical, run_plan, serial_oracle)
from repro.core.engine import CAMRConfig, CAMREngine
from repro.core.schedule import SCHEDULE_CACHE
from repro.runtime.fault import (DegradedCAMREngine, ElasticController,
                                 Membership, MembershipError,
                                 StragglerPolicy, retarget_engine)
from repro.runtime.jobstream import JobSpec, JobStream

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# detector policy for scripted Straggle events: only the synthetic
# delay (seconds) can trip the absolute timeout; real map times are
# microseconds and the huge rel_threshold keeps noise out
DETECT = StragglerPolicy(abs_timeout_s=1.0, rel_threshold=1e9,
                         patience=2, demote=True)

PLANS = [
    FaultPlan((), "healthy"),
    FaultPlan((Kill(0, 1),), "kill-first-wave"),
    FaultPlan((Kill(2, 4),), "kill-mid"),
    FaultPlan((Kill(2, 4), Rejoin(4, 4)), "kill-rejoin"),
    FaultPlan((Kill(1, 0), Rejoin(3, 0), Kill(4, 5)), "churn-twice"),
    FaultPlan((Straggle(1, 2, waves=3, delay_s=9.0),), "straggle"),
]
PLAN_BY_NAME = {p.name: p for p in PLANS}


def _run_sweep(q, k, plan, pipeline):
    specs = chaos.make_specs(q, k, waves=6, d=6)
    oracle = serial_oracle(specs)
    SCHEDULE_CACHE.warm_survivors(
        CAMREngine(specs[0].cfg, specs[0].map_fn).program)
    policy = (DETECT if any(isinstance(ev, Straggle)
                            for ev in plan.events) else None)
    for attempt in range(2):
        got, stream, ctrl = run_plan(specs, plan, policy=policy,
                                     pipeline=pipeline)
        ctx = f"q{q}k{k}:{plan.name}:pipeline={pipeline}:run{attempt}"
        assert_bit_identical(oracle, got, ctx)
        # warm-cache recovery: NO lowering on any run, first or repeat
        assert stream.last_report.cache_misses == 0, ctx
    return ctrl


@pytest.mark.parametrize("pipeline", [False, True])
@pytest.mark.parametrize("plan", ["kill-rejoin", "straggle"])
def test_chaos_quick(plan, pipeline):
    """CI-smoke subset of the sweep: one config, the two richest
    plans, both pipelining modes."""
    _run_sweep(2, 3, PLAN_BY_NAME[plan], pipeline)


@pytest.mark.slow
@pytest.mark.parametrize("q,k", [(2, 3), (3, 3), (2, 4)])
@pytest.mark.parametrize("pipeline", [False, True])
def test_chaos_sweep(q, k, pipeline):
    """Full sweep: every FaultPlan x config x pipelining mode is
    bit-identical to the healthy oracle with zero lowerings."""
    for plan in PLANS:
        _run_sweep(q, k, plan, pipeline)


def test_churn_on_a_cold_cache_then_warm():
    """Kill and rejoin mid-stream on a CLEARED schedule cache: the kill
    boundary lowers on the critical path and the output still matches
    the serial oracle bitwise. After ``warm_survivors`` the same churn
    lowers nothing."""
    specs = chaos.make_specs(2, 4, waves=8, d=32)
    oracle = serial_oracle(specs)
    plan = FaultPlan((Kill(3, 2), Rejoin(6, 2)), "kill-rejoin")
    SCHEDULE_CACHE.clear()
    got, stream, _ = run_plan(specs, plan)
    assert_bit_identical(oracle, got, "cold")
    assert stream.last_report.cache_misses > 0
    SCHEDULE_CACHE.warm_survivors(
        CAMREngine(specs[0].cfg, specs[0].map_fn).program)
    got, stream, _ = run_plan(specs, plan)
    assert_bit_identical(oracle, got, "warm")
    assert stream.last_report.cache_misses == 0


# --------------------------------------------------------------------- #
# in-flight migration: membership changes AFTER a batch mapped
# --------------------------------------------------------------------- #
def test_in_flight_kill_retargets_without_remap():
    """A worker dies while a batch is between its map and its shuffle:
    the stream re-targets that engine against the new survivor set
    (one migration, zero map recompute) and the output stays
    bit-identical. The kill fires from inside the victim wave's map
    function — deterministically after its engine was built healthy."""
    q, k, waves, kill_wave, victim = 2, 3, 5, 2, 4
    specs = chaos.make_specs(q, k, waves, d=6)
    oracle = serial_oracle(specs)
    SCHEDULE_CACHE.warm_survivors(
        CAMREngine(specs[0].cfg, specs[0].map_fn).program)

    member = Membership(q, k, policy=StragglerPolicy(demote=False))
    ctrl = ElasticController(member)
    calls = [0]

    def killing_map(job, sf):
        calls[0] += 1
        with ctrl._lock:
            if member.state[victim] != Membership.DEAD:
                member.kill(victim)
        return sf

    sp = specs[kill_wave]
    specs[kill_wave] = JobSpec(sp.cfg, killing_map, sp.datasets,
                               name=sp.name)
    stream = JobStream(elastic=ctrl, wave_batch=1, pipeline=False)
    got = stream.run(specs)
    assert_bit_identical(oracle, got, "in-flight kill")

    rep = stream.last_report
    assert rep.migrations == 1
    assert ctrl.migrations == 1
    # the victim wave's engine flipped healthy -> degraded mid-flight;
    # every later wave was BUILT degraded (no further migrations)
    assert isinstance(stream.last_engines[kill_wave], DegradedCAMREngine)
    assert not getattr(stream.last_engines[kill_wave - 1], "failed", None)
    for w in range(kill_wave, waves):
        assert stream.last_engines[w].failed == {victim}
    # zero map recompute: the killing map ran once per (job, server
    # slot) for its wave, exactly like a healthy run of the same spec
    n_churn = calls[0]
    calls[0] = 0
    member2 = Membership(q, k)
    JobStream(elastic=ElasticController(member2), wave_batch=1,
              pipeline=False).run([specs[kill_wave]])
    assert n_churn == calls[0]


def test_retarget_engine_adopts_map_state():
    cfg = CAMRConfig(q=2, k=3, gamma=1)
    rng = np.random.default_rng(1)
    Q = cfg.num_functions()
    ds = [[rng.standard_normal((Q, 4)) for _ in range(cfg.N)]
          for _ in range(cfg.J)]
    healthy = CAMREngine(cfg, chaos._identity_map).run(ds)

    eng = CAMREngine(cfg, chaos._identity_map)
    eng.map_phase(ds)
    assert retarget_engine(eng, set()) is eng       # no-op fast path
    deg = retarget_engine(eng, {3})
    assert isinstance(deg, DegradedCAMREngine)
    assert deg.servers is eng.servers               # adopted, not remapped
    assert deg.map_times is eng.map_times
    deg.shuffle_phase()
    res = JobStream._logical_slots(deg, deg.reduce_phase())
    for s in range(cfg.K):
        assert res[s].keys() == healthy[s].keys()
        for key in healthy[s]:
            np.testing.assert_array_equal(res[s][key], healthy[s][key])
    # ...and back: restoring the survivor set re-adopts the same state
    back = retarget_engine(deg, set())
    assert type(back) is CAMREngine and back.servers is eng.servers
    assert retarget_engine(deg, {3}) is deg


# --------------------------------------------------------------------- #
# straggler detection state machine
# --------------------------------------------------------------------- #
def test_straggler_flag_demote_rejoin_lifecycle():
    """live -> straggler (patience strikes) -> dead -> live again, at
    deterministic wave boundaries (no pipelining), with the replan
    receipt proving the rejoin moved zero data."""
    q, k, waves = 2, 3, 7
    specs = chaos.make_specs(q, k, waves, d=6)
    oracle = serial_oracle(specs)
    plan = FaultPlan((Straggle(1, 3, waves=3, delay_s=9.0),
                      Rejoin(5, 3)), "lifecycle")
    got, stream, ctrl = run_plan(specs, plan, policy=DETECT,
                                 pipeline=False)
    assert_bit_identical(oracle, got, "lifecycle")
    m = ctrl.membership
    assert [(kind, w) for _, kind, w in m.events] == \
        [("flag", 3), ("demote", 3), ("rejoin", 3)]
    assert m.state[3] == Membership.LIVE
    # demotion landed after wave 2's timings: waves 3-4 ran degraded,
    # wave 5 onward healthy again — all at batch boundaries
    assert stream.last_report.migrations == 0
    for w, want in enumerate([None, None, None, {3}, {3}, None, None]):
        assert (getattr(stream.last_engines[w], "failed", None) or
                None) == want, w
    # the rejoin receipt: same-K re-admission is pure re-placement
    assert m.replans[-1].moved_fraction == 0.0
    assert m.replans[-1].new_qk == (q, k)


def test_membership_transitions_and_caps():
    m = Membership(2, 3)
    with pytest.raises(MembershipError, match="outside"):
        m.kill(6)
    with pytest.raises(MembershipError, match="only dead"):
        m.rejoin(0)
    m.kill(0)
    with pytest.raises(MembershipError, match="already dead"):
        m.kill(0)
    with pytest.raises(MembershipError, match="max_failed"):
        m.kill(1)                       # cap: one concurrent failure
    assert m.demote(1) is False         # cap respected, worker stays live
    assert m.state[1] == Membership.LIVE
    assert m.failed() == {0} and 0 not in m.live()
    rep = m.rejoin(0)
    assert rep.moved_fraction == 0.0    # zero data movement certified
    m.kill(1)                           # slot free again
    assert m.failed() == {1}
    assert [e[1] for e in m.events] == ["kill", "rejoin", "kill"]
    assert m.generation == 3


def test_straggler_policy_knobs():
    base = {w: 1.0 for w in range(6)}
    # patience demands CONSECUTIVE strikes: a clean wave resets
    m = Membership(2, 3, policy=StragglerPolicy(rel_threshold=2.0,
                                                patience=2))
    assert m.observe({**base, 2: 10.0}) == []
    assert m.state[2] == Membership.STRAGGLER
    assert m.observe(base) == []                  # clean wave
    assert m.state[2] == Membership.LIVE          # flag cleared
    assert m.observe({**base, 2: 10.0}) == []
    assert m.observe({**base, 2: 10.0}) == [2]    # 2nd consecutive
    assert m.state[2] == Membership.DEAD
    # absolute timeout trips independently of the median
    m2 = Membership(2, 3, policy=StragglerPolicy(
        rel_threshold=1e9, abs_timeout_s=5.0, patience=1))
    assert m2.observe({**base, 4: 6.0}) == [4]
    # demote=False only flags
    m3 = Membership(2, 3, policy=StragglerPolicy(rel_threshold=2.0,
                                                 patience=1,
                                                 demote=False))
    assert m3.observe({**base, 1: 10.0}) == []
    assert m3.state[1] == Membership.STRAGGLER
    # min_wave_s: µs-scale waves are unmeasurable — no strikes at all
    m4 = Membership(2, 3, policy=StragglerPolicy(rel_threshold=2.0,
                                                 patience=1,
                                                 min_wave_s=1e-3))
    fast = {w: 2e-6 for w in range(6)}
    assert m4.observe({**fast, 3: 5.0}) == []
    assert m4.state[3] == Membership.LIVE
    # dead workers are ignored by the detector
    m5 = Membership(2, 3, policy=StragglerPolicy(rel_threshold=2.0,
                                                 patience=1))
    m5.kill(5)
    assert m5.observe({**base, 5: 99.0}) == []


def test_warm_survivors_makes_recovery_pure_hits():
    SCHEDULE_CACHE.clear()
    prog = CAMREngine(CAMRConfig(q=2, k=3, gamma=1),
                      chaos._identity_map).program
    assert SCHEDULE_CACHE.warm_survivors(prog) == 6   # one per worker
    s0 = SCHEDULE_CACHE.stats()
    for w in range(6):
        SCHEDULE_CACHE.degraded(prog, {w})
    s1 = SCHEDULE_CACHE.stats()
    assert s1["misses"] == s0["misses"]
    assert s1["hits"] - s0["hits"] == 6
    # k=3 double failures are all unrecoverable -> skipped, not cached
    assert SCHEDULE_CACHE.warm_survivors(prog, max_failures=2) == 6


# --------------------------------------------------------------------- #
# degraded host interpreter: dead rows are never read
# --------------------------------------------------------------------- #
def test_degraded_host_never_reads_dead_rows():
    """NaN-poison a failed worker's contribution rows: the degraded
    host lane must produce finite output bitwise equal to its own
    healthy (empty-failure) interpretation — proof that no route ever
    touches dead data."""
    from repro.core.collective import (camr_shuffle_reference, make_plan,
                                       scatter_contributions)

    q, k, d = 2, 3, 4
    from repro.runtime.fault import degraded_shuffle_host

    plan = make_plan(q, k, d)
    prog = SCHEDULE_CACHE.program(q, k, Q=plan.K)
    rng = np.random.default_rng(7)
    bg = rng.standard_normal((plan.J, k, plan.K, d)).astype(np.float32)
    contribs = scatter_contributions(plan, bg)
    healthy = degraded_shuffle_host(prog, set(), contribs)
    np.testing.assert_allclose(healthy, camr_shuffle_reference(plan, bg),
                               rtol=2e-5, atol=2e-6)
    for w in range(plan.K):
        poisoned = contribs.copy()
        poisoned[w] = np.nan
        out = degraded_shuffle_host(prog, {w}, poisoned)
        assert np.isfinite(out).all(), w
        np.testing.assert_array_equal(out, healthy, err_msg=f"worker {w}")


def test_degraded_device_executor_bitwise_vs_host_interpreter():
    """The compiled dense degraded executor (DESIGN.md §15) replays the
    host interpreter's exact fold order: bitwise-equal output for EVERY
    recoverable survivor set, across cluster shapes, with -0.0 values
    sprinkled in to catch masked-add bit rewrites (the where-select
    contract)."""
    from itertools import combinations

    from repro.runtime.fault import (build_degraded_executor,
                                     degraded_shuffle_host)

    for q, k, d in [(2, 3, 8), (2, 4, 9)]:
        prog = SCHEDULE_CACHE.program(q, k, Q=q * k, d=d)
        K, J_own = q * k, q ** (k - 2)
        rng = np.random.default_rng(11)
        contribs = rng.standard_normal(
            (K, J_own, k - 1, K, d)).astype(np.float32)
        contribs[rng.random(contribs.shape) < 0.05] = -0.0
        checked = 0
        for r in (1, 2):
            for combo in combinations(range(K), r):
                try:
                    SCHEDULE_CACHE.degraded(prog, set(combo))
                except ValueError:
                    continue
                failed = frozenset(combo)
                want = degraded_shuffle_host(prog, failed, contribs)
                exe = build_degraded_executor(prog, failed, d,
                                              np.float32)
                got = np.asarray(exe(contribs))
                assert (want.view(np.uint32)
                        == got.view(np.uint32)).all(), (q, k, combo)
                checked += 1
        assert checked >= K, (q, k, checked)


# --------------------------------------------------------------------- #
# SPMD stream elasticity (subprocess: needs a K-device mesh)
# --------------------------------------------------------------------- #
def _run_subprocess(code: str, ndev: int) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    return res.stdout


_RUN_STREAM_CHURN = textwrap.dedent("""
    import numpy as np
    from repro.compat import make_mesh
    from repro.core.collective import (ShuffleStream, make_plan,
                                       scatter_contributions)

    q, k, d = 2, 3, 8
    plan = make_plan(q, k, d)
    mesh = make_mesh((plan.K,), ("camr",))
    rng = np.random.default_rng(0)
    contribs = [scatter_contributions(
        plan, rng.standard_normal((plan.J, k, plan.K, d)).astype(
            np.float32)) for _ in range(6)]

    stream = ShuffleStream(q, k, d, mesh=mesh, wave_batch=1, depth=2)
    healthy = [np.asarray(o) for o in stream.run_waves(contribs)]
    st0 = dict(stream.stats())

    # kill worker 4 at wave 2, restore at wave 4 — same stream object
    for i, c in enumerate(contribs):
        if i == 2:
            stream.degrade({4})
        if i == 4:
            stream.restore()
        stream.submit(c)
    churned = [np.asarray(o) for o in stream.drain()]
    st1 = stream.stats()

    for h, o in zip(healthy, churned):
        np.testing.assert_array_equal(h, o)   # degraded lane == compiled
    assert st1["compiles"] == st0["compiles"] == 1, st1   # no retrace
    assert st1["swaps"] == 2 and st1["failed"] == (), st1
    assert len(stream.wave_times) == 12, len(stream.wave_times)

    # unrecoverable survivor sets are rejected up front, pre-dispatch
    try:
        stream.degrade({0, 1})
        raise SystemExit("same-class double failure must be rejected")
    except ValueError:
        pass
    print("OK")
""")


def test_shuffle_stream_degrade_restore_bitwise():
    out = _run_subprocess(_RUN_STREAM_CHURN, ndev=6)
    assert "OK" in out


_RUN_DEGRADED_DEVICE = textwrap.dedent("""
    import numpy as np
    from repro.compat import make_mesh
    from repro.core.collective import (ShuffleStream, make_plan,
                                       scatter_contributions)
    from repro.runtime.fault import degraded_shuffle_host

    q, k, d = 2, 3, 8
    plan = make_plan(q, k, d)
    prog = plan.program
    mesh = make_mesh((plan.K,), ("camr",))
    rng = np.random.default_rng(3)
    contribs = [scatter_contributions(
        plan, rng.standard_normal((plan.J, k, plan.K, d)).astype(
            np.float32)) for _ in range(4)]

    # oracle: the fault runtime's host interpreter, wave by wave
    want = [degraded_shuffle_host(prog, {4}, c) for c in contribs]

    # device lane, warmed BEFORE any failure: the degrade itself and
    # every degraded dispatch must then be completely build-free
    dev = ShuffleStream(q, k, d, mesh=mesh)
    n = dev.warm_degraded_execs(max_failures=1)
    assert n == plan.K, n                     # every single-failure set
    warmed = dev.stats()["degraded_compiles"]
    assert warmed == plan.K, dev.stats()
    dev.degrade({4})
    got = [np.asarray(o) for o in dev.run_waves(contribs)]
    st = dev.stats()
    assert st["degraded_compiles"] == warmed, st   # warm hit: 0 builds
    assert st["compiles"] == 0, st   # healthy lane never even compiled
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)   # device == host, bitwise

    # a SECOND stream of the same shape hits the process-wide
    # EXEC_CACHE: its own counter stays at zero through a live degrade
    dev2 = ShuffleStream(q, k, d, mesh=mesh)
    dev2.degrade({1})
    got2 = [np.asarray(o) for o in dev2.run_waves(contribs)]
    assert dev2.stats()["degraded_compiles"] == 0, dev2.stats()
    for c, g in zip(contribs, got2):
        np.testing.assert_array_equal(
            degraded_shuffle_host(prog, {1}, c), g)
    print("OK")
""")


def test_shuffle_stream_degraded_device_lane_warm_zero_builds():
    """Satellite gate (DESIGN.md §15): the degraded SPMD lane runs a
    pre-compiled on-device executor — warm-hit means ZERO builds at
    degrade time — and its output is bitwise the host interpreter's."""
    out = _run_subprocess(_RUN_DEGRADED_DEVICE, ndev=6)
    assert "OK" in out


_RUN_TRAINER_CHURN = textwrap.dedent("""
    import numpy as np
    from repro.configs import get_config, reduced
    from repro.data.pipeline import ShardedTokenPipeline
    from repro.runtime.train_loop import MultiModelCAMRTrainer

    cfg = reduced(get_config("granite_3_2b")).replace(
        n_layers=2, vocab=64, d_model=32, d_ff=64, n_heads=2,
        n_kv_heads=1, head_dim=16, loss_chunk=8)
    pipe = ShardedTokenPipeline(vocab=64, seq_len=8, global_batch=2)

    ref = MultiModelCAMRTrainer(cfg, q=2, k=3, seed=0)
    ref_rep = ref.train_steps(pipe, 4, mode="camr")
    ref_flat = np.asarray(ref.flat)
    ref_losses = np.asarray(ref_rep.losses)
    assert np.isfinite(ref_losses).all()

    # kill worker 2 after step 2, rejoin after step 3 — both wires
    for mode in ("camr", "camr_spmd"):
        tr = MultiModelCAMRTrainer(cfg, q=2, k=3, seed=0,
                                   spmd_oracle=(mode == "camr_spmd"))
        losses = list(tr.train_steps(pipe, 2, mode=mode).losses)
        tr.set_failed({2})
        losses += list(tr.train_steps(pipe, 1, mode=mode).losses)
        tr.set_failed(None)
        losses += list(tr.train_steps(pipe, 1, mode=mode).losses)
        np.testing.assert_array_equal(
            np.asarray(tr.flat), ref_flat,
            err_msg=f"{mode} churn diverged from uninterrupted run")
        np.testing.assert_array_equal(np.asarray(losses), ref_losses)
        if mode == "camr_spmd":
            st = tr._stream.stats()
            assert st["compiles"] == 1, st     # kill/rejoin: no retrace
            assert st["swaps"] == 2, st
            assert st["failed"] == (), st
    print("OK")
""")


@pytest.mark.slow
def test_trainer_kill_rejoin_bit_identical():
    """Mid-training churn on both grad-sync wires: the interrupted
    trajectory is bit-identical to the uninterrupted one, and the SPMD
    stream survives degrade/restore without retracing."""
    out = _run_subprocess(_RUN_TRAINER_CHURN, ndev=6)
    assert "OK" in out


# --------------------------------------------------------------------- #
# elastic runs reject conflicting configuration
# --------------------------------------------------------------------- #
def test_jobstream_rejects_elastic_plus_static_failed():
    m = Membership(2, 3)
    with pytest.raises(ValueError, match="membership"):
        JobStream(failed={0}, elastic=ElasticController(m))


def test_jobstream_wraps_bare_membership():
    specs = chaos.make_specs(2, 3, 2, d=4)
    oracle = serial_oracle(specs)
    m = Membership(2, 3, policy=StragglerPolicy(demote=False))
    m.kill(5)
    stream = JobStream(elastic=m, pipeline=False)   # bare Membership
    got = stream.run(specs)
    assert isinstance(stream.elastic, ElasticController)
    assert_bit_identical(oracle, got, "bare membership")
    assert all(e.failed == {5} for e in stream.last_engines)
