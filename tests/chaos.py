"""Deterministic chaos harness for the elastic runtime (DESIGN.md §14)
and the self-healing serving stack (DESIGN.md §15).

A :class:`FaultPlan` scripts worker churn against wave indices —
``Kill(wave, worker)``, ``Rejoin(wave, worker)``, and
``Straggle(wave, worker, waves, delay_s)`` — and
:class:`ChaosController` replays it through the
:class:`~repro.runtime.fault.ElasticController` hooks: kills/rejoins
fire when the stream starts the scripted wave, straggles inflate the
observed map timings the straggler detector sees. Everything is
deterministic: no randomness, no real clocks — the synthetic
``delay_s`` rides on top of whatever the engine measured, so a plan
replays identically on any machine.

The contract every plan must satisfy (asserted by
:func:`assert_bit_identical` in tests/test_elastic.py's sweep): the
elastic stream's output is BITWISE equal to the healthy serial oracle
for every churn schedule, and — after
:meth:`~repro.core.schedule.ScheduleCache.warm_survivors` — recovery
never pays a lowering.

The SERVING side scripts faults against decode-wave indices:
``WaveCrash(wave, times)`` raises between the device wave and its
commit (the supervisor must roll back to the wave-boundary snapshot
and retry), ``SlotPoison(wave, slot)`` corrupts one live slot's logits
to NaN on device (the jitted wave's sentinel must quarantine exactly
that slot), and ``WaveLatency(wave, delay_s)`` inflates the OBSERVED
wave wall time (drives the timeout-retry path — again no real sleeps).
:class:`ServeChaosController` also provides the stream's deadline
clock: a virtual time that advances ``tick_s`` per committed wave, so
deadline storms replay identically on any machine. The serving
contract (tests/test_serve_chaos.py): every request terminates with an
explicit status, survivors are BITWISE identical to the fault-free
run, and recovery pays zero retraces.

No ``test_`` prefix: this module is the harness, not the suite.
"""

from dataclasses import dataclass

import numpy as np

from repro.core.engine import CAMRConfig, CAMREngine
from repro.runtime.fault import (ElasticController, HostMembership,
                                 Membership, StragglerPolicy)
from repro.runtime.jobstream import JobSpec, JobStream
from repro.runtime.serve import ServeStream, WaveCrashError

__all__ = ["Kill", "Rejoin", "Straggle", "KillHost", "RejoinHost",
           "CorruptPacket", "FaultPlan", "ChaosController",
           "make_specs", "serial_oracle", "run_plan",
           "make_shuffle_waves", "run_host_plan",
           "assert_bit_identical", "WaveCrash", "SlotPoison",
           "WaveLatency", "ServeFaultPlan", "ServeChaosController",
           "run_serve_plan"]


@dataclass(frozen=True)
class Kill:
    """Worker drops dead when wave ``wave`` starts (silent after map)."""

    wave: int
    worker: int


@dataclass(frozen=True)
class Rejoin:
    """Dead worker re-admitted when wave ``wave`` starts (pure
    re-placement: the replan receipt proves zero data movement)."""

    wave: int
    worker: int


@dataclass(frozen=True)
class Straggle:
    """Worker's observed map time inflated by ``delay_s`` for waves
    ``wave .. wave + waves - 1`` — what the straggler detector sees,
    not a real sleep, so plans replay deterministically."""

    wave: int
    worker: int
    waves: int = 1
    delay_s: float = 5.0


@dataclass(frozen=True)
class KillHost:
    """Whole host ``host`` drops when wave ``wave`` starts — ONE
    correlated fault domain (DESIGN.md §17): its entire class-major
    device block dies at once, and the stream must re-home onto the
    surviving-host topology (two-level while divisibility holds, else
    flat) bitwise-identically."""

    wave: int
    host: int


@dataclass(frozen=True)
class RejoinHost:
    """Dead host re-admitted when wave ``wave`` starts; the stream
    re-homes back onto the larger host set (a warm cache hit)."""

    wave: int
    host: int


@dataclass(frozen=True)
class CorruptPacket:
    """One coded wire word of ``device``'s stage-``stage`` Δ is
    bit-flipped by ``bits`` in transit during wave ``wave`` — the
    integrity lane must detect it via the packet checksum and replay
    the wave bitwise, never silently mis-reduce. ``row=None`` targets
    the device's first participating group row (guaranteed on-wire)."""

    wave: int
    stage: int = 1
    device: int = 0
    row: int | None = None
    word: int = 0
    bits: int = 1


@dataclass(frozen=True)
class FaultPlan:
    """A named, scripted churn schedule (a tuple of events)."""

    events: tuple
    name: str = ""

    def workers(self) -> frozenset:
        return frozenset(w for w in (getattr(ev, "worker", None)
                                     for ev in self.events)
                         if w is not None)

    def hosts(self) -> frozenset:
        return frozenset(h for h in (getattr(ev, "host", None)
                                     for ev in self.events)
                         if h is not None)


class ChaosController(ElasticController):
    """Replays a :class:`FaultPlan` through the elastic hooks.

    Kills/rejoins apply exactly once, when their wave starts (under the
    controller lock, so an in-flight batch's re-target sees them
    atomically); straggles perturb the timing dict fed to
    :meth:`Membership.observe`.
    """

    def __init__(self, plan: FaultPlan, membership: Membership):
        super().__init__(membership)
        self.plan = plan
        self._applied: set = set()

    def on_wave_start(self, wave: int) -> None:
        for i, ev in enumerate(self.plan.events):
            if i in self._applied or ev.wave != wave:
                continue
            if isinstance(ev, Kill):
                self.membership.kill(ev.worker)
                self._applied.add(i)
            elif isinstance(ev, Rejoin):
                self.membership.rejoin(ev.worker)
                self._applied.add(i)

    def on_wave_timings(self, wave, timings):
        for ev in self.plan.events:
            if (isinstance(ev, Straggle)
                    and ev.wave <= wave < ev.wave + ev.waves
                    and ev.worker in timings):
                timings[ev.worker] = timings[ev.worker] + ev.delay_s
        return timings


# --------------------------------------------------------------------- #
# plan driver
# --------------------------------------------------------------------- #
def _identity_map(job, sf):
    return sf


def make_specs(q: int, k: int, waves: int, d: int = 8,
               seed: int = 0) -> list:
    """Waves of pre-mapped values (map = identity)."""
    cfg = CAMRConfig(q=q, k=k, gamma=1)
    Q = cfg.num_functions()
    rng = np.random.default_rng(seed)
    return [JobSpec(cfg, _identity_map,
                    [[rng.standard_normal((Q, d)).astype(np.float32)
                      for _ in range(cfg.N)] for _ in range(cfg.J)],
                    name=f"wave{w}")
            for w in range(waves)]


def serial_oracle(specs) -> list:
    """Healthy serial runs — the bit-identity reference for any churn."""
    return [CAMREngine(sp.cfg, sp.map_fn, combine=sp.combine).run(
        sp.datasets) for sp in specs]


def run_plan(specs, plan: FaultPlan, *, policy=None, pipeline=False,
             wave_batch=1):
    """Run ``specs`` through an elastic JobStream under ``plan``.

    Default policy disables timing-based demotion so scripted plans
    stay deterministic (µs-scale map noise must not steal the
    ``max_failed`` slot); straggler-detection tests pass an explicit
    policy with ``abs_timeout_s`` instead. Returns
    ``(results, stream, controller)``.
    """
    q, k = specs[0].cfg.q, specs[0].cfg.k
    policy = policy or StragglerPolicy(demote=False)
    ctrl = ChaosController(plan, Membership(q, k, policy=policy))
    stream = JobStream(elastic=ctrl, wave_batch=wave_batch,
                       pipeline=pipeline)
    return stream.run(specs), stream, ctrl


def make_shuffle_waves(q: int, k: int, waves: int, d: int = 12,
                       seed: int = 0, dtype=np.float32, mesh=None):
    """Waves of SPMD shuffle contributions plus their healthy oracle:
    ``(contribs [W][K, J_own, k-1, K, d], oracle [W][K, J, d])``.

    With a ``mesh``, the oracle is the HEALTHY flat stream's outputs —
    the bitwise anchor of §16/§17 (every topology, gateway assignment,
    and recovery path must match it word-for-word), itself gated
    allclose against the numpy reduction reference here so the anchor
    is numerically grounded. Without a mesh, the numpy reference is
    returned directly (allclose-grade only: the coded path reduces in
    a different association order)."""
    from repro.core.collective import (ShuffleStream,
                                       camr_shuffle_reference, make_plan,
                                       scatter_contributions)
    plan = make_plan(q, k, d)
    rng = np.random.default_rng(seed)
    contribs, refs = [], []
    for _ in range(waves):
        bg = rng.standard_normal(
            (plan.J, k, plan.K, d)).astype(np.float32).astype(dtype)
        contribs.append(scatter_contributions(plan, bg))
        refs.append(camr_shuffle_reference(plan, np.asarray(bg)))
    if mesh is None:
        return contribs, refs
    oracle = ShuffleStream(q, k, d, mesh=mesh).run_waves(contribs)
    rtol, atol = ((2e-5, 2e-6) if np.dtype(dtype) == np.float32
                  else (6e-2, 1e-1))       # bf16 wire: ~8-bit mantissa
    for got, ref in zip(oracle, refs):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=rtol, atol=atol)
    return contribs, oracle


def run_host_plan(q: int, k: int, d: int, contribs, plan: FaultPlan, *,
                  mesh, hosts: int, verify_wire: bool = False,
                  warm: bool = True, axis_name: str = "camr"):
    """Run shuffle waves through a two-level :class:`ShuffleStream`
    under a host-granularity ``plan`` (DESIGN.md §17).

    ``KillHost``/``RejoinHost`` events drive a :class:`HostMembership`
    and re-home the stream onto its ``current_topology()`` — two-level
    over the survivors while divisibility holds, else the flat
    fallback; ``CorruptPacket`` arms the stream's one-shot wire fault
    (needs ``verify_wire=True``). Deterministic: faults fire exactly
    when their wave is submitted, one wave per dispatch. Returns
    ``(outputs, stream, host_membership)``.
    """
    from repro.core.collective import ShuffleStream
    from repro.core.schedule import Topology

    topo = Topology.two_level(hosts)
    hm = HostMembership(q, k, topo)
    stream = ShuffleStream(q, k, d, mesh=mesh, axis_name=axis_name,
                           topology=topo, verify_wire=verify_wire)
    if warm:
        stream.warm_host_survivors(max_host_failures=hosts - 1)
    applied: set = set()
    outs = []
    for w, contrib in enumerate(contribs):
        for i, ev in enumerate(plan.events):
            if i in applied or ev.wave != w:
                continue
            if isinstance(ev, KillHost):
                hm.kill_host(ev.host)
                stream.set_topology(hm.current_topology())
                applied.add(i)
            elif isinstance(ev, RejoinHost):
                hm.rejoin_host(ev.host)
                stream.set_topology(hm.current_topology())
                applied.add(i)
            elif isinstance(ev, CorruptPacket):
                stream.inject_corruption(stage=ev.stage,
                                         device=ev.device, row=ev.row,
                                         word=ev.word, bits=ev.bits)
                applied.add(i)
        outs.extend(stream.run_waves([contrib]))
    return outs, stream, hm


def assert_bit_identical(oracle, got, context="") -> None:
    for w, (want, res) in enumerate(zip(oracle, got)):
        for s, (a, b) in enumerate(zip(want, res)):
            assert a.keys() == b.keys(), (context, w, s)
            for key in a:
                assert np.array_equal(a[key], b[key]), \
                    (context, w, s, key)


# --------------------------------------------------------------------- #
# serving chaos (DESIGN.md §15): wave crashes, slot poison, latency
# --------------------------------------------------------------------- #
@dataclass(frozen=True)
class WaveCrash:
    """The first ``times`` attempts of committed wave ``wave`` die
    between the device wave and its commit — the supervisor must roll
    back to the snapshot and replay bitwise."""

    wave: int
    times: int = 1


@dataclass(frozen=True)
class SlotPoison:
    """Live slot ``slot``'s carried logits are corrupted to NaN on
    device when wave ``wave`` starts; the jitted wave's sentinel — not
    host code — must quarantine exactly that slot."""

    wave: int
    slot: int


@dataclass(frozen=True)
class WaveLatency:
    """The observed wall time of the first ``times`` attempts of wave
    ``wave`` is inflated by ``delay_s`` — what the timeout supervisor
    sees, not a real sleep, so timeout-retry plans replay
    deterministically (bounded ``times`` lets the retry recover)."""

    wave: int
    delay_s: float = 60.0
    times: int = 1


@dataclass(frozen=True)
class ServeFaultPlan:
    """A named, scripted serving fault schedule."""

    events: tuple
    name: str = ""


class ServeChaosController:
    """Replays a :class:`ServeFaultPlan` through the
    :class:`~repro.runtime.serve.ServeStream` chaos hooks, and serves
    as the stream's deterministic deadline clock (virtual time starts
    at 0 and advances ``tick_s`` per OBSERVED wave attempt — crashed
    attempts never reach ``on_wave_done`` and do not advance it, so a
    replayed-after-crash wave sees the same clock)."""

    def __init__(self, plan: ServeFaultPlan, tick_s: float = 1.0):
        self.plan = plan
        self.tick_s = tick_s
        self._t = 0.0
        self._crashes: dict[int, int] = {}
        self._lat: dict[int, int] = {}
        self._poisoned: set = set()
        self.injected_crashes = 0
        self.injected_poisons = 0
        for i, ev in enumerate(plan.events):
            if isinstance(ev, WaveCrash):
                self._crashes[i] = ev.times
            elif isinstance(ev, WaveLatency):
                self._lat[i] = ev.times

    # the stream's deadline clock (virtual, per-wave ticks)
    def now(self) -> float:
        return self._t

    def on_wave_start(self, model, wave, engine) -> None:
        for i, ev in enumerate(self.plan.events):
            if (isinstance(ev, SlotPoison) and ev.wave == wave
                    and i not in self._poisoned
                    and ev.slot in engine._live):
                engine.poison_slot(ev.slot)
                self._poisoned.add(i)
                self.injected_poisons += 1

    def on_wave_crash(self, model, wave, engine) -> None:
        for i, ev in enumerate(self.plan.events):
            if (isinstance(ev, WaveCrash) and ev.wave == wave
                    and self._crashes.get(i, 0) > 0):
                self._crashes[i] -= 1
                self.injected_crashes += 1
                raise WaveCrashError(
                    f"chaos: injected crash of wave {wave} "
                    f"(plan {self.plan.name!r})")

    def on_wave_done(self, model, wave, engine, wall_s: float) -> float:
        for i, ev in enumerate(self.plan.events):
            if (isinstance(ev, WaveLatency) and ev.wave == wave
                    and self._lat.get(i, 0) > 0):
                self._lat[i] -= 1
                wall_s = wall_s + ev.delay_s
        self._t += self.tick_s       # attempt observed: clock ticks
        return wall_s


def run_serve_plan(engine, requests, plan: ServeFaultPlan, *,
                   tick_s: float = 1.0, wave_len: int = 8,
                   pipeline: bool = False, **stream_kw):
    """Run ``requests`` through a ServeStream under ``plan``. Returns
    ``(results, stream, controller)``. ``pipeline=False`` by default:
    scripted plans address slots by wave index, so the wave schedule
    must be single-threaded deterministic."""
    ctrl = ServeChaosController(plan, tick_s=tick_s)
    stream = ServeStream(engine, wave_len=wave_len, pipeline=pipeline,
                         chaos=ctrl, **stream_kw)
    return stream.run(requests), stream, ctrl
