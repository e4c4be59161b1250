"""Host-granularity chaos (DESIGN.md §17): scripted whole-host failure
re-homes the two-level stream bitwise onto the surviving topology with
zero cold lowerings after warm-up, and scripted wire corruption is
detected by the integrity lane and replayed bitwise — never silently
mis-reduced — on both wire lanes."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from chaos import CorruptPacket, FaultPlan, Kill, KillHost, RejoinHost

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_subprocess(code: str, ndev: int) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), os.path.join(ROOT, "tests")])
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    return res.stdout


_PRELUDE = textwrap.dedent("""
    import numpy as np
    from repro.compat import make_mesh
    from repro.core.schedule import SCHEDULE_CACHE
    from chaos import (CorruptPacket, FaultPlan, KillHost, RejoinHost,
                       make_shuffle_waves, run_host_plan)
""")


_RUN_KILL_HOST = _PRELUDE + textwrap.dedent("""
    q, k, hosts, d = {q}, {k}, {hosts}, {d}
    K = q * k
    mesh = make_mesh((K,), ('camr',))
    contribs, oracle = make_shuffle_waves(q, k, 5, d=d, mesh=mesh)
    plan = FaultPlan(({events}), name='kill-host')
    outs, stream, hm = run_host_plan(q, k, d, contribs, plan,
                                     mesh=mesh, hosts=hosts)
    for w, (got, want) in enumerate(zip(outs, oracle)):
        assert np.array_equal(got, want), f'wave {{w}} not bitwise'
    st = stream.stats()
    assert st['host_swaps'] == {swaps}, st
    assert hm.failed_hosts() == {dead_hosts}
    print('OK')
""")


@pytest.mark.parametrize("q,k,hosts", [(2, 4, 2), (2, 6, 3)])
def test_kill_host_recovers_bitwise(q, k, hosts):
    """A scripted whole-host kill mid-stream re-homes onto the
    surviving topology and every wave stays BITWISE identical to the
    healthy serial oracle; the rejoin re-homes back."""
    events = ("KillHost(wave=1, host=%d), RejoinHost(wave=3, host=%d),"
              % (hosts - 1, hosts - 1))
    out = _run_subprocess(
        _RUN_KILL_HOST.format(q=q, k=k, hosts=hosts, d=2 * (k - 1),
                              events=events, swaps=2,
                              dead_hosts="frozenset()"),
        ndev=q * k)
    assert "OK" in out


def test_kill_host_flat_fallback_bitwise():
    """hosts=4, k=4: losing one host leaves 3, which does not divide
    k — the stream falls back to the FLAT lowering (still bitwise);
    a second kill lands back on two_level(2)."""
    events = "KillHost(wave=1, host=3), KillHost(wave=2, host=2),"
    out = _run_subprocess(
        _RUN_KILL_HOST.format(q=2, k=4, hosts=4, d=6, events=events,
                              swaps=2, dead_hosts="{2, 3}"),
        ndev=8)
    assert "OK" in out


_RUN_WARM_GATE = _PRELUDE + textwrap.dedent("""
    q, k, hosts, d = 2, 4, 2, 6
    K = q * k
    mesh = make_mesh((K,), ('camr',))
    contribs, oracle = make_shuffle_waves(q, k, 4, d=d, mesh=mesh)

    from repro.core.collective import ShuffleStream
    from repro.core.schedule import Topology
    from repro.runtime.fault import HostMembership
    topo = Topology.two_level(hosts)
    hm = HostMembership(q, k, topo)
    stream = ShuffleStream(q, k, d, mesh=mesh, topology=topo)
    stream.warm_host_survivors(max_host_failures=hosts - 1)
    outs = stream.run_waves(contribs[:2])          # healthy steady state
    misses_warm = SCHEDULE_CACHE.stats()['misses']
    hm.kill_host(1)
    stream.set_topology(hm.current_topology())
    outs += stream.run_waves(contribs[2:])
    assert SCHEDULE_CACHE.stats()['misses'] == misses_warm, \\
        'host recovery paid a cold lowering'
    for w, (got, want) in enumerate(zip(outs, oracle)):
        assert np.array_equal(got, want), f'wave {w} not bitwise'
    print('OK')
""")


def test_kill_host_recovery_is_pure_cache_hit():
    """The acceptance gate: after ``warm_host_survivors``, host-loss
    recovery pays ZERO cold schedule lowerings (misses stay flat across
    the kill) while outputs stay bitwise."""
    out = _run_subprocess(_RUN_WARM_GATE, ndev=8)
    assert "OK" in out


_RUN_CORRUPT = _PRELUDE + textwrap.dedent("""
    import jax.numpy as jnp
    q, k, hosts, d = {q}, {k}, {hosts}, {d}
    K = q * k
    dtype = {dtype}
    mesh = make_mesh((K,), ('camr',))
    contribs, oracle = make_shuffle_waves(q, k, 4, d=d, dtype=dtype,
                                          mesh=mesh)
    plan = FaultPlan((CorruptPacket(wave=1, stage=1, device=0, bits=1),
                      CorruptPacket(wave=2, stage=2, device=K - 1,
                                    word=0, bits=0x80000000),),
                     name='corrupt')
    outs, stream, hm = run_host_plan(q, k, d, contribs, plan,
                                     mesh=mesh, hosts=hosts,
                                     verify_wire=True)
    for w, (got, want) in enumerate(zip(outs, oracle)):
        assert got.dtype == want.dtype
        assert np.array_equal(got, want), f'wave {{w}} not bitwise'
    st = stream.stats()
    assert st['wire_faults'] == 2, st
    assert st['wire_replays'] == 2, st
    print('OK')
""")


@pytest.mark.parametrize("itemsize", [4, 2], ids=["f32", "bf16"])
@pytest.mark.parametrize("q,k", [(2, 4), (2, 6), (3, 4)])
def test_checksum_word_catches_every_single_word_flip(q, k, itemsize):
    """The integrity lane's checksum word, the XOR of a packet's ``pk``
    payload words folded by the codec's own reduction: it widens each
    packet by exactly one word, passes every clean packet, flags every
    single-word flip of payload or checksum, and commutes with the XOR
    codec (checksums of XOR-combined packets XOR-combine)."""
    import jax.numpy as jnp

    from repro.core.collective import _xor_reduce
    from repro.core.schedule import payload_words

    wp = payload_words(2 * (k - 1), itemsize, k)
    assert wp % (k - 1) == 0
    pk = wp // (k - 1)
    rng = np.random.default_rng(q * 10 + k)
    w = rng.integers(0, 2 ** 32, size=(8, k - 1, pk), dtype=np.uint32)
    csum = np.asarray(_xor_reduce(jnp.asarray(w), 2))
    aug = np.concatenate([w, csum[..., None]], axis=2)
    assert aug.size * pk == w.size * (pk + 1)

    def flagged(x):                     # [..., k-1, pk+1] -> bad rows
        calc = _xor_reduce(jnp.asarray(x[..., :pk]), x.ndim - 1)
        return np.asarray(calc) != x[..., pk]

    assert not flagged(aug).any()
    flips = []
    for r in range(k - 1):
        for word in range(pk + 1):
            for bits in (1, 0x80000000, 0xDEADBEEF):
                t = aug[0].copy()
                t[r, word] ^= np.uint32(bits)
                flips.append(t)
    assert flagged(np.stack(flips)).any(axis=1).all()
    assert not flagged(aug[0] ^ aug[1]).any()


@pytest.mark.parametrize("dtype", ["np.float32", "jnp.bfloat16"])
def test_corrupt_packet_detected_and_replayed_bitwise(dtype):
    """Scripted single-word wire corruption in each coded stage is
    DETECTED by the checksum lane and replayed bitwise through the
    clean executor on both wire lanes (f32 and packed bf16) — never a
    silent mis-reduce."""
    out = _run_subprocess(
        _RUN_CORRUPT.format(q=2, k=4, hosts=2, d=6, dtype=dtype),
        ndev=8)
    assert "OK" in out


_RUN_KILL_PLUS_CORRUPT = _PRELUDE + textwrap.dedent("""
    q, k, hosts, d = 2, 4, 2, 6
    K = q * k
    mesh = make_mesh((K,), ('camr',))
    contribs, oracle = make_shuffle_waves(q, k, 4, d=d, mesh=mesh)
    plan = FaultPlan((CorruptPacket(wave=1),
                      KillHost(wave=2, host=0),), name='combined')
    outs, stream, hm = run_host_plan(q, k, d, contribs, plan,
                                     mesh=mesh, hosts=hosts,
                                     verify_wire=True)
    for w, (got, want) in enumerate(zip(outs, oracle)):
        assert np.array_equal(got, want), f'wave {w} not bitwise'
    st = stream.stats()
    assert st['wire_faults'] == 1 and st['host_swaps'] == 1, st
    print('OK')
""")


def test_combined_corruption_then_host_kill():
    """The two §17 fault models compose: a wire fault on wave 1 and a
    host kill on wave 2 both recover bitwise in one stream."""
    out = _run_subprocess(_RUN_KILL_PLUS_CORRUPT, ndev=8)
    assert "OK" in out


# --------------------------------------------------------------------- #
# in-process: the chaos vocabulary itself
# --------------------------------------------------------------------- #
def test_host_event_defaults_and_plan_queries():
    ev = CorruptPacket(wave=3)
    assert (ev.stage, ev.device, ev.row, ev.word, ev.bits) == \
        (1, 0, None, 0, 1)
    plan = FaultPlan((Kill(wave=0, worker=2), KillHost(wave=1, host=1),
                      RejoinHost(wave=2, host=1), CorruptPacket(wave=3)),
                     name="mixed")
    assert plan.workers() == {2}          # host events carry no worker
    assert plan.hosts() == {1}            # worker events carry no host


def test_corrupt_packet_requires_verify_wire():
    from repro.core.collective import ShuffleStream
    stream = ShuffleStream(2, 4, 6, mesh=None)
    with pytest.raises(ValueError, match="verify_wire"):
        stream.inject_corruption()
