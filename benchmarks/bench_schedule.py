"""ShuffleProgram IR: lowering time + batched-vs-looped shuffle wall time.

Acceptance numbers for the IR refactor (DESIGN.md §5): the batched
router issues ``2*(k-1)`` grouped collectives for stages 1+2 regardless
of J, while the legacy looped schedule issues ``(J + n_s2) * (k-1)``
per-group ppermutes — this table measures what that buys end to end on
a K-host-device mesh, and what one cold ``lower_program`` costs.

    PYTHONPATH=src python -m benchmarks.bench_schedule
"""

import os

if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=16")
# ^ before any jax import.

import time

import numpy as np

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro.compat import shard_map
from repro.core.collective import (camr_shuffle, camr_shuffle_reference,
                                   expected_collective_calls, make_plan,
                                   scatter_contributions)
from repro.core.designs import make_design
from repro.core.placement import make_placement
from repro.core.schedule import lower_program
from repro.launch.mesh import device_shortfall

CONFIGS = [(2, 3), (4, 3), (3, 4), (2, 4), (5, 3)]


def _steady(fn, n: int = 5) -> float:
    fn()  # warm-up / compile
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return min(ts)


def bench_config(q: int, k: int, d: int | None = None) -> dict:
    K = q * k
    d = d or 64 * (k - 1)
    # cold lowering (bypass the lru_cache)
    pl = make_placement(make_design(q, k), gamma=1)
    t0 = time.perf_counter()
    lower_program.__wrapped__(pl, Q=K, d=d)
    lower_us = (time.perf_counter() - t0) * 1e6

    plan = make_plan(q, k, d)
    rng = np.random.default_rng(0)
    bg = rng.standard_normal((plan.J, k, K, d)).astype(np.float32)
    contribs = scatter_contributions(plan, bg)
    ref = camr_shuffle_reference(plan, bg)
    mesh = Mesh(np.array(jax.devices()[:K]), ("camr",))

    times = {}
    for mode, router in [("batched", "all_to_all"), ("batched", "ppermute"),
                         ("looped", "all_to_all")]:
        def body(c, mode=mode, router=router):
            return camr_shuffle(plan, c[0], axis_name="camr", mode=mode,
                                router=router)[None]
        f = jax.jit(shard_map(body, mesh=mesh, in_specs=P("camr"),
                              out_specs=P("camr")))
        out = jax.block_until_ready(f(contribs))
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5,
                                   atol=2e-6)
        times[(mode, router)] = _steady(
            lambda f=f: jax.block_until_ready(f(contribs)))

    calls = expected_collective_calls(plan)
    return dict(
        q=q, k=k, K=K, J=plan.J, d=d, lower_us=lower_us,
        batched_us=times[("batched", "all_to_all")] * 1e6,
        ppermute_us=times[("batched", "ppermute")] * 1e6,
        looped_us=times[("looped", "all_to_all")] * 1e6,
        speedup=times[("looped", "all_to_all")]
        / times[("batched", "all_to_all")],
        collectives_12=calls["stage12"],
        looped_12=expected_collective_calls(plan, "looped")["stage12"],
    )


def _rows_local():
    out = []
    for q, k in CONFIGS:
        r = bench_config(q, k)
        out.append({
            "name": f"schedule_q{q}_k{k}",
            "us_per_call": r["batched_us"],
            "derived": (f"K={r['K']} J={r['J']} lower={r['lower_us']:.0f}us "
                        f"batched={r['batched_us']:.0f}us "
                        f"pp={r['ppermute_us']:.0f}us "
                        f"looped={r['looped_us']:.0f}us "
                        f"speedup={r['speedup']:.2f}x "
                        f"coll12={r['collectives_12']}"
                        f"(was {r['looped_12']})"),
        })
    return out


def rows():
    """Suite entry point for benchmarks/run.py.

    If another suite already initialized the CPU backend (the XLA_FLAGS
    device-count hack at the top of this module only works before the
    first jax import), re-run this module in a fresh CPU subprocess and
    relay its CSV rows. On any other platform it runs on the devices
    this process holds, or refuses: a CPU child would time the CPU.
    """
    need = max(q * k for q, k in CONFIGS)
    devs = jax.devices()
    if len(devs) >= need:
        return _rows_local()
    if devs[0].platform != "cpu":
        raise RuntimeError(device_shortfall(need, len(devs)))
    import csv
    import io
    import subprocess
    import sys
    res = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_schedule"],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    if res.returncode != 0:
        raise RuntimeError(f"subprocess bench failed: {res.stderr[-500:]}")
    reader = csv.DictReader(io.StringIO(res.stdout))
    return [{"name": r["name"], "us_per_call": float(r["us_per_call"]),
             "derived": r["derived"]} for r in reader]


if __name__ == "__main__":
    print("name,us_per_call,derived")
    for row in _rows_local():
        print(f"{row['name']},{row['us_per_call']:.1f},"
              f"\"{row['derived']}\"", flush=True)
