"""SPMD vs interpreter gradient sync — the training hot loop's wire.

Acceptance numbers for the device-resident training path (DESIGN.md
§11): one grad-sync step moves the stacked per-worker contribution
tensor ``[K, J_own, k-1, K, d]`` to the fully-aggregated per-worker
shards ``[K, J, d]``. Two executors of the SAME compiled schedule:

* interpreter — :class:`repro.core.engine.CAMREngine` (map over
  pre-computed gradients + 3-stage coded shuffle + reduce, byte-exact
  accounting), what ``MultiModelCAMRTrainer(mode="camr")`` runs;
* spmd — :meth:`repro.core.collective.ShuffleStream.sync` (ONE jitted
  shard_map execution, fused gather-XOR codec, executor reused across
  steps), what ``mode="camr_spmd"`` runs.

Outputs are verified BIT-identical before any time is reported (the
canonical combine order makes the two executors exactly equal, not
allclose). The SPMD path must win on every config — a hard gate under
``CAMR_BENCH_STRICT=1`` (CPU host-device meshes are noisy; compiled
TPU lanes should see far more than the 5x target).

Two packed-lane rows ride along (DESIGN.md §12): a bf16 sync config
(same identity gate, half the contribution bytes) and an END-TO-END
``MultiModelCAMRTrainer`` step with ``grad_sync_dtype="bfloat16"``
whose parameters must come out bitwise-identical across the
camr_spmd / camr / uncoded executors — the mixed-precision acceptance
gate of the training path.

    PYTHONPATH=src python -m benchmarks.bench_train [--smoke]
"""

import os

if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=16")
# ^ before any jax import.

import argparse
import sys
import time

import numpy as np

import jax

from repro.compat import make_mesh
from repro.core.collective import (ShuffleStream, make_plan,
                                   scatter_contributions)
from repro.core.engine import CAMRConfig, CAMREngine
from repro.launch.mesh import device_shortfall

# (q, k, d) — d = the per-worker function-shard width being synced
CONFIGS = [(2, 3, 256), (3, 3, 128), (2, 4, 96), (3, 4, 96), (5, 3, 64)]
SMOKE_CONFIGS = [(2, 3, 16)]
#: packed-lane sync configs (payload dtype rides the last slot)
PACKED_CONFIGS = [(2, 3, 256), (3, 3, 128)]
PACKED_SMOKE_CONFIGS = [(2, 3, 16)]
TARGET_SPEEDUP = 5.0


def _median(fn, reps: int) -> float:
    fn()  # warm-up (compile / caches)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def bench_config(q: int, k: int, d: int, reps: int,
                 dtype=np.float32) -> dict:
    import ml_dtypes

    np_dtype = np.dtype(dtype)
    dname = ("bfloat16" if np_dtype == np.dtype(ml_dtypes.bfloat16)
             else np_dtype.name)
    plan = make_plan(q, k, d)
    K, J = plan.K, plan.J
    rng = np.random.default_rng(0)
    bg = rng.standard_normal((J, k, K, d)).astype(np.float32)
    if dname != "float32":
        bg = bg.astype(np_dtype)
    datasets = [[bg[j, t] for t in range(k)] for j in range(J)]
    contribs = scatter_contributions(plan, bg)

    cfg = CAMRConfig(q=q, k=k, gamma=1)
    eng = CAMREngine(cfg, lambda job, sf: sf)

    def interp_sync():
        eng.reset()
        return eng.run(datasets)

    mesh = make_mesh((K,), ("camr",))
    stream = ShuffleStream(q, k, d, mesh=mesh)

    def spmd_sync():
        return jax.block_until_ready(stream.sync(contribs))

    # -- bit-identity gate BEFORE any timing ---------------------------- #
    results = interp_sync()
    want = np.empty((K, J, d), np_dtype)
    for s in range(K):
        for j in range(J):
            want[s, j] = results[s][(j, s)]
    got = np.asarray(spmd_sync())
    assert got.dtype == np_dtype, (got.dtype, np_dtype)
    np.testing.assert_array_equal(
        got.view(np.uint8), want.view(np.uint8),
        err_msg=f"spmd grad-sync != engine interpreter (q={q} k={k} "
                f"{dname})")

    t_interp = _median(interp_sync, reps)
    t_spmd = _median(spmd_sync, reps)
    suffix = "" if dname == "float32" else f"_{dname}"
    return dict(
        name=f"train_sync_q{q}_k{k}_d{d}{suffix}",
        config={"q": q, "k": k, "K": K, "J": J, "d": d,
                "payload_dtype": dname},
        payload_dtype=dname,
        interp_us=t_interp * 1e6, spmd_us=t_spmd * 1e6,
        speedup=t_interp / t_spmd,
        sync_bytes=int(contribs.nbytes),
    )


def trainer_bf16_identity_row(steps: int = 2) -> dict:
    """END-TO-END mixed-precision gate: a tiny MultiModelCAMRTrainer
    runs ``grad_sync_dtype="bfloat16"`` through all three grad-sync
    executors and the parameters must come out BITWISE-identical
    (camr_spmd == camr == uncoded); reports wall clock of the SPMD
    path. Raises on any divergence — this is an acceptance gate, not a
    timing row."""
    from repro.configs import get_config, reduced
    from repro.data.pipeline import ShardedTokenPipeline
    from repro.runtime.train_loop import MultiModelCAMRTrainer

    cfg = reduced(get_config("granite_3_2b")).replace(
        n_layers=2, vocab=64, d_model=32, d_ff=64, n_heads=2,
        n_kv_heads=1, head_dim=16, loss_chunk=8)
    pipe = ShardedTokenPipeline(vocab=64, seq_len=8, global_batch=2)
    flats, reports, t_spmd = {}, {}, 0.0
    for mode in ("camr", "uncoded", "camr_spmd"):
        tr = MultiModelCAMRTrainer(cfg, q=2, k=3, seed=0,
                                   grad_sync_dtype="bfloat16")
        t0 = time.perf_counter()
        reports[mode] = tr.train_steps(pipe, steps, mode=mode)
        dt = time.perf_counter() - t0
        if mode == "camr_spmd":
            t_spmd = dt
        flats[mode] = np.asarray(tr.flat)
    for mode in ("uncoded", "camr_spmd"):
        np.testing.assert_array_equal(
            flats[mode], flats["camr"],
            err_msg=f"bf16 grad-sync: {mode} params diverged from the "
                    "engine oracle")
    bytes16 = reports["camr"].bytes_total
    us = t_spmd / steps * 1e6
    return {
        "name": "train_bf16_grad_sync_identity",
        "us_per_call": us,
        "derived": (f"camr_spmd==camr==uncoded BITWISE over {steps} "
                    f"bf16 steps; shuffle_bytes={bytes16} "
                    f"spmd={us:.0f}us/step"),
        "config": {"q": 2, "k": 3, "steps": steps,
                   "payload_dtype": "bfloat16"},
        "payload_dtype": "bfloat16",
        "bytes_on_wire": bytes16,
        "median_us": us,
    }


def _bench_rows(smoke: bool, reps: int) -> list:
    import ml_dtypes

    rows, losers = [], []
    sync_cfgs = [(q, k, d, np.float32)
                 for q, k, d in (SMOKE_CONFIGS if smoke else CONFIGS)]
    sync_cfgs += [(q, k, d, ml_dtypes.bfloat16) for q, k, d in
                  (PACKED_SMOKE_CONFIGS if smoke else PACKED_CONFIGS)]
    for q, k, d, dtype in sync_cfgs:
        r = bench_config(q, k, d, reps, dtype=dtype)
        if r["speedup"] <= 1.0:
            losers.append(r["name"])
        rows.append({
            "name": r["name"],
            "us_per_call": r["spmd_us"],
            "derived": (f"interp={r['interp_us']:.0f}us "
                        f"spmd={r['spmd_us']:.0f}us "
                        f"speedup={r['speedup']:.1f}x "
                        f"(target {TARGET_SPEEDUP:.0f}x) "
                        f"dtype={r['payload_dtype']} "
                        f"sync_bytes={r['sync_bytes']} bit-identical"),
            "config": r["config"],
            "payload_dtype": r["payload_dtype"],
            "sync_bytes": r["sync_bytes"],
            "median_us": r["spmd_us"],
            "interp_median_us": r["interp_us"],
            "speedup": r["speedup"],
        })
    rows.append(trainer_bf16_identity_row())
    # --smoke configs are too tiny for a meaningful wall-clock gate
    # (same policy as bench_encoding): bit-identity still gates above
    if losers and not smoke:
        msg = ("SPMD grad-sync must beat the interpreter on every "
               f"config; lost on {losers}")
        if os.environ.get("CAMR_BENCH_STRICT") == "1":
            raise AssertionError(msg)
        print(f"# WARNING (noisy host?): {msg}", file=sys.stderr)
    return rows


def rows(smoke: bool | None = None):
    """Suite entry point for benchmarks/run.py.

    If another suite already initialized the CPU backend (the XLA_FLAGS
    device-count hack above only works before the first jax use),
    re-run in a fresh CPU subprocess and relay the CSV rows. On any
    other platform it runs on the devices this process holds, or
    refuses: a CPU child would time the CPU.
    """
    if smoke is None:
        smoke = os.environ.get("CAMR_BENCH_SMOKE", "") == "1"
    need = max(q * k for q, k, _ in (SMOKE_CONFIGS if smoke else CONFIGS))
    devs = jax.devices()
    if len(devs) >= need:
        return _bench_rows(smoke, reps=5 if smoke else 15)
    if devs[0].platform != "cpu":
        raise RuntimeError(device_shortfall(need, len(devs)))
    import json
    import subprocess
    import tempfile
    with tempfile.NamedTemporaryFile("r", suffix=".json") as tf:
        cmd = [sys.executable, "-m", "benchmarks.bench_train",
               "--json-rows", tf.name]
        if smoke:
            cmd.append("--smoke")
        res = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=900,
                             env={**os.environ, "JAX_PLATFORMS": "cpu"})
        if res.returncode != 0:
            raise RuntimeError(
                f"subprocess bench failed: {res.stderr[-500:]}")
        # full rows (payload_dtype, sync_bytes, speedup, ...) for the
        # --json artifact; a missing/corrupt file is a real bug in the
        # writer above — fail loudly rather than degrade the artifact
        with open(tf.name) as f:
            return json.load(f)


def main():
    import json

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="one tiny config, few reps (CI train-smoke)")
    ap.add_argument("--json-rows", default=None, metavar="PATH",
                    help="also dump the full row dicts as JSON (the "
                         "rows() subprocess relay uses this to keep "
                         "payload_dtype/bytes fields in the artifact)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    rows_ = _bench_rows(args.smoke, reps=5 if args.smoke else 15)
    for row in rows_:
        print(f"{row['name']},{row['us_per_call']:.1f},"
              f"\"{row['derived']}\"", flush=True)
    if args.json_rows:
        with open(args.json_rows, "w") as f:
            json.dump(rows_, f, default=str)
    print("# spmd grad-sync verified bit-identical to the engine "
          "interpreter before timing", file=sys.stderr)


if __name__ == "__main__":
    main()
