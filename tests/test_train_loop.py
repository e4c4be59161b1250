"""Device-resident coded gradient aggregation (DESIGN.md §11).

The three grad-sync wires of MultiModelCAMRTrainer — the SPMD
fused-codec collective, the numpy engine interpreter (healthy AND
degraded), and the uncoded baseline — must produce BIT-identical
parameters and loss trajectories: f32 gradients XOR-code losslessly and
every executor reduces in the engine's canonical combine order.

Also covers the satellite fixes: the (job, subfile_index) gradient
memo, the empty-loss-list guard, orphaned checkpoint tmp dirs, async
checkpoint worker errors surfacing in Trainer.run, and crash-resume
metadata.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.checkpoint.ckpt import available_steps
from repro.configs import get_config, reduced
from repro.data.pipeline import ShardedTokenPipeline
from repro.runtime.train_loop import (MultiModelCAMRTrainer, Trainer,
                                      _mean_losses)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny_cfg():
    return reduced(get_config("granite_3_2b")).replace(
        n_layers=2, vocab=64, d_model=32, d_ff=64, n_heads=2, n_kv_heads=1,
        head_dim=16, loss_chunk=8)


def _run_subprocess(code: str, ndev: int) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    return res.stdout


# --------------------------------------------------------------------- #
# the acceptance gate: camr_spmd == camr == uncoded, bit for bit,
# including a degraded survivor-set trajectory (runtime/fault.py)
# --------------------------------------------------------------------- #
_RUN_IDENTITY = textwrap.dedent("""
    import numpy as np
    from repro.configs import get_config, reduced
    from repro.data.pipeline import ShardedTokenPipeline
    from repro.runtime.train_loop import MultiModelCAMRTrainer

    cfg = reduced(get_config("granite_3_2b")).replace(
        n_layers=2, vocab=64, d_model=32, d_ff=64, n_heads=2,
        n_kv_heads=1, head_dim=16, loss_chunk=8)
    pipe = ShardedTokenPipeline(vocab=64, seq_len=8, global_batch=2)

    reports, trainers = {}, {}
    for mode in ("camr", "uncoded", "camr_spmd"):
        tr = MultiModelCAMRTrainer(cfg, q=2, k=3, seed=0,
                                   spmd_oracle=(mode == "camr_spmd"))
        reports[mode] = tr.train_steps(pipe, 3, mode=mode)
        trainers[mode] = tr

    ref_flat = np.asarray(trainers["camr"].flat)
    ref_losses = np.asarray(reports["camr"].losses)
    assert np.isfinite(ref_losses).all()
    for mode in ("uncoded", "camr_spmd"):
        np.testing.assert_array_equal(
            np.asarray(trainers[mode].flat), ref_flat,
            err_msg=f"{mode} parameters diverged from the engine oracle")
        np.testing.assert_array_equal(
            np.asarray(reports[mode].losses), ref_losses,
            err_msg=f"{mode} losses diverged")

    # the spmd stream reused ONE compiled executor for all steps
    assert reports["camr_spmd"].sync["compiles"] == 1
    assert reports["camr_spmd"].sync["dispatches"] == 3
    # coded shuffle ships fewer bytes than uncoded
    assert reports["camr"].bytes_total < reports["uncoded"].bytes_total

    # a degraded survivor-set step (runtime/fault.py) is recovery-exact:
    # worker 0 silent in every shuffle, SAME trajectory bits
    td = MultiModelCAMRTrainer(cfg, q=2, k=3, seed=0, failed={0})
    rd = td.train_steps(pipe, 3, mode="camr")
    np.testing.assert_array_equal(np.asarray(td.flat), ref_flat)
    np.testing.assert_array_equal(np.asarray(rd.losses), ref_losses)
    assert rd.bytes_total > reports["camr"].bytes_total  # load inflation

    # mixed healthy/degraded stream: healthy steps, one degraded, healthy
    tm = MultiModelCAMRTrainer(cfg, q=2, k=3, seed=0)
    tm.train_steps(pipe, 1, mode="camr")
    tm.failed = {3}
    tm.train_steps(pipe, 1, mode="camr")
    tm.failed = None
    tm.train_steps(pipe, 1, mode="camr_spmd")
    np.testing.assert_array_equal(np.asarray(tm.flat), ref_flat)
    print("OK")
""")


@pytest.mark.slow
def test_grad_sync_modes_bit_identical():
    out = _run_subprocess(_RUN_IDENTITY, ndev=6)
    assert "OK" in out


# --------------------------------------------------------------------- #
# mixed-precision grad sync: bf16 shuffle payload, f32 master params
# (DESIGN.md §12) — the bit-identity contract holds per lane
# --------------------------------------------------------------------- #
_RUN_IDENTITY_BF16 = textwrap.dedent("""
    import numpy as np
    import ml_dtypes
    from repro.configs import get_config, reduced
    from repro.data.pipeline import ShardedTokenPipeline
    from repro.runtime.train_loop import MultiModelCAMRTrainer

    cfg = reduced(get_config("granite_3_2b")).replace(
        n_layers=2, vocab=64, d_model=32, d_ff=64, n_heads=2,
        n_kv_heads=1, head_dim=16, loss_chunk=8)
    pipe = ShardedTokenPipeline(vocab=64, seq_len=8, global_batch=2)

    reports, trainers = {}, {}
    for mode in ("camr", "uncoded", "camr_spmd"):
        tr = MultiModelCAMRTrainer(cfg, q=2, k=3, seed=0,
                                   grad_sync_dtype="bfloat16",
                                   spmd_oracle=(mode == "camr_spmd"))
        reports[mode] = tr.train_steps(pipe, 2, mode=mode)
        trainers[mode] = tr

    ref_flat = np.asarray(trainers["camr"].flat)
    ref_losses = np.asarray(reports["camr"].losses)
    assert np.isfinite(ref_losses).all()
    for mode in ("uncoded", "camr_spmd"):
        assert reports[mode].grad_sync_dtype == "bfloat16"
        np.testing.assert_array_equal(
            np.asarray(trainers[mode].flat), ref_flat,
            err_msg=f"{mode} parameters diverged on the bf16 lane")
        np.testing.assert_array_equal(
            np.asarray(reports[mode].losses), ref_losses,
            err_msg=f"{mode} losses diverged on the bf16 lane")
    # master params stay f32; the synced payload was bf16
    assert np.asarray(trainers["camr"].flat).dtype == np.float32

    # a degraded bf16 survivor-set step is recovery-exact too
    td = MultiModelCAMRTrainer(cfg, q=2, k=3, seed=0, failed={0},
                               grad_sync_dtype="bfloat16")
    rd = td.train_steps(pipe, 2, mode="camr")
    np.testing.assert_array_equal(np.asarray(td.flat), ref_flat)

    # the packed lane ships ~half the engine-measured shuffle bytes of
    # the f32 lane (exactly half here: widths need no pad words)
    t32 = MultiModelCAMRTrainer(cfg, q=2, k=3, seed=0)
    r32 = t32.train_steps(pipe, 2, mode="camr")
    assert reports["camr"].bytes_total * 2 == r32.bytes_total, (
        reports["camr"].bytes_total, r32.bytes_total)

    # ...and the trajectories genuinely differ across lanes (bf16
    # rounding is real — the identity contract is PER lane)
    assert not np.array_equal(np.asarray(t32.flat), ref_flat)
    print("OK")
""")


@pytest.mark.slow
def test_grad_sync_bf16_modes_bit_identical():
    out = _run_subprocess(_RUN_IDENTITY_BF16, ndev=6)
    assert "OK" in out


def test_grad_sync_dtype_validation():
    cfg = _tiny_cfg()
    with pytest.raises(ValueError, match="loss scaling"):
        MultiModelCAMRTrainer(cfg, q=2, k=3, grad_sync_dtype="float16")
    with pytest.raises(ValueError, match="float32 or bfloat16"):
        MultiModelCAMRTrainer(cfg, q=2, k=3, grad_sync_dtype="int8")
    # the config field (previously dead) is the default source
    tr = MultiModelCAMRTrainer(cfg.replace(grad_sync_dtype="bfloat16"),
                               q=2, k=3)
    assert tr.grad_sync_dtype == "bfloat16"
    tr32 = MultiModelCAMRTrainer(cfg, q=2, k=3)
    assert tr32.grad_sync_dtype == "float32"


# --------------------------------------------------------------------- #
# satellite: the gradient memo is keyed by (job, subfile_index)
# --------------------------------------------------------------------- #
@pytest.mark.slow
def test_gradient_memo_keyed_by_job_and_index(monkeypatch):
    """Regression for the id(subfile)-keyed memo: id() of a payload is
    only unique while the object is alive, and aliased payload objects
    must still be treated as distinct subfiles. With (job, index) keys,
    every (job, subfile) slot is computed exactly once per step — even
    when one dict object is aliased into several slots."""
    import repro.data.pipeline as dp

    cfg = _tiny_cfg()
    pipe = ShardedTokenPipeline(vocab=64, seq_len=8, global_batch=2)
    orig = dp.make_camr_job_datasets

    def aliased(pipeline, J, N, step):
        ds = orig(pipeline, J, N, step)
        ds[0][1] = ds[0][0]   # same OBJECT at two subfile slots
        return ds

    monkeypatch.setattr(dp, "make_camr_job_datasets", aliased)
    tr = MultiModelCAMRTrainer(cfg, q=2, k=3, seed=0)
    rep = tr.train_steps(pipe, 1, mode="camr")
    J, N = tr.camr.J, tr.camr.N
    # an id()-keyed cache would collapse the aliased slots into one
    # gradient compute and record only N-1 losses for job 0
    assert tr.map_calls == J * N
    assert len(tr._last_loss[0]) == N
    # aliased payloads are identical content -> identical losses
    assert tr._last_loss[0][0] == tr._last_loss[0][1]
    assert np.isfinite(np.asarray(rep.losses)).all()


def test_mean_losses_guard():
    """np.mean over an empty list warns and is undefined — the guard
    pins empty per-job maps to NaN without touching np.mean."""
    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("error")
        out = _mean_losses([{0: 1.0, 1: 3.0}, {}])
    assert out[0] == pytest.approx(2.0)
    assert np.isnan(out[1])
    # keyed averaging is order-independent (modes walk subfiles in
    # different orders but must average identically)
    assert _mean_losses([{1: 3.0, 0: 1.0}]) == _mean_losses(
        [{0: 1.0, 1: 3.0}])


def test_trainer_rejects_unknown_mode():
    cfg = _tiny_cfg()
    tr = MultiModelCAMRTrainer(cfg, q=2, k=3, seed=0)
    pipe = ShardedTokenPipeline(vocab=64, seq_len=8, global_batch=2)
    with pytest.raises(ValueError, match="mode"):
        tr.train_steps(pipe, 1, mode="nope")


def test_spmd_needs_mesh_actionable_error():
    """Without K devices, camr_spmd fails at sync time with the
    XLA_FLAGS hint (never deep inside a shard_map trace)."""
    cfg = _tiny_cfg()
    tr = MultiModelCAMRTrainer(cfg, q=2, k=3, seed=0)
    if tr.mesh is not None:    # process actually has >= 6 devices
        pytest.skip("process has enough devices for a real mesh")
    pipe = ShardedTokenPipeline(vocab=64, seq_len=8, global_batch=2)
    with pytest.raises(RuntimeError, match="device_count"):
        tr.train_steps(pipe, 1, mode="camr_spmd")


_RUN_TOO_FEW_DEVICES = textwrap.dedent("""
    import jax
    import numpy as np
    from repro.configs import get_config, reduced
    from repro.data.pipeline import ShardedTokenPipeline
    from repro.launch.mesh import make_camr_mesh
    from repro.runtime.train_loop import MultiModelCAMRTrainer

    assert len(jax.devices()) == 4
    cfg = reduced(get_config("granite_3_2b")).replace(
        n_layers=2, vocab=64, d_model=32, d_ff=64, n_heads=2,
        n_kv_heads=1, head_dim=16, loss_chunk=8)
    tr = MultiModelCAMRTrainer(cfg, q=2, k=3, seed=0)
    assert tr.mesh is None
    pipe = ShardedTokenPipeline(vocab=64, seq_len=8, global_batch=2)
    flat0 = np.asarray(tr.flat)
    before = {id(a) for a in jax.live_arrays()}
    try:
        tr.train_steps(pipe, 1, mode="camr_spmd")
    except RuntimeError as e:
        msg = str(e)
    else:
        raise AssertionError("camr_spmd ran a 6-worker shuffle on 4 devices")
    assert "needs 6 devices" in msg and "device_count=6" in msg, msg
    placed = [a.shape for a in jax.live_arrays() if id(a) not in before]
    assert not placed, placed
    assert tr._stream is None and tr.step == 0
    np.testing.assert_array_equal(np.asarray(tr.flat), flat0)
    try:
        make_camr_mesh(6)
    except ValueError as e:
        assert "needs 6 devices" in str(e), e
    else:
        raise AssertionError("make_camr_mesh(6) built a mesh on 4 devices")
    print("OK")
""")


def test_spmd_refuses_fewer_than_K_devices_cleanly():
    """On 4 devices a q*k = 6-worker camr_spmd step is refused before any
    map or shuffle work: nothing new is placed on a device, the trainer
    state is untouched, and the mesh builder refuses the same way."""
    assert "OK" in _run_subprocess(_RUN_TOO_FEW_DEVICES, ndev=4)


def test_device_shortfall_on_a_chip_host_names_the_missing_fold(
        monkeypatch):
    """Off the CPU no host-platform flag can add devices: the refusal
    says a q*k-worker shuffle needs q*k devices and that folding workers
    onto chips is a roadmap item, not a flag away."""
    from repro.launch import mesh as mesh_mod

    class _Chip:
        platform = "tpu"

    monkeypatch.setattr(mesh_mod.jax, "devices", lambda *a: [_Chip()] * 4)
    msg = mesh_mod.device_shortfall(6, 4)
    assert "needs 6 devices" in msg and "4 tpu device(s)" in msg
    assert "ROADMAP Reach item 1" in msg and "XLA_FLAGS" not in msg


def test_uncoded_rejects_degraded():
    """The uncoded baseline has no degraded mode and must say so;
    camr_spmd no longer rejects a failed set — it routes through the
    stream's degraded host lane (covered by the churn tests in
    tests/test_elastic.py, which need a K-device subprocess)."""
    cfg = _tiny_cfg()
    tr = MultiModelCAMRTrainer(cfg, q=2, k=3, seed=0, failed={0})
    pipe = ShardedTokenPipeline(vocab=64, seq_len=8, global_batch=2)
    with pytest.raises(ValueError, match="uncoded|camr"):
        tr.train_steps(pipe, 1, mode="uncoded")


# --------------------------------------------------------------------- #
# satellite: orphaned checkpoint tmp dirs
# --------------------------------------------------------------------- #
def test_available_steps_skips_tmp_dirs(tmp_path):
    os.makedirs(tmp_path / "step_00000003")
    (tmp_path / "step_00000003" / "manifest.json").write_text("{}")
    os.makedirs(tmp_path / "step_00000007.tmp.12345")   # crashed save
    os.makedirs(tmp_path / "step_00000002.tmp.1")       # crashed save
    assert available_steps(str(tmp_path)) == [3]


def test_gc_reaps_orphaned_tmp_dirs(tmp_path):
    """A crashed writer's stale step_*.tmp.<pid> dirs are removed by
    the next manager's retention pass instead of accumulating forever."""
    import time

    # a pid that provably belonged to a now-dead process
    dead = subprocess.Popen([sys.executable, "-c", "pass"])
    dead.wait()
    orphan = tmp_path / f"step_00000001.tmp.{dead.pid}"
    os.makedirs(orphan)
    (orphan / "junk.npy").write_bytes(b"x")
    old = time.time() - 2 * CheckpointManager.STALE_TMP_SECS
    os.utime(orphan, (old, old))
    mgr = CheckpointManager(str(tmp_path), keep=2)
    mgr.save({"w": jnp.zeros((3,))}, step=1)
    mgr.wait()
    assert not orphan.exists()
    assert available_steps(str(tmp_path)) == [1]
    mgr.close()


def test_gc_keeps_fresh_and_own_tmp_dirs(tmp_path):
    """Never reaped: a tmp dir carrying OUR pid (could be a concurrent
    same-process writer) and any FRESH foreign tmp dir (could be
    another host's writer mid-save — pids don't compare across
    hosts, so only stale dirs are fair game)."""
    mine = tmp_path / f"step_00000009.tmp.{os.getpid()}"
    os.makedirs(mine)
    fresh_foreign = tmp_path / "step_00000008.tmp.999999"
    os.makedirs(fresh_foreign)
    mgr = CheckpointManager(str(tmp_path), keep=2)
    mgr.save({"w": jnp.zeros((3,))}, step=1)
    mgr.wait()
    assert mine.exists()
    assert fresh_foreign.exists()
    mgr.close()


# --------------------------------------------------------------------- #
# satellite: async checkpoint worker errors surface in Trainer.run
# --------------------------------------------------------------------- #
def test_async_checkpoint_error_surfaces_in_run(tmp_path, monkeypatch):
    cfg = _tiny_cfg().replace(vocab=32, loss_chunk=16)
    pipe = ShardedTokenPipeline(vocab=32, seq_len=8, global_batch=2)
    tr = Trainer(cfg, ckpt_dir=str(tmp_path), total_steps=10, seed=0)

    import repro.checkpoint.ckpt as ckpt_mod

    def boom(*a, **kw):
        raise IOError("disk full")

    monkeypatch.setattr(ckpt_mod, "save_checkpoint", boom)
    with pytest.raises(IOError, match="disk full"):
        tr.run(pipe, steps=2, ckpt_every=2)   # final wait() re-raises


def test_checkpoint_manager_wait_reraises(tmp_path, monkeypatch):
    import repro.checkpoint.ckpt as ckpt_mod

    def boom(*a, **kw):
        raise RuntimeError("torn write")

    monkeypatch.setattr(ckpt_mod, "save_checkpoint", boom)
    mgr = CheckpointManager(str(tmp_path))
    mgr.save({"w": jnp.zeros((2,))}, step=1)
    with pytest.raises(RuntimeError, match="torn write"):
        mgr.wait()
    mgr.close()


# --------------------------------------------------------------------- #
# satellite: crash-resume round trip incl. resume() metadata
# --------------------------------------------------------------------- #
@pytest.mark.slow
def test_trainer_crash_resume_roundtrip_metadata(tmp_path):
    """Kill-and-restart mid-run, then CONTINUE: the resumed trainer
    finishes with the same parameters as an uninterrupted run, and
    resume() restores the checkpointed metadata (step + data cursor)."""
    cfg = _tiny_cfg().replace(vocab=32, loss_chunk=16)
    pipe = ShardedTokenPipeline(vocab=32, seq_len=8, global_batch=2)

    straight = Trainer(cfg, ckpt_dir=str(tmp_path / "a"), total_steps=20,
                       seed=3)
    straight.run(pipe, steps=6, ckpt_every=0)

    t1 = Trainer(cfg, ckpt_dir=str(tmp_path / "b"), total_steps=20, seed=3)
    t1.run(pipe, steps=4, ckpt_every=2)
    # "crash": fresh object, different seed — resume must overwrite it
    t2 = Trainer(cfg, ckpt_dir=str(tmp_path / "b"), total_steps=20,
                 seed=1234)
    assert t2.resume()
    assert t2.step == 4
    _, meta = t2.ckpt.restore({"params": t2.params, "opt": t2.opt})
    assert meta["step"] == 4
    assert meta["pipeline_step"] == 4     # data cursor travels along
    t2.run(pipe, steps=2, ckpt_every=0)   # continue to step 6
    for a, b in zip(jax.tree.leaves(straight.params),
                    jax.tree.leaves(t2.params)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
