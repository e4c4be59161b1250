"""Training loops.

* :class:`Trainer` — single-model loop (used by launch/train.py and the
  examples): jitted step = grad-accumulated loss/grad + AdamW, metrics,
  async checkpointing, crash-resume.
* :class:`MultiModelCAMRTrainer` — the paper's setting end-to-end:
  J = q^{k-1} same-architecture models trained simultaneously on K
  workers. Per step: every worker maps its stored (job, batch)
  microbatches to gradients (computation redundancy k-1), per-batch
  gradients are compressed with the α-combiner
  (:func:`repro.kernels.aggregate.aggregate`), the CAMR 3-stage coded
  shuffle delivers each worker the fully-aggregated shard of every job
  it reduces (ZeRO-style: worker s owns optimizer shard s of ALL jobs),
  and the worker-sharded AdamW update is applied to the flat padded
  parameter vectors.

  Three grad-sync wires execute the same compiled schedule
  (DESIGN.md §11):

  * ``mode="camr_spmd"`` — the production path: the stacked per-worker
    contribution tensor ``[K, J_own, k-1, K, d]`` goes through ONE
    jitted shard_map execution of :func:`repro.core.collective
    .camr_shuffle` (fused gather-XOR codec) on a K-device mesh, reused
    across steps via :meth:`repro.core.collective.ShuffleStream.sync`;
    the synced gradient stays on the mesh for the update.
  * ``mode="camr"`` — the numpy :class:`~repro.core.engine.CAMREngine`
    interpreter, driven through a :class:`~repro.runtime.jobstream
    .JobStream` wave (byte-exact accounting; with ``failed=...`` it
    runs the degraded survivor-set schedule of runtime/fault.py).
  * ``mode="uncoded"`` — same placement, unicast everything (the
    paper's baseline).

  All three produce BIT-IDENTICAL parameters: gradients XOR-code
  losslessly, every executor reduces in the engine's canonical combine
  order (delivered batch + ascending fold), and every mode shares the
  same jitted update. Asserted exactly in tests/test_train_loop.py.

  ``grad_sync_dtype="bfloat16"`` turns on mixed-precision grad sync
  (DESIGN.md §12): the memoized map gradients are rounded to bf16 ONCE
  at the source, every wire ships them on the packed 16-bit codec lane
  (half the bytes), and the shared update upcasts the synced gradient
  to f32 against f32 master params/moments. The bitwise cross-mode
  contract holds per lane because all three executors consume the SAME
  bf16 bits and fold them in the same canonical order.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from repro.checkpoint import CheckpointManager
from repro.configs import ModelConfig
from repro.core.engine import CAMRConfig, CAMREngine
from repro.data.pipeline import ShardedTokenPipeline
from repro.models import lm
from repro.optim import AdamWState, adamw_update, adamw_init, cosine_schedule


# --------------------------------------------------------------------- #
# single-model trainer
# --------------------------------------------------------------------- #
class Trainer:
    def __init__(self, cfg: ModelConfig, *, lr: float = 3e-4,
                 warmup: int = 20, total_steps: int = 1000,
                 ckpt_dir: str | None = None, seed: int = 0):
        self.cfg = cfg
        self.lr, self.warmup, self.total = lr, warmup, total_steps
        self.params = lm.init_params(cfg, jax.random.PRNGKey(seed))
        self.opt = adamw_init(self.params)
        self.step = 0
        self.ckpt = CheckpointManager(ckpt_dir) if ckpt_dir else None
        self._jit_step = jax.jit(self._train_step)

    def _train_step(self, params, opt, batch, step):
        nmb = self.cfg.microbatches

        def loss_fn(p, mb):
            return lm.train_loss(self.cfg, p, mb)[0]

        if nmb == 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        else:
            # gradient accumulation over microbatches (scan keeps HLO small)
            def split(x):
                return x.reshape(nmb, x.shape[0] // nmb, *x.shape[1:])
            mbs = jax.tree.map(split, batch)

            def acc(carry, mb):
                l, g = jax.value_and_grad(loss_fn)(params, mb)
                return (carry[0] + l,
                        jax.tree.map(jnp.add, carry[1], g)), None

            zero = (jnp.zeros(()),
                    jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 params))
            (loss, grads), _ = jax.lax.scan(acc, zero, mbs)
            loss = loss / nmb
            grads = jax.tree.map(lambda g: g / nmb, grads)
        lr = cosine_schedule(step, peak=self.lr, warmup_steps=self.warmup,
                             total_steps=self.total)
        params, opt, gnorm = adamw_update(params, grads, opt, lr=lr)
        return params, opt, {"loss": loss, "gnorm": gnorm, "lr": lr}

    def run(self, pipeline: ShardedTokenPipeline, steps: int,
            log_every: int = 10, ckpt_every: int = 0):
        metrics = []
        for _ in range(steps):
            batch = {k: jnp.asarray(v)
                     for k, v in pipeline.batch(self.step).items()}
            self.params, self.opt, m = self._jit_step(
                self.params, self.opt, batch, jnp.int32(self.step))
            self.step += 1
            if self.step % log_every == 0 or self.step == 1:
                metrics.append({k: float(v) for k, v in m.items()}
                               | {"step": self.step})
            if self.ckpt and ckpt_every and self.step % ckpt_every == 0:
                self.ckpt.save({"params": self.params, "opt": self.opt},
                               step=self.step,
                               metadata={"pipeline_step": self.step})
        if self.ckpt:
            # the final drain is the last chance to learn that an async
            # checkpoint write failed: wait() re-raises the worker error
            # (a run that "completed" with every checkpoint silently
            # lost must not look successful)
            self.ckpt.wait()
        return metrics

    def resume(self):
        """Crash-resume from the latest checkpoint (incl. data cursor)."""
        if not self.ckpt or self.ckpt.latest_step() is None:
            return False
        tree, meta = self.ckpt.restore(
            {"params": self.params, "opt": self.opt})
        self.params, self.opt = tree["params"], tree["opt"]
        self.step = meta["step"]
        return True


# --------------------------------------------------------------------- #
# the paper's multi-job trainer
# --------------------------------------------------------------------- #
@dataclass
class CAMRTrainReport:
    loads: dict = field(default_factory=dict)
    bytes_total: int = 0
    losses: list = field(default_factory=list)
    mode: str = ""
    sync: dict = field(default_factory=dict)   # executor-reuse stats
    grad_sync_dtype: str = "float32"           # shuffle payload dtype


def _mean_losses(per_job: list) -> list[float]:
    """Per-job mean loss for one step.

    ``per_job[j]`` maps subfile index -> loss; keyed (not appended) so
    every grad-sync mode averages in the same order regardless of the
    order its engine walked the subfiles. ``np.mean`` over an empty
    list warns and is undefined — an empty map (a job served entirely
    from a warm memo) is an explicit NaN instead.
    """
    return [float(np.mean([d[n] for n in sorted(d)])) if d
            else float("nan") for d in per_job]


class MultiModelCAMRTrainer:
    """Train J = q^{k-1} models with CAMR-coded gradient aggregation.

    Parameters beyond the original (cfg, q, k, lr, seed):

    mesh
        Device mesh with a single axis of size K = q*k. ``None`` builds
        one automatically when the process has >= K devices (it is used
        by EVERY mode's update placement, so coded/uncoded/SPMD runs in
        one process stay bit-comparable); ``mode="camr_spmd"`` requires
        it.
    failed
        Failed/straggling worker set: ``mode="camr"`` steps run the
        degraded survivor-set schedule (runtime/fault.py), and
        ``mode="camr_spmd"`` steps route through the stream's degraded
        host lane (:meth:`~repro.core.collective.ShuffleStream
        .degrade` — no retrace, DESIGN.md §14). Recovery is exact — a
        degraded step leaves the trajectory bit-identical to the
        healthy one; flip membership live via :meth:`set_failed`.
    spmd_oracle
        When true, every ``camr_spmd`` step ALSO runs the numpy engine
        on the same memoized gradients and asserts the device result
        equals it bit-for-bit (and takes the measured byte accounting
        from the engine trace). Off by default: the engine is the
        *oracle*, not the fast path.
    grad_sync_dtype
        Shuffle payload dtype: ``"float32"`` (default) or
        ``"bfloat16"`` for mixed-precision grad sync — gradients are
        rounded to bf16 once at the map memo, synced on the packed
        16-bit codec lane at half the bytes-on-wire, and upcast to f32
        for the master-copy update (DESIGN.md §12). ``None`` reads
        ``cfg.grad_sync_dtype``. ``float16`` is rejected: raw LM
        gradients overflow/underflow its 5-bit exponent without loss
        scaling — use bfloat16 (f32-range exponent) instead.

    State layout: parameters, moments and synced gradients live as flat
    padded f32 vectors of ``Dpad = K * d_shard`` elements per job
    (``(k-1) | d_shard`` so every shard splits into codec packets);
    worker s owns shard s of every job — the update is worker-sharded
    ZeRO-style and identical across modes by construction (one jitted
    update function).
    """

    def __init__(self, cfg: ModelConfig, *, q: int, k: int,
                 lr: float = 1e-3, seed: int = 0, mesh=None,
                 axis_name: str = "camr", codec: str = "fused",
                 router: str = "all_to_all", use_kernels=None,
                 failed=None, spmd_oracle: bool = False,
                 grad_sync_dtype: str | None = None):
        self.cfg, self.q, self.k = cfg, q, k
        gsd = (cfg.grad_sync_dtype if grad_sync_dtype is None
               else grad_sync_dtype)
        name = jnp.dtype(gsd).name
        if name == "float16":
            raise ValueError(
                "grad_sync_dtype=float16 is unsafe for raw gradients: "
                "the 5-bit exponent overflows above 65504 and flushes "
                "below ~6e-5, and this trainer implements no loss "
                "scaling. Use grad_sync_dtype='bfloat16' (same exponent "
                "range as float32, same 2x wire savings) or 'float32'.")
        if name not in ("float32", "bfloat16"):
            raise ValueError(
                f"grad_sync_dtype must be float32 or bfloat16, got "
                f"{name}")
        self.grad_sync_dtype = name
        #: numpy view of the sync dtype (ml_dtypes.bfloat16 rounds and
        #: adds bit-identically to the XLA bf16 lane for normal values)
        self._sync_np = np.dtype(np.float32 if name == "float32"
                                 else "bfloat16")
        self.camr = CAMRConfig(q=q, k=k, gamma=1)
        J, K = self.camr.J, self.camr.K
        keys = jax.random.split(jax.random.PRNGKey(seed), J)
        params = [lm.init_params(cfg, keys[j]) for j in range(J)]
        flat0, self._unravel = ravel_pytree(params[0])
        self.D = flat0.size
        self.K = K
        # pad so the K function-shards are equal (paper: Q | gradients)
        # AND each shard splits into k-1 codec packets (collective.py)
        d = -(-self.D // K)
        d += (-d) % (k - 1)
        self.d_shard = d
        self.Dpad = K * d
        flat = np.zeros((J, self.Dpad), np.float32)
        for j in range(J):
            flat[j, :self.D] = np.asarray(ravel_pytree(params[j])[0],
                                          np.float32)
        self.flat = jnp.asarray(flat)          # f32 master copy [J, Dpad]
        self.opt = AdamWState(step=jnp.zeros((J,), jnp.int32),
                              mu=jnp.zeros((J, self.Dpad), jnp.float32),
                              nu=jnp.zeros((J, self.Dpad), jnp.float32))
        self.lr = lr
        self.step = 0
        self.axis_name = axis_name
        self.codec, self.router, self.use_kernels = codec, router, use_kernels
        self.failed = set(failed) if failed else None
        self.spmd_oracle = spmd_oracle
        self.mesh = mesh
        if self.mesh is None and len(jax.devices()) >= K:
            from repro.compat import make_mesh
            self.mesh = make_mesh((K,), (axis_name,))
        self._stream = None                    # lazy ShuffleStream
        self.map_calls = 0                     # gradient computations paid

        D, Dpad, N = self.D, self.Dpad, self.camr.N

        def _loss_grad(flat_row, batch):
            def loss_fn(fl):
                return lm.train_loss(cfg, self._unravel(fl[:D]), batch)[0]
            return jax.value_and_grad(loss_fn)(flat_row)

        self._grad = jax.jit(_loss_grad)

        def _apply(flat, opt, gsync):
            # gsync [K, J, d]: worker s holds shard s of every job's
            # summed gradient. Transpose/reshape are pure data movement;
            # the astype upcasts a bf16-lane sync to the f32 master
            # numerics (exact — a no-op on the f32 lane); /N and AdamW
            # are elementwise (+ the per-job clip norm) — ONE function
            # for every sync mode, so cross-mode parameter bits can
            # only diverge if the shuffles themselves do.
            grads = jnp.transpose(gsync, (1, 0, 2)).reshape(
                J, Dpad).astype(jnp.float32) / N
            return jax.vmap(partial(adamw_update, lr=lr))(flat, grads, opt)

        self._apply = jax.jit(_apply)

    # ------------------------------------------------------------------ #
    @property
    def params(self):
        """Per-job parameter pytrees (unravelled views of the master)."""
        return [self._unravel(self.flat[j, :self.D])
                for j in range(self.camr.J)]

    def _grad_vec(self, j: int, n: int, batch) -> np.ndarray:
        loss, g = self._grad(self.flat[j],
                             {k: jnp.asarray(v) for k, v in batch.items()})
        self._last_loss[j][n] = float(loss)
        self.map_calls += 1
        g = np.asarray(g, np.float32).reshape(self.K, self.d_shard)
        # mixed precision: round ONCE at the memo source so every
        # grad-sync wire consumes the SAME bf16 bits (ml_dtypes casts
        # round-to-nearest-even, bit-identical to the XLA convert)
        return g if self._sync_np == np.float32 else g.astype(self._sync_np)

    def _place(self, gsync):
        """Put a synced-gradient array where the update expects it: on
        the worker mesh (sharded along K) when one exists. The SPMD
        output already lives there; host-engine results are transferred
        — the point is that every mode feeds the SAME placement, so the
        jitted update compiles once and reduces identically."""
        g = gsync if isinstance(gsync, jnp.ndarray) else jnp.asarray(gsync)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            g = jax.device_put(g, NamedSharding(self.mesh,
                                                P(self.axis_name)))
        return g

    # -- grad-sync wires ------------------------------------------------ #
    def _assemble(self, results, migrate=None) -> np.ndarray:
        """Engine result dicts -> gsync [K, J, d] (pure data movement)."""
        J, K = self.camr.J, self.K
        gs = np.empty((K, J, self.d_shard), self._sync_np)
        for s in range(K):
            src = migrate(s) if migrate else s
            for j in range(J):
                gs[s, j] = results[src][(j, s)]
        return gs

    def _sync_interpreter(self, map_fn, datasets, report) -> np.ndarray:
        from repro.runtime.jobstream import JobSpec, JobStream

        stream = JobStream(failed=self.failed, pipeline=False)
        spec = JobSpec(self.camr, map_fn, datasets,
                       name=f"train-step{self.step}",
                       value_dtype=self._sync_np)
        results = stream.run([spec])[0]
        eng = stream.last_engines[0]
        report.loads = eng.measured_loads()
        report.bytes_total += eng.trace.total_bytes()
        migrate = eng.migrate_target if self.failed else None
        return self._assemble(results, migrate)

    def _sync_uncoded(self, map_fn, datasets, report) -> np.ndarray:
        from repro.core.baselines import UncodedAggregatedEngine

        if self.failed:
            raise ValueError("the uncoded baseline has no degraded mode; "
                             "failed-worker steps need mode='camr'")
        eng = UncodedAggregatedEngine(self.q, self.k, 1, map_fn)
        results = eng.run(datasets)
        report.loads = {"L_total_bus": eng.measured_load()}
        report.bytes_total += eng.trace.total_bytes()
        return self._assemble(results)

    def set_failed(self, failed) -> None:
        """Live membership change between steps: subsequent ``camr``
        steps re-lower from the warm schedule cache, and an existing
        SPMD stream swaps to its degraded lane (or back) WITHOUT
        retracing — ``stream.compiles`` stays flat across kill/rejoin
        (DESIGN.md §14). Recovery is exact: degraded steps leave the
        parameter trajectory bit-identical to the healthy one."""
        self.failed = set(failed) if failed else None
        if self._stream is not None:
            if self.failed:
                self._stream.degrade(self.failed)
            else:
                self._stream.restore()

    def _spmd_stream(self):
        if self._stream is None:
            from repro.core.collective import ShuffleStream
            if self.mesh is None:
                from repro.launch.mesh import device_shortfall
                raise RuntimeError(
                    "mode='camr_spmd': "
                    + device_shortfall(self.K, len(jax.devices()))
                    + " Or pass mesh=.")
            self._stream = ShuffleStream(
                self.q, self.k, self.d_shard, mesh=self.mesh,
                axis_name=self.axis_name, router=self.router,
                codec=self.codec,
                use_kernels=self.use_kernels)
        # reconcile with the trainer's failed set (covers both a lazy
        # first build under failure and a direct self.failed mutation)
        want = frozenset(self.failed or ())
        if want != self._stream.failed:
            self._stream.degrade(want) if want else self._stream.restore()
        return self._stream

    def _build_contribs(self, map_fn, datasets) -> np.ndarray:
        """The map lane of the SPMD path: per worker, map the stored
        (job, batch) subfiles and compress same-batch outputs with the
        α-combiner (:func:`repro.kernels.aggregate.aggregate`) into the
        stacked contribution tensor ``[K, J_own, k-1, K, d]``.

        gamma == 1 here, so each segment holds exactly one subfile and
        the combiner is bit-exact (a one-hot matmul gather); wider
        gammas would sum through the MXU."""
        from repro.core.collective import make_plan
        from repro.kernels.aggregate import aggregate

        prog = make_plan(self.q, self.k, self.d_shard).program
        K, k = self.K, self.k
        J_own = self.q ** (self.k - 2)
        pl = prog.placement
        out = np.zeros((K, J_own, k - 1, K, self.d_shard), self._sync_np)
        for s in range(K):
            vals, ids = [], []
            for a in range(J_own):
                j = int(prog.owned_jobs[s, a])
                for b in range(k - 1):
                    t = int(prog.stored_batches[s, a, b])
                    for n in pl.batch_subfiles(t):
                        vals.append(np.asarray(
                            map_fn(j, datasets[j][n])).reshape(-1))
                        ids.append(a * (k - 1) + b)
            agg = aggregate(jnp.asarray(np.stack(vals)),
                            jnp.asarray(np.asarray(ids, np.int32)),
                            J_own * (k - 1))
            out[s] = np.asarray(agg).reshape(J_own, k - 1, K, self.d_shard)
        return out

    def _sync_spmd(self, map_fn, datasets, report):
        stream = self._spmd_stream()
        contribs = self._build_contribs(map_fn, datasets)
        out = stream.sync(jnp.asarray(contribs))   # device [K, J, d]
        if self.spmd_oracle:
            # the numpy engine is the bit-identity + byte-accounting
            # oracle of the device path (map_fn memoized: no extra
            # gradient computes)
            eng = CAMREngine(self.camr, map_fn)
            results = eng.run(datasets)
            np.testing.assert_array_equal(
                np.asarray(out), self._assemble(results),
                err_msg="camr_spmd shuffle diverged from the engine "
                        "oracle")
            report.loads = eng.measured_loads()
            report.bytes_total += eng.trace.total_bytes()
        else:
            from repro.core import loads as L
            from repro.core.collective import (camr_collective_bytes,
                                               make_plan)
            plan = make_plan(self.q, self.k, self.d_shard)
            report.loads = {
                "L_total_bus": L.camr_load(self.q, self.k),
                "L_total_p2p": L.camr_load_p2p(self.q, self.k),
            }
            report.bytes_total += camr_collective_bytes(
                plan, dtype=self._sync_np)["camr_total"]
        report.sync = stream.stats()
        return out

    # ------------------------------------------------------------------ #
    def train_steps(self, pipeline: ShardedTokenPipeline, steps: int,
                    mode: str = "camr") -> CAMRTrainReport:
        """Run ``steps`` training steps; ``self.step`` advances, so
        consecutive calls continue the same data stream (a mid-run
        mode or ``failed`` switch keeps the trajectory comparable)."""
        from repro.data.pipeline import make_camr_job_datasets

        syncs = {"camr": self._sync_interpreter,
                 "uncoded": self._sync_uncoded,
                 "camr_spmd": self._sync_spmd}
        if mode not in syncs:
            raise ValueError(f"unknown mode {mode!r}; choose from "
                             f"{sorted(syncs)}")
        report = CAMRTrainReport(mode=mode,
                                 grad_sync_dtype=self.grad_sync_dtype)
        J, N = self.camr.J, self.camr.N
        for _ in range(steps):
            self._last_loss = [dict() for _ in range(J)]
            base = make_camr_job_datasets(pipeline, J, N, self.step)
            # subfile payloads carry their index: the gradient memo is
            # keyed by (job, subfile_index) — an id(subfile)-keyed memo
            # is only unique while the object lives, i.e. one GC away
            # from silently serving another subfile's gradient
            datasets = [[(n, base[j][n]) for n in range(N)]
                        for j in range(J)]
            cache: dict = {}

            def map_fn(j, subfile):
                n, batch = subfile
                key = (j, n)
                if key not in cache:   # each (job, subfile) mapped once
                    cache[key] = self._grad_vec(j, n, batch)  # per step
                return cache[key]

            gsync = syncs[mode](map_fn, datasets, report)
            self.flat, self.opt, _ = self._apply(
                self.flat, self.opt, self._place(gsync))
            report.losses.append(_mean_losses(self._last_loss))
            self.step += 1
        return report
