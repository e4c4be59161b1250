"""Training launcher.

On a real TPU pod every host runs this same script (jax.distributed
initializes from the TPU environment); on CPU it runs a reduced config.

    PYTHONPATH=src python -m repro.launch.train --arch granite_3_2b \
        --steps 100 --reduced --ckpt-dir /tmp/ckpt

``--multi-model`` switches to the paper's J = q^{k-1}-models setting
(:class:`repro.runtime.MultiModelCAMRTrainer`): ``--grad-sync camr``
runs the numpy-engine interpreter, ``camr_spmd`` the device-resident
SPMD fused-codec shuffle (needs a K = q*k device mesh — on CPU set
``XLA_FLAGS=--xla_force_host_platform_device_count=K``), ``uncoded``
the unicast baseline. All three produce bit-identical parameters.

    PYTHONPATH=src python -m repro.launch.train --arch granite_3_2b \
        --reduced --multi-model --q 2 --k 3 --grad-sync camr_spmd \
        --steps 3

``--grad-sync-dtype bfloat16`` (multi-model only) switches the shuffle
payload to the packed 16-bit codec lane — half the bytes-on-wire, f32
master params/moments (DESIGN.md §12).
"""

from __future__ import annotations

import argparse
import json
import time

import jax

from repro.configs import ARCHS, SHAPES, get_config, reduced
from repro.data.pipeline import ShardedTokenPipeline
from repro.launch.cache import use_compile_cache
from repro.runtime import MultiModelCAMRTrainer, Trainer


def _run_multi_model(cfg, args) -> None:
    if args.grad_sync == "allreduce":
        raise SystemExit("--multi-model needs --grad-sync "
                         "camr|camr_spmd|uncoded (allreduce is the "
                         "single-model data-parallel wire)")
    pipe = ShardedTokenPipeline(vocab=cfg.vocab, seq_len=args.seq_len,
                                global_batch=args.batch)
    failed = ({int(s) for s in args.failed.split(",")}
              if args.failed else None)
    tr = MultiModelCAMRTrainer(cfg, q=args.q, k=args.k, lr=args.lr,
                               failed=failed,
                               grad_sync_dtype=args.grad_sync_dtype)
    t0 = time.time()
    rep = tr.train_steps(pipe, args.steps, mode=args.grad_sync)
    dt = time.time() - t0
    for step, losses in enumerate(rep.losses):
        print(json.dumps({"step": step + 1, "losses": losses}))
    print(json.dumps({"mode": rep.mode, "bytes_total": rep.bytes_total,
                      "grad_sync_dtype": rep.grad_sync_dtype,
                      "loads": rep.loads, "sync": rep.sync}))
    print(f"# {args.steps} steps x {tr.camr.J} models in {dt:.1f}s "
          f"({args.steps / dt:.2f} steps/s)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized same-family config")
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--grad-sync",
                    choices=["allreduce", "camr", "camr_spmd", "uncoded"],
                    default="allreduce")
    ap.add_argument("--grad-sync-dtype",
                    choices=["float32", "bfloat16"], default="float32",
                    help="gradient shuffle payload dtype: bfloat16 syncs "
                         "on the packed 16-bit codec lane at half the "
                         "bytes-on-wire, with f32 master params/moments "
                         "(DESIGN.md §12; float16 is rejected by the "
                         "trainer — no loss scaling)")
    ap.add_argument("--multi-model", action="store_true",
                    help="train J = q^(k-1) models with CAMR-coded "
                         "gradient aggregation")
    ap.add_argument("--q", type=int, default=2)
    ap.add_argument("--k", type=int, default=3)
    ap.add_argument("--failed", default=None,
                    help="comma-separated failed worker ids (degraded "
                         "survivor-set schedule; --grad-sync camr only)")
    args = ap.parse_args()
    use_compile_cache()

    if jax.process_count() > 1:  # multi-host pod
        jax.distributed.initialize()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if args.multi_model:
        _run_multi_model(cfg, args)
        return
    if args.grad_sync in ("camr_spmd", "uncoded"):
        raise SystemExit(f"--grad-sync {args.grad_sync} is a "
                         "--multi-model wire; the single-model loop "
                         "takes allreduce|camr")
    if args.grad_sync_dtype != "float32":
        raise SystemExit("--grad-sync-dtype is a --multi-model option "
                         "(the compressed CAMR gradient shuffle); the "
                         "single-model loop reduces at float32")
    cfg = cfg.replace(grad_sync=args.grad_sync)
    pipe = ShardedTokenPipeline(vocab=cfg.vocab, seq_len=args.seq_len,
                                global_batch=args.batch)
    tr = Trainer(cfg, lr=args.lr, total_steps=args.steps,
                 ckpt_dir=args.ckpt_dir)
    if args.resume:
        if tr.resume():
            print(f"resumed from step {tr.step}")
    t0 = time.time()
    metrics = tr.run(pipe, steps=args.steps, ckpt_every=args.ckpt_every
                     if args.ckpt_dir else 0)
    dt = time.time() - t0
    for m in metrics:
        print(json.dumps(m))
    print(f"# {args.steps} steps in {dt:.1f}s "
          f"({args.steps / dt:.2f} steps/s)")


if __name__ == "__main__":
    main()
