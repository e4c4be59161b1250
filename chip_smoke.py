#!/usr/bin/env python3
"""One-chip smoke test: the CAMR main path, compiled, on one TPU chip.

    python chip_smoke.py              # on a machine with a TPU
    python chip_smoke.py --rehearse   # CPU, reduced sizes, interpreted
                                      # kernels; never reports ok

Phases, in one process (a phase that fails raises, and the script exits
non-zero without printing a result):

* device   — JAX's first device must be a TPU; prints its kind, the
  device count, and the jax and libtpu versions.
* codec    — the six XOR codec kernels, compiled (``interpret=False``),
  driven by the index, mask and receive-selector tables of real
  schedule lowerings (``make_plan(2, 3, d)`` and ``make_plan(3, 4, d)``)
  at 65,536 u32 words per packet, for every device's tables of both
  coded stages, on the f32/u32 lane and the packed bf16 (u16) lane.
  Every output is compared bitwise with a numpy reference.
* combiner — the alpha-combiner ``aggregate``, compiled, on
  ``[4096, 4096]`` f32 with 48 segments against ``ref.aggregate_ref``,
  and the gamma = 1 one-row-per-segment case bit-exact.
* serve    — full-width gemma2_2b (bf16, random weights from
  ``PRNGKey(0)``) through the engine stack ``python -m
  repro.launch.serve --archs gemma2_2b`` builds: 8 requests of 1-64
  prompt tokens and 32 new tokens each, 4 slots, waves of 8. A warm-up
  run compiles; the checked run must end every request ``ok`` with no
  jit trace and no wave retry, and its greedy tokens must equal the
  ``generate`` host-loop oracle's.

The last line printed is ``{"ok": true, "device": {...}}``. Tokens per
second and peak device bytes are printed as a smoke reading, not a
benchmark. JAX's persistent compile cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else at ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from dataclasses import dataclass
from importlib import metadata
from pathlib import Path

import ml_dtypes
import numpy as np

ROOT = Path(__file__).resolve().parent


class SmokeFailure(RuntimeError):
    """A phase's output was wrong."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


@dataclass(frozen=True)
class Sizes:
    packet_words: int        # u32 words per codec packet
    agg_rows: int            # combiner input rows ...
    agg_width: int           # ... and columns
    agg_segments: int
    full_width: bool         # gemma2_2b as published, or reduced()
    prompt_lens: tuple       # one request per entry
    max_new: int
    slots: int = 4
    page_size: int = 8
    wave: int = 8


CHIP = Sizes(packet_words=65536, agg_rows=4096, agg_width=4096,
             agg_segments=48, full_width=True,
             # two requests per length: four prefill shapes to compile
             prompt_lens=(1, 64, 23, 40, 40, 23, 64, 1), max_new=32)
REHEARSAL = Sizes(packet_words=4096, agg_rows=512, agg_width=512,
                  agg_segments=12, full_width=False,
                  prompt_lens=(1, 16, 9, 16), max_new=8)


def phase(name: str):
    def wrap(fn):
        def run(*a, **kw):
            t0 = time.perf_counter()
            out = fn(*a, **kw)
            print(f"[{name}] done in {time.perf_counter() - t0:.1f}s",
                  flush=True)
            return out
        return run
    return wrap


# --------------------------------------------------------------------- #
@phase("device")
def device_phase(rehearse: bool) -> dict:
    import jax
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    print(f"[device] platform={info['platform']} kind={info['kind']} "
          f"count={info['count']} jax={jax.__version__} "
          f"libtpu={metadata.version('libtpu')}", flush=True)
    if not rehearse:
        check(dev.platform == "tpu",
              f"no TPU: JAX's first device is {dev.platform!r}; this smoke "
              "test runs on a TPU chip (--rehearse runs it on the CPU)")
    return info


# --------------------------------------------------------------------- #
def _xor_rows(rows, mask):
    """numpy reference fold: XOR over axis 1 of ``rows[n, m, w]`` where
    ``mask[n, m]`` (masked-off sources are the XOR identity)."""
    return np.bitwise_xor.reduce(np.where(mask[..., None], rows, 0),
                                 axis=1)


@phase("codec")
def codec_phase(sizes: Sizes, interpret: bool) -> None:
    import jax.numpy as jnp

    from repro.core.collective import make_plan
    from repro.kernels import xor_code as X

    pk = sizes.packet_words
    rng = np.random.default_rng(0)
    for q, k in ((2, 3), (3, 4)):
        plan = make_plan(q, k, pk * (k - 1))
        K, J_own = plan.K, plan.J_own
        P = J_own * (k - 1) * K * (k - 1)       # flat packet rows
        # the chunk buffer [J_own, k-1, K, d] of f32 / bf16 contributions
        # as the codec sees it: u32 words / u16 lanes, one packet per row
        u32 = rng.standard_normal((P, pk), dtype=np.float32).view(
            np.uint32)
        u16 = rng.standard_normal((P, 2 * pk), dtype=np.float32).astype(
            ml_dtypes.bfloat16).view(np.uint16)
        d32, d16 = jnp.asarray(u32), jnp.asarray(u16)
        for stage in (1, 2):
            T = plan.program.stage_tables(stage)
            n = T.n
            R = n * (k - 1)
            for me in range(K):
                enc, ok = T.enc_src[me], T.src_ok[me]
                rsel = T.dec_recv[me].reshape(R)
                dsrc = T.dec_src[me].reshape(R, k)
                dmsk = T.dec_mask[me].reshape(R, k)
                recv32 = rng.integers(0, 2**32, (R, pk), dtype=np.uint32)
                recv16 = recv32.view(np.uint16)
                want = {
                    "encode32": _xor_rows(u32[enc], ok),
                    "decode32": recv32[rsel] ^ _xor_rows(u32[dsrc], dmsk),
                    "encode16": _xor_rows(u16[enc], ok),
                    "decode16": recv16[rsel] ^ _xor_rows(u16[dsrc], dmsk),
                }
                got = {
                    "xor_fold": X.xor_fold(
                        jnp.asarray(np.where(ok[..., None], u32[enc], 0)),
                        interpret=interpret),
                    "xor_decode": X.xor_decode(
                        jnp.asarray(recv32[rsel]), jnp.asarray(u32[dsrc]),
                        jnp.asarray(dmsk), interpret=interpret),
                    "xor_encode_gather": X.xor_encode_gather(
                        d32, jnp.asarray(enc), jnp.asarray(ok),
                        interpret=interpret),
                    "xor_decode_gather": X.xor_decode_gather(
                        jnp.asarray(recv32), d32, jnp.asarray(rsel),
                        jnp.asarray(dsrc), jnp.asarray(dmsk),
                        interpret=interpret),
                    "xor_encode_gather16": X.xor_encode_gather16(
                        d16, jnp.asarray(enc), jnp.asarray(ok),
                        interpret=interpret),
                    "xor_decode_gather16": X.xor_decode_gather16(
                        jnp.asarray(recv16), d16, jnp.asarray(rsel),
                        jnp.asarray(dsrc), jnp.asarray(dmsk),
                        interpret=interpret),
                }
                ref_of = {"xor_fold": "encode32", "xor_decode": "decode32",
                          "xor_encode_gather": "encode32",
                          "xor_decode_gather": "decode32",
                          "xor_encode_gather16": "encode16",
                          "xor_decode_gather16": "decode16"}
                for name, out in got.items():
                    out = np.asarray(out)
                    ref = want[ref_of[name]]
                    check(out.dtype == ref.dtype and out.shape == ref.shape
                          and np.array_equal(out, ref),
                          f"codec {name} q={q} k={k} stage={stage} "
                          f"device={me}: not bitwise the numpy reference")
            print(f"[codec] q={q} k={k} stage={stage}: 6 kernels x {K} "
                  f"devices' tables ({n} groups, {pk} u32 words / "
                  f"{2 * pk} bf16 lanes per packet) bitwise equal to "
                  "numpy on the f32/u32 and packed bf16 lanes", flush=True)


# --------------------------------------------------------------------- #
@phase("combiner")
def combiner_phase(sizes: Sizes, interpret: bool) -> None:
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.aggregate import aggregate

    rng = np.random.default_rng(1)
    n, d, S = sizes.agg_rows, sizes.agg_width, sizes.agg_segments
    vals = jnp.asarray(rng.standard_normal((n, d), dtype=np.float32))
    ids = jnp.asarray(rng.integers(0, S, n).astype(np.int32))
    got = np.asarray(aggregate(vals, ids, S, interpret=interpret))
    want = np.asarray(ref.aggregate_ref(vals, ids, S))
    err = float(np.max(np.abs(got - want)))
    check(got.shape == (S, d) and np.isfinite(got).all(),
          f"combiner: bad output {got.shape}")
    check(np.allclose(got, want, rtol=1e-5, atol=1e-4),
          f"combiner [{n}, {d}] {S} segments: max |err| {err} vs "
          "ref.aggregate_ref")
    print(f"[combiner] [{n}, {d}] f32, {S} segments: max |err| vs "
          f"ref.aggregate_ref = {err:.3g}", flush=True)

    # gamma = 1: every segment holds one row, so its sum is 0 + x — x
    # itself, bit for bit, except that a -0.0 (which the normal sampler
    # does draw) sums to +0.0, in the reference as in any sum
    one = rng.standard_normal((S, d), dtype=np.float32)
    perm = rng.permutation(S).astype(np.int32)
    got1 = np.asarray(aggregate(jnp.asarray(one), jnp.asarray(perm), S,
                                interpret=interpret))
    want1 = np.empty_like(one)
    want1[perm] = np.float32(0) + one
    ref1 = np.asarray(ref.aggregate_ref(jnp.asarray(one),
                                        jnp.asarray(perm), S))
    for name, want in (("the numpy gather", want1),
                       ("ref.aggregate_ref", ref1)):
        bad = np.argwhere(got1.view(np.uint32) != want.view(np.uint32))
        check(len(bad) == 0,
              f"combiner gamma=1: {len(bad)} words differ from {name}, "
              f"first at {bad[:1].tolist()}")
    neg_zeros = int(np.signbit(one[one == 0]).sum())
    print(f"[combiner] gamma=1 [{S}, {d}]: bit-exact against the numpy "
          f"gather and ref.aggregate_ref ({neg_zeros} input -0.0 summed "
          "to +0.0)", flush=True)


# --------------------------------------------------------------------- #
def _first_divergence(cfg, params, prompt, want, got):
    """Where the engine left the oracle, and the oracle's top-2 logit
    margin there (a prefill of the oracle's own prefix)."""
    import jax
    import jax.numpy as jnp

    from repro.models import lm

    i = int(np.flatnonzero(want != got)[0])
    seq = np.concatenate([prompt, want[:i]]).astype(np.int32)
    logits, _ = jax.jit(lambda p, t: lm.prefill(cfg, p, {"tokens": t}))(
        params, jnp.asarray(seq[None]))
    top = np.sort(np.asarray(logits[0, -1, :cfg.vocab], np.float32))
    return i, float(top[-1] - top[-2])


@phase("serve")
def serve_phase(sizes: Sizes) -> dict:
    import jax

    from repro.configs import get_config, reduced
    from repro.launch.serve import build_stream
    from repro.models import lm
    from repro.runtime.serve import Request, generate

    arch = "gemma2_2b"
    cfg = get_config(arch)
    if not sizes.full_width:
        cfg = reduced(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(lm.init_params(cfg,
                                                  jax.random.PRNGKey(0)))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"[serve] {arch} {'full width' if sizes.full_width else 'reduced'}"
          f": {cfg.n_layers} layers, d_model={cfg.d_model}, vocab="
          f"{cfg.vocab}, {n_params} params in {cfg.dtype}, initialised in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)

    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab, (t,)).astype(np.int32)
               for t in sizes.prompt_lens]
    reqs = [Request(prompt=p, max_new=sizes.max_new, seed=i)
            for i, p in enumerate(prompts)]
    jobs = [(arch, r) for r in reqs]
    max_ctx = max(sizes.prompt_lens) + sizes.max_new
    stream = build_stream({arch: cfg}, {arch: params},
                          slots=sizes.slots, page_size=sizes.page_size,
                          max_ctx=max_ctx, max_new=sizes.max_new,
                          wave=sizes.wave)

    t0 = time.perf_counter()
    stream.run(jobs)                                # warm-up: compiles
    print(f"[serve] warm-up run (compiles included) "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    t0 = time.perf_counter()
    results = stream.run(jobs)
    wall = time.perf_counter() - t0
    rep = stream.last_report

    statuses = [r.status for r in results]
    print(f"[serve] statuses {statuses}; traces during the run "
          f"{rep.traces}; wave retries {rep.retries}; waves {rep.waves}",
          flush=True)
    check(all(s == "ok" for s in statuses), f"serve: statuses {statuses}")
    check(rep.traces == 0, f"serve: {rep.traces} jit traces during a warm run")
    check(rep.retries == 0, f"serve: {rep.retries} wave retries")

    t0 = time.perf_counter()
    by_index = {r.index: r for r in results}
    same = 0
    for i, (p, r) in enumerate(zip(prompts, reqs)):
        want = generate(cfg, params, p[None], max_new=r.max_new,
                        seed=r.seed).tokens[0, len(p):]
        got = by_index[i].generated
        if np.array_equal(want, got):
            same += 1
            continue
        pos, margin = _first_divergence(cfg, params, p, want, got)
        print(f"[serve] request {i} (prompt {len(p)}): engine left the "
              f"oracle at generated position {pos} (oracle {want[pos]}, "
              f"engine {got[pos]}); oracle top-2 logit margin there "
              f"{margin:.6g}", flush=True)
    print(f"[serve] greedy token parity with the generate oracle: "
          f"{same}/{len(reqs)} requests identical (oracle "
          f"{time.perf_counter() - t0:.1f}s)", flush=True)
    check(same == len(reqs), "serve: engine tokens differ from the oracle")

    emitted = sum(r.emitted for r in results)
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use", "not reported")
    print(f"[serve] smoke reading, not a benchmark: {emitted} tokens in "
          f"{wall:.3f}s = {emitted / wall:.1f} tok/s (warm run, host "
          f"clock); peak_bytes_in_use={peak}", flush=True)
    return {"tokens": emitted, "wall_s": wall, "peak_bytes": peak}


# --------------------------------------------------------------------- #
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="run every phase on the CPU at reduced sizes with "
                         "interpreted kernels (never reports ok)")
    args = ap.parse_args()
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    from repro.launch.cache import use_compile_cache

    cache_dir = use_compile_cache()
    cache = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        for what in cache:
            if event == f"/jax/compilation_cache/cache_{what}":
                cache[what] += 1

    jax.monitoring.register_event_listener(on_event)

    t0 = time.perf_counter()
    info = device_phase(args.rehearse)
    sizes = REHEARSAL if args.rehearse else CHIP
    interpret = args.rehearse
    codec_phase(sizes, interpret)
    combiner_phase(sizes, interpret)
    serve_phase(sizes)
    print(f"[cache] compile cache {cache_dir}: {cache['hits']} hits, "
          f"{cache['misses']} misses this run", flush=True)
    print(f"[total] {time.perf_counter() - t0:.1f}s", flush=True)
    if args.rehearse:
        print(json.dumps({"ok": False, "rehearsal": True, "device": info}))
        return 0
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
