"""Multi-pod dry-run — deliverable (e).

For every (architecture x input shape) cell, ``jax.jit(step).lower(...)
.compile()`` against the production mesh, then record:

* ``memory_analysis()``  — proves the cell fits per-device HBM,
* ``cost_analysis()``    — HLO FLOPs / bytes,
* collective bytes       — parsed from the optimized HLO (hlo_stats).

Results go to ``results/dryrun/<arch>_<shape>_<mesh>.json``. The run
is a compile-and-fit check: it times nothing on a device.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch granite_3_2b \
        --shape train_4k --mesh single   # one cell
    PYTHONPATH=src python -m repro.launch.dryrun --all  # every cell
"""

import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS_EXTRA", ""))
# ^ MUST precede the jax import (jax locks the device count on init).

import argparse
import json
import time
import traceback

import jax

from repro.configs import ARCHS, SHAPES, get_config, shape_supported
from repro.launch.mesh import make_production_mesh
from repro.launch.hlo_stats import collective_stats
from repro.launch.steps import build_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             overrides: dict | None = None) -> dict:
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = SHAPES[shape_name]
    ok, why = shape_supported(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped", "reason": why}
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    t0 = time.time()
    bundle = build_step(cfg, mesh, shape)
    with mesh:
        lowered = bundle.fn.lower(*bundle.args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        stats = collective_stats(compiled.as_text())
    out = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "status": "ok",
        "devices": int(mesh.devices.size),
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "code_bytes": int(mem.generated_code_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
        },
        "cost": {
            "flops": float(cost.get("flops", -1)),
            "bytes_accessed": float(cost.get("bytes accessed", -1)),
        },
        "collectives": stats.as_dict(),
        "params": cfg.param_count(),
        "params_active": cfg.param_count(active_only=True),
    }
    return out


def cost_pass(arch: str, shape_name: str, mesh_kind: str = "single"
              ) -> dict:
    """Trip-true HLO cost numbers via affine extrapolation.

    ``cost_analysis()`` (and the HLO text) count scan bodies ONCE, so the
    scanned compile undercounts by the trip count. Every quantity in the
    step module is affine in the repeat count R (identical layer bodies),
    so we compile UNROLLED modules at R=1 and R=2 and extrapolate
    f(R_full) = f(1) + (R_full - 1) * (f(2) - f(1)) exactly.
    """
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_supported(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped", "reason": why}
    unit = len(cfg.pattern)
    r_full = cfg.repeats
    pts = {}
    t0 = time.time()
    for r in (1, 2):
        c = cfg.replace(n_layers=unit * r, scan_unroll=True)
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
        bundle = build_step(c, mesh, shape)
        with mesh:
            compiled = bundle.fn.lower(*bundle.args).compile()
            cost = compiled.cost_analysis()
            stats = collective_stats(compiled.as_text())
        pts[r] = {"flops": float(cost.get("flops", 0)),
                  "bytes": float(cost.get("bytes accessed", 0)),
                  "wire": float(stats.wire_bytes),
                  "coll": float(stats.total_bytes),
                  "by_kind": stats.bytes_by_kind}

    def extrap(key):
        return pts[1][key] + (r_full - 1) * (pts[2][key] - pts[1][key])

    by_kind = {}
    for k in set(pts[1]["by_kind"]) | set(pts[2]["by_kind"]):
        b1 = pts[1]["by_kind"].get(k, 0)
        b2 = pts[2]["by_kind"].get(k, 0)
        by_kind[k] = b1 + (r_full - 1) * (b2 - b1)
    return {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "status": "ok", "repeats": r_full, "seconds": round(
            time.time() - t0, 1),
        "cost": {"flops": extrap("flops"), "bytes_accessed": extrap(
            "bytes")},
        "collectives": {"wire_bytes": extrap("wire"),
                        "total_bytes": extrap("coll"),
                        "bytes_by_kind": by_kind},
        "points": pts,
    }


def save(result: dict, suffix: str = "") -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    fn = os.path.join(
        RESULTS_DIR,
        f"{result['arch']}_{result['shape']}_{result['mesh']}{suffix}"
        ".json")
    with open(fn, "w") as f:
        json.dump(result, f, indent=1)
    return fn


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multipod"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--cost", action="store_true",
                    help="run the trip-true cost pass instead of the "
                         "scanned compile")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    cells = []
    if args.all:
        for a in ARCHS:
            for s in SHAPES:
                meshes = ("single",) if args.cost else ("single",
                                                        "multipod")
                for m in meshes:
                    cells.append((a, s, m))
    else:
        if not (args.arch and args.shape):
            ap.error("--arch/--shape or --all required")
        cells = [(args.arch, args.shape, args.mesh)]

    suffix = "_cost" if args.cost else ""
    failures = 0
    for a, s, m in cells:
        fn = os.path.join(RESULTS_DIR, f"{a}_{s}_{m}{suffix}.json")
        if args.skip_existing and os.path.exists(fn):
            with open(fn) as f:
                prev = json.load(f)
            if prev.get("status") in ("ok", "skipped"):
                print(f"[dryrun] {a} {s} {m}{suffix}: cached "
                      f"{prev['status']}", flush=True)
                continue
        try:
            res = cost_pass(a, s, m) if args.cost else run_cell(a, s, m)
        except Exception as e:  # noqa: BLE001 — record and continue
            res = {"arch": a, "shape": s, "mesh": m, "status": "error",
                   "error": f"{type(e).__name__}: {e}",
                   "trace": traceback.format_exc()[-2000:]}
            failures += 1
        save(res, suffix)
        msg = res["status"]
        if res["status"] == "ok" and not args.cost:
            hbm = (res["memory"]["argument_bytes"]
                   + res["memory"]["temp_bytes"]
                   + res["memory"]["output_bytes"]
                   - res["memory"]["alias_bytes"]) / 2**30
            msg += (f" mem~{hbm:.1f}GiB flops={res['cost']['flops']:.3g}"
                    f" coll={res['collectives']['total_bytes']/2**30:.2f}"
                    f"GiB lower={res['lower_s']}s "
                    f"compile={res['compile_s']}s")
        elif res["status"] == "ok":
            msg += (f" flops={res['cost']['flops']:.3g} "
                    f"wire={res['collectives']['wire_bytes']/2**30:.2f}GiB"
                    f" ({res['seconds']}s)")
        print(f"[dryrun] {a} {s} {m}{suffix}: {msg}", flush=True)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
