#!/usr/bin/env python3
"""The readings a cell's ``logit_gap`` limit is set from.

    python3 bench/calibrate.py --workload granite-3-2b.longctx \
        --seeds 11,12,13 --seconds 30

For each seed, in one process (so set-up is paid once): serve one window
at the cell's load through the timed path, exactly as a run does, and
compare the same sample of finished requests with the float32 reference
twice: the program's widest gap (a served token's logit below the
reference's best), and the control's (the gap of the token that the fp8
reference puts first at the same positions). Prints one JSON line per
seed. The limit lies above every program reading and below every control
reading; the benchmark's own runs never run the control.
"""

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    harness.use_compile_cache(ROOT)
    cell = harness.load_cell(ROOT, args.workload)
    try:
        _, peaks = harness.chip(ROOT, cell.entry["chips"])
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        win, params = harness.serve_window(cell, seed, args.seconds,
                                           peaks=peaks, trace=False)
        harness.free_program_state(win)
        picked = harness.sample(win.results, cell.mix["check"]["requests"],
                                seed)
        gaps = harness.served_gaps(cell, params, picked, control=True)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "program": gaps["program"],
                          "control": gaps["control"],
                          "tokens": gaps["tokens"],
                          "requests": len(picked),
                          "seconds": time.monotonic() - t0}), flush=True)
        del win, params
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
