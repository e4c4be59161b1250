#!/usr/bin/env python3
"""The on-chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload granite-3-2b.longctx --seed 7 \
        --seconds 30 --trace 0

Prints, as its last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` a ``breakdown``) and, last, ``checks``: each number that
decided ``correct`` beside its limit, which also end standard error.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window. Exits non-zero,
printing no result, where JAX finds no TPU, fewer chips than the cell
needs, or a chip kind with no entry in ``bench/peaks.json``.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    try:
        line = harness.run(ROOT, args.workload, args.seed, args.seconds,
                           bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
