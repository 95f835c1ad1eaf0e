#!/usr/bin/env python3
"""Chip benchmark of the out-of-core GEMM engine: one cell, one run.

    python3 bench/run.py --workload mmooc_f32.ooc_n40960 --seed 7 \\
        --seconds 40 --trace 0

Prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device``
(``--trace 1``: the per-layer metrics and a ``breakdown``, read from a
profiler trace of the window), and as the last lines of standard error
each number compared with its limit.  Without a TPU, or with fewer chips
than the cell needs, it exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

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
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here (default: a "
                         "temporary directory, removed after reading)")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    result = harness.run_workload(args.workload, args.seed % 2**64,
                                  args.seconds, bool(args.trace),
                                  t_start=T_START, trace_dir=args.trace_dir)
    for name, c in result["compared"].items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
