#!/usr/bin/env python3
"""Readings that the limit of ``correct`` is set from, on the chip.

    python3 bench/calibrate.py --workload mmooc_f32.ooc_n40960 \\
        --seeds 1,2,3 --control-seeds 101,102,103

For each seed: the cell's operands, one call through the timed path (after
one warm-up call) and the comparison a run makes, over as many rows as a
run compares (``check_groups * check_calls`` groups).  Then the same with
the control (``bench/control.py``) in the program's place.  One process, so
JAX starts once.  Prints one JSON line per seed and a summary line.
"""

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
if __package__ in (None, ""):   # run as a script
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import gen, harness  # noqa: E402
from bench.control import make_control_call  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    calibrate(args.workload, _ints(args.seeds), _ints(args.control_seeds))
    return 0


def _ints(text):
    return [int(s) for s in text.split(",") if s]


def calibrate(workload, seeds, control_seeds, *, root=ROOT,
              require_tpu=True):
    """The max_row_rel_err of each seed, ``{"program": [...], "control":
    [...]}``."""
    spec = harness.load_spec(root)
    cell, config, traffic = harness.resolve(spec, workload, root)
    devices = harness._devices(cell["chips"], require_tpu)
    harness.enable_compile_cache(root)
    bytes_limit = harness.memory_stats(devices[0])["bytes_limit"]
    groups = traffic["check_groups"] * traffic["check_calls"]
    ops = (None, None, None)
    readings = {"program": [], "control": []}
    for side, factory, side_seeds in (
            ("program", harness.make_call, seeds),
            ("control", make_control_call, control_seeds)):
        if not side_seeds:
            continue
        call = factory(config, devices, bytes_limit)
        for j, seed in enumerate(side_seeds):
            ops = harness.make_operands(config, traffic, seed, out=ops)
            A, B, C0 = ops
            if j == 0:
                out, _ = call(A, B, C0)
                del out
            saved = gen.mutate(A, B, seed, 0, traffic["mutate_band"])
            rows = gen.check_rows(traffic["n"], seed, 0, groups,
                                  traffic["check_rows"])
            t = time.perf_counter()
            out, _ = call(A, B, C0)
            wall = time.perf_counter() - t
            got = np.array(out[rows])
            del out
            t = time.perf_counter()
            want = harness.reference_rows(A, B, C0, rows, config["alpha"],
                                          config["beta"])
            ref_s = time.perf_counter() - t
            err = harness.max_row_rel_err(got, want)
            gen.undo(saved)
            readings[side].append(err)
            print(json.dumps({"side": side, "workload": workload,
                              "seed": seed, "max_row_rel_err": err,
                              "rows": int(rows.size), "call_s": wall,
                              "reference_s": ref_s}), flush=True)
    print(json.dumps({
        "workload": workload, "limit": config["limits"]["max_row_rel_err"],
        "program_max": max(readings["program"], default=None),
        "control_min": min(readings["control"], default=None),
        "readings": readings}), flush=True)
    return readings


if __name__ == "__main__":
    sys.exit(main())
