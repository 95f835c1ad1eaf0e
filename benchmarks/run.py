"""Benchmark harness — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV:
  bench_overhead    — claim C1  (<=10 % abstraction overhead; paper §VI)
                      + the observability guard (disabled obs hooks <2 %)
  bench_transition  — claim C2  (0 % loss at the in/out-of-core boundary;
                                 Fig. 5 green line)
  bench_pipeline    — claims C3+C5 (vs CUBLAS-XT-style vendor schedule;
                                 stream-width vs hardware; Fig. 5a/5b/5c)
  bench_loc         — claim C4  (75 % LOC reduction)
  bench_validate    — validate_schedule scaling guard (linear-ish)
  bench_simulate    — simulate() ready-queue guard + reference equivalence
  bench_tune        — autotuner: tuned vs default makespans (C5 selection)
  bench_hybrid      — hybrid co-scheduling: balanced split vs best single
                      device (beyond paper; DESIGN.md §7)
  bench_reuse       — block cache + traversal order: H2D bytes-moved and
                      hit-rate vs the naive schedule (DESIGN.md §9); rows
                      land in benchmarks/bench_reuse.json so the perf
                      trajectory tracks traffic, not just makespan
  bench_fault       — resilience cost: simulated recovery overhead guard
                      (<10 % at a 1 % fault rate) plus an executed pinned
                      fault corpus recovering bitwise (DESIGN.md §12)
  bench_exec        — concurrent executor guards: engine-overlap ratio
                      (busy/makespan > 1.0 in mode="concurrent") and the
                      ExecutablePlan cache's dispatch-cost reduction
                      (DESIGN.md §13)

Each module additionally runs with the process metric registry enabled
(DESIGN.md §10) and, when it recorded anything, leaves a
``benchmarks/<module>.metrics.json`` sidecar next to the ``bench_*.json``
score files — the exact byte/op accounting behind each number, uploaded as
a CI artifact and renderable via ``scripts/run_report.py --input``.

Caveat: timed sections therefore run with metrics ON, which is fine — the
publish path is per-run and bench_overhead's ``obs_disabled_overhead`` row
separately guards the disabled cost.
"""

from __future__ import annotations

import json
import os
import sys
import traceback


def _write_sidecar(obs, mod_name: str) -> None:
    """Snapshot the registry into ``benchmarks/<module>.metrics.json``
    (skipped when the module recorded nothing)."""
    snap = obs.snapshot()
    if not snap["metrics"] and not snap["drift"]["records"]:
        return
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        f"{mod_name}.metrics.json")
    with open(path, "w") as f:
        json.dump(snap, f, indent=1, sort_keys=True)


def main() -> None:
    from benchmarks import (bench_exec, bench_fault, bench_hybrid,
                            bench_loc, bench_overhead, bench_pipeline,
                            bench_reuse, bench_simulate,
                            bench_transition, bench_tune, bench_validate)
    from repro.compile_cache import enable_compile_cache
    from repro.obs import get_observability

    enable_compile_cache()
    obs = get_observability()
    print("name,us_per_call,derived")
    failures = 0
    for mod in (bench_overhead, bench_transition, bench_pipeline,
                bench_loc, bench_validate, bench_simulate,
                bench_tune, bench_hybrid, bench_reuse, bench_fault,
                bench_exec):
        mod_name = mod.__name__.rsplit(".", 1)[-1]
        obs.reset()
        obs.enable(metrics=True)
        try:
            for row in mod.run():
                derived = str(row["derived"]).replace(",", ";")
                print(f"{row['name']},{row['us_per_call']:.1f},{derived}")
            _write_sidecar(obs, mod_name)
        except Exception as e:
            failures += 1
            print(f"{mod.__name__},0.0,ERROR {type(e).__name__}: {e}")
            traceback.print_exc(file=sys.stderr)
        finally:
            obs.reset()
    if failures:
        raise SystemExit(f"{failures} benchmark module(s) failed")


if __name__ == "__main__":
    main()
