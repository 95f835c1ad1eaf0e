"""Export an OOC pipeline timeline as chrome://tracing JSON.

Engine-model spans, one trace format (``repro.core.trace``):

  * ``--mode sim``  — spans from ``simulate()`` under a named hardware
    model: what the schedule *predicts* (the C3/C5 overlap story).
  * ``--mode hybrid`` — engine-model spans of a GEMM co-scheduled across
    the canned gpu+phi profile pair: one trace *process* (lane-group, pid =
    device index) per device, so the balanced concurrent timelines sit side
    by side without stream-id collisions.
  * ``--mode factor`` — engine-model spans of a whole factorization
    schedule (``--kind cholesky|lu``): panel ops, lookahead overlap and the
    streamed trailing update on one timeline.

GEMM and factor traces carry the schedule's block-cache counters as an
instant "reuse" annotation (hits = transfers *not* on the timeline);
``--traversal``/``--evict`` pick the step order and eviction policy so the
elided-transfer effect is visible by diffing two exports.

Open the output at chrome://tracing or https://ui.perfetto.dev.

What a chip *does* is in a profiler trace instead: ``bench/run.py --trace
1`` records one around the benchmark's window, where the engine's
``ooc.*`` spans (``repro.obs.annotate``) sit on the host plane beside the
device's operations, on one clock.

Example:
    PYTHONPATH=src python scripts/export_trace.py --mode sim \
        --M 2048 --N 2048 --K 1024 --budget-mb 16 --hw gpu -o trace.json
"""

from __future__ import annotations

import argparse
import json
import sys

from repro.core import (EVICT_POLICIES, TRAVERSALS, OpKind,
                        build_gemm_schedule, chrome_trace,
                        compile_factor_pipeline, factor_pipeline_spec,
                        gpu_like, phi_like, plan_gemm_partition, simulate,
                        tpu_v5e_ici, tpu_v5e_vmem)
from repro.obs.analyze import TraceAnalysis

HW = {
    "gpu": lambda ns: gpu_like(),
    "phi": lambda ns: phi_like(nstreams=ns),
    "tpu_vmem": lambda ns: tpu_v5e_vmem(),
    "tpu_ici": lambda ns: tpu_v5e_ici(),
}

# informational output; rebound to stderr when the trace itself goes to
# stdout (--out -) so the JSON stays parseable
log = print


def _summarize(doc: dict) -> str:
    """Per-pid digest of a Chrome-trace doc: lane name, span count, busy
    milliseconds per category, and utilization (busy / (wall span × lanes))
    — plus the modeled byte totals and attribution digest when the
    exporting mode attached them (``otherData``)."""
    lanes: dict = {}
    for e in doc.get("traceEvents", ()):
        pid = e.get("pid", 0)
        lane = lanes.setdefault(pid, {"name": f"pid {pid}", "spans": 0,
                                      "busy_ms": {}, "tids": set(),
                                      "t0": None, "t1": None})
        if e.get("ph") == "M" and e.get("name") == "process_name":
            lane["name"] = e["args"]["name"]
        elif e.get("ph") == "X":
            lane["spans"] += 1
            cat = e.get("cat", "span")
            lane["busy_ms"][cat] = (lane["busy_ms"].get(cat, 0.0)
                                    + e.get("dur", 0.0) / 1e3)
            lane["tids"].add(e.get("tid", 0))
            ts, dur = e.get("ts", 0.0), e.get("dur", 0.0)
            lane["t0"] = ts if lane["t0"] is None else min(lane["t0"], ts)
            lane["t1"] = (ts + dur if lane["t1"] is None
                          else max(lane["t1"], ts + dur))
    lines = []
    for pid in sorted(lanes):
        lane = lanes[pid]
        cats = " ".join(f"{c}={ms:.2f}ms"
                        for c, ms in sorted(lane["busy_ms"].items()))
        util = ""
        if lane["t1"] is not None and lane["t1"] > lane["t0"]:
            wall_ms = (lane["t1"] - lane["t0"]) / 1e3
            frac = (sum(lane["busy_ms"].values())
                    / (wall_ms * max(len(lane["tids"]), 1)))
            util = f"  util={frac*100:.0f}%"
        lines.append(f"  pid {pid} [{lane['name']}]: {lane['spans']} spans"
                     + (f"  {cats}" if cats else "") + util)
    for k, v in sorted(doc.get("otherData", {}).items()):
        lines.append(f"  {k}: {v}")
    return "\n".join(lines)


def _emit(doc: dict, args) -> None:
    """Write the trace doc (``--out -`` = stdout) and, with ``--summary``,
    print the per-pid digest."""
    if args.summary:
        log("summary:")
        log(_summarize(doc))
    if args.out == "-":
        json.dump(doc, sys.stdout)
        sys.stdout.write("\n")
    else:
        with open(args.out, "w") as f:
            json.dump(doc, f)
        log(f"wrote {args.out} — load at chrome://tracing or "
            f"ui.perfetto.dev")


def _hybrid_mode(args) -> None:
    from repro.hybrid import (DeviceSpec, device_schedule, plan_hybrid_gemm,
                              simulate_hybrid)
    from repro.tune import gpu_profile, phi_profile

    budget = int(args.budget_mb * 2**20)
    devices = [DeviceSpec("gpu0", gpu_profile(), budget),
               DeviceSpec("phi0", phi_profile(), budget)]
    hplan = plan_hybrid_gemm(args.M, args.N, args.K, devices,
                             nbuf_options=(1, 2), max_steps=512)
    sim = simulate_hybrid(hplan)
    for dp, span in zip(hplan.device_plans, sim.device_makespans):
        log(f"  {dp.device.name}: rows [{dp.start}, "
            f"{dp.start + dp.length}) s{dp.plan.nstreams}b{dp.plan.nbuf} "
            f"-> {span*1e3:.2f} ms")
    doc = sim.to_chrome_trace()
    scheds = [device_schedule(hplan, dp) for dp in hplan.device_plans]
    doc["otherData"] = {
        "h2d_bytes": sum(s.total_bytes(OpKind.H2D) for s in scheds),
        "d2h_bytes": sum(s.total_bytes(OpKind.D2H) for s in scheds),
        "analysis": {
            dp.device.name: TraceAnalysis.from_sim(
                sched, res,
                hw=dp.device.profile.model_for(dp.plan.nstreams)).digest()
            for dp, sched, (_, res) in zip(hplan.device_plans, scheds,
                                           sim.per_device)
        },
    }
    log(f"hybrid gemm {args.M}x{args.N}x{args.K}: aggregate makespan "
        f"{sim.makespan*1e3:.2f} ms across {len(hplan.device_plans)} "
        f"devices (one lane-group each)")
    _emit(doc, args)


def _factor_mode(args) -> None:
    budget = int(args.budget_mb * 2**20)
    spec = factor_pipeline_spec(args.n, args.panel, budget, 4,
                                kind=args.kind, lookahead=args.lookahead,
                                nbuf=args.nbuf)
    sched = compile_factor_pipeline(spec, nstreams=args.nstreams,
                                    nbuf=args.nbuf, evict=args.evict)
    res = simulate(sched, HW[args.hw](args.nstreams))
    name = (f"{args.kind} n={args.n} panel={spec.panel} "
            f"la{spec.lookahead} s{args.nstreams}b{args.nbuf} {args.evict}")
    reuse = sched.reuse.get("Fr", {})
    log(f"{name}: {len(sched.ops)} ops, simulated makespan "
        f"{res.makespan*1e3:.2f} ms on {args.hw}; factored-row cache "
        f"{reuse.get('hits', 0)} hits / {reuse.get('misses', 0)} "
        f"transfers")
    doc = chrome_trace(res.op_spans, process_name=name, reuse=sched.reuse)
    doc["otherData"] = {
        "h2d_bytes": sched.total_bytes(OpKind.H2D),
        "d2h_bytes": sched.total_bytes(OpKind.D2H),
        "analysis": TraceAnalysis.from_sim(
            sched, res, hw=HW[args.hw](args.nstreams)).digest(),
    }
    _emit(doc, args)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode", choices=("sim", "hybrid", "factor"),
                    default="sim")
    ap.add_argument("--M", type=int, default=2048)
    ap.add_argument("--N", type=int, default=2048)
    ap.add_argument("--K", type=int, default=1024)
    ap.add_argument("--budget-mb", type=float, default=16.0)
    ap.add_argument("--nstreams", type=int, default=2)
    ap.add_argument("--nbuf", type=int, default=2)
    ap.add_argument("--traversal", choices=TRAVERSALS, default="col",
                    help="block-grid step order (sim mode)")
    ap.add_argument("--evict", choices=EVICT_POLICIES, default="lru",
                    help="block-cache eviction policy (sim/factor)")
    ap.add_argument("--kind", choices=("cholesky", "lu"), default="cholesky",
                    help="factorization kind for --mode factor")
    ap.add_argument("--n", type=int, default=2048,
                    help="matrix order for --mode factor")
    ap.add_argument("--panel", type=int, default=256,
                    help="panel width for --mode factor")
    ap.add_argument("--lookahead", type=int, default=1,
                    help="lookahead depth for --mode factor")
    ap.add_argument("--hw", choices=sorted(HW), default="gpu",
                    help="hardware model for --mode sim")
    ap.add_argument("-o", "--out", default="trace.json",
                    help="output path; '-' writes the JSON to stdout "
                         "(informational output moves to stderr)")
    ap.add_argument("--summary", action="store_true",
                    help="print a per-pid digest (lane, span count, busy "
                         "ms per category, modeled byte totals)")
    args = ap.parse_args()

    global log
    if args.out == "-":
        log = lambda *a, **kw: print(*a, file=sys.stderr, **kw)  # noqa: E731

    if args.mode == "hybrid":
        _hybrid_mode(args)
        return
    if args.mode == "factor":
        _factor_mode(args)
        return

    budget = int(args.budget_mb * 2**20)
    bpe = 4
    part = plan_gemm_partition(args.M, args.N, args.K, budget, bpe,
                               nbuf=args.nbuf, nstreams=args.nstreams)
    sched = build_gemm_schedule(part, nstreams=args.nstreams, nbuf=args.nbuf,
                                traversal=args.traversal, evict=args.evict)
    name = (f"gemm {args.M}x{args.N}x{args.K} h{part.h}xw{part.w} "
            f"s{args.nstreams}b{args.nbuf} {args.traversal}/{args.evict}")

    hw = HW[args.hw](args.nstreams)
    res = simulate(sched, hw)
    analysis = TraceAnalysis.from_sim(sched, res, hw=hw).digest()
    log(f"{name}: {len(sched.ops)} ops, "
        f"simulated makespan {res.makespan*1e3:.2f} ms on {args.hw}")

    doc = chrome_trace(res.op_spans, process_name=name, reuse=sched.reuse)
    doc["otherData"] = {"h2d_bytes": sched.total_bytes(OpKind.H2D),
                        "d2h_bytes": sched.total_bytes(OpKind.D2H),
                        "analysis": analysis}
    _emit(doc, args)


if __name__ == "__main__":
    main()
