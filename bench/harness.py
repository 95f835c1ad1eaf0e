"""The chip benchmark's harness: one cell, one seed, one window.

Everything that belongs to one cell is found by name from
``BENCHMARK.json``: the configuration in the file its ``configs`` entry
names, the traffic in ``bench/traffic/<traffic>.json`` and each metric's
reader in ``bench/metrics/<metric>.py``, a module with ``read(run)`` that
returns a number, or ``None`` where it finds nothing to read.  A metric
``<quantity>.<part>``, one quantity split by the end-to-end metric it
moves, has the reader of ``<quantity>`` unless it has a file of its own.
Adding a cell, a configuration or a metric adds files and entries and
edits none.

A run makes A, B and C0 on the host from the seed, warms up the programs a
call runs (:func:`warm_up`), then drives ``ooc_gemm`` in a closed loop, one caller issuing calls back to
back: calls start while the window's clock is under ``seconds`` and every
call that starts is finished.  Before each call a few rows of A and B are
rewritten, so no call sees the operands of another.  Each call goes from
operands in host memory to its result in host memory; the harness keeps
the rows it checks and drops the result.  Once the window has closed and
the device's peak memory has been read, it rolls the operands back call by
call and compares the checked rows of a sample of the calls with a host
float64 reference.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import resource
import shutil
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from contextlib import nullcontext
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from bench import gen, work
from bench import trace as tr

ROOT = Path(__file__).resolve().parents[1]
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


# -- the spec, found by name -------------------------------------------------
def load_spec(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _entry(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} {name!r}; known: "
                   f"{', '.join(e['name'] for e in entries)}")


def resolve(spec: dict, workload: str, root: Path = ROOT) -> tuple:
    """``(cell, configuration, traffic)`` of the cell named ``workload``."""
    cell = _entry(spec["workloads"], workload, "workload")
    conf = _entry(spec["configs"], cell["config"], "configuration")
    config = json.loads((Path(root) / conf["file"]).read_text())
    traffic = json.loads((Path(root) / "bench" / "traffic"
                          / f"{cell['traffic']}.json").read_text())
    if traffic["n"] > config["n_max"]:
        raise ValueError(f"{workload}: n = {traffic['n']} is over the "
                         f"configuration's n_max = {config['n_max']}")
    return cell, config, traffic


def metrics_for(spec: dict, workload: str, traced: bool) -> list:
    """The cell's end-to-end metrics, or with a trace its per-layer ones."""
    group = spec["per_layer" if traced else "end_to_end"]
    return [m for m in group
            if workload in m.get("workloads", [workload])]


def load_reader(name: str, root: Path = ROOT):
    metrics = Path(root) / "bench" / "metrics"
    path = metrics / f"{name}.py"
    if not path.exists():
        path = metrics / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- what a run records -------------------------------------------------------
@dataclass
class Call:
    index: int
    wall_s: float             # call, operands in host memory to result there
    m: int
    n: int
    k: int
    bytes_per_el: int
    counters: dict            # the program's own counts for this call
    rows: np.ndarray = field(repr=False)    # indices of the checked rows
    got: np.ndarray = field(repr=False)     # those rows of the result
    saved: list = field(repr=False)         # what undoes its mutation


@dataclass
class Run:
    """What the metric readers read."""
    config: dict
    setup_s: float
    window_s: float
    calls: list
    memory_peak_bytes: int
    peaks: dict
    trace: tr.Trace | None = None
    notes: dict = field(default_factory=dict)


# -- the device ---------------------------------------------------------------
def memory_stats(device) -> dict:
    return device.memory_stats() or {}


def mem_available() -> int:
    with open("/proc/meminfo") as f:
        for line in f:
            if line.startswith("MemAvailable:"):
                return int(line.split()[1]) * 1024
    raise RuntimeError("no MemAvailable in /proc/meminfo")


def host_bytes_needed(config: dict, traffic: dict) -> int:
    """A, B, C0 and one result, and the configuration's working slack."""
    m = n = k = traffic["n"]
    bpe = np.dtype(config["dtype"]).itemsize
    return bpe * (m * k + k * n + 2 * m * n) + int(
        config["host_slack_gib"] * 2**30)


def enable_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where set, which JAX reads itself, else a fixed directory in the
    checkout."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(Path(root) / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


class CompileCounter:
    """Counts XLA compilations (and loads from the persistent cache) while
    ``active``."""

    def __init__(self):
        self.active = False
        self.count = 0

    def __call__(self, event: str, duration: float, **kw) -> None:
        if self.active and event == BACKEND_COMPILE:
            self.count += 1


# -- the system under test ----------------------------------------------------
def make_call(config: dict, devices: list, bytes_limit: int):
    """``call(A, B, C0) -> (result in host memory, counters)`` through
    ``ooc_gemm`` as the configuration states it, its dots at the
    configuration's matmul precision."""
    import jax

    from repro.core import HostOocRuntime, MeshOocRuntime, ooc_gemm

    jax.config.update("jax_default_matmul_precision", config["precision"])
    alpha, beta = config["alpha"], config["beta"]
    budget = bytes_limit // config["budget_divisor"]
    if config["backend"] == "host":
        rt = HostOocRuntime()
        ex = rt.executor

        def call(A, B, C0):
            # a call that takes the in-core branch leaves this None
            ex.last_wall_seconds = None
            out = ooc_gemm(A, B, C0, alpha, beta, budget_bytes=budget,
                           backend="host", runtime=rt)
            counters = {} if ex.last_wall_seconds is None else {
                "executor_wall_s": ex.last_wall_seconds,
                "h2d_bytes": ex.last_h2d_bytes,
                "d2h_bytes": ex.last_d2h_bytes}
            return out, counters
        return call
    if config["backend"] == "mesh":
        from jax.sharding import AxisType

        mesh = jax.make_mesh((len(devices),), ("model",), devices=devices,
                             axis_types=(AxisType.Auto,))
        rt = MeshOocRuntime(mesh)

        def call(A, B, C0):
            out = ooc_gemm(A, B, C0, alpha, beta, budget_bytes=budget,
                           backend="mesh", runtime=rt)
            return np.asarray(out), {}
        return call
    raise ValueError(f"unknown backend {config['backend']!r}")


# -- operands, window, check -------------------------------------------------
def make_operands(config: dict, traffic: dict, seed: int,
                  out: tuple = (None, None, None)) -> tuple:
    """A and B standard normal, C0 of the scale of A @ B so that the
    ``beta * C0`` term weighs in the comparison as much as the product.
    ``out`` are arrays to fill in place of new ones."""
    n = traffic["n"]
    if np.dtype(config["dtype"]) != np.float32:
        raise ValueError(f"no generator for dtype {config['dtype']!r}")
    return tuple(gen.random_matrix((n, n), seed, s, out=o,
                                   scale=math.sqrt(n) if s == 2 else 1.0)
                 for s, o in enumerate(out))


def warm_up(call, A, B, C0, config: dict, bytes_limit: int) -> None:
    """Compile, or load from the cache, the programs a call runs, at its
    shapes.  A host-tier call out of core runs the block product at the
    partition's block shapes, the same program a call in core runs on
    operands of one block's size; so one call on a corner of the operands
    per block shape warms it up at a fraction of a whole call's transfers.
    Any other call is warmed up by one whole call."""
    from repro.core import is_in_core, plan_gemm_partition

    m, k = A.shape
    n = B.shape[1]
    budget = bytes_limit // config["budget_divisor"]
    if config["backend"] != "host" or is_in_core(m, n, k, budget,
                                                  A.dtype.itemsize):
        call(A, B, C0)
        return
    part = plan_gemm_partition(m, n, k, budget, A.dtype.itemsize)
    for r in sorted({min(part.bm, m - i) for i in range(0, m, part.bm)}):
        for c in sorted({min(part.bn, n - j) for j in range(0, n, part.bn)}):
            call(A[:r], B[:, :c], C0[:r, :c])


def run_window(call, A, B, C0, traffic: dict, seed: int, seconds: float,
               annotate=None, log=None) -> tuple:
    """Closed loop of calls for ``seconds``; returns ``(calls, window_s,
    error)``: the window runs from its start to the return of the last
    call, and ``error`` is the exception that ended it early."""
    annotate = annotate or (lambda name: nullcontext())
    n = traffic["n"]
    bpe = A.dtype.itemsize
    calls, error = [], None
    t0 = end = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        i = len(calls)
        with annotate("bench.mutate"):
            saved = gen.mutate(A, B, seed, i, traffic["mutate_band"])
        rows = gen.check_rows(n, seed, i, traffic["check_groups"],
                              traffic["check_rows"])
        start = time.perf_counter()
        try:
            with annotate(f"bench.call[{i}]"):
                out, counters = call(A, B, C0)
        except Exception as e:  # the run reports it as a failed call
            gen.undo(saved)
            error = e
            break
        end = time.perf_counter()
        wall = end - start
        with annotate("bench.take_rows"):
            got = np.array(out[rows])
            del out   # one result alive at a time
        calls.append(Call(i, wall, n, n, n, bpe, counters, rows, got,
                          saved))
        if log:
            log(f"call {i}: {wall:.6f} s {counters}")
    return calls, end - t0, error


def reference_rows(A, B, C0, rows, alpha, beta,
                   block: int = 2048) -> np.ndarray:
    """``alpha * A[rows] @ B + beta * C0[rows]`` in host float64.  B is
    widened ``block`` rows at a time, so no float64 copy of it is made,
    and 256 rows to a thread: on one thread the widening took most of the
    reference's time."""
    a = np.asarray(A[rows], np.float64)
    acc = np.zeros((len(rows), B.shape[1]))
    wide = np.empty((min(block, B.shape[0]), B.shape[1]))
    with ThreadPoolExecutor(os.cpu_count()) as pool:
        for k in range(0, B.shape[0], block):
            src = B[k:k + block]
            dst = wide[:len(src)]
            list(pool.map(lambda i: np.copyto(dst[i:i + 256],
                                              src[i:i + 256]),
                          range(0, len(src), 256)))
            acc += a[:, k:k + len(src)] @ dst
    return alpha * acc + beta * np.asarray(C0[rows], np.float64)


def max_row_rel_err(got, want) -> float:
    """The largest over rows of ``|got - want| / |want|`` (2-norms)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    err = np.linalg.norm(got - want, axis=1) / np.linalg.norm(want, axis=1)
    return float(np.max(err)) if np.all(np.isfinite(err)) else math.inf


def check(calls: list, A, B, C0, config: dict, traffic: dict,
          seed: int) -> tuple:
    """Roll the operands back call by call and compare a sample of the
    calls, drawn from the seed, with the reference.  Returns
    ``(worst error, number of calls checked, number failed)``."""
    limit = config["limits"]["max_row_rel_err"]
    rng = np.random.default_rng([seed, 13])
    k = min(len(calls), traffic["check_calls"])
    chosen = set(rng.choice(len(calls), k, replace=False).tolist())
    worst, failed = 0.0, 0
    for c in reversed(calls):
        if c.index in chosen:
            err = max_row_rel_err(c.got, reference_rows(
                A, B, C0, c.rows, config["alpha"], config["beta"]))
            worst = max(worst, err)
            failed += not err <= limit
        gen.undo(c.saved)
    return worst, k, failed


# -- one run ------------------------------------------------------------------
def _devices(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"bench: JAX found no TPU (platform "
                         f"{devs[0].platform!r}); nothing was run")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs[:chips]


def run_workload(workload: str, seed: int, seconds: float, traced: bool, *,
                 root: Path = ROOT, t_start: float | None = None,
                 trace_dir: str | None = None, require_tpu: bool = True,
                 call_factory=make_call, log=None) -> dict:
    """One run of one cell; returns the result line.  ``call_factory`` is
    :func:`make_call`, or in the tests a broken stand-in for the timed
    path."""
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    spec = load_spec(root)
    cell, config, traffic = resolve(spec, workload, root)
    wanted = metrics_for(spec, workload, traced)
    readers = {m["name"]: load_reader(m["name"], root) for m in wanted}
    devices = _devices(cell["chips"], require_tpu)
    kind = devices[0].device_kind
    peaks = work.peaks(kind) if require_tpu else {}
    log(f"bench: {workload} seed {seed} on {len(devices)} x {kind}, "
        f"compile cache {enable_compile_cache(root)}")

    need, avail = host_bytes_needed(config, traffic), mem_available()
    if avail < need:
        raise SystemExit(f"bench: {workload} needs {need / 2**30:.2f} GiB "
                         f"of host RAM, {avail / 2**30:.2f} GiB available; "
                         f"the problem is never shrunk")
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    try:
        bytes_limit = memory_stats(devices[0])["bytes_limit"]
        call = call_factory(config, devices, bytes_limit)
        A, B, C0 = make_operands(config, traffic, seed)
        t = time.perf_counter()
        warm_up(call, A, B, C0, config, bytes_limit)
        log(f"warm-up: {time.perf_counter() - t:.6f} s")

        tmp = None
        if traced:
            tmp = trace_dir or tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(tmp, profiler_options=opts)
        annotate = jax.profiler.TraceAnnotation if traced else None
        counter.active = True
        setup_s = time.perf_counter() - t_start
        with (annotate(tr.WINDOW) if traced else nullcontext()):
            calls, window_s, error = run_window(
                call, A, B, C0, traffic, seed, seconds, annotate, log)
        counter.active = False
        trace = None
        if traced:
            jax.profiler.stop_trace()
            trace = tr.load(tmp)
            if trace_dir is None:
                shutil.rmtree(tmp, ignore_errors=True)
        peak = max(memory_stats(d).get("peak_bytes_in_use", 0)
                   for d in devices)
    finally:
        jax.monitoring.unregister_event_duration_listener(counter)

    worst, checked, failed = check(calls, A, B, C0, config, traffic, seed)
    failed += error is not None
    limit = config["limits"]["max_row_rel_err"]
    correct = error is None and checked > 0 and worst <= limit
    if error is not None:
        log(f"bench: call {len(calls)} raised {error!r}")
    walls = " ".join(f"{c.wall_s:.6f}" for c in calls)
    log(f"calls: {len(calls)} in {window_s:.6f} s, walls {walls}; "
        f"compiles in window: {counter.count}")
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20
    log(f"host peak RSS: {rss:.3f} GiB")

    run = Run(config, setup_s, window_s, calls, peak, peaks, trace)
    metrics = {}
    for m in wanted:
        value = readers[m["name"]](run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = {"platform": devices[0].platform, "kind": kind,
           "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": len(calls) + (
        error is not None), "failed": failed, "metrics": metrics,
        "device": dev}
    if trace is not None and trace.chips:
        dev["busy_s"] = sum(tr.busy_seconds(c, trace.window)
                            for c in trace.chips) / len(trace.chips)
        dev["window_s"] = trace.window_s
        result["breakdown"] = {"device_ops": tr.device_ops(trace),
                               "idle_gaps": tr.longest_gaps(trace)}
    result["window_s"] = window_s
    result["window_compiles"] = counter.count
    if run.notes:
        result["notes"] = run.notes
    result["compared"] = {"max_row_rel_err": {
        "value": worst if math.isfinite(worst) else None, "limit": limit}}
    return result
