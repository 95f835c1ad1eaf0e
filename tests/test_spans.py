"""The engine's spans on the profiler's timeline (``repro.obs.annotate``).

A small out-of-core ``ooc_gemm`` runs on the CPU under a ``jax.profiler``
session, and the ``.xplane.pb`` it writes is read back with
``jax.profiler.ProfileData``: the ``ooc.*`` spans are on the host plane,
one per transfer, nested as the benchmark's readers assume.
"""

from __future__ import annotations

import glob
import os
from contextlib import contextmanager
from dataclasses import dataclass

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import (HostOocRuntime, OpKind, ScheduleExecutor,
                        build_gemm_schedule, ooc_gemm, plan_gemm_partition)
from repro.obs import Observability

SPANS = ("ooc.gemm", "ooc.entry.copy_c", "ooc.exec.run", "ooc.exec.h2d",
         "ooc.exec.d2h", "ooc.exec.store")
M, N, K = 256, 192, 128
BUDGET = 96 * 2**10          # a sixth of A, B and C: out of core


@dataclass(frozen=True)
class Span:
    name: str
    start: int
    end: int
    thread: int              # index of the host plane's line
    stats: tuple

    def within(self, other: "Span") -> bool:
        return other.start <= self.start and self.end <= other.end

    def overlaps(self, other: "Span") -> bool:
        return self.start < other.end and other.start < self.end


@contextmanager
def profiled(log_dir):
    """A profiler session without the Python tracer; yields the list that
    holds the ``ooc.*`` and ``probe.*`` host spans once it has ended."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    spans = []
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        yield spans
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(log_dir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for thread, line in enumerate(plane.lines):
            spans.extend(Span(e.name.split("#", 1)[0], int(e.start_ns),
                              int(e.end_ns), thread,
                              tuple(sorted(dict(e.stats).items())))
                         for e in line.events
                         if e.name.startswith(("ooc.", "probe.")))


def operands():
    rng = np.random.default_rng(7)
    return (rng.standard_normal((M, K), dtype=np.float32),
            rng.standard_normal((K, N), dtype=np.float32),
            rng.standard_normal((M, N), dtype=np.float32))


@pytest.mark.parametrize("mode", ScheduleExecutor.MODES)
def test_ooc_gemm_spans_tile_the_call_in_both_modes(tmp_path, mode):
    A, B, C = operands()
    ex = ScheduleExecutor(mode=mode)
    with profiled(tmp_path) as spans:
        out = ooc_gemm(A, B, C, 1.0, 0.5, budget_bytes=BUDGET,
                       backend="host", runtime=HostOocRuntime(executor=ex))
    np.testing.assert_allclose(out, A @ B + 0.5 * C, rtol=1e-4, atol=1e-4)
    by = {name: [s for s in spans if s.name == name] for name in SPANS}
    assert all(by.values()), {n: len(v) for n, v in by.items()}
    # the schedule ooc_gemm builds: one span per transfer op
    sched = build_gemm_schedule(
        plan_gemm_partition(M, N, K, BUDGET, 4), nstreams=2, nbuf=2)
    kinds = [op.kind for op in sched.ops]
    assert len(by["ooc.exec.h2d"]) == kinds.count(OpKind.H2D)
    assert len(by["ooc.exec.d2h"]) == len(by["ooc.exec.store"]) \
        == kinds.count(OpKind.D2H)
    assert sum(dict(s.stats)["bytes"] for s in by["ooc.exec.h2d"]) \
        == ex.last_h2d_bytes > 0
    # nesting: transfers in the run, the run and the copy in the call
    gemm, = by["ooc.gemm"]
    run, = by["ooc.exec.run"]
    assert run.within(gemm)
    assert all(s.within(gemm) and not s.overlaps(run)
               for s in by["ooc.entry.copy_c"])
    for name in ("ooc.exec.h2d", "ooc.exec.d2h", "ooc.exec.store"):
        assert all(s.within(run) for s in by[name]), name
    # no H2D span nests in, or overlaps, a write-back span of its thread
    backs = by["ooc.exec.d2h"] + by["ooc.exec.store"]
    assert not any(h.thread == w.thread and h.overlaps(w)
                   for h in by["ooc.exec.h2d"] for w in backs)


def test_entry_copy_span_says_whether_the_result_was_reused(tmp_path):
    A, B, C = operands()
    rt = HostOocRuntime()
    with profiled(tmp_path) as spans:
        for _ in range(2):
            out = ooc_gemm(A, B, C, 1.0, 0.5, budget_bytes=BUDGET,
                           backend="host", runtime=rt)
            del out                # the second call writes into the first
    copies = [s for s in spans if s.name == "ooc.entry.copy_c"]
    assert len(copies) == 2
    # within each copy, the span of getting the result names the choice
    for copy, name in zip(copies, ("ooc.entry.alloc_result",
                                   "ooc.entry.reuse_result")):
        inner = [s for s in spans if s.name.startswith("ooc.entry.")
                 and s.name != "ooc.entry.copy_c" and s.within(copy)]
        assert [s.name for s in inner] == [name]


def test_in_core_ooc_gemm_is_one_span_without_executor(tmp_path):
    A, B, C = operands()
    with profiled(tmp_path) as spans:
        ooc_gemm(A, B, C, 1.0, 0.5, budget_bytes=2**30, backend="host")
    assert [s.name for s in spans] == ["ooc.gemm"]


def test_obs_span_reaches_the_profiler_and_the_tracer_only_when_on(
        tmp_path):
    obs = Observability()
    with profiled(tmp_path / "off") as off:
        with obs.span("probe.off", cat="test", k=1) as sp:
            sp.annotate(seen=False)        # no tracer: a no-op
    obs.enable(metrics=False, trace=True)
    with profiled(tmp_path / "on") as on:
        with obs.span("probe.on", cat="test", k=2) as sp:
            sp.annotate(seen=True)
    off_span, = [s for s in off if s.name == "probe.off"]
    on_span, = [s for s in on if s.name == "probe.on"]
    assert dict(off_span.stats) == {"k": 1} and dict(on_span.stats) == {
        "k": 2}
    traced, = obs.tracer.spans()
    assert traced.name == "probe.on" and traced.cat == "test"
    assert dict(traced.args) == {"k": "2", "seen": "True"}
