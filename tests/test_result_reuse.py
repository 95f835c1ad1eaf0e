"""``HostOocRuntime`` recycles its result buffer.

The runtime keeps the last result it returned and writes the next result
of the same shape and dtype into it once nothing else refers to it.  A
caller that still holds a result, a view of it, or passes it back in as C
never sees it change.  Every case runs both host-tier entry points,
``ooc_gemm`` and ``ooc_syrk``, on a small out-of-core shape and checks the
values against ``A @ B + beta * C`` (``P @ P.T + beta * C``).
"""

from __future__ import annotations

import gc
import sys
import weakref

import numpy as np
import pytest

from repro.core import HostOocRuntime, OpKind, ooc_gemm, ooc_syrk
from repro.core import runtime as rtmod
from repro.fault import FaultPlan, FaultPolicy, FaultSpec, OomError

M, N, K = 256, 192, 128
BUDGET = 96 * 2**10          # out of core for both kernels at M rows
BETA = 0.5
KINDS = ("gemm", "syrk")


def ptr(a: np.ndarray) -> int:
    return a.__array_interface__["data"][0]


class Case:
    """Operands of one kernel, ``rows`` rows of C; ``case(rt, C)`` runs it
    on the host tier and ``case.check(out, C)`` compares the values."""

    def __init__(self, kind: str, rows: int = M, seed: int = 0,
                 c_dtype=np.float32):
        rng = np.random.default_rng(seed)
        self.kind = kind
        if kind == "gemm":
            self.ops = (rng.standard_normal((rows, K), dtype=np.float32),
                        rng.standard_normal((K, N), dtype=np.float32))
            cols = N
        else:
            self.ops = (rng.standard_normal((rows, K), dtype=np.float32),)
            cols = rows
        self.C = rng.standard_normal((rows, cols)).astype(c_dtype)

    def __call__(self, rt, C=None, **kw):
        C = self.C if C is None else C
        if self.kind == "gemm":
            return ooc_gemm(*self.ops, C, 1.0, BETA, budget_bytes=BUDGET,
                            backend="host", runtime=rt, **kw)
        return ooc_syrk(*self.ops, C, 1.0, BETA, budget_bytes=BUDGET,
                        backend="host", runtime=rt, **kw)

    def want(self, C=None) -> np.ndarray:
        C = self.C if C is None else C
        a = np.asarray(self.ops[0], np.float64)
        b = np.asarray(self.ops[1], np.float64) if self.kind == "gemm" \
            else a.T
        return a @ b + BETA * np.asarray(C, np.float64)

    def check(self, out, C=None) -> None:
        C = self.C if C is None else C
        assert out.shape == C.shape and out.dtype == C.dtype
        np.testing.assert_allclose(out, self.want(C), rtol=1e-4, atol=1e-3)


def oom_at_first_compute(sched):
    i = next(i for i, op in enumerate(sched.ops)
             if op.kind == OpKind.COMPUTE)
    return FaultPlan(specs=(FaultSpec(op=i, cls="oom"),))


@pytest.mark.parametrize("kind", KINDS)
def test_a_dropped_result_is_written_into_again(kind):
    case, rt = Case(kind), HostOocRuntime()
    first = case(rt)
    case.check(first)
    kept = weakref.ref(first)
    del first
    out = case(rt)
    assert out is kept()
    case.check(out)


def test_the_sole_reference_count_is_what_a_kept_result_reads():
    # ``_result`` compares a kept result's count with the one measured at
    # import; a count that the interpreter reads otherwise would reuse a
    # held result, or never reuse one
    rt = HostOocRuntime()
    r = Case("gemm")(rt)
    del r
    idle, rt._idle = rt._idle, None
    assert sys.getrefcount(idle) == rtmod._SOLE_REFCOUNT
    view = idle[:1]
    assert sys.getrefcount(idle) == rtmod._SOLE_REFCOUNT + 1
    del view


@pytest.mark.parametrize("kind", KINDS)
def test_results_kept_alive_are_distinct_and_stay_right(kind):
    cases = [Case(kind, seed=s) for s in range(3)]
    rt = HostOocRuntime()
    kept = []
    for case in cases:
        kept.append(case(rt, case.C))
    for i, (case, out) in enumerate(zip(cases, kept)):
        case.check(out)
        assert not any(np.shares_memory(out, other)
                       for other in kept[i + 1:])


@pytest.mark.parametrize("kind", KINDS)
def test_a_held_view_blocks_reuse(kind):
    case, rt = Case(kind), HostOocRuntime()
    r = case(rt)
    view = r[:3]
    del r
    out = case(rt)
    assert not np.shares_memory(out, view)
    np.testing.assert_allclose(view, case.want()[:3], rtol=1e-4, atol=1e-3)
    case.check(out)


@pytest.mark.parametrize("kind", KINDS)
def test_a_result_passed_back_as_c_is_never_written_into(kind):
    # the caller's loop of api.py: C = rt.gemm(A, B, C, ...)
    case, rt = Case(kind), HostOocRuntime()
    C = case.C.copy()
    want = np.asarray(C, np.float64)
    for _ in range(3):
        before = ptr(C)
        C = case(rt, C)
        assert ptr(C) != before      # the old C is alive until it returns
        want = case.want(want)
        np.testing.assert_allclose(C, want, rtol=1e-4, atol=1e-2)


@pytest.mark.parametrize("change", ("shape", "dtype"))
@pytest.mark.parametrize("kind", KINDS)
def test_a_new_shape_or_dtype_drops_the_idle_result(kind, change):
    rt = HostOocRuntime()
    r = Case(kind)(rt)
    gone = weakref.ref(r)
    del r
    other = Case(kind, rows=M // 2) if change == "shape" \
        else Case(kind, c_dtype=np.float64)
    out = other(rt)
    assert gone() is None
    other.check(out)


@pytest.mark.parametrize("kind", KINDS)
def test_release_empties_the_runtime(kind):
    case, rt = Case(kind), HostOocRuntime()
    r = case(rt)
    gone = weakref.ref(r)
    del r
    assert gone() is not None          # held for the next call
    rt.release()
    assert gone() is None
    out = case(rt)
    case.check(out)


@pytest.mark.parametrize("kind", KINDS)
def test_an_oom_keeps_no_buffer_of_the_failed_attempt(kind):
    case, rt = Case(kind), HostOocRuntime()
    r = case(rt)
    gone = weakref.ref(r)
    del r
    pol = FaultPolicy(sleep=lambda s: None)
    kw = {"faults": oom_at_first_compute, "fault_policy": pol}
    if kind == "gemm":
        # the degrade ladder: the failed attempt took the idle result
        out = case(rt, **kw)
        assert [d.action for d in pol.degrades] == ["halve_nbuf"]
        gc.collect()
        assert gone() is None           # out is a fresh buffer
        case.check(out)
        held = weakref.ref(out)
        del out
    else:
        # ooc_syrk has no ladder: the oom reaches the caller
        with pytest.raises(OomError):
            case(rt, **kw)
    gc.collect()
    assert gone() is None
    again = case(rt)
    if kind == "gemm":
        # what the runtime kept is the result the ladder returned
        assert again is held()
    case.check(again)
