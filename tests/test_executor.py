"""ScheduleExecutor: one interpreter for every host path, extensible by spec.

Covers the PipelineSpec -> Schedule -> Executor contract end-to-end: typed
payloads, positional handler dispatch, async double-buffered write-back, and
that a brand-new kernel (scaled block copy) rides the DSL with ~20 lines and
no interpreter code.
"""

import jax
import numpy as np
import pytest

from repro.core import runtime
from repro.core import (
    BlockRef,
    ComputeStage,
    HostOocRuntime,
    OpKind,
    PipelineSpec,
    ScheduleExecutor,
    SliceRef,
    StreamedOperand,
    WriteBack,
    build_gemm_schedule,
    compile_pipeline,
    plan_gemm_partition,
    validate_schedule,
)


def _problem(rng, M, N, K):
    A = rng.standard_normal((M, K)).astype(np.float32)
    B = rng.standard_normal((K, N)).astype(np.float32)
    C = rng.standard_normal((M, N)).astype(np.float32)
    return A, B, C


def test_schedules_carry_typed_payloads():
    part = plan_gemm_partition(512, 384, 256, 1_000_000, 4)
    sched = build_gemm_schedule(part)
    for op in sched.ops:
        if op.kind == OpKind.COMPUTE:
            assert isinstance(op.payload, BlockRef), op.tag
        else:
            assert isinstance(op.payload, SliceRef), op.tag
    # the C block round-trips through the same typed slice
    d2h = [o for o in sched.ops if o.kind == OpKind.D2H]
    assert all(o.payload.operand == "C" for o in d2h)


@pytest.mark.parametrize("async_wb", [False, True])
def test_executor_async_matches_sync(rng, async_wb):
    """The double-buffered write-back mode is a scheduling property, never a
    numerics property."""
    A, B, C = _problem(rng, 320, 256, 128)
    budget = (A.nbytes + B.nbytes + C.nbytes) // 4
    part = plan_gemm_partition(320, 256, 128, budget, 4)
    rt = HostOocRuntime(executor=ScheduleExecutor(async_writeback=async_wb))
    out = rt.gemm(A, B, C, 1.25, -0.5, part)
    expect = 1.25 * (A.astype(np.float64) @ B) - 0.5 * C
    np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-4)


def test_direct_host_impl_matches_oracle(rng):
    """The hand-rolled benchmark baseline dispatches through the shared
    executor and still equals the oracle."""
    from benchmarks.direct_impls import direct_host_ooc_gemm
    A, B, C = _problem(rng, 384, 256, 192)
    budget = (A.nbytes + B.nbytes + C.nbytes) // 5
    out = direct_host_ooc_gemm(A, B, C, 1.5, 0.5, budget)
    expect = 1.5 * (A.astype(np.float64) @ B) + 0.5 * C
    np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-4)


def test_unknown_kernel_raises(rng):
    import dataclasses
    part = plan_gemm_partition(128, 128, 64, 200_000, 4)
    sched = build_gemm_schedule(part)
    i = next(i for i, o in enumerate(sched.ops) if o.kind == OpKind.COMPUTE)
    sched.ops[i] = dataclasses.replace(
        sched.ops[i], payload=BlockRef("no_such_kernel", 0))
    A = np.zeros((128, 64), np.float32)
    B = np.zeros((64, 128), np.float32)
    out = np.zeros((128, 128), np.float32)
    with pytest.raises(KeyError, match="no_such_kernel"):
        ScheduleExecutor().run(sched, operands={"A": A, "B": B},
                               outputs={"C": out},
                               ctx={"alpha": 1.0, "beta": 0.0})


def test_new_kernel_via_spec(rng):
    """Reuse claim, falsifiable: a scaled block-copy kernel expressed as a
    PipelineSpec + one registered handler, with no interpreter loop."""
    from repro.core.runtime import register_op_handler

    M, N = 256, 192
    X = rng.standard_normal((M, N)).astype(np.float32)
    bm = 64
    h = M // bm

    @register_op_handler("scale_copy")
    def _scale_copy(st, op, ref):
        key = op.buffers_written[0]
        st.bufs[key] = st.bufs[op.buffers_read[0]] * st.ctx["gamma"]

    x = StreamedOperand(
        name="X", nblocks=h, block_of=lambda s: s,
        slice_of=lambda b: SliceRef("X", b, rows=(b * bm, bm)),
        bytes_of=lambda b: bm * N * 4,
    )
    y = StreamedOperand(
        name="Y", nblocks=h, block_of=lambda s: s,
        slice_of=lambda b: SliceRef("Y", b, rows=(b * bm, bm)),
        bytes_of=lambda b: bm * N * 4,
        inout=True,
    )
    spec = PipelineSpec(
        name="scale_copy", nsteps=h, operands=(x, y),
        compute=ComputeStage(kernel="scale_copy", reads=("X",),
                             flops_of=lambda s: bm * N),
        writeback=WriteBack(mode="each", operand="Y"),
        budget=1 << 20,
    )
    sched = compile_pipeline(spec, nstreams=2, nbuf=2)
    validate_schedule(sched)
    out = np.zeros((M, N), np.float32)
    ScheduleExecutor().run(sched, operands={"X": X}, outputs={"Y": out},
                           ctx={"gamma": 3.0})
    np.testing.assert_allclose(out, 3.0 * X, rtol=0, atol=0)


def test_put_block_without_a_device_is_jnp_asarray_landed(monkeypatch):
    # the host tier's H2D rule, byte for byte: jnp.asarray on the default
    # device, waited for before the next block is issued
    seen = []
    asarray = runtime.jnp.asarray
    monkeypatch.setattr(runtime.jnp, "asarray",
                        lambda x: seen.append(x) or asarray(x))
    host = np.arange(48, dtype=np.float32).reshape(6, 8)[:, 2:5]
    out = runtime._put_block(host)
    assert len(seen) == 1 and seen[0] is host
    assert out.is_ready() and not out.committed
    assert out.devices() == {jax.devices()[0]}
    np.testing.assert_array_equal(np.asarray(out), host)


def test_put_block_with_a_device_lands_there():
    device = jax.devices()[-1]
    host = np.arange(48, dtype=np.float32).reshape(6, 8)[:, 2:5]
    out = runtime._put_block(host, device)
    assert out.is_ready() and out.committed
    assert out.devices() == {device}
    np.testing.assert_array_equal(np.asarray(out), host)


# The host-coherence rule: a write-back still in flight must land before an
# H2D that re-reads any element of it.  Slices are half-open (start, size);
# None is the operand's full extent.
OVERLAP_CASES = {
    "disjoint_rows": (SliceRef("C", 0, rows=(0, 8)),
                      SliceRef("C", 1, rows=(32, 8)), (64, 64), False),
    "disjoint_cols": (SliceRef("C", 0, cols=(0, 16)),
                      SliceRef("C", 1, cols=(32, 16)), (64, 64), False),
    "corner": (SliceRef("C", 0, rows=(0, 16), cols=(0, 16)),
               SliceRef("C", 1, rows=(8, 16), cols=(8, 16)), (64, 64), True),
    "containment": (SliceRef("C", 0, rows=(0, 32), cols=(0, 32)),
                    SliceRef("C", 1, rows=(8, 4), cols=(8, 4)), (64, 64),
                    True),
    "identical": (SliceRef("C", 0, rows=(16, 16), cols=(16, 16)),
                  SliceRef("C", 0, rows=(16, 16), cols=(16, 16)), (64, 64),
                  True),
    "touching_is_disjoint": (SliceRef("C", 0, rows=(0, 16)),
                             SliceRef("C", 1, rows=(16, 16)), (64, 64),
                             False),
    "none_is_full_extent": (SliceRef("C", 0),
                            SliceRef("C", 1, rows=(60, 4), cols=(60, 4)),
                            (64, 64), True),
    "other_operand": (SliceRef("C", 0, rows=(0, 16), cols=(0, 16)),
                      SliceRef("A", 0, rows=(0, 16), cols=(0, 16)), (64, 64),
                      False),
    "one_dimensional": (SliceRef("out", 0, rows=(0, 8)),
                        SliceRef("out", 1, rows=(4, 8)), (64,), True),
}


@pytest.mark.parametrize("case", OVERLAP_CASES)
def test_spans_overlap_truth_table(case):
    a, b, shape, want = OVERLAP_CASES[case]
    assert runtime._spans_overlap(a, b, shape) is want
    assert runtime._spans_overlap(b, a, shape) is want
