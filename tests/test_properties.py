"""Property-based conformance suite (ISSUE 4).

Two families, driven by hypothesis:

  * every randomly-drawn partition / pipeline-spec / config combination
    compiles to a schedule that passes ``validate_schedule`` (the event
    program is safe under ANY legal interleaving), and
  * simulate-vs-execute conformance: the ``ScheduleExecutor``'s op
    completion order is a *linear extension* of the dependency partial
    order the simulator honors (stream program order + wait -> record
    edges), and the simulator never starts an op before its dependencies
    finish.  This pins the contract that lets one Schedule object drive
    both engines.
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    OpKind,
    ScheduleExecutor,
    attention_pipeline_spec,
    build_attention_schedule,
    build_gemm_schedule,
    build_syrk_schedule,
    build_vendor_schedule,
    compile_factor_pipeline,
    compile_pipeline,
    factor_pipeline_spec,
    gpu_like,
    plan_attention_partition,
    plan_gemm_partition,
    simulate,
    validate_schedule,
)

dims = st.sampled_from([128, 256, 384, 512])


def _dependency_edges(sched):
    """(pred, succ) pairs of the dependency partial order both engines must
    honor: per-stream program order plus wait -> recorder edges."""
    recorder = {}
    for idx, op in enumerate(sched.ops):
        if op.records is not None:
            recorder[op.records.name] = idx
    edges = []
    last_in_stream = {}
    for idx, op in enumerate(sched.ops):
        if op.stream in last_in_stream:
            edges.append((last_in_stream[op.stream], idx))
        last_in_stream[op.stream] = idx
        for ev in op.waits:
            edges.append((recorder[ev.name], idx))
    return edges


def _assert_simulator_honors_deps(sched, hw):
    res = simulate(sched, hw)
    # spans are appended in placement order; map each op to its span by
    # counting per-stream (a stream's ops keep their program order)
    per_stream = {}
    span_of = {}
    for tag, stream, t0, t1 in res.op_spans:
        pos = per_stream.get(stream, 0)
        per_stream[stream] = pos + 1
        span_of[(stream, pos)] = (t0, t1)
    pos_of = {}
    seen = {}
    for idx, op in enumerate(sched.ops):
        pos_of[idx] = (op.stream, seen.get(op.stream, 0))
        seen[op.stream] = seen.get(op.stream, 0) + 1
    for pred, succ in _dependency_edges(sched):
        t_pred_end = span_of[pos_of[pred]][1]
        t_succ_start = span_of[pos_of[succ]][0]
        assert t_succ_start >= t_pred_end - 1e-12, (
            f"simulator started {sched.ops[succ].tag} at {t_succ_start} "
            f"before its dependency {sched.ops[pred].tag} ended at "
            f"{t_pred_end}")
    return res


def _assert_executor_is_linear_extension(sched):
    """The executor completes ops in issue order; that order must extend
    the dependency partial order, or in-order execution would read data
    that is not ready."""
    for pred, succ in _dependency_edges(sched):
        assert pred < succ, (
            f"issue order is not a linear extension: "
            f"{sched.ops[succ].tag} (issue {succ}) depends on "
            f"{sched.ops[pred].tag} (issue {pred})")


# ------------------------------------------------------------ validate
@given(M=dims, N=dims, K=dims,
       nstreams=st.sampled_from([1, 2, 3]),
       nbuf=st.sampled_from([1, 2, 3]),
       frac=st.sampled_from([2, 4, 8]))
@settings(max_examples=40, deadline=None)
def test_random_gemm_specs_validate(M, N, K, nstreams, nbuf, frac):
    full = (M * K + K * N + M * N) * 4
    part = plan_gemm_partition(M, N, K, max(full // frac, 700_000), 4)
    for build in (build_gemm_schedule, build_syrk_schedule):
        sched = build(part, nstreams=nstreams, nbuf=nbuf)
        validate_schedule(sched)
        _assert_executor_is_linear_extension(sched)
    validate_schedule(build_vendor_schedule(part))


@given(S=st.sampled_from([512, 1024, 2048]),
       nstreams=st.sampled_from([1, 2]),
       nbuf=st.sampled_from([2, 3]),
       frac=st.sampled_from([2, 6]))
@settings(max_examples=20, deadline=None)
def test_random_attention_specs_validate(S, nstreams, nbuf, frac):
    kv_heads, head_dim, q_heads = 4, 64, 16
    budget = max(2 * S * kv_heads * head_dim * 2 // frac, 300_000)
    part = plan_attention_partition(S, kv_heads, head_dim, budget, 2)
    sched = build_attention_schedule(part, kv_heads, head_dim, q_heads,
                                     nstreams=nstreams, nbuf=nbuf)
    validate_schedule(sched)
    _assert_executor_is_linear_extension(sched)


@given(n=st.sampled_from([256, 320, 512, 700]),
       panel=st.sampled_from([64, 96, 128, 512]),
       kind=st.sampled_from(["cholesky", "lu"]),
       lookahead=st.sampled_from([0, 1, 2]),
       nstreams=st.sampled_from([1, 2]),
       nbuf=st.sampled_from([1, 2, 3]))
@settings(max_examples=40, deadline=None)
def test_random_factor_specs_validate(n, panel, kind, lookahead, nstreams,
                                      nbuf):
    spec = factor_pipeline_spec(n, panel, 64 * n * n * 4, 4, kind=kind,
                                lookahead=lookahead, nbuf=nbuf,
                                bm=64, bn=128)
    sched = compile_factor_pipeline(spec, nstreams=nstreams, nbuf=nbuf)
    validate_schedule(sched)
    _assert_executor_is_linear_extension(sched)
    _assert_simulator_honors_deps(sched, gpu_like())


# ------------------------------------- simulate-vs-execute conformance
@given(M=dims, N=dims, K=st.sampled_from([128, 256]),
       nstreams=st.sampled_from([1, 2]),
       nbuf=st.sampled_from([1, 2, 3]))
@settings(max_examples=10, deadline=None)
def test_executor_completion_extends_simulator_order(M, N, K, nstreams,
                                                     nbuf):
    """Execute a GEMM schedule with span recording: ops complete in issue
    order, which must be a linear extension of the dependency order the
    simulator schedules by — and the recorded spans cover every op."""
    rng = np.random.default_rng(M + N + K)
    full = (M * K + K * N + M * N) * 4
    part = plan_gemm_partition(M, N, K, max(full // 4, 700_000), 4)
    sched = build_gemm_schedule(part, nstreams=nstreams, nbuf=nbuf)
    validate_schedule(sched)
    _assert_executor_is_linear_extension(sched)
    _assert_simulator_honors_deps(sched, gpu_like())

    A = rng.standard_normal((M, K)).astype(np.float32)
    B = rng.standard_normal((K, N)).astype(np.float32)
    C = np.zeros((M, N), dtype=np.float32)
    ex = ScheduleExecutor(record_spans=True)
    ex.run(sched, operands={"A": A, "B": B}, outputs={"C": C},
           ctx={"alpha": 1.0, "beta": 0.0})
    assert len(ex.last_spans) == len(sched.ops)
    # completion timestamps are monotone in issue order (in-order engine),
    # so span order IS completion order; it matches issue order op-for-op
    for (tag, stream, t0, t1), op in zip(ex.last_spans, sched.ops):
        assert tag == op.tag and stream == op.stream
    ends = [t1 for _, _, _, t1 in ex.last_spans]
    assert all(b >= a - 1e-12 for a, b in zip(ends, ends[1:]))
    np.testing.assert_allclose(C, A.astype(np.float64) @ B,
                               rtol=1e-4, atol=1e-4)


@given(M=dims, N=dims, K=st.sampled_from([128, 256]),
       nstreams=st.sampled_from([1, 2, 3]),
       nbuf=st.sampled_from([1, 2, 3]))
@settings(max_examples=10, deadline=None)
def test_concurrent_completion_is_linear_extension(M, N, K, nstreams, nbuf):
    """mode="concurrent" may complete ops out of issue order, but the
    observed completion order must still be a linear extension of the
    dependency partial order — and the result stays bitwise equal to the
    serial oracle's."""
    rng = np.random.default_rng(M * 3 + N * 5 + K)
    full = (M * K + K * N + M * N) * 4
    part = plan_gemm_partition(M, N, K, max(full // 4, 700_000), 4)
    sched = build_gemm_schedule(part, nstreams=nstreams, nbuf=nbuf)
    validate_schedule(sched)

    A = rng.standard_normal((M, K)).astype(np.float32)
    B = rng.standard_normal((K, N)).astype(np.float32)
    C_ser = np.zeros((M, N), dtype=np.float32)
    ScheduleExecutor().run(sched, {"A": A, "B": B}, {"C": C_ser},
                           {"alpha": 1.0, "beta": 0.0})
    C_conc = np.zeros((M, N), dtype=np.float32)
    ex = ScheduleExecutor(mode="concurrent")
    ex.run(sched, {"A": A, "B": B}, {"C": C_conc},
           {"alpha": 1.0, "beta": 0.0})
    assert np.array_equal(C_ser, C_conc)
    order = ex.last_completion_order
    assert sorted(order) == list(range(len(sched.ops)))
    pos = {op_idx: k for k, op_idx in enumerate(order)}
    for pred, succ in _dependency_edges(sched):
        assert pos[pred] < pos[succ], (
            f"concurrent completion violated dependency "
            f"{sched.ops[pred].tag} -> {sched.ops[succ].tag}")


def test_factor_executor_conformance():
    """The multi-kernel factor schedule (panel ops + trailing stream +
    lookahead reordering) also completes as a linear extension of its
    dependency order, with spans for every op."""
    rng = np.random.default_rng(9)
    n = 320
    X = rng.standard_normal((n, n)).astype(np.float32)
    A = (X @ X.T + n * np.eye(n)).astype(np.float32)
    spec = factor_pipeline_spec(n, 96, 64 * n * n * 4, 4, kind="cholesky",
                                lookahead=1, bm=64, bn=128)
    sched = compile_factor_pipeline(spec, nstreams=2, nbuf=2)
    validate_schedule(sched)
    _assert_executor_is_linear_extension(sched)
    out = np.array(A)
    ex = ScheduleExecutor(record_spans=True)
    ex.run(sched, operands={}, outputs={"A": out},
           ctx={"alpha": -1.0, "beta": 1.0, "panel": 96, "n": n})
    assert len(ex.last_spans) == len(sched.ops)
    expect = np.linalg.cholesky(A.astype(np.float64))
    np.testing.assert_allclose(np.tril(out), expect, rtol=1e-4, atol=1e-4)


# ------------------------------------------------- lookahead properties
@pytest.mark.parametrize("kind", ["cholesky", "lu"])
def test_lookahead_never_slower_than_sequential(kind):
    """Same block geometry, same transfers: the lookahead event graph is a
    relaxation of the sequential one, so its simulated makespan cannot
    regress (small tolerance for greedy list-scheduling noise)."""
    hw = gpu_like()
    spec0 = factor_pipeline_spec(4096, 512, 512 * 2**20, 8, kind=kind,
                                 lookahead=0, bm=512, bn=1024)
    spec1 = factor_pipeline_spec(4096, 512, 512 * 2**20, 8, kind=kind,
                                 lookahead=1, bm=512, bn=1024)
    seq = simulate(compile_factor_pipeline(spec0), hw).makespan
    la = simulate(compile_factor_pipeline(spec1), hw).makespan
    assert la <= seq * 1.02, (la, seq)
