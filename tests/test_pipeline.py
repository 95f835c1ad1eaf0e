"""Schedule correctness (the paper's event program) + simulator properties."""

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    OpKind,
    build_attention_schedule,
    build_gemm_schedule,
    build_vendor_schedule,
    gpu_like,
    phi_like,
    plan_attention_partition,
    plan_gemm_partition,
    schedule_stats,
    simulate,
    tpu_v5e_vmem,
    validate_schedule,
)
from repro.core.streams import Op, Event, Schedule, ScheduleError, Device, StreamFactory

dims = st.sampled_from([128, 256, 384, 512, 1024])


@given(M=dims, N=dims, K=dims,
       nstreams=st.sampled_from([1, 2]),
       nbuf=st.sampled_from([1, 2, 3]),
       frac=st.sampled_from([3, 4, 8]))
@settings(max_examples=60, deadline=None)
def test_gemm_schedule_event_correct(M, N, K, nstreams, nbuf, frac):
    """For any partition and any stream/buffer count, the generated event
    program is deadlock-free and never overwrites live buffers — under ANY
    legal interleaving (the validator checks the full happens-before
    relation, not one execution)."""
    full = (M * K + K * N + M * N) * 4
    # floor keeps the minimal aligned working set feasible for any K<=1024
    part = plan_gemm_partition(M, N, K, max(full // frac, 700_000), 4)
    sched = build_gemm_schedule(part, nstreams=nstreams, nbuf=nbuf)
    validate_schedule(sched)
    st_ = schedule_stats(sched)
    assert st_["flops"] >= 2 * M * N * K
    # every block of C travels H2D once and D2H once
    assert st_["d2h_bytes"] == M * N * 4


def test_gemm_schedule_transfers_B_once_per_column():
    part = plan_gemm_partition(1024, 1024, 512, 2_000_000, 4)
    sched = build_gemm_schedule(part)
    b_ops = [o for o in sched.ops if o.tag.startswith("S(b")]
    assert len(b_ops) == part.w  # column reuse (vendor baseline re-sends)
    vend = build_vendor_schedule(part, tile=512)
    vb_ops = [o for o in vend.ops if o.tag.startswith("S(b")]
    assert len(vb_ops) == 4  # one B panel per 512-tile of C: no reuse


def test_vendor_B_retransfer_bytes_exceed_lib():
    """Claim C3's mechanism: the vendor schedule re-sends B panels per C
    tile, so its B traffic strictly exceeds the libhclooc schedule's
    once-per-column reuse (and total H2D follows)."""
    part = plan_gemm_partition(2048, 2048, 1024, 8_000_000, 4)
    lib = build_gemm_schedule(part)
    vend = build_vendor_schedule(part, tile=512)

    def b_bytes(sched):
        return sum(o.bytes for o in sched.ops
                   if o.kind == OpKind.H2D and o.tag.startswith("S(b"))

    assert b_bytes(vend) > b_bytes(lib)
    # lib moves each B column exactly once: K*N elements total
    assert b_bytes(lib) == 1024 * 2048 * 4
    # vendor re-sends the panel for every tile row of C
    n_tile_rows = (2048 + 511) // 512
    assert b_bytes(vend) == n_tile_rows * 1024 * 2048 * 4
    st_l = schedule_stats(lib)
    st_v = schedule_stats(vend)
    assert st_v["h2d_bytes"] > st_l["h2d_bytes"]


def test_syrk_schedule_event_correct():
    """Third DSL kernel: the SYRK spec compiles to a valid event program
    with the panel's transposed slices transferred once per column."""
    from repro.core import build_syrk_schedule
    part = plan_gemm_partition(1024, 1024, 256, 3_000_000, 4)
    for ns, nb in ((1, 1), (2, 2), (2, 3)):
        sched = build_syrk_schedule(part, nstreams=ns, nbuf=nb)
        validate_schedule(sched)
    sched = build_syrk_schedule(part)
    pt_ops = [o for o in sched.ops if o.tag.startswith("S(pt")]
    assert len(pt_ops) == part.w  # column reuse, like GEMM's B


def test_attention_schedule_valid():
    part = plan_attention_partition(8192, 8, 128, 4 * 2**20, 2)
    sched = build_attention_schedule(part, 8, 128, 32)
    validate_schedule(sched)


def test_validator_catches_missing_wait():
    dev = Device("HBM", 0, 1 << 20)
    sched = Schedule(dev, StreamFactory.create(dev, 2))
    ev = Event("r0")
    sched.issue(Op(kind=OpKind.H2D, tag="S(a0)", stream=0, records=ev,
                   buffers_written=(("A", 0),), bytes=64))
    # compute on the OTHER stream without waiting for the transfer
    sched.issue(Op(kind=OpKind.COMPUTE, tag="GEMM", stream=1,
                   buffers_read=(("A", 0),), flops=10))
    with pytest.raises(ScheduleError):
        validate_schedule(sched)


def test_validator_catches_deadlock():
    dev = Device("HBM", 0, 1 << 20)
    sched = Schedule(dev, StreamFactory.create(dev, 2))
    e1, e2 = Event("e1"), Event("e2")
    sched.issue(Op(kind=OpKind.COMPUTE, tag="a", stream=0,
                   waits=(e2,), records=e1))
    sched.issue(Op(kind=OpKind.COMPUTE, tag="b", stream=1,
                   waits=(e1,), records=e2))
    with pytest.raises(ScheduleError):
        validate_schedule(sched)


# ---------------------------------------------------------------- simulator
def _mk(M=2048, N=2048, K=1024, frac=4):
    full = (M * K + K * N + M * N) * 8
    return plan_gemm_partition(M, N, K, full // frac, 8)


def test_overlap_beats_serial():
    """Claim C3 mechanics: the 2-stream overlapped pipeline beats the
    non-overlapping vendor-style schedule on GPU-like hardware."""
    part = _mk()
    hw = gpu_like()
    t_lib = simulate(build_gemm_schedule(part, 2, 2), hw).makespan
    t_vendor = simulate(build_vendor_schedule(part), hw).makespan
    assert t_vendor > 1.5 * t_lib


def test_phi_prefers_one_stream():
    """Claim C5: on Phi-like hardware (shared transfer engine, threads split
    across streams — measured 0.76x aggregate) a single stream wins in the
    compute-dominated regime the paper measured (large N=K)."""
    part = _mk(8192, 8192, 8192, 6)
    t1 = simulate(build_gemm_schedule(part, 1, 2), phi_like(nstreams=1)).makespan
    t2 = simulate(build_gemm_schedule(part, 2, 2), phi_like(nstreams=2)).makespan
    assert t1 < t2


def test_gpu_prefers_two_streams():
    part = _mk()
    hw = gpu_like()
    t1 = simulate(build_gemm_schedule(part, 1, 1), hw).makespan
    t2 = simulate(build_gemm_schedule(part, 2, 2), hw).makespan
    assert t2 < t1


def test_simulator_conserves_work():
    part = _mk()
    hw = tpu_v5e_vmem()
    res = simulate(build_gemm_schedule(part, 2, 2), hw)
    sched = build_gemm_schedule(part, 2, 2)
    assert res.flops == sched.total_flops()
    # makespan >= each engine's busy time (no engine overcommitted)
    for pool, busy in res.busy.items():
        cap = hw.pools[pool]
        assert busy <= res.makespan * cap + 1e-9


def test_simulator_respects_events():
    """Every op starts after its waited events record."""
    part = _mk(1024, 1024, 512)
    sched = build_gemm_schedule(part, 2, 2)
    res = simulate(sched, gpu_like())
    end = {}
    start = {}
    for tag, stream, s, e in res.op_spans:
        start[tag] = s
        end[tag] = e
    rec = {o.records.name: o.tag for o in sched.ops if o.records}
    for o in sched.ops:
        for ev in o.waits:
            assert start[o.tag] >= end[rec[ev.name]] - 1e-12
