"""Fault injection + recovery: differential oracles (DESIGN.md §12).

Every recovery mechanism is pinned against an oracle that does not share
its code path:

  * transfer retries — the backoff sequence against a fake clock, and the
    byte-accounting invariant (nominal counters unchanged, failed-attempt
    traffic in ``replayed_h2d_bytes``);
  * compute replay — bitwise equality with the fault-free run, and the
    executor's dynamic chain length against the static
    :func:`repro.fault.replay.redo_set` derivation;
  * device_lost — the hybrid rebalance result against BOTH the fault-free
    hybrid run (bitwise) and the dense reference oracle (allclose);
  * oom — the degrade ladder's landing plan against what the planner /
    tuner produces outright at the reduced knobs;
  * the simulator's faulted-makespan mode — closed-form expectations.

Also the regression test for the executor's flush-exception bug: a
write-back materialization that raises used to drop the in-flight block
(pop-then-write), silently leaving stale host state.
"""

import numpy as np
import pytest

from repro.core.api import hclFaultPolicy
from repro.core.oocgemm import ooc_gemm, ooc_syrk
from repro.core.ooc_factor import ooc_cholesky, ooc_lu
from repro.core.partitioner import plan_gemm_partition
from repro.core.pipeline import build_gemm_schedule, schedule_stats
from repro.core.runtime import HostOocRuntime, ScheduleExecutor
from repro.core.simulator import FaultModel, gpu_like, simulate
from repro.core.streams import OpKind
from repro.fault import (ComputeFault, DeviceLostError, FaultInjector,
                         FaultPlan, FaultPolicy, FaultSpec, OomError,
                         TransferError, mean_redo_len, redo_cost, redo_set)
from repro.hybrid import (DeviceSpec, plan_hybrid_gemm, plan_hybrid_syrk,
                          run_hybrid_gemm, run_hybrid_syrk,
                          surviving_devices)
from repro.kernels import ref
from repro.obs import get_observability
from repro.tune import gpu_profile, phi_profile
from repro.tune.search import search_gemm


@pytest.fixture(autouse=True)
def _clean_obs():
    obs = get_observability()
    obs.reset()
    yield
    obs.reset()


def _gemm_case(m=128, n=48, k=32, budget=60_000, seed=0, nstreams=2, nbuf=2):
    rng = np.random.default_rng(seed)
    A = rng.standard_normal((m, k))
    B = rng.standard_normal((k, n))
    C = rng.standard_normal((m, n))
    part = plan_gemm_partition(m, n, k, budget)
    sched = build_gemm_schedule(part, nstreams=nstreams, nbuf=nbuf)
    return A, B, C, part, sched


def _fake_clock():
    slept = []
    return slept, lambda s: slept.append(s)


# ------------------------------------------------------------- plan basics
def test_fault_spec_validation():
    with pytest.raises(ValueError, match="unknown fault class"):
        FaultSpec(op=0, cls="cosmic_ray")
    with pytest.raises(ValueError, match="op index"):
        FaultSpec(op=-1, cls="h2d_error")
    with pytest.raises(ValueError, match="times"):
        FaultSpec(op=0, cls="h2d_error", times=0)
    with pytest.raises(ValueError, match="rate"):
        FaultPlan.random(0, None, 1.5)


def test_random_plan_is_deterministic_and_class_stable():
    *_, sched = _gemm_case()
    p1 = FaultPlan.random(7, sched, 0.5)
    p2 = FaultPlan.random(7, sched, 0.5)
    assert p1.specs == p2.specs and len(p1) > 0
    # restricting the class set removes specs without shifting the rest:
    # one rng draw per op regardless of eligibility
    h2d_only = FaultPlan.random(7, sched, 0.5, classes=("h2d_error",))
    assert set(h2d_only.specs) == {
        s for s in p1.specs if s.cls == "h2d_error"}
    # specs address eligible ops of the right kind, pinned to their stream
    for s in p1.specs:
        op = sched.ops[s.op]
        assert s.stream == op.stream
        assert (op.kind == OpKind.H2D) == (s.cls == "h2d_error")


def test_injector_consumes_per_attempt_and_checks_stream_pin():
    *_, sched = _gemm_case()
    h2d = next(i for i, op in enumerate(sched.ops) if op.kind == OpKind.H2D)
    plan = FaultPlan(specs=(FaultSpec(op=h2d, cls="h2d_error", times=2),))
    inj = plan.injector()
    op = sched.ops[h2d]
    assert inj.check(h2d, op) == "h2d_error"
    assert not inj.exhausted()
    assert inj.check(h2d, op) == "h2d_error"
    assert inj.check(h2d, op) is None          # times=2: third attempt clean
    assert inj.exhausted()
    assert inj.injected == [(h2d, "h2d_error"), (h2d, "h2d_error")]

    bad = FaultPlan(specs=(FaultSpec(op=h2d, cls="h2d_error",
                                     stream=op.stream + 1),)).injector()
    with pytest.raises(ValueError, match="pins op"):
        bad.check(h2d, op)


def test_for_device_shards_pinned_specs():
    plan = FaultPlan(specs=(FaultSpec(op=0, cls="h2d_error", device="gpu0"),
                            FaultSpec(op=1, cls="h2d_error", device="phi0"),
                            FaultSpec(op=2, cls="h2d_error")))
    gpu = plan.for_device("gpu0")
    assert [s.op for s in gpu.specs] == [0, 2]


# --------------------------------------------------- retry / backoff oracle
def test_backoff_schedule_pinned_against_fake_clock():
    slept, sleep = _fake_clock()
    pol = FaultPolicy(backoff_base=0.5, backoff_factor=2.0, max_retries=3,
                      sleep=sleep)
    assert pol.backoff_schedule() == [0.5, 1.0, 2.0]

    A, B, C, part, sched = _gemm_case()
    h2d = next(i for i, op in enumerate(sched.ops) if op.kind == OpKind.H2D)
    rt = HostOocRuntime()
    clean = rt.gemm(A, B, C, 1.0, 0.5, part, schedule=sched)
    nominal_h2d = rt.executor.last_h2d_bytes

    plan = FaultPlan(specs=(FaultSpec(op=h2d, cls="h2d_error", times=2),))
    out = rt.gemm(A, B, C, 1.0, 0.5, part, schedule=sched,
                  faults=plan, policy=pol)
    assert np.array_equal(out, clean)
    # exactly the policy's first two backoff delays, in order
    assert slept == [0.5, 1.0]
    st = rt.executor.last_fault_stats
    assert st["injected"] == 2 and st["retries"] == 2
    assert st["recovered_retry"] == 1
    assert st["backoff_seconds"] == pytest.approx(1.5)
    # nominal counters unchanged; the two failed attempts' traffic is
    # accounted as recovery's
    assert rt.executor.last_h2d_bytes == nominal_h2d
    assert st["replayed_h2d_bytes"] == 2 * sched.ops[h2d].bytes


def test_transfer_retries_exhaust_and_raise():
    A, B, C, part, sched = _gemm_case()
    h2d = next(i for i, op in enumerate(sched.ops) if op.kind == OpKind.H2D)
    pol = FaultPolicy(max_retries=2, sleep=lambda s: None)
    plan = FaultPlan(specs=(FaultSpec(op=h2d, cls="h2d_error", times=3),))
    rt = HostOocRuntime()
    with pytest.raises(TransferError, match="after 2 retries"):
        rt.gemm(A, B, C, 1.0, 0.5, part, schedule=sched,
                faults=plan, policy=pol)
    # terminal raise still publishes the injection record
    assert rt.executor.last_fault_stats["injected"] == 3


def test_h2d_fault_on_compute_op_is_authoring_error():
    A, B, C, part, sched = _gemm_case()
    ci = next(i for i, op in enumerate(sched.ops)
              if op.kind == OpKind.COMPUTE)
    rt = HostOocRuntime()
    with pytest.raises(ValueError, match="h2d_error into compute"):
        rt.gemm(A, B, C, 1.0, 0.5, part, schedule=sched,
                faults=FaultPlan(specs=(FaultSpec(op=ci, cls="h2d_error"),)),
                policy=FaultPolicy(sleep=lambda s: None))


# ------------------------------------------------------ compute replay oracle
def test_compute_replay_every_op_bitwise_and_matches_static_redo_set():
    A, B, C, part, sched = _gemm_case()
    rt = HostOocRuntime()
    clean = rt.gemm(A, B, C, 1.0, 0.5, part, schedule=sched)
    pol = FaultPolicy(sleep=lambda s: None)
    for ci, op in enumerate(sched.ops):
        if op.kind != OpKind.COMPUTE:
            continue
        plan = FaultPlan(specs=(FaultSpec(op=ci, cls="compute_nan"),))
        out = rt.gemm(A, B, C, 1.0, 0.5, part, schedule=sched,
                      faults=plan, policy=pol)
        assert np.array_equal(out, clean), f"replay at op {ci} diverged"
        st = rt.executor.last_fault_stats
        assert st["recovered_replay"] == 1
        # the dynamic chain the executor replayed == the static derivation
        assert st["replayed_ops"] == len(redo_set(sched, ci))


def test_unrecoverable_compute_fault_raises_compute_fault():
    A, B, C, part, sched = _gemm_case()
    ci = next(i for i, op in enumerate(sched.ops)
              if op.kind == OpKind.COMPUTE)
    pol = FaultPolicy(max_retries=2, sleep=lambda s: None)
    plan = FaultPlan(specs=(FaultSpec(op=ci, cls="compute_nan", times=4),))
    rt = HostOocRuntime()
    with pytest.raises(ComputeFault, match="retries exhausted"):
        rt.gemm(A, B, C, 1.0, 0.5, part, schedule=sched,
                faults=plan, policy=pol)


def test_redo_set_properties():
    *_, sched = _gemm_case()
    computes = [i for i, op in enumerate(sched.ops)
                if op.kind == OpKind.COMPUTE]
    for ci in computes:
        rs = redo_set(sched, ci)
        assert rs[-1] == ci and rs == sorted(rs)
        key = sched.ops[ci].buffers_written[0]
        for j in rs[:-1]:
            assert key in sched.ops[j].buffers_written
    h2d = next(i for i, op in enumerate(sched.ops) if op.kind == OpKind.H2D)
    with pytest.raises(ValueError, match="not a single-writer compute"):
        redo_set(sched, h2d)
    assert mean_redo_len(sched) >= 1.0
    hw = gpu_like()
    assert redo_cost(sched, hw, computes[0]) > 0.0


# ------------------------------------------------------- flush regression
class _FlakyBlock:
    """A device block whose host materialization fails transiently —
    the shape of bug the flush fix guards: the in-flight entry must
    survive a failed write-back attempt."""

    def __init__(self, arr, fails):
        self._arr = np.asarray(arr)
        self.fails = fails

    def __array__(self, dtype=None, copy=None):
        if self.fails > 0:
            self.fails -= 1
            raise TransferError("transient write-back failure")
        return self._arr if dtype is None else self._arr.astype(dtype)


def _flaky_executor(blocks, fails_each=1):
    """An executor whose first ``blocks`` dgemm output blocks each fail
    ``fails_each`` materialization attempts before landing."""
    from repro.core.runtime import _OP_HANDLERS

    real = _OP_HANDLERS["dgemm"]
    left = {"n": blocks}

    def flaky_dgemm(st, op, fref):
        real(st, op, fref)
        if left["n"] > 0:
            left["n"] -= 1
            key = op.buffers_written[0]
            st.bufs[key] = _FlakyBlock(st.bufs[key], fails_each)

    return ScheduleExecutor(handlers={"dgemm": flaky_dgemm})


def test_flush_exception_keeps_block_in_flight_and_retries():
    A, B, C, part, sched = _gemm_case()
    rt = HostOocRuntime()
    clean = rt.gemm(A, B, C, 1.0, 0.5, part, schedule=sched)

    slept, sleep = _fake_clock()
    rt2 = HostOocRuntime(executor=_flaky_executor(blocks=1))
    # an empty plan arms fault mode (retrying flushes) with zero injections
    out = rt2.gemm(A, B, C, 1.0, 0.5, part, schedule=sched,
                   faults=FaultPlan(),
                   policy=FaultPolicy(sleep=sleep))
    # the failed first materialization did NOT drop the block: the retry
    # re-landed it and the output is exact
    assert np.array_equal(out, clean)
    st = rt2.executor.last_fault_stats
    assert st["injected"] == 0 and st["retries"] == 1
    assert st["recovered_retry"] == 1 and len(slept) == 1


def test_flush_exception_without_policy_propagates():
    A, B, C, part, sched = _gemm_case()
    rt = HostOocRuntime(executor=_flaky_executor(blocks=1))
    with pytest.raises(TransferError):
        rt.gemm(A, B, C, 1.0, 0.5, part, schedule=sched)


# --------------------------------------------------------- device_lost oracle
FAST = dict(nbuf_options=(1, 2), max_steps=256)


def _hybrid_devices(budget):
    return [DeviceSpec("gpu0", gpu_profile(), budget),
            DeviceSpec("phi0", phi_profile(), budget)]


def _first_compute_lost(sched):
    for i, op in enumerate(sched.ops):
        if op.kind == OpKind.COMPUTE:
            return FaultPlan(specs=(FaultSpec(op=i, cls="device_lost"),))
    raise AssertionError("schedule has no compute op")


def test_device_lost_gemm_rebalances_bitwise():
    rng = np.random.default_rng(3)
    m, n, k = 512, 256, 128
    budget = (m * k + k * n + m * n) * 4 // 3
    devs = _hybrid_devices(budget)
    A = rng.standard_normal((m, k)).astype(np.float32)
    B = rng.standard_normal((k, n)).astype(np.float32)
    C = rng.standard_normal((m, n)).astype(np.float32)
    hp = plan_hybrid_gemm(m, n, k, devs, **FAST)
    clean, _ = run_hybrid_gemm(A, B, C, 1.2, 0.5, hp)
    pol = FaultPolicy(sleep=lambda s: None)
    for dead in ("gpu0", "phi0"):
        out, groups = run_hybrid_gemm(
            A, B, C, 1.2, 0.5, hp,
            fault_plans={dead: _first_compute_lost},
            fault_policy=pol)
        # bitwise vs the fault-free hybrid run (K is never split, so the
        # rebalanced band's blocks are the same full-depth dots)...
        assert np.array_equal(out, clean)
        # ...and correct vs the dense oracle
        np.testing.assert_allclose(out, ref.gemm_ref(A, B, C, 1.2, 0.5),
                                   rtol=1e-5, atol=1e-5)
        names = [g[0] for g in groups]
        survivor = "phi0" if dead == "gpu0" else "gpu0"
        assert any(f"rebalance {dead}" in nm for nm in names)
        assert dead not in names and survivor in names


def test_device_lost_syrk_recovers_via_gemm_band():
    rng = np.random.default_rng(4)
    m, k = 512, 128
    budget = (m * k + k * m + m * m) * 4 // 3
    devs = _hybrid_devices(budget)
    P = rng.standard_normal((m, k)).astype(np.float32)
    C = rng.standard_normal((m, m)).astype(np.float32)
    C = C + C.T
    hp = plan_hybrid_syrk(m, k, devs, **FAST)
    clean, _ = run_hybrid_syrk(P, C, 1.2, 0.5, hp)
    out, _ = run_hybrid_syrk(P, C, 1.2, 0.5, hp,
                             fault_plans={"gpu0": _first_compute_lost},
                             fault_policy=FaultPolicy(sleep=lambda s: None))
    # the survivor recomputes gpu0's band with its own block geometry, so
    # the result matches up to fp32 summation order, not bitwise
    bound = ref.gemm_error_bound(P, P.T, C, 1.2, 0.5)
    assert np.all(np.abs(out - clean) <= 2 * bound)


def test_surviving_devices_validation():
    devs = _hybrid_devices(1 << 20)
    assert [d.name for d in surviving_devices(devs, ["gpu0"])] == ["phi0"]
    with pytest.raises(ValueError, match="not in device set"):
        surviving_devices(devs, ["nope"])
    with pytest.raises(ValueError, match="no survivors"):
        surviving_devices(devs, ["gpu0", "phi0"])


def test_device_lost_outside_hybrid_propagates():
    A, B, C, part, sched = _gemm_case()
    ci = next(i for i, op in enumerate(sched.ops)
              if op.kind == OpKind.COMPUTE)
    rt = HostOocRuntime()
    with pytest.raises(DeviceLostError):
        rt.gemm(A, B, C, 1.0, 0.5, part, schedule=sched,
                faults=FaultPlan(specs=(
                    FaultSpec(op=ci, cls="device_lost"),)))


# -------------------------------------------------------------- oom ladders
def _oom_at_first_compute(sched):
    for i, op in enumerate(sched.ops):
        if op.kind == OpKind.COMPUTE:
            return FaultPlan(specs=(FaultSpec(op=i, cls="oom"),))
    raise AssertionError


def test_oom_untuned_gemm_halves_nbuf_first_and_stays_bitwise():
    rng = np.random.default_rng(5)
    m, n, k = 128, 48, 32
    A = rng.standard_normal((m, k))
    B = rng.standard_normal((k, n))
    C = rng.standard_normal((m, n))
    clean = ooc_gemm(A, B, C, 1.0, 0.5, budget_bytes=60_000)
    pol = FaultPolicy(sleep=lambda s: None)
    out = ooc_gemm(A, B, C, 1.0, 0.5, budget_bytes=60_000,
                   faults=_oom_at_first_compute, fault_policy=pol)
    # first rung: halve nbuf — same partition, so bitwise (K never split)
    assert [d.action for d in pol.degrades] == ["halve_nbuf"]
    assert np.array_equal(out, clean)


def test_oom_tuned_gemm_lands_on_reduced_budget_plan():
    rng = np.random.default_rng(6)
    m, n, k = 256, 64, 32
    A = rng.standard_normal((m, k))
    B = rng.standard_normal((k, n))
    C = rng.standard_normal((m, n))
    budget = 120_000
    clean = ooc_gemm(A, B, C, 1.0, 0.5, budget_bytes=budget, tune="auto")
    pol = FaultPolicy(sleep=lambda s: None)
    out = ooc_gemm(A, B, C, 1.0, 0.5, budget_bytes=budget, tune="auto",
                   faults=_oom_at_first_compute, fault_policy=pol)
    # tuned runs: the tuner owns nbuf/lookahead, so the ladder is budget
    # halvings only, re-searched — the degraded run IS the tuner's plan at
    # the reduced budget
    assert [d.action for d in pol.degrades] == ["halve_budget"]
    assert pol.degrades[0].budget_bytes == budget // 2
    assert np.array_equal(out, clean)
    # the differential: running outright at the reduced budget matches
    direct = ooc_gemm(A, B, C, 1.0, 0.5, budget_bytes=budget // 2,
                      tune="auto")
    assert np.array_equal(out, direct)


def test_oom_degraded_rerun_is_fault_free_and_ladder_exhaustion_raises():
    rng = np.random.default_rng(7)
    m, n, k = 128, 48, 32
    A = rng.standard_normal((m, k))
    B = rng.standard_normal((k, n))
    C = rng.standard_normal((m, n))
    clean = ooc_gemm(A, B, C, 1.0, 0.5, budget_bytes=60_000)

    def oom_many(sched):
        for i, op in enumerate(sched.ops):
            if op.kind == OpKind.COMPUTE:
                return FaultPlan(specs=(
                    FaultSpec(op=i, cls="oom", times=10),))
        raise AssertionError

    # the degraded re-run executes fault-free by design, so even an oom
    # with 9 occurrences left recovers on the first rung
    pol = FaultPolicy(sleep=lambda s: None)
    out = ooc_gemm(A, B, C, 1.0, 0.5, budget_bytes=60_000,
                   faults=oom_many, fault_policy=pol)
    assert [d.action for d in pol.degrades] == ["halve_nbuf"]
    assert np.array_equal(out, clean)

    # tuned ladder at this budget: both halvings (30k, 15k) are below the
    # 53248B aligned working-set floor, so every rung fails to replan and
    # the oom propagates to the caller
    pol2 = FaultPolicy(sleep=lambda s: None, max_budget_halvings=2)
    with pytest.raises(OomError):
        ooc_gemm(A, B, C, 1.0, 0.5, budget_bytes=60_000, tune="auto",
                 faults=oom_many, fault_policy=pol2)
    assert [d.action for d in pol2.degrades] == ["halve_budget",
                                                 "halve_budget"]


def test_oom_cholesky_and_lu_degrade_and_stay_correct():
    rng = np.random.default_rng(8)
    n = 192
    A = rng.standard_normal((n, n))
    spd = A @ A.T + n * np.eye(n)
    budget = 4 * spd.nbytes
    pol = FaultPolicy(sleep=lambda s: None)
    clean_l = ooc_cholesky(spd, panel=64, budget_bytes=budget)
    L = ooc_cholesky(spd, panel=64, budget_bytes=budget,
                     faults=_oom_at_first_compute, fault_policy=pol)
    assert [d.action for d in pol.degrades] == ["halve_nbuf"]
    assert np.array_equal(L, clean_l)

    pol2 = FaultPolicy(sleep=lambda s: None)
    B = rng.standard_normal((n, n)) + n * np.eye(n)
    clean_lu, clean_p = ooc_lu(B, panel=64, budget_bytes=budget)
    LU, perm = ooc_lu(B, panel=64, budget_bytes=budget,
                      faults=_oom_at_first_compute, fault_policy=pol2)
    assert [d.action for d in pol2.degrades] == ["halve_nbuf"]
    assert np.array_equal(LU, clean_lu)
    assert np.array_equal(perm, clean_p)


def test_factor_compute_and_transfer_faults_recover_bitwise():
    rng = np.random.default_rng(9)
    n = 192
    A = rng.standard_normal((n, n))
    spd = A @ A.T + n * np.eye(n)
    budget = 4 * spd.nbytes
    pol = FaultPolicy(sleep=lambda s: None)
    clean = ooc_cholesky(spd, panel=64, budget_bytes=budget)
    got = ooc_cholesky(spd, panel=64, budget_bytes=budget,
                       faults=lambda s: FaultPlan.random(21, s, 0.3),
                       fault_policy=pol)
    assert np.array_equal(got, clean)

    B = rng.standard_normal((n, n)) + n * np.eye(n)
    clean_lu, clean_p = ooc_lu(B, panel=64, budget_bytes=budget)
    LU, perm = ooc_lu(B, panel=64, budget_bytes=budget,
                      faults=lambda s: FaultPlan.random(22, s, 0.3),
                      fault_policy=pol)
    assert np.array_equal(LU, clean_lu)
    assert np.array_equal(perm, clean_p)


def test_faults_rejected_on_non_host_backends():
    rng = np.random.default_rng(10)
    A = rng.standard_normal((64, 32))
    B = rng.standard_normal((32, 48))
    with pytest.raises(ValueError, match="host pipeline backend only"):
        ooc_gemm(A, B, None, 1.0, 0.0, budget_bytes=1 << 20,
                 backend="vmem", faults=FaultPlan())
    spd = A @ A.T + 64 * np.eye(64)
    with pytest.raises(ValueError, match="host pipeline backend only"):
        ooc_cholesky(spd, panel=32, budget_bytes=1 << 20,
                     devices=_hybrid_devices(1 << 20), faults=FaultPlan())


# --------------------------------------------- simulator + tuner fault mode
def test_fault_model_expected_durations_closed_form():
    *_, sched = _gemm_case()
    hw = gpu_like()
    fm = FaultModel(rate=0.1, mean_backoff=0.01, redo_factor=2.0)
    for op in sched.ops:
        dur = hw.duration(op)
        exp = fm.expected_duration(op, dur)
        if op.kind == OpKind.COMPUTE:
            assert exp == pytest.approx(dur * (1 + 0.1 * 2.0))
        else:
            assert exp == pytest.approx(
                dur + (0.1 / 0.9) * (dur + 0.01))
        # rate 0 is the identity
        assert FaultModel(rate=0.0).expected_duration(op, dur) == dur


def test_simulate_faulted_makespan_monotone_in_rate():
    *_, sched = _gemm_case()
    hw = gpu_like()
    base = simulate(sched, hw).makespan
    prev = base
    for rate in (0.01, 0.05, 0.2):
        span = simulate(sched, hw, faults=FaultModel(rate=rate)).makespan
        assert span > prev * (1 - 1e-12)
        prev = span
    assert prev > base


def test_search_ranks_under_fault_model():
    prof = gpu_profile()
    best = search_gemm(512, 256, 128, 1 << 22, prof)
    faulted = search_gemm(512, 256, 128, 1 << 22, prof, fault_rate=0.05)
    assert faulted.makespan >= best.makespan
    # the policy bridge produces the same model the tuner consumes
    pol = FaultPolicy(backoff_base=0.02)
    fm = pol.fault_model(0.05)
    assert fm.rate == 0.05 and fm.mean_backoff == 0.02
    via_model = search_gemm(512, 256, 128, 1 << 22, prof, fault_model=fm)
    assert via_model.makespan >= best.makespan


# ----------------------------------------------------------- obs + facade
def test_fault_metrics_published_and_facade():
    obs = get_observability()
    obs.enable(metrics=True)
    A, B, C, part, sched = _gemm_case()
    h2d = next(i for i, op in enumerate(sched.ops) if op.kind == OpKind.H2D)
    ci = next(i for i, op in enumerate(sched.ops)
              if op.kind == OpKind.COMPUTE)
    pol = hclFaultPolicy(sleep=lambda s: None)
    assert isinstance(pol, FaultPolicy)
    rt = HostOocRuntime()
    rt.gemm(A, B, C, 1.0, 0.5, part, schedule=sched,
            faults=FaultPlan(specs=(FaultSpec(op=h2d, cls="h2d_error"),
                                    FaultSpec(op=ci, cls="compute_nan"))),
            policy=pol)
    text = obs.metrics.to_prometheus_text()
    assert "repro_fault_injected_total" in text
    assert "repro_fault_retries_total" in text
    assert "repro_fault_replayed_ops_total" in text
    assert 'action="retry"' in text and 'action="replay"' in text


def test_executor_counters_reconcile_with_schedule_stats_under_faults():
    A, B, C, part, sched = _gemm_case()
    stats = schedule_stats(sched)
    rt = HostOocRuntime()
    plan = FaultPlan.random(33, sched, 0.4)
    rt.gemm(A, B, C, 1.0, 0.5, part, schedule=sched, faults=plan,
            policy=FaultPolicy(sleep=lambda s: None))
    assert rt.executor.last_h2d_bytes == stats["h2d_bytes"]
    assert rt.executor.last_d2h_bytes == stats["d2h_bytes"]
