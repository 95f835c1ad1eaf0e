"""Hybrid co-scheduler: balancer properties, exact execution, registry.

The acceptance bar (ISSUE 3): shares always cover the full problem and fit
each device's budget via ``working_set_bytes``; a dominated profile
degenerates to the single-device partition; hybrid GEMM/SYRK results
equal the single-device ``ScheduleExecutor`` pipeline (bit for bit where
the blocks keep their shape, else up to fp32 summation order, since the
backend's dot may order a block's sums by its shape) and match the
``kernels/ref.py`` oracle to float tolerance; hybrid attention merges
partials exactly; and
the makespan of the balanced plan beats the best single device under the
canned gpu+phi pair.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (Device, RuntimeFactory, chrome_trace_groups,
                        ooc_attention, ooc_gemm, ooc_syrk,
                        register_runtime)
from repro.core.api import hclHybridRuntime, hclRuntimeFactory
from repro.core.runtime import (_RUNTIME_REGISTRY, HostOocRuntime,
                                OocRuntime)
from repro.hybrid import (DeviceSpec, HybridOocRuntime, balance_gemm,
                          balance_units, merge_attention_partials,
                          plan_hybrid_attention, plan_hybrid_gemm,
                          plan_hybrid_syrk, run_hybrid_attention,
                          run_hybrid_gemm, run_hybrid_syrk, simulate_hybrid)
from repro.kernels import ref
from repro.tune import gpu_profile, phi_profile, tpu_v5e_profile

from hypothesis import given, settings, strategies as st

FAST = dict(nbuf_options=(1, 2), max_steps=256)


def _devices(budget, flops_ratio=1.0):
    return [DeviceSpec("gpu0", gpu_profile(), budget),
            DeviceSpec("phi0", phi_profile(flops=0.725e12 * flops_ratio),
                       budget)]


# ----------------------------------------------------------- balancer props
@settings(max_examples=20, deadline=None)
@given(m=st.sampled_from([256, 520, 1024, 2048, 4096]),
       ratio=st.floats(min_value=0.05, max_value=1.0))
def test_shares_cover_problem_and_fit_budgets(m, ratio):
    N, K = 512, 256
    budget = (m * K + K * N + m * N) * 4 // 3
    devs = _devices(budget, flops_ratio=ratio)
    hp = plan_hybrid_gemm(m, N, K, devs, **FAST)
    # disjoint contiguous spans covering [0, m)
    assert sum(hp.balance.shares) == m
    cursor = 0
    for dp in hp.device_plans:
        assert dp.start == cursor and dp.length > 0
        cursor += dp.length
    assert cursor == m
    # every active sub-plan's working set fits ITS device budget — under
    # the generalized (nbuf, nstreams) model for searched candidates, or
    # the paper's legacy 2-deep model when the tuner kept the baseline
    # (the one candidate gemm_search_space exempts, by design)
    for dp in hp.device_plans:
        part = dp.gemm_partition()
        assert (part.M, part.N, part.K) == (dp.length, N, K)
        fits = min(part.working_set_bytes(dp.plan.nbuf, dp.plan.nstreams),
                   part.working_set_bytes())
        assert fits <= dp.device.budget_bytes


def test_balance_units_equalizes_linear_costs():
    # two devices with exact 3:1 linear rates -> shares converge to 3:1
    rates = (3.0, 1.0)
    res = balance_units(4096, 2, lambda i, u: u / rates[i], tolerance=0.01)
    assert res.converged and sum(res.shares) == 4096
    assert res.shares[0] == pytest.approx(3072, abs=64)
    assert res.spread <= 0.01


def test_dominant_profile_degenerates_to_single_device():
    M, N, K = 1024, 512, 256
    budget = (M * K + K * N + M * N) * 4 // 3
    # phi at 1e-5 of its flops: a sliver of work would still take longer
    # than the gpu doing everything
    devs = _devices(budget, flops_ratio=1e-5)
    hp = plan_hybrid_gemm(M, N, K, devs, **FAST)
    assert [dp.device.name for dp in hp.device_plans] == ["gpu0"]
    assert hp.device_plans[0].length == M
    assert hp.balance.spread == 0.0
    # the surviving sub-plan IS the single-device tuned plan
    from repro.tune import search_gemm
    solo = search_gemm(M, N, K, budget, gpu_profile(), dtype="float32",
                       fingerprint="hybrid-gpu0", **FAST)
    assert hp.device_plans[0].plan == solo


def test_infeasible_device_is_dropped():
    M, N, K = 1024, 512, 256
    rich = (M * K + K * N + M * N) * 4 // 3
    # second device's budget cannot hold even one aligned K-panel block
    devs = [DeviceSpec("big", gpu_profile(), rich),
            DeviceSpec("tiny", phi_profile(), 1024)]
    hp = plan_hybrid_gemm(M, N, K, devs, **FAST)
    assert [dp.device.name for dp in hp.device_plans] == ["big"]
    with pytest.raises(ValueError, match="no feasible split"):
        plan_hybrid_gemm(M, N, K,
                         [DeviceSpec("tiny", phi_profile(), 1024)], **FAST)


def test_unaligned_total_with_infeasible_device():
    # the rounding/unaligned tail must never land on a zero-weight device:
    # M=4100 leaves a 4-row remainder that belongs to the feasible device
    M, N, K = 4100, 512, 256
    rich = (M * K + K * N + M * N) * 4 // 3
    devs = [DeviceSpec("big", gpu_profile(), rich),
            DeviceSpec("tiny", phi_profile(), 1024)]
    hp = plan_hybrid_gemm(M, N, K, devs, **FAST)
    assert [dp.device.name for dp in hp.device_plans] == ["big"]
    assert hp.device_plans[0].length == M
    # same with the infeasible device listed last (the tail position)
    hp2 = plan_hybrid_gemm(M, N, K, list(reversed(devs)), **FAST)
    assert [dp.device.name for dp in hp2.device_plans] == ["big"]
    assert sum(hp2.balance.shares) == M


def test_balance_gemm_direct_oracle():
    M, N, K = 2048, 512, 256
    budget = (M * K + K * N + M * N) * 4 // 3
    # the direct oracle's makespan is a step function of the row count
    # (default partitions change only at bm thresholds), so equalization
    # is only achievable to the partition granularity — allow 10 %
    res = balance_gemm(M, N, K, _devices(budget), tolerance=0.10)
    assert sum(res.shares) == M and res.spread <= res.tolerance
    # the faster gpu-like profile takes the larger band
    assert res.shares[0] > res.shares[1] > 0


# ------------------------------------------------------- execution exactness
def test_hybrid_gemm_bitwise_vs_single_device_and_oracle(rng):
    M, N, K = 512, 384, 256
    A = rng.standard_normal((M, K)).astype(np.float32)
    B = rng.standard_normal((K, N)).astype(np.float32)
    C = rng.standard_normal((M, N)).astype(np.float32)
    budget = (A.nbytes + B.nbytes + C.nbytes) // 4
    hp = plan_hybrid_gemm(M, N, K, _devices(budget), **FAST)
    assert len(hp.device_plans) == 2, "both profiles must take work"
    out, groups = run_hybrid_gemm(A, B, C, 1.5, -0.5, hp, validate=True)
    single = ooc_gemm(A, B, C, 1.5, -0.5, budget_bytes=budget)
    assert np.array_equal(out, single)  # same pipeline, block for block
    expect = np.asarray(ref.gemm_ref(jnp.asarray(A), jnp.asarray(B),
                                     jnp.asarray(C), 1.5, -0.5))
    np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-4)
    assert [g[0] for g in groups] == ["gpu0", "phi0"]


def test_hybrid_syrk_bitwise_vs_single_device_and_oracle(rng):
    n, K = 512, 256
    P = rng.standard_normal((n, K)).astype(np.float32)
    C = rng.standard_normal((n, n)).astype(np.float32)
    budget = (2 * n * K + n * n) * 4 // 3
    hp = plan_hybrid_syrk(n, K, _devices(budget), **FAST)
    assert len(hp.device_plans) == 2
    out, _ = run_hybrid_syrk(P, C, 2.0, 0.5, hp, validate=True)
    single = ooc_syrk(P, C, 2.0, 0.5, budget_bytes=budget)
    # the bands' blocks (24 x 512) differ in shape from the single-device
    # blocks (128 x 128), and XLA's dot may sum a K-long product in a
    # different order per block shape: equal up to fp32 summation order
    bound = ref.gemm_error_bound(P, P.T, C, 2.0, 0.5)
    assert np.all(np.abs(out - single) <= 2 * bound)
    expect = np.asarray(ref.gemm_ref(jnp.asarray(P), jnp.asarray(P).T,
                                     jnp.asarray(C), 2.0, 0.5))
    np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-4)


def test_hybrid_attention_matches_oracle(rng):
    S, hkv, d, H = 1024, 4, 64, 8
    q = rng.standard_normal((H, d)).astype(np.float32)
    k = rng.standard_normal((S, hkv, d)).astype(np.float32)
    v = rng.standard_normal((S, hkv, d)).astype(np.float32)
    devs = _devices(k.nbytes // 2)
    hp = plan_hybrid_attention(S, hkv, d, H, devs, dtype="float32")
    assert sum(hp.balance.shares) == S and len(hp.device_plans) == 2
    out, _ = run_hybrid_attention(q, k, v, hp, validate=True)
    expect = np.asarray(ref.decode_attention_ref(
        jnp.asarray(q)[None], jnp.asarray(k)[None], jnp.asarray(v)[None],
        jnp.asarray([S]))[0])
    np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-4)


def test_merge_attention_partials_is_exact(rng):
    # partials from arbitrary chunkings combine to the same answer
    H, d = 8, 16
    parts = []
    for _ in range(3):
        m = rng.standard_normal(H).astype(np.float32)
        l = rng.uniform(0.5, 2.0, H).astype(np.float32)
        acc = rng.standard_normal((H, d)).astype(np.float32)
        parts.append((m, l, acc))
    merged = merge_attention_partials(parts)
    # fold the same partials in pairwise order: must agree to fp tolerance
    ab = merge_attention_partials(parts[:2])
    m01 = np.maximum(parts[0][0], parts[1][0])
    l01 = (parts[0][1] * np.exp(parts[0][0] - m01)
           + parts[1][1] * np.exp(parts[1][0] - m01))
    acc01 = (parts[0][2] * np.exp(parts[0][0] - m01)[:, None]
             + parts[1][2] * np.exp(parts[1][0] - m01)[:, None])
    seq = merge_attention_partials([(m01, l01, acc01), parts[2]])
    np.testing.assert_allclose(merged, seq, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(ab, acc01 / l01[:, None], rtol=1e-6)


# ------------------------------------------------------- entry points/facade
def test_ooc_gemm_devices_entry_point(rng):
    M, N, K = 384, 256, 192
    A = rng.standard_normal((M, K)).astype(np.float32)
    B = rng.standard_normal((K, N)).astype(np.float32)
    budget = (A.nbytes + B.nbytes + M * N * 4) // 3
    # bare (name, profile, budget) tuples are accepted
    out = ooc_gemm(A, B, budget_bytes=1,
                   devices=[("g", gpu_profile(), budget),
                            ("p", phi_profile(), budget)])
    np.testing.assert_allclose(out, np.asarray(ref.gemm_ref(
        jnp.asarray(A), jnp.asarray(B))), rtol=1e-4, atol=1e-4)


def test_ooc_attention_devices_entry_point(rng):
    S, hkv, d, H = 512, 2, 32, 4
    q = rng.standard_normal((H, d)).astype(np.float32)
    k = rng.standard_normal((S, hkv, d)).astype(np.float32)
    v = rng.standard_normal((S, hkv, d)).astype(np.float32)
    out = np.asarray(ooc_attention(
        q, k, v, budget_bytes=1, devices=_devices(k.nbytes)))
    expect = np.asarray(ref.decode_attention_ref(
        jnp.asarray(q)[None], jnp.asarray(k)[None], jnp.asarray(v)[None],
        jnp.asarray([S]))[0])
    np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-4)


def test_hybrid_runtime_facade_and_factory(rng):
    M, N, K = 384, 256, 192
    A = rng.standard_normal((M, K)).astype(np.float32)
    B = rng.standard_normal((K, N)).astype(np.float32)
    C = np.zeros((M, N), np.float32)
    budget = (A.nbytes + B.nbytes + C.nbytes) // 3
    rt = hclHybridRuntime(_devices(budget), **FAST)
    out = rt.gemm(A, B, C, 1.0, 0.0, record_spans=True)
    np.testing.assert_allclose(out, np.asarray(ref.gemm_ref(
        jnp.asarray(A), jnp.asarray(B))), rtol=1e-4, atol=1e-4)
    assert rt.last_plan is not None and rt.last_span_groups
    # the composite resolves through the declarative registry too
    dev = Device("HYBRID", 0, 2 * budget)
    rt2 = hclRuntimeFactory.create(dev, devices=_devices(budget))
    assert isinstance(rt2, HybridOocRuntime)
    # hclDeviceFactory's sizeless HYBRID placeholder reports the member sum
    from repro.core.api import hclDeviceFactory
    rt3 = hclRuntimeFactory.create(hclDeviceFactory.create("HYBRID"),
                                   devices=_devices(budget))
    assert rt3.mem_size() == 2 * budget
    with pytest.raises(ValueError, match="needs devices"):
        RuntimeFactory.create(Device("HYBRID", 0, 0))


# ------------------------------------------------- prediction + lane groups
def test_simulate_hybrid_beats_best_single_device():
    M = N = K = 8192
    budget = (M * K + K * N + M * N) * 8 // 6
    devs = _devices(budget)
    hp = plan_hybrid_gemm(M, N, K, devs, dtype="float64", tolerance=0.05,
                          nbuf_options=(1, 2), max_steps=128)
    sim = simulate_hybrid(hp)
    from repro.tune import search_gemm
    best = min(search_gemm(M, N, K, d.budget_bytes, d.profile,
                           dtype="float64", fingerprint="x",
                           nbuf_options=(1, 2), max_steps=128).makespan
               for d in devs)
    assert sim.makespan < best
    # finish times agree within the balancer tolerance...
    assert hp.balance.spread <= hp.tolerance
    # ...and simulate_hybrid re-derives exactly the tuned predictions
    for dp, got in zip(hp.device_plans, sim.device_makespans):
        assert got == pytest.approx(dp.plan.makespan, rel=1e-12)


def test_trace_lane_group_per_device_no_collisions():
    M, N, K = 1024, 512, 256
    budget = (M * K + K * N + M * N) * 4 // 3
    hp = plan_hybrid_gemm(M, N, K, _devices(budget), **FAST)
    trace = simulate_hybrid(hp).to_chrome_trace()
    events = trace["traceEvents"]
    names = {e["pid"]: e["args"]["name"] for e in events
             if e["name"] == "process_name"}
    assert names == {0: "gpu0", 1: "phi0"}
    # spans from different devices never share a (pid, tid, ts) slot even
    # though both executors number their streams from 0
    xs = [e for e in events if e["ph"] == "X"]
    assert {e["pid"] for e in xs} == {0, 1}
    slots = [(e["pid"], e["tid"], e["ts"]) for e in xs]
    assert len(slots) == len(set(slots))
    # per-pid span sets are exactly the per-device simulations
    per_dev = simulate_hybrid(hp).per_device
    for pid, (_, res) in enumerate(per_dev):
        assert sum(e["pid"] == pid for e in xs) == len(res.op_spans)


def test_chrome_trace_groups_standalone():
    groups = [("devA", [("DGEMM[0]", 0, 0.0, 1.0)]),
              ("devB", [("DGEMM[0]", 0, 0.5, 1.5)])]
    trace = chrome_trace_groups(groups)
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert [(e["pid"], e["tid"]) for e in xs] == [(0, 0), (1, 0)]


# ------------------------------------------------------------ registry unit
def test_register_runtime_plugs_in_new_tier():
    @register_runtime("TESTTIER")
    class TestTierRuntime(HostOocRuntime):
        pass

    try:
        rt = RuntimeFactory.create(Device("TESTTIER", 0, 1 << 20))
        assert isinstance(rt, TestTierRuntime)
        assert "TESTTIER" in RuntimeFactory.registered()
    finally:
        _RUNTIME_REGISTRY.pop("TESTTIER", None)


def test_factory_rejects_unknown_tier():
    with pytest.raises(ValueError, match="registered tiers"):
        RuntimeFactory.create(Device("NOPE", 0, 1))
    for tier in ("HBM", "VMEM", "MESH", "HYBRID"):
        assert tier in RuntimeFactory.registered()
