"""hcl-prefixed facade — the paper's exact API surface, for LOC-parity demos.

The productivity claim (C4) is measured against code written in the paper's
own vocabulary; this module provides that vocabulary verbatim
(``hclDeviceFactory``, ``hclRuntimeFactory``, ``hclStreamFactory``,
``hclMatrixPartitioner``, ...), mapping onto the TPU-native engine.
``examples/mmooc_via_api.py`` is written against this facade and is the LOC
numerator; the three direct backend implementations in
``benchmarks/direct_impls.py`` are the denominator.
"""

from __future__ import annotations

from typing import List, Optional

from jax.sharding import Mesh

from repro.core.exec_plan import ExecutablePlan, compile_executable
from repro.core.partitioner import GemmPartition, plan_gemm_partition
from repro.core.pipeline import PipelineSpec, compile_pipeline
from repro.core.runtime import (
    OocRuntime,
    RuntimeFactory,
    ScheduleExecutor,
    register_op_handler,
)
from repro.core.streams import Device, Schedule, Stream, StreamFactory
from repro.kernels.block_matmul import VMEM_SCOPED_LIMIT

# Device-type names map to memory tiers (DESIGN.md §2): the analogues of the
# paper's {"GPU", "PHI", "FPGA"} triple.
_TIER_BYTES = {
    "VMEM": VMEM_SCOPED_LIMIT,  # v5e scoped VMEM per Pallas kernel
    "HBM": 16 * 2**30,     # v5e HBM
    "MESH": 16 * 2**30,    # per-shard HBM (aggregate = pod)
    "HYBRID": 0,           # composite: memory is the member devices' sum
}


class hclDeviceFactory:
    @staticmethod
    def create(name: str, dev_id: int = 0,
               mem_bytes: Optional[int] = None) -> Device:
        name = name.upper()
        if name not in _TIER_BYTES:
            raise ValueError(f"unknown device type {name!r}")
        return Device(name, dev_id, mem_bytes or _TIER_BYTES[name])


class hclRuntimeFactory:
    @staticmethod
    def create(device: Device, mesh: Optional[Mesh] = None,
               **kw) -> OocRuntime:
        return RuntimeFactory.create(device, mesh, **kw)


class hclStreamFactory:
    @staticmethod
    def create(device: Device, n: int) -> List[Stream]:
        return StreamFactory.create(device, n)


def hclGetMemSize(device: Device) -> int:
    return device.mem_size()


def hclMatrixPartitioner(M: int, N: int, K: int, dMemSize: int,
                         bytes_per_el: int = 4,
                         nbuf: Optional[int] = None,
                         nstreams: Optional[int] = None) -> GemmPartition:
    """Partition against the device memory — optionally aware of the actual
    pipeline depth (``nbuf``/``nstreams``) so deeper pipelines get blocks
    their larger buffer allocation still fits; default is the paper's fixed
    2-deep model."""
    return plan_gemm_partition(M, N, K, dMemSize, bytes_per_el,
                               nbuf=nbuf, nstreams=nstreams)


def hclCompilePipeline(spec: PipelineSpec, nstreams: int = 2,
                       nbuf: int = 2) -> Schedule:
    """DSL entry point (the paper's §V "synchronization pattern can be
    reused" future work): PipelineSpec -> event-correct Schedule."""
    return compile_pipeline(spec, nstreams=nstreams, nbuf=nbuf)


class hclScheduleExecutor(ScheduleExecutor):
    """Facade alias: the single schedule interpreter (DESIGN.md §4), with
    ``register_op_handler`` as the kernel extension point and
    ``mode="concurrent"`` selecting the per-engine worker-thread runner
    (DESIGN.md §13)."""


hclRegisterOpHandler = register_op_handler


def hclCompileExecutable(sched: Schedule) -> ExecutablePlan:
    """Compile (or fetch the cached) :class:`ExecutablePlan` for a schedule
    — pre-resolved handlers, per-engine queues, dependency edges
    (DESIGN.md §13)."""
    return compile_executable(sched)


def hclHybridRuntime(devices, **kw):
    """Facade over :class:`repro.hybrid.HybridOocRuntime` (DESIGN.md §7):
    one kernel call co-scheduled across a heterogeneous device set, load
    balanced by calibrated profiles.

        gpu = DeviceSpec("gpu0", gpu_profile(), 2 * 2**30)
        phi = DeviceSpec("phi0", phi_profile(), 2 * 2**30)
        rt = hclHybridRuntime([gpu, phi])
        C = rt.gemm(A, B, C, alpha, beta)

    ``devices`` is a sequence of :class:`~repro.hybrid.DeviceSpec` (or bare
    ``(name, profile, budget_bytes)`` tuples).  Resolved lazily —
    ``repro.hybrid`` imports ``repro.tune``, which imports this package."""
    from repro.hybrid import HybridOocRuntime

    return HybridOocRuntime(devices, **kw)


def hclOocFactor(A, kind: str = "cholesky", **kw):
    """Facade over the out-of-core factorizations (DESIGN.md §8): one
    lookahead pipeline schedule interleaving panel POTRF/GETRF/TRSM ops with
    the streamed SYRK/GEMM trailing update.

        L = hclOocFactor(A, "cholesky", budget_bytes=..., lookahead=1)
        LU, perm = hclOocFactor(A, "lu", budget_bytes=..., tune="auto")

    Keyword arguments forward to :func:`repro.core.ooc_factor.ooc_cholesky`
    / :func:`~repro.core.ooc_factor.ooc_lu` (``panel``, ``budget_bytes``,
    ``lookahead``, ``tune``, ``devices``, ...).  The engine computes in
    float32 whatever the input dtype: float64 results carry f32-level
    residuals (see the entry-point docstrings)."""
    from repro.core.ooc_factor import ooc_cholesky, ooc_lu

    if kind == "cholesky":
        return ooc_cholesky(A, **kw)
    if kind == "lu":
        return ooc_lu(A, **kw)
    raise ValueError(f"unknown factor kind {kind!r}; expected "
                     f"'cholesky' or 'lu'")


def hclObservability(enable: bool = False, trace: bool = False, **kw):
    """Facade over the process :class:`repro.obs.Observability` bundle
    (DESIGN.md §10): metrics registry, hierarchical tracer and drift
    monitor in one switch.

        obs = hclObservability(enable=True, trace=True)
        C = ooc_gemm(A, B, budget_bytes=..., tune="auto", devices=[...])
        obs.tracer.write("trace.json")           # one coherent timeline
        print(obs.metrics.to_prometheus_text())  # exact byte accounting
        print(obs.drift.snapshot()["rolling"])   # predicted vs measured

    With no arguments this just returns the singleton (everything starts
    disabled); ``enable=True`` turns on metrics, ``trace=True`` also starts
    a tracer.  Extra keywords forward to
    :meth:`~repro.obs.Observability.enable`."""
    from repro.obs import get_observability

    obs = get_observability()
    if enable or trace:
        obs.enable(metrics=True, trace=trace, **kw)
    return obs


def hclTraceAnalysis(sched: Schedule, hw=None, res=None, spans=None, **kw):
    """Facade over :class:`repro.obs.analyze.TraceAnalysis` (DESIGN.md §11):
    bottleneck attribution over one schedule's span timeline.

        ana, res = hclTraceAnalysis(sched, hw=profile.model_for(2))
        print(ana.digest())        # verdict + critical-path shares
        ana.verify_reconciliation(res)   # exact accounting, or AssertionError

    Three input shapes: simulate here (``hw`` an engine model or a
    :class:`~repro.tune.calibrate.HardwareProfile`, returns
    ``(analysis, SimResult)``), attribute an existing simulation (``res``),
    or attribute recorded wall-clock spans (``spans``, tolerance-matched).
    Resolved lazily: the analyzer imports the simulator."""
    from repro.obs.analyze import TraceAnalysis

    if res is not None:
        return TraceAnalysis.from_sim(sched, res, hw=hw)
    if spans is not None:
        return TraceAnalysis.from_spans(sched, spans, hw=hw, **kw)
    if hw is None:
        raise ValueError("hclTraceAnalysis needs hw=, res= or spans=")
    if hasattr(hw, "model_for"):       # a HardwareProfile: default 2 streams
        hw = hw.model_for(kw.pop("nstreams", 2))
    return TraceAnalysis.analyze(sched, hw)


def hclAutoTuner(device: Optional[Device] = None, **kw):
    """Facade over :class:`repro.tune.AutoTuner` (DESIGN.md §6): calibrate
    the device once, then dispense cached ``TunedPlan``s — partition
    geometry, stream count, buffer depth — per problem shape and tier.

        tuner = hclAutoTuner(device)                # calibrates lazily
        plan = tuner.gemm_plan(M, N, K, hclGetMemSize(device))
        C = ooc_gemm(A, B, budget_bytes=..., tune="auto", tuner=tuner)

    Resolved lazily: ``repro.tune`` imports ``repro.core`` submodules, so
    the facade must not import the tuner package at module load."""
    from repro.tune import AutoTuner

    if device is not None:
        kw.setdefault("tier", device.name.upper())
    return AutoTuner(**kw)


def hclFaultPolicy(**kw):
    """Facade over :class:`repro.fault.FaultPolicy` (DESIGN.md §12): the
    recovery knobs every resilient entry point shares — transfer retry
    count and exponential backoff, and the oom degradation ladder's depth.

        pol = hclFaultPolicy(max_retries=5, backoff_base=0.02)
        C = ooc_gemm(A, B, budget_bytes=..., faults=plan, fault_policy=pol)

    Pair with a :class:`~repro.fault.FaultPlan` (deterministic, seeded,
    schedule-addressable) passed as ``faults=`` to ``ooc_gemm`` /
    ``ooc_syrk`` / ``ooc_cholesky`` / ``ooc_lu``, or as per-device
    ``fault_plans=`` to ``run_hybrid_gemm`` / ``run_hybrid_syrk``.
    Resolved lazily to keep the facade import-light."""
    from repro.fault import FaultPolicy

    return FaultPolicy(**kw)
