"""MMOOC — out-of-core matrix multiplication, the paper's reference kernel.

``ooc_gemm`` is the public entry point: plan a partition for the device's
memory budget, build the event-correct pipeline schedule, and execute it on
the selected backend.  The in-core/out-of-core switch (paper §VI: libhclooc
switches when N exceeds what fits) lives here: if the whole problem fits the
budget, a single in-core DGEMM is issued — the transition that claim C2 says
must cost 0 %.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pipeline as plib
from repro.core.partitioner import GemmPartition, plan_gemm_partition
from repro.core.runtime import (
    HostOocRuntime,
    MeshOocRuntime,
    OocRuntime,
    RuntimeFactory,
    VmemOocRuntime,
    _block_dgemm,
)
from repro.core.streams import Device, OpKind, validate_schedule
from repro.obs import get_observability


def is_in_core(M: int, N: int, K: int, budget_bytes: int,
               bytes_per_el: int = 4) -> bool:
    """True if A, B and C are simultaneously resident within the budget."""
    return (M * K + K * N + M * N) * bytes_per_el <= budget_bytes


def _tuned_gemm_plan(tuner, kernel: str, M: int, N: int, K: int,
                     budget_bytes: int, dtype):
    """Resolve the full :class:`~repro.tune.search.TunedPlan` from the
    (default) autotuner's plan cache — searched once per (shape, dtype,
    tier, hardware).  Returning the plan (not just its pipeline knobs)
    keeps the predicted makespan available for drift recording."""
    if tuner is None:
        from repro.tune import get_default_tuner
        tuner = get_default_tuner()
    plan = tuner.gemm_plan(M, N, K, budget_bytes,
                           dtype=np.dtype(dtype).name, kernel=kernel)
    if not plan.write_back:
        # "keep"-mode plans describe resident-C (SUMMA-style) pipelines;
        # this entry point must land C in host memory
        raise ValueError(
            f"tuned plan for {kernel} {(M, N, K)} was searched with "
            f"write_back=False; ooc_{kernel} requires write-back plans")
    return plan


def _record_host_drift(plan, rt, sched) -> None:
    """After a tuned host-backend run: log measured wall/bytes against the
    plan's simulated makespan and the schedule's modeled byte totals."""
    ex = getattr(rt, "executor", None)
    if plan is None or ex is None:
        return
    get_observability().record_drift(
        plan.kernel, plan.tier, plan.fingerprint,
        predicted_makespan=plan.makespan,
        measured_seconds=ex.last_wall_seconds,
        predicted_h2d_bytes=sched.total_bytes(OpKind.H2D),
        measured_h2d_bytes=ex.last_h2d_bytes,
        predicted_d2h_bytes=sched.total_bytes(OpKind.D2H),
        measured_d2h_bytes=ex.last_d2h_bytes)


def _hybrid_kwargs(tolerance: Optional[float]) -> dict:
    return {} if tolerance is None else {"tolerance": tolerance}


def _host_gemm_resilient(rt, A, B, C, alpha, beta, part, sched, *, faults,
                         policy, tuned, tune, tuner, nstreams, nbuf,
                         traversal, evict, budget_bytes, bpe):
    """Host-backend GEMM under fault injection with the oom degradation
    ladder (DESIGN.md §12): an injected oom aborts the run, then halve
    nbuf / halve budget rungs replan + rebuild the schedule (tuned runs
    re-search at the reduced budget) and re-execute clean.  The attempted
    rungs are recorded in ``policy.degrades``."""
    from repro.fault.errors import OomError
    from repro.fault.policy import FaultPolicy

    M, K = A.shape
    N = B.shape[1]
    policy = policy or FaultPolicy()
    try:
        out = rt.gemm(A, B, C, alpha, beta, part, schedule=sched,
                      faults=faults, policy=policy)
        _record_host_drift(tuned, rt, sched)
        return out
    except OomError:
        obs = get_observability()
        for step in policy.degrade_ladder(nbuf=nbuf, lookahead=0,
                                          budget_bytes=budget_bytes,
                                          tuned=tune == "auto"):
            policy.degrades.append(step)
            obs.instant(f"fault:degrade:{step.action}", kernel="gemm")
            try:
                if tune == "auto":
                    t2 = _tuned_gemm_plan(tuner, "gemm", M, N, K,
                                          step.budget_bytes, A.dtype)
                    part2, ns2, nb2 = (t2.gemm_partition(), t2.nstreams,
                                       t2.nbuf)
                    tr2, ev2 = t2.traversal, t2.evict
                else:
                    part2 = plan_gemm_partition(M, N, K, step.budget_bytes,
                                                bpe)
                    ns2, nb2, tr2, ev2 = (nstreams, step.nbuf, traversal,
                                          evict)
                sched2 = plib.build_gemm_schedule(
                    part2, nstreams=ns2, nbuf=nb2, traversal=tr2, evict=ev2)
                # clean re-run: the oom occurrence was consumed above
                out = rt.gemm(A, B, C, alpha, beta, part2, schedule=sched2)
            except ValueError:
                continue
            obs.record_fault_recovery("gemm", "degrade")
            return out
        raise


def ooc_gemm(
    A,
    B,
    C=None,
    alpha: float = 1.0,
    beta: float = 0.0,
    *,
    budget_bytes: int,
    backend: str = "host",
    nstreams: int = 2,
    nbuf: int = 2,
    traversal: str = "col",
    evict: str = "lru",
    mesh=None,
    validate: bool = False,
    runtime: Optional[OocRuntime] = None,
    tune: Optional[str] = None,
    tuner=None,
    devices: Optional[Sequence] = None,
    tolerance: Optional[float] = None,
    faults=None,
    fault_policy=None,
):
    """Compute ``alpha * A @ B + beta * C`` streaming blocks through a memory
    tier of size ``budget_bytes``.

    backend: "host" (schedule-driven block streaming), "vmem" (Pallas kernel),
    "mesh" (SUMMA ring over a mesh axis).

    tune: ``None`` uses the hardcoded defaults above; ``"auto"`` asks an
    :class:`~repro.tune.tuner.AutoTuner` (``tuner`` or the process default)
    for a calibrated plan — partition geometry, stream count and buffer
    depth — served from the plan cache on repeat calls (host backend; other
    backends plan their own pipelines).

    devices: a set of :class:`~repro.hybrid.DeviceSpec` (or ``(name,
    profile, budget_bytes)`` tuples) co-executes the one GEMM across all of
    them: C's rows are split so the calibrated profiles predict equal
    per-device finish times (``tolerance`` overrides the balancer default),
    each band runs its own tuned schedule concurrently, and the disjoint
    bands merge into one result.  Per-device budgets come from the specs,
    so ``budget_bytes`` and ``backend`` are ignored on this path.

    traversal / evict (host backend): block-grid step order (see
    :data:`~repro.core.partitioner.TRAVERSALS`) and residency-cache
    eviction policy (``"lru"``/``"belady"``) — they change which H2D
    transfers the compiler's block cache elides, never the result.  Tuned
    plans carry their own searched traversal/evict and override these.

    faults / fault_policy (host backend, DESIGN.md §12): a
    :class:`~repro.fault.FaultPlan` (or ``sched -> plan`` callable) armed
    on the executor.  Transfer faults retry, compute faults replay; an
    injected oom walks the degradation ladder (halve nbuf, then halve the
    budget — tuned runs re-search at the reduced budget) and re-executes
    clean.
    """
    if tune not in (None, "auto"):
        raise ValueError(f"unknown tune mode {tune!r}; expected None/'auto'")
    if faults is not None and (devices is not None or backend != "host"):
        raise ValueError("fault injection is supported on the host "
                         "pipeline backend only (hybrid paths take "
                         "fault_plans on run_hybrid_*)")
    if devices is not None:
        from repro.hybrid import plan_hybrid_gemm, run_hybrid_gemm

        A = np.asarray(A)
        B = np.asarray(B)
        hplan = plan_hybrid_gemm(
            A.shape[0], B.shape[1], A.shape[1], devices,
            dtype=np.dtype(A.dtype).name, **_hybrid_kwargs(tolerance))
        out, _ = run_hybrid_gemm(A, B, C, alpha, beta, hplan,
                                 validate=validate)
        return out
    if backend == "mesh":
        # operands go from where they are straight to their shards, never
        # whole onto the default device first
        if C is None:
            C = np.zeros((A.shape[0], B.shape[1]), dtype=A.dtype)
            beta = 0.0
        rt = runtime or MeshOocRuntime(mesh)
        return rt.gemm(A, B, C, alpha, beta, None)
    A = np.asarray(A) if backend == "host" else jnp.asarray(A)
    B = np.asarray(B) if backend == "host" else jnp.asarray(B)
    M, K = A.shape
    K2, N = B.shape
    if K != K2:
        raise ValueError(f"inner dims mismatch: {A.shape} @ {B.shape}")
    if C is None:
        C = np.zeros((M, N), dtype=A.dtype) if backend == "host" \
            else jnp.zeros((M, N), dtype=A.dtype)
        beta = 0.0
    bpe = np.dtype(A.dtype).itemsize

    if is_in_core(M, N, K, budget_bytes, bpe):
        # In-core fast path: one resident DGEMM (claim C2 transition point).
        out = _block_dgemm(jnp.asarray(A), jnp.asarray(B), jnp.asarray(C),
                           jnp.float32(alpha), jnp.float32(beta))
        return np.asarray(out) if backend == "host" else out

    tuned = None
    if tune == "auto" and backend == "host":
        tuned = _tuned_gemm_plan(tuner, "gemm", M, N, K, budget_bytes,
                                 A.dtype)
        part, nstreams, nbuf = (tuned.gemm_partition(), tuned.nstreams,
                                tuned.nbuf)
        traversal, evict = tuned.traversal, tuned.evict
    else:
        part = plan_gemm_partition(M, N, K, budget_bytes, bpe)
    if backend == "host":
        sched = plib.build_gemm_schedule(part, nstreams=nstreams, nbuf=nbuf,
                                         traversal=traversal, evict=evict)
        if validate:
            validate_schedule(sched)
        rt = runtime or HostOocRuntime()
        if faults is None:
            out = rt.gemm(A, B, C, alpha, beta, part, schedule=sched)
            _record_host_drift(tuned, rt, sched)
            return out
        return _host_gemm_resilient(
            rt, A, B, C, alpha, beta, part, sched, faults=faults,
            policy=fault_policy, tuned=tuned, tune=tune, tuner=tuner,
            nstreams=nstreams, nbuf=nbuf, traversal=traversal, evict=evict,
            budget_bytes=budget_bytes, bpe=bpe)
    if backend == "vmem":
        rt = runtime or VmemOocRuntime()
        return rt.gemm(A, B, C, alpha, beta, part)
    raise ValueError(f"unknown backend {backend!r}")


def ooc_syrk(
    P,
    C=None,
    alpha: float = 1.0,
    beta: float = 0.0,
    *,
    budget_bytes: int,
    backend: str = "host",
    nstreams: int = 2,
    nbuf: int = 2,
    traversal: str = "col",
    evict: str = "lru",
    validate: bool = False,
    runtime: Optional[OocRuntime] = None,
    tune: Optional[str] = None,
    tuner=None,
    devices: Optional[Sequence] = None,
    tolerance: Optional[float] = None,
    faults=None,
    fault_policy=None,
):
    """Compute ``alpha * P @ P^T + beta * C`` out-of-core (blocked SYRK).

    The Cholesky trailing update as a first-class pipeline kernel: on the
    host backend the :func:`~repro.core.pipeline.syrk_pipeline_spec` streams
    the panel twice (row slices and transposed row slices) through the same
    schedule shape and ``dgemm`` handler as MMOOC, with no host-side ``P.T``
    copy — only individual blocks are transposed in flight.  The vmem and
    in-core paths delegate to the dense GEMM kernel and do materialize the
    transpose on-device.

    tune: as in :func:`ooc_gemm` — ``"auto"`` plans partition/streams/buffers
    through the autotuner (keyed as the ``syrk`` kernel, since the panel is
    streamed twice).

    devices: as in :func:`ooc_gemm` — co-execute across a heterogeneous
    device set, splitting C's rows by calibrated profile (each band's
    transposed panel still streams the full P, block by block).

    traversal / evict: as in :func:`ooc_gemm` — step order and block-cache
    eviction policy for the host pipeline; tuned plans override both.
    """
    if tune not in (None, "auto"):
        raise ValueError(f"unknown tune mode {tune!r}; expected None/'auto'")
    if faults is not None and (devices is not None or backend != "host"):
        raise ValueError("fault injection is supported on the host "
                         "pipeline backend only (hybrid paths take "
                         "fault_plans on run_hybrid_*)")
    if devices is not None:
        from repro.hybrid import plan_hybrid_syrk, run_hybrid_syrk

        P = np.asarray(P)
        hplan = plan_hybrid_syrk(
            P.shape[0], P.shape[1], devices,
            dtype=np.dtype(P.dtype).name, **_hybrid_kwargs(tolerance))
        out, _ = run_hybrid_syrk(P, C, alpha, beta, hplan,
                                 validate=validate)
        return out
    if backend not in ("host", "vmem"):
        raise ValueError(f"unknown backend {backend!r}")
    P = np.asarray(P) if backend == "host" else jnp.asarray(P)
    n, K = P.shape
    if C is None:
        C = np.zeros((n, n), dtype=P.dtype) if backend == "host" \
            else jnp.zeros((n, n), dtype=P.dtype)
        beta = 0.0
    bpe = np.dtype(P.dtype).itemsize

    if is_in_core(n, n, K, budget_bytes, bpe):
        out = _block_dgemm(jnp.asarray(P), jnp.asarray(P).T, jnp.asarray(C),
                           jnp.float32(alpha), jnp.float32(beta))
        return np.asarray(out) if backend == "host" else out

    tuned = None
    if tune == "auto" and backend == "host":
        tuned = _tuned_gemm_plan(tuner, "syrk", n, n, K, budget_bytes,
                                 P.dtype)
        part, nstreams, nbuf = (tuned.gemm_partition(), tuned.nstreams,
                                tuned.nbuf)
        traversal, evict = tuned.traversal, tuned.evict
    else:
        part = plan_gemm_partition(n, n, K, budget_bytes, bpe)
    if backend == "host":
        sched = plib.build_syrk_schedule(part, nstreams=nstreams, nbuf=nbuf,
                                         traversal=traversal, evict=evict)
        if validate:
            validate_schedule(sched)
        rt = runtime or HostOocRuntime()
        out = rt.syrk(P, C, alpha, beta, part, schedule=sched,
                      faults=faults, policy=fault_policy)
        _record_host_drift(tuned, rt, sched)
        return out
    # "vmem": the only other backend the top-of-function guard admits
    rt = runtime or VmemOocRuntime()
    return rt.gemm(P, jnp.asarray(P).T, C, alpha, beta, part)


def plan_for_device(M: int, N: int, K: int, device: Device,
                    bytes_per_el: int = 4) -> GemmPartition:
    """Partition using the device's reported memory (hclGetMemSize path)."""
    return plan_gemm_partition(M, N, K, device.mem_bytes, bytes_per_el)
