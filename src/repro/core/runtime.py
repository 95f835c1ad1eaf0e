"""OOC runtimes — the ``hclRuntime`` class hierarchy, TPU-native.

The paper's ``hclRuntimeFactory`` dispenses one of three device-type-specific
runtimes (CUDA / Phi offload / OpenCL) behind a pure-virtual interface.  Here
the three "device types" are the three TPU memory tiers a blocked workload can
stream through (DESIGN.md §2):

  * :class:`HostOocRuntime`  — host-driven block streaming through a chip's
    HBM: executes a :class:`~repro.core.streams.Schedule` op-by-op with real
    JAX dispatch (async on real hardware), buffers keyed by parity exactly as
    the schedule's event program dictates.  This is the most literal port of
    the paper's MMOOC loop.
  * :class:`VmemOocRuntime`  — HBM->VMEM streaming *inside* the chip via the
    Pallas kernel (``kernels/block_matmul.py``); the schedule is declarative
    (grid + BlockSpec index maps) and Mosaic emits the double-buffered DMAs.
  * :class:`MeshOocRuntime`  — the pod's aggregate HBM as backing store:
    SUMMA ring over ICI with ``shard_map`` + ``ppermute`` ping-pong buffers
    (the paper's §V ``nsteps``/SUMMA integration point).

All runtimes compute the same DGEMM contract ``C = alpha*A@B + beta*C`` and
are cross-checked against ``kernels/ref.py`` in tests.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
import threading
import time
from typing import Any, Callable, Dict, Hashable, List, Optional, Tuple, Type

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import exec_plan as _xplan
from repro.core import pipeline as plib
from repro.core.exec_plan import ExecutablePlan, compile_executable
from repro.core.partitioner import GemmPartition, plan_gemm_partition
from repro.core.streams import (BlockRef, Device, Op, OpKind, Schedule,
                                ScheduleError, SliceRef)
from repro.obs import get_observability


class OocRuntime:
    """Pure-virtual base (the paper's ``hclRuntime``)."""

    device: Device

    def gemm(self, A, B, C, alpha: float, beta: float,
             part: GemmPartition, **kw):
        raise NotImplementedError

    @classmethod
    def from_device(cls, device: Device, *, mesh: Optional[Mesh] = None,
                    **kw) -> "OocRuntime":
        """Factory hook :class:`RuntimeFactory` calls for the registered
        tier; override when construction needs more than the device tuple
        (the mesh runtime needs a jax Mesh, the hybrid composite a device
        set)."""
        return cls(device=device, **kw)

    # hcl-style helpers shared by backends ------------------------------------
    def mem_size(self) -> int:  # hclGetMemSize
        return self.device.mem_bytes

    def device_synchronize(self, *arrays) -> None:  # hclDeviceSynchronize
        for a in arrays:
            jax.block_until_ready(a)


# ===========================================================================
# Runtime registry — tiers self-register instead of being if/elif'd
# ===========================================================================
_RUNTIME_REGISTRY: Dict[str, Type[OocRuntime]] = {}

# Tiers whose runtime lives outside core (imported on first use so core
# stays cycle-free: the hybrid composite pulls in repro.tune which in turn
# imports repro.core).
_LAZY_RUNTIME_MODULES: Dict[str, str] = {"HYBRID": "repro.hybrid.executor"}


def register_runtime(name: str) -> Callable[[Type[OocRuntime]],
                                            Type[OocRuntime]]:
    """Class decorator registering an :class:`OocRuntime` under tier ``name``.

    ``RuntimeFactory.create`` dispatches ``Device.name`` through this
    registry via the class's :meth:`OocRuntime.from_device` hook, so new
    tiers (and composites like the hybrid runtime) plug in without editing
    the factory.
    """

    def deco(cls: Type[OocRuntime]) -> Type[OocRuntime]:
        _RUNTIME_REGISTRY[name.upper()] = cls
        return cls

    return deco


@functools.partial(jax.jit, static_argnames=("transpose",))
def _block_dgemm(a, b, c, alpha, beta, transpose: bool = False):
    """In-core DGEMM on resident blocks (the vendor-kernel slot)."""
    acc = jnp.dot(a, b, preferred_element_type=jnp.float32)
    return (alpha * acc + beta * c).astype(c.dtype)


# ===========================================================================
# ScheduleExecutor — the single schedule interpreter for every host path
# ===========================================================================
HandlerFn = Callable[["ExecState", Op, BlockRef], None]
_OP_HANDLERS: Dict[str, HandlerFn] = {}
# bumped on every registration: compiled ExecutablePlans pin the version
# they resolved handlers against, so late registrations invalidate cached
# plans instead of serving stale (or missing) resolutions
_HANDLERS_VERSION = 0


def handlers_version() -> int:
    """Monotonic handler-registry version (plan-cache invalidation key)."""
    return _HANDLERS_VERSION


def register_op_handler(kernel: str) -> Callable[[HandlerFn], HandlerFn]:
    """Register ``fn(state, op, ref)`` for ops whose :class:`BlockRef` payload
    names ``kernel`` — COMPUTE dispatch and "final"-mode D2H finalizers.

    Handlers receive parity buffers positionally via ``op.buffers_read`` /
    ``op.buffers_written`` in the order the :class:`PipelineSpec` declared
    them, kernel parameters via ``state.ctx``, and may keep carry state in
    ``state.scratch``.
    """

    def deco(fn: HandlerFn) -> HandlerFn:
        global _HANDLERS_VERSION
        _OP_HANDLERS[kernel] = fn
        _HANDLERS_VERSION += 1
        return fn

    return deco


@dataclasses.dataclass
class ExecState:
    """Mutable execution state threaded through op handlers."""

    bufs: Dict[Tuple[str, Hashable], jax.Array]  # device parity buffers
    operands: Dict[str, Any]                     # host-resident inputs
    outputs: Dict[str, np.ndarray]               # host results (in-place)
    ctx: Dict[str, Any]                          # kernel parameters
    scratch: Dict[str, Any]                      # handler carry state

    def host(self, name: str):
        """Host array an H2D slices from: inout operands read the live
        output so a kernel can accumulate into what it already wrote."""
        return self.outputs[name] if name in self.outputs \
            else self.operands[name]


def _take(arr, ref: SliceRef):
    if ref.rows is not None:
        arr = arr[ref.rows[0]:ref.rows[0] + ref.rows[1]]
    if ref.cols is not None:
        arr = arr[:, ref.cols[0]:ref.cols[0] + ref.cols[1]]
    return arr.T if ref.transpose else arr


def _put_block(host_block) -> jax.Array:
    """One H2D transfer, landed on return.  The TPU runtime stages H2D
    copies in a fixed premapped host buffer (4 GiB by default).  Transfers
    left in flight while the host's issue loop runs ahead overflow it, and
    a strided block (a column block of a row-major operand) that does not
    fit there takes a host staging copy as large as itself.  Waiting keeps
    one transfer in flight.  Compute dispatched earlier keeps running
    meanwhile."""
    return jnp.asarray(host_block).block_until_ready()


def _spans_overlap(a: SliceRef, b: SliceRef, shape) -> bool:
    def hit(sa, sb, extent):
        lo_a, n_a = sa if sa is not None else (0, extent)
        lo_b, n_b = sb if sb is not None else (0, extent)
        return lo_a < lo_b + n_b and lo_b < lo_a + n_a

    return (a.operand == b.operand
            and hit(a.rows, b.rows, shape[0])
            and hit(a.cols, b.cols, shape[1] if len(shape) > 1 else 1))


class ScheduleExecutor:
    """Executes a :class:`Schedule` against host arrays with real JAX ops.

    One interpreter for every host-driven kernel (GEMM, attention, SYRK, the
    hand-rolled benchmark baselines): H2D slices the typed
    :class:`SliceRef` payload into a parity buffer, COMPUTE dispatches the
    :class:`BlockRef` payload through the handler registry, D2H writes a
    parity buffer back into the destination slice (or dispatches a finalize
    handler).  Every run first compiles (or fetches from the per-schedule
    cache) an :class:`~repro.core.exec_plan.ExecutablePlan` — pre-resolved
    handlers, engine queues, dependency edges — so repeated runs skip all
    per-op string/dict work.

    ``mode`` selects the run loop (DESIGN.md §13):

      * ``"issue_order"`` (default) — the serial interpreter: ops run in
        global issue order on the calling thread.  Issue order + data deps
        realize the event program (it is a proven linear extension of the
        dependency order); real overlap is whatever XLA's async dispatch
        gives us.  This path is the differential oracle the concurrent
        mode is asserted bitwise-identical against, and the fallback
        whenever ``faults=`` is armed (fault injection is not ported yet).
      * ``"concurrent"`` — the event-driven runner: one worker thread per
        engine (H2D copy, D2H copy, one kernel engine per stream — the
        same engine split the simulator models) consumes its per-engine
        FIFO queue and blocks on ``threading.Event``s mirroring the
        schedule's event program, so host wall-clock genuinely overlaps
        transfers and compute.  Deadlock-free by construction: issue order
        is a linear extension of the dependency order, and each engine
        walks its queue in issue order, so the earliest unfinished op's
        predecessors are always completable.  ``last_completion_order``
        records the order ops finished (itself a linear extension — the
        conformance tests pin it).

    ``async_writeback=True`` is the double-buffered mode mirroring the event
    program on real hardware: a D2H only *dispatches* (the device block stays
    in flight) and materializes when its parity buffer is about to be
    overwritten — i.e. the host blocks on block ``idx``'s compute only after
    block ``idx+1``'s transfers were issued, exactly the paper's overlap.
    (Concurrent mode instead lands each D2H synchronously *on the D2H
    worker* — blocking an engine thread, not the pipeline, which is what a
    real copy engine does.)

    ``record_spans=True`` timestamps every op into ``last_spans`` as
    ``(tag, stream, start_s, end_s)`` — the same span shape the simulator
    emits, so :func:`repro.core.trace.chrome_trace` renders either source.
    In ``"issue_order"`` mode recording synchronizes each op's written
    buffers (JAX dispatch is async), so it serializes the pipeline: use it
    to *inspect* schedules, not to benchmark them.  In ``"concurrent"``
    mode each engine worker stamps its own ops against one shared
    ``perf_counter`` base and only synchronizes the buffers *it* wrote, so
    recording does not serialize the pipeline — spans feed
    ``TraceAnalysis.from_spans`` (wall-clock mode).  Residual skew remains:
    a span's end is when the op's outputs were observed ready on its engine
    thread, which can trail the device-side completion by the worker's
    scheduling latency, and H2D/D2H spans include host slice/copy time the
    simulator models as pure bus time.  Cross-engine ordering of recorded
    spans is therefore reliable only through the event edges, not through
    raw timestamp comparison — which is exactly the tolerance
    ``TraceAnalysis.from_spans`` applies.

    ``last_h2d_bytes``/``last_d2h_bytes`` count the bytes of the transfer
    ops the executor actually performed in the most recent :meth:`run` —
    the ground truth the simulator's modeled byte counts are asserted
    against (a cache-hit step has no H2D op, so skipped transfers are
    counted by neither).  Under fault injection these counters keep their
    meaning (nominal bytes, once per op, always reconciling with
    ``schedule_stats``); the *extra* traffic recovery caused is accounted
    separately in ``last_fault_stats["replayed_h2d_bytes"]``.

    ``faults=``/``policy=`` arm deterministic fault injection
    (DESIGN.md §12): a :class:`~repro.fault.FaultPlan` (or a prepared
    injector, or a ``sched -> plan`` callable) is consulted once per op
    *attempt*; transient transfer errors are retried with the policy's
    exponential backoff, compute faults are recovered by block-granular
    replay from the written buffer's last host-consistent point, and
    ``device_lost``/``oom`` raise immediately for the callers that own
    those recoveries (hybrid rebalance, degrade ladders).  ``faults=None``
    (the default) costs one branch per op.

    When the process :class:`~repro.obs.Observability` is enabled, every
    run publishes its aggregates (bytes, ops, flops, wall seconds,
    block-cache counters, per-stream busy time when recording) as
    ``repro_executor_*`` metrics, and recorded spans are absorbed into the
    active tracer as one lane-group (``trace_group`` names it; the hybrid
    co-scheduler passes the device name).  Disabled observability costs one
    branch per run.
    """

    MODES = ("issue_order", "concurrent")

    def __init__(self,
                 handlers: Optional[Dict[str, HandlerFn]] = None,
                 async_writeback: bool = True,
                 record_spans: bool = False,
                 trace_group: Optional[str] = None,
                 mode: str = "issue_order"):
        if mode not in self.MODES:
            raise ValueError(
                f"unknown executor mode {mode!r}; expected one of "
                f"{self.MODES}")
        self.handlers = dict(handlers) if handlers else {}
        self.async_writeback = async_writeback
        self.record_spans = record_spans
        self.mode = mode
        # lane-group name used when recorded spans are absorbed into an
        # active obs tracer (the hybrid co-scheduler names executors after
        # their device); None derives one from the schedule's kernel meta
        self.trace_group = trace_group
        self.last_spans: List[Tuple[str, int, float, float]] = []
        # issue indices in the order ops completed in the most recent run
        # (serial: identical to issue order; concurrent: a linear extension
        # of the dependency order — the conformance tests pin it)
        self.last_completion_order: List[int] = []
        self.last_h2d_bytes = 0
        self.last_d2h_bytes = 0
        self.last_wall_seconds = 0.0
        # fault-injection accounting for the most recent run (None when the
        # run was fault-free): injected / retries / replayed_ops /
        # replayed_h2d_bytes / backoff_seconds / recovered_{retry,replay}
        self.last_fault_stats: Optional[Dict[str, float]] = None

    def _handler(self, ref: BlockRef) -> HandlerFn:
        fn = self.handlers.get(ref.kernel) or _OP_HANDLERS.get(ref.kernel)
        if fn is None:
            raise KeyError(
                f"no op handler registered for kernel {ref.kernel!r}; "
                f"known: {sorted(set(_OP_HANDLERS) | set(self.handlers))}"
            )
        return fn

    def run(self,
            sched: Schedule,
            operands: Dict[str, Any],
            outputs: Dict[str, np.ndarray],
            ctx: Optional[Dict[str, Any]] = None,
            faults=None,
            policy=None) -> ExecState:
        st = ExecState(bufs={}, operands=operands, outputs=outputs,
                       ctx=ctx or {}, scratch={})
        # compile (or fetch the cached) ExecutablePlan: pre-resolved
        # handlers + engine queues + dependency edges.  A hand-built
        # schedule with a broken event graph can still run serially (the
        # serial loop never consults the edges), so compile failures only
        # propagate when the concurrent runner actually needs the plan.
        try:
            plan: Optional[ExecutablePlan] = compile_executable(sched)
        except ScheduleError:
            if self.mode == "concurrent":
                raise
            plan = None
        resolved = plan.resolved if plan is not None else None

        def handler_for(i: int, ref: BlockRef) -> HandlerFn:
            if self.handlers:
                fn = self.handlers.get(ref.kernel)
                if fn is not None:
                    return fn
            if resolved is not None:
                fn = resolved[i]
                if fn is not None:
                    return fn
            return self._handler(ref)

        # parity-buffer key -> (in-flight device block, destination slice)
        pending: Dict[Tuple[str, Hashable], Tuple[Any, SliceRef]] = {}

        def flush(key) -> None:
            # read-then-delete, NOT pop-then-write: if materializing the
            # block or the host store raises, the entry must stay in flight
            # so a retry re-lands it — popping first made later finalize
            # handlers silently observe stale host state
            blk, ref = pending[key]
            arr = np.asarray(blk)
            dest = st.outputs[ref.operand]
            if ref.transpose:
                arr = arr.T
            rs, rn = ref.rows if ref.rows is not None else (0, dest.shape[0])
            if dest.ndim > 1:
                cs, cn = ref.cols if ref.cols is not None \
                    else (0, dest.shape[1])
                dest[rs:rs + rn, cs:cs + cn] = arr
            else:
                dest[rs:rs + rn] = arr
            del pending[key]

        # ---- fault injection state (armed only when a plan is passed) ----
        fi = faults
        fstats: Optional[Dict[str, float]] = None
        if fi is not None:
            from repro.fault.errors import (ComputeFault, DeviceLostError,
                                            OomError, TransferError)
            from repro.fault.plan import REPLAYABLE_KERNELS
            if callable(fi) and not hasattr(fi, "check"):
                fi = fi(sched)            # a sched -> plan factory
            if hasattr(fi, "injector"):   # a FaultPlan: fresh one-shot state
                fi = fi.injector()
            if policy is None:
                from repro.fault.policy import FaultPolicy
                policy = FaultPolicy()
            fstats = {"injected": 0, "retries": 0, "replayed_ops": 0,
                      "replayed_h2d_bytes": 0, "backoff_seconds": 0.0,
                      "recovered_retry": 0, "recovered_replay": 0}
            # per-buffer recovery state: the value at the last
            # host-consistent point (H2D load / write-back dispatch) and
            # the compute chain applied since — buffer reassignment makes
            # these O(1) reference snapshots, not copies
            clean: Dict[Tuple[str, Hashable], Any] = {}
            chains: Dict[Tuple[str, Hashable], List] = {}

        def flush_retrying(key) -> None:
            # a write-back materialization can itself fail transiently;
            # under a policy it gets the same retry treatment as an
            # injected transfer fault (the fixed flush keeps the entry
            # in flight across attempts)
            if fi is None:
                flush(key)
                return
            attempt = 0
            while True:
                try:
                    flush(key)
                except TransferError:
                    attempt += 1
                    if attempt > policy.max_retries:
                        raise
                    fstats["retries"] += 1
                    delay = policy.backoff(attempt)
                    fstats["backoff_seconds"] += delay
                    policy.sleep(delay)
                    continue
                if attempt:
                    fstats["recovered_retry"] += 1
                return

        def exec_h2d(op, ref) -> None:
            self.last_h2d_bytes += op.bytes
            key = op.buffers_written[0]
            if key in pending:           # schedule's wC wait point: the
                flush_retrying(key)      # previous occupant lands now
            if ref.operand in st.outputs:  # host coherence on re-read
                src_shape = st.outputs[ref.operand].shape
                for k in [k for k, (_, pref) in pending.items()
                          if _spans_overlap(ref, pref, src_shape)]:
                    flush_retrying(k)
            st.bufs[key] = _put_block(_take(st.host(ref.operand), ref))
            if fi is not None:   # fresh load = host-consistent snapshot
                clean[key] = st.bufs[key]
                chains[key] = []

        def exec_compute(i, op, ref) -> None:
            handler_for(i, ref)(st, op, ref)

        def exec_d2h(i, op, ref) -> None:
            self.last_d2h_bytes += op.bytes
            if isinstance(ref, BlockRef):  # finalize handler
                for key in list(pending):  # finalizers read/patch host
                    flush_retrying(key)    # state: land in-flight blocks
                handler_for(i, ref)(st, op, ref)
                return
            key = op.buffers_read[0]
            if key in pending:
                flush_retrying(key)
            pending[key] = (st.bufs[key], ref)
            if fi is not None:
                # write-back boundary: compute replay restores from here,
                # references to the earlier chain are released
                clean[key] = st.bufs[key]
                chains[key] = []
            if not self.async_writeback:
                flush_retrying(key)

        def run_clean(i, op, ref) -> None:
            if op.kind == OpKind.H2D:
                exec_h2d(op, ref)
            elif op.kind == OpKind.COMPUTE:
                exec_compute(i, op, ref)
            elif op.kind == OpKind.D2H:
                exec_d2h(i, op, ref)

        def run_faulted(i, op, ref) -> None:
            attempt = 0              # faulted attempts of this op so far
            while True:
                cls = fi.check(i, op)
                if cls is None:
                    run_clean(i, op, ref)
                    if op.kind == OpKind.COMPUTE:
                        # successful compute: extend the redo chains of the
                        # buffers it wrote, snapshotting its read buffers
                        # so a later replay re-binds the exact inputs
                        reads = {k: st.bufs[k] for k in op.buffers_read
                                 if k in st.bufs}
                        for k in op.buffers_written:
                            if k in chains:
                                chains[k].append((op, ref, reads))
                    if attempt:
                        fstats["recovered_replay"
                               if op.kind == OpKind.COMPUTE
                               else "recovered_retry"] += 1
                    return
                fstats["injected"] += 1
                obs.instant(f"fault:{cls}", op=i, tag=op.tag,
                            stream=op.stream)
                if cls == "device_lost":
                    raise DeviceLostError(
                        f"injected device_lost at op {i} ({op.tag})")
                if cls == "oom":
                    raise OomError(f"injected oom at op {i} ({op.tag})")
                attempt += 1
                if cls == "h2d_error":
                    if op.kind == OpKind.COMPUTE:
                        raise ValueError(
                            f"fault plan injects h2d_error into compute "
                            f"op {i} ({op.tag})")
                    if attempt > policy.max_retries:
                        raise TransferError(
                            f"op {i} ({op.tag}): transfer failed after "
                            f"{policy.max_retries} retries")
                    if op.kind == OpKind.H2D:
                        # the failed attempt still moved the bytes: extra
                        # traffic is recovery's, nominal counters are not
                        fstats["replayed_h2d_bytes"] += op.bytes
                    fstats["retries"] += 1
                    delay = policy.backoff(attempt)
                    fstats["backoff_seconds"] += delay
                    policy.sleep(delay)
                    continue
                # compute_nan: the op runs but its output is corrupt;
                # recover by block-granular replay — restore the written
                # buffer's last host-consistent value and redo the chain
                key = op.buffers_written[0] if op.buffers_written else None
                self._handler(ref)(st, op, ref)
                for k in op.buffers_written:
                    if k in st.bufs:
                        st.bufs[k] = jnp.full_like(st.bufs[k], jnp.nan)
                replayable = (
                    op.kind == OpKind.COMPUTE and key is not None
                    and len(op.buffers_written) == 1 and key in clean
                    and getattr(ref, "kernel", None) in REPLAYABLE_KERNELS)
                if not replayable or attempt > policy.max_retries:
                    raise ComputeFault(
                        f"op {i} ({op.tag}): compute fault "
                        + ("retries exhausted" if replayable
                           else "not replayable"))
                st.bufs[key] = clean[key]
                for cop, cref, creads in chains[key]:
                    saved = {}
                    for rk, rv in creads.items():
                        if rk in cop.buffers_written:
                            continue
                        saved[rk] = st.bufs.get(rk)
                        st.bufs[rk] = rv
                    self._handler(cref)(st, cop, cref)
                    for rk, rv in saved.items():
                        if rv is None:
                            st.bufs.pop(rk, None)
                        else:
                            st.bufs[rk] = rv
                fstats["replayed_ops"] += len(chains[key]) + 1
                # loop: the next attempt re-consults the injector and
                # either faults again (times > 1) or dispatches cleanly

        # stale spans from a prior run must never leak into a new trace,
        # so the reset is unconditional (not gated on record_spans)
        self.last_spans = []
        self.last_completion_order = []
        self.last_h2d_bytes = 0
        self.last_d2h_bytes = 0
        self.last_fault_stats = None
        obs = get_observability()
        tracer = obs.tracer
        # an active tracer forces span recording: a trace is inspection
        # mode by definition, and a silent executor would leave a hole in
        # the timeline
        trace = self.record_spans or tracer is not None
        run_offset = tracer.now() if tracer is not None else 0.0
        t_run0 = time.perf_counter()
        if trace:
            t_base = t_run0

        # fault injection is not ported to the worker-thread runner yet:
        # an armed plan falls back to the serial oracle (same results,
        # same recovery semantics, no overlap)
        concurrent = self.mode == "concurrent" and fi is None

        try:
            if concurrent:
                self._run_concurrent(plan, st, trace, t_run0)
            else:
                for i, op in enumerate(sched.ops):
                    ref = op.payload
                    if trace:
                        t0 = time.perf_counter() - t_base
                    if fi is None:
                        run_clean(i, op, ref)
                    else:
                        run_faulted(i, op, ref)
                    if trace:
                        sync = [st.bufs[k] for k in op.buffers_written
                                if k in st.bufs]
                        if op.kind == OpKind.COMPUTE \
                                and "carry" in st.scratch:
                            sync.append(st.scratch["carry"])
                        jax.block_until_ready(sync)
                        self.last_spans.append(
                            (op.tag, op.stream, t0,
                             time.perf_counter() - t_base))
                    self.last_completion_order.append(i)
                for key in list(pending):
                    flush_retrying(key)
        finally:
            if fi is not None:
                # publish even when an unrecoverable fault propagates:
                # the caller's degrade/rebalance handler still needs the
                # injection record
                self.last_fault_stats = fstats
                obs.record_fault_run(sched.meta.get("kernel", "run"),
                                     fstats)
        self.last_wall_seconds = time.perf_counter() - t_run0
        if obs.metrics.enabled:
            obs.record_executor_run(
                sched, self.last_wall_seconds,
                self.last_h2d_bytes, self.last_d2h_bytes,
                spans=self.last_spans if trace else None)
        if tracer is not None and trace and self.last_spans:
            tracer.add_flat_spans(
                self.trace_group
                or f"executor:{sched.meta.get('kernel', 'run')}",
                self.last_spans, offset=run_offset,
                reuse=sched.reuse or None)
        return st

    def _run_concurrent(self, plan: ExecutablePlan, st: ExecState,
                        trace: bool, t_base: float) -> None:
        """Event-driven run loop: one worker thread per engine.

        Each worker walks its engine's FIFO queue in issue order; before
        dispatching op ``i`` it waits the ``threading.Event`` of every
        cross-engine predecessor in ``plan.preds[i]`` (same-engine edges
        are implied by the queue walk) and sets ``done[i]`` after the op
        completed *on this engine* — H2D after the block landed on the
        device, D2H after it landed in host memory, COMPUTE after the
        handler dispatched.  This mirrors the simulator's event program:
        engines block, the host never does.

        Failure: the first raising worker records its error, sets ``stop``
        and force-sets every ``done`` event so blocked peers wake, observe
        ``stop`` (set strictly before the force-set, so any waiter woken
        by it reads stop=True) and drain without dispatching further ops.
        The lowest-issue-index error is re-raised on the calling thread.
        """
        ops = plan.ops
        done = [threading.Event() for _ in range(plan.n_ops)]
        stop = threading.Event()
        errors: List[Tuple[int, BaseException]] = []
        err_lock = threading.Lock()
        completion: List[int] = []   # list.append is atomic under the GIL
        n_eng = len(plan.queues)
        eng_h2d = [0] * n_eng
        eng_d2h = [0] * n_eng
        eng_spans: List[List[Tuple[str, int, float, float]]] = \
            [[] for _ in range(n_eng)]
        handlers = self.handlers
        resolved = plan.resolved

        def handler_at(i: int, ref: BlockRef) -> HandlerFn:
            if handlers:
                fn = handlers.get(ref.kernel)
                if fn is not None:
                    return fn
            fn = resolved[i]
            return fn if fn is not None else self._handler(ref)

        def land(blk: Any, ref: SliceRef) -> None:
            # synchronous D2H: np.asarray blocks this worker (the "copy
            # engine") until the device value is ready, then stores it —
            # the concurrent analogue of the serial pending-flush
            arr = np.asarray(blk)
            dest = st.outputs[ref.operand]
            if ref.transpose:
                arr = arr.T
            rs, rn = ref.rows if ref.rows is not None else (0, dest.shape[0])
            if dest.ndim > 1:
                cs, cn = ref.cols if ref.cols is not None \
                    else (0, dest.shape[1])
                dest[rs:rs + rn, cs:cs + cn] = arr
            else:
                dest[rs:rs + rn] = arr

        def dispatch(e: int, i: int, op: Op) -> None:
            ref = op.payload
            kind = plan.kinds[i]
            if kind == _xplan.KIND_H2D:
                eng_h2d[e] += op.bytes
                st.bufs[op.buffers_written[0]] = _put_block(
                    _take(st.host(ref.operand), ref))
            elif kind == _xplan.KIND_COMPUTE:
                handler_at(i, ref)(st, op, ref)
            else:  # D2H
                eng_d2h[e] += op.bytes
                if isinstance(ref, BlockRef):   # finalize handler
                    handler_at(i, ref)(st, op, ref)
                else:
                    land(st.bufs[op.buffers_read[0]], ref)

        def worker(e: int) -> None:
            spans = eng_spans[e]
            for i in plan.queues[e]:
                for p in plan.preds[i]:
                    done[p].wait()
                if stop.is_set():
                    return
                op = ops[i]
                if trace:
                    t0 = time.perf_counter() - t_base
                try:
                    dispatch(e, i, op)
                    if trace:
                        # per-engine clock: synchronize only the buffers
                        # THIS op wrote — other engines keep running
                        sync = [st.bufs[k] for k in op.buffers_written
                                if k in st.bufs]
                        if plan.kinds[i] == _xplan.KIND_COMPUTE \
                                and "carry" in st.scratch:
                            sync.append(st.scratch["carry"])
                        jax.block_until_ready(sync)
                except BaseException as exc:
                    with err_lock:
                        errors.append((i, exc))
                    stop.set()
                    for d in done:
                        d.set()
                    return
                if trace:
                    spans.append((op.tag, op.stream, t0,
                                  time.perf_counter() - t_base))
                completion.append(i)
                done[i].set()

        threads = [
            threading.Thread(target=worker, args=(e,), daemon=True,
                             name=f"exec-{plan.engines[e]}")
            for e in range(n_eng) if plan.queues[e]]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        self.last_h2d_bytes += sum(eng_h2d)
        self.last_d2h_bytes += sum(eng_d2h)
        self.last_completion_order = completion
        if trace:
            merged = [sp for spans in eng_spans for sp in spans]
            merged.sort(key=lambda s: (s[2], s[3]))
            self.last_spans = merged
        if errors:
            errors.sort(key=lambda ie: ie[0])
            raise errors[0][1]


@register_op_handler("noop")
def _noop_handler(st: ExecState, op: Op, ref: BlockRef) -> None:
    """Buffer-release marker ("keep" write-back mode): nothing to execute."""


@register_op_handler("dgemm")
def _dgemm_handler(st: ExecState, op: Op, ref: BlockRef) -> None:
    """C_p = alpha * lhs @ rhs + beta * C_p on parity buffers (GEMM + SYRK:
    buffers_read = (lhs, rhs), buffers_written[0] = accumulator)."""
    ckey = op.buffers_written[0]
    st.bufs[ckey] = _block_dgemm(
        st.bufs[op.buffers_read[0]], st.bufs[op.buffers_read[1]],
        st.bufs[ckey],
        jnp.asarray(st.ctx.get("alpha", 1.0), dtype=jnp.float32),
        jnp.asarray(st.ctx.get("beta", 0.0), dtype=jnp.float32),
    )


# ---------------------------------------------------------------------------
# Factorization panel ops (the paper's §VII kernels, DESIGN.md §8): in-core
# panel factor / solve handlers the factor pipeline interleaves with the
# streamed dgemm trailing update.  Panels are resident parity buffers shaped
# (m, pw); the panel width is recovered from the buffer itself.
# ---------------------------------------------------------------------------
def getrf_panel(buf: np.ndarray) -> np.ndarray:
    """Unblocked right-looking LU with partial pivoting on an (m, pw) panel,
    in place.  Returns LAPACK-style local pivot rows ``piv`` (column ``j``
    swapped panel rows ``j`` and ``piv[j]``); L's unit diagonal is implicit,
    multipliers live below it, U on and above."""
    m, pw = buf.shape
    piv = np.arange(pw)
    for j in range(pw):
        p = j + int(np.argmax(np.abs(buf[j:, j])))
        piv[j] = p
        if p != j:
            buf[[j, p], :] = buf[[p, j], :]
        d = buf[j, j]
        if d != 0:
            buf[j + 1:, j] /= d
            if j + 1 < pw:
                buf[j + 1:, j + 1:] -= np.outer(buf[j + 1:, j],
                                                buf[j, j + 1:])
    return piv


def apply_panel_pivots(A: np.ndarray, piv: np.ndarray, k0: int, k1: int,
                       perm: np.ndarray) -> None:
    """Replay a panel's local pivots on the host matrix columns *outside*
    the panel (left of it: already-written L; right of it: the trailing
    columns), accumulating the global row permutation — the one definition
    of the swap-replay invariant, shared by the pipeline's ``lu_writeback``
    handler and the per-panel fallback loop."""
    for j, p in enumerate(piv):
        if p != j:
            r1, r2 = k0 + j, k0 + int(p)
            A[[r1, r2], :k0] = A[[r2, r1], :k0]
            A[[r1, r2], k1:] = A[[r2, r1], k1:]
            perm[[r1, r2]] = perm[[r2, r1]]


@register_op_handler("panel_chol")
def _panel_chol_handler(st: ExecState, op: Op, ref: BlockRef) -> None:
    """POTRF: factor the resident panel's diagonal block in-core (the upper
    triangle comes back zeroed, as np.linalg.cholesky leaves it)."""
    key = op.buffers_written[0]
    buf = np.array(st.bufs[key])
    d = buf.shape[1]
    buf[:d, :d] = np.linalg.cholesky(buf[:d, :d])
    st.bufs[key] = jnp.asarray(buf)


@register_op_handler("panel_trsm")
def _panel_trsm_handler(st: ExecState, op: Op, ref: BlockRef) -> None:
    """Cholesky panel solve: sub-diagonal rows <- rows @ inv(Lkk)^T, in the
    resident panel buffer."""
    key = op.buffers_written[0]
    buf = np.array(st.bufs[key])
    d = buf.shape[1]
    buf[d:, :] = np.linalg.solve(buf[:d, :d], buf[d:, :].T).T
    st.bufs[key] = jnp.asarray(buf)


@register_op_handler("panel_lu")
def _panel_lu_handler(st: ExecState, op: Op, ref: BlockRef) -> None:
    """GETRF: partial-pivot LU of the resident panel; the local pivot rows
    park in scratch for the write-back's row-swap replay."""
    key = op.buffers_written[0]
    buf = np.array(st.bufs[key])
    st.scratch[("piv", ref.index)] = getrf_panel(buf)
    st.bufs[key] = jnp.asarray(buf)


@register_op_handler("lu_trsm")
def _lu_trsm_handler(st: ExecState, op: Op, ref: BlockRef) -> None:
    """LU row-panel solve: U[k, k+1:] <- inv(unit-lower Lkk) @ U[k, k+1:],
    with Lkk read from the resident factored panel."""
    pkey, ukey = op.buffers_read
    pnl = np.asarray(st.bufs[pkey])
    urow = np.asarray(st.bufs[ukey])
    d = pnl.shape[1]
    lkk = np.tril(pnl[:d, :d], -1) + np.eye(d, dtype=pnl.dtype)
    st.bufs[ukey] = jnp.asarray(
        np.linalg.solve(lkk, urow).astype(urow.dtype))


@register_op_handler("lu_writeback")
def _lu_writeback_handler(st: ExecState, op: Op, ref: BlockRef) -> None:
    """LU panel write-back with row-swap replay: land the factored panel and
    apply its pivots to the host columns *outside* the panel (left of it:
    already-written L; right of it: the not-yet-updated trailing columns),
    accumulating the global permutation in scratch."""
    A = st.outputs["A"]
    n = A.shape[0]
    buf = np.asarray(st.bufs[op.buffers_read[0]])
    pw = buf.shape[1]
    k0 = n - buf.shape[0]
    k1 = k0 + pw
    piv = st.scratch.pop(("piv", ref.index))
    perm = st.scratch.setdefault("perm", np.arange(n))
    apply_panel_pivots(A, piv, k0, k1, perm)
    A[k0:, k0:k1] = buf.astype(A.dtype)


@register_runtime("HBM")
class HostOocRuntime(OocRuntime):
    """Host-driven block streaming: builds (or accepts) a pipeline schedule
    and hands it to the shared :class:`ScheduleExecutor` — no private
    interpreter loop.  On real hardware JAX's async dispatch overlaps the
    transfer of block ``idx+1`` with the DGEMM of block ``idx`` exactly as
    the event program orders them; on CPU the schedule executes with
    identical semantics (ordering + results), which is what tests assert.
    """

    def __init__(self, device: Optional[Device] = None,
                 executor: Optional[ScheduleExecutor] = None):
        self.device = device or Device("HBM", 0, 16 * 2**30)
        self.executor = executor or ScheduleExecutor()

    def gemm(self, A, B, C, alpha, beta, part: GemmPartition,
             nstreams: int = 2, nbuf: int = 2,
             schedule: Optional[Schedule] = None,
             faults=None, policy=None):
        sched = schedule or plib.build_gemm_schedule(
            part, nstreams=nstreams, nbuf=nbuf
        )
        out = np.array(C, copy=True)
        self.executor.run(
            sched,
            operands={"A": np.asarray(A), "B": np.asarray(B)},
            outputs={"C": out},
            ctx={"alpha": alpha, "beta": beta},
            faults=faults, policy=policy,
        )
        return out

    def syrk(self, P, C, alpha, beta, part: GemmPartition,
             nstreams: int = 2, nbuf: int = 2,
             schedule: Optional[Schedule] = None,
             faults=None, policy=None):
        """C = alpha * P @ P^T + beta * C via the SYRK pipeline spec (the
        Cholesky trailing update as a first-class schedule)."""
        sched = schedule or plib.build_syrk_schedule(
            part, nstreams=nstreams, nbuf=nbuf
        )
        out = np.array(C, copy=True)
        self.executor.run(
            sched,
            operands={"P": np.asarray(P)},
            outputs={"C": out},
            ctx={"alpha": alpha, "beta": beta},
            faults=faults, policy=policy,
        )
        return out


@register_runtime("VMEM")
class VmemOocRuntime(OocRuntime):
    """HBM->VMEM tier: delegates to the Pallas block-matmul kernel, which IS
    the paper's pipeline compiled into the chip (Mosaic double-buffers the
    A/B/C tile DMAs across grid steps)."""

    def __init__(self, device: Optional[Device] = None,
                 interpret: bool = False):
        from repro.kernels.block_matmul import VMEM_SCOPED_LIMIT
        from repro.kernels.ops import check_interpret

        self.device = device or Device("VMEM", 0, VMEM_SCOPED_LIMIT)
        # off a TPU the caller must choose Pallas interpret mode explicitly
        self.interpret = check_interpret(interpret)

    def gemm(self, A, B, C, alpha, beta, part: GemmPartition,
             block: Optional[Tuple[int, int, int]] = None, **kw):
        from repro.kernels import ops as kops

        bm = min(part.bm, 512)
        bn = min(part.bn, 512)
        bk = min(part.K, 512)
        if block is not None:
            bm, bn, bk = block
        return kops.block_matmul(
            jnp.asarray(A), jnp.asarray(B), jnp.asarray(C),
            alpha=alpha, beta=beta, block=(bm, bn, bk),
            interpret=self.interpret,
        )


@register_runtime("MESH")
class MeshOocRuntime(OocRuntime):
    """Mesh tier: SUMMA ring over ICI.

    The operands are sharded across a 1-D submesh (A by row blocks, B by
    column blocks, C by row blocks); each device streams the remote B blocks
    through a ping-pong buffer with ``ppermute`` while the MXU consumes the
    current block — the paper's 2-stream overlap where the "PCIe link" is ICI
    and the "host memory" is the neighbours' HBM.
    """

    def __init__(self, mesh: Mesh, axis: str = "model",
                 device: Optional[Device] = None):
        self.mesh = mesh
        self.axis = axis
        self.device = device or Device("MESH", 0, 16 * 2**30)

    @classmethod
    def from_device(cls, device: Device, *, mesh: Optional[Mesh] = None,
                    **kw) -> "MeshOocRuntime":
        if mesh is None:
            raise ValueError("MESH runtime needs a jax Mesh")
        return cls(mesh, device=device, **kw)

    def gemm(self, A, B, C, alpha, beta, part=None, overlap: bool = True, **kw):
        Pn = self.mesh.shape[self.axis]
        M, _ = A.shape
        _, N = B.shape
        if M % Pn or N % Pn:
            raise ValueError(f"SUMMA needs M,N divisible by mesh axis {Pn}")
        args = [jax.device_put(x, s)
                for x, s in zip((A, B, C), self.shardings())]
        return self.program(overlap)(*args, jnp.float32(alpha),
                                     jnp.float32(beta))

    def shardings(self) -> Tuple[NamedSharding, ...]:
        """Shardings of ``(A, B, C)``: A and C by row blocks, B by column
        blocks over the mesh axis."""
        return _summa_shardings(self.mesh, self.axis)

    def program(self, overlap: bool = True):
        """The jitted SUMMA ring ``(A, B, C, alpha, beta) -> C`` over this
        runtime's mesh axis (one per mesh/axis/overlap, so repeated calls
        reuse its compilation)."""
        return _summa_program(self.mesh, self.axis, overlap)


def _summa_shardings(mesh: Mesh, axis: str) -> Tuple[NamedSharding, ...]:
    return tuple(NamedSharding(mesh, s)
                 for s in (P(axis, None), P(None, axis), P(axis, None)))


@functools.lru_cache(maxsize=16)
def _summa_program(mesh: Mesh, axis: str, overlap: bool):
    Pn = mesh.shape[axis]

    def ring_body(a_blk, b_blk, c_blk, alpha, beta):
        # a_blk: (M/P, K)  b_blk: (K, N/P)  c_blk: (M/P, N)
        n_blk = b_blk.shape[1]
        me = jax.lax.axis_index(axis)
        perm = [(i, (i - 1) % Pn) for i in range(Pn)]

        def step(t, carry):
            b_cur, acc = carry
            # issue the permute FIRST so XLA can overlap the ICI transfer
            # of the next block with this block's matmul (ping-pong
            # buffer: b_nxt is a fresh buffer).
            b_nxt = jax.lax.ppermute(b_cur, axis, perm) if overlap else b_cur
            col = ((me + t) % Pn) * n_blk
            prod = jnp.dot(a_blk, b_cur, preferred_element_type=jnp.float32)
            old = jax.lax.dynamic_slice(acc, (0, col), (acc.shape[0], n_blk))
            upd = (alpha * prod + beta * old).astype(acc.dtype)
            acc = jax.lax.dynamic_update_slice(acc, upd, (0, col))
            if not overlap:
                b_nxt = jax.lax.ppermute(b_cur, axis, perm)
            return b_nxt, acc

        _, acc = jax.lax.fori_loop(0, Pn, step, (b_blk, c_blk))
        return acc

    shardings = _summa_shardings(mesh, axis)
    specs = tuple(s.spec for s in shardings)
    fn = jax.shard_map(ring_body, mesh=mesh, in_specs=specs + (P(), P()),
                       out_specs=specs[2])
    scalar = NamedSharding(mesh, P())
    return jax.jit(fn, in_shardings=shardings + (scalar, scalar),
                   out_shardings=shardings[2])


class RuntimeFactory:
    """``hclRuntimeFactory``: device tuple -> runtime, via the declarative
    registry populated by :func:`register_runtime`.  Extra keyword arguments
    are forwarded to the tier's ``from_device`` hook (e.g. ``devices=[...]``
    for the hybrid composite)."""

    @staticmethod
    def create(device: Device, mesh: Optional[Mesh] = None,
               **kw) -> OocRuntime:
        name = device.name.upper()
        cls = _RUNTIME_REGISTRY.get(name)
        if cls is None and name in _LAZY_RUNTIME_MODULES:
            importlib.import_module(_LAZY_RUNTIME_MODULES[name])
            cls = _RUNTIME_REGISTRY.get(name)
        if cls is None:
            raise ValueError(
                f"unknown device type {device.name!r}; registered tiers: "
                f"{RuntimeFactory.registered()}"
            )
        return cls.from_device(device, mesh=mesh, **kw)

    @staticmethod
    def registered() -> List[str]:
        """Tier names ``create`` accepts (registered + lazily importable)."""
        return sorted(set(_RUNTIME_REGISTRY) | set(_LAZY_RUNTIME_MODULES))
