"""Hand-written backend-specific OOC DGEMM implementations (no unified API).

These are the LOC denominator for claim C4 (75 % code reduction) and the
"direct" side of the abstraction-overhead benchmark (C1): each re-implements
the out-of-core pipeline for ONE memory tier, managing its own partitioning,
buffers and ordering — exactly the duplication the paper's unified interface
eliminates (its comparison points were ZZGemmOOC / XeonPhiOOC / an OpenCL
port; ours are the three TPU tiers).

What "direct" means per tier: the host path hand-derives its partition and
op ordering (no partitioner, no PipelineSpec, no event sets) but executes on
the engine's shared ScheduleExecutor — the repo keeps exactly one schedule
interpreter, so C1/C4 measure the *planning/abstraction* layers, not a
duplicated interpreter; the vmem and mesh paths are fully standalone.

All three compute C = alpha*A@B + beta*C and are cross-checked against the
oracle in the benchmark harness.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


# ===========================================================================
# 1. host-tier direct implementation (HBM streaming, manual double buffer)
# ===========================================================================
def direct_host_ooc_gemm(A, B, C, alpha, beta, budget_bytes):
    """Hand-rolled host-driven block streaming: inline partitioning and a
    hand-built serial op list — no partitioner, no PipelineSpec, no event
    sets.  Execution dispatches through the shared ScheduleExecutor (the one
    schedule interpreter in the engine); what stays "direct" here is
    everything the library would otherwise derive."""
    from repro.core.runtime import ScheduleExecutor
    from repro.core.streams import (BlockRef, Device, Op, OpKind, Schedule,
                                    SliceRef, StreamFactory)

    A = np.asarray(A)
    B = np.asarray(B)
    out = np.array(C, copy=True)
    M, K = A.shape
    _, N = B.shape
    bpe = A.dtype.itemsize

    # inline partitioning: shrink block dims until 2 A-slices + B-slice +
    # 2 C-blocks fit the budget, keeping alignment by hand
    bm, bn = M, N
    def ws(bm, bn):
        return (2 * bm * K + K * bn + 2 * bm * bn) * bpe
    while ws(bm, bn) > budget_bytes:
        if bm >= bn and bm > 8:
            bm = max(8, (bm // 2 + 7) // 8 * 8)
        elif bn > 128:
            bn = max(128, (bn // 2 + 127) // 128 * 128)
        elif bm > 8:
            bm = max(8, (bm // 2 + 7) // 8 * 8)
        else:
            raise ValueError("cannot fit budget")
    h = math.ceil(M / bm)
    w = math.ceil(N / bn)

    # hand-built single-stream op list: ping-pong parities, B reused per
    # column, no events (issue order is the only dependency structure)
    dev = Device("HBM", 0, budget_bytes)
    sched = Schedule(dev, StreamFactory.create(dev, 1))
    idx = 0
    for j in range(w):
        cs, cn = j * bn, min(bn, N - j * bn)
        sched.issue(Op(kind=OpKind.H2D, tag=f"S(b[{j}])", stream=0,
                       buffers_written=(("B", j % 2),), bytes=K * cn * bpe,
                       payload=SliceRef("B", j, cols=(cs, cn))))
        for i in range(h):
            rs, rn = i * bm, min(bm, M - i * bm)
            p = idx % 2
            sched.issue(Op(kind=OpKind.H2D, tag=f"S(a[{idx}])", stream=0,
                           buffers_written=(("A", p),), bytes=rn * K * bpe,
                           payload=SliceRef("A", idx, rows=(rs, rn))))
            sched.issue(Op(kind=OpKind.H2D, tag=f"S(c[{idx}])", stream=0,
                           buffers_written=(("C", p),), bytes=rn * cn * bpe,
                           payload=SliceRef("C", idx, rows=(rs, rn),
                                            cols=(cs, cn))))
            sched.issue(Op(kind=OpKind.COMPUTE, tag=f"DGEMM[{idx}]", stream=0,
                           buffers_read=(("A", p), ("B", j % 2)),
                           buffers_written=(("C", p),),
                           flops=2 * rn * cn * K,
                           payload=BlockRef("dgemm", idx)))
            sched.issue(Op(kind=OpKind.D2H, tag=f"R(c[{idx}])", stream=0,
                           buffers_read=(("C", p),), bytes=rn * cn * bpe,
                           payload=SliceRef("C", idx, rows=(rs, rn),
                                            cols=(cs, cn))))
            idx += 1
    ScheduleExecutor(async_writeback=True).run(
        sched, operands={"A": A, "B": B}, outputs={"C": out},
        ctx={"alpha": alpha, "beta": beta})
    return out


# ===========================================================================
# 2. vmem-tier direct implementation (hand-written Pallas pipeline)
# ===========================================================================
def direct_vmem_ooc_gemm(A, B, C, alpha, beta, block=(256, 256, 256),
                         interpret=True):
    """Standalone Pallas kernel written from scratch (no kernels/ reuse):
    its own grid, BlockSpecs, scratch and padding logic."""
    import functools
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bm, bn, bk = block
    M, K = A.shape
    _, N = B.shape

    def kernel(a_ref, b_ref, c_ref, o_ref, acc_ref, *, ks):
        k = pl.program_id(2)

        @pl.when(k == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                                preferred_element_type=jnp.float32)

        @pl.when(k == ks - 1)
        def _():
            o_ref[...] = (alpha * acc_ref[...]
                          + beta * c_ref[...].astype(jnp.float32)
                          ).astype(o_ref.dtype)

    pad = lambda x, m0, m1: jnp.pad(
        x, ((0, (-x.shape[0]) % m0), (0, (-x.shape[1]) % m1)))
    Ap, Bp, Cp = pad(A, bm, bk), pad(B, bk, bn), pad(C, bm, bn)
    Mp, Kp = Ap.shape
    Np = Bp.shape[1]
    grid = (Mp // bm, Np // bn, Kp // bk)
    out = pl.pallas_call(
        functools.partial(kernel, ks=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), C.dtype),
        scratch_shapes=[pltpu.MemorySpace.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(Ap, Bp, Cp)
    return out[:M, :N]


# ===========================================================================
# 3. mesh-tier direct implementation (hand-written SUMMA ring)
# ===========================================================================
def direct_mesh_ooc_gemm(A, B, C, alpha, beta, mesh, axis="model"):
    """Standalone shard_map SUMMA with its own ring bookkeeping."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    Pn = mesh.shape[axis]
    M, K = A.shape
    _, N = B.shape
    assert M % Pn == 0 and N % Pn == 0
    nb = N // Pn
    al = jnp.float32(alpha)
    be = jnp.float32(beta)

    def body(a, b, c):
        me = jax.lax.axis_index(axis)
        perm = [(i, (i - 1) % Pn) for i in range(Pn)]

        def step(t, carry):
            b_cur, acc = carry
            b_nxt = jax.lax.ppermute(b_cur, axis, perm)
            col = ((me + t) % Pn) * nb
            prod = jnp.dot(a, b_cur, preferred_element_type=jnp.float32)
            old = jax.lax.dynamic_slice(acc, (0, col), (acc.shape[0], nb))
            acc = jax.lax.dynamic_update_slice(
                acc, (al * prod + be * old).astype(acc.dtype), (0, col))
            return b_nxt, acc

        _, acc = jax.lax.fori_loop(0, Pn, step, (b, c))
        return acc

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(axis, None), P(None, axis), P(axis, None)),
                       out_specs=P(axis, None))
    sA = jax.device_put(A, NamedSharding(mesh, P(axis, None)))
    sB = jax.device_put(B, NamedSharding(mesh, P(None, axis)))
    sC = jax.device_put(C, NamedSharding(mesh, P(axis, None)))
    return jax.jit(fn)(sA, sB, sC)
