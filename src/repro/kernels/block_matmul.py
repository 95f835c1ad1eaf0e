"""Pallas TPU kernel: double-buffered out-of-core block GEMM.

This is MMOOC compiled into the chip.  The libhclooc pipeline maps onto the
Mosaic grid pipeline one-to-one (DESIGN.md §2):

  hclMatrixPartitioner      -> grid = (M/bm, N/bn, K/bk) + BlockSpec index maps
  S(a), S(b), S(c) H2D ops  -> automatic double-buffered HBM->VMEM DMAs
                               (Mosaic prefetches block g+1 while g computes —
                               the paper's two-stream round robin)
  DGEMM on resident blocks  -> MXU jnp.dot on VMEM refs, fp32 scratch acc
  R(c) D2H                  -> output block DMA on the last K step
  events rA/rB/rC/eA/wC     -> DMA semaphores emitted by Mosaic

The K axis is innermost and "arbitrary" (sequential) so the fp32 accumulator
lives in VMEM scratch across K steps; M and N are parallel.  Block shapes are
MXU-aligned (multiples of 128 lanes / 8 sublanes).  C = alpha*A@B + beta*C —
full DGEMM semantics, like the paper.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Scoped VMEM a Mosaic kernel may use by default on TPU v5e (the chip has
# 128 MiB physically; the compiler refuses kernels above this limit).
VMEM_SCOPED_LIMIT = 16 * 2**20


def vmem_working_set_bytes(block: Tuple[int, int, int], a_dtype, b_dtype,
                           c_dtype) -> int:
    """VMEM one grid step holds: the a, b, c and out blocks, each double
    buffered by Mosaic's pipeline, plus the single fp32 accumulator."""
    bm, bn, bk = block
    size = lambda dt: jnp.dtype(dt).itemsize
    blocks = (bm * bk * size(a_dtype) + bk * bn * size(b_dtype)
              + 2 * bm * bn * size(c_dtype))
    return 2 * blocks + bm * bn * 4


def _kernel(a_ref, b_ref, c_ref, out_ref, acc_ref, *, alpha, beta, k_steps):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == k_steps - 1)
    def _finalize():
        out_ref[...] = (
            alpha * acc_ref[...] + beta * c_ref[...].astype(jnp.float32)
        ).astype(out_ref.dtype)


def _pad_to(x, m0: int, m1: int):
    p0 = (-x.shape[0]) % m0
    p1 = (-x.shape[1]) % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


@functools.partial(
    jax.jit,
    static_argnames=("alpha", "beta", "block", "interpret"),
)
def block_matmul(
    a: jax.Array,
    b: jax.Array,
    c: jax.Array,
    *,
    alpha: float = 1.0,
    beta: float = 0.0,
    block: Tuple[int, int, int] = (512, 512, 512),
    interpret: bool = False,
) -> jax.Array:
    """C = alpha * a @ b + beta * c via the VMEM-streaming Pallas kernel.

    Shapes: a (M, K), b (K, N), c (M, N).  Any M/N/K — inputs are zero-padded
    up to block multiples (zero K-padding contributes nothing to the sum).

    The block's VMEM working set (:func:`vmem_working_set_bytes`) must fit
    :data:`VMEM_SCOPED_LIMIT`; a larger block raises ``ValueError`` here
    instead of ``RESOURCE_EXHAUSTED`` from the chip's compiler.  Default
    512³ blocks: 9 MiB for fp32 operands, 7 MiB for bf16 with an fp32 C.
    """
    M, K = a.shape
    K2, N = b.shape
    assert K == K2 and c.shape == (M, N), (a.shape, b.shape, c.shape)
    bm, bn, bk = block
    need = vmem_working_set_bytes(block, a.dtype, b.dtype, c.dtype)
    if need > VMEM_SCOPED_LIMIT:
        raise ValueError(
            f"block {block} needs {need / 2**20:.2f} MiB of VMEM "
            f"(double-buffered a/b/c/out + fp32 accumulator), over the "
            f"{VMEM_SCOPED_LIMIT / 2**20:.0f} MiB scoped-VMEM limit")

    ap = _pad_to(a, bm, bk)
    bp = _pad_to(b, bk, bn)
    cp = _pad_to(c, bm, bn)
    Mp, Kp = ap.shape
    Np = bp.shape[1]
    grid = (Mp // bm, Np // bn, Kp // bk)

    out = pl.pallas_call(
        functools.partial(
            _kernel, alpha=alpha, beta=beta, k_steps=grid[2]
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), cp.dtype),
        scratch_shapes=[pltpu.MemorySpace.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(ap, bp, cp)
    return out[:M, :N]
