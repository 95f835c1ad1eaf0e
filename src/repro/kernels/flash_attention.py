"""Pallas TPU kernel: out-of-core decode attention (flash-decoding style).

The KV cache is the out-of-core operand: queries for one new token stay
resident in VMEM while K/V stream through in sequence blocks (Mosaic
double-buffers the DMAs across grid steps — the MMOOC pipeline again), with
an online-softmax carry (m, l, acc) instead of the GEMM beta-accumulate.
This realizes ``core/ooc_attention.py``'s schedule in-silicon and is the
hot kernel behind the ``decode_32k`` / ``long_500k`` serving shapes.

Layout: queries are grouped by KV head (GQA): q (B, Hkv, G, d) where
G = H // Hkv, so each grid step's MXU work is a fat (G, d) x (d, bs) matmul.
The cache is head-major, k/v (B, Hkv, S, d), so one grid step's K/V block
(1, 1, bs, d) is a dense (bs, d) tile: its last two dims are a multiple of
the (8, 128) tiling, which Mosaic requires of a block.
Valid cache length is per-batch in SMEM; fully-masked blocks contribute zero.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, len_ref, out_ref, m_ref, l_ref, acc_ref,
            *, bs: int, k_steps: int, scale: float):
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (G, d)
    k = k_ref[0, 0].astype(jnp.float32)            # (bs, d)
    v = v_ref[0, 0].astype(jnp.float32)            # (bs, d)

    scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    offs = s * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    mask = offs < len_ref[pl.program_id(0)]        # (1, bs)
    scores = jnp.where(mask, scores, NEG_INF)      # (G, bs)

    m_prev = m_ref[:, 0]                           # (G,)
    m_new = jnp.maximum(m_prev, scores.max(axis=-1))
    p = jnp.where(mask, jnp.exp(scores - m_new[:, None]), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_ref[:, 0] * corr + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(s == k_steps - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, 0], 1e-20)
        out_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def flash_decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    length: jax.Array,
    *,
    block_s: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Single-token GQA attention against a blocked KV cache.

    q: (B, H, d); k, v: (B, Hkv, S, d); length: (B,) int32 valid positions.
    Returns (B, H, d).  S is padded to a multiple of ``block_s`` (padded
    positions are masked by ``length``).
    """
    B, H, d = q.shape
    hkv, S = k.shape[1], k.shape[2]
    assert H % hkv == 0, (H, hkv)
    G = H // hkv
    qg = q.reshape(B, hkv, G, d)

    pad = (-S) % block_s
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    Sp = S + pad
    k_steps = Sp // block_s
    grid = (B, hkv, k_steps)

    out = pl.pallas_call(
        functools.partial(
            _kernel, bs=block_s, k_steps=k_steps, scale=1.0 / (d ** 0.5)
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, G, d), lambda b, h, s: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_s, d), lambda b, h, s: (b, h, s, 0)),
            pl.BlockSpec((1, 1, block_s, d), lambda b, h, s: (b, h, s, 0)),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.SMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, G, d), lambda b, h, s: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, hkv, G, d), q.dtype),
        scratch_shapes=[
            pltpu.MemorySpace.VMEM((G, 128), jnp.float32),  # m
            pltpu.MemorySpace.VMEM((G, 128), jnp.float32),  # l
            pltpu.MemorySpace.VMEM((G, d), jnp.float32),    # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qg, k, v, length.astype(jnp.int32))
    return out.reshape(B, H, d)
