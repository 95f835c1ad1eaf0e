"""The control of the comparison that decides ``correct``.

The configuration states fp32 operands at HIGHEST matmul precision, which
on a TPU is six bf16 passes.  The precision below that is HIGH, three
passes (XLA's bf16_3x): each operand split into a bf16 head and a bf16
tail, and the products head·head + head·tail + tail·head accumulated in
fp32.  The control is the reference put in the program's place at that
precision, spelt out in bf16 so that it computes the same on a TPU and on
a CPU, which ignores the precision setting.  It has to come out as not
correct.
"""

from __future__ import annotations

import numpy as np

BLOCK_ROWS = 4096


def make_control_call(config: dict, devices: list, bytes_limit: int):
    """``call(A, B, C0)`` like :func:`bench.harness.make_call`'s, computed
    on the first device in row blocks that fit it."""
    import jax
    import jax.numpy as jnp

    dev = devices[0]
    alpha, beta = np.float32(config["alpha"]), np.float32(config["beta"])

    @jax.jit
    def split(x):
        # x rounded to bf16 (to nearest even) in the bits of an fp32: a
        # convert there and back would be folded away as excess precision
        u = jax.lax.bitcast_convert_type(x, jnp.uint32)
        u = (u + np.uint32(0x7FFF) + ((u >> 16) & 1)) & np.uint32(0xFFFF0000)
        head = jax.lax.bitcast_convert_type(u, jnp.float32)
        return head.astype(jnp.bfloat16), (x - head).astype(jnp.bfloat16)

    @jax.jit
    def rows(a_head, a_tail, b_head, b_tail, c):
        def dot(x, y):
            return jnp.dot(x, y, preferred_element_type=jnp.float32)
        acc = (dot(a_head, b_head) + dot(a_head, b_tail)
               + dot(a_tail, b_head))
        return alpha * acc + beta * c

    def put(x):
        return jax.device_put(np.ascontiguousarray(x), dev)

    def call(A, B, C0):
        heads, tails = zip(*(split(put(B[i:i + BLOCK_ROWS]))
                             for i in range(0, B.shape[0], BLOCK_ROWS)))
        b_head = jnp.concatenate(heads)
        del heads    # at most one operand's bytes more than B on the chip
        b_tail = jnp.concatenate(tails)
        del tails
        out = np.empty(C0.shape, np.float32)
        for i in range(0, A.shape[0], BLOCK_ROWS):
            blk = slice(i, i + BLOCK_ROWS)
            out[blk] = np.asarray(rows(*split(put(A[blk])), b_head, b_tail,
                                       put(C0[blk])))
        return out, {}
    return call
