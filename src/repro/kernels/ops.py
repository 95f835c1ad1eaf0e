"""jit'd public wrappers for the Pallas kernels.

On a TPU the kernels lower through Mosaic.  Anywhere else the caller must
ask for Pallas interpret mode (``interpret=True``: the kernel body runs in
Python, which is how the CPU tests validate it); without that choice the
wrappers raise instead of quietly running somewhere other than the chip.
"""

from __future__ import annotations

import jax

from repro.kernels.block_matmul import block_matmul as _block_matmul
from repro.kernels.flash_attention import (
    flash_decode_attention as _flash_decode_attention,
)


def check_interpret(interpret: bool) -> bool:
    """``interpret`` if it is allowed here: compiled kernels need a TPU."""
    if not interpret and jax.default_backend() != "tpu":
        raise RuntimeError(
            f"Pallas TPU kernels need a TPU, and the JAX backend is "
            f"{jax.default_backend()!r}; pass interpret=True to run them in "
            f"Pallas interpret mode")
    return interpret


def block_matmul(a, b, c, *, alpha=1.0, beta=0.0, block=(512, 512, 512),
                 interpret=False):
    return _block_matmul(
        a, b, c, alpha=alpha, beta=beta, block=tuple(block),
        interpret=check_interpret(interpret),
    )


def flash_decode_attention(q, k, v, length, *, block_s=512, interpret=False):
    return _flash_decode_attention(
        q, k, v, length, block_s=block_s,
        interpret=check_interpret(interpret),
    )
