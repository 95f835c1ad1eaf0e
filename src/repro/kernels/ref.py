"""Pure-jnp oracles for every Pallas kernel (the ground truth in tests)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def gemm_ref(a, b, c=None, alpha: float = 1.0, beta: float = 0.0):
    """DGEMM contract: alpha * a @ b + beta * c, fp32 accumulation, the
    product at HIGHEST precision (a TPU otherwise multiplies fp32 in one
    bf16 pass)."""
    acc = jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                  precision=jax.lax.Precision.HIGHEST,
                  preferred_element_type=jnp.float32)
    out = alpha * acc
    if c is not None:
        out = out + beta * c.astype(jnp.float32)
    dtype = a.dtype if c is None else c.dtype
    return out.astype(dtype)


def gemm_error_bound(a, b, c=None, alpha: float = 1.0, beta: float = 0.0,
                     dtype=np.float32) -> np.ndarray:
    """Elementwise bound on ``|computed - exact|`` for ``alpha*a@b + beta*c``
    evaluated in ``dtype`` with the K-term sums taken in ANY order:
    ``gamma_{K+2} * (|alpha| |a| @ |b| + |beta| |c|)``, where
    ``gamma_n = n u / (1 - n u)`` and ``u`` is the unit roundoff.  Two
    results that differ only in summation order (block geometry, backend
    kernel choice) therefore differ by at most twice this bound."""
    a = np.abs(np.asarray(a, np.float64))
    b = np.abs(np.asarray(b, np.float64))
    u = np.finfo(dtype).eps / 2
    n = a.shape[1] + 2
    mag = abs(alpha) * (a @ b)
    if c is not None:
        mag = mag + abs(beta) * np.abs(np.asarray(c, np.float64))
    return n * u / (1 - n * u) * mag


def decode_attention_ref(q, k, v, length=None):
    """Single-token GQA attention oracle.

    q: (B, H, d); k, v: (B, S, Hkv, d); length: (B,) valid cache length
    (positions >= length are masked).  Returns (B, H, d).
    """
    B, H, d = q.shape
    S, hkv = k.shape[1], k.shape[2]
    group = H // hkv
    kb = jnp.repeat(k, group, axis=2)  # (B, S, H, d)
    vb = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32),
                   kb.astype(jnp.float32)) / np.sqrt(d)
    if length is not None:
        mask = jnp.arange(S)[None, None, :] < length[:, None, None]
        s = jnp.where(mask, s, -jnp.inf)
    p = jnp.exp(s - s.max(axis=-1, keepdims=True))
    p = p / p.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bhs,bshd->bhd", p, vb.astype(jnp.float32))
    return out.astype(q.dtype)
