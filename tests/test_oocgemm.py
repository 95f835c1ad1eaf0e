"""MMOOC end-to-end: every backend must equal the DGEMM oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import VmemOocRuntime, is_in_core, ooc_gemm, ooc_syrk
from repro.core.api import (hclDeviceFactory, hclGetMemSize,
                            hclMatrixPartitioner, hclRuntimeFactory)
from repro.core.ooc_attention import ooc_attention
from repro.kernels import ref


def _problem(rng, M, N, K, dtype=np.float32):
    A = rng.standard_normal((M, K)).astype(dtype)
    B = rng.standard_normal((K, N)).astype(dtype)
    C = rng.standard_normal((M, N)).astype(dtype)
    return A, B, C


@pytest.mark.parametrize("M,N,K,frac", [
    (256, 256, 128, 4),
    (512, 384, 256, 8),
    (640, 128, 128, 3),
    (128, 128, 64, 1),     # in-core path
])
def test_ooc_gemm_host_matches_oracle(rng, M, N, K, frac):
    A, B, C = _problem(rng, M, N, K)
    budget = (A.nbytes + B.nbytes + C.nbytes) // frac
    out = ooc_gemm(A, B, C, 1.5, 0.25, budget_bytes=budget,
                   backend="host", validate=True)
    expect = 1.5 * (A.astype(np.float64) @ B) + 0.25 * C
    np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-4)


@given(nstreams=st.sampled_from([1, 2]), nbuf=st.sampled_from([1, 2, 3]),
       frac=st.sampled_from([2, 5]))
@settings(max_examples=10, deadline=None)
def test_ooc_gemm_any_pipeline_config(nstreams, nbuf, frac):
    """Result is invariant to the pipeline configuration (the overlap is a
    schedule property, never a numerics property)."""
    rng = np.random.default_rng(7)
    A, B, C = _problem(rng, 320, 192, 128)
    budget = (A.nbytes + B.nbytes + C.nbytes) // frac
    out = ooc_gemm(A, B, C, 2.0, -0.5, budget_bytes=budget, backend="host",
                   nstreams=nstreams, nbuf=nbuf, validate=True)
    expect = 2.0 * (A.astype(np.float64) @ B) - 0.5 * C
    np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-4)


def test_ooc_gemm_vmem_backend(rng):
    A, B, C = _problem(rng, 256, 256, 256)
    budget = A.nbytes  # force OOC
    out = ooc_gemm(jnp.asarray(A), jnp.asarray(B), jnp.asarray(C),
                   1.0, 1.0, budget_bytes=budget, backend="vmem",
                   runtime=VmemOocRuntime(interpret=True))
    expect = A.astype(np.float64) @ B + C
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-4, atol=1e-4)


def test_in_core_switch():
    assert is_in_core(64, 64, 64, 1 << 20, 4)
    assert not is_in_core(1024, 1024, 1024, 1 << 20, 4)


def test_hcl_facade(rng):
    dev = hclDeviceFactory.create("HBM", 0, mem_bytes=300_000)
    assert hclGetMemSize(dev) == 300_000
    rt = hclRuntimeFactory.create(dev)
    part = hclMatrixPartitioner(512, 256, 128, dev.mem_bytes)
    A, B, C = _problem(rng, 512, 256, 128)
    out = rt.gemm(A, B, C, 1.0, 0.0, part)
    np.testing.assert_allclose(out, A @ B, rtol=1e-4, atol=1e-4)


def test_ooc_attention_matches_oracle(rng):
    H, hkv, d, S = 16, 4, 64, 2048
    q = rng.standard_normal((H, d)).astype(np.float32)
    k = rng.standard_normal((S, hkv, d)).astype(np.float32)
    v = rng.standard_normal((S, hkv, d)).astype(np.float32)
    out = ooc_attention(q, k, v, budget_bytes=S * hkv * d * 4 // 3,
                        validate=True)
    expect = ref.decode_attention_ref(
        jnp.asarray(q)[None], jnp.asarray(k)[None], jnp.asarray(v)[None],
        jnp.asarray([S]))[0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-4, atol=1e-4)


def test_ooc_attention_narrow_kv_dtype_keeps_f32_accuracy(rng):
    """A reduced-precision KV cache must not quantize the f32 carry on its
    way out (regression: the host output buffer briefly took the KV dtype)."""
    H, hkv, d, S = 16, 4, 64, 1024
    q = rng.standard_normal((H, d)).astype(np.float32)
    k = rng.standard_normal((S, hkv, d)).astype(np.float16)
    v = rng.standard_normal((S, hkv, d)).astype(np.float16)
    out = ooc_attention(q, k, v, budget_bytes=S * hkv * d * 4 // 3)
    expect = ref.decode_attention_ref(
        jnp.asarray(q)[None], jnp.asarray(k).astype(jnp.float32)[None],
        jnp.asarray(v).astype(jnp.float32)[None], jnp.asarray([S]))[0]
    assert out.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


def test_ooc_cholesky(rng):
    """Paper future-work: blocked Cholesky with the OOC-SYRK trailing
    update (repro.core.ooc_factor)."""
    from repro.core.ooc_factor import ooc_cholesky
    n = 320
    X = rng.standard_normal((n, n)).astype(np.float32)
    A = (X @ X.T + n * np.eye(n)).astype(np.float32)
    L = ooc_cholesky(A, panel=128,
                     budget_bytes=(3 * n * n * 4) // 4, backend="host")
    # fp32 engine (JAX x64 is off): relative reconstruction error
    rel = np.abs(L @ L.T - A).max() / np.abs(A).max()
    assert rel < 1e-5, rel
    assert np.allclose(L, np.tril(L))


def test_ooc_cholesky_matches_numpy_oracle(rng):
    """Element-wise agreement with np.linalg.cholesky, not just L@L^T."""
    from repro.core.ooc_factor import ooc_cholesky
    n = 384
    X = rng.standard_normal((n, n)).astype(np.float32)
    A = (X @ X.T + n * np.eye(n)).astype(np.float32)
    L = ooc_cholesky(A, panel=128,
                     budget_bytes=(3 * n * n * 4) // 5, backend="host")
    expect = np.linalg.cholesky(A.astype(np.float64))
    scale = np.abs(expect).max()
    np.testing.assert_allclose(L / scale, expect / scale,
                               rtol=0, atol=2e-6)


@pytest.mark.parametrize("backend", ["host", "vmem"])
def test_ooc_syrk_matches_oracle(rng, backend):
    """The third DSL kernel: blocked SYRK (the Cholesky trailing update) as
    a first-class PipelineSpec, cross-checked on both single-chip tiers."""
    n, k = 384, 192
    P = rng.standard_normal((n, k)).astype(np.float32)
    C = rng.standard_normal((n, n)).astype(np.float32)
    budget = (2 * P.nbytes + C.nbytes) // 4  # force out-of-core
    rt = VmemOocRuntime(interpret=True) if backend == "vmem" else None
    out = ooc_syrk(P, C, -2.0, 0.5, budget_bytes=budget, backend=backend,
                   validate=(backend == "host"), runtime=rt)
    expect = np.asarray(ref.gemm_ref(
        jnp.asarray(P), jnp.asarray(P).T, jnp.asarray(C),
        alpha=-2.0, beta=0.5))
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-4, atol=1e-4)


def test_ooc_syrk_in_core_switch(rng):
    n, k = 128, 64
    P = rng.standard_normal((n, k)).astype(np.float32)
    out = ooc_syrk(P, budget_bytes=1 << 30, backend="host")
    np.testing.assert_allclose(out, P @ P.T, rtol=1e-4, atol=1e-4)
