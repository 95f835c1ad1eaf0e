"""Substrate tests: optimizer, compression, data pipeline, checkpointing."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.checkpoint import CheckpointManager
from repro.data import MemmapSource, Prefetcher, SyntheticSource
from repro.optim import AdamWConfig, adamw, compression

KEY = jax.random.PRNGKey(0)


# ------------------------------------------------------------------ optimizer
def _toy_params(rng):
    return {"w": jnp.asarray(rng.standard_normal((8, 4)), jnp.float32),
            "b": jnp.asarray(rng.standard_normal((4,)), jnp.float32)}


def test_adamw_decreases_quadratic(rng):
    params = _toy_params(rng)
    target = jax.tree.map(lambda p: jnp.ones_like(p), params)
    cfg = AdamWConfig(lr=5e-2, weight_decay=0.0, warmup_steps=1,
                      total_steps=200)
    state = adamw.init(params)

    def loss(p):
        return sum(jnp.sum((a - b) ** 2)
                   for a, b in zip(jax.tree.leaves(p),
                                   jax.tree.leaves(target)))

    l0 = float(loss(params))
    for _ in range(100):
        grads = jax.grad(loss)(params)
        params, state, _ = adamw.update(grads, state, params, cfg)
    assert float(loss(params)) < 0.2 * l0


def test_adamw_no_master_close_to_master(rng):
    params = _toy_params(rng)
    cfgm = AdamWConfig(lr=1e-2, use_master=True)
    cfgn = AdamWConfig(lr=1e-2, use_master=False)
    sm = adamw.init(params, use_master=True)
    sn = adamw.init(params, use_master=False)
    g = jax.tree.map(lambda p: 0.1 * jnp.ones_like(p), params)
    pm, sm, _ = adamw.update(g, sm, params, cfgm)
    pn, sn, _ = adamw.update(g, sn, params, cfgn)
    for a, b in zip(jax.tree.leaves(pm), jax.tree.leaves(pn)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_grad_clipping_bounds_update(rng):
    params = _toy_params(rng)
    cfg = AdamWConfig(lr=1e-3, clip_norm=1.0)
    state = adamw.init(params)
    big = jax.tree.map(lambda p: 1e6 * jnp.ones_like(p), params)
    _, _, metrics = adamw.update(big, state, params, cfg)
    assert float(metrics["grad_norm"]) > 1e5  # pre-clip norm reported


# ---------------------------------------------------------------- compression
@given(scale=st.floats(min_value=1e-6, max_value=1e4),
       n=st.integers(min_value=1, max_value=500))
@settings(max_examples=50, deadline=None)
def test_quantize_roundtrip_error_bounded(scale, n):
    rng = np.random.default_rng(42)
    g = jnp.asarray(rng.standard_normal(n) * scale, jnp.float32)
    q, s = compression.quantize(g)
    err = np.abs(np.asarray(compression.dequantize(q, s) - g))
    assert err.max() <= float(s) / 2 + 1e-12  # half-ULP of the int8 grid


def test_error_feedback_unbiased_over_time(rng):
    """With EF, the *accumulated* applied gradient converges to the
    accumulated true gradient (residual stays bounded)."""
    g = jnp.asarray(rng.standard_normal((64,)), jnp.float32)
    err = jnp.zeros_like(g)
    total_applied = jnp.zeros_like(g)
    for t in range(50):
        comp, err_tree = compression.ef_compress({"g": g}, {"g": err})
        err = err_tree["g"]
        q, s = comp["g"]
        total_applied = total_applied + compression.dequantize(q, s)
    np.testing.assert_allclose(np.asarray(total_applied / 50), np.asarray(g),
                               rtol=0.05,
                               atol=float(jnp.max(jnp.abs(g))) / 50)


def test_shared_scale_int8_sum_exact(rng):
    """The compressed_pod_psum math: with a shared scale, the int16 sum of
    int8 payloads dequantizes to the exact sum of the quantized values."""
    gs = [jnp.asarray(rng.standard_normal((32,)), jnp.float32)
          for _ in range(4)]
    s = max(float(jnp.max(jnp.abs(g))) for g in gs) / 127.0 + 1e-12
    qs = [jnp.clip(jnp.round(g / s), -127, 127).astype(jnp.int8) for g in gs]
    qsum = sum(q.astype(jnp.int16) for q in qs)
    deq = np.asarray(qsum, np.float32) * s
    direct = sum(np.asarray(q, np.float32) * s for q in qs)
    np.testing.assert_allclose(deq, direct, rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------------ data
def test_synthetic_deterministic_and_seekable():
    src = SyntheticSource(vocab_size=1000, seed=3)
    a = src.batch_at(7, 8, 16)
    b = src.batch_at(7, 8, 16)
    np.testing.assert_array_equal(a["inputs"], b["inputs"])
    c = src.batch_at(8, 8, 16)
    assert not np.array_equal(a["inputs"], c["inputs"])
    # labels are next-token shifted
    full_a = src.batch_at(7, 8, 16)
    np.testing.assert_array_equal(a["labels"][:, :-1], full_a["inputs"][:, 1:])
    assert a["inputs"].max() < 1000


def test_synthetic_host_sharding_partitions_batch():
    src = SyntheticSource(vocab_size=500, seed=0)
    full = src.batch_at(3, 8, 4, host_index=0, host_count=1)
    h0 = src.batch_at(3, 8, 4, host_index=0, host_count=2)
    h1 = src.batch_at(3, 8, 4, host_index=1, host_count=2)
    np.testing.assert_array_equal(
        np.concatenate([h0["inputs"], h1["inputs"]]), full["inputs"])


def test_memmap_source(tmp_path):
    path = str(tmp_path / "tokens.bin")
    toks = np.arange(10000, dtype=np.int32)
    toks.tofile(path)
    src = MemmapSource(path, vocab_size=1 << 30)
    b = src.batch_at(0, 4, 16)
    assert b["inputs"].shape == (4, 16)
    np.testing.assert_array_equal(b["labels"], b["inputs"] + 1)


def test_prefetcher_orders_steps():
    src = SyntheticSource(vocab_size=100, seed=1)
    pf = Prefetcher(src, batch=4, seq=8, start_step=5, depth=2)
    for expect in (5, 6, 7):
        step, batch = next(pf)
        assert step == expect
        ref_b = src.batch_at(step, 4, 8)
        np.testing.assert_array_equal(batch["inputs"], ref_b["inputs"])
    pf.close()


# ------------------------------------------------------------------ checkpoint
def test_checkpoint_roundtrip_and_gc(tmp_path, rng):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    state = {"params": {"w": jnp.asarray(rng.standard_normal((4, 4)),
                                         jnp.float32)},
             "step": jnp.asarray(3)}
    for step in (1, 2, 3):
        mgr.save(step, state, data_cursor=step * 10, blocking=True)
    assert mgr.all_steps() == [2, 3]  # keep=2 garbage-collects step 1
    target = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state)
    restored, cursor = mgr.restore(3, target)
    assert cursor == 30
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                  np.asarray(state["params"]["w"]))


def test_checkpoint_atomic_no_partial(tmp_path, rng):
    """tmp dirs never count as checkpoints."""
    mgr = CheckpointManager(str(tmp_path))
    os.makedirs(os.path.join(str(tmp_path), "tmp.99.0"))
    assert mgr.latest_step() is None
    state = {"w": jnp.ones((2,), jnp.float32)}
    mgr.save(5, state, blocking=True)
    assert mgr.latest_step() == 5


def test_checkpoint_shape_mismatch_raises(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"w": jnp.ones((2, 2), jnp.float32)}, blocking=True)
    bad = {"w": jax.ShapeDtypeStruct((3, 3), jnp.float32)}
    with pytest.raises(ValueError, match="shape"):
        mgr.restore(1, bad)


def test_train_restart_resumes_identically(tmp_path):
    """Kill-and-restart determinism: a run checkpointed at step 10 and
    resumed to 20 produces the same losses as an uninterrupted 20-step
    run (fault-tolerance contract)."""
    from repro.launch.train import main as train_main

    ck1 = str(tmp_path / "a")
    full = train_main(["--arch", "stablelm-1.6b", "--smoke",
                       "--steps", "14", "--batch", "2", "--seq", "32",
                       "--log-every", "100"])
    # interrupted run: first 7 steps, checkpoint, then resume
    part1 = train_main(["--arch", "stablelm-1.6b", "--smoke",
                        "--steps", "7", "--total-steps", "14",
                        "--batch", "2", "--seq", "32",
                        "--ckpt-dir", ck1, "--ckpt-every", "7",
                        "--log-every", "100"])
    part2 = train_main(["--arch", "stablelm-1.6b", "--smoke",
                        "--steps", "14", "--batch", "2", "--seq", "32",
                        "--ckpt-dir", ck1, "--resume", "auto",
                        "--log-every", "100"])
    combined = part1["losses"] + part2["losses"]
    np.testing.assert_allclose(combined, full["losses"], rtol=1e-4)
