"""Differential oracle tests for the OOC factorizations (ISSUE 4).

``ooc_cholesky`` / ``ooc_lu`` against ``numpy.linalg`` (and scipy's getrf
when available) on random SPD and ill-conditioned matrices, across dtypes,
non-divisible panel sizes and panel >= n edge cases, including the LU
pivot-permutation round-trip.  The engine computes in f32 (JAX x64 is off),
so float64 inputs are held to f32-level tolerances.
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (compile_factor_pipeline, factor_pipeline_spec,
                        ooc_cholesky, ooc_lu, schedule_stats,
                        validate_schedule)
from repro.core.api import hclOocFactor


def _spd(rng, n, dtype=np.float32, cond=None):
    """Random SPD matrix; ``cond`` spreads the spectrum geometrically."""
    Q, _ = np.linalg.qr(rng.standard_normal((n, n)))
    if cond is None:
        lam = rng.uniform(1.0, 2.0, n)
    else:
        lam = np.geomspace(1.0, cond, n)
    return (Q * lam @ Q.T + n * np.finfo(np.float32).eps * np.eye(n)) \
        .astype(dtype)


def _square(rng, n, dtype=np.float32, cond=None):
    """Random well- or ill-conditioned square matrix via its SVD."""
    A = rng.standard_normal((n, n))
    if cond is not None:
        U, _, Vt = np.linalg.svd(A)
        A = U * np.geomspace(cond, 1.0, n) @ Vt
    return A.astype(dtype)


def _lu_factors(LU, dtype):
    n = LU.shape[0]
    return np.tril(LU, -1) + np.eye(n, dtype=dtype), np.triu(LU)


# ------------------------------------------------------------ Cholesky
@pytest.mark.parametrize("n,panel", [
    (256, 64),     # divisible
    (300, 96),     # non-divisible (last panel is 12 wide)
    (192, 512),    # panel >= n: a single in-core panel step
    (260, 64),     # non-divisible, small last panel
])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_cholesky_matches_numpy(rng, n, panel, dtype):
    A = _spd(rng, n, dtype)
    L = ooc_cholesky(A, panel=panel, budget_bytes=4 * A.nbytes,
                     validate=True)
    expect = np.linalg.cholesky(A.astype(np.float64))
    scale = np.abs(expect).max()
    np.testing.assert_allclose(L / scale, expect / scale, rtol=0, atol=5e-6)
    assert L.dtype == A.dtype
    np.testing.assert_array_equal(L, np.tril(L))


def test_cholesky_ill_conditioned(rng):
    """A 1e5 condition number loses digits but the factorization must stay
    backward-stable: reconstruct A within a modest multiple of f32 eps."""
    n = 256
    A = _spd(rng, n, cond=1e5)
    L = ooc_cholesky(A, panel=64, budget_bytes=4 * A.nbytes, validate=True)
    rel = np.abs(L @ L.T - A).max() / np.abs(A).max()
    assert rel < 1e-4, rel


@given(lookahead=st.sampled_from([0, 1, 2]),
       nstreams=st.sampled_from([1, 2]),
       nbuf=st.sampled_from([1, 2, 3]))
@settings(max_examples=12, deadline=None)
def test_cholesky_invariant_to_pipeline_config(lookahead, nstreams, nbuf):
    """Lookahead depth, stream count and buffer depth are scheduling
    properties, never numerics properties: every trailing element is a
    full-K dot regardless of block geometry, so all configs agree to
    rounding noise."""
    rng = np.random.default_rng(5)
    A = _spd(rng, 260, np.float32)
    base = ooc_cholesky(A, panel=96, budget_bytes=4 * A.nbytes,
                        lookahead=0, nstreams=2, nbuf=2)
    got = ooc_cholesky(A, panel=96, budget_bytes=4 * A.nbytes,
                       lookahead=lookahead, nstreams=nstreams, nbuf=nbuf,
                       validate=True)
    np.testing.assert_allclose(got, base, rtol=0,
                               atol=1e-6 * np.abs(base).max())


# ------------------------------------------------------------------ LU
@pytest.mark.parametrize("n,panel", [
    (256, 64),
    (300, 96),
    (192, 512),    # panel >= n
    (260, 64),
])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_lu_reconstructs_with_bounded_multipliers(rng, n, panel, dtype):
    A = _square(rng, n, dtype)
    LU, perm = ooc_lu(A, panel=panel, budget_bytes=4 * A.nbytes,
                      validate=True)
    L, U = _lu_factors(LU, dtype)
    # P A = L U within dtype (engine) tolerance
    rel = np.abs(A[perm] - L @ U).max() / np.abs(A).max()
    assert rel < 5e-6, rel
    # the partial-pivoting invariant: every multiplier is bounded by 1
    assert np.abs(np.tril(LU, -1)).max() <= 1.0 + 1e-6


def test_lu_pivots_match_scipy(rng):
    """Same pivot choices as the LAPACK oracle on a well-separated matrix
    (pivot magnitudes far apart, so f32-vs-f64 rounding cannot flip an
    argmax)."""
    scipy_linalg = pytest.importorskip("scipy.linalg")
    n = 96
    A = _square(rng, n)
    LU, perm = ooc_lu(A, panel=32, budget_bytes=4 * A.nbytes)
    _, piv = scipy_linalg.lu_factor(A.astype(np.float64))
    sperm = np.arange(n)
    for j, p in enumerate(piv):
        sperm[[j, p]] = sperm[[p, j]]
    assert np.array_equal(perm, sperm)


def test_lu_permutation_round_trip(rng):
    """perm is a true permutation and inverts cleanly: scattering the
    factored rows back restores original row order."""
    n = 260
    A = _square(rng, n)
    LU, perm = ooc_lu(A, panel=96, budget_bytes=4 * A.nbytes)
    assert sorted(perm.tolist()) == list(range(n))
    L, U = _lu_factors(LU, np.float32)
    inv = np.empty(n, dtype=perm.dtype)
    inv[perm] = np.arange(n)
    recon = (L @ U)[inv]          # undo the row permutation
    rel = np.abs(recon - A).max() / np.abs(A).max()
    assert rel < 5e-6, rel


def test_lu_solves_like_numpy(rng):
    """Forward/back substitution through our factors reproduces
    np.linalg.solve — the end-to-end use an LU exists for."""
    scipy_linalg = pytest.importorskip("scipy.linalg")
    n = 256
    A = _square(rng, n)
    b = rng.standard_normal(n).astype(np.float32)
    LU, perm = ooc_lu(A, panel=64, budget_bytes=4 * A.nbytes)
    L, U = _lu_factors(LU, np.float32)
    y = scipy_linalg.solve_triangular(L, b[perm], lower=True,
                                      unit_diagonal=True)
    x = scipy_linalg.solve_triangular(U, y, lower=False)
    expect = np.linalg.solve(A.astype(np.float64), b)
    np.testing.assert_allclose(x, expect, rtol=2e-3, atol=2e-3)


def test_lu_ill_conditioned_stays_backward_stable(rng):
    n = 256
    A = _square(rng, n, cond=1e5)
    LU, perm = ooc_lu(A, panel=64, budget_bytes=4 * A.nbytes, validate=True)
    L, U = _lu_factors(LU, np.float32)
    rel = np.abs(A[perm] - L @ U).max() / np.abs(A).max()
    assert rel < 1e-4, rel


def test_lu_pivoting_beats_no_pivot_case(rng):
    """A matrix with a tiny leading diagonal forces row swaps; without
    pivoting the factorization would blow up, with it the reconstruction
    stays exact — and the permutation is non-trivial."""
    n = 128
    A = _square(rng, n)
    A[0, 0] = 1e-30
    LU, perm = ooc_lu(A, panel=32, budget_bytes=4 * A.nbytes)
    assert not np.array_equal(perm, np.arange(n))
    L, U = _lu_factors(LU, np.float32)
    rel = np.abs(A[perm] - L @ U).max() / np.abs(A).max()
    assert rel < 5e-6, rel


def test_lu_invariant_to_pipeline_config(rng):
    A = _square(rng, 300)
    base = None
    for lookahead in (0, 1):
        for nstreams in (1, 2):
            got, perm = ooc_lu(A, panel=96, budget_bytes=4 * A.nbytes,
                               lookahead=lookahead, nstreams=nstreams,
                               validate=True)
            if base is None:
                base = (got, perm)
            np.testing.assert_allclose(got, base[0], rtol=0,
                                       atol=1e-5 * np.abs(base[0]).max())
            np.testing.assert_array_equal(base[1], perm)


# ---------------------------------------------------------- facade/spec
def test_hcl_ooc_factor_facade(rng):
    n = 192
    A = _spd(rng, n)
    L = hclOocFactor(A, "cholesky", panel=64, budget_bytes=4 * A.nbytes)
    np.testing.assert_allclose(L @ L.T, A, rtol=1e-4, atol=1e-4)
    B = _square(rng, n)
    LU, perm = hclOocFactor(B, "lu", panel=64, budget_bytes=4 * B.nbytes)
    L, U = _lu_factors(LU, np.float32)
    np.testing.assert_allclose(B[perm], L @ U, rtol=1e-4, atol=1e-4)
    with pytest.raises(ValueError, match="unknown factor kind"):
        hclOocFactor(A, "qr", budget_bytes=1 << 20)


def test_factor_schedule_accounts_full_flops():
    """The compiled schedule's flop total covers the n^3/3 factorization
    (trailing updates dominate), and both lookahead modes move identical
    bytes — lookahead reorders, never re-transfers."""
    spec0 = factor_pipeline_spec(1024, 128, 1 << 30, 4, kind="cholesky",
                                 lookahead=0)
    spec1 = factor_pipeline_spec(1024, 128, 1 << 30, 4, kind="cholesky",
                                 lookahead=1)
    st0 = schedule_stats(compile_factor_pipeline(spec0))
    st1 = schedule_stats(compile_factor_pipeline(spec1))
    assert st0["flops"] >= 1024 ** 3 // 3
    assert st0["h2d_bytes"] == st1["h2d_bytes"]
    assert st0["d2h_bytes"] == st1["d2h_bytes"]
    assert st0["flops"] == st1["flops"]


def test_factor_budget_infeasible_raises():
    with pytest.raises(ValueError, match="budget"):
        factor_pipeline_spec(4096, 512, 1024, 4, kind="cholesky")
    with pytest.raises(ValueError, match="unknown factor kind"):
        factor_pipeline_spec(512, 128, 1 << 30, 4, kind="qr")


def test_factor_tiny_matrices(rng):
    """n smaller than any sensible panel still factors (single in-core
    panel step) — regression: the feasibility ladder once floored the
    panel width at 8 and rejected n < 8 outright."""
    for n in (1, 2, 4, 7):
        A = _spd(rng, n)
        L = ooc_cholesky(A, budget_bytes=1 << 24)
        np.testing.assert_allclose(L @ L.T, A, rtol=1e-5, atol=1e-5)
        B = _square(rng, n) + n * np.eye(n, dtype=np.float32)
        LU, perm = ooc_lu(B, budget_bytes=1 << 24)
        L2, U2 = _lu_factors(LU, np.float32)
        np.testing.assert_allclose(B[perm], L2 @ U2, rtol=1e-5, atol=1e-5)


def test_factor_rejects_non_square(rng):
    with pytest.raises(ValueError, match="square"):
        ooc_cholesky(rng.standard_normal((64, 32)), budget_bytes=1 << 20)
    with pytest.raises(ValueError, match="square"):
        ooc_lu(rng.standard_normal((64, 32)), budget_bytes=1 << 20)
