"""Property-based safety tests for the screening rule (the paper's core claim).

Invariants:
  S1 (safety):       every feature active at lam2 is kept by the screen.
  S2 (bound valid):  bound_j >= |fhat_j^T theta*(lam2)| for every j.
  S3 (exactness):    solving the screened problem == solving the full one.
  S4 (monotonicity): lam2 -> lam1 keeps everything active at lam1.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # optional dev dep; see tests/_hyp_compat.py + pyproject
    from _hyp_compat import given, settings, st

from repro.core import (
    fista_solve,
    lambda_max,
    screen,
    screen_bounds,
    theta_at_lambda_max,
    theta_from_primal,
)
from repro.core.dual import safe_theta_and_delta
from repro.data import make_sparse_classification

ACTIVE_TOL = 1e-6


def _setup(m, n, seed, correlated=0.0):
    ds = make_sparse_classification(m=m, n=n, k_active=max(2, m // 20),
                                    seed=seed, correlated=correlated)
    X, y = jnp.asarray(ds.X), jnp.asarray(ds.y)
    lmax = float(lambda_max(X, y))
    return X, y, lmax


@settings(max_examples=12, deadline=None)
@given(
    seed=st.integers(0, 10_000),
    ratio=st.floats(0.05, 0.95),
    m=st.sampled_from([60, 150, 300]),
    n=st.sampled_from([40, 100]),
    correlated=st.sampled_from([0.0, 0.5]),
)
def test_safety_from_lambda_max(seed, ratio, m, n, correlated):
    """S1 + S2 with the exact closed-form theta1 at lam1 = lam_max."""
    X, y, lmax = _setup(m, n, seed, correlated)
    theta1 = theta_at_lambda_max(y, jnp.asarray(lmax))
    lam2 = ratio * lmax

    keep, bounds = screen(X, y, lmax, lam2, theta1)
    res = fista_solve(X, y, lam2, max_iters=50000, tol=1e-14)
    w = np.asarray(res.w)
    active = np.abs(w) > ACTIVE_TOL
    keep = np.asarray(keep)

    assert not np.any(active & ~keep), (
        f"UNSAFE: active features screened out at ratio={ratio}"
    )
    theta2 = theta_from_primal(X, y, res.w, res.b, jnp.asarray(lam2))
    tv = np.abs(np.asarray(X @ (y * theta2)))
    bb = np.asarray(bounds)
    assert np.all(bb >= tv - 5e-4), f"bound violated by {np.max(tv - bb)}"


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10_000), r1=st.floats(0.5, 0.95), r2=st.floats(0.1, 0.95))
def test_safety_sequential(seed, r1, r2):
    """S1 with theta1 from a *solved* intermediate lambda (sequential use)."""
    X, y, lmax = _setup(200, 80, seed)
    lam1 = r1 * lmax
    lam2 = r2 * lam1
    res1 = fista_solve(X, y, lam1, max_iters=50000, tol=1e-14)
    # theta1 is inexact -> use the gap-certified (theta, delta) pair
    theta1, delta = safe_theta_and_delta(X, y, res1.w, res1.b, jnp.asarray(lam1))

    keep, _ = screen(X, y, lam1, lam2, theta1, delta=delta)
    res2 = fista_solve(X, y, lam2, max_iters=50000, tol=1e-14)
    active = np.abs(np.asarray(res2.w)) > ACTIVE_TOL
    assert not np.any(active & ~np.asarray(keep))


def test_exactness_of_screened_solve():
    """S3: solution of the reduced problem == full problem solution."""
    X, y, lmax = _setup(300, 120, seed=42)
    theta1 = theta_at_lambda_max(y, jnp.asarray(lmax))
    lam2 = 0.4 * lmax
    keep, _ = screen(X, y, lmax, lam2, theta1)
    keep = np.asarray(keep)

    full = fista_solve(X, y, lam2, max_iters=60000, tol=1e-14)
    idx = np.nonzero(keep)[0]
    Xr = jnp.asarray(np.asarray(X)[idx])
    red = fista_solve(Xr, y, lam2, max_iters=60000, tol=1e-14)

    w_full = np.asarray(full.w)
    w_red = np.zeros_like(w_full)
    w_red[idx] = np.asarray(red.w)
    np.testing.assert_allclose(w_red, w_full, atol=2e-4)
    np.testing.assert_allclose(float(red.obj), float(full.obj), rtol=1e-4)


def test_no_screening_when_lambdas_equal():
    """lam2 == lam1: K degenerates to {theta1}; kept set ⊇ active set at lam1."""
    X, y, lmax = _setup(150, 80, seed=9)
    lam = 0.5 * lmax
    res = fista_solve(X, y, lam, max_iters=50000, tol=1e-14)
    theta, delta = safe_theta_and_delta(X, y, res.w, res.b, jnp.asarray(lam))
    keep, bounds = screen(X, y, lam, lam, theta, delta=delta)
    active = np.abs(np.asarray(res.w)) > ACTIVE_TOL
    assert not np.any(active & ~np.asarray(keep))


def test_screening_becomes_aggressive_near_lambda_max():
    """Rejection rate should grow as lam2 -> lam_max (paper Fig./Table trend)."""
    X, y, lmax = _setup(400, 100, seed=11)
    theta1 = theta_at_lambda_max(y, jnp.asarray(lmax))
    rates = []
    for ratio in (0.95, 0.6, 0.2):
        keep, _ = screen(X, y, lmax, ratio * lmax, theta1)
        rates.append(1.0 - float(np.mean(np.asarray(keep))))
    assert rates[0] >= rates[1] >= rates[2]
    assert rates[0] > 0.5  # near lam_max almost everything screens out


def test_bounds_dtype_stability():
    """fp32 vs fp64 bounds agree to fp32 tolerance (safety under rounding)."""
    X, y, lmax = _setup(200, 100, seed=13)
    theta1 = theta_at_lambda_max(y, jnp.asarray(lmax))
    b32 = np.asarray(screen_bounds(X, y, lmax, 0.3 * lmax, theta1))
    with jax.enable_x64(True):
        X64 = jnp.asarray(np.asarray(X), jnp.float64)
        y64 = jnp.asarray(np.asarray(y), jnp.float64)
        t64 = theta_at_lambda_max(y64, jnp.asarray(lmax, jnp.float64))
        b64 = np.asarray(screen_bounds(X64, y64, lmax, 0.3 * lmax, t64))
    np.testing.assert_allclose(b32, b64, rtol=2e-3, atol=2e-3)


def test_sparse_theta_reduction_exact():
    """paper Sec 6.4: O(m*s) d_theta == dense O(m*n) when s >= nnz(theta)."""
    from repro.core.screening import d_theta_sparse, feature_reductions

    X, y, lmax = _setup(150, 80, seed=17)
    lam = 0.05 * lmax  # small lambda: strong fit => few margin violations
    res = fista_solve(X, y, lam, max_iters=60000, tol=1e-14)
    theta, _ = safe_theta_and_delta(X, y, res.w, res.b, jnp.asarray(lam))
    # the gap certificate's equality projection leaves O(|alpha^T y|/n)
    # dust on theta's zeros (~1e-9 here), so count the support above the
    # dust level, not strict positivity
    t_np = np.asarray(theta)
    nnz = int(np.sum(t_np > 1e-6 * t_np.max()))
    dense = feature_reductions(X, y, theta).d_theta
    sparse = d_theta_sparse(X, y, theta, support=max(nnz, 1))
    np.testing.assert_allclose(np.asarray(sparse), np.asarray(dense),
                               rtol=1e-4, atol=1e-4)
    assert nnz < 80  # sanity: theta is actually sparse at small lambda
