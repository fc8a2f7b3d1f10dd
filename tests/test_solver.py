import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # optional dev dep; see tests/_hyp_compat.py + pyproject
    from _hyp_compat import given, settings, st

from repro.core import fista_solve, lambda_max, lipschitz_estimate, primal_objective
from repro.core import solver
from repro.data import make_sparse_classification


def test_objective_monotone_convergence():
    ds = make_sparse_classification(m=120, n=90, seed=21)
    X, y = jnp.asarray(ds.X), jnp.asarray(ds.y)
    lam = 0.3 * float(lambda_max(X, y))
    r1 = fista_solve(X, y, lam, max_iters=50, tol=0.0)
    r2 = fista_solve(X, y, lam, max_iters=500, tol=0.0)
    r3 = fista_solve(X, y, lam, max_iters=5000, tol=0.0)
    assert float(r1.obj) >= float(r2.obj) >= float(r3.obj) - 1e-6


def test_kkt_conditions_at_solution():
    """Subgradient optimality: |fhat_j^T alpha| <= lam, == lam sign-matched on support."""
    ds = make_sparse_classification(m=100, n=200, seed=22)
    X, y = jnp.asarray(ds.X), jnp.asarray(ds.y)
    lam = 0.25 * float(lambda_max(X, y))
    res = fista_solve(X, y, lam, max_iters=80000, tol=1e-15)
    xi = jnp.maximum(0.0, 1.0 - y * (X.T @ res.w + res.b))
    corr = np.asarray(X @ (y * xi))  # = alpha^T fhat per feature
    w = np.asarray(res.w)
    # inactive: |corr| <= lam (+tol)
    assert np.all(np.abs(corr[np.abs(w) <= 1e-8]) <= lam * (1 + 5e-3) + 1e-4)
    # active: corr ~= sign(w) * lam (paper Eq. 21)
    act = np.abs(w) > 1e-6
    if act.any():
        np.testing.assert_allclose(corr[act], np.sign(w[act]) * lam, rtol=2e-2, atol=1e-3)
    # bias optimality: sum_i alpha_i y_i = 0 (paper Eq. 17)
    assert abs(float(xi @ y)) < 1e-2 * max(1.0, float(jnp.sum(xi)))


def test_warm_start_reduces_iterations():
    ds = make_sparse_classification(m=200, n=150, seed=23)
    X, y = jnp.asarray(ds.X), jnp.asarray(ds.y)
    lmax = float(lambda_max(X, y))
    r1 = fista_solve(X, y, 0.5 * lmax, max_iters=30000, tol=1e-12)
    cold = fista_solve(X, y, 0.45 * lmax, max_iters=30000, tol=1e-12)
    warm = fista_solve(X, y, 0.45 * lmax, w0=r1.w, b0=r1.b, max_iters=30000, tol=1e-12)
    assert int(warm.n_iters) <= int(cold.n_iters)
    np.testing.assert_allclose(float(warm.obj), float(cold.obj), rtol=1e-5)


def test_lipschitz_upper_bounds_spectrum():
    ds = make_sparse_classification(m=80, n=60, seed=24)
    X = jnp.asarray(ds.X)
    L = float(lipschitz_estimate(X, n_iters=80))
    A = np.concatenate([np.asarray(X), np.ones((1, 60))], axis=0)
    true = np.linalg.norm(A, 2) ** 2
    np.testing.assert_allclose(L, true, rtol=1e-2)


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 1000), ratio=st.floats(0.15, 0.9))
def test_solution_agrees_with_scipy_reference(seed, ratio):
    """Cross-check against an independent scipy LBFGS solve of a smoothed dual
    formulation — here instead: verify against scipy.optimize on the primal
    with huberized L1 (tight smoothing), objective within tolerance."""
    import scipy.optimize as sopt

    ds = make_sparse_classification(m=40, n=60, seed=seed)
    X, y = jnp.asarray(ds.X), jnp.asarray(ds.y)
    lam = ratio * float(lambda_max(X, y))
    res = fista_solve(X, y, lam, max_iters=60000, tol=1e-15)

    Xn, yn = np.asarray(X, np.float64), np.asarray(y, np.float64)

    def obj(z):
        w, b = z[:-1], z[-1]
        xi = np.maximum(0.0, 1.0 - yn * (Xn.T @ w + b))
        return 0.5 * xi @ xi + lam * np.sum(np.sqrt(w * w + 1e-12))

    z0 = np.concatenate([np.asarray(res.w, np.float64), [float(res.b)]])
    out = sopt.minimize(obj, np.zeros_like(z0), method="L-BFGS-B",
                        options={"maxiter": 5000, "ftol": 1e-14})
    ours = float(primal_objective(X, y, res.w, res.b, lam))
    assert ours <= out.fun + 1e-3 * max(1.0, abs(out.fun))


@pytest.fixture(scope="module")
def feas_problem():
    """A problem at half its lambda_max, a converged iterate of it, and the
    dual optimum from a tight solve."""
    ds = make_sparse_classification(m=120, n=90, seed=21)
    X, y = jnp.asarray(ds.X), jnp.asarray(ds.y)
    lam = jnp.float32(0.5 * float(lambda_max(X, y)))
    solved = fista_solve(X, y, lam, max_iters=5000, tol=1e-9)
    tight = fista_solve(X, y, lam, max_iters=80000, tol=1e-15)
    theta_ref = jnp.maximum(0.0, 1.0 - y * (X.T @ tight.w + tight.b)) / lam
    points = {"zero": (jnp.zeros((X.shape[0],), X.dtype), jnp.float32(0.0)),
              "solved": (solved.w, solved.b)}
    return X, y, lam, points, theta_ref


@pytest.mark.parametrize("forced,n_feas_iters,point,want_rounds", [
    *[(True, n, p, n + 1) for n in (0, 1, 8) for p in ("zero", "solved")],
    (False, 8, "zero", 2),
    (False, 8, "solved", 2),
])
def test_feasibility_rounds_stop_early_within_the_cap_and_stay_safe(
        feas_problem, monkeypatch, forced, n_feas_iters, point, want_rounds):
    """The certificate's rounds stop after the first that does not project.
    Forced to bind every round (a negative binding tolerance), they run the
    cap, ``n_feas_iters + 1``; left alone, the first round binds and the
    second finds the max at ``lam``. Either way the certificate is
    feasible and its radius covers the distance to the dual optimum."""
    X, y, lam, points, theta_ref = feas_problem
    if forced:
        monkeypatch.setattr(solver, "FEAS_BINDING_RTOL", -0.5)
    w, b = points[point]
    theta, delta, _gap, binding, rounds = solver.gap_theta_delta_binding(
        X, y, w, b, lam, n_feas_iters=n_feas_iters)
    assert int(rounds) == want_rounds
    assert int(binding) == (want_rounds if forced else 1)
    assert float(jnp.min(theta)) >= 0.0
    corr = np.asarray(X @ (y * theta * lam))
    assert np.max(np.abs(corr)) <= float(lam) * (1 + 1e-6)
    assert float(delta) >= float(jnp.linalg.norm(theta - theta_ref))
