"""Dynamic (in-solver) screening, DVI rule, and path-grid safety tests.

Invariants:
  D1 (solver equiv):   fista_solve_dynamic returns the same solution as
                       fista_solve to solver tolerance, with monotonically
                       non-increasing per-segment kept counts.
  D2 (solver safety):  every dynamically screened feature is inactive at an
                       independently solved high-precision optimum.
  D3 (path safety):    PathDriver(dynamic=True) never changes the accepted
                       path beyond tol, for gather and mask reduction, and
                       its telemetry shows in-solve tightening.
  D4 (refresh hook):   a region rebuilt from a solved iterate via
                       ScreeningRule.refresh screens safely (keeps the
                       support) and at least as hard as the step's
                       sequential region.
  G1 (grid):           a custom grid starting below lambda_max matches an
                       unscreened solve (the closed form must NOT be
                       assumed); increasing / non-positive grids raise.
  V1 (dvi):            the DVI rule is registered, is never looser than
                       feature_vi, and its path matches the unscreened path.
  S1 (dtype):          sample_margin_surplus respects x64 input dtypes for
                       the w1-is-None margin vector.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    DVIRule,
    FeatureVIRule,
    PathDriver,
    available_rules,
    fista_solve,
    fista_solve_dynamic,
    get_rule,
    lambda_max,
)
from repro.core.rules import ConvexRegion, sample_margin_surplus
from repro.data import make_sparse_classification


@pytest.fixture(scope="module")
def inst():
    ds = make_sparse_classification(m=400, n=160, k_active=12, seed=77)
    return ds, jnp.asarray(ds.X), jnp.asarray(ds.y)


# -- D1/D2: dynamic solver ---------------------------------------------------

def test_dynamic_solver_matches_and_tightens(inst):
    _, X, y = inst
    lam = 0.25 * float(lambda_max(X, y))
    ref = fista_solve(X, y, lam, max_iters=20000, tol=1e-11)
    dyn = fista_solve_dynamic(X, y, lam, max_iters=20000, tol=1e-11,
                              screen_every=20)
    np.testing.assert_allclose(float(dyn.obj), float(ref.obj), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(dyn.w), np.asarray(ref.w), atol=1e-4)
    n_seg = int(dyn.n_segments)
    kept = np.asarray(dyn.kept_per_segment)[:n_seg]
    gaps = np.asarray(dyn.gap_per_segment)[:n_seg]
    assert n_seg >= 2
    assert np.all(np.diff(kept) <= 0), kept          # mask only shrinks
    assert kept[-1] < X.shape[0], kept               # and it does shrink
    assert np.all(np.isfinite(gaps)) and np.all(gaps >= 0.0)
    # unused telemetry slots keep their sentinels
    assert np.all(np.asarray(dyn.kept_per_segment)[n_seg:] == -1)


def test_dynamic_screened_features_truly_inactive(inst):
    _, X, y = inst
    lam = 0.3 * float(lambda_max(X, y))
    dyn = fista_solve_dynamic(X, y, lam, max_iters=20000, tol=1e-11,
                              screen_every=20)
    screened = ~np.asarray(dyn.feature_mask)
    assert screened.any()
    full = fista_solve(X, y, lam, max_iters=60000, tol=1e-13)
    assert np.abs(np.asarray(full.w))[screened].max() <= 1e-6


def test_dynamic_solver_respects_seed_mask(inst):
    _, X, y = inst
    m = X.shape[0]
    lam = 0.3 * float(lambda_max(X, y))
    seed = np.ones((m,), np.float32)
    seed[: m // 4] = 0.0  # pretend a sequential screen dropped these
    Xm = X * jnp.asarray(seed)[:, None]
    dyn = fista_solve_dynamic(Xm, y, lam, max_iters=20000, tol=1e-11,
                              screen_every=20, feature_mask=jnp.asarray(seed))
    # seeded zeros never resurrect (check magnitude: a sign-agnostic leak
    # through e.g. an unmasked prox output must fail this too)
    assert not np.asarray(dyn.feature_mask)[: m // 4].any()
    assert np.abs(np.asarray(dyn.w)[: m // 4]).max(initial=0.0) == 0.0


# -- D3: dynamic path safety -------------------------------------------------

@pytest.mark.parametrize("reduce", ["gather", "mask"])
def test_dynamic_path_matches_sequential(inst, reduce):
    ds, _, _ = inst
    kw = dict(tol=1e-10, max_iters=20000, reduce=reduce)
    grid = dict(n_lambdas=6, lam_min_ratio=0.05)
    seq = PathDriver(rules="feature_vi", **kw).run(ds.X, ds.y, **grid)
    dyn = PathDriver(rules="feature_vi", dynamic=True, screen_every=25,
                     **kw).run(ds.X, ds.y, **grid)
    np.testing.assert_allclose(dyn.objectives, seq.objectives,
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(dyn.weights, seq.weights, atol=3e-3)
    tele = dyn.extras["dynamic"]
    assert tele, "dynamic path produced no telemetry"
    # in-solve tightening: some step ends with fewer live features than its
    # between-lambda screen fed the solver
    assert any(
        d["kept_per_segment"] and d["kept_per_segment"][-1] < dyn.kept[k]
        for k, d in tele.items() if k > 0
    ), tele


# -- D4: the refresh protocol hook ------------------------------------------

def test_refresh_region_is_safe_and_tightens(inst):
    _, X, y = inst
    lmax = float(lambda_max(X, y))
    lam1, lam2 = 0.5 * lmax, 0.3 * lmax
    res1 = fista_solve(X, y, jnp.asarray(lam1), max_iters=40000, tol=1e-13)
    res2 = fista_solve(X, y, jnp.asarray(lam2), max_iters=40000, tol=1e-13)
    rule = FeatureVIRule()

    region = rule.refresh(X, y, res2.w, res2.b, lam2)
    assert region.lam1 == region.lam2 == pytest.approx(lam2)
    keep = np.asarray(rule.keep(rule.bounds(X, y, region)))
    support = np.abs(np.asarray(res2.w)) > 1e-7
    assert np.all(keep[support]), "refresh screened an active feature"
    # and it is at least as tight as the sequential lam1 -> lam2 region
    from repro.core.dual import safe_theta_and_delta

    theta1, delta1 = safe_theta_and_delta(X, y, res1.w, res1.b, jnp.asarray(lam1))
    seq_region = ConvexRegion.build(y, lam1, lam2, theta1, delta=delta1)
    keep_seq = np.asarray(rule.keep(rule.bounds(X, y, seq_region)))
    assert keep.sum() <= keep_seq.sum()


# -- G1: custom grids --------------------------------------------------------

def test_custom_grid_below_lambda_max_matches_unscreened(inst):
    ds, X, y = inst
    lmax = float(lambda_max(X, y))
    # starts strictly below lambda_max: step 0 must be SOLVED, not assumed 0
    grid = [0.55 * lmax, 0.35 * lmax, 0.2 * lmax]
    kw = dict(tol=1e-10, max_iters=20000)
    scr = PathDriver(rules="feature_vi", **kw).run(ds.X, ds.y, lambdas=grid)
    off = PathDriver(rules=None, **kw).run(ds.X, ds.y, lambdas=grid)
    np.testing.assert_allclose(scr.objectives, off.objectives,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(scr.weights, off.weights, atol=3e-3)
    # step 0 actually has support (the old closed-form assumption gave w=0)
    assert scr.active[0] > 0
    assert scr.kept[0] == ds.X.shape[0]
    # independent oracle for step 0
    ref0 = fista_solve(X, y, jnp.asarray(grid[0]), max_iters=40000, tol=1e-12)
    np.testing.assert_allclose(scr.objectives[0], float(ref0.obj), rtol=1e-5)


def test_bad_grids_raise(inst):
    ds, _, _ = inst
    driver = PathDriver(rules="feature_vi")
    with pytest.raises(ValueError, match="decreasing"):
        driver.run(ds.X, ds.y, lambdas=[1.0, 2.0, 3.0])
    with pytest.raises(ValueError, match="decreasing"):
        driver.run(ds.X, ds.y, lambdas=[2.0, 2.0])
    with pytest.raises(ValueError, match="positive"):
        driver.run(ds.X, ds.y, lambdas=[1.0, -0.5])
    with pytest.raises(ValueError):
        driver.run(ds.X, ds.y, lambdas=[])


# -- V1: DVI rule ------------------------------------------------------------

def test_dvi_registered_and_no_looser_than_feature_vi(inst):
    ds, _, _ = inst
    assert "dvi" in available_rules()
    assert isinstance(get_rule("dvi"), DVIRule)
    grid = dict(n_lambdas=6, lam_min_ratio=0.05)
    kw = dict(tol=1e-10, max_iters=20000)
    fv = PathDriver(rules="feature_vi", **kw).run(ds.X, ds.y, **grid)
    dvi = PathDriver(rules="dvi", **kw).run(ds.X, ds.y, **grid)
    off = PathDriver(rules=None, **kw).run(ds.X, ds.y, **grid)
    # exactness despite the extra anchor
    np.testing.assert_allclose(dvi.objectives, off.objectives,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(dvi.weights, off.weights, atol=3e-3)
    # min of two valid bounds can only screen more
    assert np.all(dvi.kept <= fv.kept)


def test_dvi_anchor_state_resets_per_path(inst):
    ds, X, y = inst
    rule = DVIRule()
    grid = dict(n_lambdas=4, lam_min_ratio=0.2)
    r1 = PathDriver(rules=rule, tol=1e-9, max_iters=8000).run(ds.X, ds.y, **grid)
    assert rule._anchor is not None
    rule.prepare(X, y)
    assert rule._anchor is None
    r2 = PathDriver(rules=rule, tol=1e-9, max_iters=8000).run(ds.X, ds.y, **grid)
    np.testing.assert_allclose(r1.kept, r2.kept)


# -- DS: dynamic *sample* re-screen ------------------------------------------

def test_dynamic_sample_solver_screens_and_verifies(inst):
    """Solver-level: honest radii screen samples whose margins truly clear."""
    _, X, y = inst
    lam = 0.15 * float(lambda_max(X, y))
    ref = fista_solve(X, y, lam, max_iters=40000, tol=1e-12)
    # warm-start AT the optimum with (essentially) zero movement radii: the
    # margin prediction is then exact, so every screened sample must have
    # margin >= 1 at the optimum — and the objective must not move
    dyn = fista_solve_dynamic(X, y, lam, w0=ref.w, b0=ref.b,
                              max_iters=20000, tol=1e-11, screen_every=10,
                              dynamic_samples=True,
                              sample_dw=1e-4, sample_db=1e-4)
    assert dyn.sample_mask is not None
    screened = ~np.asarray(dyn.sample_mask)
    assert screened.any(), "no sample screened with zero-movement radii"
    margins = np.asarray(y * (X.T @ ref.w + ref.b))
    assert margins[screened].min() >= 1.0 - 1e-4
    np.testing.assert_allclose(float(dyn.obj), float(ref.obj), rtol=1e-5)
    n_seg = int(dyn.n_segments)
    kept_s = np.asarray(dyn.kept_samples_per_segment)[:n_seg]
    assert np.all(np.diff(kept_s) <= 0)  # sample mask only shrinks


def test_dynamic_sample_mask_default_off(inst):
    _, X, y = inst
    lam = 0.3 * float(lambda_max(X, y))
    dyn = fista_solve_dynamic(X, y, lam, max_iters=5000, tol=1e-9,
                              screen_every=25)
    assert dyn.sample_mask is None
    assert dyn.kept_samples_per_segment is None


def test_dynamic_sample_path_exact_with_verification(inst):
    """Path-level: dynamic in-solver sample drops ride the KKT verification
    loop, so the accepted path equals the sequential one."""
    ds, _, _ = inst
    grid = dict(n_lambdas=6, lam_min_ratio=0.05)
    kw = dict(tol=1e-10, max_iters=20000, reduce="mask")
    seq = PathDriver(rules="composite", **kw).run(ds.X, ds.y, **grid)
    dyn = PathDriver(rules="composite", dynamic=True, screen_every=25,
                     **kw).run(ds.X, ds.y, **grid)
    np.testing.assert_allclose(dyn.objectives, seq.objectives,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(dyn.weights, seq.weights, atol=3e-3)
    tele = dyn.extras["dynamic"]
    assert any("kept_samples_per_segment" in d for d in tele.values()), tele


# -- S1: dtype ---------------------------------------------------------------

def test_sample_margin_surplus_respects_x64():
    with jax.enable_x64(True):
        n = 32
        rng = np.random.default_rng(0)
        X = jnp.asarray(rng.standard_normal((8, n)))
        y = jnp.asarray(np.where(rng.random(n) < 0.5, -1.0, 1.0))
        assert X.dtype == jnp.float64
        region = ConvexRegion.build(y, 2.0, 1.0,
                                    jnp.zeros((n,), jnp.float64), b1=0.25)
        surplus, u1 = sample_margin_surplus(X, y, region)
        assert u1.dtype == jnp.float64, u1.dtype
        assert surplus.dtype == jnp.float64, surplus.dtype
