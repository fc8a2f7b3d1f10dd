"""Out-of-core + sparse-matrix engine tests (repro/sparse).

Invariants:
  C1 (bitwise screen):  the chunk-streamed bound sweep equals the in-core
                        sweep BITWISE, for every chunking (incl. ragged) —
                        the row-stable reduction contract.
  C2 (container):       from_dense / from_csr round-trip exactly;
                        gather_rows returns the exact rows.
  C3 (solver seam):     fista_solve(operator=FeatureChunked) matches the
                        dense solver's objective to solver tolerance.
  C4 (BCOO tolerance):  low-density CSR chunks sweep as BCOO; matvec pair
                        and bound sweep agree with dense to fp32 tolerance.
  C5 (memory shape):    no per-chunk kernel traces an intermediate of the
                        full (m, n) shape — the device never holds more
                        than one chunk of X (stats observe the transfers).
  C6 (path):            the chunked screened path matches the in-core host
                        driver (objectives <= 1e-6; bitwise with a shared
                        Lipschitz bound) for feature, sample and dynamic
                        configs; mask reduce / program-less rules / scan
                        engines rejected loudly.
  C7 (data):            sparse synthetic datasets carry an exact CSR view;
                        the libsvm loader parses indices/labels correctly
                        (gzip input, comments, dtype override).
  C8 (chunk skip):      chunk-level gating is safe (a skipped chunk's
                        stamped bounds sit below tau and agree with the
                        fresh sweep) and free (the skip path is bitwise
                        equal to the full-stream twin, transferring
                        strictly fewer chunks); the mmap store round-trips.

The CI ``stream`` lane runs this file with REPRO_STREAM_CHUNK_M forcing a
small, ragged chunk size.
"""

import gzip
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import PathDriver, fista_solve, lambda_max, screen, \
    theta_at_lambda_max
from repro.core.dual import safe_theta_and_delta
from repro.data import load_libsvm, make_sparse_classification
from repro.sparse import (
    BCOO_DENSITY_THRESHOLD,
    ChunkScreenCache,
    FeatureChunked,
    fista_solve_chunked,
    lambda_max_stream,
    lipschitz_estimate_stream,
    screen_step_stream,
    screen_stream,
    stream_feature_reductions,
)

# the CI stream lane forces a small (deliberately ragged) chunk size so the
# suite exercises many-chunk paths even on the small test instances
ENV_CHUNK_M = int(os.environ.get("REPRO_STREAM_CHUNK_M", "64"))


@pytest.fixture(scope="module")
def dense_inst():
    ds = make_sparse_classification(m=300, n=130, k_active=12, seed=21)
    return ds, jnp.asarray(ds.X), jnp.asarray(ds.y)


@pytest.fixture(scope="module")
def planted_inst():
    """Informative head block + weak noise tail: features past row 64 have
    tiny norms, so whole tail chunks screen out early and *stay* dead —
    the geometry chunk-level gating is built for."""
    ds = make_sparse_classification(m=320, n=120, k_active=8, seed=7)
    X = np.array(ds.X, copy=True)
    X[64:] *= 0.05
    return X, np.asarray(ds.y)


@pytest.fixture(scope="module")
def sparse_inst():
    ds = make_sparse_classification(m=300, n=130, k_active=12, seed=23,
                                    density=0.04)
    return ds, jnp.asarray(ds.X), jnp.asarray(ds.y)


# -- C1: bitwise bound sweep --------------------------------------------------

@pytest.mark.parametrize("chunk_m", [ENV_CHUNK_M, 97, 300])
def test_stream_bounds_bitwise_vs_dense(dense_inst, chunk_m):
    ds, X, y = dense_inst
    lmax = float(lambda_max(X, y))
    theta1 = theta_at_lambda_max(y, jnp.asarray(lmax))
    keep_d, bounds_d = screen(X, y, lmax, 0.6 * lmax, theta1)

    fc = FeatureChunked.from_dense(ds.X, chunk_m=chunk_m)
    keep_s, bounds_s = screen_stream(fc, ds.y, lmax, 0.6 * lmax, theta1)
    np.testing.assert_array_equal(np.asarray(bounds_s), np.asarray(bounds_d))
    np.testing.assert_array_equal(np.asarray(keep_s), np.asarray(keep_d))


def test_stream_bounds_bitwise_with_delta(dense_inst):
    """The inexact-anchor (delta > 0) scalar path is shared too."""
    ds, X, y = dense_inst
    lmax = float(lambda_max(X, y))
    lam1 = 0.5 * lmax
    res = fista_solve(X, y, lam1, max_iters=20000, tol=1e-11)
    theta1, delta = safe_theta_and_delta(X, y, res.w, res.b, jnp.asarray(lam1))
    _, bounds_d = screen(X, y, lam1, 0.8 * lam1, theta1, delta=delta)
    fc = FeatureChunked.from_dense(ds.X, chunk_m=ENV_CHUNK_M)
    _, bounds_s = screen_stream(fc, ds.y, lam1, 0.8 * lam1, theta1,
                                delta=delta)
    np.testing.assert_array_equal(np.asarray(bounds_s), np.asarray(bounds_d))


def test_lambda_max_stream_bitwise(dense_inst):
    ds, X, y = dense_inst
    fc = FeatureChunked.from_dense(ds.X, chunk_m=ENV_CHUNK_M)
    assert float(lambda_max_stream(fc, ds.y)) == float(lambda_max(X, y))


# -- C2: container ------------------------------------------------------------

def test_container_round_trip(sparse_inst):
    ds, _, _ = sparse_inst
    fc_d = FeatureChunked.from_dense(ds.X, chunk_m=ENV_CHUNK_M)
    np.testing.assert_array_equal(fc_d.as_dense(), ds.X)
    fc_c = FeatureChunked.from_csr(ds.csr, chunk_m=ENV_CHUNK_M)
    np.testing.assert_array_equal(fc_c.as_dense(), ds.X)
    assert fc_c.shape == ds.X.shape
    assert abs(fc_c.density() - ds.csr.density) < 1e-12

    idx = np.asarray([0, 5, ENV_CHUNK_M, ds.X.shape[0] - 1])
    np.testing.assert_array_equal(fc_c.gather_rows(idx), ds.X[idx])
    np.testing.assert_array_equal(fc_d.gather_rows(idx), ds.X[idx])


def test_container_matches_scipy_csr(sparse_inst):
    """Cross-check our numpy CSR triple against scipy's (optional extra)."""
    sp = pytest.importorskip("scipy.sparse")
    ds, _, _ = sparse_inst
    ref = sp.csr_matrix(ds.X)
    np.testing.assert_array_equal(ds.csr.indptr, ref.indptr)
    np.testing.assert_array_equal(ds.csr.indices, ref.indices)
    np.testing.assert_array_equal(ds.csr.data, ref.data)
    fc = FeatureChunked.from_csr(ref, chunk_m=ENV_CHUNK_M)  # scipy accepted
    np.testing.assert_array_equal(fc.as_dense(), ds.X)


# -- C3: solver seam ----------------------------------------------------------

def test_chunked_solver_matches_dense(dense_inst):
    ds, X, y = dense_inst
    lam = 0.3 * float(lambda_max(X, y))
    ref = fista_solve(X, y, lam, max_iters=20000, tol=1e-10)
    fc = FeatureChunked.from_dense(ds.X, chunk_m=ENV_CHUNK_M)
    # the operator= seam on the standard entry point
    ch = fista_solve(None, ds.y, lam, max_iters=20000, tol=1e-10, operator=fc)
    assert abs(float(ch.obj) - float(ref.obj)) / float(ref.obj) < 1e-6
    np.testing.assert_allclose(np.asarray(ch.w), np.asarray(ref.w), atol=1e-3)
    assert bool(ch.converged)
    # u is carried like the fused in-core body's
    np.testing.assert_allclose(np.asarray(ch.u),
                               np.asarray(X.T @ ch.w), atol=1e-4)


def test_chunked_solver_warm_start_and_mask(dense_inst):
    ds, X, y = dense_inst
    n = ds.X.shape[1]
    lam = 0.35 * float(lambda_max(X, y))
    sm = np.ones((n,), np.float32)
    sm[: n // 5] = 0.0
    ref = fista_solve(X, y, lam, max_iters=20000, tol=1e-10,
                      sample_mask=jnp.asarray(sm))
    fc = FeatureChunked.from_dense(ds.X, chunk_m=ENV_CHUNK_M)
    ch = fista_solve_chunked(fc, ds.y, lam, w0=ref.w, b0=ref.b,
                             max_iters=20000, tol=1e-10,
                             sample_mask=jnp.asarray(sm))
    assert abs(float(ch.obj) - float(ref.obj)) / float(ref.obj) < 1e-6


def test_lipschitz_stream_close(dense_inst):
    ds, X, _ = dense_inst
    from repro.core.solver import lipschitz_estimate

    Ld = float(lipschitz_estimate(X))
    Ls = float(lipschitz_estimate_stream(
        FeatureChunked.from_dense(ds.X, chunk_m=ENV_CHUNK_M)))
    assert abs(Ld - Ls) / Ld < 1e-4


# -- C4: BCOO route -----------------------------------------------------------

def test_bcoo_selected_below_threshold(sparse_inst):
    ds, _, _ = sparse_inst
    fc = FeatureChunked.from_csr(ds.csr, chunk_m=ENV_CHUNK_M)
    assert ds.csr.density <= BCOO_DENSITY_THRESHOLD
    list(fc.stream())
    assert fc.stats["bcoo_puts"] > 0
    # a dense-threshold container densifies instead
    fc2 = FeatureChunked.from_csr(ds.csr, chunk_m=ENV_CHUNK_M,
                                  bcoo_threshold=0.0)
    list(fc2.stream())
    assert fc2.stats["bcoo_puts"] == 0


def test_bcoo_margin_sweep_tolerance(sparse_inst):
    """BCOO matvec pair + bound sweep vs dense, fp32 tolerance."""
    ds, X, y = sparse_inst
    fc = FeatureChunked.from_csr(ds.csr, chunk_m=ENV_CHUNK_M)
    rng = np.random.default_rng(3)
    v = jnp.asarray(rng.standard_normal(ds.X.shape[1]).astype(np.float32))
    w = jnp.asarray(rng.standard_normal(ds.X.shape[0]).astype(np.float32))
    np.testing.assert_allclose(np.asarray(fc.matvec(v)), np.asarray(X @ v),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(fc.rmatvec(w)), np.asarray(X.T @ w),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(fc.row_sq()),
                               np.asarray(jnp.sum(X * X, axis=1)),
                               rtol=2e-4, atol=2e-4)

    lmax = float(lambda_max(X, y))
    theta1 = theta_at_lambda_max(y, jnp.asarray(lmax))
    keep_d, bounds_d = screen(X, y, lmax, 0.6 * lmax, theta1)
    keep_s, bounds_s = screen_stream(fc, ds.y, lmax, 0.6 * lmax, theta1)
    np.testing.assert_allclose(np.asarray(bounds_s), np.asarray(bounds_d),
                               rtol=2e-4, atol=2e-4)
    # decisions agree away from the tau boundary (the tau margin is sized
    # to absorb exactly this class of reassociation noise)
    mism = int(np.sum(np.asarray(keep_s) != np.asarray(keep_d)))
    assert mism <= 2, mism


def test_bcoo_solver_matches_dense(sparse_inst):
    ds, X, y = sparse_inst
    lam = 0.3 * float(lambda_max(X, y))
    ref = fista_solve(X, y, lam, max_iters=20000, tol=1e-10)
    fc = FeatureChunked.from_csr(ds.csr, chunk_m=ENV_CHUNK_M)
    ch = fista_solve_chunked(fc, ds.y, lam, max_iters=20000, tol=1e-10)
    assert abs(float(ch.obj) - float(ref.obj)) / float(ref.obj) < 1e-5


# -- C5: memory-shape property ------------------------------------------------

def _walk_avals(jaxpr):
    """All intermediate avals of a (closed) jaxpr, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        for v in (*eqn.invars, *eqn.outvars):
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                yield aval
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk_avals(sub)


def test_no_full_matrix_in_chunk_jaxprs(dense_inst):
    """No per-chunk kernel ever traces a (m, n)-sized intermediate."""
    ds, _, _ = dense_inst
    m, n = ds.X.shape
    chunk_m = ENV_CHUNK_M if ENV_CHUNK_M < m else 64
    from repro.core.screening import _row_stable_reductions, row_dot
    from repro.sparse.chunked import _chunk_csq, _chunk_mv, _chunk_rmv, \
        _chunk_sq

    Xc = jnp.zeros((chunk_m, n), jnp.float32)
    v = jnp.zeros((n,), jnp.float32)
    wc = jnp.zeros((chunk_m,), jnp.float32)
    traced = [
        jax.make_jaxpr(_chunk_mv)(Xc, v),
        jax.make_jaxpr(_chunk_rmv)(Xc, wc),
        jax.make_jaxpr(_chunk_sq)(Xc),
        jax.make_jaxpr(_chunk_csq)(Xc),
        jax.make_jaxpr(row_dot)(Xc, v),
        jax.make_jaxpr(_row_stable_reductions)(Xc, v, v),
    ]
    cap = chunk_m * n  # one chunk; the (m, n) matrix is m//chunk_m x larger
    for jx in traced:
        for aval in _walk_avals(jx.jaxpr):
            assert int(np.prod(aval.shape or (1,))) <= cap, (
                f"chunk kernel traced an aval of shape {aval.shape} "
                f"(> one chunk {chunk_m}x{n})"
            )
            assert tuple(aval.shape) != (m, n)


def test_stream_stats_observe_device_contract(dense_inst):
    """A chunk_m << m run never puts more than chunk_m rows at once."""
    ds, _, _ = dense_inst
    m = ds.X.shape[0]
    chunk_m = 48
    fc = FeatureChunked.from_dense(ds.X, chunk_m=chunk_m)
    stream_feature_reductions(fc, ds.y, jnp.zeros((ds.X.shape[1],)))
    fista_solve_chunked(fc, ds.y, 1.0, max_iters=5, tol=0.0)
    assert fc.stats["puts"] > 0
    assert fc.stats["max_put_rows"] == chunk_m < m


# -- C6: chunked path ---------------------------------------------------------

def test_chunked_path_matches_host(dense_inst):
    ds, X, y = dense_inst
    from repro.core.solver import lipschitz_estimate

    # shared L isolates storage (see PathDriver docstring); grids already
    # match bitwise via the row-stable lambda_max
    L = lipschitz_estimate(X)
    kw = dict(rules="feature_vi", tol=1e-10, max_iters=20000, L=L)
    grid = dict(n_lambdas=6, lam_min_ratio=0.1)
    host = PathDriver(**kw).run(ds.X, ds.y, **grid)
    fc = FeatureChunked.from_dense(ds.X, chunk_m=ENV_CHUNK_M)
    ch = PathDriver(**kw).run(fc, ds.y, **grid)
    np.testing.assert_array_equal(host.lambdas, ch.lambdas)
    rel = np.max(np.abs(host.objectives - ch.objectives)
                 / np.maximum(np.abs(host.objectives), 1.0))
    assert rel < 1e-6, rel
    np.testing.assert_allclose(ch.weights, host.weights, atol=1e-3)
    assert ch.extras["storage"] == "chunked"
    assert ch.extras["stream_stats"]["max_put_rows"] <= max(ENV_CHUNK_M, 64)


def test_chunked_path_self_contained(dense_inst):
    """No in-core inputs at all: streamed L, streamed certification."""
    ds, X, y = dense_inst
    kw = dict(rules="feature_vi", tol=1e-10, max_iters=20000)
    grid = dict(n_lambdas=5, lam_min_ratio=0.15)
    host = PathDriver(**kw).run(ds.X, ds.y, **grid)
    ch = PathDriver(**kw).run(
        FeatureChunked.from_dense(ds.X, chunk_m=ENV_CHUNK_M), ds.y, **grid)
    rel = np.max(np.abs(host.objectives - ch.objectives)
                 / np.maximum(np.abs(host.objectives), 1.0))
    assert rel < 1e-5, rel  # fp32 plateau floor (see PathDriver docstring)


def test_chunked_path_sample_rules_match_host(dense_inst):
    """sifs (EDPP feature half + verified sample half) out of core: the
    transposed sweep feeds the margins, verification rides the carried u."""
    ds, X, y = dense_inst
    from repro.core.solver import lipschitz_estimate

    L = lipschitz_estimate(X)
    kw = dict(rules="sifs", tol=1e-10, max_iters=20000, L=L)
    grid = dict(n_lambdas=10, lam_min_ratio=0.02)
    host = PathDriver(**kw).run(ds.X, ds.y, **grid)
    ch = PathDriver(**kw).run(
        FeatureChunked.from_dense(ds.X, chunk_m=ENV_CHUNK_M), ds.y, **grid)
    rel = np.max(np.abs(host.objectives - ch.objectives)
                 / np.maximum(np.abs(host.objectives), 1.0))
    assert rel < 1e-6, rel  # verification makes any sample screen exact
    assert "sample_masks" in ch.extras
    np.testing.assert_array_equal(ch.kept_samples, host.kept_samples)
    n = ds.X.shape[1]
    assert np.any(ch.kept_samples[1:] < n)  # the screen actually fires


def test_chunked_path_dynamic_matches_host(dense_inst):
    """dynamic=True routes to the segmented streamed solver; objectives
    still match the (non-dynamic) host path."""
    ds, X, y = dense_inst
    kw = dict(rules="feature_vi", tol=1e-10, max_iters=20000)
    grid = dict(n_lambdas=5, lam_min_ratio=0.15)
    host = PathDriver(**kw).run(ds.X, ds.y, **grid)
    ch = PathDriver(dynamic=True, screen_every=40, **kw).run(
        FeatureChunked.from_dense(ds.X, chunk_m=ENV_CHUNK_M), ds.y, **grid)
    rel = np.max(np.abs(host.objectives - ch.objectives)
                 / np.maximum(np.abs(host.objectives), 1.0))
    assert rel < 1e-5, rel
    assert "dynamic" in ch.extras


def test_chunked_path_rejects_unsupported_configs(dense_inst):
    ds, _, _ = dense_inst
    fc = FeatureChunked.from_dense(ds.X, chunk_m=ENV_CHUNK_M)
    with pytest.raises(ValueError, match="gather"):
        PathDriver(rules="feature_vi", reduce="mask").run(fc, ds.y)

    from repro.core.rules.base import (AXIS_FEATURES, AXIS_SAMPLES,
                                       ScreeningRule)

    class _NoProgram(ScreeningRule):
        axis = AXIS_FEATURES

        def bounds(self, X, y, region):  # pragma: no cover - never reached
            raise NotImplementedError

    with pytest.raises(ValueError, match="feature rule"):
        PathDriver(rules=[_NoProgram()]).run(fc, ds.y)

    class _OddSample(ScreeningRule):
        axis = AXIS_SAMPLES

        def bounds(self, X, y, region):  # pragma: no cover - never reached
            raise NotImplementedError

    with pytest.raises(ValueError, match="SampleVIRule"):
        PathDriver(rules=[_OddSample()]).run(fc, ds.y)
    from repro.core import svm_path

    with pytest.raises(ValueError, match="scan"):
        svm_path(fc, ds.y, engine="scan")


# -- C8: chunk-skip data plane + disk-resident store --------------------------

def test_chunk_skip_bitwise_vs_full_stream(planted_inst):
    """The gated path is the full-stream path minus transfers: identical
    gating/cache policy in both modes, so objectives, weights and kept
    counts are bitwise equal while the skip side streams strictly fewer
    chunks (and actually skips some)."""
    X, y = planted_inst
    kw = dict(rules="feature_vi", tol=1e-9, max_iters=8000)
    grid = dict(n_lambdas=8, lam_min_ratio=0.05)
    fc_skip = FeatureChunked.from_dense(X, chunk_m=32)
    r_skip = PathDriver(chunk_skip=True, **kw).run(fc_skip, y, **grid)
    fc_full = FeatureChunked.from_dense(X, chunk_m=32)
    r_full = PathDriver(chunk_skip=False, **kw).run(fc_full, y, **grid)

    np.testing.assert_array_equal(r_skip.objectives, r_full.objectives)
    np.testing.assert_array_equal(r_skip.weights, r_full.weights)
    np.testing.assert_array_equal(r_skip.kept, r_full.kept)

    st = r_skip.extras["stream_stats"]
    assert st["chunks_skipped"] > 0
    assert st["chunks_streamed"] < r_full.extras["stream_stats"][
        "chunks_streamed"]
    assert st["bytes_put"] < r_full.extras["stream_stats"]["bytes_put"]
    # gating visibly shrank the live set on some step
    assert int(np.min(r_skip.extras["live_chunks"])) < fc_skip.n_chunks
    assert r_skip.extras["chunk_skip"] and not r_full.extras["chunk_skip"]


def test_skipped_chunk_bounds_safe(planted_inst):
    """Safety property of chunk gating: every chunk the cache declares dead
    has (a) all stamped stale bounds below tau, and (b) a fresh full sweep
    from the same anchor agrees — no feature the fresh screen would keep is
    ever gated away. With identical anchors the gated and fresh sweeps
    produce the same keep decisions."""
    X, y = planted_inst
    fc = FeatureChunked.from_dense(X, chunk_m=32)
    lmax = float(lambda_max_stream(fc, y))
    theta1 = theta_at_lambda_max(jnp.asarray(y), jnp.asarray(lmax))

    cache = ChunkScreenCache(fc)
    # first gated step: empty cache, every chunk streams + refreshes
    screen_step_stream(fc, y, lmax, 0.7 * lmax, theta1, cache=cache)
    # second step re-uses the cached (lmax, theta1) anchors for gating
    keep_g, bounds_g, _, live = screen_step_stream(
        fc, y, lmax, 0.5 * lmax, theta1, cache=cache)
    assert not live.all(), "planted instance must trigger gating"
    assert live.any()

    keep_f, bounds_f = screen_stream(
        FeatureChunked.from_dense(X, chunk_m=32), y, lmax, 0.5 * lmax, theta1)
    from repro.core.screening import SAFE_TAU

    bounds_g, bounds_f = np.asarray(bounds_g), np.asarray(bounds_f)
    for i in np.nonzero(~live)[0]:
        s, e = fc.chunk_bounds(int(i))
        assert np.all(bounds_g[s:e] < SAFE_TAU)  # stamped bounds honest
        assert np.all(bounds_f[s:e] < SAFE_TAU)  # fresh sweep agrees
    np.testing.assert_array_equal(np.asarray(keep_g), np.asarray(keep_f))


def test_chunk_cache_refuses_larger_targets(planted_inst):
    """A cached region certifies only strictly smaller lambdas: gating at a
    target >= the cached anchor's lambda must declare every chunk live."""
    X, y = planted_inst
    fc = FeatureChunked.from_dense(X, chunk_m=32)
    lmax = float(lambda_max_stream(fc, y))
    theta1 = theta_at_lambda_max(jnp.asarray(y), jnp.asarray(lmax))
    cache = ChunkScreenCache(fc)
    screen_step_stream(fc, y, lmax, 0.6 * lmax, theta1, cache=cache)
    from repro.core.screening import fixed_stats
    from repro.sparse import fixed_reductions

    d_one, d_y, d_sq = fixed_reductions(fc, y)
    fixed = fixed_stats(jnp.asarray(y, fc.dtype), d_one, d_y, d_sq)
    live, _ = cache.live_mask(lmax, fixed)
    assert live.all()


def test_col_sq_matches_dense(sparse_inst):
    """The transposed reduction (CSR host scatter + dense kernel) and its
    memoization."""
    ds, X, _ = sparse_inst
    ref = np.asarray(jnp.sum(X * X, axis=0))
    fc = FeatureChunked.from_csr(ds.csr, chunk_m=ENV_CHUNK_M)
    np.testing.assert_allclose(np.asarray(fc.col_sq()), ref,
                               rtol=2e-4, atol=2e-4)
    assert fc.col_sq() is fc.col_sq()  # theta-independent: memoized
    fcd = FeatureChunked.from_dense(ds.X, chunk_m=ENV_CHUNK_M)
    np.testing.assert_allclose(np.asarray(fcd.col_sq()), ref,
                               rtol=2e-4, atol=2e-4)


_TOY_LIBSVM = (
    "+1 1:0.5 3:-2.0\n"
    "-1 2:1.25\n"
    "+1 1:3.0 4:0.125\n"
    "-1 3:0.75\n"
)


def test_memmap_store_roundtrip(tmp_path):
    p = tmp_path / "toy.svm"
    p.write_text(_TOY_LIBSVM)
    ref = load_libsvm(p)

    fc, yv = FeatureChunked.from_libsvm_cached(
        p, store_dir=tmp_path / "store", chunk_m=2)
    np.testing.assert_array_equal(np.asarray(fc.as_dense()), ref.X)
    np.testing.assert_array_equal(np.asarray(yv), ref.y)
    # second open re-uses the store (and may re-slice the chunking)
    fc2, y2 = FeatureChunked.from_libsvm_cached(
        p, store_dir=tmp_path / "store", chunk_m=3)
    np.testing.assert_array_equal(np.asarray(fc2.as_dense()), ref.X)
    np.testing.assert_array_equal(np.asarray(y2), ref.y)

    # gzip input builds the same store
    pgz = tmp_path / "toy.svm.gz"
    with gzip.open(pgz, "wt") as f:
        f.write(_TOY_LIBSVM)
    fcz, yz = FeatureChunked.from_libsvm_cached(
        pgz, store_dir=tmp_path / "gz_store", chunk_m=2)
    np.testing.assert_array_equal(np.asarray(fcz.as_dense()), ref.X)
    np.testing.assert_array_equal(np.asarray(yz), ref.y)


def test_memmap_store_runs_screened_path(tmp_path, planted_inst):
    """End to end: dense store on disk -> memmap container -> gated path."""
    X, y = planted_inst
    fc_mem = FeatureChunked.from_dense(X, chunk_m=32)
    store = tmp_path / "planted_store"
    fc_mem.save_store(store, y=y)
    fc = FeatureChunked.from_store(store, chunk_m=32)
    assert fc.labels is not None
    res = PathDriver(rules="feature_vi", tol=1e-9, max_iters=8000).run(
        fc, fc.labels, n_lambdas=6, lam_min_ratio=0.05)
    assert res.extras["stream_stats"]["chunks_skipped"] > 0
    ref = PathDriver(rules="feature_vi", tol=1e-9, max_iters=8000).run(
        FeatureChunked.from_dense(X, chunk_m=32), y,
        n_lambdas=6, lam_min_ratio=0.05)
    np.testing.assert_array_equal(res.objectives, ref.objectives)


# -- C7: data -----------------------------------------------------------------

def test_sparse_dataset_carries_exact_csr():
    ds = make_sparse_classification(m=64, n=40, density=0.3, seed=5)
    assert ds.csr is not None
    np.testing.assert_array_equal(ds.csr.to_dense(ds.X.dtype), ds.X)
    # sparsity is real (scale-only standardization keeps the zeros)
    assert 0.0 < ds.csr.density < 0.5
    dense = make_sparse_classification(m=64, n=40, seed=5)
    assert dense.csr is None


def test_libsvm_loader(tmp_path):
    p = tmp_path / "toy.svm"
    p.write_text(
        "+1 1:0.5 3:-2.0\n"
        "-1 2:1.25\n"
        "# comment line\n"
        "0 1:3.0 4:0.125  # trailing comment\n"
    )
    ds = load_libsvm(p)
    assert ds.X.shape == (4, 3)  # 4 features (max index), 3 samples
    np.testing.assert_array_equal(ds.y, [1.0, -1.0, -1.0])
    assert ds.X[0, 0] == np.float32(0.5)
    assert ds.X[2, 0] == np.float32(-2.0)
    assert ds.X[1, 1] == np.float32(1.25)
    assert ds.X[3, 2] == np.float32(0.125)
    assert ds.csr is not None and ds.csr.nnz == 5
    # n_features override + zero-based indexing
    ds2 = load_libsvm(p, n_features=6)
    assert ds2.X.shape == (6, 3)
    with pytest.raises(ValueError):
        load_libsvm(p, n_features=2)
    fc = FeatureChunked.from_csr(ds.csr, chunk_m=2)
    np.testing.assert_array_equal(fc.as_dense(), ds.X)


def test_libsvm_loader_gzip_and_dtype(tmp_path):
    p = tmp_path / "toy.svm"
    p.write_text(_TOY_LIBSVM)
    ref = load_libsvm(p)
    # gzip is detected from the magic bytes, not the extension
    pgz = tmp_path / "toy.svm.gz"
    with gzip.open(pgz, "wt") as f:
        f.write(_TOY_LIBSVM)
    dz = load_libsvm(pgz)
    np.testing.assert_array_equal(dz.X, ref.X)
    np.testing.assert_array_equal(dz.y, ref.y)
    # dtype override flows through X, y and the CSR view
    d64 = load_libsvm(p, dtype=np.float64)
    assert d64.X.dtype == np.float64
    np.testing.assert_allclose(d64.X, ref.X.astype(np.float64))
    assert d64.csr is not None and d64.csr.data.dtype == np.float64
