"""On-device path engine: scan-vs-host equivalence, compact-vs-mask
reduction equivalence (incl. the overflow fallback), the sharded scan's
bitwise port check, Pallas-vs-XLA solver equivalence (interpret mode), the
shared-Lipschitz upper-bound property, and batched-vs-single equivalence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    PathDriver,
    compact_caps,
    compact_caps_batched,
    fista_solve,
    lambda_max,
    lipschitz_estimate,
    svm_path,
    svm_path_batched,
    svm_path_scan,
    svm_path_scan_sharded,
)
from repro.core.distributed import svm_mesh
from repro.data import make_sparse_classification

GRID = dict(n_lambdas=6, lam_min_ratio=0.15)
SOLVE = dict(tol=1e-11, max_iters=20000)


@pytest.fixture(scope="module")
def ds():
    return make_sparse_classification(m=300, n=120, k_active=10, seed=41)


@pytest.fixture(scope="module")
def host_path(ds):
    return PathDriver(rules="feature_vi", **SOLVE).run(ds.X, ds.y, **GRID)


@pytest.fixture(scope="module")
def scan_path(ds):
    return svm_path_scan(ds.X, ds.y, **GRID, **SOLVE)


def test_scan_matches_host_screened(host_path, scan_path):
    """Same grid, same solutions: objectives to 1e-6 (relative), weights to
    fp32 solver resolution."""
    np.testing.assert_allclose(scan_path.lambdas, host_path.lambdas)
    rel = np.max(np.abs(host_path.objectives - scan_path.objectives)
                 / np.maximum(np.abs(host_path.objectives), 1.0))
    assert rel < 1e-6, rel
    np.testing.assert_allclose(scan_path.weights, host_path.weights, atol=1e-3)
    np.testing.assert_allclose(scan_path.biases, host_path.biases, atol=1e-3)


def test_scan_matches_host_unscreened(ds):
    h = PathDriver(rules=[], **SOLVE).run(ds.X, ds.y, **GRID)
    s = svm_path_scan(ds.X, ds.y, screening=False, **GRID, **SOLVE)
    rel = np.max(np.abs(h.objectives - s.objectives)
                 / np.maximum(np.abs(h.objectives), 1.0))
    assert rel < 1e-6, rel
    assert np.all(s.kept == ds.X.shape[0])
    assert not s.screened


def test_scan_never_screens_an_active_feature(scan_path):
    """Safety end-to-end: a screened (masked-out) feature is never active."""
    for k in range(len(scan_path.lambdas)):
        assert scan_path.active[k] <= scan_path.kept[k]
    assert scan_path.extras["converged"].all()


def test_scan_dynamic_matches_sequential(ds, scan_path):
    dyn = svm_path_scan(ds.X, ds.y, dynamic=True, screen_every=25,
                        **GRID, **SOLVE)
    rel = np.max(np.abs(dyn.objectives - scan_path.objectives)
                 / np.maximum(np.abs(scan_path.objectives), 1.0))
    assert rel < 1e-6, rel


def test_svm_path_engine_dispatch(ds, scan_path):
    via = svm_path(ds.X, ds.y, engine="scan", **GRID, **SOLVE)
    np.testing.assert_allclose(via.objectives, scan_path.objectives, rtol=1e-7)
    assert via.extras["engine"] == "scan"
    with pytest.raises(ValueError, match="engine"):
        svm_path(ds.X, ds.y, engine="warp")
    with pytest.raises(ValueError, match="feature rule"):
        svm_path(ds.X, ds.y, engine="scan", rules="composite")


def test_scan_grid_validation(ds):
    with pytest.raises(ValueError, match="decreasing"):
        svm_path_scan(ds.X, ds.y, lambdas=[0.1, 0.2])
    with pytest.raises(ValueError, match="positive"):
        svm_path_scan(ds.X, ds.y, lambdas=[0.1, -0.2])


# ---------------------------------------------------------------------------
# Compact reduction: on-device active-set gather
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def compact_path(ds):
    return svm_path_scan(ds.X, ds.y, reduce="compact", **GRID, **SOLVE)


def test_compact_matches_mask_and_host(ds, scan_path, host_path, compact_path):
    """The gathered subproblem is the masked problem with screened rows
    physically absent: same inv_L, same iteration map => objectives match to
    solver resolution (a few fp32 ulps of the objective — the gathered GEMV
    reassociates, so the two trajectories stop 1-2 ulps apart; the bench
    instance records the <=1e-7 criterion, BENCH_screening.json) and
    weights to fp32 resolution."""
    for ref in (scan_path, host_path):
        rel = np.max(np.abs(ref.objectives - compact_path.objectives)
                     / np.maximum(np.abs(ref.objectives), 1.0))
        assert rel < 5e-7, rel
    np.testing.assert_allclose(compact_path.weights, scan_path.weights,
                               atol=1e-3)
    # screened features scatter back as exact zeros
    masks = compact_path.extras["keep_masks"]
    assert np.all(compact_path.weights[~masks] == 0.0)


def test_compact_uses_small_buffers_when_screening_bites(ds, compact_path):
    """Early steps keep few features => the step must have solved in a
    bucket well below m, and resurrection telemetry tracks mask growth."""
    m = ds.X.shape[0]
    caps = compact_path.extras["caps"]
    kept = compact_path.kept
    assert caps[0] < m and caps[0] >= kept[0]
    assert np.all(caps >= kept)  # a bucket always fits the certified keeps
    # kept counts grow along this grid => some features resurrect
    assert compact_path.extras["resurrected"].sum() > 0


def test_compact_overflow_falls_back_to_mask(ds):
    """With screening off every step keeps all m features — past the largest
    bucket — so the lax.cond/switch fallback must engage (cap == m) and
    still match the mask engine."""
    s = svm_path_scan(ds.X, ds.y, screening=False, **GRID, **SOLVE)
    c = svm_path_scan(ds.X, ds.y, screening=False, reduce="compact",
                      **GRID, **SOLVE)
    assert np.all(c.extras["caps"] == ds.X.shape[0])
    rel = np.max(np.abs(s.objectives - c.objectives)
                 / np.maximum(np.abs(s.objectives), 1.0))
    assert rel < 1e-9, rel


def test_compact_dynamic_matches(ds, scan_path):
    dyn = svm_path_scan(ds.X, ds.y, reduce="compact", dynamic=True,
                        screen_every=25, **GRID, **SOLVE)
    rel = np.max(np.abs(dyn.objectives - scan_path.objectives)
                 / np.maximum(np.abs(scan_path.objectives), 1.0))
    assert rel < 1e-6, rel


def test_compact_caps_schedule():
    assert compact_caps(2000) == (64, 128, 256, 512)
    assert compact_caps(300) == (32, 64, 128)
    assert compact_caps(16) == ()  # degenerates to mask mode
    caps = compact_caps(10**6)
    assert len(caps) == 4 and all(c <= 10**6 // 2 for c in caps)


def test_reduce_validation(ds):
    with pytest.raises(ValueError, match="mask' or 'compact"):
        svm_path_scan(ds.X, ds.y, reduce="gather", **GRID)
    with pytest.raises(ValueError, match="scan engine"):
        PathDriver(reduce="compact")
    # svm_path dispatch: per-engine defaults + pass-through
    r = svm_path(ds.X, ds.y, engine="scan", reduce="compact", **GRID, **SOLVE)
    assert r.extras["options"]["reduce"] == "compact"


# ---------------------------------------------------------------------------
# Sharded scan engine: one shard_map'd program over the svm_mesh
# ---------------------------------------------------------------------------


def test_sharded_scan_bitwise_on_unit_mesh(ds, scan_path):
    """On a trivial (1, 1) CPU mesh every collective binds to the identity,
    so the shard_map'd program must reproduce the single-device scan
    BITWISE — keep masks, objectives, weights, and certificates. This is
    the port check: any drift means the sharded step diverged from the
    local step."""
    sh = svm_path_scan_sharded(svm_mesh(1, 1), ds.X, ds.y, **GRID, **SOLVE)
    assert sh.extras["engine"] == "scan_sharded"
    np.testing.assert_array_equal(sh.extras["keep_masks"],
                                  scan_path.extras["keep_masks"])
    np.testing.assert_array_equal(sh.objectives, scan_path.objectives)
    np.testing.assert_array_equal(sh.weights, scan_path.weights)
    np.testing.assert_array_equal(sh.extras["gaps"], scan_path.extras["gaps"])
    np.testing.assert_array_equal(sh.solver_iters, scan_path.solver_iters)


def test_masked_padding_reproduces_the_unpadded_path(ds, scan_path):
    """The sharded wrapper pads X to its mesh: zero feature rows, and zero
    sample columns masked out of the loss, the certificate, the Lipschitz
    bias row and the anchor (``sm``). The padded program must solve the
    unpadded path: same objectives, padded rows never active."""
    from functools import partial

    from repro.core.dual import bias_at_lambda_max, theta_at_lambda_max
    from repro.core.path import default_lambda_grid
    from repro.core.path_scan import _path_scan_program, _static_opts
    from repro.core.screening import SAFE_TAU

    m, n = ds.X.shape
    Xp = jnp.asarray(np.pad(ds.X, ((0, 3), (0, 5))))
    yp = jnp.asarray(np.pad(ds.y, (0, 5)))
    sm = jnp.asarray((np.arange(n + 5) < n).astype(np.float32))
    lam_max = lambda_max(Xp, yp, sm)
    np.testing.assert_allclose(float(lam_max),
                               float(lambda_max(ds.X, ds.y)), rtol=1e-6)
    lambdas = jnp.asarray(default_lambda_grid(float(lam_max), **GRID),
                          jnp.float32)
    static_kw = _static_opts(SOLVE["max_iters"], True, False, 1, False,
                             False, "mask")
    run = jax.jit(partial(_path_scan_program, sm=sm, **dict(static_kw)))
    outs = run(Xp, yp, lambdas, jnp.zeros((m + 3,)),
               bias_at_lambda_max(yp, sm),
               theta_at_lambda_max(yp, lam_max, sm), 0.0, lam_max, None,
               SAFE_TAU, SOLVE["tol"])
    obj = np.asarray(outs.obj, np.float64)
    rel = np.abs(obj - scan_path.objectives) / scan_path.objectives
    assert rel.max() < 1e-5, rel
    assert not np.asarray(outs.w)[:, m:].any()
    assert np.asarray(outs.converged).all()
    assert not np.asarray(outs.health).any()


# ---------------------------------------------------------------------------
# Pallas-fused solver vs XLA solver (interpret mode on non-TPU backends)
# ---------------------------------------------------------------------------


def test_pallas_fista_matches_xla(ds, monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    X, y = jnp.asarray(ds.X), jnp.asarray(ds.y)
    lam = 0.3 * float(lambda_max(X, y))
    ref = fista_solve(X, y, lam, max_iters=20000, tol=1e-12, use_pallas=False)
    out = fista_solve(X, y, lam, max_iters=20000, tol=1e-12, use_pallas=True)
    np.testing.assert_allclose(float(out.obj), float(ref.obj), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(out.w), np.asarray(ref.w), atol=1e-3)


def test_pallas_scan_path_matches_xla(ds, scan_path, monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    p = svm_path_scan(ds.X, ds.y, use_pallas=True, **GRID, **SOLVE)
    rel = np.max(np.abs(p.objectives - scan_path.objectives)
                 / np.maximum(np.abs(scan_path.objectives), 1.0))
    assert rel < 1e-5, rel


def test_pallas_compact_path_matches_xla(ds, compact_path, monkeypatch):
    """Compact solves hand the kernels their live-row count (valid_m); the
    skipped padded blocks must not change the path."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    p = svm_path_scan(ds.X, ds.y, use_pallas=True, reduce="compact",
                      **GRID, **SOLVE)
    rel = np.max(np.abs(p.objectives - compact_path.objectives)
                 / np.maximum(np.abs(compact_path.objectives), 1.0))
    assert rel < 1e-5, rel


def test_restart_fallback_is_conditional():
    """The monotone-restart branch must sit under lax.cond — not be computed
    eagerly every iteration (the perf bug this PR fixes)."""
    X = jnp.asarray(np.random.default_rng(0).standard_normal((16, 12)),
                    jnp.float32)
    y = jnp.asarray(np.sign(np.random.default_rng(1).standard_normal(12)),
                    jnp.float32)
    jaxpr = str(jax.make_jaxpr(
        lambda X, y: fista_solve(X, y, 0.5, max_iters=7, use_pallas=False)
    )(X, y))
    assert "cond[" in jaxpr


# ---------------------------------------------------------------------------
# Shared Lipschitz bound: full X upper-bounds every masked submatrix
# ---------------------------------------------------------------------------


def test_full_lipschitz_upper_bounds_masked_submatrices():
    rng = np.random.default_rng(7)
    X = jnp.asarray(rng.standard_normal((120, 80)), jnp.float32)
    L_full = float(lipschitz_estimate(X, n_iters=120))
    for seed in range(5):
        r = np.random.default_rng(seed)
        rows = r.random(120) < r.uniform(0.3, 0.9)
        cols = r.random(80) < r.uniform(0.3, 0.9)
        rows[0] = cols[0] = True  # keep non-empty
        # mask mode: zeroed rows (samples all kept)
        L_mask = float(lipschitz_estimate(
            X * jnp.asarray(rows[:, None], jnp.float32), n_iters=120))
        # gather mode: physical submatrix on both axes
        L_sub = float(lipschitz_estimate(
            jnp.asarray(np.asarray(X)[rows][:, cols]), n_iters=120))
        assert L_mask <= L_full * 1.01 + 1e-4, (seed, L_mask, L_full)
        assert L_sub <= L_full * 1.01 + 1e-4, (seed, L_sub, L_full)


# ---------------------------------------------------------------------------
# Batched engine
# ---------------------------------------------------------------------------


def test_batched_grids_match_single(ds, host_path):
    lmax = host_path.extras["lam_max"]
    grids = np.stack([
        np.geomspace(lmax, lmax * r, 5) for r in (0.15, 0.25, 0.4)
    ])
    batched = svm_path_batched(ds.X, ds.y, lambdas=grids, **SOLVE)
    assert len(batched) == 3
    for i in range(3):
        single = svm_path_scan(ds.X, ds.y, lambdas=grids[i], **SOLVE)
        # vmap changes the XLA lowering (GEMV -> GEMM, different fp32
        # accumulation order), so near-threshold screening decisions and
        # noise-level stopping may differ — solutions agree to fp32 solver
        # resolution, not bitwise.
        rel = np.max(np.abs(batched[i].objectives - single.objectives)
                     / np.maximum(np.abs(single.objectives), 1.0))
        assert rel < 1e-4, (i, rel)
        np.testing.assert_allclose(batched[i].weights, single.weights,
                                   atol=5e-3)


def test_batched_problems_match_single():
    sets = [make_sparse_classification(m=200, n=90, k_active=8, seed=s)
            for s in (51, 52)]
    Xb = np.stack([d.X for d in sets])
    yb = np.stack([d.y for d in sets])
    batched = svm_path_batched(Xb, yb, n_lambdas=5, lam_min_ratio=0.25,
                               **SOLVE)
    assert len(batched) == 2
    for i, d in enumerate(sets):
        single = svm_path_scan(d.X, d.y, n_lambdas=5, lam_min_ratio=0.25,
                               **SOLVE)
        rel = np.max(np.abs(batched[i].objectives - single.objectives)
                     / np.maximum(np.abs(single.objectives), 1.0))
        assert rel < 1e-4, (i, rel)  # see grids test: vmap lowering differs


def test_batched_input_validation(ds):
    with pytest.raises(ValueError, match="lambdas"):
        svm_path_batched(ds.X, ds.y)  # 2-D X needs explicit grids
    with pytest.raises(ValueError, match="B, T"):
        svm_path_batched(ds.X, ds.y, lambdas=np.array([0.5, 0.1]))


# ---------------------------------------------------------------------------
# Batched compact: the shared-cap schedule under vmap
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def batch_sets():
    return [make_sparse_classification(m=200, n=90, k_active=8, seed=s)
            for s in (51, 52)]


@pytest.fixture(scope="module")
def batched_compact(batch_sets):
    Xb = np.stack([d.X for d in batch_sets])
    yb = np.stack([d.y for d in batch_sets])
    return svm_path_batched(Xb, yb, n_lambdas=6, lam_min_ratio=0.15,
                            reduce="compact", **SOLVE)


def test_compact_caps_batched_schedule():
    # no counts: the ladder itself (same as the per-problem schedule)
    assert compact_caps_batched(300) == compact_caps(300) == (32, 64, 128)
    # with counts: the smallest shared cap fitting the batch-max keep
    assert compact_caps_batched(300, [5]) == 32
    assert compact_caps_batched(300, [10, 40]) == 64
    assert compact_caps_batched(300, [10, 200]) == 300  # overflow -> mask
    assert compact_caps_batched(16, [4]) == 16  # no ladder -> mask mode


def test_batched_compact_matches_single_compact(batch_sets, batched_compact):
    """vmapped compact == per-problem compact: same screen math, same
    cumsum compaction, same solver trajectory (observed bitwise on CPU;
    asserted at fp32 solver resolution since vmap may change the XLA
    lowering) — and the certified keep masks agree exactly."""
    for i, d in enumerate(batch_sets):
        single = svm_path_scan(d.X, d.y, n_lambdas=6, lam_min_ratio=0.15,
                               reduce="compact", **SOLVE)
        rel = np.max(np.abs(batched_compact[i].objectives - single.objectives)
                     / np.maximum(np.abs(single.objectives), 1.0))
        assert rel < 1e-4, (i, rel)
        np.testing.assert_array_equal(
            batched_compact[i].extras["keep_masks"],
            single.extras["keep_masks"])
        assert batched_compact[i].extras["options"]["reduce"] == "compact"


def test_batched_compact_matches_batched_mask(batch_sets, batched_compact):
    """Compact vs mask reduction on the same batched program structure:
    objectives to solver resolution, screened features exactly zero, and
    the compact caps shared across the batch (ONE capacity per step)."""
    Xb = np.stack([d.X for d in batch_sets])
    yb = np.stack([d.y for d in batch_sets])
    masked = svm_path_batched(Xb, yb, n_lambdas=6, lam_min_ratio=0.15,
                              reduce="mask", **SOLVE)
    for i in range(2):
        rel = np.max(np.abs(batched_compact[i].objectives
                            - masked[i].objectives)
                     / np.maximum(np.abs(masked[i].objectives), 1.0))
        assert rel < 5e-6, (i, rel)
        km = batched_compact[i].extras["keep_masks"]
        assert np.all(batched_compact[i].weights[~km] == 0.0)
        caps = batched_compact[i].extras["caps"]
        kept = batched_compact[i].kept
        assert np.all(caps >= kept)  # the shared cap fits every element
        assert caps[0] < Xb.shape[1]  # early steps actually compacted
    # the schedule is batch-level: every element reports the same cap
    np.testing.assert_array_equal(batched_compact[0].extras["caps"],
                                  batched_compact[1].extras["caps"])


def test_batched_grids_compact_matches_single(ds, host_path):
    lmax = host_path.extras["lam_max"]
    grids = np.stack([
        np.geomspace(lmax, lmax * r, 5) for r in (0.15, 0.25, 0.4)
    ])
    batched = svm_path_batched(ds.X, ds.y, lambdas=grids, reduce="compact",
                               **SOLVE)
    for i in range(3):
        single = svm_path_scan(ds.X, ds.y, lambdas=grids[i],
                               reduce="compact", **SOLVE)
        rel = np.max(np.abs(batched[i].objectives - single.objectives)
                     / np.maximum(np.abs(single.objectives), 1.0))
        assert rel < 1e-4, (i, rel)


def test_batched_compact_overflow_falls_back(batch_sets):
    """Screening off keeps all m features every step — past the largest
    bucket — so the batch-level overflow branch must fire (cap == m for
    every element) and still match the batched mask engine."""
    Xb = np.stack([d.X for d in batch_sets])
    yb = np.stack([d.y for d in batch_sets])
    kw = dict(n_lambdas=4, lam_min_ratio=0.3, screening=False,
              tol=1e-9, max_iters=4000)
    c = svm_path_batched(Xb, yb, reduce="compact", **kw)
    s = svm_path_batched(Xb, yb, reduce="mask", **kw)
    for i in range(2):
        assert np.all(c[i].extras["caps"] == Xb.shape[1])
        rel = np.max(np.abs(c[i].objectives - s[i].objectives)
                     / np.maximum(np.abs(s[i].objectives), 1.0))
        assert rel < 1e-9, (i, rel)


def test_svm_path_engine_batched_dispatch(batch_sets):
    """PR-4 leftover: svm_path now dispatches engine='batched' (returns a
    list) and accepts reduce='compact' there; the engine validation names
    all three engines."""
    Xb = np.stack([d.X for d in batch_sets])
    yb = np.stack([d.y for d in batch_sets])
    rs = svm_path(Xb, yb, engine="batched", reduce="compact", n_lambdas=4,
                  lam_min_ratio=0.25, tol=1e-9, max_iters=4000)
    assert isinstance(rs, list) and len(rs) == 2
    for r in rs:
        assert r.extras["options"]["reduce"] == "compact"
        assert r.extras["batch"] == 2
    with pytest.raises(ValueError, match="'host', 'scan', or 'batched'"):
        svm_path(Xb, yb, engine="bogus")
    with pytest.raises(ValueError, match="feature rule only"):
        svm_path(Xb, yb, engine="batched", rules="sample_vi")


def test_engine_cache_no_retrace(batch_sets):
    """Same config + same shapes must hit both warm-cache layers: the
    engine dict (one jitted engine per static-opts key) and jit's own trace
    cache (no retrace on the repeat call)."""
    from repro.core.path_scan import _engine_jit, _static_opts, engine_cache_info

    # layer 1: static opts are hashable and hit the engine dict
    a = _engine_jit(_static_opts(4000, True, False, 50, None, False,
                                 "compact"), batched="problems_compact")
    b = _engine_jit(_static_opts(4000, True, False, 50, None, False,
                                 "compact"), batched="problems_compact")
    assert a is b

    # layer 2: a repeated same-shape call leaves every trace count alone
    Xb = np.stack([d.X for d in batch_sets])
    yb = np.stack([d.y for d in batch_sets])
    kw = dict(n_lambdas=4, lam_min_ratio=0.25, reduce="compact",
              tol=1e-9, max_iters=4000)
    svm_path_batched(Xb, yb, **kw)
    before = engine_cache_info()
    svm_path_batched(Xb, yb, **kw)
    after = engine_cache_info()
    assert after == before, (before, after)
