"""Safe feature screening for the L1-regularized L2-loss SVM (paper Sec. 6).

Given the dual optimum ``theta1`` at ``lam1`` and a target ``lam2 < lam1``,
the unknown optimum ``theta2`` lies in the closed convex set (paper Eq. 43)

    K = Ball(c, R)  ∩  Halfspace  ∩  Hyperplane
      = {theta : ||theta - c|| <= R}
        ∩ {theta : a^T (theta - theta1) >= 0}
        ∩ {theta : y^T theta = 0}

    c = (1/lam2 + theta1) / 2          (vector; 1/lam2 means (1/lam2)*ones)
    R = || 1/lam2 - theta1 ||_2 / 2
    a = (theta1 - 1/lam1) / || theta1 - 1/lam1 ||_2

(The paper's Eq. 43 writes the halfspace as ``a^T(b+r) <= 0``; the
variational inequality Eq. 31 it is derived from gives
``(theta1 - 1/lam1)^T (theta2 - theta1) >= 0`` and ``b + r = theta2 -
theta1``, so we implement the ``>= 0`` orientation. Safety is verified
empirically by property tests.)

A feature ``f`` can be active at ``lam2`` only if ``|fhat^T theta2| = 1``
(paper Eq. 22), so any feature with ``max_{theta in K} |fhat^T theta| < 1``
is *safely* discarded.

Closed form for ``T(v) := max_{theta in K} v^T theta`` (our derivation; it
reproduces the paper's Theorems 6.5/6.7/6.9 — by Thm 6.3 the paper's
switch to the minimal ball ``B_t`` in the alpha>0 case computes the max over
the *same* sphere∩plane set, so the two forms agree):

  Work inside the hyperplane H = {y^T theta = 0}. With
  Q u := u - (u^T y / ||y||^2) y  (projection onto H's direction space),

    c_H  = Q c,   R_H^2 = R^2 - (y^T c)^2 / ||y||^2      (ball ∩ H)
    g0   = a^T (c_H - theta1)                            (halfspace offset)

  T(v) = v^T c_H + max_{||s|| <= R_H, (Qa)^T s >= -g0} (Qv)^T s:

    case A ("alpha=0", Thm 6.7): the ball max  s* = R_H Qv/||Qv||  already
      satisfies the halfspace  =>  T = v^T c_H + R_H ||Qv||.
    case B ("alpha>0", Thm 6.9): max on sphere ∩ {(Qa)^T s = -g0}:
      mu    = (Qv)^T Qa / ||Qa||^2
      vperp = Qv - mu Qa ;  rho^2 = max(0, R_H^2 - g0^2/||Qa||^2)
      T = v^T c_H - mu g0 + rho ||vperp||.
    case "beta=0" (Thm 6.5) is the ||vperp|| -> 0 limit of case B and needs
      no special handling in floating point (guarded divisions).

Everything reduces to four per-feature reductions over samples

    d_theta_j = fhat_j^T theta1,  d_1_j = fhat_j^T 1,
    d_y_j     = fhat_j^T y,       d_sq_j = ||fhat_j||^2

(i.e. ``X @ (y*theta1)``, ``X @ y``, ``X @ 1``, ``(X*X) @ 1`` in unsigned
coordinates) plus O(1) shared scalars — the paper's O(mn) bound, realized as
one GEMM-shaped sweep (see kernels/screen.py for the fused TPU kernel).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .linalg import dot

__all__ = [
    "FeatureReductions",
    "ScreenShared",
    "feature_reductions",
    "row_dot",
    "shared_scalars",
    "shared_scalars_from_stats",
    "screen_bounds_from_reductions",
    "screen_bounds",
    "screen",
    "SAFE_TAU",
    "anchor_slice",
    "fixed_slice",
    "finalize_from_anchor_jit",
]

# Keep a feature unless its bound is provably below 1; the tau margin absorbs
# floating-point accumulation error so rounding can never cause an *unsafe*
# rejection (it can only make screening slightly conservative). Sized from
# measurement: fp32 bound evaluation deviates from fp64 by up to ~2e-3 on
# adversarial instances (tests/test_screening.py::test_bounds_dtype_stability;
# a hypothesis-found case showed a 1.1e-4 violation at 1e-6 margin), so the
# default margin is 2e-3 with the rejection-power cost measured at <1%
# (benchmarks). Callers with fp64 inputs may tighten.
SAFE_TAU = 1.0 - 2e-3

_EPS = 1e-30


class FeatureReductions(NamedTuple):
    """Per-feature sample-axis reductions (all shape ``(m,)``)."""

    d_theta: jax.Array  # fhat_j^T theta1 = f_j^T (y * theta1)
    d_one: jax.Array    # fhat_j^T 1     = f_j^T y
    d_y: jax.Array      # fhat_j^T y     = f_j^T 1
    d_sq: jax.Array     # ||fhat_j||^2   = ||f_j||^2


class ScreenShared(NamedTuple):
    """Feature-independent scalars (paper Sec. 6.4 'precompute & share')."""

    inv_lam1: jax.Array
    inv_lam2: jax.Array
    yc: jax.Array          # y^T c
    ysq: jax.Array         # ||y||^2
    r_h_sq: jax.Array      # R_H^2 (ball radius^2 inside the hyperplane)
    g0: jax.Array          # a^T (c_H - theta1)
    qa_theta: jax.Array    # (Qa)^T (Q theta1)  [for v^T terms via reductions]
    qa_sq: jax.Array       # ||Qa||^2
    a_norm: jax.Array      # ||theta1 - 1/lam1||
    a_dot_one: jax.Array   # a^T 1
    a_dot_y: jax.Array     # a^T y
    theta_dot_one: jax.Array
    theta_dot_y: jax.Array  # == 0 for an exactly feasible theta1
    halfspace_valid: jax.Array  # bool: ||theta1 - 1/lam1|| > 0


@jax.jit
def row_dot(X: jax.Array, v: jax.Array) -> jax.Array:
    """``X @ v`` as an explicit multiply + last-axis reduction.

    Row-stable formulation: each output row reduces over its own samples
    only, and XLA lowers ``sum(X * v, axis=1)`` identically for any leading
    row count — so concatenating the results of row *chunks* reproduces the
    full-matrix result **bitwise** (a matmul/matvec does not: its tiling
    depends on the row count). This is the contract the out-of-core streamed
    screen (``repro/sparse/screen_stream.py``) is built on: the in-core and
    chunk-accumulated bound sweeps share this kernel and agree exactly.
    """
    return jnp.sum(X * v[None, :], axis=1)


@jax.jit
def _row_stable_reductions(X, y_theta, y):
    d_theta = jnp.sum(X * y_theta[None, :], axis=1)
    d_one = jnp.sum(X * y[None, :], axis=1)
    d_y = jnp.sum(X, axis=1)
    d_sq = jnp.sum(X * X, axis=1)
    return d_theta, d_one, d_y, d_sq


def feature_reductions(X: jax.Array, y: jax.Array, theta1: jax.Array) -> FeatureReductions:
    """The four O(mn) reductions, batched over all features.

    ``X``: (m, n) features-major. This is the only data-touching step; the
    Pallas kernel in ``repro/kernels`` fuses the four passes into one.
    Computed in the row-stable formulation (see :func:`row_dot`) so the
    streamed per-chunk sweep concatenates to these values bitwise.
    """
    d_theta, d_one, d_y, d_sq = _row_stable_reductions(X, y * theta1, y)
    return FeatureReductions(d_theta=d_theta, d_one=d_one, d_y=d_y, d_sq=d_sq)


def d_theta_sparse(X: jax.Array, y: jax.Array, theta1: jax.Array,
                   support: int) -> jax.Array:
    """``fhat_j^T theta1`` exploiting theta1's sparsity (paper Sec. 6.4).

    Along a path the other three reductions are theta-independent and
    precomputed once; this is the only O(mn) term per lambda. theta1 has at
    most #support-vectors nonzeros (samples with positive hinge), so a
    static-size gather of its ``support`` largest entries turns the sweep
    into O(m * support). ``support`` must upper-bound nnz(theta1) for
    exactness (a static shape, so jit-stable); entries beyond nnz are zero
    and contribute nothing.
    """
    support = min(support, theta1.shape[0])
    vals, idx = jax.lax.top_k(jnp.abs(theta1), support)
    coef = (y * theta1)[idx]                       # signed, true values
    return dot(X[:, idx], coef)


def shared_scalars(
    y: jax.Array, lam1: jax.Array, lam2: jax.Array, theta1: jax.Array,
    delta: jax.Array | float = 0.0,
) -> ScreenShared:
    """Scalars shared by every feature's bound (computed once, O(n)).

    ``delta`` is an upper bound on ``||theta1 - theta1*||_2`` when theta1 is
    only approximately optimal (the paper assumes it exact). With
    ``||theta1 - theta*|| <= delta`` the exact-theta ball
    Ball(c*, R*) is contained in Ball(c, R + delta) and the halfspace
    ``a*^T (theta2 - theta1*) >= 0`` relaxes to
    ``a^T (theta2 - theta1) >= -delta (2R + 3 delta + ||u||)/||u||``
    (u = theta1 - 1/lam1), so safety is preserved under inexact solves.
    ``delta = sqrt(2 * duality_gap) / lam1`` by 1-strong convexity of the
    dual objective (see dual.duality_gap_estimate). This robustification is
    a beyond-paper addition (in the spirit of later GAP-sphere rules).
    """
    dtype = theta1.dtype
    n = y.shape[0]
    return shared_scalars_from_stats(
        jnp.asarray(lam1, dtype),
        jnp.asarray(lam2, dtype),
        one_y=jnp.sum(y),
        theta_dot_one=jnp.sum(theta1),
        theta_dot_y=dot(theta1, y),
        theta_sq=dot(theta1, theta1),
        n_tot=jnp.asarray(float(n), dtype),  # ||y||^2 = n for +-1 labels
        delta=jnp.asarray(delta, dtype),
    )


def shared_scalars_from_stats(
    lam1: jax.Array,
    lam2: jax.Array,
    one_y: jax.Array,
    theta_dot_one: jax.Array,
    theta_dot_y: jax.Array,
    theta_sq: jax.Array,
    n_tot: jax.Array,
    delta: jax.Array | float = 0.0,
) -> ScreenShared:
    """:class:`ScreenShared` from global scalar statistics of ``(y, theta1)``.

    The stats-based entry point exists so every execution path — local
    (:func:`shared_scalars`), sharded (``distributed.screen_sharded`` psums
    per-shard partial sums into the same five scalars), and the in-solver
    dynamic refresh on a sample-masked problem (``solver.fista_solve_dynamic``
    computes masked stats) — runs the *identical* scalar arithmetic,
    including the inexact-theta ``delta`` inflation. Inputs:

        one_y = y^T 1,  theta_dot_one = theta1^T 1,  theta_dot_y = theta1^T y,
        theta_sq = ||theta1||^2,  n_tot = ||y||^2 (= #live samples).
    """
    inv1, inv2 = 1.0 / lam1, 1.0 / lam2
    ysq = n_tot

    # ball: c = (inv2*1 + theta1)/2 ; R^2 = ||inv2*1 - theta1||^2 / 4
    yc = 0.5 * (inv2 * one_y + theta_dot_y)
    r_sq = 0.25 * (inv2 * inv2 * n_tot - 2.0 * inv2 * theta_dot_one + theta_sq)
    r_base = jnp.sqrt(jnp.maximum(r_sq, 0.0))
    r_infl = r_base + delta          # inexact-theta1 inflation (no-op at 0)
    r_h_sq = r_infl * r_infl - yc * yc / ysq

    # halfspace normal a = (theta1 - inv1*1)/||.||
    diff_sq = theta_sq - 2.0 * inv1 * theta_dot_one + inv1 * inv1 * n_tot
    a_norm = jnp.sqrt(jnp.maximum(diff_sq, 0.0))
    # RELATIVE validity: when theta1 == 1/lam1 analytically (balanced classes
    # at lam_max), a is pure rounding noise — a random halfspace direction
    # would cut the ball unsafely. Compare against theta1's own scale.
    scale = jnp.sqrt(theta_sq + inv1 * inv1 * n_tot)
    halfspace_valid = a_norm > 1e-6 * scale
    safe_norm = jnp.maximum(a_norm, _EPS)
    a_dot_one = (theta_dot_one - inv1 * n_tot) / safe_norm
    a_dot_y = (theta_dot_y - inv1 * one_y) / safe_norm
    a_dot_theta = (theta_sq - inv1 * theta_dot_one) / safe_norm

    # c_H = c - (yc/ysq) y ;  g0 = a^T c_H - a^T theta1 (relaxed by delta slack)
    a_dot_c = 0.5 * (inv2 * a_dot_one + a_dot_theta)
    g0 = a_dot_c - (yc / ysq) * a_dot_y - a_dot_theta
    g0 = g0 + delta * (2.0 * r_base + 3.0 * delta + a_norm) / safe_norm
    qa_sq = jnp.maximum(1.0 - a_dot_y * a_dot_y / ysq, 0.0)  # ||a||=1

    return ScreenShared(
        inv_lam1=inv1,
        inv_lam2=inv2,
        yc=yc,
        ysq=ysq,
        r_h_sq=r_h_sq,
        g0=g0,
        qa_theta=a_dot_theta - a_dot_y * theta_dot_y / ysq,
        qa_sq=qa_sq,
        a_norm=a_norm,
        a_dot_one=a_dot_one,
        a_dot_y=a_dot_y,
        theta_dot_one=theta_dot_one,
        theta_dot_y=theta_dot_y,
        halfspace_valid=halfspace_valid,
    )


def _t_max(
    v_ch: jax.Array,
    qv_qa: jax.Array,
    qv_sq: jax.Array,
    sh: ScreenShared,
) -> jax.Array:
    """``max_{theta in K} v^T theta`` given hyperplane-projected stats of v.

    v_ch  : v^T c_H            (m,)
    qv_qa : (Qv)^T (Qa)        (m,)
    qv_sq : ||Qv||^2           (m,)
    """
    r_h = jnp.sqrt(jnp.maximum(sh.r_h_sq, 0.0))
    qv_norm = jnp.sqrt(jnp.maximum(qv_sq, 0.0))

    # case A: ball max satisfies the halfspace. The halfspace is only
    # informative when a has a component INSIDE the hyperplane: at
    # lam1 = lam_max with unbalanced classes a ∝ y exactly, ||Qa|| = 0 and
    # the constraint is vacuous there (found by the paper-reference
    # cross-check; both case conditions are 0/0 noise in that geometry).
    ball_val = v_ch + r_h * qv_norm
    at_ball = sh.g0 + r_h * qv_qa / jnp.maximum(qv_norm, _EPS)
    halfspace_informative = sh.halfspace_valid & (sh.qa_sq > 1e-9)
    use_ball = (at_ball >= 0.0) | (~halfspace_informative) | (qv_norm <= _EPS)

    # case B: sphere ∩ halfspace-boundary
    qa_sq = jnp.maximum(sh.qa_sq, _EPS)
    mu = qv_qa / qa_sq
    vperp_sq = jnp.maximum(qv_sq - mu * mu * qa_sq, 0.0)
    rho_sq = jnp.maximum(sh.r_h_sq - sh.g0 * sh.g0 / qa_sq, 0.0)
    cut_val = v_ch - mu * sh.g0 + jnp.sqrt(rho_sq) * jnp.sqrt(vperp_sq)

    return jnp.where(use_ball, ball_val, cut_val)


def screen_bounds_from_reductions(
    red: FeatureReductions, sh: ScreenShared
) -> jax.Array:
    """Upper bound on ``|fhat_j^T theta2|`` per feature, from reductions only."""
    # v = fhat: project the per-feature stats into the hyperplane.
    v_y = red.d_y
    v_c = 0.5 * (sh.inv_lam2 * red.d_one + red.d_theta)
    v_ch = v_c - (sh.yc / sh.ysq) * v_y
    qv_sq = red.d_sq - v_y * v_y / sh.ysq

    # (Qv)^T (Qa) = v^T a - (v^T y)(a^T y)/||y||^2, with
    # a = (theta1 - 1/lam1)/||.||  =>  v^T a = (v^T theta1 - v^T 1/lam1)/||.||
    safe_norm = jnp.maximum(sh.a_norm, _EPS)
    v_a = (red.d_theta - sh.inv_lam1 * red.d_one) / safe_norm
    qv_qa = v_a - v_y * sh.a_dot_y / sh.ysq

    m_pos = _t_max(v_ch, qv_qa, qv_sq, sh)            # max  fhat^T theta
    m_neg = _t_max(-v_ch, -qv_qa, qv_sq, sh)          # max -fhat^T theta
    return jnp.maximum(m_pos, m_neg)


# jitted separately from the reduction sweep (not one fused program): the
# streamed screen computes the reductions chunk-by-chunk and must finalize
# through the *same* compiled function to preserve the bitwise contract —
# a single whole-program jit would fuse reduction and finalizer into a
# different lowering than the chunked path can reproduce.
_finalize_bounds = jax.jit(screen_bounds_from_reductions)


class AnchorStats(NamedTuple):
    """A dual anchor ``theta1`` at ``lam``, as the scalars + one reduction
    every screening-rule program consumes (the anchor half of a rule's
    *region pytree*).

    Engines own the sweeps that produce these (psum-reduced on a mesh,
    chunk-accumulated out of core, plain reductions in core), so a rule
    program evaluated on :class:`AnchorStats` is pure and collective-free —
    the property that makes it lowerable into ``lax.scan``/``vmap``/
    ``shard_map`` bodies unchanged.
    """

    lam: jax.Array            # anchor regularization (lam1)
    delta: jax.Array          # ||theta1 - theta*(lam)|| inexactness radius
    theta_dot_one: jax.Array  # theta1^T 1
    theta_dot_y: jax.Array    # theta1^T y
    theta_sq: jax.Array       # ||theta1||^2
    d_theta: jax.Array        # (m,) fhat_j^T theta1 = f_j^T (y * theta1)


class FixedStats(NamedTuple):
    """Theta-independent statics shared by every anchor and every rule
    (the fixed half of the region pytree; hoisted once per path)."""

    d_one: jax.Array   # (m,) fhat_j^T 1
    d_y: jax.Array     # (m,) fhat_j^T y
    d_sq: jax.Array    # (m,) ||fhat_j||^2
    one_y: jax.Array   # y^T 1
    n_tot: jax.Array   # ||y||^2 = #live samples


def anchor_stats(y: jax.Array, lam, theta1: jax.Array, delta,
                 d_theta: jax.Array) -> AnchorStats:
    """Build :class:`AnchorStats` from an in-core anchor (caller supplies the
    one O(mn) reduction ``d_theta``). Scalar arithmetic matches
    :func:`shared_scalars` exactly so anchor-based and legacy entry points
    produce bitwise-identical :class:`ScreenShared` values."""
    dtype = theta1.dtype
    return AnchorStats(
        lam=jnp.asarray(lam, dtype),
        delta=jnp.asarray(delta, dtype),
        theta_dot_one=jnp.sum(theta1),
        theta_dot_y=dot(theta1, y),
        theta_sq=dot(theta1, theta1),
        d_theta=d_theta,
    )


def fixed_stats(y: jax.Array, d_one: jax.Array, d_y: jax.Array,
                d_sq: jax.Array) -> FixedStats:
    """Build :class:`FixedStats` from an in-core ``y`` and the three hoisted
    per-feature reductions."""
    n = y.shape[0]
    return FixedStats(d_one=d_one, d_y=d_y, d_sq=d_sq, one_y=jnp.sum(y),
                      n_tot=jnp.asarray(float(n), y.dtype))


def shared_scalars_from_anchor(anchor: AnchorStats, lam2,
                               fixed: FixedStats) -> ScreenShared:
    """:class:`ScreenShared` for the VI set anchored at ``anchor``,
    targeting ``lam2`` — the region-pytree face of
    :func:`shared_scalars_from_stats`."""
    return shared_scalars_from_stats(
        anchor.lam, lam2, one_y=fixed.one_y,
        theta_dot_one=anchor.theta_dot_one, theta_dot_y=anchor.theta_dot_y,
        theta_sq=anchor.theta_sq, n_tot=fixed.n_tot, delta=anchor.delta,
    )


def finalize_from_anchor(anchor: AnchorStats, lam2,
                         fixed: FixedStats) -> jax.Array:
    """The VI bound finalizer over the region pytree: per-feature upper
    bounds on ``|fhat_j^T theta*(lam2)|`` from one anchor's stats. Inlines
    :func:`screen_bounds_from_reductions` (no nested jit) so engine traces
    that embed it lower exactly as the pre-pytree code did."""
    sh = shared_scalars_from_anchor(anchor, lam2, fixed)
    red = FeatureReductions(d_theta=anchor.d_theta, d_one=fixed.d_one,
                            d_y=fixed.d_y, d_sq=fixed.d_sq)
    return screen_bounds_from_reductions(red, sh)


#: Jitted :func:`finalize_from_anchor` for host-driven callers evaluating
#: many small region pytrees eagerly (one compile per d_theta shape). The
#: chunk-skip plane leans on a property of the VI region worth stating once:
#: an anchor certified at ``lam1`` yields a *valid* safe region for ANY
#: target ``lam2 < lam1`` — a stale anchor's bounds are merely looser, never
#: unsafe. That is what lets a chunk's features be certified dead from the
#: reductions cached at the chunk's last stream, without re-streaming it.
finalize_from_anchor_jit = jax.jit(finalize_from_anchor)


def anchor_slice(anchor: AnchorStats, lo: int, hi: int) -> AnchorStats:
    """Restrict an anchor's per-feature reduction to rows ``[lo, hi)`` (the
    scalars are feature-independent and pass through) — the region pytree a
    single chunk's bound evaluation consumes."""
    return anchor._replace(d_theta=anchor.d_theta[lo:hi])


def fixed_slice(fixed: FixedStats, lo: int, hi: int) -> FixedStats:
    """Restrict the fixed statics to feature rows ``[lo, hi)``."""
    return fixed._replace(d_one=fixed.d_one[lo:hi], d_y=fixed.d_y[lo:hi],
                          d_sq=fixed.d_sq[lo:hi])


def screen_bounds(
    X: jax.Array,
    y: jax.Array,
    lam1: jax.Array,
    lam2: jax.Array,
    theta1: jax.Array,
    red: Optional[FeatureReductions] = None,
    delta: jax.Array | float = 0.0,
) -> jax.Array:
    """Upper bound on ``|fhat_j^T theta*(lam2)|`` for every feature j."""
    if red is None:
        red = feature_reductions(X, y, theta1)
    sh = shared_scalars(y, lam1, lam2, theta1, delta=delta)
    return _finalize_bounds(red, sh)


def screen(
    X: jax.Array,
    y: jax.Array,
    lam1: jax.Array,
    lam2: jax.Array,
    theta1: jax.Array,
    tau: float = SAFE_TAU,
    red: Optional[FeatureReductions] = None,
    delta: jax.Array | float = 0.0,
) -> tuple[jax.Array, jax.Array]:
    """Safe screening (paper Algorithm 1), batched over all m features.

    Returns ``(keep_mask, bounds)``; ``keep_mask[j] = bounds[j] >= tau``.
    Discarded features are guaranteed inactive at ``lam2`` (given an exact
    ``theta1``, or ``||theta1 - theta*|| <= delta``); kept features *may* be
    active.

    The comparison is NaN-safe in the keep direction: a non-finite bound
    (poisoned anchor, overflowed reduction) certifies nothing, so the
    feature is KEPT — discarding is the only unsafe failure mode.
    """
    bounds = screen_bounds(X, y, lam1, lam2, theta1, red=red, delta=delta)
    return ~(bounds < tau), bounds
