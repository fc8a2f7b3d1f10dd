"""Sample screening for the L1-regularized L2-loss SVM.

A sample ``i`` drops out of every solver GEMV iff its slack vanishes at the
target optimum: ``xi_i*(lam2) = max(0, 1 - y_i (w*^T x_i + b*)) = 0``, i.e.
its margin is satisfied. Equivalently ``theta_i*(lam2) = 0`` (paper Eq. 20).

Why this rule is *verified*-safe rather than a-priori safe
----------------------------------------------------------
For the squared hinge the dual coordinate ``theta_i = xi_i / lam`` is a
*continuous* function of the data (support vectors leave the active set
smoothly), so no bounded region ``K ∋ theta*`` can certify the closed
condition ``theta_i* = 0``: ``max_{theta in K} e_i^T theta >= theta_i* >= 0``
with equality only in degenerate geometry. This is the structural reason the
safe-sample-screening literature targets the *hinge* loss (discrete dual,
Ogawa et al., "Safe Sample Screening for Support Vector Machines") or adds
primal strong convexity (elastic net, Zhang et al., "Scaling Up Sparse SVMs
by Simultaneous Feature and Sample Reduction"). Pure L1 + squared hinge has
neither. ``sample_slack_caps`` below computes the best certified a-priori
bound (the VI-region coordinate maximum); it is valid — and provably too
loose to screen (the region's coordinate extent is O(ball radius), not
O(xi); measured on the bench instances its minimum is ~1 when true slacks
are 0).

The practical rule therefore splits the guarantee in two:

1. **Margin prediction** (this class). Screen sample ``i`` when its margin
   surplus at the previous solution clears a per-sample slack budget:

       y_i u1_i - 1  >=  slack_i,      u1 = X^T w1 + b1,

   with two slack models, tightest applicable wins:

   * *secant* (needs one step of history): ``slack_i =
     shrink_factor * |u1_i - u0_i| + margin_floor`` where ``u0`` is the
     margin at the previous-previous path anchor — first-order continuation
     of each sample's margin trajectory along the (geometric) lambda grid;
   * *trust region* (certificate if the radii hold): ``slack_i =
     ||x_i||_2 * dw + db`` bounds the margin change via Cauchy-Schwarz
     whenever ``||w* - w1|| <= dw`` and ``|b* - b1| <= db``. With the
     driver's default ``dw = inf`` before any movement history exists, the
     first screened step keeps every sample — correct anyway, since near
     ``lam_max`` nearly every sample is a support vector.

2. **KKT verification** (``verify``): at the solved reduced point every
   screened sample's margin is re-checked; violators are re-admitted and the
   step re-solved (warm-started, so re-solves are cheap). On acceptance all
   screened samples have ``xi_i = 0`` *at the returned solution*, so the
   reduced and full problems share that optimum: zero false rejections at
   termination, regardless of the quality of the slack model.

This is the screening-rule formalization of solver "shrinking"
(LIBLINEAR-style), upgraded with an explicit certificate at both ends. The
per-sample inputs (``u1`` and ``||x_i||^2``) are exactly the two
feature-axis reductions the fused sample-axis Pallas kernel computes in one
transposed sweep of X (kernels/screen.py).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..linalg import dot
from ..screening import _EPS, _t_max
from .base import AXIS_SAMPLES, ConvexRegion, ScreeningRule, register_rule

__all__ = ["SampleVIRule", "sample_slack_caps", "sample_margin_surplus",
           "margin_surplus_core", "violators_from_margins"]

# stands in for the driver's "no movement bound yet" dw/db = inf inside the
# arithmetic: inf would produce 0 * inf = NaN for zero-norm sample columns,
# and NaN fails every keep comparison (silently screening the sample with a
# vacuous certificate). Matches the kernel's clamp (kernels/screen.py _BIG).
_BIG = 1e30


def sample_slack_caps(region: ConvexRegion) -> jax.Array:
    """Certified per-sample cap: ``xi_i*(lam2) <= lam2 * max_{theta in K} theta_i``.

    The stats of ``v = e_i`` against the VI set are closed-form — no data
    sweep: ``e_i^T theta1 = theta1_i``, ``e_i^T 1 = 1``, ``e_i^T y = y_i``,
    ``||e_i||^2 = 1``. Valid upper bound on the true slack (property-tested),
    but loose: the region's coordinate extent is O(ball radius), so these
    caps certify screening only for ``lam2/lam1 -> 1``. Exposed as a
    diagnostic and as the honest a-priori baseline the margin rule beats.
    """
    sh = region.shared
    y = region.y
    theta1 = region.theta1
    v_ch = 0.5 * (sh.inv_lam2 + theta1) - (sh.yc / sh.ysq) * y
    qv_sq = jnp.maximum(1.0 - y * y / sh.ysq, 0.0)
    v_a = (theta1 - sh.inv_lam1) / jnp.maximum(sh.a_norm, _EPS)
    qv_qa = v_a - y * sh.a_dot_y / sh.ysq
    t_i = _t_max(v_ch, qv_qa, qv_sq, sh)
    return region.lam2 * jnp.maximum(t_i, 0.0)


def margin_surplus_core(
    u1: jax.Array,
    y: jax.Array,
    x_sq: jax.Array,
    dw: float,
    db: float,
    u_prev: Optional[jax.Array] = None,
    shrink_factor: float = 2.0,
    margin_floor: float = 1e-3,
) -> jax.Array:
    """Surplus from precomputed margins + column norms (the slack arithmetic).

    Factored out so the local rule (:func:`sample_margin_surplus`), the
    sharded sweep (``distributed.sample_surplus_sharded`` — which psums the
    same two feature-axis reductions over the mesh), and the in-solver
    dynamic sample re-screen (``solver.fista_solve_dynamic`` with
    ``dynamic_samples=True``) finalize with *bitwise identical* scalar math;
    keep the reduction producers in sync with this signature rather than
    re-deriving the slack models. ``dw``/``db`` may be python floats or
    traced scalars (the in-solver path passes tracers), hence the jnp clamp.
    """
    dw = jnp.minimum(jnp.asarray(dw), _BIG)
    db = jnp.minimum(jnp.asarray(db), _BIG)
    slack = jnp.sqrt(x_sq) * dw + db  # huge (never screens) until history
    if u_prev is not None:
        secant = shrink_factor * jnp.abs(u1 - u_prev) + margin_floor
        slack = jnp.minimum(slack, secant)
    return y * u1 - 1.0 - slack


def violators_from_margins(y, margins, screened_idx):
    """KKT check from precomputed margins: screened samples with slack > 0.

    ``margins`` holds ``x_i^T w + b`` for the *screened* samples only
    (``margins[j]`` belongs to sample ``screened_idx[j]``). This is the
    verification arithmetic shared by :meth:`SampleVIRule.verify` (which
    computes the margins from in-core X) and the chunked path driver (which
    reads them off the solver's carried ``u = X^T w`` — zero extra
    streams). Works on numpy and jax arrays alike.
    """
    return screened_idx[y[screened_idx] * margins < 1.0]


def sample_margin_surplus(
    X: jax.Array,
    y: jax.Array,
    region: ConvexRegion,
    u_prev: Optional[jax.Array] = None,
    shrink_factor: float = 2.0,
    margin_floor: float = 1e-3,
    x_sq: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array]:
    """Per-sample screening score and the margins it was computed from.

    Returns ``(surplus, u1)`` with ``surplus_i = y_i u1_i - 1 - slack_i``;
    ``surplus_i >= 0`` predicts ``xi_i*(lam2) = 0`` (to be verified). The
    slack is the minimum of the secant model (when ``u_prev`` is given) and
    the Cauchy-Schwarz trust-region model (when ``region.dw`` is finite).
    ``x_sq`` optionally supplies the theta-independent column norms
    ``sum(X*X, axis=0)`` (cached once per path by the rule's ``prepare``).
    """
    if region.w1 is None:
        # match the data dtype (not a hardcoded float32) so x64 paths stay
        # in double precision end to end
        u1 = jnp.full(y.shape, region.b1, jnp.result_type(X.dtype, y.dtype))
    else:
        u1 = dot(X.T, region.w1) + region.b1
    if x_sq is None:
        x_sq = jnp.sum(X * X, axis=0)
    surplus = margin_surplus_core(
        u1, y, x_sq, region.dw, region.db, u_prev=u_prev,
        shrink_factor=shrink_factor, margin_floor=margin_floor,
    )
    return surplus, u1


@register_rule("sample_vi")
class SampleVIRule(ScreeningRule):
    """Margin-predicted sample screening with a-posteriori KKT verification.

    ``bounds`` returns the margin surplus minus the per-sample slack budget;
    ``keep`` keeps every sample whose score is negative (slack not certified
    zero). ``verify`` re-checks screened samples at the solved point — the
    driver must re-admit returned violators and re-solve before accepting.

    Stateful across path steps: the rule remembers the previous anchor's
    margins for the secant slack model; ``prepare`` (called once per path)
    resets the history.
    """

    axis = AXIS_SAMPLES
    needs_verification = True

    def __init__(self, shrink_factor: float = 2.0, margin_floor: float = 1e-3):
        self.shrink_factor = float(shrink_factor)
        self.margin_floor = float(margin_floor)
        self._u_prev: Optional[jax.Array] = None
        self._x_sq: Optional[jax.Array] = None

    def prepare(self, X: jax.Array, y: jax.Array) -> None:
        self._u_prev = None
        self._x_sq = jnp.sum(X * X, axis=0)  # theta-independent, shared

    def bounds(self, X: jax.Array, y: jax.Array, region: ConvexRegion) -> jax.Array:
        surplus, u1 = sample_margin_surplus(
            X, y, region, u_prev=self._u_prev,
            shrink_factor=self.shrink_factor, margin_floor=self.margin_floor,
            x_sq=self._x_sq,
        )
        self._u_prev = u1
        return surplus

    def keep(self, bounds: jax.Array) -> jax.Array:
        return bounds < 0.0

    def verify(self, X, y, w, b, screened_idx) -> jax.Array:
        """Screened samples whose margin at ``(w, b)`` is actually < 1."""
        u = dot(X[:, screened_idx].T, w) + b
        return violators_from_margins(y, u, screened_idx)
