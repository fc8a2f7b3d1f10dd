"""Dual-side quantities for the L1-regularized L2-loss (squared hinge) SVM.

Primal (paper Eq. 1):

    min_{w,b}  1/2 sum_i max(0, 1 - y_i (w^T x_i + b))^2 + lam * ||w||_1

Data layout follows the paper: ``X`` has shape ``(m, n)`` = (features,
samples); ``y in {-1,+1}^n``.

Scaled dual variable ``theta = alpha / lam`` (paper Eq. 19):

    min_theta ||theta - 1/lam||_2^2
    s.t.      |fhat_j^T theta| <= 1  for all features j
              theta^T y = 0,   theta >= 0

with ``fhat_j = y * X[j]`` (elementwise label signing).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .linalg import dot

__all__ = [
    "safe_theta_and_delta",
    "bias_at_lambda_max",
    "lambda_max",
    "first_features",
    "theta_at_lambda_max",
    "xi_from_primal",
    "theta_from_primal",
    "primal_objective",
    "dual_objective",
    "duality_gap_estimate",
]


def bias_at_lambda_max(y: jax.Array, sample_mask=None) -> jax.Array:
    """Optimal bias when ``w = 0``: ``b* = (n+ - n-)/n`` (paper Sec. 4).

    A 0/1 ``sample_mask`` marks the live samples of a zero-padded problem
    (padded labels are 0): ``n`` is then the live count."""
    if sample_mask is None:
        return jnp.mean(y)
    return jnp.sum(y * sample_mask) / jnp.sum(sample_mask)


def lambda_max(X: jax.Array, y: jax.Array, sample_mask=None) -> jax.Array:
    """Smallest ``lam`` such that ``w*(lam) = 0`` (paper Eq. 26).

    ``lambda_max = || sum_i (y_i - b*) x_i ||_inf = || X (y - b*) ||_inf``.
    Computed in the row-stable formulation (``screening.row_dot``) so the
    out-of-core ``sparse.lambda_max_stream`` — a max of per-chunk maxima —
    reproduces this value bitwise and both storages walk the *same*
    default lambda grid. ``sample_mask`` as in :func:`bias_at_lambda_max`.
    """
    from .screening import row_dot  # local: keep dual.py dependency-light

    b_star = bias_at_lambda_max(y, sample_mask)
    r = y - b_star
    if sample_mask is not None:
        r = r * sample_mask
    moment = row_dot(X, r)
    return jnp.max(jnp.abs(moment))


def first_features(X: jax.Array, y: jax.Array) -> jax.Array:
    """Index of the first feature to enter the model (paper Sec. 5)."""
    b_star = bias_at_lambda_max(y)
    moment = dot(X, y - b_star)
    return jnp.argmax(jnp.abs(moment))


def theta_at_lambda_max(y: jax.Array, lam_max: jax.Array,
                        sample_mask=None) -> jax.Array:
    """Closed-form dual point at ``lam_max`` (paper Eq. 20 with w=0).

    ``theta_i = max(0, 1 - y_i b*) / lam_max``; with ``b* in [-1, 1]`` the max
    is inactive, so ``theta_i = (1 - y_i b*) / lam_max`` and ``theta^T y = 0``
    holds exactly. ``sample_mask`` as in :func:`bias_at_lambda_max` (padded
    samples get ``theta_i = 0``).
    """
    b_star = bias_at_lambda_max(y, sample_mask)
    theta = (1.0 - y * b_star) / lam_max
    return theta if sample_mask is None else theta * sample_mask


def xi_from_primal(X: jax.Array, y: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Hinge slack ``xi_i = max(0, 1 - y_i (w^T x_i + b))`` (paper Eq. 20)."""
    margins = y * (dot(X.T, w) + b)
    return jnp.maximum(0.0, 1.0 - margins)


def theta_from_primal(
    X: jax.Array, y: jax.Array, w: jax.Array, b: jax.Array, lam: jax.Array
) -> jax.Array:
    """``theta = xi / lam`` (paper Eq. 20)."""
    return xi_from_primal(X, y, w, b) / lam


def primal_objective(
    X: jax.Array, y: jax.Array, w: jax.Array, b: jax.Array, lam: jax.Array
) -> jax.Array:
    xi = xi_from_primal(X, y, w, b)
    return 0.5 * jnp.sum(xi * xi) + lam * jnp.sum(jnp.abs(w))


def dual_objective(alpha: jax.Array) -> jax.Array:
    """``D(alpha) = sum_i alpha_i - 1/2 sum_i alpha_i^2`` (from paper Eq. 13/16).

    The dual problem is ``max_alpha D(alpha)`` subject to
    ``|fhat_j^T alpha| <= lam``, ``alpha^T y = 0``, ``alpha >= 0``.
    """
    return jnp.sum(alpha) - 0.5 * jnp.sum(alpha * alpha)


class GapEstimate(NamedTuple):
    gap: jax.Array
    primal: jax.Array
    dual: jax.Array
    alpha: jax.Array  # the dual-feasible point achieving ``dual``

    @property
    def theta_radius(self):
        """``||theta_feas - theta*|| <= sqrt(2 gap)/lam`` by 1-strong concavity
        of D(alpha); divide by lam at the call site (theta = alpha/lam)."""
        return jnp.sqrt(2.0 * jnp.maximum(self.gap, 0.0))


def duality_gap_estimate(
    X: jax.Array,
    y: jax.Array,
    w: jax.Array,
    b: jax.Array,
    lam: jax.Array,
    n_feas_iters: int = 2,
) -> GapEstimate:
    """Approximate duality gap via feasibility projection of ``alpha = xi``.

    ``alpha = xi(w, b)`` satisfies the box/equality constraints only at the
    optimum; we alternate (a) rescale so ``max_j |fhat_j^T alpha| <= lam`` and
    (b) clip the ``alpha^T y = 0`` projection to stay nonnegative. The result
    is dual-feasible up to the equality residual; good enough as a stopping
    heuristic and reported as an *estimate*.
    """
    alpha = xi_from_primal(X, y, w, b)
    p_obj = 0.5 * jnp.sum(alpha * alpha) + lam * jnp.sum(jnp.abs(w))
    n = y.shape[0]

    def body(alpha, _):
        corr = dot(X, y * alpha)  # fhat_j^T alpha for all j
        scale = jnp.minimum(1.0, lam / jnp.maximum(jnp.max(jnp.abs(corr)), 1e-30))
        alpha = alpha * scale
        alpha = jnp.maximum(0.0, alpha - dot(alpha, y) / n * y)
        return alpha, None

    alpha, _ = jax.lax.scan(body, alpha, None, length=n_feas_iters)
    # final rescale so the inequality constraints hold for sure
    corr = dot(X, y * alpha)
    scale = jnp.minimum(1.0, lam / jnp.maximum(jnp.max(jnp.abs(corr)), 1e-30))
    alpha = alpha * scale
    d_obj = dual_objective(alpha)
    return GapEstimate(gap=p_obj - d_obj, primal=p_obj, dual=d_obj, alpha=alpha)


def safe_theta_and_delta(
    X: jax.Array, y: jax.Array, w: jax.Array, b: jax.Array, lam: jax.Array,
    n_feas_iters: int = 8,
) -> tuple[jax.Array, jax.Array]:
    """(theta1, delta) for screening from an *approximate* primal solution.

    theta1 is a (near-)dual-feasible point; delta upper-bounds
    ``||theta1 - theta*||`` via 1-strong concavity of the dual plus a slack
    for the residual of the ``alpha^T y = 0`` equality after the alternating
    projection. Feed both into ``screening.screen(..., delta=delta)``.
    """
    est = duality_gap_estimate(X, y, w, b, lam, n_feas_iters=n_feas_iters)
    n = y.shape[0]
    eq_resid = (jnp.abs(dot(est.alpha, y))
                / jnp.sqrt(jnp.asarray(float(n), y.dtype)))
    delta = (est.theta_radius + 2.0 * eq_resid) / lam
    return est.alpha / lam, delta
