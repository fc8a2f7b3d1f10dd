"""DVI rule: dual variational-inequality screening from a *pair* of anchors.

The sequential feature rule certifies ``theta*(lam2)`` from the most recent
anchor ``theta(lam1)`` only. But along a path every previously solved dual
point is a valid anchor: the VI set built from the step-before-last point
``theta(lam0)`` (with its own inexactness radius ``delta0``) also contains
``theta*(lam2)`` whenever ``lam0 > lam2``. Intersecting the two sets can
only shrink the certificate, and the cheap relaxation of the intersection is
the elementwise minimum of the two per-feature bounds — each is a valid
upper bound on ``|fhat_j^T theta*(lam2)|``, so their min is too (this is the
"DVI" composition of Liu et al., "Safe Screening with Variational
Inequalities and Its Application to Lasso", transplanted to the paper's
squared-hinge dual geometry).

When it helps: near a kink of the path the latest anchor's halfspace can be
nearly uninformative (``theta(lam1) - 1/lam1`` almost parallel to ``y``)
while the older anchor still cuts the ball; and for a *coarse* grid the
older region's smaller ``1/lam0`` offset occasionally dominates. Cost: one
extra ``X @ (y * theta0)`` sweep per step — the three theta-independent
reductions are shared with the primary bound via the cached statics.

Stateful like the sample rule: ``bounds`` remembers the incoming region's
anchor for the next step; ``prepare`` resets the history (so the first
screened step, having one anchor only, degenerates exactly to feature_vi).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..linalg import dot
from ..screening import (
    SAFE_TAU,
    FeatureReductions,
    screen_bounds_from_reductions,
    shared_scalars,
)
from .base import ConvexRegion, register_rule
from .feature_vi import FeatureVIRule

__all__ = ["DVIRule"]


@register_rule("dvi")
class DVIRule(FeatureVIRule):
    """Feature screening from the min of the last and step-before-last
    anchors' VI bounds. A-priori safe (each constituent bound is).

    Scan-lowerable via ``PROGRAMS["dvi"]`` (``n_anchors = 2``): the scan
    engines carry the step-before-last anchor in the scan carry instead of
    on this object, seeding it with a copy of the initial anchor so step 1
    degenerates to plain VI exactly like the host path does.
    """

    program = "dvi"

    def __init__(self, tau: float = SAFE_TAU):
        super().__init__(tau=tau)
        self._anchor: Optional[tuple] = None  # (lam0, theta0, delta0)

    def prepare(self, X: jax.Array, y: jax.Array) -> None:
        super().prepare(X, y)
        self._anchor = None

    def bounds(self, X: jax.Array, y: jax.Array, region: ConvexRegion) -> jax.Array:
        b = super().bounds(X, y, region)
        anchor = self._anchor
        # the old anchor certifies theta*(lam2) only when screening downward
        # from it (lam0 > lam2); a replayed/non-monotone step invalidates it
        if anchor is not None and anchor[0] > region.lam2:
            lam0, theta0, delta0 = anchor
            sh0 = shared_scalars(y, jnp.asarray(lam0), jnp.asarray(region.lam2),
                                 theta0, delta=delta0)
            d_theta0 = dot(X, y * theta0)
            if self._static is not None:
                d_one, d_y, d_sq = self._static
                red0 = FeatureReductions(d_theta=d_theta0, d_one=d_one,
                                         d_y=d_y, d_sq=d_sq)
            else:
                ones = jnp.ones((X.shape[1],), X.dtype)
                red0 = FeatureReductions(
                    d_theta=d_theta0, d_one=dot(X, y), d_y=dot(X, ones),
                    d_sq=jnp.sum(X * X, axis=1))
            b = jnp.minimum(b, screen_bounds_from_reductions(red0, sh0))
        self._anchor = (region.lam1, region.theta1, region.delta)
        return b
