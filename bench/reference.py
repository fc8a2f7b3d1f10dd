"""Plain reference for the L1-regularized squared-hinge SVM lambda path.

The problem, with X of shape (features m, samples n) and y in {-1, +1}^n:

    min_{w,b}  1/2 sum_i max(0, 1 - y_i (x_i^T w + b))^2 + lam ||w||_1

solved at every lambda of a decreasing grid by monotone FISTA (Beck and
Teboulle), warm-started from the previous lambda's solution, with no
screening and no reduction: every iteration sweeps all of X. Straightforward
``jax.numpy`` in float32; every contraction at ``Precision.HIGHEST``. It
imports nothing of the system under test.

``precision="high"`` computes every contraction with three bfloat16 passes
(the split that ``Precision.HIGH`` runs on a TPU, written out so that it
means the same on any backend): the lower-precision control.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _split_bf16(a):
    hi = a.astype(jnp.bfloat16)
    lo = (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def _mm_bf16(a, b):
    return jnp.matmul(a, b, preferred_element_type=jnp.float32)


def contractions(X, precision: str):
    """``(xt, xv)``: ``v -> X^T v`` (margins) and ``v -> X v`` (gradients)."""
    if precision == "highest":
        return (lambda v: jnp.matmul(v, X, precision=HIGHEST),
                lambda v: jnp.matmul(X, v, precision=HIGHEST))
    if precision != "high":
        raise ValueError(f"precision must be 'highest' or 'high': {precision}")
    X_hi, X_lo = _split_bf16(X)

    def xt(v):
        v_hi, v_lo = _split_bf16(v)
        return (_mm_bf16(v_hi, X_hi) + _mm_bf16(v_lo, X_hi)
                + _mm_bf16(v_hi, X_lo))

    def xv(v):
        v_hi, v_lo = _split_bf16(v)
        return (_mm_bf16(X_hi, v_hi) + _mm_bf16(X_hi, v_lo)
                + _mm_bf16(X_lo, v_hi))

    return xt, xv


@jax.jit
def lambda_max(X, y):
    """Smallest lambda at which ``w* = 0``: ``||X (y - mean(y))||_inf``."""
    return jnp.max(jnp.abs(jnp.matmul(X, y - jnp.mean(y), precision=HIGHEST)))


def lambda_grid(lam_max: float, grid: dict):
    """The first ``take`` points of ``total`` geometric points from
    ``lam_max`` down to ``min_ratio * lam_max`` (glmnet's grid rule)."""
    import numpy as np

    k = np.arange(int(grid["take"]), dtype=np.float64)
    step = float(grid["min_ratio"]) ** (1.0 / (int(grid["total"]) - 1))
    return lam_max * step ** k


def _objective(y, u, b, w, lam):
    xi = jnp.maximum(0.0, 1.0 - y * (u + b))
    return 0.5 * jnp.sum(xi * xi) + lam * jnp.sum(jnp.abs(w))


def _soft(v, t):
    return jnp.sign(v) * jnp.maximum(jnp.abs(v) - t, 0.0)


class _State(NamedTuple):
    w: jax.Array
    b: jax.Array
    u: jax.Array          # X^T w
    w_prev: jax.Array
    b_prev: jax.Array
    u_prev: jax.Array
    t: jax.Array
    k: jax.Array
    obj: jax.Array
    rel: jax.Array        # relative objective change, last three iterations


def _fista(xt, xv, y, lam, w0, b0, u0, inv_L, tol, max_iters):
    def prox_step(w, b, u):
        """Proximal gradient step from (w, b) with margins u."""
        xi = jnp.maximum(0.0, 1.0 - y * (u + b))
        g = -xv(y * xi)
        w_new = _soft(w - inv_L * g, inv_L * lam)
        b_new = b + inv_L * jnp.sum(y * xi)
        u_new = xt(w_new)
        return w_new, b_new, u_new, _objective(y, u_new, b_new, w_new, lam)

    def body(s: _State) -> _State:
        t_next = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * s.t * s.t))
        beta = (s.t - 1.0) / t_next
        # momentum point; its margins are the same extrapolation of u
        z = s.w + beta * (s.w - s.w_prev)
        zb = s.b + beta * (s.b - s.b_prev)
        zu = s.u + beta * (s.u - s.u_prev)
        w1, b1, u1, obj1 = prox_step(z, zb, zu)
        # monotone: where the momentum step raised the objective, take the
        # plain step from the current point instead and restart momentum
        up = obj1 > s.obj
        w1, b1, u1, obj1 = jax.lax.cond(
            up, lambda: prox_step(s.w, s.b, s.u), lambda: (w1, b1, u1, obj1))
        obj_new = jnp.minimum(obj1, s.obj)
        keep_old = obj1 > s.obj
        w_new = jnp.where(keep_old, s.w, w1)
        b_new = jnp.where(keep_old, s.b, b1)
        u_new = jnp.where(keep_old, s.u, u1)
        rel = jnp.abs(s.obj - obj_new) / jnp.maximum(jnp.abs(obj_new), 1e-30)
        return _State(w_new, b_new, u_new, s.w, s.b, s.u,
                      jnp.where(up, 1.0, t_next), s.k + 1, obj_new,
                      jnp.stack([rel, s.rel[0], s.rel[1]]))

    def cond(s: _State):
        return (s.k < max_iters) & (jnp.max(s.rel) > tol)

    obj0 = _objective(y, u0, b0, w0, lam)
    s0 = _State(w0, b0, u0, w0, b0, u0, jnp.asarray(1.0, jnp.float32),
                jnp.asarray(0, jnp.int32), obj0,
                jnp.full((3,), jnp.inf, jnp.float32))
    s = jax.lax.while_loop(cond, body, s0)
    return s.w, s.b, s.u, s.obj, s.k, jnp.max(s.rel) <= tol


def _gap(xv, y, u, b, w, lam, n_feas_iters=8):
    """Duality gap of (w, b) from the dual point alpha = xi(w, b), made
    feasible (|X_j (y alpha)| <= lam, alpha^T y = 0, alpha >= 0) by
    alternating rescaling and projection, then a last rescale."""
    n = y.shape[0]
    xi = jnp.maximum(0.0, 1.0 - y * (u + b))
    primal = 0.5 * jnp.sum(xi * xi) + lam * jnp.sum(jnp.abs(w))

    def rescale(a):
        corr = jnp.max(jnp.abs(xv(y * a)))
        return a * jnp.minimum(1.0, lam / jnp.maximum(corr, 1e-30))

    def body(a, _):
        a = rescale(a)
        return jnp.maximum(0.0, a - jnp.dot(a, y) / n * y), None

    alpha, _ = jax.lax.scan(body, xi, None, length=n_feas_iters)
    alpha = rescale(alpha)
    return primal - (jnp.sum(alpha) - 0.5 * jnp.sum(alpha * alpha))


def _lipschitz(xt, xv, n, iters=30):
    """sigma_max([X; 1^T])^2 by power iteration from a fixed start."""
    v = jax.random.normal(jax.random.key(0), (n,), jnp.float32)

    def body(v, _):
        v = v / jnp.linalg.norm(v)
        return xt(xv(v)) + jnp.sum(v), None

    v, _ = jax.lax.scan(body, v, None, length=iters)
    v = v / jnp.linalg.norm(v)
    return jnp.dot(v, xt(xv(v)) + jnp.sum(v))


class RefPath(NamedTuple):
    w: jax.Array          # (T, m)
    b: jax.Array          # (T,)
    obj: jax.Array        # (T,)
    gap: jax.Array        # (T,)
    iters: jax.Array      # (T,)
    converged: jax.Array  # (T,)


@partial(jax.jit, static_argnames=("precision", "max_iters"))
def solve_path(X, y, lambdas, tol, *, max_iters: int,
               precision: str = "highest") -> RefPath:
    """The whole path, unscreened, warm-started from ``w = 0`` and the
    optimal bias at ``lambda_max``."""
    m, n = X.shape
    xt, xv = contractions(X, precision)
    inv_L = 1.0 / (1.01 * _lipschitz(xt, xv, n))
    tol = jnp.asarray(tol, jnp.float32)

    def step(carry, lam):
        w, b, u = carry
        w, b, u, obj, k, conv = _fista(xt, xv, y, lam, w, b, u, inv_L, tol,
                                       max_iters)
        gap = _gap(xv, y, u, b, w, lam)
        return (w, b, u), (w, b, obj, gap, k, conv)

    w0 = jnp.zeros((m,), jnp.float32)
    b0 = jnp.mean(y)
    _, outs = jax.lax.scan(step, (w0, b0, jnp.zeros((n,), jnp.float32)),
                           jnp.asarray(lambdas, jnp.float32))
    return RefPath(*outs)


def objectives64(X, y, W, B, lambdas, rows=None):
    """Objective of each step's ``(W[t], B[t])`` at ``lambdas[t]`` in
    float64 on the host. Only the rows of X where some ``W[t]`` is nonzero
    enter the margins; ``rows`` (sorted feature indices, with those rows of
    X as a float64 array) may be given to share them between calls, and
    must hold every row where a ``W[t]`` is nonzero."""
    import numpy as np

    W = np.asarray(W, np.float64)
    if rows is None:
        idx = np.flatnonzero(np.any(W != 0.0, axis=0))
        rows = idx, np.asarray(jnp.take(X, jnp.asarray(idx), axis=0),
                               np.float64)
    idx, Xs = rows
    y = np.asarray(y, np.float64)
    U = W[:, idx] @ Xs                                       # (T, n)
    xi = np.maximum(0.0, 1.0 - y[None, :] * (U + np.asarray(
        B, np.float64)[:, None]))
    return (0.5 * np.sum(xi * xi, axis=1)
            + np.asarray(lambdas, np.float64) * np.sum(np.abs(W), axis=1))
