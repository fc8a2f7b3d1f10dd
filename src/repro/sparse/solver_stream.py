"""Streamed FISTA over :class:`FeatureChunked` — the solver's two O(mn)
sweeps as chunk-accumulated GEMVs.

Mirrors the fused in-core body (``core/solver.py``): the iterate carries its
margins ``u = X^T w`` so the momentum point's margins are an axpy, and one
iteration costs exactly two streams of X —

* gradient sweep  ``grad_w = -X (y xi)``: per-chunk rows, concatenated;
* margin sweep    ``u_new = X^T w_new``: per-chunk partials, accumulated —

with the monotone-restart fallback paying its two extra streams only when
it fires. Orchestration is a host loop (each chunk transfer is a host
decision), so per-iteration host sync is inherent to the out-of-core
regime; the chunk transfers themselves are double-buffered by
``FeatureChunked.stream``.

This is the implementation behind ``core/solver.fista_solve(operator=...)``
— the seam that lets every in-core call site run unchanged on data that
does not fit on the device. Objectives match the dense solver to solver
tolerance (chunk accumulation reassociates the ``X^T w`` reduction, so
bitwise equality is *not* claimed here — that contract belongs to the
screening bound sweep, see ``screen_stream.py``).

``gap_theta_delta_stream`` is the streamed twin of
``dual.safe_theta_and_delta`` (same alternating feasibility projection,
same 1-strong-concavity radius), so the chunked path driver can certify
anchors without an in-core X.

Dynamic chunk-level re-screening: ``screen_every`` turns the solve into
segments. Between segments the live duality gap certifies an at-lambda
region, the region's bounds AND into the live *feature* mask (certified
features have ``w* = 0``, so the reduced problem shares the optimum —
the standard dynamic-screening argument), and the live *chunk* set is
whatever chunks still hold a live feature — every subsequent gradient /
margin / certification sweep streams only those. Dead chunks' gradient
rows are exact zeros (their weights are pinned 0 by the mask), so mid-
solve transfer volume tracks the certified support, not ``m``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.linalg import dot
from repro.obs import trace as obs_trace
from repro.obs.trace import span

from repro.core.screening import (
    SAFE_TAU,
    FeatureReductions,
    _finalize_bounds,
    row_dot,
    shared_scalars,
)
from repro.core.solver import (
    HEALTH_SCREEN_REFUSED,
    MAX_GUARD_TRIPS,
    FistaResult,
    _resolve_guards,
    soft_threshold,
)

from .chunked import FeatureChunked

__all__ = [
    "fista_solve_chunked",
    "lipschitz_estimate_stream",
    "gap_theta_delta_stream",
]


@jax.jit
def _slacks(u, b, y, sm):
    xi = jnp.maximum(0.0, 1.0 - y * (u + b))
    return xi * sm


@jax.jit
def _objective(xi, w, lam):
    return 0.5 * jnp.sum(xi * xi) + lam * jnp.sum(jnp.abs(w))


@jax.jit
def _prox(zw, zb, gw, gb, inv_L, lam):
    return soft_threshold(zw - inv_L * gw, lam * inv_L), zb - inv_L * gb


def lipschitz_estimate_stream(fc: FeatureChunked, n_iters: int = 30,
                              key: Optional[jax.Array] = None) -> jax.Array:
    """Power iteration for ``sigma_max([X; 1^T])^2``, two streams per iter.

    Same recurrence (and start vector) as ``solver.lipschitz_estimate``; the
    chunked GEMVs reassociate the reductions, so the estimate agrees to
    float tolerance — still an upper-bound-compatible step size after the
    solver's 1% safety factor, and still monotone under row masking.
    """
    n = fc.shape[1]
    if key is None:
        key = jax.random.PRNGKey(0)
    v = jax.random.normal(key, (n,), dtype=fc.dtype)

    def norm(v):
        return jnp.sqrt(jnp.maximum(jnp.sum(v * v), 0.0))

    for _ in range(n_iters):
        v = v / jnp.maximum(norm(v), 1e-30)
        u_w = fc.matvec(v)
        u_b = jnp.sum(v)
        v = fc.rmatvec(u_w) + u_b
    return norm(v)


def _chunks_with_live_features(fc: FeatureChunked, fmask: np.ndarray) -> np.ndarray:
    """Chunk live mask: a chunk stays live while any of its features does."""
    live = np.zeros((fc.n_chunks,), dtype=bool)
    for i in range(fc.n_chunks):
        s, e = fc.chunk_bounds(i)
        live[i] = bool(fmask[s:e].any())
    return live


def fista_solve_chunked(
    fc: FeatureChunked,
    y,
    lam,
    w0=None,
    b0=None,
    max_iters: int = 2000,
    tol: float = 1e-9,
    L: Optional[jax.Array] = None,
    sample_mask=None,
    feature_mask=None,
    screen_every: Optional[int] = None,
    screen_tau: float = SAFE_TAU,
    report: Optional[dict] = None,
    guards: Optional[bool] = None,
    iteration_hook=None,
) -> FistaResult:
    """Solve the primal over chunked storage (see module docstring).

    Same contract as ``solver.fista_solve`` (warm starts, path-shared ``L``,
    0/1 ``sample_mask`` dropping loss columns); device memory stays at one
    chunk plus ``O(m + n)`` vectors.

    ``feature_mask`` (bool ``(m,)``) pins screened features to zero and
    derives the live *chunk* set every sweep streams over — a chunk with no
    live feature is never transferred. ``screen_every`` additionally
    re-certifies from the live duality gap between segments (at-lambda VI
    region), shrinking both masks mid-solve; ``report`` (a dict, mutated)
    receives ``screens`` / ``live_chunks`` / ``kept`` telemetry.

    ``guards`` (None = ``REPRO_SOLVER_GUARDS`` env default) is the host-loop
    twin of the in-core numerical health guard: a non-finite objective after
    a step, or a post-restart increase beyond rounding noise, rolls back to
    the last accepted iterate with a halved step size; trips are bounded by
    ``MAX_GUARD_TRIPS`` and returned in ``FistaResult.health``. Checking the
    *objective* alone suffices — any NaN/inf in ``w``/``u``/``b`` propagates
    into it through ``lam * sum|w|`` and the slacks. ``iteration_hook``
    (fault-injection seam, ``testing/faults.py``) is called as
    ``hook(k, w, b, u, obj) -> None | (w, b, u, obj)`` on each candidate
    iterate before the guard inspects it.
    """
    m, n = fc.shape
    y_key = y
    y = jnp.asarray(y, fc.dtype)
    lam = jnp.asarray(lam, fc.dtype)
    sm = (jnp.ones_like(y) if sample_mask is None
          else jnp.asarray(sample_mask, fc.dtype))
    if L is None:
        L = lipschitz_estimate_stream(fc)
    L = jnp.maximum(jnp.asarray(L, fc.dtype) * 1.01, 1e-12)
    inv_L = 1.0 / L

    dynamic = screen_every is not None and screen_every > 0
    if feature_mask is not None:
        fmask = np.asarray(feature_mask, bool).copy()
    else:
        fmask = np.ones((m,), dtype=bool)
    masked = not fmask.all()
    live = _chunks_with_live_features(fc, fmask) if (masked or dynamic) else None
    live_arg = None if (live is None or live.all()) else live
    fmask_dev = jnp.asarray(fmask, fc.dtype)

    if dynamic:
        from .screen_stream import fixed_reductions

        d_one, d_y, d_sq = fixed_reductions(fc, y_key)

    guards = _resolve_guards(guards)
    health = 0
    backoff = 1.0

    if w0 is None:
        w = jnp.zeros((m,), fc.dtype)
        u = jnp.zeros((n,), fc.dtype)
    else:
        w = jnp.asarray(w0, fc.dtype)
        if guards and not bool(jnp.all(jnp.isfinite(w))):
            # sanitize the warm start (cf. solver._init_state): w = 0 is
            # always feasible, and a poisoned coordinate would otherwise
            # poison every later iterate through the carried margins
            w = jnp.where(jnp.isfinite(w), w, jnp.zeros_like(w))
            health += 1
        if masked:
            w = w * fmask_dev
        u = fc.rmatvec(w, live_chunks=live_arg)
    b = jnp.asarray(jnp.mean(y) if b0 is None else b0, fc.dtype)
    if guards and not bool(jnp.isfinite(b)):
        b = jnp.asarray(0.0, fc.dtype)
        health += 1

    xi = _slacks(u, b, y, sm)
    obj = _objective(xi, w, lam)
    w_prev, b_prev, u_prev = w, b, u
    t = 1.0
    tol = float(tol)
    k = 0
    converged = False
    rel_prev = rel_prev2 = float("inf")
    n_screens = 0

    def prox_from(w_a, b_a, u_a, inv_Le):
        """One proximal step anchored at known margins: 2 streams of X
        (live chunks only — dead rows are pinned zero by the mask)."""
        xi_a = _slacks(u_a, b_a, y, sm)
        gv = y * xi_a
        gw = -fc.matvec(gv, live_chunks=live_arg)
        gb = -jnp.sum(gv)
        w_new, b_new = _prox(w_a, b_a, gw, gb, inv_Le, lam)
        if masked:
            w_new = w_new * fmask_dev
        u_new = fc.rmatvec(w_new, live_chunks=live_arg)
        obj_new = _objective(_slacks(u_new, b_new, y, sm), w_new, lam)
        return w_new, b_new, u_new, obj_new

    eps = float(jnp.finfo(fc.dtype).eps)
    with span("stream.solve") as sp:
        while k < max_iters:
            inv_Le = inv_L * backoff if guards else inv_L
            t_next = 0.5 * (1.0 + float(jnp.sqrt(1.0 + 4.0 * t * t)))
            beta = (t - 1.0) / t_next
            zw = w + beta * (w - w_prev)
            zb = b + beta * (b - b_prev)
            uz = u + beta * (u - u_prev)

            w_new, b_new, u_new, obj_new = prox_from(zw, zb, uz, inv_Le)
            restarted = float(obj_new) > float(obj)
            if restarted:
                # monotone restart: plain step from (w, b) — margins are
                # carried
                w_new, b_new, u_new, obj_new = prox_from(w, b, u, inv_Le)
                t_next = 1.0

            if iteration_hook is not None:
                hooked = iteration_hook(k, w_new, b_new, u_new, obj_new)
                if hooked is not None:
                    w_new, b_new, u_new, obj_new = hooked

            if guards:
                obj_f = float(obj_new)
                # a non-finite objective, or a *plain* (post-restart) step that
                # still increased it beyond rounding noise: the step size is
                # invalid — roll back, halve it, restart momentum (cf. the
                # on-device guard in solver._make_fista_body)
                bad = not np.isfinite(obj_f) or (
                    restarted and obj_f > float(obj)
                    + 256.0 * eps * max(abs(float(obj)), 1.0))
                if bad:
                    health += 1
                    backoff *= 0.5
                    w_prev, b_prev, u_prev, t = w, b, u, 1.0
                    rel_prev = rel_prev2 = float("inf")
                    k += 1
                    trips = health & (HEALTH_SCREEN_REFUSED - 1)
                    if trips >= MAX_GUARD_TRIPS:
                        break  # unrecoverable: poisoned operands (see solver)
                    continue

            # restart iterations are not convergence evidence (cf. the in-core
            # body): force one more plain iteration after every restart
            rel = (float("inf") if restarted
                   else abs(float(obj) - float(obj_new))
                   / max(abs(float(obj)), 1e-30))
            w_prev, b_prev, u_prev = w, b, u
            w, b, u, obj, t = w_new, b_new, u_new, obj_new, t_next
            k += 1
            # three consecutive sub-tol iterations (see
            # solver.FistaState.rel_prev)
            if max(rel, rel_prev, rel_prev2) <= tol:
                converged = True
                break
            rel_prev, rel_prev2 = rel, rel_prev

            if dynamic and k % int(screen_every) == 0 and k < max_iters:
                # segment boundary: certify the reduced problem's gap, screen
                # the at-lambda region, AND into the live masks
                theta, delta = gap_theta_delta_stream(
                    fc, y, w, b, lam, u=u,
                    live_chunks=live_arg, feature_mask=fmask_dev)
                if not bool(jnp.isfinite(delta)):
                    # refused certificate (non-finite gap/theta, sanitized to
                    # delta = inf): screening from it could discard a live
                    # feature — fail-safe to keep-all for this segment
                    health |= HEALTH_SCREEN_REFUSED
                    continue
                yt = y * theta
                parts = []
                for i in range(fc.n_chunks):
                    s, e = fc.chunk_bounds(i)
                    parts.append(jnp.zeros((e - s,), fc.dtype))
                for (s, e), dev in fc.stream(live_chunks=live_arg):
                    i = int(np.searchsorted(fc.offsets[1:], s, side="right"))
                    parts[i] = (row_dot(dev, yt)
                                if isinstance(dev, jnp.ndarray)
                                else dev @ yt)
                red = FeatureReductions(d_theta=jnp.concatenate(parts),
                                        d_one=d_one, d_y=d_y, d_sq=d_sq)
                sh = shared_scalars(y, lam, lam, theta, delta=delta)
                # NaN-safe keep: a non-finite bound must KEEP its feature
                keep = np.asarray(~(_finalize_bounds(red, sh) < screen_tau))
                new_fmask = fmask & keep
                n_screens += 1
                obs_trace.instant("stream.solve.screen", iter=k,
                                  kept=int(new_fmask.sum()))
                if new_fmask.sum() < fmask.sum():
                    fmask = new_fmask
                    masked = True
                    fmask_dev = jnp.asarray(fmask, fc.dtype)
                    live = _chunks_with_live_features(fc, fmask)
                    live_arg = None if live.all() else live
                    w = w * fmask_dev
                    u = fc.rmatvec(w, live_chunks=live_arg)
                    obj = _objective(_slacks(u, b, y, sm), w, lam)
                    # mask change invalidates momentum: restart cleanly
                    w_prev, b_prev, u_prev, t = w, b, u, 1.0
                    rel_prev = rel_prev2 = float("inf")
        sp.set(iters=k, converged=bool(converged), screens=n_screens,
               kept=int(fmask.sum()))

    if report is not None:
        report.update(
            screens=n_screens,
            kept=int(fmask.sum()),
            live_chunks=int(live.sum()) if live is not None else fc.n_chunks,
        )
    return FistaResult(
        w=w, b=b, obj=obj, n_iters=jnp.asarray(k, jnp.int32),
        converged=jnp.asarray(converged), u=u,
        health=jnp.asarray(health, jnp.int32),
    )


def gap_theta_delta_stream(
    fc: FeatureChunked,
    y,
    w,
    b,
    lam,
    n_feas_iters: int = 8,
    u: Optional[jax.Array] = None,
    live_chunks=None,
    feature_mask=None,
    want_corr: bool = False,
):
    """Streamed ``(theta1, delta)`` certificate — twin of
    ``dual.safe_theta_and_delta``.

    Each feasibility iteration needs the correlation sweep ``X (y * alpha)``
    (the rescale is a max over the problem's features), so this costs
    ``n_feas_iters + 1`` streams; ``u`` (margins ``X^T w``, e.g. the
    solver's carried ones) saves the extra margin stream.

    ``live_chunks`` / ``feature_mask`` certify the *reduced* problem
    instead: features already screened out have ``w* = 0``, so the reduced
    problem shares the full optimum and its dual-feasibility max runs over
    live features only — that is what lets both mid-solve certification
    (dynamic chunked solves) and the path driver's between-step anchor
    certification skip dead chunks' transfers.

    ``want_corr=True`` returns ``(theta1, delta, d_theta)`` where
    ``d_theta = X (y * theta1)`` falls out of the *final* rescale's own
    correlation sweep (``theta1 = s * alpha / lam`` implies
    ``X (y * theta1) = s * corr / lam`` — zero extra streams). Entries in
    skipped chunks are zeros: only live chunks' slices are valid, which is
    exactly what the chunk-skip cache refresh consumes
    (``ChunkScreenCache.refresh``). The feature mask applies to the rescale
    *max* only, so live chunks' ``d_theta`` entries are valid for every
    feature in them, screened or not.
    """
    y = jnp.asarray(y, fc.dtype)
    lam = jnp.asarray(lam, fc.dtype)
    if u is None:
        u = fc.rmatvec(jnp.asarray(w, fc.dtype), live_chunks=live_chunks)
    xi = jnp.maximum(0.0, 1.0 - y * (u + jnp.asarray(b, fc.dtype)))
    alpha = xi
    n = y.shape[0]
    fm = None if feature_mask is None else jnp.asarray(feature_mask, fc.dtype)

    def rescale(alpha):
        corr = fc.matvec(y * alpha, live_chunks=live_chunks)
        mx = jnp.max(jnp.abs(corr if fm is None else corr * fm))
        s = jnp.minimum(1.0, lam / jnp.maximum(mx, 1e-30))
        return alpha * s, corr * s

    for _ in range(n_feas_iters):
        alpha, _ = rescale(alpha)
        alpha = jnp.maximum(0.0, alpha - dot(alpha, y) / n * y)
    alpha, corr = rescale(alpha)

    gap = (0.5 * jnp.sum(xi * xi)
           + lam * jnp.sum(jnp.abs(jnp.asarray(w, fc.dtype)))
           - (jnp.sum(alpha) - 0.5 * jnp.sum(alpha * alpha)))
    eq_resid = (jnp.abs(dot(alpha, y))
                / jnp.sqrt(jnp.asarray(float(n), fc.dtype)))
    delta = (jnp.sqrt(2.0 * jnp.maximum(gap, 0.0)) + 2.0 * eq_resid) / lam
    theta = alpha / lam
    # certificate sanitize (twin of solver.gap_theta_delta): any non-finite
    # component refuses the certificate — delta = inf is the one downstream
    # signal ("isfinite(delta)") that screening from this anchor is unsafe
    cert_ok = (jnp.isfinite(gap) & jnp.isfinite(delta)
               & jnp.all(jnp.isfinite(theta)))
    delta = jnp.where(cert_ok, delta, jnp.asarray(jnp.inf, fc.dtype))
    if want_corr:
        return theta, delta, corr / lam
    return theta, delta
