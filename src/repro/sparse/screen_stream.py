"""Chunk-accumulated safe-screening bound sweep over :class:`FeatureChunked`.

The paper's O(mn) screen reduces each feature row independently (the four
per-feature reductions of ``core/screening.py``), so it streams perfectly:
sweep one feature chunk at a time, concatenate the per-chunk reductions, and
finalize with the same closed-form bound — the device never holds more than
one chunk of ``X``.

Bitwise contract
----------------
For dense chunks the per-chunk reduction is the *same jitted row-stable
kernel* (``core/screening._row_stable_reductions`` / ``row_dot``) the
in-core sweep uses, and row-stable reductions are invariant to the leading
row count — so ``screen_stream`` on any chunking returns **bitwise** the
bounds of ``core/screening.screen_bounds`` on the dense matrix (asserted in
``tests/test_sparse_stream.py``). BCOO chunks (low-density CSR) use sparse
matvecs instead — FLOPs proportional to ``nnz`` — which reassociate the
reduction; they carry a tolerance guarantee, and screening *safety* is
unaffected either way (the tau margin absorbs ulp noise by design).

Per-chunk Pallas route: ``use_pallas=True`` sends each dense chunk through
the fused TPU bound kernel (``kernels/ops.screen_bounds_op``) instead — the
bound finalizer is per-row, so evaluating it per chunk with the globally
shared scalars is exact. fp32 kernel accumulation makes this a tolerance
route too; default policy is Mosaic-on-TPU, XLA elsewhere.

Theta-independent caching (paper Sec. 6.4): ``d_one``, ``d_y``, ``d_sq``
do not depend on the anchor, so a path driver screens T lambdas with
``T + 1`` streams of X, not ``4T`` — :func:`fixed_reductions` computes them
once and memoizes on the container.

Chunk-level screening (the skip plane)
--------------------------------------
:class:`ChunkScreenCache` remembers, per chunk, the anchor (scalars +
that chunk's fresh ``d_theta`` slice) from the step the chunk was last
streamed. A VI region built from *any* certified anchor stays safe for
every smaller target lambda, so evaluating the cached anchor's bounds at
the current ``lam2`` — pure per-feature arithmetic, zero streams — yields
valid safe bounds for the whole chunk. When the chunk's max bound falls
below tau the chunk is certified dead *before* its ``device_put``:
:func:`screen_step_stream` streams only the live chunks (refreshing their
cache entries) and stamps the dead chunks' features with their
(stale-anchor, still-valid) bounds. The full-stream twin (``skip=False``)
runs the *identical* cache policy and arithmetic but transfers every chunk
anyway — which is what makes "skip vs full-stream is bitwise equal" a
testable property rather than a hope.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import metrics as obs_metrics
from repro.obs.trace import span
from repro.core.rules.programs import PROGRAMS, stack_bounds
from repro.core.screening import (
    SAFE_TAU,
    AnchorStats,
    FeatureReductions,
    _finalize_bounds,
    _row_stable_reductions,
    anchor_slice,
    anchor_stats,
    finalize_from_anchor_jit,
    fixed_slice,
    fixed_stats,
    row_dot,
    shared_scalars,
)

from .chunked import FeatureChunked

__all__ = [
    "fixed_reductions",
    "stream_feature_reductions",
    "stream_anchor_stats",
    "stream_sample_stats",
    "screen_bounds_stream",
    "screen_stream",
    "screen_stack_stream",
    "screen_step_stream",
    "ChunkScreenCache",
    "lambda_max_stream",
]

_FIXED_CACHE = "_fixed_reductions"


def fixed_reductions(fc: FeatureChunked, y) -> tuple[jax.Array, jax.Array, jax.Array]:
    """``(d_one, d_y, d_sq)`` for every feature, streamed once and memoized.

    The cache is keyed on the identity of the *caller's* ``y`` object — not
    the dtype-converted copy, which would be fresh every call and silently
    turn the "T + 1 streams per path" contract into 2T+ (one dataset per
    container is the expected usage; a different ``y`` object recomputes).
    """
    cached = getattr(fc, _FIXED_CACHE, None)
    if cached is not None and cached[0] is y:
        return cached[1]
    y_key = y
    y = jnp.asarray(y, fc.dtype)
    d_one, d_y, d_sq = [], [], []
    for (_, _), dev in fc.stream():
        if isinstance(dev, jnp.ndarray):
            _, o, dy, sq = _row_stable_reductions(dev, y, y)
            d_one.append(o), d_y.append(dy), d_sq.append(sq)
        else:  # BCOO: sparse matvecs + data-side row norms
            d_one.append(dev @ y)
            d_y.append(dev @ jnp.ones_like(y))
            d_sq.append(_bcoo_row_sq(dev))
    out = (jnp.concatenate(d_one), jnp.concatenate(d_y), jnp.concatenate(d_sq))
    setattr(fc, _FIXED_CACHE, (y_key, out))
    return out


@functools.partial(jax.jit, static_argnums=(2,))
def _bcoo_row_sq_impl(data, rows, n_rows):
    return jax.ops.segment_sum(data * data, rows, num_segments=n_rows)


def _bcoo_row_sq(dev) -> jax.Array:
    """``||f_j||^2`` of a BCOO chunk from its data (nnz work, no densify)."""
    return _bcoo_row_sq_impl(dev.data, dev.indices[:, 0], int(dev.shape[0]))


def stream_feature_reductions(fc: FeatureChunked, y, theta1) -> FeatureReductions:
    """The four screening reductions for every feature, one stream of X."""
    # cache first, with the caller's y object (see fixed_reductions), then
    # convert for the local arithmetic
    d_one, d_y, d_sq = fixed_reductions(fc, y)
    y = jnp.asarray(y, fc.dtype)
    theta1 = jnp.asarray(theta1, fc.dtype)
    yt = y * theta1
    parts = []
    for (_, _), dev in fc.stream():
        parts.append(row_dot(dev, yt) if isinstance(dev, jnp.ndarray)
                     else dev @ yt)
    return FeatureReductions(d_theta=jnp.concatenate(parts), d_one=d_one,
                             d_y=d_y, d_sq=d_sq)


def screen_bounds_stream(
    fc: FeatureChunked,
    y,
    lam1,
    lam2,
    theta1,
    delta=0.0,
    use_pallas: Optional[bool] = None,
) -> jax.Array:
    """Upper bound on ``|fhat_j^T theta*(lam2)|``, chunk-streamed.

    XLA route (default off-TPU): per-chunk row-stable reductions + the
    shared jitted finalizer — bitwise vs the in-core sweep on dense chunks.
    Pallas route: per-chunk fused bound kernel (TPU hot path).
    """
    from repro.kernels.ops import fista_use_pallas  # lazy: no import cycle

    if fista_use_pallas(use_pallas):
        from repro.kernels.ops import screen_bounds_op

        from .chunked import CsrChunk

        y = jnp.asarray(y, fc.dtype)
        theta1 = jnp.asarray(theta1, fc.dtype)
        parts = []
        # iterate the host chunks directly (densifying CSR ones) rather
        # than fc.stream(): the fused kernel needs dense input, and going
        # through stream() would build-and-discard a BCOO per sparse chunk
        # — a second transfer the stats would record as the one used
        for i, c in enumerate(fc.chunks):
            dense = c.to_dense(fc.dtype) if isinstance(c, CsrChunk) else c
            rows = dense.shape[0]
            fc._bump("puts")
            fc.stats["max_put_rows"] = max(fc.stats["max_put_rows"], rows)
            obs_metrics.gauge("stream.max_put_rows").set_max(rows)
            parts.append(screen_bounds_op(jnp.asarray(dense, fc.dtype), y,
                                          lam1, lam2, theta1, delta=delta))
        return jnp.concatenate(parts)

    red = stream_feature_reductions(fc, y, theta1)
    sh = shared_scalars(jnp.asarray(y, fc.dtype), lam1, lam2,
                        jnp.asarray(theta1, fc.dtype), delta=delta)
    return _finalize_bounds(red, sh)


def screen_stream(
    fc: FeatureChunked,
    y,
    lam1,
    lam2,
    theta1,
    tau: float = SAFE_TAU,
    delta=0.0,
    use_pallas: Optional[bool] = None,
) -> tuple[jax.Array, jax.Array]:
    """Safe screening over chunked storage: ``(keep_mask, bounds)``."""
    bounds = screen_bounds_stream(fc, y, lam1, lam2, theta1, delta=delta,
                                  use_pallas=use_pallas)
    # NaN-safe keep: a non-finite bound certifies nothing — keep the feature
    return ~(bounds < tau), bounds


def stream_anchor_stats(fc: FeatureChunked, y, lam1, theta1, delta=0.0,
                        live_chunks=None, cache: Optional["ChunkScreenCache"] = None,
                        ) -> AnchorStats:
    """:class:`~repro.core.screening.AnchorStats` from ONE stream of X.

    The only chunk-streamed component is the per-feature ``d_theta`` sweep
    (same row-stable kernel as :func:`stream_feature_reductions`); the
    anchor scalars are in-core reductions of ``theta1``/``y``. Callers that
    evaluate multi-anchor stacks (dvi) should hold on to the returned
    pytree — re-using last step's anchor costs zero extra streams.

    ``live_chunks`` restricts the sweep to live chunks; dead chunks fill
    their ``d_theta`` slice from ``cache`` (stale values — only valid to
    read through the cache's own stale-anchor bounds, which is exactly what
    the chunk-skip plane does; live-chunk entries are refreshed in place).
    """
    y = jnp.asarray(y, fc.dtype)
    theta1 = jnp.asarray(theta1, fc.dtype)
    yt = y * theta1
    if live_chunks is None:
        parts = [row_dot(dev, yt) if isinstance(dev, jnp.ndarray) else dev @ yt
                 for (_, _), dev in fc.stream()]
        anchor = anchor_stats(y, lam1, theta1, delta, jnp.concatenate(parts))
        if cache is not None:
            cache.refresh(anchor, live=None)
        return anchor
    if cache is None:
        raise ValueError("live_chunks needs a ChunkScreenCache for the "
                         "dead chunks' d_theta slices")
    live = set(fc.live_order(live_chunks))
    it = fc.stream(live_chunks=live_chunks)
    parts = []
    for i in range(fc.n_chunks):
        if i in live:
            dev = next(it)[1]
            parts.append(row_dot(dev, yt) if isinstance(dev, jnp.ndarray)
                         else dev @ yt)
        else:
            parts.append(cache.d_theta_slice(i))
    anchor = anchor_stats(y, lam1, theta1, delta, jnp.concatenate(parts))
    cache.refresh(anchor, live=live)
    return anchor


def stream_sample_stats(fc: FeatureChunked, y, w1, b1) -> tuple[jax.Array, jax.Array]:
    """The transposed (sample-axis) sweep: ``(u1, x_sq)`` chunk-accumulated.

    ``u1 = X^T w1 + b1`` rides :meth:`FeatureChunked.rmatvec` and
    ``x_sq = ||x_i||^2`` per sample rides the memoized
    :meth:`FeatureChunked.col_sq` — together they are every input
    :func:`~repro.core.rules.sample_vi.margin_surplus_core` needs, so
    ``sifs``/``sample_vi`` screening runs out-of-core without
    ``as_dense()``. Costs one stream for ``u1`` (skippable via the caller's
    live set when ``w1`` is certified zero on dead chunks) and one
    once-per-container stream for ``x_sq``.
    """
    w1 = jnp.asarray(w1, fc.dtype)
    u1 = fc.rmatvec(w1) + jnp.asarray(b1, fc.dtype)
    return u1, fc.col_sq()


class ChunkScreenCache:
    """Per-chunk stale-anchor state for chunk-level safe screening.

    For each chunk: the :class:`AnchorStats` scalars from the step the
    chunk was last streamed, plus the chunk's ``d_theta`` slice from that
    same stream. :meth:`live_mask` evaluates each cached chunk's VI bounds
    at the *current* target ``lam2`` — valid because a certified anchor's
    region is safe for any smaller lambda (see
    ``core/screening.finalize_from_anchor_jit``) — and declares a chunk
    dead when even its loosest surviving feature bound is below tau. Dead
    chunks keep their (stale) cache entries; live chunks are refreshed
    after each stream, so the staleness of any chunk is exactly "how long
    it has been certifiably dead".
    """

    def __init__(self, fc: FeatureChunked):
        self.fc = fc
        self._scalars: list = [None] * fc.n_chunks  # (lam, delta, tdo, tdy, tsq)
        self._d_theta: list = [None] * fc.n_chunks
        self._lam_host: list = [None] * fc.n_chunks  # float lam for the guard

    def d_theta_slice(self, i: int) -> jax.Array:
        part = self._d_theta[i]
        if part is None:
            raise ValueError(f"chunk {i} marked dead but never streamed")
        return part

    def refresh(self, anchor: AnchorStats, live=None) -> None:
        """Record ``anchor`` as the cached region for the streamed chunks
        (``live=None`` = all). ``anchor.d_theta`` must be full-``m``.

        A poisoned anchor (any non-finite scalar or ``d_theta`` entry) must
        never become a cached region — its stale bounds could later certify
        a live chunk dead. Such an anchor *invalidates* the entries it would
        have refreshed instead: ``live_mask`` then treats those chunks as
        never-streamed (+inf stale bounds, always live), i.e. the cache
        fail-safes to streaming everything it can no longer vouch for.
        """
        lam_host = float(anchor.lam)
        bad = not (np.isfinite(lam_host)
                   and np.isfinite(float(anchor.delta))
                   and np.isfinite(float(anchor.theta_dot_one))
                   and np.isfinite(float(anchor.theta_dot_y))
                   and np.isfinite(float(anchor.theta_sq))
                   and bool(jnp.all(jnp.isfinite(anchor.d_theta))))
        scalars = (anchor.lam, anchor.delta, anchor.theta_dot_one,
                   anchor.theta_dot_y, anchor.theta_sq)
        for i in range(self.fc.n_chunks):
            if live is not None and i not in live:
                continue
            if bad:
                self._scalars[i] = None
                self._d_theta[i] = None
                self._lam_host[i] = None
                continue
            s, e = self.fc.chunk_bounds(i)
            self._scalars[i] = scalars
            self._d_theta[i] = anchor.d_theta[s:e]
            self._lam_host[i] = lam_host

    def chunk_anchor(self, i: int) -> Optional[AnchorStats]:
        if self._scalars[i] is None:
            return None
        lam, delta, tdo, tdy, tsq = self._scalars[i]
        return AnchorStats(lam=lam, delta=delta, theta_dot_one=tdo,
                           theta_dot_y=tdy, theta_sq=tsq,
                           d_theta=self._d_theta[i])

    def live_mask(self, lam2, fixed, tau: float = SAFE_TAU,
                  ) -> tuple[np.ndarray, Optional[jax.Array]]:
        """``(live, stale_bounds)`` for the current target ``lam2``.

        ``live[i]`` is True when chunk ``i`` must be streamed (no cache yet,
        or some cached bound survives tau). ``stale_bounds`` is the full
        ``(m,)`` vector of cached-anchor bounds (+inf for never-streamed
        chunks): every finite entry is a *valid* safe bound, and for dead
        chunks every entry is < tau — the caller stamps these over the
        dead features so the returned bounds stay honest without a stream.
        """
        fc = self.fc
        live = np.ones((fc.n_chunks,), dtype=bool)
        parts = []
        lam2_host = float(lam2)
        for i in range(fc.n_chunks):
            s, e = fc.chunk_bounds(i)
            a = self.chunk_anchor(i)
            # the stale region certifies only strictly-smaller targets
            if a is None or not lam2_host < self._lam_host[i]:
                parts.append(jnp.full((e - s,), jnp.inf, fc.dtype))
                continue
            b = finalize_from_anchor_jit(a, lam2, fixed_slice(fixed, s, e))
            parts.append(b)
            # NaN-safe liveness: a non-finite bound must keep its chunk live
            live[i] = not bool(jnp.max(b) < tau)
        return live, jnp.concatenate(parts)


def screen_step_stream(
    fc: FeatureChunked,
    y,
    lam1,
    lam2,
    theta1,
    delta=0.0,
    rules: tuple = ("feature_vi",),
    tau: float = SAFE_TAU,
    cache: Optional[ChunkScreenCache] = None,
    anchor_old: Optional[AnchorStats] = None,
    skip: bool = True,
    use_pallas: Optional[bool] = None,
):
    """One path step's screening with chunk-level skipping.

    Returns ``(keep, bounds, anchor, live)``: the per-feature keep mask and
    bounds, the fresh :class:`AnchorStats` (for multi-anchor stacks), and
    the chunk live mask actually used. Dead chunks — certified by their
    cached stale-anchor bounds — are never transferred when ``skip`` is
    True; with ``skip=False`` the identical decisions are made but every
    chunk is streamed (full-stream twin, for equivalence testing and as the
    no-cache baseline). Their features carry the stale bounds (valid, all
    < tau) so ``keep = bounds >= tau`` needs no side-band mask.

    With ``rules == ("feature_vi",)`` and no ``anchor_old`` the bounds ride
    the same kernels as :func:`screen_stream` (bitwise vs in-core on dense
    chunks, Pallas-eligible); other stacks go through
    :func:`~repro.core.rules.programs.stack_bounds` on the fresh anchors.

    Multi-anchor stacks (dvi) disable the skip: a returned anchor whose
    dead-chunk ``d_theta`` entries are stale would be *invalid* as next
    step's old anchor for features whose chunk comes back alive (a dead
    chunk's bounds grow again as ``lam2`` shrinks) — so history-carrying
    stacks stream every chunk, every step, and chunk skipping stays a
    single-anchor-stack feature. The cache itself already plays the
    old-anchor role there, per chunk.
    """
    from repro.kernels.ops import fista_use_pallas  # lazy: no import cycle

    with span("stream.screen", chunks=int(fc.n_chunks),
              skip=bool(skip)) as sp:
        y_key = y
        d_one, d_y, d_sq = fixed_reductions(fc, y)
        y = jnp.asarray(y, fc.dtype)
        theta1 = jnp.asarray(theta1, fc.dtype)
        fixed = fixed_stats(y, d_one, d_y, d_sq)

        if cache is None:
            cache = ChunkScreenCache(fc)
        needs_hist = (anchor_old is not None
                      or any(PROGRAMS[nm].n_anchors > 1 for nm in rules))
        if needs_hist:
            live = np.ones((fc.n_chunks,), dtype=bool)
            stale_bounds = None
        else:
            live, stale_bounds = cache.live_mask(lam2, fixed, tau)
        live_arg = None if bool(live.all()) else live

        pure_vi = tuple(rules) == ("feature_vi",) and anchor_old is None
        if pure_vi and fista_use_pallas(use_pallas):
            anchor, bounds = _pallas_step(fc, y_key, y, lam1, lam2, theta1,
                                          delta, cache, live, skip)
        else:
            anchor = stream_anchor_stats(
                fc, y_key, lam1, theta1, delta=delta,
                live_chunks=live_arg if skip else None,
                cache=cache if skip else None)
            if not skip:
                # full-stream twin: the transfer happened for every chunk, but
                # cache entries for dead chunks must NOT advance — identical
                # cache evolution to the skipping run is what makes the two
                # modes bitwise-comparable — so refresh the live set only.
                cache.refresh(anchor,
                              live=set(int(i) for i in np.nonzero(live)[0]))
            if pure_vi:
                red = FeatureReductions(d_theta=anchor.d_theta, d_one=d_one,
                                        d_y=d_y, d_sq=d_sq)
                sh = shared_scalars(y, lam1, lam2, theta1, delta=delta)
                bounds = _finalize_bounds(red, sh)
            else:
                anchors = ((anchor,) if anchor_old is None
                           else (anchor_old, anchor))
                progs = tuple(PROGRAMS[nm] for nm in rules)
                bounds = stack_bounds(progs, lam2, anchors, fixed)

        if not bool(live.all()):
            dead_feat = np.repeat(
                ~live, np.diff(fc.offsets).astype(np.int64))
            bounds = jnp.where(jnp.asarray(dead_feat), stale_bounds, bounds)
        # NaN-safe keep: a non-finite bound certifies nothing — keep the
        # feature
        keep = ~(bounds < tau)
        sp.set(live=int(np.count_nonzero(live)))
    return keep, bounds, anchor, live


def _pallas_step(fc, y_key, y, lam1, lam2, theta1, delta, cache, live, skip):
    """Pure-VI chunk loop through the fused TPU bound kernel, with the same
    live gating as the XLA route. One transfer per live chunk serves both
    the fused bounds and the ``d_theta`` cache refresh."""
    from repro.kernels.ops import screen_bounds_op

    from .chunked import CsrChunk

    yt = y * theta1
    bounds_parts, d_parts = [], []
    for i, c in enumerate(fc.chunks):
        s, e = fc.chunk_bounds(i)
        if not live[i] and skip:
            fc._bump("chunks_skipped")
            bounds_parts.append(jnp.zeros((e - s,), fc.dtype))  # stamped over
            d_parts.append(cache.d_theta_slice(i))
            continue
        dense = c.to_dense(fc.dtype) if isinstance(c, CsrChunk) else c
        dense = np.asarray(dense, fc.dtype)
        fc._bump("puts")
        fc._bump("chunks_streamed")
        fc._bump("bytes_put", dense.nbytes)
        fc.stats["max_put_rows"] = max(fc.stats["max_put_rows"],
                                       dense.shape[0])
        obs_metrics.gauge("stream.max_put_rows").set_max(dense.shape[0])
        dev = jnp.asarray(dense)
        bounds_parts.append(screen_bounds_op(dev, y, lam1, lam2, theta1,
                                             delta=delta))
        d_parts.append(row_dot(dev, yt) if live[i] else cache.d_theta_slice(i))
    anchor = anchor_stats(y, lam1, theta1, delta, jnp.concatenate(d_parts))
    cache.refresh(anchor, live=set(int(i) for i in np.nonzero(live)[0]))
    return anchor, jnp.concatenate(bounds_parts)


def screen_stack_stream(
    fc: FeatureChunked,
    y,
    lam2,
    anchors,
    rules,
    tau: float = SAFE_TAU,
) -> tuple[jax.Array, jax.Array]:
    """Rule-program stack screening over chunked storage.

    Generalizes :func:`screen_stream` from the hard-coded VI bound to any
    stack of scan-lowerable rule programs (``rules`` is a tuple of names in
    :data:`~repro.core.rules.programs.PROGRAMS`): the theta-independent
    reductions come from the memoized :func:`fixed_reductions`, ``anchors``
    are :func:`stream_anchor_stats` pytrees (oldest first — a two-anchor
    program consumes the last two), and the bound finalizers are pure
    per-feature arithmetic, so nothing here streams X again. XLA route
    only; the fused Pallas chunk kernel stays VI-only (``screen_stream``),
    which the host driver uses for the pure-VI fast path anyway.
    """
    progs = tuple(PROGRAMS[nm] for nm in rules)
    d_one, d_y, d_sq = fixed_reductions(fc, y)
    fixed = fixed_stats(jnp.asarray(y, fc.dtype), d_one, d_y, d_sq)
    bounds = stack_bounds(progs, lam2, anchors, fixed)
    # NaN-safe keep: a non-finite bound certifies nothing — keep the feature
    return ~(bounds < tau), bounds


def lambda_max_stream(fc: FeatureChunked, y) -> jax.Array:
    """``|| X (y - mean y) ||_inf`` without an in-core X (cf. dual.lambda_max).

    A max of per-chunk maxima is exact (max is associative), and the
    per-chunk moment rows ride the same row-stable kernel as
    ``dual.lambda_max`` — so on dense chunks this matches the in-core value
    **bitwise**, and both storages walk identical default lambda grids.
    """
    y = jnp.asarray(y, fc.dtype)
    v = y - jnp.mean(y)
    best = jnp.asarray(0.0, fc.dtype)
    for (_, _), dev in fc.stream():
        moment = row_dot(dev, v) if isinstance(dev, jnp.ndarray) else dev @ v
        best = jnp.maximum(best, jnp.max(jnp.abs(moment)))
    return best
