"""On-device regularization-path engine: the whole path as one XLA program.

Why a second engine
-------------------
``core/path.py::PathDriver`` (``engine="host"``) orchestrates the path from
Python: per step it screens, gathers the kept rows/columns into a bucketed
submatrix, solves, verifies sample rules at the solution, and certifies the
next region — paying a device↔host round trip, a dispatch, and (in gather
mode) a possible re-trace at every step. That is the right engine when
verified sample rules are in play (the KKT re-admission loop is inherently
host-side control flow) or when the matrix is too large for a single device.

On the bench-scale instances the opposite regime holds: solves converge in
tens of iterations and the path is *orchestration*-bound — profiles show the
host engine spending most of its wall clock blocked on transfers, eager
re-compiles of the per-step certificate, and per-solve Lipschitz power
iterations. This module is the engine for that regime (``engine="scan"``):

* the lambda grid is walked by a single jitted ``lax.scan`` whose carry is
  ``(w, b, theta, delta, lam_prev, keep_mask)`` — XLA updates the carry
  buffers in place, and nothing syncs to the host until the final stacked
  ``PathResult`` is pulled once at the end;
* each scan step rebuilds the rule stack's screening region(s) from the
  carried anchor (``screening.AnchorStats`` + the pure rule programs of
  ``rules/programs.py``), evaluates the feature bounds with the
  theta-independent reductions hoisted out of the loop (one sweep
  per step, paper Sec. 6.4), solves with the fused two-sweep FISTA body
  (``solver.fista_run``, optionally Pallas-backed and/or dynamic), and
  gap-certifies the solution (``solver.gap_theta_delta``, reusing the
  solver's carried margins) to anchor the next step;
* the Lipschitz constant is estimated once for the full ``X`` and reused by
  every step — valid because masking rows/columns never increases
  ``sigma_max`` (see ``solver.lipschitz_estimate``); per-step re-estimation
  is available via ``exact_lipschitz=True``;
* :func:`svm_path_batched` is ``vmap`` of the same step over a batch of
  problems or lambda grids — one program solving B paths at once;
* :func:`svm_path_scan_sharded` wraps the *same* program in ``shard_map`` on
  the ``svm_mesh`` (features x samples), so the whole path also runs as one
  sharded XLA program — the solver/certificate reductions bind to mesh
  collectives through ``solver.Collectives``
  (``distributed.mesh_collectives``), not a forked implementation.

Reductions inside the scan step (``reduce=``)
---------------------------------------------
``"mask"``     solves the full-shape problem with screened feature rows
               frozen at zero: static shapes, zero data movement, but every
               FISTA sweep still pays O(m·n) FLOPs no matter how many
               features screening removed.
``"compact"``  physically gathers the live features into a fixed-capacity
               padded buffer *inside* the jitted step: the keep mask is
               compacted with a ``jnp.cumsum`` scatter into a static
               ``(cap, n)`` submatrix, the fused FISTA body runs on it, and
               the solution is scattered back before the anchor is
               certified — so a step that keeps ``k`` of ``m`` features
               sweeps ``O(cap·n)``, ``cap`` the smallest bucket holding
               ``k``. The capacity comes from a small static bucket schedule
               (à la ``path.py::_bucket``; one ``lax.switch`` branch per
               bucket, so jit compiles a handful of solver bodies, not one
               per kept-count), and a kept-count overflowing the largest
               bucket falls back to the mask-mode branch — never wrong,
               only less reduced. The carry holds each step's certified
               keep mask (resurrection tracking): features re-entering the
               keep set are counted per step (``extras["resurrected"]``),
               and the buffer is sized to the certified keeps — which by
               construction contain every feature allowed to be nonzero at
               the step's lambda, warm-start support included.

Rule of thumb across the three reductions (host ``gather`` + scan
``mask``/``compact``): **gather** wins when sample rules shrink the n-axis
too or a verified-exact reduced problem is wanted (host round trips buy
multiplicative kept_m x kept_n FLOPs); **mask** wins when screening is weak
(kept ~ m, compaction would only add gather traffic) or when sharded
(compaction needs local row indices); **compact** wins whenever screening
certifies a small active set — the paper's whole value proposition —
keeping the path single-program *and* FLOP-proportional to what screening
certifies. Compaction composes with batching too: batched paths share ONE
capacity per step, picked by the scalar batch-max kept count, so the bucket
switch stays a real switch under ``vmap`` instead of lowering to a
run-every-branch select (``_batched_path_scan_program``; one overflowing
element demotes that step to mask for the whole sub-batch). Measure with
``benchmarks/bench_screening.py`` (``BENCH_screening.json["engines"]``).

Rule stacks inside the jitted step (``rules=``)
-----------------------------------------------
The scan engines accept any stack of a-priori-safe *feature* rules that
ship a jittable :class:`~repro.core.rules.programs.RuleProgram` —
``"feature_vi"`` (the paper's rule), ``"edpp"`` (projection-enhanced,
strictly tighter at equal sweep cost), ``"dvi"`` (two-anchor min
composition; the scan carry grows the step-before-last anchor), or a list
of them (bounds AND-ed elementwise inside the step). The spec is resolved
at dispatch (``rules/programs.resolve_programs``) so unlowerable specs
fail loudly before tracing. Sample rules need the a-posteriori
verification loop, which is host control flow — use ``engine="host"`` for
those (including ``"sifs"``).
"""

from __future__ import annotations

import time
from functools import lru_cache, partial
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import metrics as obs_metrics
from repro.obs.path_trace import build_path_trace
from repro.obs.trace import span

from .dual import bias_at_lambda_max, lambda_max, theta_at_lambda_max
from .linalg import dot
# _validate_grid shared with the host driver: a grid-validation change
# applied to one engine must never leave the other accepting what the
# first rejects
from .path import PathDriver, PathResult, _validate_grid, default_lambda_grid
from .rules.programs import (
    PROGRAMS,
    resolve_programs,
    stack_bounds,
    stack_needs_history,
)
from .screening import (
    SAFE_TAU,
    AnchorStats,
    FixedStats,
)
from .solver import (
    HEALTH_SCREEN_REFUSED,
    LOCAL,
    Collectives,
    _dynamic_run,
    _resolve_guards,
    _resolve_pallas,
    _sweep_operand,
    fista_run,
    gap_theta_delta_binding,
    lipschitz_estimate,
)

__all__ = [
    "svm_path_scan",
    "svm_path_batched",
    "svm_path_scan_sharded",
    "ScanPathOutputs",
    "compact_caps",
    "compact_caps_batched",
    "engine_cache_info",
]


class ScanPathOutputs(NamedTuple):
    """Stacked device-side per-step outputs of the scan engine (leading T)."""

    w: jax.Array           # (T, m)
    b: jax.Array           # (T,)
    obj: jax.Array         # (T,)
    kept: jax.Array        # (T,) int32 — live features fed to the solver
    active: jax.Array      # (T,) int32 — nnz(w) at the solution
    n_iters: jax.Array     # (T,) int32
    converged: jax.Array   # (T,) bool
    gap: jax.Array         # (T,) duality gap certified at the accepted point
    delta: jax.Array       # (T,) theta-radius anchoring the next step
    fmask: jax.Array       # (T, m) bool — the certified keep mask per step
    cap: jax.Array         # (T,) int32 — compact buffer capacity (m = mask)
    resurrected: jax.Array  # (T,) int32 — keeps the previous mask had dropped
    # (T,) int32 guard telemetry: low bits = solver rollback trips,
    # HEALTH_SCREEN_REFUSED flags a step that screened from a refused
    # (non-finite) certificate and fail-safed to keep-all. 0 = clean.
    health: jax.Array
    # (T,) int32 — certification rounds whose feasibility rescale was
    # binding (solver.gap_theta_delta_binding), of at most n_feas_iters + 1
    # per step
    feas_binding: jax.Array
    # (T,) int32 — feasibility rounds (full sweeps of X) each step's
    # certificate ran, at most n_feas_iters + 1
    feas_rounds: jax.Array


#: cap on the projecting feasibility rounds of each step's certificate; the
#: rounds stop at the first whose rescale does not bind, so a step runs at
#: most N_FEAS_ITERS + 1 (``solver.gap_theta_delta_binding``)
N_FEAS_ITERS = 8


def compact_caps(m: int, max_buckets: int = 4, min_cap: int = 32) -> tuple:
    """Static bucket schedule for the compacted active-set buffer.

    Powers of two up to ``m // 2`` (beyond that the gather/scatter overhead
    cancels the FLOP win — the mask fallback is cheaper), keeping the
    largest ``max_buckets`` so the jitted step compiles a bounded number of
    ``lax.switch`` branches. Empty for small ``m`` — compact mode then
    degenerates to mask mode.
    """
    caps = []
    c = min_cap
    while c <= m // 2:
        caps.append(c)
        c *= 2
    return tuple(caps[-max_buckets:])


def compact_caps_batched(m: int, kept_counts=None, max_buckets: int = 4,
                         min_cap: int = 32):
    """Shared-cap schedule for a *batch* of compacting path elements.

    Under ``vmap`` the per-element bucket ``lax.switch`` degenerates to a
    select (a batched predicate runs every branch), so batched compaction
    shares ONE capacity per step across the whole sub-batch: the ladder is
    the same as :func:`compact_caps`, but the branch index is a *scalar* —
    the batch-max kept count over live elements — so exactly one branch
    executes. With ``kept_counts`` given (observed or predicted per-element
    keeps), returns the shared cap that sub-batch would select (``m`` means
    the mask-mode overflow branch); with ``kept_counts=None``, returns the
    ladder itself. The path server uses the ``kept_counts`` form to pick the
    ``cap_bucket`` component of its program-cache key.
    """
    caps = compact_caps(m, max_buckets=max_buckets, min_cap=min_cap)
    if kept_counts is None:
        return caps
    ks = np.asarray(kept_counts)
    k = int(ks.max()) if ks.size else 0
    for c in caps:
        if k <= c:
            return int(c)
    return int(m)


def _batched_statics(X, y, sm, shared_x: bool):
    """Theta-independent screen reductions, per batch element.

    The sample-masked generalization of the hoisted reductions in
    ``_path_scan_program``: with a 0/1 ``sm`` the reductions are those of
    the problem with masked-out columns removed (padded columns of a
    zero-padded ``X`` contribute nothing), and ``n_tot`` is the live-sample
    count — never the padded width.
    """
    def one(Xe, ye, sme):
        d_one = dot(Xe, ye)
        d_y = dot(Xe, sme)
        d_sq = dot(Xe * Xe, sme)
        return (d_one, d_y, d_sq, jnp.sum(ye * sme), jnp.sum(sme))

    return one(X, y, sm) if shared_x else jax.vmap(one)(X, y, sm)


def _batched_path_step(
    X, y, sm, statics, inv_L, tau, tol, carry, lam, act,
    *,
    caps: tuple,
    shared_x: bool,
    max_iters: int,
    screening: bool,
    dynamic: bool,
    screen_every: int,
    use_pallas: bool,
    exact_lipschitz: bool,
    rules: tuple = ("feature_vi",),
    n_feas_iters: int = N_FEAS_ITERS,
    guards: bool = False,
):
    """One batched lambda step: screen -> shared-cap solve -> certify.

    The batched counterpart of ``_path_scan_program.step`` — same screen /
    solve / certify math per element (vmapped), but the compact bucket
    schedule is lifted to the batch level: the ``lax.switch`` index is the
    scalar batch-max kept count over live elements (``act``), so every
    element of the sub-batch compacts into the same static ``(cap, n)``
    buffer and exactly one branch runs. One element overflowing the largest
    bucket demotes the whole step to the mask branch — the price of
    shared-cap composition; never wrong, only less reduced.

    Shapes: ``lam``/``act``/``inv_L`` are ``(B,)``; carry leaves lead with
    B; ``X``/``y``/``sm``/``statics`` are shared (``shared_x=True``) or lead
    with B. ``sm`` is a 0/1 sample mask (live columns) so padded elements
    solve their true, unpadded problem. Returns ``(carry', out)`` with every
    ``ScanPathOutputs`` leaf leading with B — usable directly as a scan body
    (the full-path program below) or as a standalone jitted step (the path
    server).
    """
    m, n = X.shape[-2], X.shape[-1]
    dt = X.dtype
    B = lam.shape[0]
    ax = None if shared_x else 0
    progs = tuple(PROGRAMS[nm] for nm in rules) if screening else ()
    needs_hist = stack_needs_history(progs)
    if needs_hist:
        (w, b, theta, delta, lam_prev, fmask_prev,
         lam_old, theta_old, delta_old) = carry
    else:
        w, b, theta, delta, lam_prev, fmask_prev = carry

    def screen_one(Xe, ye, st, th, de, lp, la, *hist):
        d_one, d_y, d_sq, one_y, n_tot = st
        fixed = FixedStats(d_one=d_one, d_y=d_y, d_sq=d_sq, one_y=one_y,
                           n_tot=n_tot)

        def anchor(lam_a, th_a, de_a):
            return AnchorStats(
                lam=lam_a, delta=de_a, theta_dot_one=jnp.sum(th_a),
                theta_dot_y=dot(th_a, ye), theta_sq=dot(th_a, th_a),
                d_theta=dot(Xe, ye * th_a),
            )

        anchors = (anchor(lp, th, de),)
        if hist:
            l0, th0, de0 = hist
            anchors = (anchor(l0, th0, de0),) + anchors
        return stack_bounds(progs, la, anchors, fixed)

    # fail-safe screening: a carry anchored by a refused certificate
    # (gap_theta_delta collapses delta to inf when any component is
    # non-finite) must keep EVERY feature this step — screening degrades to
    # "no speedup", never to a wrong discard. The keep comparison is
    # NaN-safe too (~(b < tau) keeps non-finite bounds), and the refusal is
    # recorded in the step's health word below.
    anchor_ok = jnp.isfinite(delta)
    if needs_hist:
        anchor_ok = anchor_ok & jnp.isfinite(delta_old)
    with jax.named_scope("svm_path_batched/screen"):
        if screening and needs_hist:
            bounds = jax.vmap(
                screen_one, in_axes=(ax, ax, ax, 0, 0, 0, 0, 0, 0, 0))(
                X, y, statics, theta, delta, lam_prev, lam,
                lam_old, theta_old, delta_old)
            keep = (~(bounds < tau)) | (~anchor_ok)[:, None]
        elif screening:
            bounds = jax.vmap(screen_one, in_axes=(ax, ax, ax, 0, 0, 0, 0))(
                X, y, statics, theta, delta, lam_prev, lam)
            keep = (~(bounds < tau)) | (~anchor_ok)[:, None]
        else:
            keep = jnp.ones((B, m), bool)
        fmask = keep.astype(dt)
    resurrected = jnp.sum(keep & (fmask_prev < 0.5), axis=1).astype(jnp.int32)
    kept_ct = jnp.sum(fmask, axis=1).astype(jnp.int32)

    def solve(Xs, ye, sme, la, ws, bs, fms, inv_Ls, vm):
        if dynamic:
            return _dynamic_run(
                Xs, ye, la, ws, bs, inv_Ls, sme, fms,
                max_iters, tol, screen_every, tau, 4, use_pallas,
                valid_m=vm, guards=guards,
            )
        return fista_run(
            Xs, ye, la, ws, bs, inv_Ls, sme, fms,
            max_iters, tol, use_pallas, valid_m=vm, guards=guards,
        )

    def inv_L_for(Xs, inv_Ls):
        if exact_lipschitz:
            return 1.0 / jnp.maximum(lipschitz_estimate(Xs) * 1.01, 1e-12)
        return inv_Ls

    def mask_one(Xe, ye, sme, la, inv_Ls, w_, b_, fmask_):
        res = solve(Xe, ye, sme, la, w_ * fmask_, b_, fmask_,
                    inv_L_for(Xe * fmask_[:, None], inv_Ls), None)
        return (res.w, res.b, res.obj, jnp.asarray(res.n_iters, jnp.int32),
                res.converged, res.u, jnp.asarray(res.health, jnp.int32))

    def make_compact_one(cap):
        def one(Xe, ye, sme, la, inv_Ls, w_, b_, fmask_):
            # same cumsum compaction as the single-path compact branch
            with jax.named_scope("svm_path_batched/solve/compact"):
                pos = jnp.cumsum(fmask_.astype(jnp.int32)) - 1
                slot = jnp.where(fmask_ > 0.5, pos, cap)
                sel = jnp.full((cap,), m, jnp.int32).at[slot].set(
                    jnp.arange(m, dtype=jnp.int32), mode="drop")
                validf = (sel < m).astype(dt)
                selc = jnp.minimum(sel, m - 1)
                Xc = jnp.take(Xe, selc, axis=0) * validf[:, None]
                w0_c = jnp.take(w_, selc) * validf
                vcount = jnp.sum(fmask_).astype(jnp.int32)
            res = solve(Xc, ye, sme, la, w0_c, b_, validf,
                        inv_L_for(Xc, inv_Ls), vcount)
            with jax.named_scope("svm_path_batched/solve/compact"):
                w_full = jnp.zeros((m,), dt).at[selc].add(res.w * validf)
            return (w_full, res.b, res.obj,
                    jnp.asarray(res.n_iters, jnp.int32), res.converged,
                    res.u, jnp.asarray(res.health, jnp.int32))
        return one

    def batch_branch(elem):
        f = jax.vmap(elem, in_axes=(ax, ax, ax, 0, 0, 0, 0, 0))
        return lambda args: f(X, y, sm, lam, inv_L, *args)

    with jax.named_scope("svm_path_batched/solve"):
        if caps:
            caps_arr = jnp.asarray(caps, jnp.int32)
            # the switch index is a SCALAR (batch-max keeps over live
            # elements) — a batched predicate would lower the switch to a
            # select and run every branch, forfeiting the compact win
            max_kept = jnp.max(jnp.where(act, kept_ct, 0))
            idx = jnp.sum(max_kept > caps_arr)
            branches = [batch_branch(make_compact_one(c)) for c in caps]
            branches.append(batch_branch(mask_one))  # shared overflow
            w2, b2, obj, n_it, conv, u_fin, health = jax.lax.switch(
                idx, branches, (w, b, fmask))
            cap_used = jnp.full(
                (B,), jnp.asarray((*caps, m), jnp.int32)[idx])
        else:
            w2, b2, obj, n_it, conv, u_fin, health = batch_branch(mask_one)(
                (w, b, fmask))
            cap_used = jnp.full((B,), m, jnp.int32)

    def certify_one(Xe, ye, sme, w_, b_, la, u_):
        return gap_theta_delta_binding(
            Xe, ye, w_, b_, la, sme, n_feas_iters=n_feas_iters, u=u_,
            scope="svm_path_batched/certify/feasibility")

    with jax.named_scope("svm_path_batched/certify"):
        theta2, delta2, gap, feas_binding, feas_rounds = jax.vmap(
            certify_one, in_axes=(ax, ax, ax, 0, 0, 0, 0))(
            X, y, sm, w2, b2, lam, u_fin)

    out = ScanPathOutputs(
        w=w2, b=b2, obj=obj, kept=kept_ct,
        active=jnp.sum(jnp.abs(w2) > 1e-10, axis=1).astype(jnp.int32),
        n_iters=n_it, converged=conv, gap=gap, delta=delta2,
        fmask=keep, cap=cap_used, resurrected=resurrected,
        health=health | jnp.where(
            anchor_ok, 0, HEALTH_SCREEN_REFUSED).astype(jnp.int32),
        feas_binding=feas_binding, feas_rounds=feas_rounds,
    )
    new_carry = (w2, b2, theta2, delta2, lam, fmask)
    if needs_hist:
        # two-anchor programs (dvi) carry the step-before-last anchor too
        new_carry = new_carry + (lam_prev, theta, delta)
    return new_carry, out


def _batched_path_scan_program(
    X: jax.Array,
    y: jax.Array,
    sm: Optional[jax.Array],
    lambdas: jax.Array,
    w0: jax.Array,
    b0: jax.Array,
    theta0: jax.Array,
    delta0: jax.Array,
    lam0: jax.Array,
    L: Optional[jax.Array],
    tau,
    tol,
    *,
    max_iters: int,
    screening: bool,
    dynamic: bool,
    screen_every: int,
    use_pallas: bool,
    exact_lipschitz: bool,
    reduce: str = "compact",
    rules: tuple = ("feature_vi",),
    shared_x: bool = False,
    n_feas_iters: int = N_FEAS_ITERS,
    guards: bool = False,
) -> ScanPathOutputs:
    """B whole paths as one program, compaction composed with batching.

    Structure matters here: ``vmap(_path_scan_program)`` batches the bucket
    switch's predicate, which lowers the switch to a select — every branch
    executes and compact mode pays mask-mode FLOPs plus gather traffic.
    This program inverts the nesting: ``lax.scan`` over the T grid steps
    stays OUTER, the per-element work is vmapped INNER, and each step picks
    one shared compact capacity from the scalar batch-max kept count
    (:func:`_batched_path_step`). Grids must share T (ragged grids are the
    path server's job, which drives the same step one lambda at a time).

    ``shared_x``: one dataset, B grids (``X (m, n)``) vs B problems
    (``X (B, m, n)``). Anchors broadcast to B if given unbatched. ``sm`` is
    an optional 0/1 live-column mask per element — zero-padded problems
    solve their true geometry. Outputs lead with ``(B, T)``.
    """
    m, n = X.shape[-2], X.shape[-1]
    dt = X.dtype
    lambdas = jnp.asarray(lambdas, dt)
    B, _ = lambdas.shape
    tau = jnp.asarray(tau, dt)
    caps = compact_caps(m) if reduce == "compact" else ()

    if sm is None:
        sm = jnp.ones((n,), dt) if shared_x else jnp.ones((B, n), dt)
    with jax.named_scope("svm_path_batched/prologue"):
        if L is None:
            L = lipschitz_estimate(X) if shared_x else jax.vmap(
                lipschitz_estimate)(X)
        inv_L = 1.0 / jnp.maximum(
            jnp.broadcast_to(jnp.asarray(L, dt), (B,)) * 1.01, 1e-12)
        statics = _batched_statics(X, y, sm, shared_x)
    act = jnp.ones((B,), bool)
    step_kw = dict(
        caps=caps, shared_x=shared_x, max_iters=max_iters,
        screening=screening, dynamic=dynamic, screen_every=screen_every,
        use_pallas=use_pallas, exact_lipschitz=exact_lipschitz,
        rules=rules, n_feas_iters=n_feas_iters, guards=guards,
    )

    def step(carry, lam):
        return _batched_path_step(X, y, sm, statics, inv_L, tau, tol,
                                  carry, lam, act, **step_kw)

    carry0 = (
        jnp.broadcast_to(jnp.asarray(w0, dt), (B, m)),
        jnp.broadcast_to(jnp.asarray(b0, dt), (B,)),
        jnp.broadcast_to(jnp.asarray(theta0, dt), (B, n)),
        jnp.broadcast_to(jnp.asarray(delta0, dt), (B,)),
        jnp.broadcast_to(jnp.asarray(lam0, dt), (B,)),
        jnp.ones((B, m), dt),
    )
    progs = tuple(PROGRAMS[nm] for nm in rules) if screening else ()
    if stack_needs_history(progs):
        # old anchor seeded as a copy of the initial anchor: step 1's
        # two-anchor bound degenerates to the single-anchor bound, matching
        # the host DVIRule which starts with no stored anchor
        carry0 = carry0 + (carry0[4], carry0[2], carry0[3])
    _, outs = jax.lax.scan(step, carry0, jnp.swapaxes(lambdas, 0, 1))
    # scan stacks along T; callers want per-element (B, T, ...) blocks
    return jax.tree_util.tree_map(lambda a: jnp.swapaxes(a, 0, 1), outs)


def _path_scan_program(
    X: jax.Array,
    y: jax.Array,
    lambdas: jax.Array,
    w0: jax.Array,
    b0: jax.Array,
    theta0: jax.Array,
    delta0: jax.Array,
    lam0: jax.Array,
    L: Optional[jax.Array],
    tau,
    tol,
    *,
    max_iters: int,
    screening: bool,
    dynamic: bool,
    screen_every: int,
    use_pallas: bool,
    exact_lipschitz: bool,
    reduce: str = "mask",
    rules: tuple = ("feature_vi",),
    col: Collectives = LOCAL,
    n_feas_iters: int = N_FEAS_ITERS,
    guards: bool = False,
    sm: Optional[jax.Array] = None,
) -> ScanPathOutputs:
    """The traced whole-path program (one ``lax.scan`` over the grid).

    Pure function of device values — jitted (and optionally vmapped or
    shard_mapped) by the public wrappers. ``(w0, b0, theta0, delta0)`` seed
    the carry: an anchor primal/dual pair at ``lam0`` with
    ``||theta0 - theta*(lam0)|| <= delta0`` (the closed form at
    ``lambda_max`` in the standard entry points). Under ``shard_map`` the
    shapes here are the per-device blocks and ``col`` binds the reductions
    to the mesh (compact reduction requires global row indices, so it is
    local-only — wrappers enforce ``reduce="mask"`` when sharded). ``sm``
    (0/1 over samples, optional) marks the live columns of a zero-padded
    ``X`` (the sharded wrapper pads samples to the mesh); the anchor must
    already be masked.
    """
    m, n = X.shape
    dt = X.dtype
    tau = jnp.asarray(tau, dt)
    lambdas = jnp.asarray(lambdas, dt)
    caps = compact_caps(m) if reduce == "compact" else ()
    if dynamic and col is not LOCAL:
        # _dynamic_run has no collectives seam: on shard blocks it would
        # silently compute unreduced partial sums — fail loudly instead
        raise NotImplementedError(
            "dynamic in-solver screening is not plumbed through the "
            "sharded collectives seam yet; use dynamic=False when sharded"
        )

    # the per-call work on X before the first step, in one name scope
    with jax.named_scope("svm_path/prologue"):
        if L is None:
            L = lipschitz_estimate(X, col=col, sample_mask=sm)
        L = jnp.maximum(L * 1.01, 1e-12)
        inv_L = 1.0 / L
        # the Pallas sweeps' operand, padded once per path: every
        # mask-branch solve reads it (compact solves pad their own buffer)
        X_sweep = _sweep_operand(X, use_pallas, col)

        # theta-independent screen reductions, hoisted out of the scan: per
        # step only the O(mn) ``X @ (y * theta)`` sweep remains (paper
        # Sec. 6.4).
        ones = jnp.ones((n,), dt) if sm is None else sm
        d_one = col.psum_data(dot(X, y))          # fhat_j^T 1
        d_y = col.psum_data(dot(X, ones))         # fhat_j^T y
        d_sq = col.psum_data(jnp.sum(X * X, axis=1))
        one_y = col.psum_data(jnp.sum(y))
        n_tot = col.psum_data(jnp.asarray(float(n), dt) if sm is None
                              else jnp.sum(sm))
        m_tot = col.psum_model(jnp.asarray(float(m), dt)).astype(jnp.int32)

    progs = tuple(PROGRAMS[nm] for nm in rules) if screening else ()
    needs_hist = stack_needs_history(progs)
    fixed = FixedStats(d_one=d_one, d_y=d_y, d_sq=d_sq, one_y=one_y,
                       n_tot=n_tot)

    def anchor_from(lam_a, theta_a, delta_a):
        # psummed anchor scalars + the per-step O(mn) sweep — every program
        # in the stack shares these; a two-anchor stack pays one extra sweep
        return AnchorStats(
            lam=lam_a, delta=delta_a,
            theta_dot_one=col.psum_data(jnp.sum(theta_a)),
            theta_dot_y=col.psum_data(dot(theta_a, y)),
            theta_sq=col.psum_data(dot(theta_a, theta_a)),
            d_theta=col.psum_data(dot(X, y * theta_a)),
        )

    def step(carry, lam):
        if needs_hist:
            (w, b, theta, delta, lam_prev, fmask_prev,
             lam_old, theta_old, delta_old) = carry
        else:
            w, b, theta, delta, lam_prev, fmask_prev = carry

        def solve(Xs, ws, bs, fms, inv_Ls, vm, sweep_X=None):
            """Fused-FISTA (or dynamic segmented) solve on one reduction."""
            if dynamic:
                return _dynamic_run(
                    Xs, y, lam, ws, bs, inv_Ls, sm, fms,
                    max_iters, tol, screen_every, tau, 4, use_pallas,
                    valid_m=vm, guards=guards, sweep_X=sweep_X,
                )
            return fista_run(
                Xs, y, lam, ws, bs, inv_Ls, sm, fms,
                max_iters, tol, use_pallas, col=col, valid_m=vm,
                guards=guards, sweep_X=sweep_X,
            )

        # -- sequential screen from the carried anchor(s) ------------------
        # fail-safe: a refused certificate in the carry (delta collapsed to
        # inf by gap_theta_delta) keeps EVERY feature this step, and the
        # keep test itself is NaN-safe (~(b < tau) keeps non-finite bounds)
        # — an unhealthy anchor can cost speed, never a wrong discard.
        anchor_ok = jnp.isfinite(delta)
        if needs_hist:
            anchor_ok = anchor_ok & jnp.isfinite(delta_old)
        with jax.named_scope("svm_path/screen"):
            if screening:
                anchors = (anchor_from(lam_prev, theta, delta),)
                if needs_hist:
                    anchors = (anchor_from(lam_old, theta_old, delta_old),
                               ) + anchors
                bounds = stack_bounds(progs, lam, anchors, fixed)
                keep = (~(bounds < tau)) | (~anchor_ok)
            else:
                keep = jnp.ones((m,), bool)
            fmask = keep.astype(dt)

        # resurrection tracking: the carried mask records what the previous
        # step certified, so features re-entering the keep set are counted
        # per step. The buffer is sized to the certified keeps alone — they
        # already contain every feature allowed to be nonzero at this
        # lambda (a union with the carried support was considered and
        # rejected: carried-but-uncertified features are provably zero, so
        # buffering them frozen-at-zero only inflates the bucket).
        resurrected = col.psum_model(
            jnp.sum(keep & (fmask_prev < 0.5))).astype(jnp.int32)

        # -- solve on the reduced problem ----------------------------------
        def inv_L_for(Xs):
            if exact_lipschitz:
                return 1.0 / jnp.maximum(
                    lipschitz_estimate(Xs, col=col, sample_mask=sm) * 1.01,
                    1e-12)
            return inv_L

        def mask_branch(args):
            w_, b_, fmask_ = args
            # inv_L_for ignores its operand unless exact_lipschitz (the
            # masked multiply is DCE'd then), mirroring the compact branch
            res = solve(X, w_ * fmask_, b_, fmask_,
                        inv_L_for(X * fmask_[:, None]), None, X_sweep)
            return (res.w, res.b, res.obj, jnp.asarray(res.n_iters, jnp.int32),
                    res.converged, res.u, jnp.asarray(res.health, jnp.int32))

        def make_compact_branch(cap):
            def branch(args):
                w_, b_, fmask_ = args
                # the full scope name: inside the switch the name stack
                # reads ".../svm_path/solve/cond/branch_<i>_fun/..."
                with jax.named_scope("svm_path/solve/compact"):
                    # cumsum compaction: kept row j lands in slot rank(j);
                    # screened rows scatter to the dropped sentinel slot
                    pos = jnp.cumsum(fmask_.astype(jnp.int32)) - 1
                    slot = jnp.where(fmask_ > 0.5, pos, cap)
                    sel = jnp.full((cap,), m, jnp.int32).at[slot].set(
                        jnp.arange(m, dtype=jnp.int32), mode="drop")
                    validf = (sel < m).astype(dt)
                    selc = jnp.minimum(sel, m - 1)
                    # gathered from the padded sweep operand, so the
                    # kernels read the buffer as built (Xc is its logical
                    # slice)
                    Xc_s = jnp.take(X_sweep, selc, axis=0) * validf[:, None]
                    Xc = Xc_s[:, :n]
                    # every gathered row is a certified keep, so the
                    # buffer's live mask IS the validity mask; w already
                    # respects fmask on gathered rows (screened rows are
                    # not in the buffer)
                    w0_c = jnp.take(w_, selc) * validf
                    vcount = jnp.sum(fmask_).astype(jnp.int32)
                res = solve(Xc, w0_c, b_, validf, inv_L_for(Xc), vcount,
                            _sweep_operand(Xc_s, use_pallas, col))
                with jax.named_scope("svm_path/solve/compact"):
                    w_full = jnp.zeros((m,), dt).at[selc].add(
                        res.w * validf)
                return (w_full, res.b, res.obj,
                        jnp.asarray(res.n_iters, jnp.int32), res.converged,
                        res.u, jnp.asarray(res.health, jnp.int32))
            return branch

        with jax.named_scope("svm_path/solve"):
            if caps:
                caps_arr = jnp.asarray(caps, jnp.int32)
                kept_ct = jnp.sum(fmask).astype(jnp.int32)
                idx = jnp.sum(kept_ct > caps_arr)  # first bucket that fits
                branches = [make_compact_branch(c) for c in caps]
                branches.append(mask_branch)  # overflow: mask-mode fallback
                w2, b2, obj, n_it, conv, u_fin, health = jax.lax.switch(
                    idx, branches, (w, b, fmask))
                cap_used = jnp.asarray((*caps, m), jnp.int32)[idx]
            else:
                w2, b2, obj, n_it, conv, u_fin, health = mask_branch(
                    (w, b, fmask))
                cap_used = m_tot

        # -- gap-certify the accepted point: anchor for the next step ------
        # (full-X certificate — the dual feasibility max runs over every
        # feature — but the margin sweep rides the solver's carried u)
        with jax.named_scope("svm_path/certify"):
            theta2, delta2, gap, feas_binding, feas_rounds = (
                gap_theta_delta_binding(
                    X, y, w2, b2, lam, sm, n_feas_iters=n_feas_iters,
                    col=col, u=u_fin,
                ))

        out = ScanPathOutputs(
            w=w2, b=b2, obj=obj,
            kept=col.psum_model(jnp.sum(fmask)).astype(jnp.int32),
            active=col.psum_model(jnp.sum(jnp.abs(w2) > 1e-10)).astype(
                jnp.int32),
            n_iters=n_it,
            converged=conv,
            gap=gap, delta=delta2,
            fmask=keep, cap=cap_used, resurrected=resurrected,
            health=health | jnp.where(
                anchor_ok, 0, HEALTH_SCREEN_REFUSED).astype(jnp.int32),
            feas_binding=feas_binding, feas_rounds=feas_rounds,
        )
        new_carry = (w2, b2, theta2, delta2, lam, fmask)
        if needs_hist:
            # two-anchor programs (dvi) also carry the previous anchor
            new_carry = new_carry + (lam_prev, theta, delta)
        return new_carry, out

    carry0 = (w0, jnp.asarray(b0, dt), theta0, jnp.asarray(delta0, dt),
              jnp.asarray(lam0, dt), jnp.ones((m,), dt))
    if needs_hist:
        # seed the old anchor with the initial anchor: step 1's two-anchor
        # bound degenerates to the single-anchor bound, matching the host
        # DVIRule which starts with no stored anchor
        carry0 = carry0 + (jnp.asarray(lam0, dt), theta0,
                           jnp.asarray(delta0, dt))
    _, outs = jax.lax.scan(step, carry0, lambdas)
    return outs


def _engine_jit(static_kw: tuple, batched: Optional[str] = None):
    """Build (and cache) the jitted single/vmapped engine for static opts.

    ``batched``: None (single path), ``"grids"`` (shared problem, batched
    lambda grids — X/y/anchors broadcast by vmap, not materialized), or
    ``"problems"`` (independent problems, everything batched). Nothing is
    donated: no output has an anchor's shape (they are stacked per step),
    so the TPU compiler could use none of the anchor buffers.
    ``"grids_compact"``/``"problems_compact"`` route to the scan-outer /
    vmap-inner :func:`_batched_path_scan_program` (shared-cap compaction —
    the plain vmapped program would run every switch branch); note the extra
    ``sm`` argument in that program's signature.

    Cache hygiene contract (regression-tested): ``static_kw`` is a tuple of
    ``(name, value)`` pairs of hashable primitives, so the engine dict hits
    on repeated configs, and every jitted engine takes only arrays (or None)
    as runtime arguments, so repeated same-shape calls hit jit's own cache
    without retracing — :func:`engine_cache_info` exposes both layers.
    """
    key = (static_kw, batched)
    fn = _ENGINE_CACHE.get(key)
    if fn is not None:
        return fn
    if batched in ("grids_compact", "problems_compact"):
        raw = partial(_batched_path_scan_program,
                      shared_x=(batched == "grids_compact"),
                      **dict(static_kw))
    else:
        raw = partial(_path_scan_program, **dict(static_kw))
        # arg order: (X, y, lambdas, w0, b0, theta0, delta0, lam0, L, tau,
        # tol)
        if batched == "grids":
            raw = jax.vmap(raw, in_axes=(None, None, 0, None, None, None,
                                         None, None, None, None, None))
        elif batched == "problems":
            raw = jax.vmap(raw, in_axes=(0, 0, 0, 0, 0, 0, None, 0, None,
                                         None, None))
    fn = _ENGINE_CACHE[key] = jax.jit(raw)
    return fn


_ENGINE_CACHE: dict = {}


def engine_cache_info() -> dict:
    """Both warm-cache layers of the scan engines, for retrace accounting.

    Returns ``{(batched, static_opts): n_traces}`` — one entry per engine
    variant built by :func:`_engine_jit`, with ``n_traces`` the number of
    distinct traces jit holds for it (one per argument-shape signature; a
    same-config same-shape call that bumps this number is a retrace
    regression).
    """
    return {(batched, static_kw): fn._cache_size()
            for (static_kw, batched), fn in _ENGINE_CACHE.items()}


def _validate_reduce(reduce: str) -> str:
    if reduce not in ("mask", "compact"):
        raise ValueError(
            "scan-engine reduce must be 'mask' or 'compact' (gather needs "
            f"the host engine's per-step re-trace), got {reduce!r}"
        )
    return reduce


def _static_opts(max_iters, screening, dynamic, screen_every, use_pallas,
                 exact_lipschitz, reduce="mask", rules=None,
                 guards=None) -> tuple:
    # the rule spec is resolved HERE — at dispatch, not inside the trace —
    # so unlowerable specs (sample rules, containers holding them) fail
    # with resolve_programs' error before any engine is built, and the
    # resolved program tuple becomes part of the engine-cache key. The
    # screening flag is re-derived from the resolved stack: rules="none"
    # turns screening off, rules=None keeps the legacy screening=bool.
    progs = resolve_programs(rules, screening=bool(screening))
    return (
        ("max_iters", int(max_iters)),
        ("screening", bool(progs)),
        ("dynamic", bool(dynamic)),
        ("screen_every", max(int(screen_every), 1)),
        ("use_pallas", _resolve_pallas(use_pallas)),
        ("exact_lipschitz", bool(exact_lipschitz)),
        ("reduce", _validate_reduce(reduce)),
        ("rules", progs),
        # numerical health guards (core/solver.py): None resolves the
        # REPRO_SOLVER_GUARDS env default at dispatch, and the resolved bool
        # is part of the engine-cache key like every other static
        ("guards", _resolve_guards(guards)),
    )


def _to_path_result(lambdas, outs: ScanPathOutputs, lam_max_val, wall_s,
                    screening, static_kw, engine: str = "scan") -> PathResult:
    T = len(lambdas)
    opts = dict(static_kw)
    screened = bool(opts.get("screening", screening))
    per_step = np.full((T,), wall_s / max(T, 1), dtype=np.float64)
    # the uniform PathTrace artifact, synthesized post-hoc from the scan
    # carry's streamed telemetry (kept/iters/gap/delta/health ride the
    # device outputs; per-step walls are the uniform share of the blocked
    # dispatch — walls_observed=False says so)
    path_trace = build_path_trace(
        engine, lambdas, np.asarray(outs.kept, np.int64), None,
        np.asarray(outs.active, np.int64),
        np.asarray(outs.n_iters, np.int64), per_step,
        gaps=np.asarray(outs.gap, np.float64),
        deltas=np.asarray(outs.delta, np.float64),
        health=np.asarray(outs.health, np.int64),
        total_s=float(wall_s), walls_observed=False,
        meta={"reduce": opts.get("reduce"), "lam_max": float(lam_max_val)},
    )
    # same registry counters the host driver feeds (steps / guard trips /
    # kept histogram), so every engine's runs aggregate in one place
    PathDriver._observe_run(engine, np.asarray(outs.kept, np.int64),
                            np.asarray(outs.health, np.int64))
    feas_binding = np.asarray(outs.feas_binding, np.int64)
    feas_rounds = np.asarray(outs.feas_rounds, np.int64)
    # both over the rounds' cap, so the binding share keeps its meaning
    feas_cap = max(T * (N_FEAS_ITERS + 1), 1)
    obs_metrics.histogram("path.certify_binding_share").observe(
        float(feas_binding.sum()) / feas_cap)
    obs_metrics.histogram("path.certify_rounds_share").observe(
        float(feas_rounds.sum()) / feas_cap)
    return PathResult(
        lambdas=np.asarray(lambdas, np.float64),
        weights=np.asarray(outs.w, np.float64),
        biases=np.asarray(outs.b, np.float64),
        objectives=np.asarray(outs.obj, np.float64),
        kept=np.asarray(outs.kept, np.int64),
        active=np.asarray(outs.active, np.int64),
        solver_iters=np.asarray(outs.n_iters, np.int64),
        # the engine never syncs mid-path, so per-step walls are not
        # observable — report the uniform share of the (blocked) total and
        # keep the exact total in extras.
        wall_times=per_step,
        screen_times=np.zeros((T,), np.float64),
        screened=screened,
        kept_samples=np.zeros((T,), np.int64),
        verify_rounds=np.zeros((T,), np.int64),
        rules=opts.get("rules", ("feature_vi",) if screened else ()),
        extras={
            "engine": engine,
            "path_trace": path_trace,
            "lam_max": float(lam_max_val),
            "total_seconds": float(wall_s),
            "gaps": np.asarray(outs.gap, np.float64),
            "deltas": np.asarray(outs.delta, np.float64),
            "converged": np.asarray(outs.converged, bool),
            "keep_masks": np.asarray(outs.fmask, bool),
            "caps": np.asarray(outs.cap, np.int64),
            "resurrected": np.asarray(outs.resurrected, np.int64),
            # per-step guard telemetry (solver.HEALTH_SCREEN_REFUSED flags a
            # fail-safe keep-all step; low bits count solver rollbacks)
            "health": np.asarray(outs.health, np.int64),
            # per-step certification rounds whose rescale was binding, and
            # the rounds run, each of at most N_FEAS_ITERS + 1
            "feas_binding": feas_binding,
            "feas_rounds": feas_rounds,
            "options": dict(static_kw),
        },
    )


@lru_cache(maxsize=None)
def _lambda_max_program(prefix: str):
    """``dual.lambda_max`` as one jitted program whose ops carry the name
    scope ``<prefix>/lambda_max``."""
    def scoped_lambda_max(X, y):
        with jax.named_scope(f"{prefix}/lambda_max"):
            return lambda_max(X, y)

    return jax.jit(scoped_lambda_max)


def svm_path_scan(
    X: jax.Array,
    y: jax.Array,
    lambdas: Optional[Sequence[float]] = None,
    n_lambdas: int = 10,
    lam_min_ratio: float = 0.1,
    *,
    screening: bool = True,
    tau: float = SAFE_TAU,
    tol: float = 1e-9,
    max_iters: int = 4000,
    dynamic: bool = False,
    screen_every: int = 50,
    use_pallas: Optional[bool] = None,
    exact_lipschitz: bool = False,
    reduce: str = "mask",
    rules=None,
    guards: Optional[bool] = None,
) -> PathResult:
    """Solve the feature-screened path as ONE jitted XLA program.

    Semantics match ``svm_path(..., rules="feature_vi")``: every step
    screens against the previous step's gap-certified anchor, solves under
    the certified keep set to ``tol``, and certifies its own anchor — but
    with zero host involvement between the first dispatch and the final
    transfer. See the module docstring for when to prefer which engine.

    ``rules`` picks the screening-rule stack evaluated inside the jitted
    step: any spec of a-priori-safe feature rules that ship a
    :class:`~repro.core.rules.programs.RuleProgram` (``"feature_vi"``,
    ``"edpp"``, ``"dvi"``, ``"auto"``, or a list of them — the bounds are
    AND-ed elementwise). ``None`` keeps the legacy default
    (``feature_vi`` when ``screening=True``); ``"none"`` disables
    screening. Sample rules and verification-needing specs raise at
    dispatch — use ``engine="host"`` for those.

    ``reduce="compact"`` turns the keep mask into a physically gathered
    fixed-capacity active set inside the step (``jnp.cumsum`` compaction,
    static bucket schedule, mask-mode overflow fallback — module docstring),
    making per-step solver FLOPs proportional to the surviving features;
    ``reduce="mask"`` (default) keeps the full-shape zero-frozen solve.
    ``use_pallas`` routes the FISTA hot-loop sweeps through the fused Pallas
    kernels (None = env/backend policy, ``kernels/ops.fista_use_pallas``;
    compacted solves pass their live-row count so the kernels skip padded
    blocks); ``dynamic=True`` swaps each step's solve for the segmented
    ``screen_every``-interval in-solver re-screen; ``exact_lipschitz=True``
    re-runs the power iteration per step on the reduced matrix instead of
    reusing the full-X upper bound.
    """
    X = jnp.asarray(X)
    y = jnp.asarray(y)
    m, n = X.shape

    with span("svm_path.lambda_max"):
        lam_max_val = float(_lambda_max_program("svm_path")(X, y))
    if lambdas is None:
        lambdas = default_lambda_grid(lam_max_val, n_lambdas, lam_min_ratio)
    lambdas = _validate_grid(lambdas)

    # anchor at lambda_max: closed form is exact => delta = 0 (core/dual.py)
    w0 = jnp.zeros((m,), X.dtype)
    b0 = bias_at_lambda_max(y)
    theta0 = theta_at_lambda_max(y, jnp.asarray(lam_max_val, X.dtype))
    delta0 = jnp.asarray(0.0, X.dtype)

    static_kw = _static_opts(max_iters, screening, dynamic, screen_every,
                             use_pallas, exact_lipschitz, reduce, rules,
                             guards)
    with span("svm_path.place_x"):
        if dict(static_kw)["use_pallas"]:
            from repro.kernels.ops import place_row_major  # lazy: no cycle

            X = place_row_major(X, scope="svm_path/place_x")
    engine = _engine_jit(static_kw, batched=None)
    with span("svm_path.dispatch", steps=len(lambdas),
              reduce=dict(static_kw)["reduce"]):
        t0 = time.perf_counter()
        outs = engine(X, y, jnp.asarray(lambdas, X.dtype), w0, b0, theta0,
                      delta0, jnp.asarray(lam_max_val, X.dtype), None,
                      float(tau), float(tol))
        outs = jax.block_until_ready(outs)
        wall_s = time.perf_counter() - t0
    with span("svm_path.result"):
        return _to_path_result(lambdas, outs, lam_max_val, wall_s,
                               screening, static_kw)


def svm_path_scan_sharded(
    mesh,
    X: jax.Array,
    y: jax.Array,
    lambdas: Optional[Sequence[float]] = None,
    n_lambdas: int = 10,
    lam_min_ratio: float = 0.1,
    *,
    screening: bool = True,
    tau: float = SAFE_TAU,
    tol: float = 1e-9,
    max_iters: int = 4000,
    dynamic: bool = False,
    exact_lipschitz: bool = False,
    rules=None,
    guards: Optional[bool] = None,
    data_axes=("data",),
) -> PathResult:
    """The scan engine as ONE ``shard_map``'d program on the ``svm_mesh``.

    The exact step program of :func:`svm_path_scan` runs on the per-device
    blocks of a 2-D (features x samples) mesh: the screen reductions, the
    fused FISTA sweeps, the Lipschitz power iteration, and the gap
    certificate all bind their reductions to ``lax.psum``/``pmax`` over the
    mesh axes via ``distributed.mesh_collectives`` — same communication
    pattern as ``distributed.fista_sharded`` (4-scalar + per-shard-vector
    psums; margins over "model", gradients over "data"). On a trivial
    ``svm_mesh(1, 1)`` every collective is an identity, so the outputs match
    the single-device engine bitwise (tested in tests/test_path_scan.py).

    Mask reduction only (compaction needs global row indices inside the
    step — sharding the feature axis already divides the sweep); XLA sweeps
    only (the fused Pallas margin kernel finalizes xi in-kernel, which needs
    the un-psummed full margins); the dynamic in-solver re-screen is not
    yet plumbed through the collectives seam.

    ``X``/``y`` go straight from the host (or from their current
    placement) to their shards on the mesh, so ``X`` is never materialized
    whole on one device, and the setup reductions (``lambda_max``, anchors)
    run SPMD on the sharded global array. Shapes that the mesh does not
    divide are zero-padded: padded feature rows screen out and are sliced
    off the result, padded samples are masked out of the loss, the
    certificate and the anchor (``_path_scan_program``'s ``sm``).
    """
    from .distributed import mesh_collectives  # lazy: no cycle
    from jax.sharding import NamedSharding, PartitionSpec as P

    if dynamic:
        # validate at dispatch — previously this only surfaced as a
        # NotImplementedError from deep inside the traced program
        raise ValueError(
            "dynamic in-solver screening is not supported on the sharded "
            "scan engine: _dynamic_run has no collectives seam, so shard "
            "blocks would compute unreduced partial sums. Use "
            "svm_path_scan(dynamic=True) on a single device, or the host "
            "engine (svm_path(engine='host', dynamic=True))."
        )

    m, n = np.shape(X)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    d_size = int(np.prod([sizes[a] for a in data_axes]))
    m_pad = -(-m // sizes["model"]) * sizes["model"]
    n_pad = -(-n // d_size) * d_size

    def place(a, spec, shape):
        pads = [(0, t - s) for s, t in zip(np.shape(a), shape)]
        if any(p for _, p in pads):
            a = (jnp.pad(a, pads) if isinstance(a, jax.Array)
                 else np.pad(np.asarray(a), pads))
        return jax.device_put(a, NamedSharding(mesh, spec))

    X = place(X, P("model", *data_axes), (m_pad, n_pad))
    y = place(y, P(*data_axes), (n_pad,))
    sm = (None if n_pad == n else
          place(np.ones((n,), X.dtype), P(*data_axes), (n_pad,)))

    with span("svm_path_sharded.lambda_max"):
        lam_max_val = float(lambda_max(X, y, sm))
    if lambdas is None:
        lambdas = default_lambda_grid(lam_max_val, n_lambdas, lam_min_ratio)
    lambdas = _validate_grid(lambdas)

    w0 = jnp.zeros((m_pad,), X.dtype)
    b0 = bias_at_lambda_max(y, sm)
    theta0 = theta_at_lambda_max(y, jnp.asarray(lam_max_val, X.dtype), sm)
    delta0 = jnp.asarray(0.0, X.dtype)

    static_kw = _static_opts(max_iters, screening, False, 1, False,
                             exact_lipschitz, "mask", rules, guards)
    col = mesh_collectives(mesh, data_axes)

    def local_fn(Xb, yb, lams, w0b, b0b, th0b, d0b, lam0b, taub, tolb,
                 *smb):
        return _path_scan_program(
            Xb, yb, lams, w0b, b0b, th0b, d0b, lam0b, None, taub, tolb,
            col=col, sm=smb[0] if smb else None, **dict(static_kw),
        )

    in_specs = (P("model", *data_axes), P(*data_axes), P(), P("model"), P(),
                P(*data_axes), P(), P(), P(), P())
    if sm is not None:
        in_specs += (P(*data_axes),)
    out_specs = ScanPathOutputs(
        w=P(None, "model"), b=P(), obj=P(), kept=P(), active=P(),
        n_iters=P(), converged=P(), gap=P(), delta=P(),
        fmask=P(None, "model"), cap=P(), resurrected=P(),
        # replicated: the guard's trip verdict is pmax'd over the model axis
        # inside the body (solver._make_fista_body), so shards agree
        health=P(),
        # replicated: the rounds' maxima are pmax'd over the model axis
        feas_binding=P(), feas_rounds=P(),
    )
    fn = jax.jit(jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                               out_specs=out_specs, check_vma=False))
    with span("svm_path_sharded.dispatch", steps=len(lambdas)):
        t0 = time.perf_counter()
        outs = fn(X, y, jnp.asarray(lambdas, X.dtype), w0, b0, theta0,
                  delta0, jnp.asarray(lam_max_val, X.dtype),
                  jnp.asarray(float(tau), X.dtype),
                  jnp.asarray(float(tol), X.dtype),
                  *(() if sm is None else (sm,)))
        outs = jax.block_until_ready(outs)
        wall_s = time.perf_counter() - t0
    with span("svm_path_sharded.result"):
        if m_pad != m:
            fmask = outs.fmask[:, :m]
            outs = outs._replace(w=outs.w[:, :m], fmask=fmask, kept=jnp.sum(
                fmask, axis=1).astype(jnp.int32))
        r = _to_path_result(lambdas, outs, lam_max_val, wall_s, screening,
                            static_kw, engine="scan_sharded")
    r.extras["engine"] = "scan_sharded"
    r.extras["mesh"] = dict(zip(mesh.axis_names, mesh.devices.shape))
    return r


def svm_path_batched(
    X: jax.Array,
    y: jax.Array,
    lambdas: Optional[np.ndarray] = None,
    n_lambdas: int = 10,
    lam_min_ratio: float = 0.1,
    *,
    screening: bool = True,
    tau: float = SAFE_TAU,
    tol: float = 1e-9,
    max_iters: int = 4000,
    dynamic: bool = False,
    screen_every: int = 50,
    use_pallas: Optional[bool] = None,
    exact_lipschitz: bool = False,
    reduce: str = "mask",
    rules=None,
    guards: Optional[bool] = None,
) -> list[PathResult]:
    """``vmap`` of the scan engine over a batch of problems or grids.

    Two batching modes, selected by the rank of ``X``:

    * ``X (m, n)``, ``lambdas (B, T)`` — one dataset, B lambda grids
      (hyperparameter sweep / cross-validation over grids);
    * ``X (B, m, n)``, ``y (B, n)`` — B independent problems
      (multi-tenant serving), each on its own grid (``lambdas (B, T)``) or
      on its own default geometric grid anchored at its own
      ``lambda_max`` when ``lambdas`` is None.

    Executes as ONE jitted program: every sweep, reduction, and solver
    launch is batched, so B paths cost roughly one path's worth of
    launches. The usual vmap caveats apply — the while loops run until the
    slowest batch element converges and the restart ``lax.cond`` becomes a
    select — so wall clock per path is bounded by the hardest problem in
    the batch.

    ``reduce="compact"`` composes with batching through the shared-cap
    schedule (:func:`_batched_path_scan_program`): the scan over the grid
    stays outer, the per-element work is vmapped inner, and each step's
    compact capacity is picked by the *scalar* batch-max kept count — so
    one switch branch runs, FLOPs track what screening certifies, and one
    overflowing element demotes only that step to mask mode. Same rule of
    thumb as the single-path engine: compact when screening certifies a
    small active set, mask (default) when screening is weak and compaction
    would only add gather traffic. The mask-mode program is
    shard-transparent: inputs placed on a mesh (e.g. batch-sharded ``X``)
    keep their sharding through jit, which is how the sharded-solver mesh
    serves batched paths (compact mode needs local row indices — keep it
    single-device).

    Returns one :class:`~repro.core.path.PathResult` per batch element
    (shared total wall clock in ``extras["total_seconds"]``, batch size in
    ``extras["batch"]``).
    """
    X = jnp.asarray(X)
    y = jnp.asarray(y)
    static_kw = _static_opts(max_iters, screening, dynamic, screen_every,
                             use_pallas, exact_lipschitz, reduce, rules,
                             guards)
    compact = dict(static_kw)["reduce"] == "compact"
    if dict(static_kw)["use_pallas"]:
        from repro.kernels.ops import place_row_major  # lazy: no cycle

        with span("svm_path_batched.place_x"):
            X = place_row_major(X, scope="svm_path_batched/place_x")
    if X.ndim == 2:
        # one problem, B grids — X/y/anchors stay unbatched (vmap broadcasts)
        if lambdas is None:
            raise ValueError(
                "grid-batched mode (2-D X) needs an explicit (B, T) lambdas"
            )
        grids = np.asarray(lambdas, np.float64)
        if grids.ndim != 2:
            raise ValueError(f"lambdas must be (B, T), got {grids.shape}")
        B = grids.shape[0]
        for g in grids:
            _validate_grid(g)
        m = X.shape[0]
        with span("svm_path_batched.lambda_max"):
            lam_max_val = float(_lambda_max_program("svm_path_batched")(X, y))
        lam_maxs = np.full((B,), lam_max_val)
        engine = _engine_jit(
            static_kw, batched="grids_compact" if compact else "grids")
        args = (
            X, y, jnp.asarray(grids, X.dtype), jnp.zeros((m,), X.dtype),
            bias_at_lambda_max(y),
            theta_at_lambda_max(y, jnp.asarray(lam_max_val, X.dtype)),
            jnp.asarray(0.0, X.dtype), jnp.asarray(lam_max_val, X.dtype),
        )
    elif X.ndim == 3:
        B, m, _ = X.shape
        if y.ndim != 2 or y.shape[0] != B:
            raise ValueError(f"y must be (B, n) for 3-D X, got {y.shape}")
        with span("svm_path_batched.lambda_max"):
            lam_maxs = np.asarray(jax.vmap(lambda_max)(X, y), np.float64)
        if lambdas is None:
            ratios = np.geomspace(1.0, lam_min_ratio, n_lambdas)
            grids = lam_maxs[:, None] * ratios[None, :]
        else:
            grids = np.asarray(lambdas, np.float64)
            if grids.ndim == 1:
                grids = np.broadcast_to(grids, (B, grids.shape[0])).copy()
        for g in grids:
            _validate_grid(g)
        lam_maxs_j = jnp.asarray(lam_maxs, X.dtype)
        engine = _engine_jit(
            static_kw, batched="problems_compact" if compact else "problems")
        args = (
            X, y, jnp.asarray(grids, X.dtype), jnp.zeros((B, m), X.dtype),
            jax.vmap(bias_at_lambda_max)(y),
            jax.vmap(theta_at_lambda_max)(y, lam_maxs_j),
            jnp.asarray(0.0, X.dtype), lam_maxs_j,
        )
    else:
        raise ValueError(f"X must be (m, n) or (B, m, n), got {X.shape}")

    if compact:
        # the batched-compact program takes an optional per-element sample
        # mask right after (X, y) — unpadded callers pass None
        args = args[:2] + (None,) + args[2:]
    with span("svm_path_batched.dispatch", batch=B):
        t0 = time.perf_counter()
        outs = engine(*args, None, float(tau), float(tol))
        outs = jax.block_until_ready(outs)
        wall_s = time.perf_counter() - t0

    results = []
    with span("svm_path_batched.result", batch=B):
        for i in range(B):
            sub = ScanPathOutputs(*(np.asarray(v)[i] for v in outs))
            r = _to_path_result(grids[i], sub, float(lam_maxs[i]),
                                wall_s / B, screening, static_kw,
                                engine="batched")
            r.extras["total_seconds"] = float(wall_s)
            r.extras["batch"] = B
            r.extras["batch_index"] = i
            r.extras["path_trace"].meta["batch_index"] = i
            results.append(r)
    return results
