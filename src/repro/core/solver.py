"""Jittable FISTA solver for the L1-regularized L2-loss SVM (paper Eq. 1/23).

Unconstrained composite form (paper Eq. 23):

    min_{w,b}  h(w, b) + lam ||w||_1,
    h(w, b) = 1/2 sum_i max(0, 1 - y_i (w^T x_i + b))^2

``h`` is convex with Lipschitz-continuous gradient (the squared hinge is C^1),
so accelerated proximal gradient (FISTA) applies; the prox of ``lam||.||_1``
is soft-thresholding on ``w`` only (``b`` is unpenalized).

Gradients (paper Eqs. 24-25), with xi = max(0, 1 - y*(X^T w + b)):

    grad_w = -X (y * xi),     grad_b = -y^T xi

Lipschitz constant: L <= sigma_max([X; 1^T])^2, estimated by power iteration.
Along a path the estimate for the *full* X upper-bounds the constant of any
row/column-masked (or gathered) subproblem — removing rows/columns of a
matrix never increases its largest singular value — so drivers estimate L
once per path and thread it through every reduced solve (see
``core/path.py`` / ``core/path_scan.py``; per-solve re-estimation stays
available via their ``exact_lipschitz`` opt-in).

Everything is pure ``jax.lax`` control flow: the whole solve jit-compiles to
one XLA program (and runs unchanged under shard_map — see
``core/distributed.py``).

Performance architecture — the fused hot loop
---------------------------------------------
A FISTA iteration needs margins at the momentum point z (for the gradient)
and the objective at the new iterate (for the monotone-restart test). The
naive body pays three full sweeps of X per iteration — ``X^T z`` (margins),
``X (y xi)`` (gradient), ``X^T w_new`` (objective) — plus two more when the
restart fires. This body pays **two**:

* the state carries ``u = X^T w`` and ``u_prev = X^T w_prev``; since the
  momentum point is the linear extrapolation ``z = w + beta (w - w_prev)``,
  its margins are ``u + beta (u - u_prev)`` — an O(n) axpy, no sweep;
* the sweep at the new iterate is *fused*: one pass over X produces
  ``u_new``, the slacks ``xi_new``, and the squared-hinge loss (and hence
  the objective), so the old separate ``_objective`` sweep is gone. On TPU
  this is the Pallas kernel ``kernels/hinge.py::hinge_margin`` (fp32 VMEM
  accumulation, loss partials reduced per block); elsewhere it is the same
  computation in XLA. Dispatch is per-call/env via
  ``kernels/ops.py::fista_use_pallas`` (``use_pallas=``,
  ``REPRO_FISTA_PALLAS``; interpret-mode fallback off-TPU honors
  ``REPRO_PALLAS_INTERPRET``);
* the monotone-restart fallback (a plain proximal step from ``(w, b)``) sits
  under ``lax.cond``, so its two extra sweeps are paid only on iterations
  whose extrapolated step actually increased the objective — not eagerly on
  every iteration as the pre-fusion ``tree_map(where, ...)`` body did.
  (Under ``vmap`` — the batched path engine — XLA lowers the cond to a
  select and both branches run; correctness is unaffected.)

Dynamic (in-solver) screening — ``fista_solve_dynamic``
-------------------------------------------------------
The VI region certifying ``theta*(lam)`` shrinks as the iterate converges:
with ``theta`` the gap-certified dual-feasible point at the *current*
``(w, b)`` and ``delta = O(sqrt(gap))`` its distance bound to ``theta*``,
the at-lambda region (``lam1 = lam2 = lam``) is the ball through ``theta``
cut by its own tangent halfspace — a set of diameter ``O(sqrt(R*delta))``
that collapses onto ``theta*`` as the gap goes to zero. Features whose
bound over that set stays below 1 are provably inactive at ``lam`` and can
be zeroed *mid-solve* (Liu et al.-style dynamic screening), which compounds
multiplicatively with the between-lambda sequential screen.

``fista_solve_dynamic`` therefore runs a segmented solve: an outer
``lax.while_loop`` whose body (a) runs up to ``screen_every`` plain FISTA
iterations, (b) computes the duality gap of the (possibly sample-masked)
problem, (c) rebuilds the region from the current iterate and re-evaluates
the feature bounds, and (d) ANDs the result into a live feature mask that
zeroes screened coordinates for all remaining iterations. Per-segment
kept-counts and gaps are returned as telemetry (`DynamicFistaResult`).
"""

from __future__ import annotations

import os
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .linalg import dot
from .screening import (
    SAFE_TAU,
    FeatureReductions,
    screen_bounds_from_reductions,
    shared_scalars_from_stats,
)

__all__ = [
    "Collectives",
    "LOCAL",
    "FistaState",
    "FistaResult",
    "DynamicFistaResult",
    "lipschitz_estimate",
    "soft_threshold",
    "fista_solve",
    "fista_solve_dynamic",
    "fista_run",
    "gap_theta_delta",
    "gap_theta_delta_binding",
]


class Collectives(NamedTuple):
    """Reduction seam: the four cross-shard reductions the solver math needs.

    Every O(mn) routine in this module reduces over exactly two axes — the
    feature ("model") axis for margins/L1 norms and the sample ("data") axis
    for gradients/losses — plus a replicated bias-gradient reduction and a
    max for the dual-feasibility rescale. Parameterizing the implementations
    over these four callables lets ONE body serve both execution modes:

    * :data:`LOCAL` (the default) binds all four to the identity, which is
      exactly the single-device math — same ops, same order, bitwise;
    * ``distributed.mesh_collectives`` binds them to ``lax.psum``/``pmax``
      over the ``svm_mesh`` axes, which is how the sharded path engine
      (``path_scan.svm_path_scan_sharded``) runs this module's FISTA body,
      gap certificate, and Lipschitz power iteration inside ``shard_map``
      without a forked implementation.
    """

    psum_model: "object"  # reduce over the feature axis (margins, sum|w|)
    psum_data: "object"   # reduce over the sample axis (grads, losses)
    psum_bias: "object"   # bias grad: global sum averaged over model replicas
    pmax_model: "object"  # max over the feature axis (dual feasibility)


def _identity(x):
    return x


# The local binding: every reduction is already global. Note for sharded
# bindings (distributed.mesh_collectives): a psum over a size-1 mesh axis
# must bind to this same identity, not to a degenerate all-reduce — a
# trivial all-reduce is value-preserving but changes XLA's fusion context,
# and the resulting 1-ulp objective differences flip the monotone-restart /
# stopping predicates exactly at their convergence-plateau ties, breaking
# the sharded-vs-local bitwise guarantee (tests/test_path_scan.py).
LOCAL = Collectives(_identity, _identity, _identity, _identity)

#: Cap on health-guard rollbacks per solve. Each trip halves the step size,
#: so 8 trips leave a 256x smaller step — a solve still tripping past that
#: is unrecoverable (poisoned operands), and bounding the trips keeps a
#: NaN'd problem from burning max_iters on rollback churn.
MAX_GUARD_TRIPS = 8

#: Bit set in ``health`` when a screening refresh was *refused* because the
#: gap certificate was non-finite (the fail-safe kept every feature). Low
#: bits count solver guard trips (rollbacks + sanitized warm starts).
HEALTH_SCREEN_REFUSED = 1 << 16


def _resolve_guards(flag: Optional[bool] = None) -> bool:
    """Numerical health guards default ON; ``REPRO_SOLVER_GUARDS=0``
    disables them (the bench's guard-off baseline). Resolved at dispatch so
    the flag lands in jit static args — an env read inside a trace would not
    retrace on change (cf. ``_resolve_pallas``)."""
    if flag is not None:
        return bool(flag)
    return os.environ.get("REPRO_SOLVER_GUARDS", "1").lower() not in (
        "0", "false", "off")


class FistaState(NamedTuple):
    w: jax.Array
    b: jax.Array
    w_prev: jax.Array
    b_prev: jax.Array
    u: jax.Array       # X^T w      (margins of the current point, no bias)
    u_prev: jax.Array  # X^T w_prev
    t: jax.Array
    k: jax.Array
    obj: jax.Array
    rel_change: jax.Array
    # previous iterations' rel_change: convergence requires THREE consecutive
    # sub-tol iterations. In fp32 the objective's relative ulp is ~6e-8, so
    # below that any single rel_change is an exact-tie coin flip — FISTA's
    # momentum plateaus produce such ties mid-trajectory while ``w`` is
    # still moving (observed: a one-ulp different L stops 2.4e-5 short of
    # the optimum on a plateau the other L sails through; a single
    # look-back still stranded 1.3e-6). A run of three ties at a
    # non-optimum is rare enough that engines with reassociated reductions
    # (chunked storage, sharded meshes) agree to <=1e-6.
    rel_prev: jax.Array = jnp.inf
    rel_prev2: jax.Array = jnp.inf
    # health-guard state (guards on only — see _make_fista_body): rollback
    # trip count, and the multiplicative step-size backoff the trips applied.
    # A trip means the candidate iterate was non-finite or a plain prox step
    # increased the objective — both say the current step size is invalid.
    health: jax.Array = 0
    backoff: jax.Array = 1.0


class FistaResult(NamedTuple):
    w: jax.Array
    b: jax.Array
    obj: jax.Array
    n_iters: jax.Array
    converged: jax.Array
    # margins u = X^T w at the accepted point (carried by the fused body, so
    # returning them is free); callers certifying the solution can hand them
    # to gap_theta_delta and skip its re-sweep. None from legacy paths.
    u: Optional[jax.Array] = None
    # int32 guard telemetry: low bits count rollback trips (0 = clean solve),
    # HEALTH_SCREEN_REFUSED flags a refused screening refresh. None from
    # legacy paths that never threaded guards.
    health: Optional[jax.Array] = None


class DynamicFistaResult(NamedTuple):
    """`FistaResult` plus in-solver screening telemetry.

    ``kept_per_segment[s]`` is the live-feature count after segment ``s``'s
    re-screen; ``gap_per_segment[s]`` the duality-gap estimate it certified
    the region from. Segments never run (early convergence) hold the
    sentinel ``-1`` / ``inf``.
    """

    w: jax.Array
    b: jax.Array
    obj: jax.Array
    n_iters: jax.Array
    converged: jax.Array
    feature_mask: jax.Array      # (m,) bool — final live mask
    kept_per_segment: jax.Array  # (S,) int32
    gap_per_segment: jax.Array   # (S,) float
    n_segments: jax.Array        # int32 — segments actually run
    u: Optional[jax.Array] = None  # X^T w at the accepted point (see FistaResult)
    # dynamic *sample* re-screen telemetry (``dynamic_samples=True`` only):
    # final live sample mask and per-segment live-sample counts. The sample
    # screen is margin-*predicted*, not a-priori safe — callers must verify
    # screened samples at the solution (core/path.py's verification loop
    # does) before treating the result as exact.
    sample_mask: Optional[jax.Array] = None          # (n,) bool
    kept_samples_per_segment: Optional[jax.Array] = None  # (S,) int32
    # guard telemetry, same encoding as FistaResult.health
    health: Optional[jax.Array] = None


def soft_threshold(x: jax.Array, tau: jax.Array) -> jax.Array:
    return jnp.sign(x) * jnp.maximum(jnp.abs(x) - tau, 0.0)


def _rel3(s: "FistaState") -> jax.Array:
    """Worst rel_change of the last three iterations (the stop criterion —
    see ``FistaState.rel_prev``)."""
    return jnp.maximum(jnp.maximum(s.rel_change, s.rel_prev), s.rel_prev2)


def lipschitz_estimate(X: jax.Array, n_iters: int = 30, key: Optional[jax.Array] = None,
                       col: Collectives = LOCAL,
                       sample_mask: Optional[jax.Array] = None) -> jax.Array:
    """Power iteration for ``sigma_max([X; 1^T])^2`` (augmented bias row).

    Monotonicity along a path: any row/column submatrix of ``[X; 1^T]`` that
    keeps the bias row (which every masked/gathered subproblem does) has
    ``sigma_max`` no larger than the full matrix's, so this estimate is a
    valid step-size bound for every screened solve of the same path
    (property-tested in tests/test_path_scan.py).

    ``col`` binds the two GEMV reductions to mesh collectives when ``X`` is a
    ``shard_map`` block (under sharding every data shard seeds the same local
    key, so the implied global start vector is block-periodic — any nonzero
    start is valid for power iteration). ``sample_mask`` (0/1 over samples)
    restricts the bias row to the live samples of a zero-padded ``X``.
    """
    n = X.shape[1]
    if key is None:
        key = jax.random.PRNGKey(0)
    v = jax.random.normal(key, (n,), dtype=X.dtype)
    bias_row = 1.0 if sample_mask is None else sample_mask

    def norm(v):
        return jnp.sqrt(jnp.maximum(col.psum_data(jnp.sum(v * v)), 0.0))

    def body(v, _):
        v = v / jnp.maximum(norm(v), 1e-30)
        u_w = col.psum_data(dot(X, v))
        u_b = col.psum_data(jnp.sum(v * bias_row))
        v = col.psum_model(dot(X.T, u_w)) + u_b * bias_row
        return v, None

    v, _ = jax.lax.scan(body, v, None, length=n_iters)
    return norm(v)  # ||A^T A v|| / ||v|| with ||v||=1 pre-normalized


def _objective(X, y, w, b, lam, sample_mask=None):
    xi = jnp.maximum(0.0, 1.0 - y * (dot(X.T, w) + b))
    if sample_mask is not None:
        xi = xi * sample_mask
    return 0.5 * jnp.sum(xi * xi) + lam * jnp.sum(jnp.abs(w))


def _margin_obj_sweep(X, y, lam, w, b, sm, use_pallas, col=LOCAL, valid_m=None):
    """One fused pass over X: ``(u = X^T w, objective(w, b))``.

    The Pallas route also folds the loss partials into the sweep; with a
    sample mask the (cheap, O(n)) masked loss is recomputed from the
    returned slacks, so no second pass over X is ever needed. ``valid_m``
    (dynamic scalar, Pallas route only) marks rows past the compacted active
    set so the kernel can skip their blocks. The Pallas route needs the full
    margins locally (xi is finalized in-kernel), so it is single-device only
    — sharded callers (``col`` non-local) take the XLA path.
    """
    if use_pallas and col is LOCAL:
        from repro.kernels.ops import margin_obj_op  # lazy: no import cycle

        u, xi, loss = margin_obj_op(X, w, y, b, valid_m=valid_m)
        u = u.astype(X.dtype)
        if sm is not None:
            xi = xi.astype(X.dtype) * sm
            loss = 0.5 * jnp.sum(xi * xi)
        loss = jnp.asarray(loss, X.dtype)
    else:
        u = col.psum_model(dot(X.T, w))
        xi = jnp.maximum(0.0, 1.0 - y * (u + b))
        if sm is not None:
            xi = xi * sm
        loss = col.psum_data(0.5 * jnp.sum(xi * xi))
    return u, loss + lam * col.psum_model(jnp.sum(jnp.abs(w)))


def _grad_sweep(X, y, xi, use_pallas, col=LOCAL, valid_m=None):
    """``grad_w = -X (y * xi)`` — the transposed pass over X (one entry per
    row of ``X``: a padded kernel operand yields padded rows, which the
    caller slices off)."""
    if use_pallas and col is LOCAL:
        from repro.kernels.ops import hinge_grad_op  # lazy: no import cycle

        return hinge_grad_op(X, y, xi, valid_m=valid_m).astype(X.dtype)
    return col.psum_data(-dot(X, y * xi))


def _sweep_operand(X, use_pallas, col=LOCAL):
    """The matrix the FISTA sweeps read: on the Pallas route, ``X`` padded
    once to the kernels' block multiples (``kernels/ops.kernel_operand``),
    so no sweep of the solve copies X; otherwise ``X`` itself."""
    if use_pallas and col is LOCAL:
        from repro.kernels.ops import kernel_operand  # lazy: no import cycle

        return kernel_operand(X)
    return X


def _init_state(X, y, lam, w0, b0, sm, use_pallas, col=LOCAL,
                valid_m=None, guards=False) -> FistaState:
    trips = jnp.asarray(0, jnp.int32)
    if guards:
        # sanitize the warm start: a poisoned w0/b0 (NaN/inf from a faulted
        # previous path step) would poison every later iterate through the
        # carried margins; zeroing the bad coordinates is always feasible
        # (w = 0 is in the domain) and counts one trip.
        bad0 = (~jnp.all(jnp.isfinite(w0))) | (~jnp.isfinite(b0))
        # mesh-consistent verdict: w0 is a shard block under shard_map, so
        # every shard must agree on the trip (divergent health would split
        # the while-loop conds and deadlock the body's psums). Identity
        # under LOCAL.
        bad0 = col.pmax_model(bad0.astype(X.dtype)) > 0.5
        w0 = jnp.where(jnp.isfinite(w0), w0, jnp.zeros_like(w0))
        b0 = jnp.where(jnp.isfinite(b0), b0, jnp.zeros_like(b0))
        trips = bad0.astype(jnp.int32)
    u0, obj0 = _margin_obj_sweep(X, y, lam, w0, b0, sm, use_pallas, col,
                                 valid_m)
    return FistaState(
        w=w0, b=b0, w_prev=w0, b_prev=b0, u=u0, u_prev=u0,
        t=jnp.asarray(1.0, X.dtype), k=jnp.asarray(0, jnp.int32),
        obj=obj0, rel_change=jnp.asarray(jnp.inf, X.dtype),
        rel_prev=jnp.asarray(jnp.inf, X.dtype),
        rel_prev2=jnp.asarray(jnp.inf, X.dtype),
        health=trips, backoff=jnp.asarray(1.0, X.dtype),
    )


def _make_fista_body(X, y, lam, inv_L, sm, fmask=None, use_pallas=False,
                     col=LOCAL, valid_m=None, guards=False):
    """One FISTA iteration ``FistaState -> FistaState`` as a closure.

    ``fmask`` (0/1 over features, optional) freezes screened coordinates at
    zero: the prox output is masked, so a coordinate once zeroed stays zero
    — this is exactly the problem with those feature rows removed (the rows
    contribute nothing to the margins either, since ``w_j = 0``). Shared by
    :func:`fista_solve` and the dynamic solver's inner segments.

    Cost: 2 fused sweeps of X per iteration (gradient at the momentum point,
    margins+objective at the new point); +2 under ``lax.cond`` when the
    monotone restart fires. See the module docstring for the architecture.

    ``guards`` adds the on-device numerical health guard: a non-finite
    candidate iterate, or a *plain* prox step that still increased the
    objective (a valid ``inv_L <= 1/L`` makes that step monotone, so an
    increase beyond rounding noise means the step size is invalid), rolls
    the iterate back to the last accepted finite point, halves the step via
    ``FistaState.backoff``, and counts a trip in ``FistaState.health``. A
    genuine momentum restart is NOT a trip — only its fallback step failing
    is.
    """

    def mask_w(w):
        return w if fmask is None else w * fmask

    def prox_from(w_a, b_a, u_a, inv_Le):
        """One proximal-gradient step anchored at ``(w_a, b_a)`` whose
        margins ``u_a = X^T w_a`` are already known. 2 sweeps of X."""
        xi = jnp.maximum(0.0, 1.0 - y * (u_a + b_a))
        if sm is not None:
            xi = xi * sm
        gw = _grad_sweep(X, y, xi, use_pallas, col, valid_m)[:w_a.shape[0]]
        gb = col.psum_bias(-jnp.sum(y * xi))
        w_new = mask_w(soft_threshold(w_a - inv_Le * gw, lam * inv_Le))
        b_new = b_a - inv_Le * gb
        u_new, obj_new = _margin_obj_sweep(X, y, lam, w_new, b_new, sm,
                                           use_pallas, col, valid_m)
        return w_new, b_new, u_new, obj_new

    def body(s: FistaState) -> FistaState:
        inv_Le = inv_L * s.backoff if guards else inv_L
        # momentum extrapolation — margins included (u is linear in w, so
        # the momentum point's margins need no sweep)
        t_next = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * s.t * s.t))
        beta = (s.t - 1.0) / t_next
        zw = s.w + beta * (s.w - s.w_prev)
        zb = s.b + beta * (s.b - s.b_prev)
        uz = s.u + beta * (s.u - s.u_prev)

        w_new, b_new, u_new, obj_new = prox_from(zw, zb, uz, inv_Le)

        # monotone restart: if the extrapolated step increased the objective,
        # fall back to a plain proximal step from (w, b) — under lax.cond so
        # its two sweeps are paid only when the restart actually fires.
        # (A NaN obj_new compares False here and falls through to the guard.)
        restarted = obj_new > s.obj

        def restart(_):
            w_p, b_p, u_p, obj_p = prox_from(s.w, s.b, s.u, inv_Le)
            return w_p, b_p, u_p, obj_p, jnp.asarray(1.0, X.dtype)

        def accept(_):
            return w_new, b_new, u_new, obj_new, t_next

        w_new, b_new, u_new, obj_new, t_next = jax.lax.cond(
            restarted, restart, accept, None
        )

        # a restart iteration is not convergence evidence: the fallback step
        # from (w, b) moves little by construction, so counting its tiny
        # objective change as rel_change stops the solve at a momentum
        # overshoot instead of the optimum (observed: ulp-level L
        # differences flip a restart tie and strand the objective 2e-5 off).
        # Force one more (plain, t=1) iteration after every restart.
        rel = jnp.where(
            restarted, jnp.asarray(jnp.inf, X.dtype),
            jnp.abs(s.obj - obj_new) / jnp.maximum(jnp.abs(s.obj), 1e-30),
        )
        health, backoff = s.health, s.backoff
        if guards:
            eps = jnp.finfo(X.dtype).eps
            finite = (jnp.all(jnp.isfinite(w_new)) & jnp.isfinite(b_new)
                      & jnp.isfinite(obj_new))
            # post-restart increase beyond rounding noise: the plain step is
            # monotone under a valid step size, so this is a blowup, not a
            # momentum artifact. 256 eps relative keeps fp32 plateau ties
            # from tripping the guard at convergence.
            blowup = restarted & (obj_new > s.obj + 256.0 * eps
                                  * jnp.maximum(jnp.abs(s.obj), 1.0))
            bad = (~finite) | blowup
            # shard-consistent verdict (see _init_state): all shards must
            # agree or the guarded while-loop conds diverge across the mesh
            bad = col.pmax_model(bad.astype(X.dtype)) > 0.5
            w_new = jnp.where(bad, s.w, w_new)
            b_new = jnp.where(bad, s.b, b_new)
            u_new = jnp.where(bad, s.u, u_new)
            obj_new = jnp.where(bad, s.obj, obj_new)
            t_next = jnp.where(bad, jnp.asarray(1.0, X.dtype), t_next)
            rel = jnp.where(bad, jnp.asarray(jnp.inf, X.dtype), rel)
            health = s.health + bad.astype(jnp.int32)
            backoff = jnp.where(bad, s.backoff * 0.5, s.backoff)
        return FistaState(
            w=w_new, b=b_new, w_prev=s.w, b_prev=s.b, u=u_new, u_prev=s.u,
            t=t_next, k=s.k + 1, obj=obj_new, rel_change=rel,
            rel_prev=s.rel_change, rel_prev2=s.rel_prev,
            health=health, backoff=backoff,
        )

    return body


def fista_run(
    X: jax.Array,
    y: jax.Array,
    lam: jax.Array,
    w0: jax.Array,
    b0: jax.Array,
    inv_L: jax.Array,
    sample_mask: Optional[jax.Array],
    feature_mask: Optional[jax.Array],
    max_iters: int,
    tol: float,
    use_pallas: bool = False,
    col: Collectives = LOCAL,
    valid_m: Optional[jax.Array] = None,
    guards: bool = False,
    sweep_X: Optional[jax.Array] = None,
) -> FistaResult:
    """The raw (unjitted) FISTA loop — trace-safe building block.

    Callers own the defaults, the Lipschitz constant, and the jit boundary:
    :func:`fista_solve` wraps this for standalone solves, and the on-device
    path engine (``core/path_scan.py``) inlines it into each ``lax.scan``
    step so the whole regularization path stays one XLA program.
    ``feature_mask`` (0/1, optional) freezes screened rows at zero — the
    mask-mode reduction. ``w0`` must already respect it. ``col`` binds the
    body's reductions to mesh collectives when the operands are ``shard_map``
    blocks (the sharded path engine); ``valid_m`` is the live-row count of a
    compacted active set (Pallas sweeps skip blocks past it). ``guards``
    enables the numerical health guard (warm-start sanitization, on-device
    rollback with step-size backoff, trip-bounded loop — see
    :func:`_make_fista_body`); the trip count is returned as
    ``FistaResult.health``. ``sweep_X`` is the caller's already padded
    kernel operand of ``X`` (a path engine pads once per path); by default
    the solve pads its own.
    """
    Xs = sweep_X if sweep_X is not None else _sweep_operand(X, use_pallas,
                                                             col)
    init = _init_state(Xs, y, lam, w0, jnp.asarray(b0, X.dtype), sample_mask,
                       use_pallas, col, valid_m, guards=guards)

    def cond(s: FistaState):
        # three consecutive sub-tol iterations (see FistaState.rel_prev)
        go = (s.k < max_iters) & (_rel3(s) > tol)
        if guards:
            go = go & (s.health < MAX_GUARD_TRIPS)
        return go

    body = _make_fista_body(Xs, y, lam, inv_L, sample_mask, feature_mask,
                            use_pallas, col, valid_m, guards=guards)
    out = jax.lax.while_loop(cond, body, init)
    return FistaResult(
        w=out.w, b=out.b, obj=out.obj, n_iters=out.k,
        converged=_rel3(out) <= tol, u=out.u, health=out.health,
    )


def _resolve_pallas(flag: Optional[bool]) -> bool:
    from repro.kernels.ops import fista_use_pallas  # lazy: no import cycle

    return fista_use_pallas(flag)


@partial(jax.jit, static_argnames=("max_iters", "use_pallas", "guards"))
def _fista_solve_jit(X, y, lam, w0, b0, max_iters, tol, L, sample_mask,
                     use_pallas, guards):
    m = X.shape[0]
    lam = jnp.asarray(lam, X.dtype)
    if w0 is None:
        w0 = jnp.zeros((m,), X.dtype)
    if b0 is None:
        b0 = jnp.mean(y)
    if L is None:
        L = lipschitz_estimate(X)
    L = jnp.maximum(L * 1.01, 1e-12)  # small safety factor
    return fista_run(X, y, lam, w0, b0, 1.0 / L, sample_mask, None,
                     max_iters, tol, use_pallas, guards=guards)


def fista_solve(
    X: jax.Array,
    y: jax.Array,
    lam: jax.Array,
    w0: Optional[jax.Array] = None,
    b0: Optional[jax.Array] = None,
    max_iters: int = 2000,
    tol: float = 1e-9,
    L: Optional[jax.Array] = None,
    sample_mask: Optional[jax.Array] = None,
    use_pallas: Optional[bool] = None,
    operator=None,
    guards: Optional[bool] = None,
) -> FistaResult:
    """Solve the primal to relative-objective tolerance ``tol``.

    ``X``: (m, n) features x samples. Warm starts via ``w0``/``b0``.
    ``sample_mask`` (0/1 over samples) drops columns from the loss without
    changing shapes — with a binary mask, masking ``xi`` is exactly the
    problem with those samples removed (screened samples and gather-mode
    padding columns both use this; see core/path.py).

    ``L`` (optional): a known upper bound on the Lipschitz constant — path
    drivers pass the full-X estimate so reduced solves skip the 30-iteration
    power sweep. ``use_pallas`` routes the two O(mn) sweeps per iteration
    through the fused Pallas kernels (None = the
    ``kernels/ops.py::fista_use_pallas`` policy: env override, else TPU).

    ``operator`` (optional): the design-matrix seam. Accepts either a dense
    array (identical to passing it as ``X``) or a
    ``repro.sparse.FeatureChunked`` — the latter routes the solve through
    the streamed chunk-accumulated GEMV pair
    (``sparse/solver_stream.fista_solve_chunked``: host-orchestrated, one
    chunk on device at a time), so in-core call sites run unchanged on data
    that does not fit on the device. Chunked solves ignore ``use_pallas``
    (the streamed sweeps are XLA/BCOO per chunk). Passing a chunked
    container *as* ``X`` dispatches the same way.
    """
    A = operator if operator is not None else X
    if hasattr(A, "stream") and hasattr(A, "rmatvec"):  # FeatureChunked
        from repro.sparse.solver_stream import fista_solve_chunked  # lazy

        return fista_solve_chunked(A, y, lam, w0=w0, b0=b0,
                                   max_iters=max_iters, tol=tol, L=L,
                                   sample_mask=sample_mask,
                                   guards=_resolve_guards(guards))
    return _fista_solve_jit(A, y, lam, w0, b0, max_iters, float(tol), L,
                            sample_mask, _resolve_pallas(use_pallas),
                            _resolve_guards(guards))


#: A feasibility round of :func:`gap_theta_delta_binding` counts as binding
#: when its max exceeds ``lam`` by more than this share. A round right after
#: a rescale recomputes a max of ``lam`` itself, give or take the rounding
#: of its sweep: in float32 that reads one or two ulps above ``lam`` as often
#: as below, which a plain ``max > lam`` would count.
FEAS_BINDING_RTOL = 1e-5


def gap_theta_delta(
    X: jax.Array,
    y: jax.Array,
    w: jax.Array,
    b: jax.Array,
    lam: jax.Array,
    sample_mask: Optional[jax.Array] = None,
    n_feas_iters: int = 4,
    col: Collectives = LOCAL,
    u: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Gap-certified ``(theta1, delta, gap)`` at the current iterate.

    :func:`gap_theta_delta_binding` with its counts of binding rounds and
    of rounds left out; see there.
    """
    return gap_theta_delta_binding(X, y, w, b, lam, sample_mask,
                                   n_feas_iters, col, u)[:3]


def gap_theta_delta_binding(
    X: jax.Array,
    y: jax.Array,
    w: jax.Array,
    b: jax.Array,
    lam: jax.Array,
    sample_mask: Optional[jax.Array] = None,
    n_feas_iters: int = 4,
    col: Collectives = LOCAL,
    u: Optional[jax.Array] = None,
    scope: str = "feasibility",
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Gap-certified ``(theta1, delta, gap, binding, rounds)`` at the current
    iterate.

    The sample-masked generalization of ``dual.safe_theta_and_delta`` (same
    alternating feasibility projection, same 1-strong-concavity radius):
    with a 0/1 ``sample_mask`` the problem being certified is the one with
    masked-out columns removed, so the projection keeps their dual
    coordinates pinned at zero and the equality projection uses the live
    sample count. Pure ``jnp`` — callable from inside a jitted solve loop.

    ``u`` (optional): precomputed margins ``X^T w`` — the fused solver body
    already carries them for its accepted point, so certifying a just-solved
    iterate saves one full sweep of X. ``col`` binds the reductions to mesh
    collectives for ``shard_map`` blocks (see :class:`Collectives`).

    Each round sweeps ``max_j |X_j^T (y*alpha)|``, rescales alpha onto the
    feasible box, then applies the equality projection if the rescale was
    binding or the round is the first, and the round is below
    ``n_feas_iters``. The loop ends after the first round that did not
    project, so its last operation is a rescale by ``lam / max`` of exactly
    the alpha whose max it measured: the certificate is feasible however
    many rounds ran, and ``eq_resid`` covers what the projection left. At
    most ``n_feas_iters + 1`` rounds run; ``rounds`` (int32) is how many did.

    ``binding`` (int32) counts the rounds run whose max exceeded ``lam`` by
    more than :data:`FEAS_BINDING_RTOL`, so that their rescale moved alpha by
    more than the rounding of the max's own sweep; the same test decides
    whether a round projects. Both are read from the maxima the rounds
    compute anyway: no extra sweep. The rounds run in the name scope
    ``scope``: under ``vmap`` the name stack reads ``vmap(<scope>)``, so a
    batched caller passes its full path.
    """
    sm = sample_mask
    if u is None:
        u = col.psum_model(dot(X.T, w))
    xi = jnp.maximum(0.0, 1.0 - y * (u + b))
    if sm is not None:
        xi = xi * sm
    p_obj = col.psum_data(0.5 * jnp.sum(xi * xi)) + lam * col.psum_model(
        jnp.sum(jnp.abs(w)))
    if sm is not None:
        n_eff = col.psum_data(jnp.sum(sm))
    else:
        n_eff = col.psum_data(jnp.asarray(float(y.shape[0]), X.dtype))

    def feas_round(carry):
        """One round: rescale alpha onto the box, then project onto the
        equality if the rescale was binding or the round is the first."""
        r, alpha, binding, _ = carry
        corr = col.psum_data(dot(X, y * alpha))  # fhat_j^T alpha for all j
        mx = col.pmax_model(jnp.max(jnp.abs(corr)))
        bound = mx > lam * (1.0 + FEAS_BINDING_RTOL)
        alpha = alpha * jnp.minimum(1.0, lam / jnp.maximum(mx, 1e-30))
        project = (bound | (r == 0)) & (r < n_feas_iters)
        proj = jnp.maximum(
            0.0, alpha - col.psum_data(dot(alpha, y)) / n_eff * y)
        if sm is not None:
            proj = proj * sm
        return (r + 1, jnp.where(project, proj, alpha),
                binding + bound.astype(jnp.int32), project)

    with jax.named_scope(scope):
        zero = jnp.zeros((), jnp.int32)
        rounds, alpha, binding, _ = jax.lax.while_loop(
            lambda carry: carry[3], feas_round,
            (zero, xi, zero, jnp.ones((), bool)))
    d_obj = col.psum_data(jnp.sum(alpha)) - 0.5 * col.psum_data(
        jnp.sum(alpha * alpha))
    gap = jnp.maximum(p_obj - d_obj, 0.0)
    # the gap is a difference of two O(p_obj) reductions: floor it at a few
    # ulps of p_obj so cancellation noise can never *under*-inflate delta
    # (an underestimated delta is the unsafe direction)
    gap = jnp.maximum(gap, 4.0 * jnp.finfo(X.dtype).eps * jnp.abs(p_obj))
    eq_resid = jnp.abs(col.psum_data(dot(alpha, y))) / jnp.sqrt(n_eff)
    delta = (jnp.sqrt(2.0 * gap) + 2.0 * eq_resid) / lam
    theta = alpha / lam
    # fail-safe: a non-finite certificate must never feed screening. A NaN
    # theta with a *finite* delta is the dangerous combination (bounds come
    # out NaN and `bounds >= tau` silently discards), so collapse delta and
    # gap to inf whenever any component is non-finite — every screening
    # consumer gates on isfinite(delta) / the NaN-safe keep comparison.
    cert_ok = (jnp.isfinite(gap) & jnp.isfinite(delta)
               & jnp.all(jnp.isfinite(theta)))
    inf = jnp.asarray(jnp.inf, X.dtype)
    return (theta, jnp.where(cert_ok, delta, inf),
            jnp.where(cert_ok, gap, inf), binding, rounds)


def _dynamic_run(
    X: jax.Array,
    y: jax.Array,
    lam: jax.Array,
    w0: jax.Array,
    b0: jax.Array,
    inv_L: jax.Array,
    sample_mask: Optional[jax.Array],
    fmask0: jax.Array,
    max_iters: int,
    tol: float,
    screen_every: int,
    tau: float,
    n_feas_iters: int,
    use_pallas: bool,
    valid_m: Optional[jax.Array] = None,
    dynamic_samples: bool = False,
    sample_dw=None,
    sample_db=None,
    sample_u_prev: Optional[jax.Array] = None,
    sample_shrink: float = 2.0,
    sample_floor: float = 1e-3,
    guards: bool = False,
    sweep_X: Optional[jax.Array] = None,
) -> DynamicFistaResult:
    """Raw segmented dynamic solve (see :func:`fista_solve_dynamic`).

    Trace-safe like :func:`fista_run`; the scan path engine calls this
    directly with the path-shared ``inv_L``, the step's sequential screen
    as ``fmask0``, and (compact reduction) the live-row count ``valid_m``
    for the Pallas sweeps. ``dynamic_samples`` additionally re-checks the
    margin surplus of every live sample at each refresh (the carried
    margins make it O(n)) and ANDs it into a live *sample* mask — see
    :func:`fista_solve_dynamic` for the safety contract.
    """
    sm = sample_mask
    screen_every = max(int(screen_every), 1)
    n_seg = -(-max_iters // screen_every)  # ceil; static

    sm_vec = jnp.ones_like(y) if sm is None else sm
    if dynamic_samples:
        from .rules.sample_vi import margin_surplus_core  # lazy: no cycle

        # per-sample column norms over the (already feature-masked) matrix:
        # valid for the trust-region slack — the weight movement it bounds is
        # supported on live feature rows only — and theta-independent, so one
        # sweep serves every refresh
        x_sq_cols = jnp.sum(X * X, axis=0)

    def bound_statics(smv):
        """theta-independent bound reductions over the live samples."""
        return (dot(X, y * smv), dot(X, smv), dot(X * X, smv),
                jnp.sum(y * smv), jnp.sum(smv))

    # one sweep hoisted out of the loop; with dynamic_samples the values are
    # carried and re-swept only after a refresh that actually dropped
    # samples (the sm_dirty flag) — a stabilized sample mask costs nothing
    statics0 = bound_statics(sm_vec)

    Xs = sweep_X if sweep_X is not None else _sweep_operand(X, use_pallas)
    s0 = _init_state(Xs, y, lam, w0, jnp.asarray(b0, X.dtype), sm, use_pallas,
                     valid_m=valid_m, guards=guards)
    kept0 = jnp.full((n_seg,), -1, jnp.int32)
    gaps0 = jnp.full((n_seg,), jnp.inf, X.dtype)
    kept_s0 = jnp.full((n_seg,), -1, jnp.int32)

    def _trips(s):
        # the trip bound looks at the low (rollback) bits only — refused
        # screening refreshes (HEALTH_SCREEN_REFUSED) don't stop the solve
        return s.health & (HEALTH_SCREEN_REFUSED - 1)

    def outer_cond(carry):
        s, *_ = carry
        go = (s.k < max_iters) & (_rel3(s) > tol)
        if guards:
            go = go & (_trips(s) < MAX_GUARD_TRIPS)
        return go

    def outer_body(carry):
        s, fmask, smask, statics, sm_dirty, kept, gaps, kept_s, seg = carry
        seg_sm = smask if dynamic_samples else sm

        # -- segment: up to screen_every FISTA steps on the live mask ------
        body = _make_fista_body(Xs, y, lam, inv_L, seg_sm, fmask, use_pallas,
                                valid_m=valid_m, guards=guards)
        k_stop = jnp.minimum(s.k + screen_every, max_iters)

        def inner_cond(st):
            go = (st.k < k_stop) & (_rel3(st) > tol)
            if guards:
                go = go & (_trips(st) < MAX_GUARD_TRIPS)
            return go

        s = jax.lax.while_loop(inner_cond, body, s)

        # -- refresh: gap-certified region at the current iterate ----------
        # the carried margins s.u are X^T w at the current point, so the
        # certificate skips its own margin sweep
        theta, delta, gap = gap_theta_delta(
            X, y, s.w, s.b, lam, seg_sm, n_feas_iters=n_feas_iters, u=s.u
        )
        if dynamic_samples:
            # re-sweep the statics only if the previous refresh shrank the
            # sample mask (this refresh's feature screen must see the mask
            # the segment just ran with — exactly the carried smask)
            statics = jax.lax.cond(
                sm_dirty, lambda _: bound_statics(smask), lambda _: statics,
                None,
            )
        d_one_c, d_y_c, d_sq_c, one_y_c, n_tot_c = statics
        sh = shared_scalars_from_stats(
            lam, lam, one_y=one_y_c,
            theta_dot_one=jnp.sum(theta), theta_dot_y=dot(theta, y),
            theta_sq=dot(theta, theta), n_tot=n_tot_c, delta=delta,
        )
        red = FeatureReductions(
            d_theta=dot(X, y * theta), d_one=d_one_c, d_y=d_y_c, d_sq=d_sq_c
        )
        # two independent certificates, elementwise min (each is a valid
        # upper bound on |fhat_j^T theta*|): the at-lambda VI cap, and the
        # GAP-sphere bound |fhat^T theta| + ||fhat|| * delta — linear in
        # delta, so it is the one that bites as the solve converges.
        bounds = jnp.minimum(
            screen_bounds_from_reductions(red, sh),
            jnp.abs(red.d_theta) + jnp.sqrt(jnp.maximum(d_sq_c, 0.0)) * delta,
        )
        # fail-safe keep: ~(b < tau) keeps NaN/inf bounds (a poisoned
        # certificate degrades to "no screening this segment", never to a
        # wrong discard), and the explicit cert gate records the refusal
        cert_ok = jnp.isfinite(delta)
        keep = (~(bounds < tau)) | (~cert_ok)
        new_mask = fmask * keep.astype(X.dtype)

        # -- dynamic sample re-screen: margin surplus at the carried
        # margins (O(n) — no sweep). Samples whose surplus clears the slack
        # budget are *predicted* inactive and dropped from the loss for the
        # rest of the solve; the driver's KKT verification re-admits any
        # violator, so exactness is restored at acceptance.
        if dynamic_samples:
            surplus = margin_surplus_core(
                s.u + s.b, y, x_sq_cols, sample_dw, sample_db,
                u_prev=sample_u_prev, shrink_factor=sample_shrink,
                margin_floor=sample_floor,
            )
            # NaN-safe drop test: a non-finite surplus keeps the sample
            # (~(s >= 0) is True for NaN), so a poisoned margin can only
            # cost speed, never silently drop loss terms
            new_sm = smask * (~(surplus >= 0.0)).astype(X.dtype)
            sm_dirty = jnp.sum(smask - new_sm) > 0.0  # statics stale now
        else:
            new_sm = smask

        # zero the dropped coordinates; restart momentum only when the mask
        # change actually moved the problem (a moved iterate / shrunk loss
        # is a fresh point — stale momentum and a stale rel_change would
        # otherwise terminate the solve early; dropping already-zero
        # coordinates is free). The carried margins are re-swept for the
        # masked point — one fused pass per segment, amortized over
        # screen_every iterations.
        w_m = s.w * new_mask
        changed = jnp.sum((s.w - w_m) * (s.w - w_m)) > 0.0
        if dynamic_samples:
            changed = changed | (jnp.sum(smask - new_sm) > 0.0)
        u_m, obj_m = _margin_obj_sweep(
            Xs, y, lam, w_m, s.b, new_sm if dynamic_samples else sm,
            use_pallas, valid_m=valid_m)
        s_masked = FistaState(
            w=w_m, b=s.b, w_prev=w_m, b_prev=s.b, u=u_m, u_prev=u_m,
            t=jnp.asarray(1.0, X.dtype), k=s.k,
            obj=obj_m,
            rel_change=jnp.asarray(jnp.inf, X.dtype),
            rel_prev=jnp.asarray(jnp.inf, X.dtype),
            rel_prev2=jnp.asarray(jnp.inf, X.dtype),
            health=s.health, backoff=s.backoff,
        )
        s = jax.tree_util.tree_map(
            lambda a, b_: jnp.where(changed, a, b_), s_masked, s
        )
        # a refused refresh is health telemetry, not a solver trip: set the
        # flag bit once (idempotent under repeated refusals via bitwise or)
        s = s._replace(health=s.health | jnp.where(
            cert_ok, 0, HEALTH_SCREEN_REFUSED).astype(jnp.int32))

        # a segment may consume fewer than screen_every iterations (inner
        # convergence followed by a mask change restarts iteration), so more
        # than n_seg refreshes are possible — clamp into the last telemetry
        # slot instead of silently dropping the scatter out of bounds
        slot = jnp.minimum(seg, n_seg - 1)
        kept = kept.at[slot].set(jnp.sum(new_mask).astype(jnp.int32))
        gaps = gaps.at[slot].set(gap)
        kept_s = kept_s.at[slot].set(jnp.sum(new_sm).astype(jnp.int32))
        return (s, new_mask, new_sm, statics, sm_dirty, kept, gaps, kept_s,
                jnp.minimum(seg + 1, n_seg))

    out, fmask, smask, _, _, kept, gaps, kept_s, seg = jax.lax.while_loop(
        outer_cond, outer_body,
        (s0, fmask0, sm_vec, statics0, jnp.asarray(False), kept0, gaps0,
         kept_s0, jnp.asarray(0, jnp.int32))
    )
    return DynamicFistaResult(
        w=out.w, b=out.b, obj=out.obj, n_iters=out.k,
        converged=_rel3(out) <= tol,
        feature_mask=fmask > 0.5, kept_per_segment=kept,
        gap_per_segment=gaps, n_segments=seg, u=out.u,
        sample_mask=(smask > 0.5) if dynamic_samples else None,
        kept_samples_per_segment=kept_s if dynamic_samples else None,
        health=out.health,
    )


@partial(jax.jit, static_argnames=("max_iters", "screen_every", "n_feas_iters",
                                   "use_pallas", "dynamic_samples", "guards"))
def _fista_solve_dynamic_jit(X, y, lam, w0, b0, max_iters, tol, L,
                             sample_mask, feature_mask, screen_every, tau,
                             n_feas_iters, use_pallas, dynamic_samples,
                             sample_dw, sample_db, sample_u_prev,
                             sample_shrink, sample_floor, guards):
    m = X.shape[0]
    lam = jnp.asarray(lam, X.dtype)
    if w0 is None:
        w0 = jnp.zeros((m,), X.dtype)
    if b0 is None:
        b0 = jnp.mean(y)
    if L is None:
        L = lipschitz_estimate(X)
    L = jnp.maximum(L * 1.01, 1e-12)

    fmask0 = (
        jnp.ones((m,), X.dtype) if feature_mask is None
        else jnp.asarray(feature_mask, X.dtype)
    )
    w0 = w0 * fmask0
    return _dynamic_run(X, y, lam, w0, b0, 1.0 / L, sample_mask, fmask0,
                        max_iters, tol, screen_every, tau, n_feas_iters,
                        use_pallas, dynamic_samples=dynamic_samples,
                        sample_dw=sample_dw, sample_db=sample_db,
                        sample_u_prev=sample_u_prev,
                        sample_shrink=sample_shrink,
                        sample_floor=sample_floor, guards=guards)


def fista_solve_dynamic(
    X: jax.Array,
    y: jax.Array,
    lam: jax.Array,
    w0: Optional[jax.Array] = None,
    b0: Optional[jax.Array] = None,
    max_iters: int = 2000,
    tol: float = 1e-9,
    L: Optional[jax.Array] = None,
    sample_mask: Optional[jax.Array] = None,
    feature_mask: Optional[jax.Array] = None,
    screen_every: int = 50,
    tau: float = SAFE_TAU,
    n_feas_iters: int = 4,
    use_pallas: Optional[bool] = None,
    dynamic_samples: bool = False,
    sample_dw: float = float("inf"),
    sample_db: float = float("inf"),
    sample_u_prev: Optional[jax.Array] = None,
    sample_shrink_factor: float = 2.0,
    sample_margin_floor: float = 1e-3,
    guards: Optional[bool] = None,
) -> DynamicFistaResult:
    """Segmented FISTA with gap-driven dynamic feature screening.

    Solves the same problem as :func:`fista_solve`, but every
    ``screen_every`` iterations it (a) computes the duality gap at the
    current iterate, (b) rebuilds the at-lambda VI region from the
    gap-certified dual point (``lam1 = lam2 = lam``; the region collapses
    onto ``theta*`` as the gap shrinks), (c) re-evaluates the feature
    bounds, and (d) ANDs the keep mask into a live ``feature_mask`` that
    zeroes screened coordinates for the rest of the solve. Screened
    features are *provably* inactive at the optimum of the (sample-masked)
    problem, so the accepted solution is unchanged beyond solver tolerance.

    ``feature_mask`` (0/1 over rows, optional) seeds the live mask — e.g.
    the path driver's between-lambda sequential screen; refreshes only ever
    shrink it. ``L``/``use_pallas`` as in :func:`fista_solve`. Returns
    :class:`DynamicFistaResult` with per-segment kept-counts and gaps
    (sentinels ``-1`` / ``inf`` for segments not run).

    Dynamic *sample* re-screen (``dynamic_samples=True``): each refresh
    additionally evaluates every live sample's margin surplus at the
    carried margins (``rules/sample_vi.margin_surplus_core`` — O(n), no
    extra sweep) against the trust-region radii ``sample_dw``/``sample_db``
    and the secant model anchored at ``sample_u_prev``, and ANDs
    ``surplus < 0`` into a live *sample* mask: samples predicted to satisfy
    their margin stop contributing to gradients and to the gap certificate
    for the rest of the solve. Unlike the feature screen this is
    margin-*predicted*, not a-priori safe — the returned
    ``DynamicFistaResult.sample_mask`` must be KKT-verified at the solution
    (the path driver's verification loop re-admits violators and re-solves),
    after which screened samples provably have ``xi_i = 0`` and the accepted
    solution is exact.
    """
    return _fista_solve_dynamic_jit(
        X, y, lam, w0, b0, max_iters, float(tol), L, sample_mask,
        feature_mask, max(int(screen_every), 1), float(tau),
        int(n_feas_iters), _resolve_pallas(use_pallas),
        bool(dynamic_samples),
        jnp.asarray(min(float(sample_dw), 1e30)),
        jnp.asarray(min(float(sample_db), 1e30)),
        sample_u_prev,
        jnp.asarray(float(sample_shrink_factor)),
        jnp.asarray(float(sample_margin_floor)),
        _resolve_guards(guards),
    )
