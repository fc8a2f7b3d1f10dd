"""2-D sharded screening + solver via shard_map (features x samples mesh).

Layout: ``X`` is sharded ``P("model", "data")`` — feature rows over the
"model" axis, sample columns over the "data" axis. Sample-space vectors
(``y``, ``theta``) shard over "data"; feature-space vectors (``w``, bounds,
keep masks) shard over "model".

Communication pattern (maps the paper's O(mn) screen onto the mesh):

* the four per-feature reductions are computed locally over each shard's
  sample columns, then ``psum`` over the "data" axis → 4 scalars per local
  feature, i.e. 4·(m/P_model) floats per device — the only screen traffic;
* bound evaluation is local to the "model" shard (zero communication);
* FISTA: margins need ``psum`` over "model" (features), gradients need
  ``psum`` over "data" (samples) — the classic 2-D GEMV pattern.

On a multi-pod mesh the "pod" axis is folded into the data axis for the SVM
workload (samples shard over ("pod", "data")) so inter-pod traffic is only
the 4-scalar psum and the margin psum, both tiny and DCN-tolerant.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .linalg import dot
from .screening import (
    SAFE_TAU,
    FeatureReductions,
    screen_bounds_from_reductions,
    shared_scalars,
    shared_scalars_from_stats,
)
from .solver import Collectives, DynamicFistaResult, FistaResult, soft_threshold

__all__ = [
    "screen_sharded",
    "fista_sharded",
    "svm_mesh",
    "mesh_collectives",
    "sample_surplus_sharded",
]


def svm_mesh(model: int, data: int, devices=None) -> Mesh:
    """Build a (model x data) mesh for the SVM workload."""
    devices = devices if devices is not None else jax.devices()
    assert len(devices) >= model * data, (len(devices), model, data)
    import numpy as np

    arr = np.asarray(devices[: model * data]).reshape(model, data)
    return Mesh(arr, ("model", "data"))


def mesh_collectives(mesh: Mesh, data_axes=("data",),
                     model_axis: str = "model") -> Collectives:
    """``solver.Collectives`` bound to the ``svm_mesh`` 2-D psum pattern.

    This is the plumbing that lets the *local* solver bodies (fused FISTA,
    gap certificate, Lipschitz power iteration — ``core/solver.py``) and the
    on-device path engine (``core/path_scan.py``) run unchanged inside a
    ``shard_map``: margins and L1 norms reduce over the feature ("model")
    axis, gradients and losses over the sample ("data") axes, the bias
    gradient over both (averaged over the model replicas that each computed
    the same xi), and the dual-feasibility rescale takes a pmax over
    features. Same communication pattern as :func:`fista_sharded`.

    Axes of size 1 bind to the identity, not to a degenerate all-reduce:
    a trivial psum is value-preserving but still restructures XLA's fusion,
    and the resulting 1-ulp objective noise flips the solver's restart /
    stopping predicates at their convergence-plateau ties. Pruning trivial
    axes keeps a 1-D mesh free of no-op collectives (e.g. a pure
    data-parallel ``svm_mesh(1, N)`` issues zero "model" psums) and makes
    the ``svm_mesh(1, 1)`` sharded engine bit-identical to the local one.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    d_axes = tuple(a for a in data_axes if sizes.get(a, 1) > 1)
    have_model = sizes.get(model_axis, 1) > 1

    def psum_model(x):
        return jax.lax.psum(x, model_axis) if have_model else x

    def psum_data(x):
        return jax.lax.psum(x, d_axes) if d_axes else x

    def psum_bias(x):
        axes = (*d_axes, *((model_axis,) if have_model else ()))
        if not axes:
            return x
        out = jax.lax.psum(x, axes)
        if have_model:
            out = out / jax.lax.psum(1.0, model_axis)
        return out

    def pmax_model(x):
        return jax.lax.pmax(x, model_axis) if have_model else x

    return Collectives(psum_model, psum_data, psum_bias, pmax_model)


def sample_surplus_sharded(
    mesh: Mesh,
    X: jax.Array,
    y: jax.Array,
    w: jax.Array,
    b,
    dw=float("inf"),
    db=float("inf"),
    u_prev: Optional[jax.Array] = None,
    shrink_factor: float = 2.0,
    margin_floor: float = 1e-3,
    data_axes=("data",),
):
    """Distributed sample-rule margin sweep. Returns ``(surplus, u1)``.

    The sharded mirror of ``rules/sample_vi.sample_margin_surplus``: the two
    feature-axis reductions it needs — the margins ``u1 = X^T w + b`` and
    the column norms ``||x_i||^2`` — are computed locally over each shard's
    feature rows and ``psum``-reduced over the "model" axis (one fused
    2-row stack, mirroring :func:`screen_sharded`'s packed reduction), then
    finalized with the *identical* slack arithmetic as the local rule
    (``rules/sample_vi.margin_surplus_core``), so on a mesh that keeps the
    feature axis whole the result matches the local oracle bitwise.

    ``X``: (m, n) sharded ``P("model", data_axes)``; ``y``/``u_prev``: (n,)
    sharded ``P(data_axes)``; ``w``: (m,) sharded ``P("model")``. Outputs
    shard over ``P(data_axes)``. ``dw``/``db`` are the host trust-region
    radii (python floats; ``inf`` = no movement history, never screens).
    """
    from .rules.sample_vi import margin_surplus_core  # lazy: no import cycle

    # match the data dtype (not a hardcoded float32): the bitwise-oracle
    # contract must hold under JAX_ENABLE_X64 too
    b = jnp.asarray(b, X.dtype)
    has_history = u_prev is not None
    have_model = dict(zip(mesh.axis_names, mesh.devices.shape)).get(
        "model", 1) > 1

    def local(x_blk, y_blk, w_blk, up_blk):
        if have_model:
            # fused 2-row reduction over this shard's feature rows, one psum
            part = jnp.stack([dot(x_blk.T, w_blk),
                              jnp.sum(x_blk * x_blk, axis=0)])
            part = jax.lax.psum(part, "model")
            u1, x_sq = part[0] + b, part[1]
        else:
            # feature axis whole on this shard: identical arithmetic to the
            # local oracle (no stack/psum detour), so the bitwise-equality
            # contract of margin_surplus_core extends to the reductions too
            u1 = dot(x_blk.T, w_blk) + b
            x_sq = jnp.sum(x_blk * x_blk, axis=0)
        surplus = margin_surplus_core(
            u1, y_blk, x_sq, dw, db,
            u_prev=up_blk if has_history else None,
            shrink_factor=shrink_factor, margin_floor=margin_floor,
        )
        return surplus, u1

    up = u_prev if has_history else jnp.zeros_like(y)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("model", *data_axes), P(*data_axes), P("model"),
                  P(*data_axes)),
        out_specs=(P(*data_axes), P(*data_axes)),
        check_vma=False,
    )
    return fn(X, y, w, up)


def screen_sharded(
    mesh: Mesh,
    X: jax.Array,
    y: jax.Array,
    lam1,
    lam2,
    theta1: jax.Array,
    tau: float = SAFE_TAU,
    data_axes=("data",),
    *,
    delta,
):
    """Distributed safe screening. Returns (keep_mask, bounds), sharded on "model".

    ``X``: (m, n) sharded P("model", data_axes); ``y``/``theta1``: (n,)
    sharded P(data_axes). ``delta`` is the inexact-theta1 radius bound
    (``||theta1 - theta*(lam1)|| <= delta``, see ``dual.safe_theta_and_delta``):
    it inflates the ball and relaxes the halfspace exactly like
    ``screening.shared_scalars``. It is deliberately a *required* keyword —
    a sharded screen that silently assumed theta1 exact could unsafely
    reject features for any iteratively solved anchor; callers with a
    truly exact theta1 (closed form at lambda_max) state ``delta=0.0``.
    """
    lam1 = jnp.asarray(lam1, jnp.float32)
    lam2 = jnp.asarray(lam2, jnp.float32)
    delta = jnp.asarray(delta, jnp.float32)

    def local(x_blk, y_blk, th_blk):
        # local partial reductions over this shard's sample columns
        rhs = jnp.stack([y_blk * th_blk, y_blk, jnp.ones_like(y_blk)], axis=1)
        d = dot(x_blk, rhs)                       # (m_loc, 3)
        d_sq = jnp.sum(x_blk * x_blk, axis=1)  # (m_loc,)
        packed = jnp.concatenate([d, d_sq[:, None]], axis=1)
        packed = jax.lax.psum(packed, data_axes)

        # shared scalars need full-sample reductions of y/theta1: psum too
        n_loc = y_blk.shape[0]
        stats = jnp.stack(
            [
                jnp.sum(y_blk),
                jnp.sum(th_blk),
                dot(th_blk, y_blk),
                dot(th_blk, th_blk),
                jnp.asarray(n_loc, jnp.float32),
            ]
        )
        stats = jax.lax.psum(stats, data_axes)
        one_y, th_one, th_y, th_sq, n_tot = stats

        sh = _shared_from_stats(lam1, lam2, one_y, th_one, th_y, th_sq, n_tot,
                                delta=delta)
        red = FeatureReductions(
            d_theta=packed[:, 0], d_one=packed[:, 1], d_y=packed[:, 2], d_sq=packed[:, 3]
        )
        bounds = screen_bounds_from_reductions(red, sh)
        return bounds >= tau, bounds

    specs_in = (
        P("model", *data_axes),
        P(*data_axes),
        P(*data_axes),
    )
    fn = jax.shard_map(
        local, mesh=mesh, in_specs=specs_in, out_specs=(P("model"), P("model")),
        check_vma=False,
    )
    return fn(X, y, theta1)


def _shared_from_stats(lam1, lam2, one_y, th_one, th_y, th_sq, n_tot, delta=0.0):
    """ScreenShared from global scalar statistics, delta-inflated.

    Delegates to ``screening.shared_scalars_from_stats`` so the sharded
    screen runs the *identical* scalar arithmetic as the local oracle —
    including the inexact-theta ball inflation (``r_base + delta``) and the
    ``g0`` halfspace relaxation. (The pre-delta version of this function
    re-derived the scalars locally and dropped ``delta`` entirely, which
    made the sharded screen unsafe for sequentially-solved theta1.)
    """
    return shared_scalars_from_stats(
        lam1, lam2, one_y=one_y, theta_dot_one=th_one, theta_dot_y=th_y,
        theta_sq=th_sq, n_tot=n_tot, delta=delta,
    )


def fista_sharded(
    mesh: Mesh,
    X: jax.Array,
    y: jax.Array,
    lam,
    max_iters: int = 2000,
    tol: float = 1e-9,
    w0: Optional[jax.Array] = None,
    b0: Optional[jax.Array] = None,
    data_axes=("data",),
    sample_mask: Optional[jax.Array] = None,
    feature_mask: Optional[jax.Array] = None,
    screen_every: Optional[int] = None,
    tau: float = SAFE_TAU,
    n_feas_iters: int = 4,
    L: Optional[jax.Array] = None,
):
    """Distributed FISTA on 2-D sharded X. Same math as solver.fista_solve.

    ``L`` (optional): a known Lipschitz upper bound — the path launcher
    passes the full-X estimate once per path so every sharded solve skips
    the 30-iteration distributed power sweep (masked subproblems never
    have a larger ``sigma_max``; see ``solver.lipschitz_estimate``).

    ``sample_mask`` (0/1 over samples, sharded like ``y``) drops screened
    samples from the loss without reshaping the sharded operands — the
    mask-mode counterpart of the sample-screening rules (core/rules).

    ``screen_every`` (optional) turns on in-solver *dynamic* screening —
    the sharded mirror of ``solver.fista_solve_dynamic``: every
    ``screen_every`` iterations the local function computes the duality gap
    (margin psum over "model", correlation psum over the data axes),
    rebuilds the at-lambda VI region from the gap-certified dual point, and
    re-evaluates the feature bounds with the same psum sweep as
    :func:`screen_sharded`, ANDing the result into a live feature mask
    sharded over "model" (``feature_mask`` seeds it; without
    ``screen_every`` the mask is honored statically — seeded zeros stay
    zero — just never refreshed). Returns ``solver.DynamicFistaResult``
    (with per-segment kept/gap telemetry) when ``screen_every`` is set,
    plain ``FistaResult`` otherwise.
    """
    lam = jnp.asarray(lam, jnp.float32)
    m, n = X.shape
    if sample_mask is None:
        sample_mask = jnp.ones_like(y)
    dynamic = screen_every is not None and int(screen_every) > 0
    if dynamic:
        screen_every = int(screen_every)
        n_seg = -(-max_iters // screen_every)  # ceil; static
    if feature_mask is None:
        feature_mask = jnp.ones((m,), jnp.float32)

    have_L = L is not None

    def local(x_blk, y_blk, sm_blk, fm_blk, w_blk, b_scalar, L_in):
        def margins(w):
            part = dot(x_blk.T, w)  # (n_loc,)
            return jax.lax.psum(part, "model")

        def grad(w, b):
            u = margins(w) + b
            xi = sm_blk * jnp.maximum(0.0, 1.0 - y_blk * u)
            gw = -dot(x_blk, y_blk * xi)
            gw = jax.lax.psum(gw, data_axes)
            gb = -jnp.sum(y_blk * xi)
            gb = jax.lax.psum(gb, (*data_axes, "model")) / jax.lax.psum(
                1.0, "model"
            )  # each model row computed same xi; average the replicas
            loss = 0.5 * jnp.sum(xi * xi)
            loss = jax.lax.psum(loss, data_axes)
            return gw, gb, loss

        def objective(w, b):
            u = margins(w) + b
            xi = sm_blk * jnp.maximum(0.0, 1.0 - y_blk * u)
            loss = 0.5 * jnp.sum(xi * xi)
            loss = jax.lax.psum(loss, data_axes)
            l1 = jax.lax.psum(jnp.sum(jnp.abs(w)), "model")
            return loss + lam * l1

        if have_L:
            # path-shared upper bound: skip the distributed power sweep
            Lc = L_in
        else:
            # power iteration for L (sharded)
            def pow_body(v, _):
                nrm = jnp.sqrt(jax.lax.psum(dot(v, v), data_axes))
                v = v / jnp.maximum(nrm, 1e-30)
                u_w = jax.lax.psum(dot(x_blk, v), data_axes)  # X@v reduces over data
                u_b = jax.lax.psum(jnp.sum(v), data_axes)
                vn = dot(x_blk.T, u_w)
                vn = jax.lax.psum(vn, "model") + u_b
                return vn, None

            v0 = jnp.cos(jnp.arange(y_blk.shape[0], dtype=jnp.float32) + 1.0)
            v, _ = jax.lax.scan(pow_body, v0, None, length=30)
            Lc = jnp.sqrt(jax.lax.psum(dot(v, v), data_axes))
        Lc = jnp.maximum(Lc * 1.01, 1e-12)
        inv_L = 1.0 / Lc

        def make_body(fm):
            def prox_step(w_a, b_a):
                """Proximal-gradient step anchored at (w_a, b_a)."""
                gw, gb, _ = grad(w_a, b_a)
                w_s = soft_threshold(w_a - inv_L * gw, lam * inv_L)
                b_s = b_a - inv_L * gb
                if fm is not None:
                    w_s = w_s * fm
                return w_s, b_s, objective(w_s, b_s)

            def body(st):
                w, b, wp, bp, t, k, obj, rel, rel_prev, rel_prev2 = st
                t_next = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
                beta = (t - 1.0) / t_next
                zw = w + beta * (w - wp)
                zb = b + beta * (b - bp)
                w_new, b_new, obj_new = prox_step(zw, zb)

                # monotone restart under lax.cond: the plain step's three
                # psum sweeps are paid only when the extrapolated step
                # actually increased the (replicated) objective — the
                # predicate is identical on every device, so all shards
                # take the same branch and the collectives stay matched.
                restarted = obj_new > obj

                def restart(_):
                    w_pl, b_pl, obj_pl = prox_step(w, b)
                    return w_pl, b_pl, obj_pl, jnp.float32(1.0)

                def accept(_):
                    return w_new, b_new, obj_new, t_next

                w_new, b_new, obj_new, t_next = jax.lax.cond(
                    restarted, restart, accept, None
                )

                # restart iterations don't count as convergence evidence
                # (cf. solver._make_fista_body: the fallback step's tiny
                # objective change is a momentum artifact, not a plateau)
                rel_new = jnp.where(
                    restarted, jnp.float32(jnp.inf),
                    jnp.abs(obj - obj_new) / jnp.maximum(jnp.abs(obj), 1e-30),
                )
                return (w_new, b_new, w, b, t_next, k + 1, obj_new, rel_new,
                        rel, rel_prev)

            return body

        if not dynamic:
            # honor a static feature_mask here too (same contract as the
            # dynamic path, just never refreshed): seeded zeros stay zero
            w_init = w_blk * fm_blk
            obj0 = objective(w_init, b_scalar)

            def cond(st):
                w, b, wp, bp, t, k, obj, rel, rel_prev, rel_prev2 = st
                # three consecutive sub-tol iterations (see solver.FistaState)
                return (k < max_iters) & (
                    jnp.maximum(jnp.maximum(rel, rel_prev), rel_prev2) > tol)

            st0 = (w_init, b_scalar, w_init, b_scalar, jnp.float32(1.0),
                   jnp.int32(0), obj0, jnp.float32(jnp.inf),
                   jnp.float32(jnp.inf), jnp.float32(jnp.inf))
            w, b, _, _, _, k, obj, rel, rel_p, rel_p2 = jax.lax.while_loop(
                cond, make_body(fm_blk), st0)
            return (w, b, obj, k,
                    jnp.maximum(jnp.maximum(rel, rel_p), rel_p2) <= tol)

        # ---- dynamic: segmented solve with in-loop gap screening ---------
        # theta-independent bound reductions over live samples (one sweep +
        # one 3-scalar psum, shared by every refresh — cf. screen_sharded)
        stat = jnp.stack([y_blk * sm_blk, sm_blk], axis=1)       # (n_loc, 2)
        dd = dot(x_blk, stat)                                    # (m_loc, 2)
        d_sq = dot(x_blk * x_blk, sm_blk)
        dd = jax.lax.psum(jnp.concatenate([dd, d_sq[:, None]], axis=1), data_axes)
        d_one_blk, d_y_blk, d_sq_blk = dd[:, 0], dd[:, 1], dd[:, 2]
        sums = jax.lax.psum(
            jnp.stack([jnp.sum(y_blk * sm_blk), jnp.sum(sm_blk)]), data_axes
        )
        one_y, n_tot = sums[0], sums[1]

        def gap_certificate(w, b):
            """(theta_blk, delta, gap) — sharded gap_theta_delta."""
            u = margins(w) + b
            xi = sm_blk * jnp.maximum(0.0, 1.0 - y_blk * u)
            p_obj = jax.lax.psum(0.5 * jnp.sum(xi * xi), data_axes) + (
                lam * jax.lax.psum(jnp.sum(jnp.abs(w)), "model")
            )

            def feas_body(alpha, _):
                corr = jax.lax.psum(dot(x_blk, y_blk * alpha), data_axes)
                mx = jax.lax.pmax(jnp.max(jnp.abs(corr)), "model")
                alpha = alpha * jnp.minimum(1.0, lam / jnp.maximum(mx, 1e-30))
                ay = jax.lax.psum(dot(alpha, y_blk), data_axes)
                return sm_blk * jnp.maximum(0.0, alpha - ay / n_tot * y_blk), None

            alpha, _ = jax.lax.scan(feas_body, xi, None, length=n_feas_iters)
            corr = jax.lax.psum(dot(x_blk, y_blk * alpha), data_axes)
            mx = jax.lax.pmax(jnp.max(jnp.abs(corr)), "model")
            alpha = alpha * jnp.minimum(1.0, lam / jnp.maximum(mx, 1e-30))
            stats = jax.lax.psum(
                jnp.stack([jnp.sum(alpha), jnp.sum(alpha * alpha), dot(alpha, y_blk)]),
                data_axes,
            )
            gap = jnp.maximum(p_obj - (stats[0] - 0.5 * stats[1]), 0.0)
            # few-ulp floor against cancellation noise (see gap_theta_delta)
            gap = jnp.maximum(gap, 4.0 * jnp.finfo(jnp.float32).eps * jnp.abs(p_obj))
            eq_resid = jnp.abs(stats[2]) / jnp.sqrt(n_tot)
            delta = (jnp.sqrt(2.0 * gap) + 2.0 * eq_resid) / lam
            return alpha / lam, delta, gap

        def outer_cond(carry):
            st, *_ = carry
            return (st[5] < max_iters) & (
                jnp.maximum(jnp.maximum(st[7], st[8]), st[9]) > tol)

        def outer_body(carry):
            st, fm, kept, gaps, seg = carry
            k_stop = jnp.minimum(st[5] + screen_every, max_iters)

            def inner_cond(s_):
                return (s_[5] < k_stop) & (
                    jnp.maximum(jnp.maximum(s_[7], s_[8]), s_[9]) > tol)

            st = jax.lax.while_loop(inner_cond, make_body(fm), st)
            w, b = st[0], st[1]

            # refresh: certify the region at the current iterate, re-screen
            theta, delta, gap = gap_certificate(w, b)
            th_stats = jax.lax.psum(
                jnp.stack([jnp.sum(theta), dot(theta, y_blk), dot(theta, theta)]),
                data_axes,
            )
            sh = _shared_from_stats(lam, lam, one_y, th_stats[0], th_stats[1],
                                    th_stats[2], n_tot, delta=delta)
            d_theta_blk = jax.lax.psum(dot(x_blk, y_blk * theta), data_axes)
            red = FeatureReductions(d_theta=d_theta_blk, d_one=d_one_blk,
                                    d_y=d_y_blk, d_sq=d_sq_blk)
            # min of the VI cap and the GAP-sphere bound — see
            # solver.fista_solve_dynamic for the derivation
            bounds = jnp.minimum(
                screen_bounds_from_reductions(red, sh),
                jnp.abs(d_theta_blk)
                + jnp.sqrt(jnp.maximum(d_sq_blk, 0.0)) * delta,
            )
            new_fm = fm * (bounds >= tau).astype(jnp.float32)
            n_live = jax.lax.psum(jnp.sum(new_fm), "model")

            # zero dropped coords + momentum restart only when zeroing moved
            # the iterate (cf. fista_solve_dynamic)
            w_m = w * new_fm
            changed = jax.lax.psum(jnp.sum((w - w_m) * (w - w_m)), "model") > 0.0
            obj_m = objective(w_m, b)
            st_masked = (w_m, b, w_m, b, jnp.float32(1.0), st[5], obj_m,
                         jnp.float32(jnp.inf), jnp.float32(jnp.inf),
                         jnp.float32(jnp.inf))
            st = jax.tree_util.tree_map(
                lambda a_, b_: jnp.where(changed, a_, b_), st_masked, st
            )

            # clamp into the last slot: > n_seg refreshes are possible when
            # segments end early (see fista_solve_dynamic)
            slot = jnp.minimum(seg, n_seg - 1)
            kept = kept.at[slot].set(n_live.astype(jnp.int32))
            gaps = gaps.at[slot].set(gap)
            return (st, new_fm, kept, gaps, jnp.minimum(seg + 1, n_seg))

        obj0 = objective(w_blk * fm_blk, b_scalar)
        st0 = (w_blk * fm_blk, b_scalar, w_blk * fm_blk, b_scalar,
               jnp.float32(1.0), jnp.int32(0), obj0, jnp.float32(jnp.inf),
               jnp.float32(jnp.inf), jnp.float32(jnp.inf))
        carry0 = (st0, fm_blk, jnp.full((n_seg,), -1, jnp.int32),
                  jnp.full((n_seg,), jnp.inf, jnp.float32),
                  jnp.int32(0))
        st, fm, kept, gaps, seg = jax.lax.while_loop(outer_cond, outer_body, carry0)
        w, b, _, _, _, k, obj, rel, rel_p, rel_p2 = st
        return (w, b, obj, k,
                jnp.maximum(jnp.maximum(rel, rel_p), rel_p2) <= tol,
                fm > 0.5, kept, gaps, seg)

    if w0 is None:
        w0 = jnp.zeros((m,), jnp.float32)
    if b0 is None:
        b0 = jnp.mean(y)
    b0 = jnp.asarray(b0, jnp.float32)

    scalar_out = (P(), P(), P(), P())
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P("model", *data_axes), P(*data_axes), P(*data_axes),
                  P("model"), P("model"), P(), P()),
        out_specs=(P("model"), *scalar_out)
        if not dynamic
        else (P("model"), *scalar_out, P("model"), P(), P(), P()),
        check_vma=False,
    )
    out = fn(X, y, jnp.asarray(sample_mask, jnp.float32),
             jnp.asarray(feature_mask, jnp.float32), w0, b0,
             jnp.asarray(L if have_L else 0.0, jnp.float32))
    if not dynamic:
        w, b, obj, k, conv = out
        return FistaResult(w=w, b=b, obj=obj, n_iters=k, converged=conv)
    w, b, obj, k, conv, fm, kept, gaps, seg = out
    return DynamicFistaResult(
        w=w, b=b, obj=obj, n_iters=k, converged=conv, feature_mask=fm,
        kept_per_segment=kept, gap_per_segment=gaps, n_segments=seg,
    )
