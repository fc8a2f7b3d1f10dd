"""Fused Pallas TPU kernels for screening bounds, parameterized by axis.

One kernel family, two reduction axes, one shared pattern: sweep X once,
accumulate an (8, units) reduction block in VMEM across the grid's
reduction axis, and on the final grid step apply a ~30-flop closed-form
finalizer entirely in VMEM. X is read from HBM exactly once; nothing of
size O(units x 4) round-trips to HBM between the reduction and the bound.

``axis="features"`` (paper Alg. 1): per feature row j, reduce over samples

    d_theta = f_j . (y*theta1),  d_one = f_j . y,
    d_y     = f_j . 1,           d_sq  = f_j . f_j

then the three-case VI bound on ``|fhat_j^T theta2|`` (core/screening.py).

``axis="samples"`` (core/rules/sample_vi.py): per sample column i, the
transposed sweep reduces over features

    u_i = x_i . w1,              s_sq_i = ||x_i||^2

then the margin-surplus finalizer: ``y_i (u_i + b1) - 1 - slack_i`` with
``slack_i = min(sqrt(s_sq_i) * dw + db,  shrink * |u_i + b1 - u0_i| +
floor)`` (trust-region and secant slack models; see the rule's docstring).

TPU adaptation notes (vs the paper's per-unit CPU loop):
  * the per-unit side vectors enter as the rows of an (8, len) operand
    (unused rows zero) and the accumulator is (8, units): units ride the
    128-wide lanes, so every block Mosaic sees is lane-dense and the
    result is one ``(1, units)`` row;
  * the dot-reductions are one (8, br) x (br, bu) MXU contraction, and the
    squared norms a second one against a ones block — the squared-norm row
    is merged into the accumulator with a row select, not a scatter;
  * both contractions run at ``Precision.HIGHEST`` (fp32 on the chip: a
    bf16-pass reduction could understate a bound and discard a live unit);
  * the packed scalars live in SMEM;
  * the grid is (units/bu, reduction/br) with the reduction axis innermost
    ("arbitrary" semantics), accumulating into a VMEM scratch block that
    lives across the sweep — the canonical Pallas reduction pattern.

VMEM budget per program instance (defaults bm=256, bn=512, fp32):
  X tile 512 KiB + side tiles 16 KiB + acc 8-16 KiB << 16 MiB VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NUM_SCALARS = 12  # packed per-axis scalars, padded to a common length
SIDE_ROWS = 8     # rows of the stacked side-vector operands (one f32 tile)

_BIG = 1e30  # stands in for inf inside the kernel (avoids 0 * inf = nan)
_HI = jax.lax.Precision.HIGHEST


# --------------------------------------------------------------------------
# scalar packing
# --------------------------------------------------------------------------

def pack_shared(sh) -> jax.Array:
    """Pack ScreenShared scalars into a flat fp32 vector (feature axis)."""
    vals = [
        sh.inv_lam1, sh.inv_lam2, sh.yc, sh.ysq, sh.r_h_sq, sh.g0,
        sh.qa_sq, sh.a_norm, sh.a_dot_y,
        jnp.where(sh.halfspace_valid, 1.0, 0.0),
    ]
    v = jnp.stack([jnp.asarray(x, jnp.float32) for x in vals])
    return jnp.pad(v, (0, NUM_SCALARS - v.shape[0]))


def pack_sample_scalars(b1, dw, db, shrink_factor, margin_floor,
                        has_history) -> jax.Array:
    """Pack the sample-axis finalizer scalars (infs clamped to _BIG)."""
    vals = jnp.stack([
        jnp.asarray(b1, jnp.float32),
        jnp.minimum(jnp.asarray(dw, jnp.float32), _BIG),
        jnp.minimum(jnp.asarray(db, jnp.float32), _BIG),
        jnp.asarray(shrink_factor, jnp.float32),
        jnp.asarray(margin_floor, jnp.float32),
        jnp.where(jnp.asarray(has_history, bool), 1.0, 0.0).astype(jnp.float32),
    ])
    return jnp.pad(vals, (0, NUM_SCALARS - vals.shape[0]))


def stack_rows(*rows: jax.Array) -> jax.Array:
    """``(SIDE_ROWS, len)`` fp32 operand holding ``rows``, the rest zero."""
    v = jnp.stack([jnp.asarray(r, jnp.float32) for r in rows])
    return jnp.pad(v, ((0, SIDE_ROWS - v.shape[0]), (0, 0)))


# --------------------------------------------------------------------------
# closed-form finalizers (vector over the unit tile)
# --------------------------------------------------------------------------

def _t_cases(v_ch, qv_qa, qv_sq, r_h, g0, qa_sq, hv):
    """One-sided ``max_{theta in K} v^T theta`` from hyperplane-projected
    stats of v — the three KKT cases of core/screening._t_max."""
    eps = jnp.float32(1e-30)
    qv_norm = jnp.sqrt(jnp.maximum(qv_sq, 0.0))

    ball = v_ch + r_h * qv_norm
    at_ball = g0 + r_h * qv_qa / jnp.maximum(qv_norm, eps)

    qa_sq_s = jnp.maximum(qa_sq, eps)
    mu = qv_qa / qa_sq_s
    vperp = jnp.sqrt(jnp.maximum(qv_sq - mu * mu * qa_sq_s, 0.0))
    rho = jnp.sqrt(jnp.maximum(r_h * r_h - g0 * g0 / qa_sq_s, 0.0))
    cut = v_ch - mu * g0 + rho * vperp

    use_ball = (at_ball >= 0.0) | (hv < 0.5) | (qv_norm <= eps)
    return jnp.where(use_ball, ball, cut)


def _feature_bound_from_acc(acc, sc):
    """VI bound on |fhat^T theta2| from the 4 reduction rows of ``acc``
    ((8, bm)); ``sc`` is the SMEM scalar ref. Returns (1, bm)."""
    eps = jnp.float32(1e-30)
    d_theta, d_one, d_y, d_sq = acc[0:1], acc[1:2], acc[2:3], acc[3:4]
    inv1, inv2 = sc[0], sc[1]
    yc, ysq, r_h_sq, g0 = sc[2], sc[3], sc[4], sc[5]
    qa_sq, a_norm, a_dot_y, hv = sc[6], sc[7], sc[8], sc[9]

    v_c = 0.5 * (inv2 * d_one + d_theta)
    v_ch = v_c - (yc / ysq) * d_y
    qv_sq = jnp.maximum(d_sq - d_y * d_y / ysq, 0.0)
    v_a = (d_theta - inv1 * d_one) / jnp.maximum(a_norm, eps)
    qv_qa = v_a - d_y * a_dot_y / ysq

    r_h = jnp.sqrt(jnp.maximum(r_h_sq, 0.0))
    m_pos = _t_cases(v_ch, qv_qa, qv_sq, r_h, g0, qa_sq, hv)
    m_neg = _t_cases(-v_ch, -qv_qa, qv_sq, r_h, g0, qa_sq, hv)
    return jnp.maximum(m_pos, m_neg)


def _sample_surplus_from_acc(acc, aux, sc):
    """Margin surplus y*(u+b1) - 1 - slack from the 2 transposed reduction
    rows of ``acc`` and the ``[y, u_prev]`` rows of ``aux``. (1, bn)."""
    u_part, x_sq = acc[0:1], acc[1:2]
    y, u_prev = aux[0:1], aux[1:2]
    b1, dw, db = sc[0], sc[1], sc[2]
    shrink, floor, has_hist = sc[3], sc[4], sc[5]

    u = u_part + b1
    slack_tr = jnp.sqrt(jnp.maximum(x_sq, 0.0)) * dw + db
    secant = shrink * jnp.abs(u - u_prev) + floor
    slack = jnp.minimum(slack_tr, jnp.where(has_hist > 0.5, secant, _BIG))
    return y * u - 1.0 - jnp.minimum(slack, _BIG)


# --------------------------------------------------------------------------
# kernels
# --------------------------------------------------------------------------

def _with_sq_row(dots, sq, row: int):
    """``dots`` with row ``row`` replaced by the squared-norm row of ``sq``."""
    rows = jax.lax.broadcasted_iota(jnp.int32, dots.shape, 0)
    return jnp.where(rows == row, sq, dots)


def _feature_kernel(x_ref, side_ref, sc_ref, out_ref, acc_ref):
    """Grid = (m_blocks, n_blocks); sample axis (dim 1) is the reduction."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)          # (bm, bn)
    side = side_ref[...]                         # (8, bn) rows: y*theta, y, 1
    nt = (((1,), (1,)), ((), ()))
    dots = jax.lax.dot_general(side, x, nt, precision=_HI,
                               preferred_element_type=jnp.float32)  # (8, bm)
    sq = jax.lax.dot_general(jnp.ones_like(side), x * x, nt, precision=_HI,
                             preferred_element_type=jnp.float32)    # row sums
    acc_ref[...] += _with_sq_row(dots, sq, 3)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        out_ref[...] = _feature_bound_from_acc(acc_ref[...], sc_ref)


def _sample_kernel(x_ref, side_ref, aux_ref, sc_ref, out_ref, acc_ref):
    """Grid = (n_blocks, m_blocks); feature axis (dim 1) is the reduction."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)          # (bm, bn) — transposed sweep
    side = side_ref[...]                         # (8, bm) rows: w1
    nn = (((1,), (0,)), ((), ()))
    dots = jax.lax.dot_general(side, x, nn, precision=_HI,
                               preferred_element_type=jnp.float32)  # (8, bn)
    sq = jax.lax.dot_general(jnp.ones_like(side), x * x, nn, precision=_HI,
                             preferred_element_type=jnp.float32)    # col sums
    acc_ref[...] += _with_sq_row(dots, sq, 1)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        out_ref[...] = _sample_surplus_from_acc(acc_ref[...], aux_ref[...],
                                                sc_ref)


# --------------------------------------------------------------------------
# entry point
# --------------------------------------------------------------------------

@functools.partial(
    jax.jit, static_argnames=("axis", "block_m", "block_n", "interpret")
)
def screen_bounds_pallas(
    X: jax.Array,
    side: jax.Array,
    scalars: jax.Array,
    aux: jax.Array | None = None,
    axis: str = "features",
    block_m: int = 256,
    block_n: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Fused per-unit screening bounds; ``axis`` picks the reduction axis.

    ``axis="features"``: ``side`` is the (8, n) ``stack_rows(y*theta1, y,
    ones)``, ``scalars`` packs ScreenShared (``pack_shared``), ``aux``
    unused; returns (m,) VI bounds.

    ``axis="samples"``: ``side`` is the (8, m) ``stack_rows(w1)``, ``aux``
    is the (8, n) ``stack_rows(y, u_prev)``, ``scalars`` packs the slack
    model (``pack_sample_scalars``); returns (n,) margin surpluses.

    X is (m, n), pre-padded to block multiples (see kernels/ops.py).
    """
    m, n = X.shape
    assert m % block_m == 0 and n % block_n == 0, (m, n, block_m, block_n)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    if axis == "features":
        grid = (m // block_m, n // block_n)
        return pl.pallas_call(
            _feature_kernel,
            name="screen_bounds_pallas",
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
                pl.BlockSpec((SIDE_ROWS, block_n), lambda i, j: (0, j)),
                smem,
            ],
            out_specs=pl.BlockSpec((1, block_m), lambda i, j: (0, i)),
            out_shape=jax.ShapeDtypeStruct((1, m), jnp.float32),
            scratch_shapes=[pltpu.VMEM((SIDE_ROWS, block_m), jnp.float32)],
            interpret=interpret,
        )(X, side, scalars)[0]

    if axis == "samples":
        assert aux is not None, "sample axis needs aux = stack_rows(y, u_prev)"
        grid = (n // block_n, m // block_m)
        return pl.pallas_call(
            _sample_kernel,
            name="screen_bounds_pallas",
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_m, block_n), lambda i, j: (j, i)),
                pl.BlockSpec((SIDE_ROWS, block_m), lambda i, j: (0, j)),
                pl.BlockSpec((SIDE_ROWS, block_n), lambda i, j: (0, i)),
                smem,
            ],
            out_specs=pl.BlockSpec((1, block_n), lambda i, j: (0, i)),
            out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
            scratch_shapes=[pltpu.VMEM((SIDE_ROWS, block_n), jnp.float32)],
            interpret=interpret,
        )(X, side, aux, scalars)[0]

    raise ValueError(f"axis must be 'features' or 'samples', got {axis!r}")
