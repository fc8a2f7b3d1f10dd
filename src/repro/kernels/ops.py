"""Public jit'd wrappers for the Pallas kernels.

Handle padding to block multiples, dtype policy (fp32 accumulation), backend
dispatch (Mosaic on TPU, ``interpret=True`` elsewhere / in tests), and
packing of the feature-independent scalars.
"""

from __future__ import annotations

import os
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.screening import shared_scalars
from . import hinge as _hinge
from . import screen as _screen


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _default_interpret() -> bool:
    """Interpret-mode policy: forced on by ``REPRO_PALLAS_INTERPRET=1`` (the
    CI kernel lane, scripts/ci.sh), otherwise Mosaic on TPU, interpret
    elsewhere."""
    if os.environ.get("REPRO_PALLAS_INTERPRET", "") not in ("", "0"):
        return True
    return not _on_tpu()


def fista_use_pallas(flag: bool | None = None) -> bool:
    """Resolve the solver's kernel-dispatch toggle to a concrete bool.

    This is the seam ``solver._make_fista_body`` dispatches through: ``True``
    routes the two O(mn) sweeps per FISTA iteration to the fused Pallas
    kernels (:func:`margin_obj_op` + :func:`hinge_grad_op`), ``False`` keeps
    the pure-XLA matmuls. Resolution order:

    1. an explicit ``flag`` (the per-call argument) wins;
    2. ``REPRO_FISTA_PALLAS=1`` / ``=0`` forces it on / off globally;
    3. default: on when running on TPU (Mosaic), off elsewhere — on CPU the
       kernels fall back to Pallas interpret mode (``_default_interpret``),
       which is correct but far slower than XLA, so it is opt-in there
       (tests force it to check solver equivalence).
    """
    if flag is not None:
        return bool(flag)
    env = os.environ.get("REPRO_FISTA_PALLAS", "")
    if env != "":
        return env != "0"
    return _on_tpu()


def _pad_to(x: jax.Array, mult: int, axis: int) -> jax.Array:
    size = x.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads)


def _pad_len(v: jax.Array, size: int) -> jax.Array:
    """Zero-pad a vector to ``size`` entries (no-op when already there)."""
    return v if v.shape[0] == size else jnp.pad(v, (0, size - v.shape[0]))


def kernel_operand(X: jax.Array, block_m: int = 256,
                   block_n: int = 512) -> jax.Array:
    """``X`` zero-padded to the sweep kernels' block multiples.

    The scan engine pads once per path, a standalone solve once per solve,
    and every :func:`margin_obj_op` / :func:`hinge_grad_op` call then gets
    the padded operand (whose own padding is a no-op), so no sweep copies X.
    """
    return _pad_to(_pad_to(X, block_m, 0), block_n, 1)


def place_row_major(X, scope: str):
    """``X`` on the device in row-major tiled layout, on a TPU.

    The default device layout of an (m, n) array whose n is not a multiple
    of 128 can be column-major; a program that hands it to the kernels then
    keeps a row-major copy of all of X. Path engines that run the Pallas
    sweeps place X with this once, before dispatch (off a TPU: unchanged).
    The copy is one jitted program whose device op carries the name scope
    ``scope`` (``svm_path/place_x``).
    """
    if not _on_tpu():
        return X
    from jax.experimental.layout import Format, Layout
    from jax.sharding import SingleDeviceSharding

    sharding = getattr(X, "sharding", None)
    if sharding is None or len(sharding.device_set) != 1:
        sharding = SingleDeviceSharding(jax.devices()[0])
    fmt = Format(Layout(major_to_minor=tuple(range(np.ndim(X)))), sharding)
    return _relayout_program(fmt, scope)(X)


@lru_cache(maxsize=None)
def _relayout_program(fmt, scope: str):
    """A jitted copy of its argument into ``fmt``. The barrier keeps an
    instruction under ``scope`` in the program, and the layout copy XLA
    inserts after it takes its name (an identity would give the copy the
    parameter's name, outside any scope)."""
    def place_row_major_copy(X):
        with jax.named_scope(scope):
            return jax.lax.optimization_barrier(X)

    return jax.jit(place_row_major_copy, out_shardings=fmt)


def screen_bounds_op(
    X: jax.Array,
    y: jax.Array,
    lam1,
    lam2,
    theta1: jax.Array,
    block_m: int = 256,
    block_n: int = 512,
    interpret: bool | None = None,
    delta=0.0,
) -> jax.Array:
    """Fused screening bounds for all m features (kernel-backed).

    ``delta`` is the inexact-theta1 radius bound; it enters the kernel only
    through the packed shared scalars (ball inflation + g0 relaxation happen
    in ``shared_scalars``), so the sweep itself is unchanged — the in-solver
    dynamic refresh and the sequential screen share one kernel.
    """
    if interpret is None:
        interpret = _default_interpret()
    m, n = X.shape
    yf = y.astype(jnp.float32)
    tf = theta1.astype(jnp.float32)
    side = _screen.stack_rows(yf * tf, yf, jnp.ones_like(yf))
    sh = shared_scalars(yf, lam1, lam2, tf, delta=delta)
    scalars = _screen.pack_shared(sh)

    Xp = kernel_operand(X, block_m, block_n)
    out = _screen.screen_bounds_pallas(
        Xp, _pad_to(side, block_n, 1), scalars,
        block_m=block_m, block_n=block_n, interpret=interpret,
    )
    return out[:m]


def sample_surplus_op(
    X: jax.Array,
    w: jax.Array,
    y: jax.Array,
    b,
    dw=float("inf"),
    db=float("inf"),
    u_prev: jax.Array | None = None,
    shrink_factor: float = 2.0,
    margin_floor: float = 1e-3,
    block_m: int = 256,
    block_n: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused sample-screening margin surpluses (kernel-backed).

    One transposed sweep of X computes ``u = X^T w + b`` and ``||x_i||^2``
    and finalizes ``y*u - 1 - slack`` in VMEM (see rules/sample_vi.py for
    the slack models). ``u_prev=None`` disables the secant model.
    """
    if interpret is None:
        interpret = _default_interpret()
    m, n = X.shape
    yf = y.astype(jnp.float32)
    has_history = u_prev is not None
    up = (u_prev.astype(jnp.float32) if has_history else jnp.zeros_like(yf))
    scalars = _screen.pack_sample_scalars(
        b, dw, db, shrink_factor, margin_floor, has_history
    )

    Xp = kernel_operand(X, block_m, block_n)
    # zero w columns: no u / ||x||^2 contribution; padded samples (y = 0)
    # are sliced off below
    side = _pad_to(_screen.stack_rows(w), block_m, 1)
    aux = _pad_to(_screen.stack_rows(yf, up), block_n, 1)
    out = _screen.screen_bounds_pallas(
        Xp, side, scalars, aux=aux, axis="samples",
        block_m=block_m, block_n=block_n, interpret=interpret,
    )
    return out[:n]


def margin_obj_op(
    X: jax.Array, w: jax.Array, y: jax.Array, b,
    block_m: int = 256, block_n: int = 512, interpret: bool | None = None,
    valid_m: jax.Array | None = None,
):
    """(u, xi, loss) = fused margin/residual/loss sweep (kernel-backed).

    One pass over X yields ``u = X^T w`` (bias not added), the hinge slacks
    ``xi = max(0, 1 - y(u + b))``, and the squared-hinge loss
    ``0.5 * sum(xi^2)`` — this is the sweep the fused FISTA body issues at
    each *new* iterate, so the objective costs no extra pass over X (the
    separate ``_objective`` sweep of the pre-fusion solver is gone).

    ``X`` may already be padded (:func:`kernel_operand`): the logical sizes
    are those of ``w`` and ``y``, and extra rows/columns of ``X`` must be
    zero. ``valid_m`` (dynamic scalar): live leading-row count of a
    compacted active set (``core/path_scan.py reduce="compact"``); rows past
    it must be zero padding — the kernel skips their blocks.
    """
    if interpret is None:
        interpret = _default_interpret()
    n = y.shape[0]
    Xp = kernel_operand(X, block_m, block_n)
    wp = _pad_len(w, Xp.shape[0])
    # pad y with 0 => padded xi = max(0, 1-0*(u+b)) = 1: inert for u (w rows
    # are zero-padded) but each padded slot adds 0.5 to the loss — mask xi
    # and subtract the padded contribution after the call.
    yp = _pad_len(y, Xp.shape[1])
    u, xi, loss = _hinge.hinge_margin_pallas(
        Xp, wp, yp, jnp.asarray(b, jnp.float32), valid_m=valid_m,
        block_m=block_m, block_n=block_n, interpret=interpret,
    )
    if yp.shape[0] != n:
        mask = (jnp.arange(yp.shape[0]) < n).astype(jnp.float32)
        xi = xi * mask
        # padded slots contributed 0.5 * 1^2 each to the loss (y=0 => xi=1)
        loss = loss - 0.5 * jnp.sum(1.0 - mask)
    return u[:n], xi[:n], loss


def hinge_margin_op(
    X: jax.Array, w: jax.Array, y: jax.Array, b,
    block_m: int = 256, block_n: int = 512, interpret: bool | None = None,
):
    """(xi, loss) = fused margin/residual sweep (kernel-backed)."""
    _, xi, loss = margin_obj_op(X, w, y, b, block_m=block_m, block_n=block_n,
                                interpret=interpret)
    return xi, loss


def hinge_grad_op(
    X: jax.Array, y: jax.Array, xi: jax.Array,
    block_m: int = 256, block_n: int = 512, interpret: bool | None = None,
    valid_m: jax.Array | None = None,
) -> jax.Array:
    """g = -X (y*xi) (kernel-backed), one entry per row of ``X``.

    ``X`` may already be padded (:func:`kernel_operand`, extra entries zero;
    the caller slices ``g`` to its logical rows). ``valid_m`` as in
    :func:`margin_obj_op` (output rows past the live count are written as
    zeros, not computed)."""
    if interpret is None:
        interpret = _default_interpret()
    m = X.shape[0]
    Xp = kernel_operand(X, block_m, block_n)
    v = _pad_len(y.astype(jnp.float32) * xi.astype(jnp.float32), Xp.shape[1])
    g = _hinge.hinge_grad_pallas(Xp, v, valid_m=valid_m, block_m=block_m,
                                 block_n=block_n, interpret=interpret)
    return g[:m]
