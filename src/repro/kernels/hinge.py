"""Pallas TPU kernels for the squared-hinge solver hot loop.

Two tiled GEMV-shaped kernels (the FISTA iteration's only O(mn) work):

  * ``hinge_margin``  : u = X^T w, fused with xi = max(0, 1 - y(u + b)) and
                        the per-block loss partials — saves one HBM round
                        trip of u and one of xi vs composing XLA ops. The
                        raw margins ``u`` are emitted alongside ``xi`` so the
                        solver can carry them across iterations (the fused
                        FISTA body extrapolates the momentum point's margins
                        linearly from carried ``u`` instead of re-sweeping X).
  * ``hinge_grad``    : g = -X (y * xi), the transposed sweep.

Layout (what Mosaic accepts): X tiles are (sublane x 128-lane) blocks;
every vector operand and result is a lane-dense ``(1, len)`` row, so its
blocks are ``(1, block)`` with ``block`` a multiple of 128, and the loss
partials are one 128-lane row per output block (the partial in lane 0).
The scalars (live-row count, bias) are scalar-prefetch operands in SMEM.
Both sweeps are MXU contractions at ``Precision.HIGHEST`` with fp32
accumulation in VMEM scratch: the carried margins feed the gap
certificate, so they must be float32-accurate on the chip, not bf16-pass
products.

Row-validity counts (the active-set compaction seam, ``core/path_scan.py``
``reduce="compact"``): both kernels take a dynamic scalar ``valid_m`` — the
number of live leading feature rows — as a scalar-prefetch operand.
Compacted operands zero-pad the rows past ``valid_m``, so those blocks
contribute nothing; the count lets the kernel *skip* their MXU work
(``pl.when`` on the feature-block id), and the index maps clamp the block
index of skipped steps to the last live block, so their X tiles are not
fetched from HBM either. Passing ``valid_m = m`` is the full-matrix case and
leaves the schedule untouched.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))  # (1, k) x (k, n): contract lhs lanes
_NT = (((1,), (1,)), ((), ()))  # (1, k) x (n, k): contract both lane axes


def _last_live_block(vm_ref, block: int):
    """Index of the last block holding a live row (0 when none is live)."""
    return jnp.maximum((vm_ref[0] + block - 1) // block - 1, 0)


def _margin_kernel(vm_ref, b_ref, x_ref, w_ref, y_ref, u_ref, xi_ref,
                   loss_ref, acc_ref):
    j = pl.program_id(1)  # feature-axis reduction step

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # skip blocks entirely past the live rows of a compacted active set
    # (their x/w are zero padding — no contribution, so no MXU work)
    @pl.when(j * x_ref.shape[0] < vm_ref[0])
    def _acc():
        x = x_ref[...].astype(jnp.float32)   # (bm, bn)
        w = w_ref[...].astype(jnp.float32)   # (1, bm)
        acc_ref[...] += jax.lax.dot_general(
            w, x, _NN, precision=_HI, preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _fin():
        y = y_ref[...].astype(jnp.float32)
        u = acc_ref[...]
        xi = jnp.maximum(0.0, 1.0 - y * (u + b_ref[0]))
        u_ref[...] = u
        xi_ref[...] = xi
        lane = jax.lax.broadcasted_iota(jnp.int32, loss_ref.shape, 1)
        loss_ref[...] = jnp.where(lane == 0, 0.5 * jnp.sum(xi * xi), 0.0)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "interpret"))
def hinge_margin_pallas(
    X: jax.Array, w: jax.Array, y: jax.Array, b: jax.Array,
    valid_m: jax.Array | None = None,
    block_m: int = 256, block_n: int = 512, interpret: bool = False,
):
    """Returns (u, xi, loss). Shapes must be pre-padded to block multiples.

    ``u = X^T w`` (bias NOT added), ``xi = max(0, 1 - y(u + b))``,
    ``loss = 0.5 * sum(xi^2)`` — all three from one sweep of X. ``valid_m``
    (dynamic scalar, default all rows) skips feature blocks past the live
    rows of a compacted active set.
    """
    m, n = X.shape
    assert m % block_m == 0 and n % block_n == 0
    grid = (n // block_n, m // block_m)
    vm = jnp.reshape(jnp.asarray(m if valid_m is None else valid_m,
                                 jnp.int32), (1,))
    b_s = jnp.reshape(jnp.asarray(b, jnp.float32), (1,))

    def feat_block(j, vm_ref):
        return jnp.minimum(j, _last_live_block(vm_ref, block_m))

    u, xi, loss_parts = pl.pallas_call(
        _margin_kernel,
        name="hinge_margin_pallas",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_m, block_n),
                             lambda i, j, vm, b: (feat_block(j, vm), i)),
                pl.BlockSpec((1, block_m),
                             lambda i, j, vm, b: (0, feat_block(j, vm))),
                pl.BlockSpec((1, block_n), lambda i, j, vm, b: (0, i)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_n), lambda i, j, vm, b: (0, i)),
                pl.BlockSpec((1, block_n), lambda i, j, vm, b: (0, i)),
                pl.BlockSpec((1, 128), lambda i, j, vm, b: (0, i)),
            ],
            scratch_shapes=[pltpu.VMEM((1, block_n), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, grid[0] * 128), jnp.float32),
        ],
        interpret=interpret,
    )(vm, b_s, X, w.reshape(1, m), y.reshape(1, n))
    return u[0], xi[0], jnp.sum(loss_parts)


def _grad_kernel(vm_ref, x_ref, v_ref, g_ref, acc_ref):
    i = pl.program_id(0)  # feature-axis output block
    j = pl.program_id(1)  # sample-axis reduction step

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # output blocks past the compacted active set stay zero: skip their MXU
    # work (the final write still runs so every output row is defined)
    @pl.when(i * x_ref.shape[0] < vm_ref[0])
    def _acc():
        x = x_ref[...].astype(jnp.float32)   # (bm, bn)
        v = v_ref[...].astype(jnp.float32)   # (1, bn) = y * xi
        acc_ref[...] += jax.lax.dot_general(
            v, x, _NT, precision=_HI, preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _fin():
        g_ref[...] = -acc_ref[...]


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "interpret"))
def hinge_grad_pallas(
    X: jax.Array, v: jax.Array,
    valid_m: jax.Array | None = None,
    block_m: int = 256, block_n: int = 512, interpret: bool = False,
) -> jax.Array:
    """g = -X v with fp32 accumulation (v = y * xi precomputed).

    ``valid_m`` (dynamic scalar, default all rows) skips output blocks past
    the live rows of a compacted active set — they are written as zeros.
    """
    m, n = X.shape
    assert m % block_m == 0 and n % block_n == 0
    grid = (m // block_m, n // block_n)
    vm = jnp.reshape(jnp.asarray(m if valid_m is None else valid_m,
                                 jnp.int32), (1,))

    def tile(i, j, vm_ref):
        # a skipped output block re-points at the tile fetched last (the
        # final tile of the last live block), so the pipeline fetches nothing
        last = _last_live_block(vm_ref, block_m)
        skip = i > last
        return (jnp.where(skip, last, i),
                jnp.where(skip, grid[1] - 1, j))

    return pl.pallas_call(
        _grad_kernel,
        name="hinge_grad_pallas",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_m, block_n), tile),
                pl.BlockSpec((1, block_n),
                             lambda i, j, vm: (0, tile(i, j, vm)[1])),
            ],
            out_specs=pl.BlockSpec((1, block_m), lambda i, j, vm: (0, i)),
            scratch_shapes=[pltpu.VMEM((1, block_m), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((1, m), jnp.float32),
        interpret=interpret,
    )(vm, X, v.reshape(1, n))[0]
