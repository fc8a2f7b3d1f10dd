"""The main path's programs compile for a TPU v5e, at rcv1 width.

Compiles for a described ``v5e:2x2`` topology, with no chip attached: the
TPU compiler refuses here what it would refuse on the chip (Mosaic's block
and layout rules, a program that does not fit the 16 GB of HBM), at no chip
time. Nothing runs, so nothing here is a time. The topology is described
inside a module fixture only; where it cannot be described, every test of
this file skips.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest

M, N = 47_236, 20_242          # LIBSVM rcv1.binary: features x samples
HBM_BYTES = 16e9               # one TPU v5e
# "clearly under" 16 GB: the compact scan program measures ~9.1 GB; this
# catches a stray full copy of X (3.8 GB) before it makes the step not fit
BUDGET_BYTES = 12e9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape_on_chip(topo):
    """``shape(*dims, dtype=f32, row_major=False)`` on one described chip."""
    from jax.experimental.layout import Format, Layout
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topo.devices[0])

    def shape(*dims, dtype=jnp.float32, row_major=False):
        sharding = one
        if row_major:  # as kernels.ops.place_row_major places X on a TPU
            sharding = Format(Layout(major_to_minor=tuple(range(len(dims)))),
                              one)
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    return shape


@pytest.fixture
def tpu_policy(monkeypatch):
    """Steer the kernel policy as on a TPU (Pallas on, Mosaic, no interpret
    mode), and keep these compiles out of any persistent cache."""
    import repro.kernels.ops as ops

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    monkeypatch.delenv("REPRO_FISTA_PALLAS", raising=False)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _fits(compiled):
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes)


KERNELS = ["margin", "grad", "screen_features", "screen_samples"]


@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_with_mosaic(kernel, shape_on_chip):
    """Each Pallas kernel at rcv1 width, ``interpret=False``: Mosaic accepts
    its blocks, and the program holds the kernel (``tpu_custom_call``)."""
    from repro.kernels import ops

    S = shape_on_chip
    swept = S(*jax.eval_shape(ops.kernel_operand, S(M, N)).shape)
    cases = {
        "margin": (lambda X, w, y, b, vm: ops.margin_obj_op(
                       X, w, y, b, interpret=False, valid_m=vm),
                   (swept, S(M), S(N), S(), S(dtype=jnp.int32))),
        "grad": (lambda X, y, xi, vm: ops.hinge_grad_op(
                     X, y, xi, interpret=False, valid_m=vm),
                 (swept, S(N), S(N), S(dtype=jnp.int32))),
        "screen_features": (lambda X, y, th: ops.screen_bounds_op(
                                X, y, 1.0, 0.5, th, interpret=False),
                            (S(M, N), S(N), S(N))),
        "screen_samples": (lambda X, w, y: ops.sample_surplus_op(
                               X, w, y, 0.1, dw=0.3, db=0.1,
                               interpret=False),
                           (S(M, N), S(M), S(N))),
    }
    fn, args = cases[kernel]
    compiled = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()
    assert _fits(compiled) < HBM_BYTES


def test_compact_scan_program_fits_with_kernels(shape_on_chip, tpu_policy):
    """The compact scan engine's whole-path program, as ``svm_path_scan``
    dispatches it on a TPU (default kernel policy, X placed row-major):
    every solver branch runs the Pallas sweeps, and arguments plus
    temporaries stay clearly under one chip's HBM."""
    from repro.core.path_scan import _engine_jit, _static_opts

    S = shape_on_chip
    static_kw = _static_opts(2000, True, False, 50, None, False, "compact")
    assert dict(static_kw)["use_pallas"]
    T = 8
    compiled = _engine_jit(static_kw).lower(
        S(M, N, row_major=True), S(N), S(T), S(M), S(), S(N), S(), S(),
        None, S(), S()).compile()
    hlo = compiled.as_text()
    # 4 compact buckets + the mask overflow branch, each with its sweeps
    assert hlo.count("tpu_custom_call") >= 5
    assert _fits(compiled) < BUDGET_BYTES


def test_server_step_compiles_with_batched_kernels(shape_on_chip, tpu_policy):
    """The path server's batched step (the kernels under ``vmap``) compiles
    with Mosaic at a two-slot 16,384 x 4,096 bucket."""
    from repro.core.path_scan import _batched_path_step, _static_opts

    S = shape_on_chip
    B, m, n = 2, 16_384, 4_096
    cfg = dict(_static_opts(2000, True, False, 50, None, False, "compact",
                            "feature_vi"))
    cfg.pop("reduce")
    step = functools.partial(_batched_path_step, caps=(2048,),
                             shared_x=False, **cfg)
    statics = (S(B, m), S(B, m), S(B, m), S(B), S(B))
    carry = (S(B, m), S(B), S(B, n), S(B), S(B), S(B, m))
    compiled = _compile(step, S(B, m, n), S(B, n), S(B, n), statics, S(B),
                        S(), S(), carry, S(B), S(B, dtype=jnp.bool_))
    assert "tpu_custom_call" in compiled.as_text()
    assert _fits(compiled) < HBM_BYTES
