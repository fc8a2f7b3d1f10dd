"""The path engine's own spans, scopes and counters: host spans on the
profiler's clock, name scopes in the compiled program, the certification's
binding rounds, and the benchmark's readers of them."""

import glob
import importlib.util
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import svm_path
from repro.core.dual import lambda_max
from repro.core.path_scan import (
    N_FEAS_ITERS,
    _engine_jit,
    _lambda_max_program,
    _static_opts,
)
from repro.core.solver import FEAS_BINDING_RTOL
from repro.data import make_sparse_classification
from repro.obs import metrics as obs_metrics
from repro.obs.trace import Tracer

ROOT = Path(__file__).resolve().parent.parent
SOLVE = dict(tol=1e-9, max_iters=4000)
T = 8
ENTRY_SPANS = ("svm_path.lambda_max", "svm_path.place_x",
               "svm_path.dispatch", "svm_path.result")


@pytest.fixture(autouse=True)
def _quiet_registry():
    obs_metrics.reset()
    yield
    obs_metrics.reset()


@pytest.fixture(scope="module")
def ds():
    return make_sparse_classification(m=300, n=120, k_active=8, seed=1)


def _host_events(trace_dir):
    """``[(name, start_ns, end_ns)]`` of the host plane's events."""
    from jax.profiler import ProfileData

    (xplane,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
    pd = ProfileData.from_file(xplane)
    return [(ev.name, ev.start_ns, ev.end_ns)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


def test_entry_spans_nest_on_the_profiler_host_plane(ds, tmp_path):
    """One scan call under ``jax.profiler`` puts ``svm_path`` on the host
    plane with its four phases inside it, in order and not overlapping."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        svm_path(ds.X, ds.y, n_lambdas=T, engine="scan", reduce="compact",
                 **SOLVE)
    finally:
        jax.profiler.stop_trace()
    evs = _host_events(str(tmp_path))
    (outer,) = [e for e in evs if e[0] == "svm_path"]
    inner = []
    for name in ENTRY_SPANS:
        (ev,) = [e for e in evs if e[0] == name]
        assert outer[1] <= ev[1] <= ev[2] <= outer[2], name
        inner.append(ev)
    for a, b in zip(inner, inner[1:]):
        assert a[2] <= b[1], (a[0], b[0])


def test_disabled_recorder_span_reaches_the_profiler(tmp_path):
    """With the Chrome recorder off a span records nothing there, and still
    lands on an active profiler trace."""
    t = Tracer(enabled=False)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with t.span("probe.span", step=1) as sp:
            sp.set(kept=3)
    finally:
        jax.profiler.stop_trace()
    assert t.events == []
    assert [e for e in _host_events(str(tmp_path)) if e[0] == "probe.span"]


@pytest.mark.parametrize("batched,scopes", [
    (None, ("svm_path/prologue", "svm_path/certify/feasibility",
            "svm_path/solve/compact", "svm_path/screen")),
    ("grids_compact", ("svm_path_batched/prologue",
                       "svm_path_batched/certify/feasibility",
                       "svm_path_batched/solve/compact")),
])
def test_compiled_program_carries_the_scopes(batched, scopes):
    """The compiled path program names its prologue, the certificate's
    feasibility rounds and the compact gather/scatter in its ``op_name``s,
    each under its parent scope."""
    m, n = 256, 64
    f32 = jnp.float32
    S = jax.ShapeDtypeStruct
    static_kw = _static_opts(50, True, False, 50, False, False, "compact")
    fn = _engine_jit(static_kw, batched=batched)
    if batched is None:
        args = (S((m, n), f32), S((n,), f32), S((T,), f32), S((m,), f32),
                S((), f32), S((n,), f32), S((), f32), S((), f32), None,
                S((), f32), S((), f32))
    else:
        args = (S((m, n), f32), S((n,), f32), None, S((2, T), f32),
                S((m,), f32), S((), f32), S((n,), f32), S((), f32),
                S((), f32), None, S((), f32), S((), f32))
    hlo = fn.lower(*args).compile().as_text()
    op_names = set(re.findall(r'op_name="([^"]*)"', hlo))
    for sc in scopes:
        assert any(sc in name for name in op_names), sc


def test_lambda_max_program_matches_the_eager_reduction(ds):
    """The scoped ``lambda_max`` program gives the value the eager reduction
    gives, so the lambda grid and the anchor do not move."""
    X, y = jnp.asarray(ds.X), jnp.asarray(ds.y)
    assert float(_lambda_max_program("svm_path")(X, y)) == float(
        lambda_max(X, y))


def _recount(X, y, w, b, lam):
    """``(binding, rounds)`` of one step's certificate, recounted in plain
    ``jnp`` from the returned ``w`` and ``b``: each round rescales, projects
    if it bound or is the first (and is below the cap), and the rounds stop
    after the first that did not project."""
    X = jnp.asarray(X, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    alpha = jnp.maximum(0.0, 1.0 - y * (X.T @ jnp.asarray(w, jnp.float32)
                                         + jnp.float32(b)))
    binding = 0
    for r in range(N_FEAS_ITERS + 1):
        mx = jnp.max(jnp.abs(X @ (y * alpha)))
        bound = bool(mx > lam * (1.0 + FEAS_BINDING_RTOL))
        binding += int(bound)
        alpha = alpha * jnp.minimum(1.0, lam / mx)
        if not ((bound or r == 0) and r < N_FEAS_ITERS):
            return binding, r + 1
        alpha = jnp.maximum(0.0, alpha - jnp.dot(alpha, y) / y.size * y)
    raise AssertionError("the rounds never stopped")


def test_feas_binding_matches_a_plain_recount(ds):
    """``extras["feas_binding"]`` counts, per step, the certificate's rounds
    whose rescale was binding, and ``extras["feas_rounds"]`` the rounds it
    ran, as an independent recount on the returned path does; the path's
    shares of them, both over the rounds' cap, land in the registry."""
    r = svm_path(ds.X, ds.y, n_lambdas=T, engine="scan", reduce="compact",
                 **SOLVE)
    binding, rounds = r.extras["feas_binding"], r.extras["feas_rounds"]
    want = [_recount(ds.X, ds.y, r.weights[k], r.biases[k],
                     np.float32(r.lambdas[k])) for k in range(T)]
    assert binding.tolist() == [bd for bd, _ in want]
    assert rounds.tolist() == [rd for _, rd in want]
    cap = T * (N_FEAS_ITERS + 1)
    assert 0 < binding.sum() < rounds.sum() < cap
    snap = obs_metrics.snapshot()
    assert snap["path.certify_binding_share"]["last"] == binding.sum() / cap
    assert snap["path.certify_rounds_share"]["last"] == rounds.sum() / cap


def test_entry_self_time_is_observed_per_call(ds):
    """Each single-dispatch call observes its self time (wall less
    dispatch) into ``path.entry_s``; the host engine observes none."""
    r = svm_path(ds.X, ds.y, n_lambdas=T, engine="scan", **SOLVE)
    h = obs_metrics.snapshot()["path.entry_s"]
    assert h["count"] == 1 and 0.0 < h["last"] < 60.0
    assert r.extras["total_seconds"] > 0.0
    rs = svm_path(ds.X, ds.y, engine="batched", reduce="compact",
                  lambdas=np.stack([r.lambdas, r.lambdas]), **SOLVE)
    assert len(rs) == 2
    assert obs_metrics.snapshot()["path.entry_s"]["count"] == 2
    svm_path(ds.X, ds.y, n_lambdas=4, engine="host", **SOLVE)
    assert obs_metrics.snapshot()["path.entry_s"]["count"] == 2


def _reader(name):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("name,histogram,scale", [
    ("entry_ms", "path.entry_s", 1e3),
    ("feas_binding", "path.certify_binding_share", 100.0),
    ("feas_rounds", "path.certify_rounds_share", 100.0),
])
def test_bench_reader_reads_the_last_call(ds, monkeypatch, name, histogram,
                                          scale):
    """The benchmark's reader gives the histogram's last observation,
    scaled, after one call, and nothing where the histogram is absent or
    empty."""
    read = _reader(name)
    monkeypatch.setattr(obs_metrics, "REGISTRY",
                        obs_metrics.MetricsRegistry())
    assert read(None) is None
    svm_path(ds.X, ds.y, n_lambdas=T, engine="scan", reduce="compact",
             **SOLVE)
    last = obs_metrics.snapshot()[histogram]["last"]
    assert read(None) == pytest.approx(scale * last)
    assert read(None) > 0.0
    obs_metrics.reset()
    assert read(None) is None
