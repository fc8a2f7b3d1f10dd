"""Unified observability layer: span recorder round-trips, disabled-mode
no-op guarantees, the metrics registry mirroring the legacy stats dicts
bitwise, and the uniform PathTrace artifact across engines."""

import json

import numpy as np
import pytest

from repro.core import PathDriver, svm_path
from repro.data import make_sparse_classification
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.path_trace import PathStep, PathTrace
from repro.obs.trace import Tracer
from repro.sparse import FeatureChunked

SOLVE = dict(tol=1e-9, max_iters=4000)


@pytest.fixture()
def tracer():
    """A private enabled tracer (does not touch the process singleton)."""
    return Tracer(enabled=True)


@pytest.fixture(autouse=True)
def _quiet_registry():
    """Reset the process registry around every test so counter equality
    checks see only this test's increments."""
    obs_metrics.reset()
    yield
    obs_metrics.reset()


@pytest.fixture(scope="module")
def ds():
    return make_sparse_classification(m=120, n=60, k_active=8, seed=0)


# -- span recorder ----------------------------------------------------------


def test_span_nesting_and_export_roundtrip(tracer, tmp_path):
    """Nested spans land as complete events whose intervals nest, attrs
    ride args, and the exported file is valid Chrome trace JSON."""
    with tracer.span("outer", step=1):
        with tracer.span("inner", phase="solve"):
            pass
        tracer.instant("marker", note="hi")
    evs = tracer.events
    names = [e["name"] for e in evs]
    assert names == ["inner", "marker", "outer"]  # exit order records
    inner = evs[0]
    outer = evs[2]
    assert outer["ph"] == "X" and inner["ph"] == "X"
    assert outer["args"] == {"step": 1}
    assert inner["args"] == {"phase": "solve"}
    # nesting: inner's interval sits inside outer's
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3

    out = tmp_path / "trace.json"
    tracer.export_chrome(out)
    doc = json.loads(out.read_text())
    assert "traceEvents" in doc
    byname = {e["name"]: e for e in doc["traceEvents"]}
    assert byname["process_name"]["ph"] == "M"
    assert byname["outer"]["args"] == {"step": 1}
    assert byname["marker"]["ph"] == "i"
    # every event is pid-stamped (Perfetto groups by pid/tid)
    assert all("pid" in e for e in doc["traceEvents"])


def test_span_set_attaches_attrs_mid_span(tracer):
    with tracer.span("solve") as sp:
        sp.set(iters=17)
    (ev,) = tracer.events
    assert ev["args"] == {"iters": 17}


def test_disabled_mode_is_noop_singleton():
    """A disabled recorder records nothing: spans (entered, attributed) and
    instants leave its event list empty."""
    t = Tracer(enabled=False)
    with t.span("solve", step=1) as sp:
        sp.set(iters=3)
        t.instant("marker")
    with t.span("other"):
        pass
    assert t.events == []

    # module-level span honors the process tracer's switch
    was = obs_trace.enabled()
    obs_trace.disable()
    try:
        n0 = len(obs_trace.get_tracer().events)
        with obs_trace.span("x"):
            pass
        obs_trace.instant("x")
        assert len(obs_trace.get_tracer().events) == n0
    finally:
        if was:
            obs_trace.enable()


def test_thread_safety_under_concurrent_spans(tracer):
    import threading

    barrier = threading.Barrier(4)  # all four alive at once: distinct tids

    def work(i):
        barrier.wait()
        for k in range(50):
            with tracer.span("w", tid_hint=i, k=k):
                pass

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    evs = tracer.events
    assert len(evs) == 200
    assert len({e["tid"] for e in evs}) == 4


# -- metrics registry -------------------------------------------------------


def test_metric_kinds_and_dumps():
    c = obs_metrics.counter("t.count")
    c.inc()
    c.inc(4)
    obs_metrics.gauge("t.gauge").set_max(7)
    obs_metrics.gauge("t.gauge").set_max(3)  # keeps the max
    h = obs_metrics.histogram("t.hist")
    for v in (1.0, 3.0):
        h.observe(v)
    snap = obs_metrics.snapshot()
    assert snap["t.count"] == 5
    assert snap["t.gauge"] == 7
    assert snap["t.hist"] == {"count": 2, "sum": 4.0, "min": 1.0,
                              "max": 3.0, "mean": 2.0, "last": 3.0}
    # kind collisions are typed errors, not silent re-registration
    with pytest.raises(TypeError):
        obs_metrics.gauge("t.count")
    doc = json.loads(obs_metrics.to_json())
    assert doc["t.count"] == 5
    prom = obs_metrics.to_prometheus()
    assert "repro_t_count_total 5" in prom
    assert "repro_t_hist_count 2" in prom
    assert "repro_t_hist_last 3.0" in prom


def test_histogram_last_is_the_newest_observation():
    """``last`` follows the newest observation, not the extremes, and
    empties with the histogram."""
    h = obs_metrics.histogram("t.last")
    assert h.get()["last"] is None
    for v in (5.0, 1.0, 3.0):
        h.observe(v)
        assert h.get()["last"] == v
    assert (h.get()["min"], h.get()["max"]) == (1.0, 5.0)
    h.reset()
    assert h.get()["last"] is None
    assert "repro_t_last_last" not in obs_metrics.to_prometheus()


def test_registry_mirrors_stream_stats_bitwise(ds):
    """The stream.* counters must equal FeatureChunked's legacy stats dict
    exactly after a chunked path run — same increments, one API."""
    fc = FeatureChunked.from_dense(np.asarray(ds.X), chunk_m=32)
    driver = PathDriver(**SOLVE)
    driver.run(fc, ds.y, n_lambdas=4)
    snap = obs_metrics.snapshot()
    for key in ("puts", "chunks_streamed", "chunks_skipped", "bytes_put",
                "bcoo_puts"):
        # counters register lazily; never-incremented ones read 0
        assert snap.get(f"stream.{key}", 0) == fc.stats[key], key
    assert snap["stream.max_put_rows"] == fc.stats["max_put_rows"]


def test_registry_mirrors_server_stats_bitwise(ds):
    """Every serve.* counter must equal the server's legacy stats dict
    after a drain, and metrics() returns the unified snapshot with the
    cache state absorbed."""
    from repro.launch.path_server import PathServer, demo_jobs

    server = PathServer(slots=2, **SOLVE)
    jobs = demo_jobs(3, m=60, n=40, seed=1)
    results = server.serve(jobs, log=lambda *a, **k: None)
    assert all(r is not None for r in results)
    snap = server.metrics()
    for key, val in server.stats.items():
        # counters register lazily; never-incremented ones read 0
        assert snap.get(f"serve.{key}", 0) == val, key
    cs = server.cache_stats()
    for key, val in cs.items():
        assert snap[f"serve.cache.{key}"] == val, key
    assert snap["serve.latency_s"]["count"] == len(jobs)
    # the path.* counters aggregate the assembled per-job traces
    assert snap["path.steps"] == sum(len(j.lambdas) for j in jobs)


# -- PathTrace --------------------------------------------------------------


def _trace_of(r):
    pt = r.extras["path_trace"]
    assert isinstance(pt, PathTrace)
    return pt


def _assert_schema(pt, T):
    assert len(pt.steps) == T
    for k, s in enumerate(pt.steps):
        assert isinstance(s, PathStep)
        assert s.step == k
        assert s.kept >= 0 and s.iters >= 0
    assert pt.total_s >= 0.0
    d = pt.to_dict()
    json.dumps(d)  # plain data, artifact-ready


def test_path_trace_uniform_across_engines(ds):
    """host, scan, and serve runs must all attach the SAME PathTrace
    schema: one record per lambda, matching grids, engine-tagged."""
    from repro.launch.path_server import PathJob, PathServer

    T = 4
    host = svm_path(ds.X, ds.y, n_lambdas=T, engine="host", **SOLVE)
    scan = svm_path(ds.X, ds.y, n_lambdas=T, engine="scan", **SOLVE)
    server = PathServer(slots=1, **SOLVE)
    job = PathJob(jid=0, X=np.asarray(ds.X), y=np.asarray(ds.y),
                  lambdas=np.asarray(host.lambdas))
    (serve,) = server.serve([job], log=lambda *a, **k: None)

    traces = {"host": _trace_of(host), "scan": _trace_of(scan),
              "serve": _trace_of(serve)}
    for name, pt in traces.items():
        assert pt.engine == name
        _assert_schema(pt, T)
        np.testing.assert_allclose([s.lam for s in pt.steps], host.lambdas)
    # host engines measure walls; single-dispatch engines synthesize them
    assert traces["host"].walls_observed
    assert not traces["scan"].walls_observed
    assert not traces["serve"].walls_observed
    # host phase walls are real measurements that add up inside the step
    for s in traces["host"].steps:
        assert np.isfinite(s.screen_s) and np.isfinite(s.certify_s)
        assert s.screen_s + s.solve_s + s.certify_s <= s.wall_s + 1e-6
    # the server's shared latency field equals the job's extras bookkeeping
    assert traces["serve"].total_s == pytest.approx(
        serve.extras["latency_s"])
    assert traces["serve"].meta["jid"] == 0


def test_path_trace_chunked_engine(ds):
    fc = FeatureChunked.from_dense(np.asarray(ds.X), chunk_m=32)
    r = PathDriver(**SOLVE).run(fc, ds.y, n_lambdas=4)
    pt = _trace_of(r)
    assert pt.engine == "chunked"
    _assert_schema(pt, 4)
    assert pt.walls_observed
    assert pt.meta["storage"] == "chunked"
