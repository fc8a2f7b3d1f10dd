"""Unified path observability: trace spans, metrics, logging, PathTrace.

The repo runs the screened SVM path through six engines (host, scan,
batched, sharded, server, chunked/mmap); this package is the one
instrumentation layer they all report through:

- :mod:`repro.obs.trace` — the one span primitive
  (``span("svm_path.dispatch")`` context manager, thread-safe for the
  server drain loop). Every span is a ``jax.profiler.TraceAnnotation``, so
  under ``jax.profiler`` (``train_svm --profile DIR``) the program's host
  spans share one timeline with the device ops; a recorder, off by
  default, also exports them as Chrome trace-event JSON loadable in
  Perfetto (``REPRO_TRACE=1`` or ``train_svm --trace out.json``). Threaded
  through the entry (``svm_path`` and its phases), ``PathDriver.run``, the
  streamed solver's host loop, ``screen_step_stream``, and the
  ``PathServer`` drain/refill/checkpoint cycle.
- :mod:`repro.obs.metrics` — a process-wide registry of counters /
  gauges / histograms absorbing the previously scattered telemetry
  (engine-cache hit/miss/retrace, ``chunks_streamed`` /
  ``chunks_skipped`` / ``bytes_put``, guard trips, kept-per-step, job
  latency, the entry's host time per call, the certificate's binding
  share per path) with JSON and Prometheus-text dumps;
  ``PathServer.metrics()`` returns its snapshot.
- :mod:`repro.obs.log` — structured-logging setup (module-level loggers,
  one handler on the ``repro`` root, ``REPRO_LOG_LEVEL`` env-tunable).
- :mod:`repro.obs.path_trace` — the uniform ``PathTrace`` artifact every
  engine attaches at ``PathResult.extras["path_trace"]``.

PathTrace field reference (per step; ``nan`` where an engine cannot
observe the quantity):

====================  ====================================================
field                 meaning
====================  ====================================================
``step``              lambda-grid index ``k``
``lam``               regularization value solved at this step
``kept``              features fed to the solver after screening
``kept_samples``      samples fed to the solver (0 = axis unused)
``active``            nnz(w) at the accepted solution
``iters``             FISTA iterations spent
``gap``               duality gap certified at the accepted point
``delta``             certified theta-radius anchoring the next screen
``health``            guard word (``HEALTH_SCREEN_REFUSED`` = keep-all)
``wall_s``            step wall seconds (measured, or uniform share of a
                      single-dispatch total — ``walls_observed`` says
                      which)
``screen_s``          host-measured screening wall (host engines)
``solve_s``           host-measured solve wall (host engines)
``certify_s``         host-measured certification wall (host engines)
====================  ====================================================

Run-level: ``engine`` (host / scan / batched / scan_sharded / serve /
chunked), ``total_s`` (the shared latency field — the server's per-job
``latency_s`` and the host driver's summed step walls land here),
``walls_observed``, and free-form ``meta`` (jid, stream stats, ...).
"""

from .log import get_logger, setup
from .metrics import (
    REGISTRY,
    MetricsRegistry,
    absorb,
    counter,
    gauge,
    histogram,
    snapshot,
    to_json,
    to_prometheus,
)
from .path_trace import PathStep, PathTrace, build_path_trace
from .trace import (
    Tracer,
    enable,
    enabled,
    disable,
    export_chrome,
    get_tracer,
    instant,
    span,
)

__all__ = [
    "get_logger",
    "setup",
    "REGISTRY",
    "MetricsRegistry",
    "absorb",
    "counter",
    "gauge",
    "histogram",
    "snapshot",
    "to_json",
    "to_prometheus",
    "PathStep",
    "PathTrace",
    "build_path_trace",
    "Tracer",
    "enable",
    "enabled",
    "disable",
    "export_chrome",
    "get_tracer",
    "instant",
    "span",
]
