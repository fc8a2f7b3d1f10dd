"""Program spans on the profiler's clock, and a Chrome trace-event recorder.

:func:`span` is the one span primitive of the repo. Every span enters a
``jax.profiler.TraceAnnotation`` of its name, always: under a running
``jax.profiler`` session (``train_svm --profile DIR``, the benchmark's
traced runs) the program's host spans land on the profiler's host plane,
on the same clock as the device ops and their ``jax.named_scope`` regions.
With no profiler session an annotation costs about a microsecond.

Beside that, one process-wide :class:`Tracer` (off by default) records
the spans and *instant events* itself and exports them as Chrome
trace-event JSON (``{"traceEvents": [...]}``), loadable in Perfetto
(https://ui.perfetto.dev) or ``chrome://tracing``: spans become complete
events (``ph="X"``, microsecond ``ts``/``dur`` on the recorder's own
``perf_counter`` epoch), instants become ``ph="i"``, and span attributes
ride ``args``. When the recorder is on, a span costs two more
``perf_counter`` reads and one locked list append; it is thread-safe, so
the server drain loop and worker threads interleave correctly (events
carry the recording thread's id). When it is off, a span records nothing
in it.

Enable the recorder programmatically (:func:`enable`) or via
``REPRO_TRACE=1`` in the environment; ``train_svm --trace out.json`` wires
both ends together.
"""

from __future__ import annotations

import json
import os
import threading
import time
from pathlib import Path

from jax.profiler import TraceAnnotation

__all__ = [
    "Tracer",
    "span",
    "instant",
    "enable",
    "disable",
    "enabled",
    "get_tracer",
    "export_chrome",
]


class _Span:
    """A named interval: a profiler annotation always, and a recorded
    event when its recorder was on at the span's creation."""

    __slots__ = ("_tracer", "name", "attrs", "_t0", "_ann")

    def __init__(self, tracer: "Tracer | None", name: str, attrs: dict):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs

    def set(self, **attrs):
        """Attach/overwrite attributes mid-span (e.g. iteration counts
        known only at the end of the timed region)."""
        self.attrs.update(attrs)
        return self

    def __enter__(self):
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        if self._tracer is not None:
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._tracer is not None:
            t1 = time.perf_counter()
            self._tracer._record(self.name, self._t0, t1 - self._t0,
                                 self.attrs)
        self._ann.__exit__(*exc)
        return False


class Tracer:
    """Thread-safe span/event recorder with Chrome trace-event export.

    All timestamps are relative to the tracer's epoch (construction or the
    most recent :meth:`clear`), in seconds; export converts to the
    microseconds the trace-event format wants.
    """

    def __init__(self, enabled: bool = False, process_name: str = "repro"):
        self._enabled = bool(enabled)
        self.process_name = process_name
        self._lock = threading.Lock()
        self._events: list[dict] = []
        self._epoch = time.perf_counter()

    # -- state -------------------------------------------------------------

    @property
    def enabled(self) -> bool:
        return self._enabled

    def enable(self):
        self._enabled = True

    def disable(self):
        self._enabled = False

    def clear(self):
        with self._lock:
            self._events = []
            self._epoch = time.perf_counter()

    def now(self) -> float:
        """Seconds since the tracer epoch."""
        return time.perf_counter() - self._epoch

    @property
    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    # -- recording ---------------------------------------------------------

    def span(self, name: str, **attrs):
        """Context manager timing a named interval; recorded only when the
        recorder is on, annotated for the profiler always."""
        return _Span(self if self._enabled else None, name, attrs)

    def instant(self, name: str, **attrs):
        """Record a zero-duration marker event; no-op when disabled."""
        if not self._enabled:
            return
        self._append({
            "name": name, "ph": "i", "s": "t",
            "ts": self.now() * 1e6,
            "tid": threading.get_ident(),
            "args": attrs,
        })

    def _record(self, name, t0, dur_s, attrs):
        self._append({
            "name": name, "ph": "X",
            "ts": (t0 - self._epoch) * 1e6,
            "dur": dur_s * 1e6,
            "tid": threading.get_ident(),
            "args": attrs,
        })

    def _append(self, ev: dict):
        with self._lock:
            self._events.append(ev)

    # -- export ------------------------------------------------------------

    def to_chrome(self) -> dict:
        """The Chrome trace-event JSON object (Perfetto-loadable)."""
        pid = os.getpid()
        with self._lock:
            events = [dict(ev, pid=pid) for ev in self._events]
        meta = [{
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": self.process_name},
        }]
        return {"traceEvents": meta + events, "displayTimeUnit": "ms"}

    def export_chrome(self, path) -> str:
        """Write the Chrome trace-event JSON to ``path``; returns the path."""
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(self.to_chrome()))
        return str(path)


# -- process-wide tracer ---------------------------------------------------

_TRACER = Tracer(enabled=os.environ.get("REPRO_TRACE", "0") not in
                 ("", "0", "false", "False"))


def get_tracer() -> Tracer:
    return _TRACER


def enabled() -> bool:
    return _TRACER._enabled


def enable():
    _TRACER.enable()


def disable():
    _TRACER.disable()


def span(name: str, **attrs):
    """Module-level ``with span("solve", step=k): ...`` on the process
    tracer — the form the engines thread through their hot loops."""
    return _TRACER.span(name, **attrs)


def instant(name: str, **attrs):
    _TRACER.instant(name, **attrs)


def export_chrome(path) -> str:
    return _TRACER.export_chrome(path)
