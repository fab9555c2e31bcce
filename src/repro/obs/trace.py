"""Span tracer exporting Chrome-trace / Perfetto JSON.

A *span* is a named wall-clock interval with optional key/value args.  The
tracer buffers complete events in memory and writes the standard Chrome
trace-event JSON object (``{"traceEvents": [...]}``, timestamps in µs) that
``chrome://tracing`` and https://ui.perfetto.dev load directly.

Usage::

    from repro import obs

    obs.start_trace("trace.json")          # or PATHSIG_TRACE=trace.json
    with obs.span("serve.flush", rungs=3):
        ...
    obs.stop_trace()                       # writes + returns the path

Design rules (mirroring :mod:`repro.obs.metrics`):

- **On the device trace's clock.** While a ``jax.profiler`` session runs
  (``jax.profiler.trace``, the benchmark's), every span also enters a
  ``jax.profiler.TraceAnnotation`` of its name and args, so the session
  shows the program's spans on its host plane beside the device ops.
- **Idle costs one flag check.** With no trace file, no flight recorder
  and no profiler session, ``span()`` returns a shared null context
  manager.
- **Nesting is implicit.** Spans emit Chrome "complete" (``ph: "X"``)
  events on one thread-id track; the viewer reconstructs the stack from
  containment.  A thread-local depth counter is recorded in ``args.depth``
  so tests (and offline tooling) can assert nesting without a viewer.
- **jit-friendly.** Spans measure *host* wall-clock; device work launched
  asynchronously inside a span is attributed to it only up to dispatch.
  Device time is read from a profiler trace, where the device ops carry
  the program's named scopes (:data:`DEVICE_SCOPES`).

- **Bounded buffer.** The in-memory event list is a ring of
  ``PATHSIG_TRACE_MAX_EVENTS`` (default 100000) most-recent events; on a
  long traced run the oldest events are evicted and counted in
  ``Tracer.dropped`` / the ``pathsig_trace_events_dropped_total`` metric,
  and the save-at-exit still writes whatever the ring holds.

``PATHSIG_TRACE=<path>`` starts tracing at import and registers an atexit
save to ``<path>``.
"""
from __future__ import annotations

import atexit
import collections
import json
import os
import threading
import time

from jax.profiler import TraceAnnotation

from . import metrics as _metrics

__all__ = [
    "Tracer", "TRACER", "span", "instant",
    "start_trace", "stop_trace", "trace_active", "trace_scope",
    "DEFAULT_MAX_EVENTS", "DEVICE_SCOPES", "SPAN_PREFIXES",
]

# The ``jax.named_scope`` names the program sets on its device work (the
# op_name prefixes in compiled HLO): the custom VJPs' forward and backward,
# the two halves of the §4.2 reverse scans' body, and the closure-table
# gathers of the projected step.
DEVICE_SCOPES = ("pathsig.fwd", "pathsig.bwd", "pathsig.bwd.reconstruct",
                 "pathsig.bwd.cotangent", "pathsig.proj.index")

# Every ``span()`` name in the program starts with one of these.
SPAN_PREFIXES = ("kernels.", "serve.", "train.")

_PID = os.getpid()

DEFAULT_MAX_EVENTS = 100_000

DROP_COUNTER_NAME = "pathsig_trace_events_dropped_total"


def _env_max_events() -> int:
    raw = os.environ.get("PATHSIG_TRACE_MAX_EVENTS", "").strip()
    try:
        n = int(raw) if raw else DEFAULT_MAX_EVENTS
    except ValueError:
        n = DEFAULT_MAX_EVENTS
    return max(1, n)


# True while a jax.profiler session records host events.
_profiling = TraceAnnotation.is_enabled


class _NullSpan:
    """Shared do-nothing context manager: the idle fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args):      # same surface as Span
        return self


_NULL_SPAN = _NullSpan()


class _Annotation(TraceAnnotation):
    """A span only a profiler session records: its host annotation alone,
    with :class:`Span`'s ``set``."""

    __slots__ = ()

    def set(self, **args) -> "_Annotation":
        self.set_metadata(**args)
        return self


class Span:
    __slots__ = ("_tracer", "name", "args", "_t0", "_depth", "_ann")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self._tracer = tracer
        self.name = name
        self.args = args
        self._t0 = 0.0
        self._depth = 0
        self._ann = TraceAnnotation(name, **args) if _profiling() else None

    def set(self, **args) -> "Span":
        """Attach/update args after entry (e.g. results known at exit)."""
        self.args.update(args)
        if self._ann is not None:
            self._ann.set_metadata(**args)
        return self

    def __enter__(self) -> "Span":
        self._depth = self._tracer._enter_depth()
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        tr = self._tracer
        tr._exit_depth()
        tr._emit(self.name, self._t0, t1, self._depth, self.args)
        return False


class Tracer:
    """Buffers Chrome trace events; one per process (:data:`TRACER`)."""

    def __init__(self, max_events: int | None = None):
        self._active = False
        self._path: str | None = None
        self._max_events = _env_max_events() if max_events is None \
            else max(1, int(max_events))
        self._events: collections.deque = collections.deque(
            maxlen=self._max_events)
        self._lock = threading.Lock()
        self._epoch = 0.0
        self._local = threading.local()
        self._flight = None        # repro.obs.flight ring (always-on sink)
        self._record = False       # := _active or _flight is not None
        self.dropped = 0           # ring evictions since last reset

    # -- lifecycle ---------------------------------------------------------

    @property
    def active(self) -> bool:
        return self._active

    def _update_record(self) -> None:
        self._record = self._active or self._flight is not None

    def set_flight(self, recorder) -> None:
        """Attach/detach the flight-recorder ring — spans keep feeding it
        even when no trace file is active."""
        self._flight = recorder
        self._update_record()

    def start(self, path: str | None = None, *, reset: bool = True) -> None:
        with self._lock:
            if reset:
                self._events.clear()
                self.dropped = 0
            self._path = path
            self._epoch = time.perf_counter()
            self._active = True
            self._update_record()

    def stop(self, path: str | None = None) -> str | None:
        """Deactivate and, when a path is known, write the JSON file.
        Returns the written path (None if nothing was written)."""
        with self._lock:
            self._active = False
            self._update_record()
            out = path or self._path
        if out:
            self.save(out)
        return out

    def save(self, path: str) -> str:
        """Write buffered events as Chrome trace JSON (tracer may still be
        active; events keep accumulating)."""
        with self._lock:
            doc = {
                "traceEvents": list(self._events),
                "displayTimeUnit": "ms",
                "otherData": {"producer": "repro.obs.trace",
                              "events_dropped": self.dropped,
                              "max_events": self._max_events},
            }
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(doc, f)
            f.write("\n")
        return path

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self.dropped = 0

    @property
    def events(self) -> list[dict]:
        """Snapshot of buffered events (tests/tooling)."""
        with self._lock:
            return list(self._events)

    # -- emission ----------------------------------------------------------

    def _enter_depth(self) -> int:
        d = getattr(self._local, "depth", 0)
        self._local.depth = d + 1
        return d

    def _exit_depth(self) -> None:
        self._local.depth = max(0, getattr(self._local, "depth", 1) - 1)

    def _append(self, ev: dict) -> None:
        dropped = False
        with self._lock:
            if len(self._events) == self._max_events:
                self.dropped += 1       # deque(maxlen) evicts the oldest
                dropped = True
            self._events.append(ev)
        if dropped:
            _metrics.counter(
                DROP_COUNTER_NAME,
                "trace events evicted from the bounded ring "
                "(PATHSIG_TRACE_MAX_EVENTS)").inc()

    def _emit(self, name, t0, t1, depth, args) -> None:
        fl = self._flight
        if fl is not None:
            fl.record_span(name, t0, t1, depth, args)
        if not self._active:
            return
        self._append({
            "name": name,
            "ph": "X",
            "ts": (t0 - self._epoch) * 1e6,
            "dur": (t1 - t0) * 1e6,
            "pid": _PID,
            "tid": threading.get_ident() & 0xFFFF,
            "args": {"depth": depth, **args},
        })

    def _emit_instant(self, name, args) -> None:
        fl = self._flight
        if fl is not None:
            fl.record_instant(name, args)
        if not self._active:
            return
        self._append({
            "name": name,
            "ph": "i",
            "s": "t",
            "ts": (time.perf_counter() - self._epoch) * 1e6,
            "pid": _PID,
            "tid": threading.get_ident() & 0xFFFF,
            "args": dict(args),
        })

    # -- user API ----------------------------------------------------------

    def span(self, name: str, **args):
        if self._record:
            return Span(self, name, args)
        return _Annotation(name, **args) if _profiling() else _NULL_SPAN

    def instant(self, name: str, **args) -> None:
        if not self._record:
            return
        self._emit_instant(name, args)


TRACER = Tracer()


def span(name: str, **args):
    """``with obs.span("kernels.signature", backend="pallas"):`` — the
    profiler's annotation alone when neither a trace nor the flight
    recorder is active, a null context manager when no profiler session
    runs either."""
    if TRACER._record:
        return Span(TRACER, name, args)
    return _Annotation(name, **args) if _profiling() else _NULL_SPAN


def instant(name: str, **args) -> None:
    TRACER.instant(name, **args)


def start_trace(path: str | None = None, *, reset: bool = True) -> None:
    TRACER.start(path, reset=reset)


def stop_trace(path: str | None = None) -> str | None:
    return TRACER.stop(path)


def trace_active() -> bool:
    return TRACER._active


class trace_scope:
    """``with obs.trace_scope("t.json"):`` — start on entry, stop+write on
    exit.  Used by tests."""

    def __init__(self, path: str | None = None):
        self._path = path

    def __enter__(self) -> Tracer:
        TRACER.start(self._path)
        return TRACER

    def __exit__(self, *exc):
        TRACER.stop()
        return False


_ENV_TRACE = os.environ.get("PATHSIG_TRACE", "").strip()
if _ENV_TRACE:
    TRACER.start(_ENV_TRACE)
    atexit.register(TRACER.stop)
