"""repro.obs — process-wide observability: metrics, tracing, and
compile/retrace accounting.

Five modules, one import surface::

    from repro import obs

    obs.enable()                                  # or PATHSIG_METRICS=1
    obs.counter("my_events_total").inc()
    with obs.span("train.phase", n=3):            # PATHSIG_TRACE=t.json
        ...
    print(obs.to_prometheus())

- :mod:`repro.obs.metrics` — counters / gauges / histograms with label
  sets; JSON snapshot, JSONL append, Prometheus text exporters; pull
  collectors.  Near-zero overhead when disabled (one flag check).
- :mod:`repro.obs.trace` — span tracer exporting Chrome-trace/Perfetto
  JSON; every span is also a ``jax.profiler`` host annotation, so it
  shows in any profiler trace; the program's device scope names
  (``DEVICE_SCOPES``).
- :mod:`repro.obs.compile` — jit compile/retrace counters labelled with
  offending shape keys (:func:`instrument_jit`, :func:`count_trace`),
  lowered-cost and HLO-collective recording.
- :mod:`repro.obs.slo` — declarative SLOs over snapshots / value dicts /
  JSONL run logs; backs ``SessionStore.health()``,
  ``DynamicBatcher.health()`` and the train loop's ``slo_callback``.
- :mod:`repro.obs.flight` — always-on crash flight recorder: a bounded
  ring of recent spans/instants/metric deltas + last-N retrace keys,
  dumped as Chrome-trace JSON on boundary exceptions, SIGUSR2, or
  :func:`flight.dump` (``PATHSIG_FLIGHT=off`` disables).

This package imports nothing from the rest of ``repro`` — every layer
(core, kernels, serve, train) imports *it*.
"""
from . import slo
from .compile import (TRACE_COUNTER_NAME, count_trace, instrument_jit,
                      record_collectives, record_cost, set_retrace_sink,
                      shape_key)
from .flight import (FLIGHT, FlightRecorder, disable_flight, dump_on_error,
                     enable_flight, flight_active)
from .metrics import (DEFAULT_BUCKETS, DEFAULT_MAX_LABEL_SETS, REGISTRY,
                      Counter, Gauge, Histogram, Registry, append_jsonl,
                      counter, disable, enable, enabled, enabled_scope,
                      gauge, histogram, jsonl_sink, register_collector,
                      reset, set_flight_sink, snapshot, to_prometheus,
                      write_snapshot)
from .slo import (Slo, SloBreach, SloResult, batcher_slos, default_slos,
                  evaluate_log, evaluate_snapshot, evaluate_values,
                  session_slos, train_slos)
from .trace import (TRACER, Tracer, instant, span, start_trace, stop_trace,
                    trace_active, trace_scope)

__all__ = [
    # metrics
    "Counter", "Gauge", "Histogram", "Registry", "REGISTRY",
    "DEFAULT_BUCKETS", "DEFAULT_MAX_LABEL_SETS", "counter", "gauge",
    "histogram", "enable", "disable", "enabled", "enabled_scope", "reset",
    "snapshot", "to_prometheus", "write_snapshot", "append_jsonl",
    "register_collector", "jsonl_sink", "set_flight_sink",
    # trace
    "Tracer", "TRACER", "span", "instant", "start_trace",
    "stop_trace", "trace_active", "trace_scope",
    # compile accounting
    "TRACE_COUNTER_NAME", "shape_key", "count_trace", "instrument_jit",
    "record_cost", "record_collectives", "set_retrace_sink",
    # decision layer (PR 9)
    "slo", "Slo", "SloResult", "SloBreach", "evaluate_values",
    "evaluate_snapshot", "evaluate_log", "default_slos", "session_slos",
    "batcher_slos", "train_slos",
    # flight recorder
    "FLIGHT", "FlightRecorder", "enable_flight", "disable_flight",
    "flight_active", "dump_on_error",
]
