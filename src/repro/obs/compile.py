"""Compile / retrace accounting and lowered-cost helpers.

The central trick: the Python body of a ``jax.jit``-wrapped function runs
exactly once per compiled variant (one trace per new static/shape
signature), so a counter incremented at the top of the jitted body *is* the
compile/retrace counter.  Two entry points use it:

- :func:`instrument_jit` — drop-in replacement for ``jax.jit(fn, **kw)``
  that wires the counting body in; labels the counter with a compact
  shape key of the offending call so a retrace storm names its cause.
- :func:`count_trace` — one line placed inside an already-jitted body
  (module-level kernels like ``sig_trunc``) when rebuilding the jit wrapper
  isn't practical.

Both route through the ``pathsig_jit_traces_total`` counter of the global
registry, labelled ``(site, shapes)``.  Tracing-time work is off the
execution hot path by construction — a trace happens once per variant —
so these are safe even at full metric volume.

Cost helpers (:func:`record_cost`, :func:`record_collectives`) publish
lowered-cost gauges from ``Compiled.cost_analysis()`` and collective
counters from :func:`repro.distributed.hlo.collective_stats`.  They compile
(AOT) on purpose, so they are opt-in: benchmarks and the observability
example call them; the dispatch hot path does not.
"""
from __future__ import annotations

import functools

from . import metrics

__all__ = [
    "shape_key", "count_trace", "instrument_jit", "record_cost",
    "record_collectives", "TRACE_COUNTER_NAME", "set_retrace_sink",
]

TRACE_COUNTER_NAME = "pathsig_jit_traces_total"

# repro.obs.flight mirror: (site, shape_key) per trace, fed even when the
# registry is disabled — compiles are rare, and the last-N retrace keys
# are the flight recorder's most useful breadcrumb
_RETRACE_SINK = None


def set_retrace_sink(fn) -> None:
    global _RETRACE_SINK
    _RETRACE_SINK = fn


def _trace_counter() -> metrics.Counter:
    return metrics.counter(
        TRACE_COUNTER_NAME,
        "jit traces (== compiles) per site, labelled with the shape key "
        "that caused the trace", ("site", "shapes"))


def shape_key(*xs, **kxs) -> str:
    """Compact, stable description of argument shapes/dtypes — the label a
    retrace counter carries so the offending signature is visible.

    Arrays render as ``f32[32,100,6]``; pytrees recurse; everything else
    falls back to ``repr`` truncated to keep label cardinality sane.
    """
    parts = [_describe(x) for x in xs]
    parts += [f"{k}={_describe(v)}" for k, v in sorted(kxs.items())]
    return ",".join(parts)


def _describe(x) -> str:
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return f"{_short_dtype(dtype)}[{','.join(map(str, shape))}]"
    if isinstance(x, (list, tuple)):
        inner = ",".join(_describe(v) for v in x[:4])
        if len(x) > 4:
            inner += ",..."
        return f"({inner})"
    if isinstance(x, dict):
        inner = ",".join(f"{k}:{_describe(v)}"
                         for k, v in sorted(x.items())[:4])
        return f"{{{inner}}}"
    r = repr(x)
    return r if len(r) <= 24 else r[:21] + "..."


def _short_dtype(dtype) -> str:
    s = str(dtype)
    return (s.replace("float", "f").replace("int", "i").replace("uint", "u")
            .replace("complex", "c").replace("bool", "pred"))


def count_trace(site: str, *xs, **kxs) -> None:
    """Tick the retrace counter for ``site``.  Call at the top of a jitted
    body: it runs once per compiled variant, so ticks == compiles.  No-op
    when metrics are disabled and no flight recorder is attached."""
    sink = _RETRACE_SINK
    if not metrics.REGISTRY._enabled and sink is None:
        return
    key = shape_key(*xs, **kxs)
    if sink is not None:
        sink(site, key)
    if metrics.REGISTRY._enabled:
        _trace_counter().inc(site=site, shapes=key)


def instrument_jit(fn, *, site: str, **jit_kw):
    """``jax.jit`` with retrace accounting: returns a jitted callable whose
    every trace ticks ``pathsig_jit_traces_total{site=...,shapes=...}``.

    The shape label is computed *inside* the traced body (from the tracers'
    abstract shapes), so it costs nothing per execution — only per compile.
    Static args configured via ``jit_kw`` pass through untouched.
    """
    import jax

    @functools.wraps(fn)
    def counted(*args, **kwargs):
        count_trace(site, *args, **kwargs)
        return fn(*args, **kwargs)

    return jax.jit(counted, **jit_kw)


def record_cost(site: str, fn, *args, **kwargs) -> dict:
    """AOT-lower ``fn(*args, **kwargs)`` and publish its lowered cost as
    gauges: ``pathsig_lowered_flops{site=}`` and
    ``pathsig_lowered_bytes{site=}``.  Returns the raw cost dict.

    Compiles (cached by jax's jit cache when fn is already jitted with the
    same signature) — opt-in for examples and tests, not the hot path.
    """
    import jax

    jfn = fn if hasattr(fn, "lower") else jax.jit(fn)
    try:
        compiled = jfn.lower(*args, **kwargs).compile()
        ca = compiled.cost_analysis() or {}
    except Exception:
        ca = {}
    flops = float(ca.get("flops", 0.0) or 0.0)
    nbytes = float(ca.get("bytes accessed", 0.0) or 0.0)
    metrics.gauge("pathsig_lowered_flops",
                  "XLA cost_analysis flops of the lowered computation",
                  ("site",)).set(flops, site=site)
    metrics.gauge("pathsig_lowered_bytes",
                  "XLA cost_analysis bytes accessed of the lowered "
                  "computation", ("site",)).set(nbytes, site=site)
    return {"flops": flops, "bytes": nbytes, "raw": ca}


def record_collectives(site: str, stats) -> None:
    """Publish a :class:`repro.distributed.hlo.CollectiveStats` (from
    ``collective_stats(hlo_text)``) as per-kind counters:
    ``pathsig_hlo_collectives_total{site=,kind=}`` plus wire-byte totals."""
    c = metrics.counter(
        "pathsig_hlo_collectives_total",
        "collective op count in lowered HLO", ("site", "kind"))
    b = metrics.counter(
        "pathsig_hlo_collective_wire_bytes_total",
        "wire bytes moved by collectives in lowered HLO", ("site", "kind"))
    for kind, (count, _result_bytes, wire_bytes) in stats.by_kind.items():
        c.inc(count, site=site, kind=kind)
        b.inc(wire_bytes, site=site, kind=kind)
