"""Unified telemetry: tracing spans, process counters, live metrics.

Three layers, one import (``from repro import obs``):

* :mod:`repro.obs.tracing` — ``obs.span("compile_graph", ...)`` context
  managers with thread-local nesting and a JSONL sink
  (``REPRO_TRACE=/path`` or ``tuning_config(trace_path=...)``), mirrored
  onto the profiler's host plane as ``TraceAnnotation`` events while
  a profile records;
* :mod:`repro.obs.recorders` — GC pauses and JAX compilations, counted
  while tracing is enabled (``obs.counters()``; compilations also as
  ``jax_compile`` spans);
* :mod:`repro.obs.metrics` — process-global counters / gauges /
  exponential-bucket histograms behind ``obs.metrics_snapshot()`` and a
  Prometheus-style ``obs.render_text()`` exporter.

stdlib-only on purpose: ``repro.core`` imports ``repro.obs``, never the
reverse, so instrumentation can sit in the lowest layers. Everything is
zero-cost when disabled — ``obs.span`` returns a shared no-op behind one
``obs.enabled()`` check, and only cold structural counters are always on.
"""

from repro.obs.tracing import (   # noqa: F401
    NOOP_SPAN,
    Span,
    TRACE_ENV,
    current_span,
    disable,
    drain,
    enable,
    enabled,
    record,
    restore,
    span,
    trace_path,
)
from repro.obs.recorders import counters, gc_by_generation   # noqa: F401
from repro.obs.metrics import (   # noqa: F401
    Counter,
    Gauge,
    Histogram,
    counter,
    gauge,
    histogram,
    metrics_clear,
    metrics_snapshot,
    parse_text,
    render_text,
)

__all__ = [
    "NOOP_SPAN", "Span", "TRACE_ENV", "current_span", "disable", "drain",
    "enable", "enabled", "record", "restore", "span", "trace_path",
    "counters", "gc_by_generation",
    "Counter", "Gauge", "Histogram", "counter", "gauge", "histogram",
    "metrics_clear", "metrics_snapshot", "parse_text", "render_text",
]
