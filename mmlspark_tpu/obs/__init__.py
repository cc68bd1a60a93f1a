"""Runtime telemetry: metrics registry, span tracing, Prometheus exposition.

The reference ships per-stage StopWatch timers and VW TrainingStats
DataFrames; production visibility there came from Spark's own metrics
system. This package is the TPU rebuild's equivalent substrate — a
dependency-free (stdlib-only: it never imports jax) telemetry layer every subsystem reports into:

- :class:`MetricsRegistry` — process-wide counters, gauges and
  fixed-bucket histograms with labels; thread-safe; snapshot +
  Prometheus text exposition v0.0.4 (:func:`render`); scrape-side
  :func:`parse_text` for the fleet aggregator.
- :func:`span` / :func:`record_span` — host-side tracing with trace-id
  propagation (the gateway stamps :data:`TRACE_HEADER` into forwarded
  requests; workers continue the trace). Spans export to the registry
  (``mmlspark_trace_span_seconds`` latency histograms) and to the process
  span buffer (``GET /traces``); ``core.profiling.trace`` writes the
  buffered spans beside a device capture, on the capture's epoch clock.

Metric names follow ``mmlspark_<subsystem>_<name>_<unit>`` — enforced by
``tools/lint_metric_names.py``. Catalogue: docs/observability.md.

Hot-path contract: every instrument op on a disabled registry
(:func:`set_enabled`\\ (False)) returns after one attribute read — the
serving path's full per-request instrumentation costs < 1 µs
(asserted in tests/test_obs.py).
"""

from mmlspark_tpu.obs.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    REGISTRY,
    counter,
    gauge,
    histogram,
    parse_text,
    render,
    sum_samples,
)
from mmlspark_tpu.obs.tracing import (
    BUFFER,
    PARENT_HEADER,
    Span,
    SpanBuffer,
    TRACE_HEADER,
    clear_recent_spans,
    current_span_id,
    current_trace_id,
    new_span_id,
    new_trace_id,
    process_label,
    recent_spans,
    record_span,
    render_traces,
    set_process_label,
    span,
    traces_payload,
)


def set_enabled(on: bool) -> None:
    """Enable/disable the process-wide default registry (and with it span
    recording). Disabled instruments are ~free (< 1 µs for a whole
    request's worth of calls)."""
    REGISTRY.enabled = bool(on)


def enabled() -> bool:
    return REGISTRY.enabled


def reset() -> None:
    """Zero every metric in the default registry IN PLACE (children stay
    bound — call sites pre-resolve label children for hot-path speed) and
    drop recorded spans, flight records, profiler aggregates and watchdog
    counters. Test isolation helper."""
    import sys as _sys

    from mmlspark_tpu.obs import flightrec

    REGISTRY.reset()
    clear_recent_spans()
    flightrec.FLIGHT.clear()
    # prof/watchdog state only if those modules were actually imported —
    # reset() must not drag them (and core.faults) into every test
    prof_mod = _sys.modules.get("mmlspark_tpu.obs.prof")
    if prof_mod is not None:
        prof_mod.PROFILER.reset()
    wd_mod = _sys.modules.get("mmlspark_tpu.obs.watchdog")
    if wd_mod is not None:
        wd_mod.WATCHDOG.reset()


__all__ = [
    "BUFFER",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "PARENT_HEADER",
    "REGISTRY",
    "Span",
    "SpanBuffer",
    "TRACE_HEADER",
    "clear_recent_spans",
    "counter",
    "current_span_id",
    "current_trace_id",
    "enabled",
    "gauge",
    "histogram",
    "new_span_id",
    "new_trace_id",
    "parse_text",
    "process_label",
    "recent_spans",
    "record_span",
    "render",
    "render_traces",
    "reset",
    "set_enabled",
    "set_process_label",
    "span",
    "sum_samples",
    "traces_payload",
]
