"""Unified telemetry subsystem (ISSUE 5): one registry, spans, exporters,
recompile watchdog.

The whole stack reports through this package:

* ``registry``  — counters / gauges / fixed-ladder histograms in ONE
  :class:`MetricsRegistry`; ``RateWindow`` (the shared windowed-rate
  plumbing) lives here too.
* ``peaks``     — the single roofline table (``PEAK_FLOPS`` /
  ``PEAK_HBM_BYTES``) ``training/metrics.py`` consumes.
* ``spans``     — monotonic-clock nested spans in a bounded ring with an
  optional JSONL sink, plus ``log_event`` (prefixed, attributable
  replacement for bare prints in multi-process paths).
* ``programs``  — the ``program`` record (ISSUE 34): which named scope
  each instruction of a compiled program came from, filed by the
  program's owner as a pinned record of its tracer, so any device
  profile sums by scope.
* ``export``    — Prometheus text exposition + strict parser, the
  versioned JSONL event schema, and the stdlib ``/metrics`` +
  ``/healthz`` HTTP server.
* ``watchdog``  — post-warmup recompile detection over the serving
  engine's compiled program families.
* ``tracing``   — request-scoped traces (ISSUE 10): a TraceContext
  minted at submit and propagated router → replica → scheduler, spans
  and emit events collected per request, sampled ``mingpt-trace/1``
  JSONL export with a strict loader.
* ``flightrec`` — bounded flight-recorder ring dumped atomically on
  crash / breaker trip / recompile / drain and via ``/debug/flight``.
* ``slo``       — graded SLO reports from exact per-request trace
  durations (not histogram-bucket upper bounds).

Process-wide defaults: :func:`get_registry` / :func:`get_tracer` are the
lazily-created singletons entry points (``train.py``, ``serve.py``) wire
into every logger so one scrape page exposes the whole process. Library
classes (``MetricsLogger``, ``ServingMetrics``) default to private
instances for test isolation — pass the globals explicitly to unify.
"""

from __future__ import annotations

from typing import Optional

from mingpt_distributed_tpu.telemetry.export import (
    SCHEMA_VERSION,
    JsonlEventSink,
    TelemetryServer,
    merge_fleet_pages,
    parse_prometheus,
    register_build_info,
    render_fleet_prometheus,
    render_prometheus,
)
from mingpt_distributed_tpu.telemetry.flightrec import (
    FLIGHT_SCHEMA,
    FlightRecorder,
    load_flight_dir,
    validate_flight_dump,
)
from mingpt_distributed_tpu.telemetry.peaks import (
    PEAK_FLOPS,
    PEAK_HBM_BYTES,
    peak_flops_per_chip,
    peak_hbm_bytes_per_chip,
)
from mingpt_distributed_tpu.telemetry.registry import (
    LATENCY_BUCKETS_S,
    Counter,
    Gauge,
    Histogram,
    MetricFamily,
    MetricsRegistry,
    RateWindow,
)
from mingpt_distributed_tpu.telemetry.slo import (
    SLO_SCHEMA,
    SLObjective,
    diff_slo_reports,
    evaluate_slos,
    exact_quantile,
    parse_slo_spec,
    render_slo_diff,
    render_slo_report,
)
from mingpt_distributed_tpu.telemetry.spans import (
    SpanTracer,
    log_event,
    process_index,
)
from mingpt_distributed_tpu.telemetry.tracing import (
    TRACE_SCHEMA,
    TraceContext,
    TraceRecorder,
    load_trace_jsonl,
    trace_baggage,
    trace_sink,
    validate_trace_records,
)
from mingpt_distributed_tpu.telemetry.watchdog import (
    RecompileError,
    RecompileWatchdog,
)

__all__ = [
    "FLIGHT_SCHEMA",
    "SCHEMA_VERSION",
    "SLO_SCHEMA",
    "TRACE_SCHEMA",
    "LATENCY_BUCKETS_S",
    "PEAK_FLOPS",
    "PEAK_HBM_BYTES",
    "Counter",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "JsonlEventSink",
    "MetricFamily",
    "MetricsRegistry",
    "RateWindow",
    "RecompileError",
    "RecompileWatchdog",
    "SLObjective",
    "SpanTracer",
    "TelemetryServer",
    "TraceContext",
    "TraceRecorder",
    "diff_slo_reports",
    "evaluate_slos",
    "exact_quantile",
    "get_registry",
    "get_tracer",
    "load_flight_dir",
    "load_trace_jsonl",
    "log_event",
    "merge_fleet_pages",
    "parse_prometheus",
    "parse_slo_spec",
    "peak_flops_per_chip",
    "peak_hbm_bytes_per_chip",
    "process_index",
    "register_build_info",
    "render_fleet_prometheus",
    "render_prometheus",
    "render_slo_diff",
    "render_slo_report",
    "trace_baggage",
    "trace_sink",
    "validate_trace_records",
]

_registry: Optional[MetricsRegistry] = None
_tracer: Optional[SpanTracer] = None


def get_registry() -> MetricsRegistry:
    """The process-wide registry every entry point exports from."""
    global _registry
    if _registry is None:
        _registry = MetricsRegistry()
    return _registry


def get_tracer() -> SpanTracer:
    """The process-wide tracer, gated to process 0 (single-writer, the
    same convention as MetricsLogger) — other processes get a disabled
    tracer whose spans are no-ops."""
    global _tracer
    if _tracer is None:
        _tracer = SpanTracer(enabled=process_index() == 0)
    return _tracer
