"""Unified telemetry subsystem (DESIGN.md §Observability).

Three planes behind one sink API:

* metrics — in-graph `MetricStream` ring buffer accumulated inside the
  jit'd train step, drained to host asynchronously every `flush_every`
  steps (`TrainTelemetry`); integer per-expert load histograms, MaxVio,
  BIP dual health, dispatch stats, guard events.
* tracing — `named_span` (jax.named_scope, in-graph) / `trace_span`
  (profiler annotation, host-side) + `Profiler` windows for `--profile N:M`.
* serving SLOs — `ServingTelemetry` streaming TTFT / inter-token-latency /
  queue-wait histograms, per-expert live load, shed/deadline counters.

`metrics_report` renders a sink file on the terminal or as HTML.
"""
from repro.telemetry.metrics import (
    LOAD_HIST_KEYS,
    MetricSeries,
    MetricStream,
    TrainTelemetry,
)
from repro.telemetry.sinks import (
    CSVSink,
    JSONLSink,
    MemorySink,
    Sink,
    open_sink,
)
from repro.telemetry.slo import ServingTelemetry, StreamingHistogram
from repro.telemetry.trace import Profiler, named_span, profile_window, trace_span

__all__ = [
    "CSVSink",
    "JSONLSink",
    "LOAD_HIST_KEYS",
    "MemorySink",
    "MetricSeries",
    "MetricStream",
    "Profiler",
    "ServingTelemetry",
    "Sink",
    "StreamingHistogram",
    "TrainTelemetry",
    "named_span",
    "open_sink",
    "profile_window",
    "trace_span",
]
