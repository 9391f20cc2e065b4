"""Unified telemetry: labeled metric registry, request-lifecycle span
tracer, the pump loops' phase clock, the device's completion clock, and
pluggable exporters (Chrome trace / Prometheus text).

Shared by the serving engine and the trainer (docs/11_observability.md):
``MetricRegistry`` is the one store every counter/gauge/histogram lives
in, ``Tracer`` records lifecycle spans on per-slot tracks, and the
exporters serialize both without touching instrumentation.

Since the fleet-tracing PR the layer also crosses processes:
``TraceContext`` travels in the ``X-TP-Trace`` header, ``SpanSpool``
appends each process's finished spans to a bounded JSONL span log, and
``stitch_traces`` rebases N processes' logs onto one clock and emits a
single Perfetto timeline with flow arrows across the wire crossings.
"""

from tpu_parallel.obs.device_clock import DeviceClock
from tpu_parallel.obs.exporters import (
    chrome_trace_events,
    parse_prometheus_text,
    prometheus_lines,
    prometheus_text,
    write_chrome_trace,
    write_prometheus,
)
from tpu_parallel.obs.phases import phase
from tpu_parallel.obs.registry import (
    Counter,
    Gauge,
    Histogram,
    HistogramWindow,
    MetricRegistry,
    PercentileWindow,
    validate_snapshot,
)
from tpu_parallel.obs.spool import SpanSpool, read_span_log
from tpu_parallel.obs.stitch import (
    clock_offsets,
    phase_breakdown,
    stitch_traces,
    trace_summary,
)
from tpu_parallel.obs.tracer import (
    NULL_SPAN,
    NULL_TRACER,
    TRACE_HEADER,
    NullTracer,
    Span,
    TraceContext,
    Tracer,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "HistogramWindow",
    "PercentileWindow",
    "MetricRegistry",
    "validate_snapshot",
    "Span",
    "Tracer",
    "TraceContext",
    "TRACE_HEADER",
    "NullTracer",
    "NULL_SPAN",
    "NULL_TRACER",
    "SpanSpool",
    "read_span_log",
    "clock_offsets",
    "stitch_traces",
    "trace_summary",
    "phase_breakdown",
    "chrome_trace_events",
    "write_chrome_trace",
    "prometheus_lines",
    "prometheus_text",
    "parse_prometheus_text",
    "write_prometheus",
    "phase",
    "DeviceClock",
]
