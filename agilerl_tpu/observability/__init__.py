"""Unified telemetry: metrics registry, JSONL events, step/MFU timelines,
evolution lineage, serving latency histograms, distributed tracing, and the
cross-process telemetry plane (see docs/observability.md)."""

from agilerl_tpu.observability.events import (
    JsonlSink,
    MemorySink,
    NullSink,
    read_jsonl,
)
from agilerl_tpu.observability.export import (
    TelemetryAggregator,
    TelemetryPublisher,
    TelemetrySchemaError,
    merge_histogram_dumps,
)
from agilerl_tpu.observability.facade import (
    RunTelemetry,
    get_registry,
    init_run_telemetry,
    warn_once,
)
from agilerl_tpu.observability.lineage import LineageTracker
from agilerl_tpu.observability.slo import (
    AlertPolicy,
    Objective,
    SLOEvaluator,
    SLOSpec,
    aligned_buckets,
    attribute_scale_ups,
    load_slo_spec,
    registry_source,
    save_slo_spec,
    write_report,
)
from agilerl_tpu.observability.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from agilerl_tpu.observability.timeline import (
    PROFILER_PREFIX,
    PhaseTimer,
    StepTimeline,
    device_memory_stats,
    device_scope,
)
from agilerl_tpu.observability.trace import (
    Span,
    SpanContext,
    Tracer,
    configure_tracer,
    current_span,
    export_perfetto,
    get_tracer,
    set_tracer,
    span_records,
    trace_tree,
)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "JsonlSink", "MemorySink", "NullSink", "read_jsonl",
    "StepTimeline", "PhaseTimer", "PROFILER_PREFIX", "device_memory_stats",
    "device_scope",
    "LineageTracker",
    "RunTelemetry", "init_run_telemetry", "get_registry", "warn_once",
    "Tracer", "Span", "SpanContext", "get_tracer", "set_tracer",
    "configure_tracer", "current_span", "export_perfetto", "span_records",
    "trace_tree",
    "TelemetryPublisher", "TelemetryAggregator", "TelemetrySchemaError",
    "merge_histogram_dumps",
    "SLOSpec", "Objective", "AlertPolicy", "SLOEvaluator",
    "load_slo_spec", "save_slo_spec", "aligned_buckets",
    "attribute_scale_ups", "registry_source", "write_report",
]
