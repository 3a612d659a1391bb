"""Observability subsystem: metrics registry, federation spans, run telemetry.

The capability SURVEY.md §5 calls out as missing from the reference (whose only
instrument is a wall-time decorator), built natively: a zero-dependency, thread-safe
:class:`MetricsRegistry` (counters / gauges / histograms with labels, Prometheus text
exposition — served at ``GET /metrics`` by ``communication.http_server``), a nestable
:class:`SpanTracer` for the federation loop's phase structure (round → cohort-sample →
local-train → aggregate → publish; JSONL + Chrome-trace export, composing with the
device captures from ``utils.profiling.trace``), and :class:`RunTelemetry`, the per-run
``telemetry.jsonl`` artifact both coordinators write.

The compiled-program cost layer (:mod:`nanofed_tpu.observability.profiling`) adds
what the wall-clock layers cannot: XLA's own ``cost_analysis()`` /
``memory_analysis()`` of every round program, rooflined against per-platform
peaks into a :class:`ProgramCostReport`, catalogued per process by
:class:`ProgramCatalog`, and surfaced as ``nanofed_program_*`` gauges,
``program_profile`` telemetry records, and the ``nanofed-tpu profile``
subcommand.

The distributed-tracing layer (:mod:`nanofed_tpu.observability.tracing` +
:mod:`nanofed_tpu.observability.critical_path`) connects the per-process
streams into one story: W3C-style trace contexts ride the ``X-NanoFed-Trace``
header from the submitting client through decode and ingest into the round
that consumes the submit; per-host telemetry streams merge — clock-aligned at
the bring-up-barrier epoch — into a host-laned Chrome/Perfetto timeline with a
per-round critical-path decomposition
(``nanofed_round_critical_path_seconds{segment}``); and a bounded
:class:`FlightRecorder` ring, dumped by the multihost supervisor on reap of a
crashed host, decomposes MTTR into named phases.

See ``docs/observability.md`` for the span taxonomy, metric inventory, and how to
scrape ``/metrics`` or read ``telemetry.jsonl``.
"""

from nanofed_tpu.observability.critical_path import (
    CRITICAL_PATH_HISTOGRAM,
    CRITICAL_PATH_SEGMENTS,
    SYNC_LOOP_SEGMENTS,
    clock_offsets,
    critical_path_rounds,
    federation_timeline,
    load_host_streams,
    merge_timeline,
    resolve_traces,
    segment_digest,
)
from nanofed_tpu.observability.profiling import (
    PlatformPeaks,
    ProgramCatalog,
    ProgramCostReport,
    format_cost_table,
    peaks_for_device_kind,
    profile_program,
    update_device_occupancy,
)
from nanofed_tpu.observability.registry import (
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
)
from nanofed_tpu.observability.spans import (
    SPAN_HISTOGRAM,
    SpanRecord,
    SpanTiming,
    SpanTracer,
)
from nanofed_tpu.observability.telemetry import (
    TELEMETRY_FILENAME,
    RunTelemetry,
    find_latest_telemetry,
    install_jax_event_bridge,
    summarize_telemetry,
)
from nanofed_tpu.observability.tracing import (
    FLIGHT_RECORDER_FILENAME,
    TRACE_VERSION,
    FlightRecorder,
    TraceContext,
    forensic_now,
    mttr_decomposition,
    new_trace,
    parse_trace,
)

__all__ = [
    "CRITICAL_PATH_HISTOGRAM",
    "CRITICAL_PATH_SEGMENTS",
    "Counter",
    "DEFAULT_BUCKETS",
    "FLIGHT_RECORDER_FILENAME",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "PlatformPeaks",
    "ProgramCatalog",
    "ProgramCostReport",
    "RunTelemetry",
    "SPAN_HISTOGRAM",
    "SYNC_LOOP_SEGMENTS",
    "SpanRecord",
    "SpanTiming",
    "SpanTracer",
    "TELEMETRY_FILENAME",
    "TRACE_VERSION",
    "TraceContext",
    "clock_offsets",
    "critical_path_rounds",
    "federation_timeline",
    "find_latest_telemetry",
    "forensic_now",
    "format_cost_table",
    "get_registry",
    "install_jax_event_bridge",
    "load_host_streams",
    "merge_timeline",
    "mttr_decomposition",
    "new_trace",
    "parse_trace",
    "peaks_for_device_kind",
    "profile_program",
    "resolve_traces",
    "segment_digest",
    "summarize_telemetry",
    "update_device_occupancy",
]
