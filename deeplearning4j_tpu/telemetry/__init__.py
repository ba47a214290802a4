"""Unified telemetry: structured spans, runtime counters, and
recompile/host-sync detectors across training, the input pipeline, and
serving.

One process-wide, thread-safe :class:`MetricsRegistry` (counters, gauges,
histograms with p50/p95/p99) plus :func:`span` — a context manager
producing structured, nested spans exported as Chrome-trace JSON
(Perfetto-loadable, ``write_chrome_trace``), a Prometheus-style text dump
(``to_prometheus_text``) and a bridge into the existing StatsStorage /
dashboard SPI (``publish``). JAX-native signal capture attributes backend
compiles to the active span (:class:`RecompileDetector`), flags
accidental device->host readbacks (:class:`HostSyncDetector`) and
snapshots device memory watermarks (:func:`device_memory_gauges`).

Built-in instrumentation reports here from ``Solver``/``MultiLayerNetwork``
/``ComputationGraph.fit`` (fit/epoch/window/dispatch spans),
``DevicePrefetchIterator`` (queue depth, ship latency, stall time),
``ParallelWrapper``, ``PerformanceListener`` and the ``serving/`` engine —
disable it all with ``get_registry().enabled = False`` (a near-no-op).
"""
from .flightrec import (FlightRecorder, configure_flight_recorder,
                        get_flight_recorder, set_flight_recorder)
from .jaxsignals import (HostSyncDetector, HostSyncError, RecompileDetector,
                         device_memory_gauges, ensure_monitoring_hook,
                         xla_cache_hit_count, xla_compile_count)
from .perf import (ProgramCostIndex, StepAccounting, classify_roofline,
                   get_cost_index, implied_mfu, normalize_cost_analysis,
                   perf_snapshot, set_cost_index, write_perf_dump)
from .registry import (Counter, Gauge, Histogram, HistogramLadderMismatch,
                       MetricsRegistry, bucket_quantile, get_registry,
                       merge_cumulative_buckets, set_registry)
from .slo import (ErrorRateSLO, LatencySLO, SLOWatchdog, ThroughputSLO,
                  TrainingWatch, get_slo_watchdog, get_training_watch,
                  set_slo_watchdog, set_training_watch)
from .spans import (Span, current_span, current_span_path,
                    record_external_span, span)
from .spool import TraceSpool, read_spool
from .tracecontext import (TraceContext, adopt, current_trace_context,
                           current_trace_id, event, handoff,
                           new_trace_context, normalize_trace_id,
                           use_trace_context)

__all__ = [
    "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "HistogramLadderMismatch", "bucket_quantile",
    "merge_cumulative_buckets",
    "get_registry", "set_registry",
    "TraceSpool", "read_spool",
    "Span", "span", "current_span", "current_span_path",
    "record_external_span",
    "TraceContext", "new_trace_context", "normalize_trace_id",
    "current_trace_context", "current_trace_id", "use_trace_context",
    "handoff", "adopt", "event",
    "FlightRecorder", "get_flight_recorder", "set_flight_recorder",
    "configure_flight_recorder",
    "SLOWatchdog", "LatencySLO", "ErrorRateSLO", "ThroughputSLO",
    "get_slo_watchdog", "set_slo_watchdog",
    "ProgramCostIndex", "StepAccounting",
    "get_cost_index", "set_cost_index", "perf_snapshot", "write_perf_dump",
    "implied_mfu", "classify_roofline", "normalize_cost_analysis",
    "TrainingWatch", "get_training_watch", "set_training_watch",
    "RecompileDetector", "HostSyncDetector", "HostSyncError",
    "device_memory_gauges", "xla_compile_count", "xla_cache_hit_count",
    "ensure_monitoring_hook",
    "reset",
]


def reset() -> None:
    """Clear the global registry's metrics and trace buffer (tests /
    between runs). The enabled flag is preserved."""
    get_registry().reset()
