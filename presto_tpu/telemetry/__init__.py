"""Telemetry export: OTLP-shaped span/metric export, the query history
store, and per-query device profiler capture.

This package is the boundary where in-process observability (the PR 9
Tracer spans, the exchange/fabric/serving/storage metric registries,
terminal QueryInfo snapshots) leaves the worker process — the analog of
the reference's OpenTelemetry TracerProvider plugin, event-listener
shipping of QueryCompletedEvents, and ClusterStatsResource.

Layers:

  * otlp.py     — pure conversion: Tracer span trees -> OTLP
                  `resourceSpans`, metric registry snapshots -> OTLP
                  `resourceMetrics`.  Trace ids derive from the
                  X-Presto-Trace-Token so coordinator and worker spans
                  stitch into ONE distributed trace.
  * export.py   — the pipeline: bounded queue + background flush thread
                  with the PR 2 jittered-backoff error budget, pluggable
                  sinks (JSONL file / HTTP OTLP-JSON / in-process
                  collector), drop/flush/retry counters.
  * history.py  — retention-bounded JSONL query history store (count +
                  age limits, reload across worker restarts).
  * profiler.py — `profile` session property: wrap one query's execution
                  in jax.profiler.trace() writing a per-query directory.
  * jax_events.py — JAX's trace / lower / compile / cache events,
                  attributed to the query or task whose thread caused
                  them, and the per-program process table.
  * gaps.py     — a capture reduced over the program's `presto:` spans:
                  which span was innermost while the device idled.
"""
from .otlp import (trace_id_for, span_id_for, spans_to_resource_spans,
                   metrics_to_resource_metrics, scrape_metric_points)
from .export import (TelemetrySink, CollectorSink, JsonlFileSink,
                     HttpOtlpSink, TelemetryExporter, make_sink,
                     set_process_exporter, get_process_exporter)
from .history import QueryHistoryStore, HistoryEventListener
from . import gaps
from .profiler import profile_capture

__all__ = [
    "trace_id_for", "span_id_for", "spans_to_resource_spans",
    "metrics_to_resource_metrics", "scrape_metric_points",
    "TelemetrySink", "CollectorSink", "JsonlFileSink", "HttpOtlpSink",
    "TelemetryExporter", "make_sink",
    "set_process_exporter", "get_process_exporter",
    "QueryHistoryStore", "HistoryEventListener",
    "profile_capture", "gaps",
]
