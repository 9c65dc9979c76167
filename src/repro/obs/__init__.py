"""Structured observability for the platform: tracing, metrics, export.

Three pieces, deliberately dependency-free (stdlib only) so every layer
of the platform — client, driver protocol, isolation supervisor, chaos
controller, serving routers — can emit telemetry without import cycles:

- ``obs.trace``  — hierarchical spans with ``(job, attempt, span)`` ids
  and a pluggable clock (wall or the concurrency harness's virtual
  clock), mergeable across the process-isolation boundary.
- ``obs.metrics`` — a lock-safe counter/gauge/histogram registry
  snapshotted into ``JobReport.metrics`` and the platform wait result.
- ``obs.export`` — JSONL dump, Chrome ``trace_event`` conversion
  (Perfetto-loadable), and a per-stage p50/p99 text report.
"""

from repro.obs.trace import CHILD_SPAN_BASE, Span, Tracer, annotate
from repro.obs.metrics import MetricsRegistry, stage_summary
from repro.obs.export import (
    read_jsonl,
    text_report,
    to_chrome_trace,
    validate_chrome,
    write_jsonl,
)

__all__ = [
    "CHILD_SPAN_BASE",
    "Span",
    "Tracer",
    "annotate",
    "MetricsRegistry",
    "stage_summary",
    "read_jsonl",
    "text_report",
    "to_chrome_trace",
    "validate_chrome",
    "write_jsonl",
]
