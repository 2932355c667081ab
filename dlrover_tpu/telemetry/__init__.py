"""dlrover_tpu.telemetry — unified observability substrate.

Three planes, one package (see docs/observability.md):

  metrics   lock-light registry (counters/gauges/histograms) with
            Prometheus text exposition (``exporter``)
  events    append-only JSONL lifecycle timeline; MTTR and recovery
            counts are DERIVED from it (``mttr``, the CLI)
  tracing   host spans as events of the profiler's own trace
            (``jax.profiler.TraceAnnotation``), read in the dump of
            the executor's ``jax.profiler`` window

All metric/event/span names live in ``names`` (enforced by lint rule
DLR007).
"""

from dlrover_tpu.telemetry import names
from dlrover_tpu.telemetry.events import (
    emit_event,
    read_events,
    recent_events,
)
from dlrover_tpu.telemetry.metrics import (
    DURATION_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    NullRegistry,
    get_registry,
    process_registry,
)
from dlrover_tpu.telemetry.correlate import (
    export_merged_trace,
    incident_records,
)
from dlrover_tpu.telemetry.goodput import derive_goodput
from dlrover_tpu.telemetry.mttr import derive_incidents, mttr_report
from dlrover_tpu.telemetry.names import EventKind, SpanName
from dlrover_tpu.telemetry.trace_context import (
    current_trace_id,
    new_trace_id,
    trace_scope,
)
from dlrover_tpu.telemetry.tracing import span

__all__ = [
    "names",
    "EventKind",
    "SpanName",
    "emit_event",
    "read_events",
    "recent_events",
    "DURATION_BUCKETS",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NullRegistry",
    "get_registry",
    "process_registry",
    "derive_incidents",
    "mttr_report",
    "derive_goodput",
    "export_merged_trace",
    "incident_records",
    "current_trace_id",
    "new_trace_id",
    "trace_scope",
    "span",
]
