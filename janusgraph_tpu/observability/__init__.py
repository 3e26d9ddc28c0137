"""Unified telemetry: histogram metrics, structured spans, exposition.

The subsystem the reference spreads across its Dropwizard stack
(reference: util/stats/MetricManager.java:36 registry singleton,
MetricInstrumentedStore.java per-store timers, per-tx metric groups
StandardJanusGraphTx.java:258-262, reporters
GraphDatabaseConfiguration.java:1012-1094) plus what it does NOT have —
a span tracer and OLAP superstep telemetry for the TPU path (compile vs
execute split, retraces, transfer bytes, frontier occupancy, ELL pad
waste), the quantities that actually dominate graph-engine performance
(PAPERS.md: arxiv 2011.08451 propagation blocking, 2108.11521 on-chip
communication for graph analytics).

Layout:

- ``metrics_core``: :class:`Counter`, :class:`Timer`, :class:`Histogram`,
  :class:`Gauge`, and :class:`TelemetryRegistry` — the registry that
  ``janusgraph_tpu.util.metrics`` re-exports as its ``metrics`` singleton
  (absorbed from the old ``MetricManager``).
- ``spans``: context-var tracer with parent/child nesting and the
  always-on slow-op ring buffer.
- ``exposition``: Prometheus-text and JSON snapshot renderers served at
  ``GET /metrics`` / ``GET /telemetry`` and by
  ``python -m janusgraph_tpu telemetry``.

Recording is HOST-ONLY by contract: no metric or span call may run inside
jit-traced code (it would record once per compile, not per execution, and
coercing tracer attribute values forces a device sync). graphlint rule
JG106 enforces this mechanically.
"""

from janusgraph_tpu.observability.continuous import (
    BundleWriter,
    InstrumentedLock,
    SamplingProfiler,
    StallWatchdog,
    bundle_writer,
    flame_from_artifact,
    flamediff,
    sampling_profiler,
    watchdog,
)
from janusgraph_tpu.observability.exposition import (
    json_snapshot,
    prometheus_text,
)
from janusgraph_tpu.observability.federation import (
    ClockOffsets,
    FleetBundleStore,
    FleetFederation,
    FleetHistory,
    fleet_default_specs,
    merge_incident_events,
    merge_series,
    merge_windows,
)
from janusgraph_tpu.observability.flight import FlightRecorder
from janusgraph_tpu.observability.flight import recorder as flight_recorder
from janusgraph_tpu.observability.identity import (
    replica_name,
    set_replica,
)
from janusgraph_tpu.observability.logging import (
    StructuredLogger,
    get_logger,
)
from janusgraph_tpu.observability.metrics_core import (
    BUCKET_BOUNDS,
    Counter,
    Gauge,
    Histogram,
    TelemetryRegistry,
    Timer,
)
from janusgraph_tpu.observability.profiler import (
    DigestTable,
    ResourceLedger,
    accrue,
    accrue_wall,
    current_ledger,
    digest_table,
    flame_lines,
    ledger_scope,
)
from janusgraph_tpu.observability.slo import (
    SLOEngine,
    SLOSpec,
    slo_engine,
)
from janusgraph_tpu.observability.spans import (
    Span,
    TraceContext,
    Tracer,
    capture_scope,
    tracer,
)
from janusgraph_tpu.observability.stream import (
    STREAMS,
    Subscription,
    TelemetryBus,
    telemetry_bus,
)
from janusgraph_tpu.observability.timeline import (
    chrome_trace,
    render_run,
)
from janusgraph_tpu.observability.timeseries import (
    MetricsHistory,
    history,
)

#: process-wide registry (reference: MetricManager.INSTANCE);
#: `janusgraph_tpu.util.metrics.metrics` is THIS object
registry = TelemetryRegistry()

#: convenience alias: `with span("name", attr=...):` on the global tracer
span = tracer.span

# phases put their self time into this registry (timers `phase.<name>`)
tracer.registry = registry


def _slow_span_to_flight(event: dict) -> None:
    # the query digest (annotated onto the span by traversal execution)
    # rides along so recurring slow shapes group instead of appearing as
    # one-off offenders
    flight_recorder.record(
        "slow_span",
        name=event["name"],
        ms=event["ms"],
        trace_id=event.get("trace_id"),
        span_id=event.get("span_id"),
        digest=event.get("attrs", {}).get("digest"),
    )


# every span crossing the slow-op threshold also lands in the black box
tracer.on_slow = _slow_span_to_flight

__all__ = [
    "BUCKET_BOUNDS",
    "BundleWriter",
    "ClockOffsets",
    "Counter",
    "DigestTable",
    "FleetBundleStore",
    "FleetFederation",
    "FleetHistory",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "InstrumentedLock",
    "MetricsHistory",
    "ResourceLedger",
    "SLOEngine",
    "SLOSpec",
    "STREAMS",
    "SamplingProfiler",
    "Span",
    "StallWatchdog",
    "StructuredLogger",
    "Subscription",
    "TelemetryBus",
    "TelemetryRegistry",
    "Timer",
    "TraceContext",
    "Tracer",
    "accrue",
    "accrue_wall",
    "bundle_writer",
    "capture_scope",
    "chrome_trace",
    "current_ledger",
    "digest_table",
    "flame_from_artifact",
    "flame_lines",
    "flamediff",
    "fleet_default_specs",
    "flight_recorder",
    "get_logger",
    "history",
    "json_snapshot",
    "ledger_scope",
    "merge_incident_events",
    "merge_series",
    "merge_windows",
    "prometheus_text",
    "registry",
    "render_run",
    "replica_name",
    "sampling_profiler",
    "set_replica",
    "slo_engine",
    "span",
    "telemetry_bus",
    "tracer",
    "watchdog",
]
