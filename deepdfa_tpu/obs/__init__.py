"""One telemetry plane: request/step tracing (W3C ``traceparent``,
Chrome trace-event export), the shared Prometheus-exposition metrics
registry, training-step timelines and the cadence of completed steps, the
score-drift sentinel — and the
verdict layer on top of it: the perf-regression ledger, the SLO
burn-rate engine, and the crash flight recorder."""

from deepdfa_tpu.obs.cadence import interval_stats, step_cadence
from deepdfa_tpu.obs.drift import ScoreDriftSentinel, psi
from deepdfa_tpu.obs.flightrec import FlightRecorder, install_sigusr2
from deepdfa_tpu.obs.ledger import Ledger, LedgerEntry, LedgerStore
from deepdfa_tpu.obs.registry import Family, MetricsRegistry, escape_label_value
from deepdfa_tpu.obs.slo import (
    SLOEngine,
    SLOSpec,
    federation_specs,
    router_specs,
    serve_specs,
    train_specs,
    write_alerts_artifact,
)
from deepdfa_tpu.obs.telemetry import (
    TelemetryServer,
    TrainTelemetry,
    train_telemetry,
)
from deepdfa_tpu.obs.tracing import (
    Span,
    SpanContext,
    Tracer,
    chrome_trace,
    load_trace_records,
    new_span_id,
    new_trace_id,
    parse_traceparent,
)

__all__ = [
    "Family",
    "FlightRecorder",
    "Ledger",
    "LedgerEntry",
    "LedgerStore",
    "MetricsRegistry",
    "SLOEngine",
    "SLOSpec",
    "ScoreDriftSentinel",
    "Span",
    "SpanContext",
    "TelemetryServer",
    "Tracer",
    "TrainTelemetry",
    "chrome_trace",
    "escape_label_value",
    "federation_specs",
    "install_sigusr2",
    "interval_stats",
    "load_trace_records",
    "new_span_id",
    "new_trace_id",
    "parse_traceparent",
    "psi",
    "router_specs",
    "serve_specs",
    "step_cadence",
    "train_specs",
    "train_telemetry",
    "write_alerts_artifact",
]
