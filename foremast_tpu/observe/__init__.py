"""Observability: spans, gauge export, structured logs."""

from foremast_tpu.observe.gauges import (
    BrainGauges,
    WorkerMetrics,
    make_verdict_hook,
    start_metrics_server,
)
from foremast_tpu.observe.logs import JsonFormatter, ctx_log, setup_logging
from foremast_tpu.observe.spans import (
    Span,
    SpanRing,
    Tracer,
    counter,
    current_span,
    span,
    start_observe_server,
)

__all__ = [
    "BrainGauges",
    "WorkerMetrics",
    "make_verdict_hook",
    "start_metrics_server",
    "JsonFormatter",
    "ctx_log",
    "setup_logging",
    "Span",
    "SpanRing",
    "Tracer",
    "counter",
    "current_span",
    "span",
    "start_observe_server",
]
