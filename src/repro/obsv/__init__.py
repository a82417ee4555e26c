"""Unified observability: tracing, metrics, and typed telemetry reports.

Three pieces, one import surface (``from repro import obsv``):

* **Tracing** (``obsv.trace``): per-query span trees on the monotonic
  clock, Chrome/Perfetto-exportable, zero-cost when no tracer is
  installed; live spans also appear in a ``jax.profiler`` trace.
  Instrumented layers call ``obsv.span("enum.count", ...)``; callers opt
  in with ``with obsv.tracing() as tracer: ...``.
* **Metrics** (``obsv.metrics``): counters / gauges / exponential-bucket
  histograms in a ``MetricsRegistry``, rendered in Prometheus exposition
  format and validated by the in-repo ``parse_prometheus`` checker.
* **Reports** (``obsv.reports``): the typed, versioned schema of record
  for every ``QueryStats.extras`` key — Mapping-compatible dataclasses
  validated at each producer's exit path.

See docs/OBSERVABILITY.md for the span taxonomy, metric names, and
scrape/viewer howtos.
"""

from repro.obsv.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    parse_prometheus,
)
from repro.obsv.reports import (
    SCHEMA_VERSION,
    BatchReport,
    EnumLevel,
    EnumReport,
    OocReport,
    PlanReport,
    Report,
    ServiceReport,
    validate_extras,
)
from repro.obsv.trace import (
    NOOP_SPAN,
    Span,
    Tracer,
    activate,
    enabled,
    end,
    get_tracer,
    mirror,
    set_tracer,
    span,
    span_at,
    start_detached,
    tracing,
)

__all__ = [
    "NOOP_SPAN",
    "SCHEMA_VERSION",
    "BatchReport",
    "Counter",
    "EnumLevel",
    "EnumReport",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "OocReport",
    "PlanReport",
    "Report",
    "ServiceReport",
    "Span",
    "Tracer",
    "activate",
    "enabled",
    "end",
    "get_tracer",
    "mirror",
    "parse_prometheus",
    "set_tracer",
    "span",
    "span_at",
    "start_detached",
    "tracing",
    "validate_extras",
]
