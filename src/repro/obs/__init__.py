"""Observability: tracing, metrics, export, health, drift, refit.

The feedback channel FLOWER gets from the HLS toolchain's analyzers,
rebuilt for the reproduction — and grown (PR 10) from a recorder into
a telemetry *plane*: :mod:`~repro.obs.tracer` records spans into a
bounded ring, :mod:`~repro.obs.export` renders the ring as a
Perfetto-loadable Chrome trace, :mod:`~repro.obs.metrics` is the
unified counter/gauge/histogram registry that runtime telemetry
publishes into, :mod:`~repro.obs.exporter` renders that registry as
an OpenMetrics/Prometheus exposition (with an optional stdlib scrape
endpoint), :mod:`~repro.obs.health` evaluates rolling-window SLOs
with hysteresis, :mod:`~repro.obs.drift` persists the
(modeled, measured) pairs that calibrate the cost model, and
:mod:`~repro.obs.sentinel` watches those pairs and triggers
recalibration when the fitted constants go stale.

This package imports only the standard library, numpy and
``jax.profiler`` at module load — every repro layer can depend on it
without cycles (the sentinel pulls in :mod:`repro.tune` lazily, at
use).
"""
from repro.obs.drift import (DRIFT_ENV, DriftLog, DriftRow,
                             default_drift_path, drift_report,
                             predict_features, resolve_drift, spearman)
from repro.obs.export import (export_chrome_trace, load_chrome_trace,
                              to_chrome_events, validate_chrome_trace)
from repro.obs.exporter import (MetricFamily, MetricsHTTPServer, Sample,
                                export_metrics_at_exit, flatten_report,
                                parse_openmetrics, registry_families,
                                render_openmetrics, validate_openmetrics,
                                write_openmetrics)
from repro.obs.health import SLO, STATES, HealthMonitor
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.sentinel import DriftSentinel, SentinelPolicy
from repro.obs.tracer import (TRACE_ENV, Event, Tracer, get_tracer,
                              install, maybe_span, program_span,
                              resolve_tracer, uninstall)

__all__ = [
    "Event", "Tracer", "install", "uninstall", "get_tracer",
    "resolve_tracer", "maybe_span", "program_span", "TRACE_ENV",
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "to_chrome_events", "export_chrome_trace", "load_chrome_trace",
    "validate_chrome_trace",
    "DriftLog", "DriftRow", "default_drift_path", "drift_report",
    "predict_features", "resolve_drift", "spearman", "DRIFT_ENV",
    "Sample", "MetricFamily", "registry_families", "render_openmetrics",
    "parse_openmetrics", "validate_openmetrics", "MetricsHTTPServer",
    "write_openmetrics", "export_metrics_at_exit", "flatten_report",
    "SLO", "STATES", "HealthMonitor",
    "DriftSentinel", "SentinelPolicy",
]
