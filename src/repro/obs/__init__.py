"""Unified observability for the serving stack: metrics, tracing, export.

Three modules, layered so the hot path stays cheap:

* :mod:`repro.obs.metrics` — process registry of counters, gauges and
  log-bucketed histograms (per-thread shards merged on read; documented
  percentile error bound), plus the blessed timing helpers
  (:func:`~repro.obs.metrics.now` / :func:`~repro.obs.metrics.timed`)
  the O001 lint rule steers ``repro.serving`` / ``repro.ann`` stage
  timing through.
* :mod:`repro.obs.trace` — per-request span trees with explicit
  cross-thread propagation, probabilistic sampling, a bounded ring, and
  Chrome ``trace_event`` export for Perfetto / ``chrome://tracing``; and
  scoped ``taco.*`` stages that also land in a JAX profiler trace, on the
  device trace's clock.
* :mod:`repro.obs.export` — a stdlib HTTP thread serving ``/metrics``
  (Prometheus text), ``/telemetry`` (JSON) and ``/trace`` (Chrome JSON)
  for ``serve_ann --metrics-port``.

Deliberately dependency-free (stdlib only, no jax/numpy imports on the
metrics/trace hot path; a stage imports jax when it opens) so any layer of
the repo may import it.
"""
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    RELATIVE_ERROR_BOUND,
    counter,
    default_registry,
    gauge,
    histogram,
    now,
    render_prometheus,
    set_enabled,
    snapshot,
    timed,
)
from repro.obs.trace import (
    NULL_SPAN,
    Span,
    Tracer,
    default_tracer,
    set_default_tracer,
)
from repro.obs.export import ObsServer

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "RELATIVE_ERROR_BOUND", "counter", "default_registry", "gauge",
    "histogram", "now", "render_prometheus", "set_enabled", "snapshot",
    "timed", "NULL_SPAN", "Span", "Tracer", "default_tracer",
    "set_default_tracer", "ObsServer",
]
