"""Unified telemetry subsystem (DESIGN.md §11).

Three dependency-free pillars, all off by default:

* :mod:`repro.obs.trace`   — structured spans/events with an injectable
  clock and deterministic sortable span ids; the Chrome/Perfetto exporter
  lives in :mod:`repro.obs.perfetto`.  ``Tracer(profiler=True)`` also
  writes each span into the JAX profiler's trace (``jax`` is imported only
  for it).
* :mod:`repro.obs.metrics` — a process-global registry of counters /
  gauges / histograms with JSONL and Prometheus-textfile exporters.
* :mod:`repro.obs.drift`   — the model-vs-measured drift monitor: one
  JSONL record per executed GEMM/step plus a rolling fidelity gauge —
  the dataset the future learned-residual corrector consumes
  (ROADMAP item 5).

Import rule: ``repro.obs`` imports nothing from ``repro.core`` /
``repro.launch`` — instrumented call sites import *us*, never the other
way around, so there are no cycles and the disabled path costs one
module-global ``is None`` / ``bool`` check.
"""
from repro.obs.drift import (DriftMonitor, get_drift_monitor,
                             set_drift_monitor)
from repro.obs.metrics import (JsonlSink, MetricsRegistry, get_registry,
                               metrics_enabled)
from repro.obs.trace import Tracer, get_tracer, set_tracer, tracing_enabled

__all__ = [
    "DriftMonitor", "get_drift_monitor", "set_drift_monitor",
    "JsonlSink", "MetricsRegistry", "get_registry", "metrics_enabled",
    "Tracer", "get_tracer", "set_tracer", "tracing_enabled",
]
