"""repro.obs — the observability layer: in-graph probes, metrics bus, traces.

Three parts (DESIGN.md §7 is the full contract):

* **probes** (:mod:`.probes`) — ``HSGD(..., metrics="on")`` carries a
  :class:`MetricBuffer` in the training state and pushes the paper's
  per-level parameter divergences (eq. (10): global = upward + downward)
  at every sync event, ON device, inside the jitted round body; drained in
  one transfer at eval boundaries.  ``metrics=None`` (default) is
  bitwise-identical to no observability at all.
* **bus** (:mod:`.bus`) — the typed channel registry
  (:func:`register_metric` / :class:`MetricSpec`) and record linter
  (:func:`validate_record`) every telemetry producer emits through.
* **trace** (:mod:`.trace`) — :class:`TraceRecorder` exports the run as
  Chrome-trace-event/Perfetto JSON (``python -m repro.obs`` is the CLI;
  ``run_rounds(..., trace=recorder)`` the engine hook).
* **spans** (:mod:`.spans`) — the names the profiler's trace of a real run
  carries: ``run_rounds``'s host spans (:func:`span`) and the device
  scopes of the round programs.
"""
from repro.obs.bus import (SCHEMA_VERSION, MetricSpec, register_metric,
                           registered_metrics, spec_for, validate_record)
from repro.obs.probes import MetricBuffer, Metrics, MetricsLike, make_metrics
from repro.obs.spans import SPANS, span
from repro.obs.trace import TraceRecorder, validate_trace

__all__ = [
    "Metrics", "MetricsLike", "MetricBuffer", "make_metrics",
    "MetricSpec", "SCHEMA_VERSION", "register_metric", "registered_metrics",
    "spec_for", "validate_record",
    "TraceRecorder", "validate_trace",
    "SPANS", "span",
]
