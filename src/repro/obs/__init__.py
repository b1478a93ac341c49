"""Observability subsystem — spans, metrics, and cost-model drift.

The paper's results are measurement all the way down: Table 1's
per-layer vertex/edge counters, §5's per-run TEPS methodology, the
hyperthreading/affinity studies — and the hybrid follow-up
(arXiv:1704.02259) shows the direction switch is only *tunable* when
per-layer behavior is visible.  The engine has captured on-device
counters since PR 1 (`LayerStats`, `direction_log`) and an analytic
bytes model gated in CI since PR 3; this package adds the axis none of
those record: **time**, plus the check that the hand-derived bytes
model still matches what XLA actually compiles.

Three modules, one concern each:

* `obs.trace`      — `span`, the one span primitive (a
  ``jax.profiler.TraceAnnotation``: on the device trace's clock while
  a profiler session is open, about a microsecond otherwise), the
  span tracer (traversal → layer → step nesting,
  wall clock + optional device sync) exporting Chrome trace-event
  JSON viewable in Perfetto, plus the host-stepped instrumented
  traversal (`trace_run`) that reuses the plan cache's compiled
  `layer_step` so timing never perturbs the fused ``lax.while_loop``
  fast path.
* `obs.metrics`    — process-local counters/gauges/histograms with a
  JSON snapshot and Prometheus-style text exposition; the serve tier
  records submit→harvest latency (p50/p99) and its queue-wait /
  in-slot parts, tick duration, queue depth and slot occupancy
  through it.
* `obs.cost_drift` — the analytic `layer_bytes`/`traversal_bytes`
  models compared against what the compiled program reports
  (``jax.jit(...).lower().compile().cost_analysis()`` and the
  trip-count-aware `roofline.hlo_analyze`), per (format, pipeline) —
  wired as a CI gate so the PR-3/4/6 bytes gates can never silently
  diverge from the compiled program.
"""
from repro.obs.cost_drift import Drift, drift_rows, measure_drift
from repro.obs.metrics import (Counter, DegradeEvent, Gauge, Histogram,
                               MetricsRegistry, clear_degrade_log,
                               degrade_log, get_registry,
                               record_degrade)
from repro.obs.trace import (SpanTracer, TraceRun, span, trace_run,
                             xla_profiler)

__all__ = [
    "Counter",
    "DegradeEvent",
    "Drift",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "SpanTracer",
    "TraceRun",
    "clear_degrade_log",
    "degrade_log",
    "drift_rows",
    "get_registry",
    "measure_drift",
    "record_degrade",
    "span",
    "trace_run",
    "xla_profiler",
]
