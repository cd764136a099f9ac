"""Query-lifecycle observability — the Spark UI / SparkListener analogue.

The reference inherits Spark's entire observability stack: the UI's
stage/task timelines, the JSON event log a history server replays, and
accumulator counters (both papers report their strategy wins off those
surfaces). This package is the TPU rebuild's equivalent, three layers:

- :mod:`matrel_tpu.obs.metrics` — process-wide metrics registry
  (counters / gauges / timing histograms; thread-safe, zero-dep), the
  accumulator analogue.
- :mod:`matrel_tpu.obs.events` — structured JSONL event log, the Spark
  event-log analogue: ``MatrelSession`` emits one record per query run
  (optimize/compile/execute phases, rewrite-rule hits, plan-cache
  hit/miss/evictions, per-matmul planner decisions with estimated ICI
  bytes + FLOPs).
- :mod:`matrel_tpu.obs.analyze` + :mod:`matrel_tpu.obs.history` — the
  debugging surfaces: ``session.explain(expr, analyze=True)`` renders
  the physical tree with MEASURED per-op milliseconds next to the
  planner's estimates, and ``python -m matrel_tpu history`` aggregates
  an event-log file (the history-server analogue).

Tier 2 (round 9) adds the runtime-behaviour surfaces on top:

- :mod:`matrel_tpu.obs.trace` — structured tracing spans (parent-linked
  ``span`` records through sql → compute (plan → compile → dispatch) →
  fetch, the serve admission path and PageRank's host side;
  ``python -m matrel_tpu trace --export chrome`` renders them as a
  Perfetto timeline) and the bounded in-memory flight recorder
  (``config.obs_flight_recorder``) dumped as a post-mortem artifact on
  verification/compile/serve failures. Four tiers: inactive, profiler
  session (any running ``jax.profiler`` trace makes the spans
  ``TraceAnnotation``s on the profiler's own clock, plus a ring read
  with ``trace.profile_spans()`` — no config change), flight recorder,
  full.
- :mod:`matrel_tpu.obs.drift` — the cost-model drift auditor
  (``history --drift``): estimated bytes/FLOPs joined to measured
  per-op times, calibration ratios persisted per (strategy,
  shape-class, backend), rank-order disagreements flagged.

Tier 3 (round 15) is the LIVE plane — the operator tier the reference
gets from Spark's live UI + metrics sink:

- :mod:`matrel_tpu.obs.metrics` gained :class:`QuantileSketch` — a
  bounded-memory, mergeable DDSketch-style quantile sketch with a
  proven relative-error bound backing every timing histogram, and
  :func:`percentile`, the ONE quantile definition history's replay,
  the endpoint and ``top`` all report through.
- :mod:`matrel_tpu.obs.slo` — declarative per-tenant SLOs
  (``config.slo_targets``) tracked by multi-window burn-rate
  monitors; alert transitions emit ``alert`` events that land in the
  flight-recorder ring regardless of ``obs_level``.
- :mod:`matrel_tpu.obs.export` — the in-process metrics endpoint
  (``config.obs_metrics_port``): ``/metrics`` Prometheus text +
  ``/json`` snapshot, zero threads at the default port 0.
- :mod:`matrel_tpu.obs.top` — ``python -m matrel_tpu top``, the live
  per-tenant QPS/latency/burn console.

Instrumentation is off-hot-path by contract: event assembly happens
outside jitted code, per-op timing only under ``analyze=True``, and with
``config.obs_level == "off"`` (the default) plus the flight recorder
off and no profiler session running, the query path takes zero extra
syncs, appends zero events and creates zero span objects.
"""

from matrel_tpu.obs.events import EventLog, SCHEMA_VERSION, read_events
from matrel_tpu.obs.metrics import MetricsRegistry, REGISTRY
from matrel_tpu.obs.trace import (FlightRecorder, Span, Tracer,
                                  chrome_trace, profile_spans, span)

__all__ = [
    "EventLog", "FlightRecorder", "MetricsRegistry", "REGISTRY",
    "SCHEMA_VERSION", "Span", "Tracer", "chrome_trace", "profile_spans",
    "read_events", "span",
]
