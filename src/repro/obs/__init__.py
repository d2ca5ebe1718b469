"""Observability subsystem (DESIGN.md §11, §13).

Four layers, all opt-in and all zero-cost when off (a span without a
trace or recorder costs an inactive ``TraceMe``):

* **device-resident fixpoint telemetry** (``obs.stats``) — per-round
  stats (frontier size, edges traversed, counter decrements) threaded
  through the engines' jitted fixpoints as extra carry outputs when a
  plan is built with ``instrument=True``.  Buffers are pow2-padded to a
  static round capacity so instrumented plans compile once;
  ``instrument=False`` compiles the stats out entirely (bit-identical
  results, identical dispatch and trace counts).
* **host-side span tracing** (``obs.recorder``) — every
  ``EngineBase._dispatch`` is wrapped in a structured span (engine
  family, plan signature, wall time, compile-vs-execute attribution).
  Each span is a ``jax.profiler.TraceAnnotation`` named
  ``<cat>.<name>`` (``engine.dispatch``, ``scc.sync``), so a profiler
  trace holds it beside the device's events, and is collected by a
  process-global :class:`Recorder` when one is enabled.  The default
  global recorder is disabled; install one with :func:`recording`
  (nested scopes tee spans to both recorders).
* **continuous metrics** (``obs.metrics`` + ``obs.memory`` +
  ``obs.profile``) — the process-global :class:`MetricsPlane`: labeled
  counters/gauges/histograms with OpenMetrics exposition, per-engine
  live-buffer byte gauges, XLA plan cost analysis, and the SLO tracker
  behind ``launch/serve.py``'s ``/metrics`` endpoint.  Disabled by
  default; install one with :func:`collecting_metrics`.
* **exporters** (``obs.export``) — JSONL (one span per line) and
  chrome://tracing ``traceEvents`` JSON, both round-trippable.
"""
from .export import (read_chrome_trace, read_jsonl, to_chrome_trace,
                     to_jsonl)
from .memory import (array_nbytes, device_memory_stats, engine_nbytes,
                     publish_device_memory, publish_engine_memory)
from .metrics import (LABEL_CARDINALITY_CAP, MetricsPlane, MetricsServer,
                      RetraceStormWarning, SLOTracker, collecting_metrics,
                      get_plane, load_snapshot, log_buckets,
                      parse_openmetrics, set_plane)
from .profile import normalize_cost, plan_cost_of, record_plan_cost
from .recorder import (Recorder, Span, SpanScope, TeeRecorder,
                       get_recorder, instant, note_kernel, recording,
                       set_recorder, span)
from .stats import RoundStats, round_capacity, stats_init, stats_record

__all__ = [
    "Recorder", "Span", "SpanScope", "TeeRecorder", "get_recorder", "set_recorder",
    "recording", "span", "instant", "note_kernel",
    "RoundStats", "round_capacity", "stats_init", "stats_record",
    "MetricsPlane", "MetricsServer", "SLOTracker", "RetraceStormWarning",
    "LABEL_CARDINALITY_CAP", "get_plane", "set_plane",
    "collecting_metrics", "load_snapshot", "log_buckets",
    "parse_openmetrics",
    "array_nbytes", "device_memory_stats", "engine_nbytes",
    "publish_engine_memory", "publish_device_memory",
    "normalize_cost", "plan_cost_of", "record_plan_cost",
    "to_jsonl", "read_jsonl", "to_chrome_trace", "read_chrome_trace",
]
