"""Observability layer: listener bus, spans, XLA cost accounting, metrics.

The reference splits observability across a typed listener event stream
(`SparkListener` / `EventLoggingListener.scala`), per-operator
`SQLMetrics`, the SQL UI status store (`SQLAppStatusListener`), and the
codahale-backed `MetricsSystem` with pluggable sinks. This package is
the engine-sized analog, organized the same way:

- ``listener``: the typed event stream. ``QueryListener`` is the
  SparkListener seat (on_query_start / on_stage_compiled /
  on_stage_completed / on_fault / on_query_end); ``ListenerBus``
  delivers events so the event log, the Chrome-trace writer, the
  metrics sinks, and tests are all just subscribers.
- ``spans``: one query's span tree over both of its threads, each
  span with the span that caused it (``parent``) and its thread
  (``tid``): the lifecycle phases (analysis, optimize, plan, analyze,
  compile, streaming / external, ingest, dispatch with
  dispatch.launch / dispatch.sync, egress, the service's queue), the
  chunk pipeline of a streamed scan (chunk.wait, chunk.decode,
  chunk.unify, chunk.convert per column, chunk.to_device with
  chunk.put per column, chunk.launch, stream.drain) and marks (aqe_replan,
  aqe_overflow, retry:<action>, cancelled). On two clocks: a
  wall-clock anchor for Chrome trace-event JSON (Perfetto-loadable),
  and a ``spark_tpu.<name>`` annotation in the ``jax.profiler`` trace
  whenever a profiler session is on.
- ``xla_cost``: XLA cost/HBM accounting off the AOT API
  (``compiled.cost_analysis()`` / ``memory_analysis()``) — flops,
  bytes accessed, argument/output/temp sizes and the derived peak-HBM
  demand per compiled stage.
- ``metrics``: process metrics registry (counters/gauges/timers/
  log-bucketed latency histograms) with JSONL + Prometheus
  text-exposition sinks, plus the registered traced-metric name
  prefixes the lint framework's ``metric-prefix`` pass enforces.
- ``sinks``: the built-in bus subscribers (event-log writer with
  rotation, Chrome-trace writer, metrics-sink updater) a session
  installs at construction.
- ``status_store``: the ``AppStatusStore`` seat — bounded, typed,
  listener-bus-fed rolling view of engine health (in-flight queries,
  queue depth, lease occupancy, cache hit rates, latency percentiles,
  SLO burn), heartbeat-sampled into ring time-series and served by
  the SQL service's ``GET /status`` endpoints.
- ``flight_recorder``: always-on bounded rings of recent events per
  subsystem; dumps a self-contained diagnostic bundle (rings, plans,
  conf, metrics, thread stacks, event-log tail) on FATAL / OOM-ladder
  exhaustion / non-convergent recovery or on demand.
"""

from .listener import (AnalysisEvent, FaultEvent, ListenerBus,
                       QueryEndEvent, QueryListener, QueryStartEvent,
                       ServiceEvent, ShardChunkEvent, StageCompiledEvent,
                       StageCompletedEvent, StragglerEvent)
from .flight_recorder import FlightRecorder
from .metrics import (METRIC_PREFIXES, Histogram, MetricsRegistry,
                      is_registered_metric)
from .spans import (ShardStreamTelemetry, Span, SpanRecorder,
                    current_recorder, current_shard_telemetry,
                    to_chrome_trace, use_recorder, use_shard_telemetry)
from .status_store import StatusStore
from .straggler import StragglerMonitor

__all__ = [
    "AnalysisEvent", "FaultEvent", "FlightRecorder", "Histogram",
    "ListenerBus", "MetricsRegistry", "METRIC_PREFIXES",
    "QueryEndEvent", "QueryListener", "QueryStartEvent", "ServiceEvent",
    "ShardChunkEvent", "ShardStreamTelemetry", "Span", "SpanRecorder",
    "StageCompiledEvent", "StageCompletedEvent", "StatusStore",
    "StragglerEvent", "StragglerMonitor", "current_recorder",
    "current_shard_telemetry", "is_registered_metric", "to_chrome_trace",
    "use_recorder", "use_shard_telemetry",
]
