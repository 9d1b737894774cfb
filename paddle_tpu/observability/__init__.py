"""paddle_tpu.observability — unified telemetry for the whole stack.

Reference: the reference treated profiling as a platform layer
(platform/profiler.h RecordEvent + tools/timeline.py); this package
extends that idea to the three things a production deployment actually
needs from one place:

* ``registry`` — ONE process-wide MetricsRegistry. Serving, the
  dispatch/compile caches, executors, supervisors and data loaders all
  register into it, so a single ``/metrics`` scrape (or
  ``observability.snapshot()``) shows the whole stack.
* ``tracing`` — ``span(name)``. Always on: a named range on the
  profiler's clock (``jax.profiler.TraceAnnotation``), so any attached
  profiler session reads the Executor's ``executor/bind|feed|step|
  fetch`` and the GenerationEngine's ``generation/<phase>`` loop
  phases beside the device trace, whatever the flags say
  (``annotation(name)`` is that and nothing more, flag or no flag). What
  ``observability_tracing`` adds: trace/span/parent ids, propagated
  across threads (serving request -> micro-batch -> worker -> jit
  step; supervisor step -> retry/rollback), ``flow_from`` arrows
  rendered by ``tools_timeline``, and the flight-ring copy of every
  span.
* ``flight`` — an always-on constant-memory flight recorder dumped to
  JSON on NaN rollback, watchdog hang, uncaught loop exception,
  SIGTERM and SIGUSR2.
* ``propagate`` — the cross-process trace-context codec
  (traceparent-style headers, page-store wire heads, ``PADDLE_TRACE_*``
  env for spawned workers) plus the per-process trace index behind
  ``/v1/admin/trace/<id>``.
* ``fleet`` — ``FleetAggregator`` merges every worker's ``/metrics``
  into one ``{worker=,phase=,rank=}``-labeled exposition
  (``/metrics/fleet`` / ``fleet_snapshot()``); ``SLOMonitor`` computes
  windowed deadline-miss ratio and error-budget burn over it
  (``paddle_slo_*`` gauges, fleet-wide flight dump on sustained burn).

Live flags (flags.py): ``observability_metrics``,
``observability_tracing``, ``observability_flight``,
``observability_flight_capacity``, ``observability_dump_dir``,
``observability_xla_analysis``, ``observability_fleet_endpoints``,
``observability_fleet_timeout_s``, plus the ``slo_*`` family.
tests/test_observability.py holds the scrape's families and the flight
dumps; no cell of the benchmark turns these flags on.
"""

from __future__ import annotations

from . import fleet, flight, propagate, registry, tracing
from .fleet import (FleetAggregator, SLOMonitor, assemble_trace,
                    configure_fleet, default_aggregator, fleet_snapshot)
from .flight import dump as flight_dump
from .flight import install_signal_handlers
from .registry import (Counter, Gauge, Histogram, MetricsRegistry,
                       overlap_telemetry, step_telemetry, watch_adapters,
                       watch_collectives, watch_coordinator, watch_disagg,
                       watch_engine, watch_executor, watch_generation,
                       watch_loader, watch_partition, watch_serving,
                       watch_supervisor, watch_traffic)
from .registry import registry as get_registry
from .tracing import SpanContext, attach, current, span, traced

__all__ = [
    "registry", "tracing", "flight", "propagate", "fleet",
    "FleetAggregator", "SLOMonitor", "configure_fleet",
    "default_aggregator", "fleet_snapshot", "assemble_trace",
    "MetricsRegistry", "Counter", "Gauge", "Histogram", "get_registry",
    "span", "traced", "attach", "current", "SpanContext",
    "flight_dump", "install_signal_handlers",
    "watch_serving", "watch_engine", "watch_executor", "watch_supervisor",
    "watch_loader", "watch_generation", "watch_partition",
    "watch_collectives", "watch_coordinator", "watch_traffic",
    "watch_disagg", "watch_adapters", "step_telemetry",
    "overlap_telemetry", "snapshot",
    "to_prometheus_text",
]


def snapshot():
    """One JSON-serializable view of every registered metric family —
    the programmatic twin of ``GET /metrics``."""
    return get_registry().snapshot()


def to_prometheus_text() -> str:
    """The unified Prometheus exposition (what ServingServer's
    ``/metrics`` serves)."""
    return get_registry().to_prometheus_text()
