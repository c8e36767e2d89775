"""Telemetry core: metrics, tracing, live events, watchdog, runtime.

The observability subsystem the ROADMAP's perf work hangs off:

- `metrics`: zero-dependency Counter/Gauge/Histogram registry with
  Prometheus text exposition (cardinality-capped per metric), served
  by `/distributed/metrics`;
- `tracing`: span trees keyed by the existing ``exec_*`` trace ids,
  propagated master→worker via the ``X-CDT-Trace-Id`` header and
  served by `/distributed/trace/{trace_id}`: a served request's queue
  wait, nodes, device waits and PNG save are one tree, mirrored into
  an open profiler capture; the process's own start is the trace
  `startup`; JSONL export feeds `scripts/perf_report.py` and, written
  beside a capture, the benchmark's set-up split;
- `instruments`: every metric name/label vocabulary in one place,
  plus `bind_server_collectors` for live-state gauges;
- `events`: push-based event bus (metric deltas, span open/close,
  health transitions, watchdog verdicts) streamed by the
  `GET /distributed/events` WebSocket;
- `watchdog`: straggler & stall detector feeding breaker suspect
  transitions and speculative tail-tile re-dispatch;
- `runtime`: JAX compile/cache/HBM/host-RSS collectors on the scrape
  (read by `benchmark/client.py`) and, via `runtime_snapshot`, in a
  worker's fleet snapshot; one `program.build` span a program JAX
  takes to the device, its trace/lower/build/fetch on a wall clock;
- `timeseries`: bounded two-tier ring-buffer retention (10 s raw /
  5 min rollup) for the fleet plane's windowed history;
- `fleet`: worker snapshot production + the master's `FleetRegistry`
  (per-worker merge, rollups, departed-worker eviction), served by
  `GET /distributed/fleet`;
- `slo`: declarative SLOs with multi-window burn-rate alerting —
  `alert_fired`/`alert_resolved` bus events, `GET /distributed/alerts`,
  and the `cdt_alert_active` scrape gauge;
- `usage`: chip-time attribution — both execution tiers emit
  slot-exact timed records per device dispatch (tenant/job/lane
  charges + padding/recompute/speculation/poison waste buckets, exact
  conservation), worker meters merge into the master by riding the
  fleet snapshot, served by `GET /distributed/usage`.

All clocks are injectable so tier-1 tests run deterministically on
CPU. See docs/observability.md for the operator-facing story.
"""

from __future__ import annotations

from .instruments import BREAKER_STATE_CODES, bind_server_collectors
from .metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_metrics_registry,
    reset_metrics_registry,
)
from .tracing import (
    TRACE_HEADER,
    Span,
    Tracer,
    current_trace_id,
    get_tracer,
    reset_tracer,
    set_tracer,
)
from .events import EventBus, get_event_bus, reset_event_bus
from .fleet import FleetMonitor, FleetRegistry, local_snapshot
from .flight import (
    FlightRecorder,
    get_flight_recorder,
    peek_flight_recorder,
    reset_flight_recorder,
)
from .incidents import IncidentManager, validate_bundle
from .slo import BurnRule, SLOEngine, SLOSpec, default_slos
from .timeseries import SeriesStore
from .usage import UsageAggregator, UsageMeter, get_usage_meter
from .watchdog import Watchdog

__all__ = [
    "BREAKER_STATE_CODES",
    "BurnRule",
    "Counter",
    "EventBus",
    "FleetMonitor",
    "FleetRegistry",
    "FlightRecorder",
    "Gauge",
    "IncidentManager",
    "Histogram",
    "MetricsRegistry",
    "SLOEngine",
    "SLOSpec",
    "SeriesStore",
    "Span",
    "TRACE_HEADER",
    "Tracer",
    "UsageAggregator",
    "UsageMeter",
    "Watchdog",
    "default_slos",
    "local_snapshot",
    "bind_server_collectors",
    "current_trace_id",
    "get_event_bus",
    "get_flight_recorder",
    "get_metrics_registry",
    "get_tracer",
    "get_usage_meter",
    "peek_flight_recorder",
    "reset_event_bus",
    "reset_flight_recorder",
    "reset_metrics_registry",
    "reset_tracer",
    "set_tracer",
    "validate_bundle",
]
