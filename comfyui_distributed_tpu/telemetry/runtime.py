"""JAX runtime health: compile activity, cache hits, HBM, host RSS.

Scrape-time collectors that put the *runtime* next to the *protocol*
on `/distributed/metrics`: a latency regression means nothing without
knowing whether the process was recompiling, missing the compilation
cache, or running the chip's HBM to the edge. `benchmark/client.py`
reads these gauges off the scrape (`compile_s`, program counts,
`peak_hbm_gb`), and a worker's fleet snapshot carries the same numbers.

Three sources:

- **jax.monitoring** — `install_jax_monitoring()` registers listeners
  for the events of a program's way to the device (jaxpr tracing,
  lowering to MLIR, backend compile — which holds the
  compilation-cache retrieval) and the compilation-cache hit/miss
  events. Installed once per process (idempotent), as early as
  possible (server start) so compiles are counted from the first
  program. The time-span listener turns one thread's events into one
  `program.build` span a program (`close_programs`), on the tracer's
  clock; `program_work()` is the snapshot the graph executor diffs
  around each node.
- **device.memory_stats()** — per-device HBM gauges
  (`bytes_in_use`, `peak_bytes_in_use`, `bytes_limit`, ...). Only
  consulted when jax is ALREADY imported AND its backend is already
  up: a metrics scrape runs on the event loop and must never be the
  thing that initialises a backend. `CDT_RUNTIME_DEVICE_STATS=0`
  disables device enumeration at scrape entirely.
- **psutil** — host RSS of this process.

`ensure_runtime_collectors()` binds the scrape collector to the
CURRENT global registry (re-binding transparently after a test reset);
`runtime_snapshot()` returns the same numbers as a plain dict for the
worker's fleet snapshot (telemetry/fleet.local_snapshot).
"""

from __future__ import annotations

import os
import sys
import threading
import time
from typing import Any, NamedTuple

from . import instruments
from .metrics import MetricsRegistry, get_metrics_registry
from .tracing import STARTUP_TRACE, get_tracer

# Monotonic process-lifetime tallies filled by the jax.monitoring
# listeners; plain floats/ints guarded by a lock (listener callbacks
# can fire from compile threads).
_tallies_lock = threading.Lock()
_tallies = {
    "compiles": 0,
    "compile_time_s": 0.0,
    "cache_hits": 0,
    "cache_misses": 0,
}

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_TALLIES = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
# time-span event -> the phase of a program's way to the device
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    _BACKEND_COMPILE_EVENT: "compile",
}
# the phase that ends just before a phase of the same program begins
_PRECEDES = {"compile": "lower", "lower": "trace"}

PROGRAM_SPAN = "program.build"
_MAX_PENDING = 4096  # events a thread may hold that no compile has closed


class _Event(NamedTuple):
    """One time span JAX reported, on the tracer's clock."""

    phase: str  # trace | lower | compile
    name: str  # JAX's fun_name: `outer` traced, `jit(outer)` lowered and compiled
    start: float
    end: float


class _Pending(threading.local):
    """One thread's share: the events since its last closed program, in
    the order they ended (an inner jit's before its caller's); what the
    backend compile under way has fetched; and the seconds, by node
    attribute, of every `program.build` span this thread has closed."""

    def __init__(self) -> None:
        self.events: list[_Event] = []
        self.fetch_s = 0.0
        self.hit = False
        self.seconds = {"trace_s": 0.0, "lower_s": 0.0, "cache_fetch_s": 0.0}


_pending = _Pending()
# JAX stamps its time spans with time.time(); added to one, this gives
# time.monotonic(), the clock a tracer has unless a test gave it
# another. Read once, when the listeners are installed.
_clock_offset = 0.0

_monitoring_installed = False
_bound_registry: MetricsRegistry | None = None
_bind_lock = threading.Lock()


def install_jax_monitoring() -> None:
    """Register jax.monitoring listeners for compile + cache events;
    idempotent."""
    global _monitoring_installed, _clock_offset
    if _monitoring_installed:
        return
    from jax import monitoring

    def on_event(event: str, **kwargs: Any) -> None:
        key = _CACHE_TALLIES.get(event)
        if key is None:
            return
        if key == "cache_hits":
            _pending.hit = True  # of the backend compile under way here
        with _tallies_lock:
            _tallies[key] += 1

    def on_duration(event: str, duration: float, **kwargs: Any) -> None:
        if event == _RETRIEVAL_EVENT:
            # inside the backend compile that the time-span listener
            # is about to hear of, on the same thread
            _pending.fetch_s += float(duration)
        elif event == _BACKEND_COMPILE_EVENT:
            with _tallies_lock:
                _tallies["compile_time_s"] += float(duration)
                _tallies["compiles"] += 1

    def on_time_span(
        event: str, start_time: float, end_time: float, **kwargs: Any
    ) -> None:
        phase = _PHASES.get(event)
        if phase is None:
            return
        events = _pending.events
        events.append(_Event(
            phase, str(kwargs.get("fun_name", "")),
            start_time + _clock_offset, end_time + _clock_offset,
        ))
        if phase == "compile":
            close_programs()  # nothing of a program comes after it
        elif len(events) > _MAX_PENDING:
            del events[0]

    _clock_offset = time.monotonic() - time.time()
    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_time_span_listener(on_time_span)
    _monitoring_installed = True


def _covered(intervals: list[tuple[float, float]]) -> float:
    """Length of the union of `intervals`."""
    total, reach = 0.0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total += end - start
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total


def _program_start(events: list[_Event]) -> int:
    """Index at which the program that `events` ends with begins. On
    one thread events lie inside one another or apart, so what began
    inside the program's stretch is its own; the stretch reaches back
    from a backend compile over the lowering before it, and from a
    lowering over the trace before it, where the names agree
    (`outer` traced, `jit(outer)` lowered and compiled)."""
    phase, name, start, _ = events[-1]
    first = len(events) - 1
    while True:
        while first > 0 and events[first - 1].start >= start:
            first -= 1
        before = events[first - 1] if first > 0 else None
        if (
            before is None
            or before.phase != _PRECEDES.get(phase)
            or before.name not in name
        ):
            return first
        phase, start = before.phase, before.start
        first -= 1


def close_programs() -> None:
    """Close one `program.build` span for each outermost program among
    the calling thread's pending events: the one a backend compile has
    just ended, and before it whatever was only traced (an
    `eval_shape`) or lowered. A span goes under the span active on this
    thread, outside a trace under the `startup` trace's root, and
    without either nowhere: the counter and the thread's seconds take
    its phases all the same."""
    mine = _pending
    if not mine.events:
        return
    events, mine.events = mine.events, []
    fetched_s, hit = mine.fetch_s, mine.hit
    mine.fetch_s, mine.hit = 0.0, False
    programs = []
    while events:
        first = _program_start(events)
        programs.append(events[first:])
        del events[first:]
    tracer = get_tracer()
    trace_id = tracer.current_trace_id()
    if trace_id is None and tracer.root_span_id(STARTUP_TRACE) is not None:
        trace_id = STARTUP_TRACE
    counter = instruments.program_seconds_total()
    for members in reversed(programs):  # in the order they happened
        phase, name, _, end = members[-1]
        traced = [(m.start, m.end) for m in members if m.phase == "trace"]
        lowered = traced + [(m.start, m.end) for m in members if m.phase == "lower"]
        # each phase the union of its intervals, less what an earlier
        # phase holds of them: the four add up to no more than the span
        trace_s = _covered(traced)
        lower_s = _covered(lowered) - trace_s
        compile_s = _covered([(m.start, m.end) for m in members]) - trace_s - lower_s
        fetch_s = min(fetched_s, compile_s) if phase == "compile" else 0.0
        seconds = {
            "trace": trace_s, "lower": lower_s,
            "build": compile_s - fetch_s, "fetch": fetch_s,
        }
        for label, value in seconds.items():
            if value > 0:
                counter.inc(value, phase=label)
        mine.seconds["trace_s"] += trace_s
        mine.seconds["lower_s"] += lower_s
        mine.seconds["cache_fetch_s"] += fetch_s
        if trace_id is None:
            continue
        outcome = "traced" if phase != "compile" else "fetched" if hit else "built"
        tracer.record_span(
            PROGRAM_SPAN, min(m.start for m in members), end, trace_id=trace_id,
            attrs={"program": name, "outcome": outcome,
                   **{f"{label}_s": value for label, value in seconds.items()}},
        )


def _host_rss_bytes() -> int | None:
    try:
        import psutil

        return int(psutil.Process().memory_info().rss)
    except Exception:  # noqa: BLE001 - psutil optional
        try:
            import resource

            # ru_maxrss is KiB on Linux (peak, not current — close enough
            # for a fallback gauge)
            return int(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss) * 1024
        except Exception:  # noqa: BLE001
            return None


def _device_memory() -> list[dict[str, Any]]:
    """Per-device memory stats, ONLY if jax's backend is already up in
    this process (never trigger backend init from a scrape)."""
    if os.environ.get("CDT_RUNTIME_DEVICE_STATS", "1") == "0":
        return []
    jax = sys.modules.get("jax")
    if jax is None or not backend_is_up():
        return []
    out = []
    for device in jax.local_devices():
        stats = device.memory_stats() or {}
        out.append(
            {
                "id": f"{device.platform}:{getattr(device, 'id', '?')}",
                "kind": str(getattr(device, "device_kind", "?")),
                "platform": device.platform,
                "memory": {k: v for k, v in stats.items() if isinstance(v, (int, float))},
            }
        )
    return out


def backend_is_up() -> bool:
    """Whether a JAX backend has been initialised in this process. jax
    has no public query for it, and asking for devices is the very call
    that initialises one — this is the one place the private call
    lives."""
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def tallies() -> dict[str, Any]:
    """The monitoring tallies as they stand (monotonic since process
    start)."""
    with _tallies_lock:
        return dict(_tallies)


def program_work() -> dict[str, Any]:
    """What the graph executor diffs around a node, on the node's own
    thread: the process's tallies, and `trace_s`, `lower_s`,
    `cache_fetch_s` summed over the `program.build` spans this thread
    has closed, what was pending on it closed first (so a node's
    attributes are sums over its `program.build` children)."""
    close_programs()
    return {**tallies(), **_pending.seconds}


def collect_runtime_gauges() -> None:
    """Scrape-time collector body: refresh the cdt_jax_* / host gauges
    from the monitoring tallies and live device state."""
    snap = tallies()
    instruments.jax_compiles().set(snap["compiles"])
    instruments.jax_compile_time_seconds().set(snap["compile_time_s"])
    instruments.jax_cache_hits().set(snap["cache_hits"])
    instruments.jax_cache_misses().set(snap["cache_misses"])
    rss = _host_rss_bytes()
    if rss is not None:
        instruments.host_rss_bytes().set(rss)
    gauge = instruments.device_memory_bytes()
    gauge.clear()  # stats keys vary by backend; don't freeze stale series
    for device in _device_memory():
        for stat, value in device["memory"].items():
            gauge.set(value, device=device["id"], stat=stat)


def ensure_runtime_collectors() -> None:
    """Bind `collect_runtime_gauges` to the current global registry
    (idempotent per registry — a test reset re-binds on next call) and
    make sure the jax.monitoring listeners are installed."""
    global _bound_registry
    install_jax_monitoring()
    registry = get_metrics_registry()
    with _bind_lock:
        if _bound_registry is registry:
            return
        registry.register_collector(collect_runtime_gauges)
        _bound_registry = registry


def runtime_snapshot() -> dict[str, Any]:
    """The same runtime health numbers as a plain dict — what a
    worker's fleet snapshot (telemetry/fleet.local_snapshot) carries."""
    out = tallies()
    out["compile_time_s"] = round(out["compile_time_s"], 3)
    jax = sys.modules.get("jax")
    if jax is not None:
        cache_dir = jax.config.jax_compilation_cache_dir
        if cache_dir:
            # the hit/miss tallies above say whether it actually helped
            out["compile_cache_dir"] = cache_dir
    rss = _host_rss_bytes()
    if rss is not None:
        out["host_rss_bytes"] = rss
    devices = _device_memory()
    if devices:
        out["devices"] = devices
    return out


def reset_runtime_tallies() -> None:
    """Zero the monitoring tallies and the calling thread's share
    (tests)."""
    with _tallies_lock:
        for key, value in _tallies.items():
            _tallies[key] = type(value)()
    _pending.__init__()
