"""JAX runtime health: compile activity, cache hits, HBM, host RSS.

Scrape-time collectors that put the *runtime* next to the *protocol*
on `/distributed/metrics`: a latency regression means nothing without
knowing whether the process was recompiling, missing the compilation
cache, or running the chip's HBM to the edge. `benchmark/client.py`
reads these gauges off the scrape (`compile_s`, program counts,
`peak_hbm_gb`), and a worker's fleet snapshot carries the same numbers.

Three sources:

- **jax.monitoring** — `install_jax_monitoring()` registers listeners
  for the duration events of a program's way to the device (jaxpr
  tracing, lowering to MLIR, backend compile — which holds the
  compilation-cache retrieval, also tallied alone) and the
  compilation-cache hit/miss events. Installed once per process
  (idempotent), as early as possible (server start) so
  compiles are counted from the first program. `tallies()` is the raw
  snapshot the graph executor diffs around each node.
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
from typing import Any

from . import instruments
from .metrics import MetricsRegistry, get_metrics_registry

# Monotonic process-lifetime tallies filled by the jax.monitoring
# listeners; plain floats/ints guarded by a lock (listener callbacks
# can fire from compile threads).
_tallies_lock = threading.Lock()
_tallies = {
    "compiles": 0,
    "compile_time_s": 0.0,
    "cache_hits": 0,
    "cache_misses": 0,
    "trace_time_s": 0.0,
    "lower_time_s": 0.0,
    "cache_retrieval_s": 0.0,
}

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# duration event -> the tally it adds to; nested jits are traced and
# lowered inside their caller's events, so these two can count a
# stretch of time twice
_DURATION_TALLIES = {
    _BACKEND_COMPILE_EVENT: "compile_time_s",
    "/jax/core/compile/jaxpr_trace_duration": "trace_time_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_time_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval_s",
}
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_monitoring_installed = False
_bound_registry: MetricsRegistry | None = None
_bind_lock = threading.Lock()


def install_jax_monitoring() -> None:
    """Register jax.monitoring listeners for compile + cache events;
    idempotent."""
    global _monitoring_installed
    if _monitoring_installed:
        return
    from jax import monitoring

    def on_event(event: str, **kwargs: Any) -> None:
        with _tallies_lock:
            if event == _CACHE_HIT_EVENT:
                _tallies["cache_hits"] += 1
            elif event == _CACHE_MISS_EVENT:
                _tallies["cache_misses"] += 1

    def on_duration(event: str, duration: float, **kwargs: Any) -> None:
        key = _DURATION_TALLIES.get(event)
        if key is not None:
            with _tallies_lock:
                _tallies[key] += float(duration)
                if event == _BACKEND_COMPILE_EVENT:
                    _tallies["compiles"] += 1

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)
    _monitoring_installed = True


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


def collect_runtime_gauges() -> None:
    """Scrape-time collector body: refresh the cdt_jax_* / host gauges
    from the monitoring tallies and live device state."""
    snap = tallies()
    instruments.jax_compiles().set(snap["compiles"])
    instruments.jax_compile_time_seconds().set(snap["compile_time_s"])
    instruments.jax_trace_time_seconds().set(snap["trace_time_s"])
    instruments.jax_lower_time_seconds().set(snap["lower_time_s"])
    instruments.jax_cache_retrieval_seconds().set(snap["cache_retrieval_s"])
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
    for key in _DURATION_TALLIES.values():
        out[key] = round(out[key], 3)
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
    """Zero the monitoring tallies (tests)."""
    with _tallies_lock:
        for key, value in _tallies.items():
            _tallies[key] = type(value)()
