"""Master startup/shutdown: auto-launch, signal cleanup, stale-PID
recovery.

Parity with reference workers/startup.py: a delayed auto-launch of
enabled local workers (skipped on worker processes), async signal
handlers for graceful cleanup, and an atexit fallback that stops
managed workers when configured to.
"""

from __future__ import annotations

import asyncio
import atexit
import os
import signal
import threading
from typing import Any

from ..utils import config as config_mod
from ..utils.constants import (
    AUTO_LAUNCH_DELAY_SECONDS,
    COMPILE_CACHE_ENV,
    TPU_VISIBLE_CHIPS_ENV,
    WORKER_ENV_FLAG,
    default_compile_cache_dir,
)
from ..utils.logging import log
from .process_manager import chip_environment, get_worker_manager

_cleanup_done = threading.Event()


def is_worker_process() -> bool:
    return os.environ.get(WORKER_ENV_FLAG) == "1"


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache so every process
    after the first skips its first compiles. Where
    JAX_COMPILATION_CACHE_DIR is set jax has already read it and no
    directory is set in code; otherwise the cache goes to the fixed
    in-checkout path (utils/constants.default_compile_cache_dir).
    Master and managed workers (and so chip_smoke.py and benchmark/run.py,
    which start them) all come through here. Must run before the first jit compile. Returns the
    directory in use.

    Thresholds are zeroed so even small/fast programs cache — the
    elastic tier compiles one tile-processor per shape bucket, a model
    load dispatches ~1,000 small eager programs, and every one of them
    is worth persisting. jax.monitoring cache hit/miss events land in
    cdt_jax_cache_hits/misses on /distributed/metrics
    (telemetry/runtime.py) — installed here, the earliest
    backend-adjacent moment every process passes through, so the
    tallies count from the FIRST program."""
    import jax

    from ..telemetry.runtime import install_jax_monitoring

    if not os.environ.get(COMPILE_CACHE_ENV):
        cache_dir = default_compile_cache_dir()
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    install_jax_monitoring()
    cache_dir = jax.config.jax_compilation_cache_dir
    log(f"persistent compilation cache at {cache_dir}")
    return cache_dir


def host_tpu_chips(dev_root: str = "/dev") -> list[int]:
    """Indices of the TPU chips this host lets a process open, counted
    from their device nodes so no JAX backend is initialised (a backend
    that enumerates chips also takes them, and the master must leave
    its workers' chips free): /dev/accelN on the older generations,
    the numbered IOMMU groups under /dev/vfio from v5e on. The PCI bus
    is no guide — a host that passes one chip of four through still
    lists all four there."""
    import re

    def numbered(directory: str, pattern: str) -> int:
        try:
            names = os.listdir(directory)
        except OSError:
            return 0
        return sum(1 for name in names if re.fullmatch(pattern, name))

    chips = numbered(dev_root, r"accel\d+") or numbered(
        os.path.join(dev_root, "vfio"), r"\d+"
    )
    return list(range(chips))


def apply_master_chips(config_path: str | None = None) -> list[int]:
    """Pin THIS process to config master.tpu_chips (process-per-chip
    mode) — before any backend initialises, or libtpu has already
    taken every chip on the host and no managed worker can start.
    Empty (the default) leaves the master every local chip: the
    in-process mesh is the TPU-native path. A worker process comes
    pre-pinned by its launcher and is left alone."""
    if is_worker_process():
        return []
    chips = master_chips(config_path)
    os.environ.update(chip_environment(chips))
    return chips


def master_chips(config_path: str | None = None) -> list[int]:
    raw = config_mod.load_config(config_path).get("master", {}).get("tpu_chips")
    return [int(c) for c in raw or []]


def init_backend(platform_flag: str | None) -> Any:
    """Initialise the JAX backend once, on purpose, at start-up — so no
    request handler ever does it on the event loop — and say what it
    is. Refuses the CPU unless it was asked for by name: with
    JAX_PLATFORMS unset jax falls back to the CPU when libtpu cannot
    take the chip, and a server that then serves SDXL at CPU speed
    looks like a hang. Raises RuntimeError (jax's own, or the refusal)
    for the CLI to turn into a non-zero exit."""
    import jax

    from ..parallel.multihost import maybe_init_multihost

    if platform_flag:
        jax.config.update("jax_platforms", platform_flag)
    # join the pod's shared JAX runtime when configured (no-op
    # otherwise); it has to precede the first device query
    maybe_init_multihost()
    devices = jax.local_devices()
    platform = devices[0].platform
    log(
        f"serving on platform={platform} device_kind={devices[0].device_kind} "
        f"local_devices={len(devices)} "
        f"visible_chips={os.environ.get(TPU_VISIBLE_CHIPS_ENV) or 'all'} "
        f"host_chips={len(host_tpu_chips())}"
    )
    if platform == "cpu" and "cpu" not in (platform_flag or "").split(","):
        raise RuntimeError(
            "refusing to serve on platform 'cpu': no accelerator backend "
            "initialised (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r}). Pass --platform cpu "
            "to serve on the CPU on purpose."
        )
    return devices


def auto_populate_workers(config_path: str | None = None) -> list[dict[str, Any]]:
    """First-run convenience for process-per-chip mode: create one
    local worker entry per chip of this host the master is not pinned
    to, ports 8189+. With the master unpinned (the default) its mesh
    already drives every chip and there is nothing to populate.

    The reference does this from the browser (reference
    web/masterDetection.js auto-populate, flag
    has_auto_populated_workers); runtime-side here so headless
    deployments get it too. Runs once — the flag persists in config.
    """
    if is_worker_process():
        return []
    pinned = set(master_chips(config_path))
    if not pinned:
        return []
    created: list[dict[str, Any]] = []
    config = config_mod.load_config(config_path)
    if config.get("settings", {}).get("has_auto_populated_workers"):
        return []
    spare = [c for c in host_tpu_chips() if c not in pinned]
    port = 8189
    for chip in spare:
        created.append(
            {
                "id": f"chip{chip}",
                "name": f"chip{chip}",
                "type": "local",
                "host": "127.0.0.1",
                "port": port,
                "tpu_chips": [chip],
                "enabled": False,
                "extra_args": "",
                # surfaced by the control panel's Network section
                "auto_populated": True,
            }
        )
        port += 1
    config.setdefault("workers", []).extend(created)
    config.setdefault("settings", {})["has_auto_populated_workers"] = True
    config_mod.save_config(config, config_path)
    if created:
        log(f"auto-populated {len(created)} worker(s) for spare chips {spare}")
    return created


def delayed_auto_launch(config_path: str | None = None) -> threading.Timer | None:
    """After a short delay (server must be up first), clear stale PID
    records and launch enabled local workers if auto_launch is on."""
    if is_worker_process():
        return None

    def launch():
        manager = get_worker_manager()
        stale = manager.clear_stale(config_path)
        if stale:
            log(f"cleared stale managed workers: {stale}")
        config = config_mod.load_config(config_path)
        if not config.get("settings", {}).get("auto_launch_workers"):
            return
        for worker in config.get("workers", []):
            if not worker.get("enabled") or worker.get("type") not in ("local",):
                continue
            try:
                manager.launch_worker(worker, config_path)
            except Exception as exc:  # noqa: BLE001 - continue others
                log(f"auto-launch of {worker.get('id')} failed: {exc}")

    timer = threading.Timer(AUTO_LAUNCH_DELAY_SECONDS, launch)
    timer.daemon = True
    timer.start()
    return timer


def sync_cleanup(config_path: str | None = None) -> None:
    """Stop managed workers if configured (atexit / signal path)."""
    if _cleanup_done.is_set() or is_worker_process():
        return
    _cleanup_done.set()
    config = config_mod.load_config(config_path)
    if config.get("settings", {}).get("stop_workers_on_master_exit", True):
        stopped = get_worker_manager().stop_all(config_path)
        if stopped:
            log(f"stopped {stopped} managed worker(s) on exit")


def register_signals(loop: asyncio.AbstractEventLoop, config_path: str | None = None):
    """SIGINT/SIGTERM/SIGHUP → cleanup then stop the loop; atexit as
    fallback for abnormal paths."""
    if is_worker_process():
        return

    def handler():
        sync_cleanup(config_path)
        loop.stop()

    for sig in (signal.SIGINT, signal.SIGTERM, signal.SIGHUP):
        try:
            loop.add_signal_handler(sig, handler)
        except (NotImplementedError, RuntimeError):
            # non-unix or nested loop: atexit still covers us
            pass
    atexit.register(sync_cleanup, config_path)


async def drain_worker(server, grace_seconds: float = 30.0) -> bool:
    """Graceful worker drain: interrupt the in-flight execution (the
    tile pipeline finishes its current device batch, flushes encoded
    tiles, RETURNS the unprocessed remainder via return_tiles, and its
    final flush marks this worker done on the master), wait up to
    `grace_seconds` for the executor to settle, then stop the server.
    Returns True when the executor drained inside the grace window."""
    server.interrupt()
    deadline = asyncio.get_running_loop().time() + max(0.0, grace_seconds)
    drained = True
    while server._executing.is_set():
        if asyncio.get_running_loop().time() > deadline:
            drained = False
            log(
                f"worker drain: executor still busy after {grace_seconds}s; "
                "stopping anyway (the master's heartbeat timeout covers "
                "whatever was left)"
            )
            break
        await asyncio.sleep(0.1)
    await server.stop()
    return drained


def register_worker_drain(
    loop: asyncio.AbstractEventLoop, server, grace_seconds: float = 30.0
):
    """SIGTERM/SIGINT on a WORKER process: graceful drain instead of a
    hard death. Without this, a terminated worker's in-flight grant
    sits assigned until the master's heartbeat timeout requeues it;
    with it, the interrupt path hands the tiles back immediately and
    the worker deregisters via its final flush."""
    # env flag OR the server's own role: a worker started directly
    # (not via the process manager's env injection) still drains
    if not (is_worker_process() or getattr(server, "is_worker", False)):
        return

    draining = threading.Event()

    def handler():
        if draining.is_set():
            # second signal: the operator means it — stop now
            loop.stop()
            return
        draining.set()
        log("worker received SIGTERM/SIGINT: draining in-flight grant")

        async def _drain_and_stop():
            try:
                await drain_worker(server, grace_seconds)
            finally:
                loop.stop()

        loop.create_task(_drain_and_stop())

    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            loop.add_signal_handler(sig, handler)
        except (NotImplementedError, RuntimeError):
            pass
