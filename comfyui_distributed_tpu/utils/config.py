"""Persistent JSON config system.

Behavior parity with reference utils/config.py: defaults merged
recursively while preserving unknown keys, an mtime-based read cache,
atomic writes (tmp + fsync + os.replace), and an asyncio-locked
transaction helper that only persists when the mutation changed
something. The schema is TPU-native: workers are addressed by TPU chip
sets / mesh slices rather than CUDA devices, and the master carries a
mesh section describing the local pod slice.
"""

from __future__ import annotations

import asyncio
import contextlib
import copy
import json
import os
import threading
from typing import Any, AsyncIterator

from . import logging as dlog
from .constants import HEARTBEAT_TIMEOUT_SECONDS
from .fsio import atomic_write_json

CONFIG_FILENAME = "tpu_config.json"

DEFAULT_CONFIG: dict[str, Any] = {
    "master": {
        "host": "",
        # Chips the master process pins itself to before its backend
        # starts (process-per-chip mode: the rest are left for managed
        # workers). Empty = every local chip, driven as one in-process
        # mesh — the TPU-native path.
        "tpu_chips": [],
    },
    "mesh": {
        # Logical axis names for the local slice mesh. "data" is the
        # participant axis used for seed-parallel replication; "model"
        # is used by tensor/FSDP sharded models.
        "axes": {"data": -1, "model": 1},
        # ICI topology override, e.g. [4, 2] for a v5e-8 host; -1 = auto.
        "topology": None,
    },
    "workers": [],
    "settings": {
        "debug": False,
        "auto_launch_workers": False,
        "stop_workers_on_master_exit": True,
        "master_delegate_only": False,
        "websocket_orchestration": True,
        "worker_timeout_seconds": HEARTBEAT_TIMEOUT_SECONDS,
        "probe_concurrency": 8,
        "prep_concurrency": 4,
        "media_sync_concurrency": 2,
    },
    "tunnel": {},
    "managed_processes": {},
}

# Template for entries in config["workers"]. type: "mesh" = a set of
# local chips driven in-process over ICI (the TPU-native fast path);
# "local" = a separate worker process on this host; "remote"/"cloud" =
# HTTP participants on other hosts (DCN tier).
WORKER_TEMPLATE: dict[str, Any] = {
    "id": "",
    "name": "",
    "type": "mesh",
    "host": "",
    "port": 0,
    "tpu_chips": [],
    "enabled": False,
    "extra_args": "",
}


def _package_dir() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def get_config_path() -> str:
    override = os.environ.get("CDT_CONFIG_PATH")
    if override:
        return override
    return os.path.join(_package_dir(), CONFIG_FILENAME)


def _merge_defaults(defaults: Any, loaded: Any) -> Any:
    """Recursively overlay `loaded` on `defaults`, keeping unknown keys."""
    if isinstance(defaults, dict) and isinstance(loaded, dict):
        merged = {k: copy.deepcopy(v) for k, v in defaults.items()}
        for key, value in loaded.items():
            if key in merged:
                merged[key] = _merge_defaults(merged[key], value)
            else:
                merged[key] = copy.deepcopy(value)
        return merged
    return copy.deepcopy(loaded)


class _Cache:
    def __init__(self) -> None:
        self.path: str | None = None
        self.mtime: float | None = None
        self.data: dict[str, Any] | None = None
        self.lock = threading.Lock()


_cache = _Cache()
# Transaction mutex: a threading.Lock (acquired via executor so the event
# loop never blocks) rather than an asyncio.Lock — transactions may run on
# different event loops (server loop vs asyncio.run fallbacks on compute
# threads), and an asyncio.Lock binds to whichever loop first awaits it.
_txn_lock = threading.Lock()


def load_config(path: str | None = None) -> dict[str, Any]:
    """Load config with defaults merged in; cached by file mtime."""
    path = path or get_config_path()
    with _cache.lock:
        try:
            mtime = os.path.getmtime(path)
        except OSError:
            mtime = None
        if (
            _cache.data is not None
            and _cache.path == path
            and _cache.mtime == mtime
            and mtime is not None
        ):
            return copy.deepcopy(_cache.data)

        loaded: dict[str, Any] = {}
        if mtime is not None:
            try:
                with open(path, "r", encoding="utf-8") as fh:
                    loaded = json.load(fh)
            except (OSError, json.JSONDecodeError) as exc:
                dlog.log(f"Config load failed ({exc}); using defaults")
                loaded = {}
        merged = _merge_defaults(DEFAULT_CONFIG, loaded)
        _cache.path = path
        _cache.mtime = mtime
        _cache.data = merged
        return copy.deepcopy(merged)


def save_config(config: dict[str, Any], path: str | None = None) -> None:
    """Atomic write via the shared crash-safe recipe (utils/fsio.py:
    tmp + fsync + os.replace + directory fsync)."""
    path = path or get_config_path()
    atomic_write_json(path, config, indent=2, sort_keys=False)
    with _cache.lock:
        _cache.path = path
        try:
            _cache.mtime = os.path.getmtime(path)
        except OSError:
            _cache.mtime = None
        # Cache the defaults-merged view, not the raw input — cache hits
        # must return the same shape a fresh load would.
        _cache.data = _merge_defaults(DEFAULT_CONFIG, config)


@contextlib.contextmanager
def locked_config(path: str | None = None):
    """Synchronous locked read-modify-write on the SAME mutex as
    config_transaction; persists only if mutated. For sync callers on
    executor threads (e.g. the worker process manager's PID
    persistence) — a private lock there would not exclude the async
    transaction path and load/save interleavings could drop writes.
    """
    with _txn_lock:
        config = load_config(path)
        snapshot = copy.deepcopy(config)
        yield config
        if config != snapshot:
            save_config(config, path)


@contextlib.asynccontextmanager
async def config_transaction(path: str | None = None) -> AsyncIterator[dict[str, Any]]:
    """Locked read-modify-write; persists only if mutated.

    Usage:
        async with config_transaction() as cfg:
            cfg["settings"]["debug"] = True
    """
    loop = asyncio.get_running_loop()
    await loop.run_in_executor(None, _txn_lock.acquire)
    try:
        config = load_config(path)
        snapshot = copy.deepcopy(config)
        yield config
        if config != snapshot:
            save_config(config, path)
    finally:
        _txn_lock.release()


# --- convenience accessors ----------------------------------------------

def get_setting(name: str, default: Any = None, path: str | None = None) -> Any:
    return load_config(path).get("settings", {}).get(name, default)


def get_worker_timeout_seconds(path: str | None = None) -> float:
    value = get_setting("worker_timeout_seconds", HEARTBEAT_TIMEOUT_SECONDS, path)
    try:
        value = float(value)
    except (TypeError, ValueError):
        return HEARTBEAT_TIMEOUT_SECONDS
    return value if value > 0 else HEARTBEAT_TIMEOUT_SECONDS


def is_master_delegate_only(path: str | None = None) -> bool:
    return bool(get_setting("master_delegate_only", False, path))


def get_enabled_workers(path: str | None = None) -> list[dict[str, Any]]:
    return [w for w in load_config(path).get("workers", []) if w.get("enabled")]


def _read_debug_flag() -> bool:
    return bool(get_setting("debug", False))


# Wire the hot-reloadable debug flag into the logger.
dlog.set_debug_flag_reader(_read_debug_flag)
