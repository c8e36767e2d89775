"""Device-time attribution: the transfer ledger + on-demand profiler
capture.

The ROADMAP's "speed-of-light on real chips" item needs one number the
existing spans cannot produce: of each tile's wall time, how much was
the chip computing versus the host gathering/encoding/shipping around
it? The spans time whole stages; this module splits the device/host
seam inside them.

Two pieces:

- :class:`TransferLedger` — cumulative integer-nanosecond accounting of
  the device↔host boundary, fed by the execution seams on both tiers
  (``GrantSampler``/``TilePipeline`` on the scan tier,
  ``CrossJobExecutor`` on the xjob tier, checkpoint encode in
  ``ops/stepwise.py``): device-execute time (dispatch bracketing on an
  injectable clock; only dispatches of COMPILED programs count —
  eager-stub harness dispatches are host work by construction, so a
  zero-device run reports host-tax 1.0, never a fiction), bytes moved
  each direction, and host time split into ``gather`` (device→host
  readback), ``encode`` (PNG/decode work), and ``ship`` (submit RPCs).
  The roll-up is the **host-tax ratio** ``host_ns / (host_ns +
  device_ns)`` — the fraction of attributable time the host ate. The
  ledger's cumulative block rides the fleet snapshot piggyback (wire
  v3, telemetry/fleet.py) and is mirrored into
  ``cdt_transfer_bytes_total`` / ``cdt_device_execute_seconds`` /
  ``cdt_host_tax_ratio`` at scrape time.

- :class:`ProfilerCapture` — ``jax.profiler.start_trace``/``stop_trace``
  behind a single-flight guard with a duration cap
  (``CDT_PROFILE_MAX_SECONDS``) and bounded on-disk retention under
  ``CDT_PROFILE_DIR`` (``CDT_PROFILE_MAX`` dirs / ``CDT_PROFILE_MAX_MB``
  total, prune-oldest but never the newest). Served by
  ``POST /distributed/profile/start|stop`` + the index route
  (api/profile_routes.py); the incident manager auto-captures a short
  trace alongside a debug bundle when ``CDT_PROFILE_AUTO=1``. The
  Python tracer is off (it slowed the server and made stop outlast the
  slice); while a capture is open the tracer's context-managed spans
  are written into it as ``TraceAnnotation``s, and the start answer
  carries the tracer's clock and the wall clock read beside
  ``start_trace``. Every span the tracer holds at that moment (the
  ``startup`` trace, the requests so far) is written once into the
  capture's own directory, ``spans_before.jsonl``: a capture is one
  bundle of the device's trace, the two clocks and what the host did
  up to its start.

Determinism contract (cdt-lint CDT004 covers this file): all clocks are
injectable and used only for durations, capture ids derive from a
scanned sequence counter (never wall time), and directory listings sort
before use.
"""

from __future__ import annotations

import contextlib
import os
import re
import shutil
import threading
import time
from typing import Any, Callable, Optional

from ..utils import constants
from ..utils.logging import debug_log
from .tracing import get_tracer, set_span_annotator

_NS = 1_000_000_000
# beside a capture's .xplane.pb: the tracer's spans when it began
SPANS_BEFORE = "spans_before.jsonl"

# Transfer directions (metric label vocabulary).
H2D = "h2d"
D2H = "d2h"

# Host-time buckets; stage_span feeds these via STAGE_HOST_BUCKETS.
HOST_BUCKETS = ("gather", "encode", "ship")

# tile.<stage> span names -> the host bucket their wall time charges.
# `readback` is the device→host gather, `encode`/`decode` are pixel
# codec work, `submit` is the ship RPC. `pull`/`blend`/`dispatch` are
# deliberately absent: pull is wait, blend is master canvas math, and
# dispatch is attributed through note_dispatch's device/eager split.
STAGE_HOST_BUCKETS = {
    "readback": "gather",
    "encode": "encode",
    "decode": "encode",
    "submit": "ship",
}


# Span attributes of these types go into the mirrored annotation.
_ANNOTATION_TYPES = (str, int, float, bool)


def _annotate_span(span: Any) -> Any:
    """The span mirror a capture installs (tracing.set_span_annotator):
    a TraceAnnotation of the span's name and the plain attributes it
    was opened with, entered by the tracer on the span's own thread."""
    import jax

    return jax.profiler.TraceAnnotation(
        span.name,
        **{
            k: v for k, v in span.attrs.items()
            if k != "name" and isinstance(v, _ANNOTATION_TYPES)
        },
    )


def _write_spans_before(path: str) -> dict[str, Any]:
    """Every span the tracer holds, one a line, into the capture's
    directory; on the thread that asked for the capture (the route's
    pool, never the executor). `spans` written and the `write_s` it
    took, on the tracer's clock; a disk that refuses costs the file,
    not the capture."""
    tracer = get_tracer()
    began = tracer.now()
    try:
        spans = tracer.write_jsonl(None, os.path.join(path, SPANS_BEFORE))
    except OSError as exc:
        debug_log(f"profiler capture: {SPANS_BEFORE} not written: {exc}")
        return {}
    return {"spans": spans, "write_s": tracer.now() - began}


def _to_ns(seconds: float) -> int:
    """Non-negative integer nanoseconds (the PR-15 conservation idiom:
    all arithmetic downstream is integral, so sums are exact)."""
    return max(0, int(round(float(seconds) * _NS)))


def transfer_nbytes(array: Any) -> int:
    """Byte size of one transferred array, 0 when it cannot say.

    Typed PRNG key arrays (extended dtypes) raise NotImplementedError
    on ``.nbytes``; their backing uint32 buffer answers instead. The
    ledger must never turn a dispatch into a crash, so anything else
    unanswerable counts 0 bytes (the transfer's TIME still lands)."""
    try:
        return int(array.nbytes)
    except AttributeError:
        return 0
    except Exception:
        try:
            import jax

            return int(jax.random.key_data(array).nbytes)
        except Exception:
            return 0


class TransferLedger:
    """Cumulative device/host attribution for one process.

    Thread-safe; every count is a non-negative integer (ns or bytes).
    ``clock`` is injectable for the few places the ledger measures
    itself (``timed_sync``); seams that already bracket their own work
    pass ``elapsed_s`` in.
    """

    def __init__(self, clock: Callable[[], float] = time.monotonic) -> None:
        self.clock = clock
        self._lock = threading.Lock()
        self.device_ns = 0
        self.device_dispatches = 0
        # eager (non-compiled) dispatch wall: tracked so the split is
        # auditable, but NEVER counted as device time — a stubbed run
        # has no device, and its host-tax must read 1.0
        self.eager_ns = 0
        self.eager_dispatches = 0
        self.host_ns: dict[str, int] = {b: 0 for b in HOST_BUCKETS}
        self.transfer: dict[str, dict[str, int]] = {
            H2D: {"bytes": 0, "ns": 0, "count": 0},
            D2H: {"bytes": 0, "ns": 0, "count": 0},
        }
        self.tiles = 0
        # scrape-time delta marks for the mirrored counters (the
        # flight-recorder idiom — see instruments.bind_server_collectors)
        self.scrape_mirrored: dict[str, int] = {}

    # -- seams -------------------------------------------------------------

    def note_dispatch(
        self,
        elapsed_s: float,
        *,
        tier: str = "scan",
        role: str = "worker",
        device: bool = True,
    ) -> None:
        """One device dispatch's bracketed wall time. ``device=False``
        (an eager/stub processor — nothing ran on a chip) keeps the
        time out of ``device_ns``."""
        ns = _to_ns(elapsed_s)
        with self._lock:
            if device:
                self.device_ns += ns
                self.device_dispatches += 1
            else:
                self.eager_ns += ns
                self.eager_dispatches += 1
        if device:
            try:
                from .instruments import device_execute_seconds

                device_execute_seconds().observe(
                    float(elapsed_s), role=role, tier=tier
                )
            except Exception:  # noqa: BLE001 - accounting is best effort
                pass

    def note_host(self, bucket: str, elapsed_s: float) -> None:
        """Host-side wall time in one of the gather/encode/ship
        buckets; unknown buckets are ignored (the stage vocabulary can
        grow without version-locking the ledger)."""
        if bucket not in self.host_ns:
            return
        ns = _to_ns(elapsed_s)
        with self._lock:
            self.host_ns[bucket] += ns

    def note_transfer(
        self, direction: str, nbytes: int, elapsed_s: float = 0.0
    ) -> None:
        """Bytes crossing the device↔host boundary (``h2d``/``d2h``)
        plus the transfer's wall time when the caller measured it."""
        entry = self.transfer.get(direction)
        if entry is None:
            return
        with self._lock:
            entry["bytes"] += max(0, int(nbytes))
            entry["ns"] += _to_ns(elapsed_s)
            entry["count"] += 1

    def note_tiles(self, n: int = 1) -> None:
        with self._lock:
            self.tiles += int(n)

    @contextlib.contextmanager
    def timed_sync(self, *, bucket: str = "gather"):
        """Bracket a host-side materialisation (a ``device_get`` /
        ``block_until_ready`` sync point) on the ledger's clock; the
        elapsed wall charges ``bucket``."""
        started = self.clock()
        try:
            yield
        finally:
            self.note_host(bucket, self.clock() - started)

    # -- roll-ups ----------------------------------------------------------

    def host_total_ns(self) -> int:
        with self._lock:
            return sum(self.host_ns.values())

    def host_tax(self) -> float:
        """``host_ns / (host_ns + device_ns)``. A run that never
        touched a device (device_ns == 0 — eager stubs, CPU fallbacks
        that recorded nothing) reports 1.0: all attributable time was
        host time. Never NaN."""
        with self._lock:
            host = sum(self.host_ns.values())
            device = self.device_ns
        if device <= 0:
            return 1.0
        return host / float(host + device)

    def snapshot(self, role: str = "worker") -> dict[str, Any]:
        """The cumulative wire block (fleet snapshot v3 piggyback).
        All integers except the derived ratio."""
        with self._lock:
            return {
                "role": role,
                "device_ns": self.device_ns,
                "device_dispatches": self.device_dispatches,
                "eager_ns": self.eager_ns,
                "eager_dispatches": self.eager_dispatches,
                "host_ns": dict(self.host_ns),
                "transfer": {
                    d: dict(v) for d, v in self.transfer.items()
                },
                "tiles": self.tiles,
                "host_tax": self._host_tax_locked(),
            }

    def _host_tax_locked(self) -> float:
        host = sum(self.host_ns.values())
        if self.device_ns <= 0:
            return 1.0
        return host / float(host + self.device_ns)

    def totals(self, role: str = "worker") -> dict[str, Any]:
        snap = self.snapshot(role)
        snap["host_total_ns"] = sum(snap["host_ns"].values())
        return snap


def merge_profiling_blocks(blocks: list) -> dict[str, Any]:
    """Sum snapshot() wire blocks into one fleet-level profiling
    roll-up (telemetry/fleet.py rollup). Malformed blocks contribute
    nothing; the derived host-tax follows the same zero-device rule."""
    device_ns = 0
    host_ns = {b: 0 for b in HOST_BUCKETS}
    transfer = {
        H2D: {"bytes": 0, "ns": 0, "count": 0},
        D2H: {"bytes": 0, "ns": 0, "count": 0},
    }
    dispatches = 0
    tiles = 0
    for block in blocks:
        if not isinstance(block, dict):
            continue
        try:
            device_ns += int(block.get("device_ns") or 0)
            dispatches += int(block.get("device_dispatches") or 0)
            tiles += int(block.get("tiles") or 0)
            for bucket in HOST_BUCKETS:
                host_ns[bucket] += int(
                    (block.get("host_ns") or {}).get(bucket) or 0
                )
            for direction in (H2D, D2H):
                src = (block.get("transfer") or {}).get(direction) or {}
                for field in ("bytes", "ns", "count"):
                    transfer[direction][field] += int(src.get(field) or 0)
        except (TypeError, ValueError):
            continue
    host_total = sum(host_ns.values())
    tax = 1.0 if device_ns <= 0 else host_total / float(host_total + device_ns)
    return {
        "device_ns": device_ns,
        "device_dispatches": dispatches,
        "host_ns": host_ns,
        "host_total_ns": host_total,
        "transfer": transfer,
        "tiles": tiles,
        "host_tax": tax,
    }


# --- on-demand jax.profiler capture ----------------------------------------

_CAPTURE_DIR_RE = re.compile(r"trace-(\d{4,})(?:-[a-z0-9_]+)?")
_TAG_SAFE_RE = re.compile(r"[^a-z0-9_]+")


class ProfilerCapture:
    """Single-flight on-demand device trace capture with bounded
    retention. One capture at a time; a start while one is active
    answers ``busy`` (never a second ``start_trace`` — TensorBoard's
    tracer is process-global). Captures auto-stop at their duration cap
    via a daemon timer, so an operator who never POSTs /stop cannot
    leave the profiler running."""

    def __init__(
        self,
        directory: str,
        *,
        clock: Callable[[], float] = time.monotonic,
        max_seconds: Optional[float] = None,
        max_captures: Optional[int] = None,
        max_bytes: Optional[float] = None,
    ) -> None:
        self.directory = directory
        self.clock = clock
        self.max_seconds = (
            float(max_seconds)
            if max_seconds is not None
            else constants.PROFILE_MAX_SECONDS
        )
        self.max_captures = (
            int(max_captures)
            if max_captures is not None
            else constants.PROFILE_MAX_CAPTURES
        )
        self.max_bytes = (
            int(max_bytes)
            if max_bytes is not None
            else int(constants.PROFILE_MAX_MB * 1024 * 1024)
        )
        self._lock = threading.Lock()
        self._active: Optional[dict[str, Any]] = None
        self._timer: Optional[threading.Timer] = None
        self._seq = self._scan_seq()
        self.counters = {
            "started": 0, "stopped": 0, "busy": 0, "errors": 0,
            "auto_stopped": 0,
        }
        # scrape-time delta marks for the mirrored counters (the
        # flight-recorder idiom — see instruments.bind_server_collectors)
        self.scrape_mirrored: dict[str, int] = {}

    # -- capture lifecycle -------------------------------------------------

    def start(
        self, duration_s: Optional[float] = None, tag: str = "manual"
    ) -> dict[str, Any]:
        """Begin a capture; returns the disposition dict the route
        serves verbatim. Duration is clamped to the cap; the auto-stop
        timer fires even if nobody ever calls stop()."""
        duration = self.max_seconds
        if duration_s is not None:
            try:
                duration = float(duration_s)
            except (TypeError, ValueError):
                return {"started": False, "reason": "bad_duration"}
        duration = max(0.1, min(duration, self.max_seconds))
        tag_safe = _TAG_SAFE_RE.sub("_", str(tag).lower())[:32] or "manual"
        with self._lock:
            if self._active is not None:
                self.counters["busy"] += 1
                return {
                    "started": False,
                    "reason": "busy",
                    "active": self._active["id"],
                }
            self._seq += 1
            capture_id = f"trace-{self._seq:04d}-{tag_safe}"
            path = os.path.join(self.directory, capture_id)
            try:
                os.makedirs(path, exist_ok=True)
                import jax

                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(path, profiler_options=options)
                # the two clocks a reader needs to place spans fetched
                # over /distributed/trace/<id> on the capture's axis
                tracer_clock_s, unix_ns = get_tracer().now(), time.time_ns()
                set_span_annotator(_annotate_span)
            except Exception as exc:  # noqa: BLE001 - degrade, never 500
                self.counters["errors"] += 1
                with contextlib.suppress(OSError):
                    os.rmdir(path)
                return {"started": False, "reason": f"{type(exc).__name__}: {exc}"}
            self._active = {
                "id": capture_id,
                "path": path,
                "tag": tag_safe,
                "duration_s": duration,
                "started_at": self.clock(),
            }
            self.counters["started"] += 1
            timer = threading.Timer(duration, self._auto_stop, args=(capture_id,))
            timer.daemon = True
            timer.start()
            self._timer = timer
            return {
                "started": True,
                "id": capture_id,
                "path": path,
                "duration_s": duration,
                "tracer_clock_s": tracer_clock_s,
                "unix_ns": unix_ns,
                **_write_spans_before(path),
            }

    def stop(self) -> dict[str, Any]:
        """End the active capture (idempotent: no active capture
        answers ``stopped: False``); prunes retention afterwards."""
        with self._lock:
            active = self._active
            self._active = None
            timer, self._timer = self._timer, None
        if timer is not None:
            timer.cancel()
        if active is None:
            return {"stopped": False, "reason": "not_running"}
        elapsed = self.clock() - active["started_at"]
        set_span_annotator(None)
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception as exc:  # noqa: BLE001 - report, don't raise
            with self._lock:
                self.counters["errors"] += 1
            return {
                "stopped": False,
                "id": active["id"],
                "reason": f"{type(exc).__name__}: {exc}",
            }
        with self._lock:
            self.counters["stopped"] += 1
        self._prune()
        return {
            "stopped": True,
            "id": active["id"],
            "path": active["path"],
            "elapsed_s": round(elapsed, 6),
            "bytes": _dir_bytes(active["path"]),
        }

    def _auto_stop(self, capture_id: str) -> None:
        """Timer callback: stop only if the SAME capture is still
        active (a manual stop + fresh start must not be killed by the
        old capture's timer)."""
        with self._lock:
            active = self._active
            if active is None or active["id"] != capture_id:
                return
            self.counters["auto_stopped"] += 1
        result = self.stop()
        debug_log(f"profiler capture {capture_id} auto-stopped: {result}")

    # -- retention / listing -----------------------------------------------

    def _scan_seq(self) -> int:
        """Resume the capture sequence past existing dirs so ids never
        collide across restarts (deterministic: derived from the sorted
        listing, not a clock)."""
        seq = 0
        try:
            names = sorted(os.listdir(self.directory))
        except OSError:
            return 0
        for name in names:
            match = _CAPTURE_DIR_RE.fullmatch(name)
            if match:
                seq = max(seq, int(match.group(1)))
        return seq

    def _capture_dirs(self) -> list[tuple[str, str]]:
        """(name, path) pairs oldest-first — zero-padded sequence ids
        make lexical order capture order."""
        try:
            names = sorted(os.listdir(self.directory))
        except OSError:
            return []
        return [
            (name, os.path.join(self.directory, name))
            for name in names
            if _CAPTURE_DIR_RE.fullmatch(name)
            and os.path.isdir(os.path.join(self.directory, name))
        ]

    def _prune(self) -> None:
        dirs = self._capture_dirs()
        with self._lock:
            active_path = self._active["path"] if self._active else None
        sizes = {path: _dir_bytes(path) for _name, path in dirs}
        total = sum(sizes.values())
        while len(dirs) > 1 and (
            len(dirs) > self.max_captures
            or (self.max_bytes > 0 and total > self.max_bytes)
        ):
            _name, oldest = dirs.pop(0)
            if oldest == active_path:
                continue
            total -= sizes.get(oldest, 0)
            shutil.rmtree(oldest, ignore_errors=True)

    def captures(self) -> list[dict[str, Any]]:
        """Newest-first index of retained trace dirs."""
        out = []
        for name, path in reversed(self._capture_dirs()):
            out.append({"id": name, "bytes": _dir_bytes(path)})
        return out

    def status(self) -> dict[str, Any]:
        with self._lock:
            active = dict(self._active) if self._active else None
            counters = dict(self.counters)
        if active is not None:
            active["elapsed_s"] = round(
                self.clock() - active.pop("started_at"), 6
            )
        return {
            "directory": self.directory,
            "active": active,
            "max_seconds": self.max_seconds,
            "max_captures": self.max_captures,
            "max_bytes": self.max_bytes,
            "counters": counters,
        }


def _dir_bytes(path: str) -> int:
    total = 0
    try:
        for root, _dirs, files in os.walk(path):
            for name in files:
                with contextlib.suppress(OSError):
                    total += os.path.getsize(os.path.join(root, name))
    except OSError:
        return total
    return total


# --- process-global accessors (telemetry/usage.py's meter idiom) -----------

_ledger: TransferLedger | None = None
_ledger_lock = threading.Lock()


def get_transfer_ledger() -> TransferLedger:
    """The process-global ledger (created on first use). Callers gate
    on ``constants.PROFILING_ENABLED`` — the ledger itself is always
    constructible so tests can meter with the knob off."""
    global _ledger
    with _ledger_lock:
        if _ledger is None:
            _ledger = TransferLedger()
        return _ledger


def peek_transfer_ledger() -> TransferLedger | None:
    """The global ledger if one exists — never creates (scrape-time
    mirrors must not allocate state on an idle process)."""
    with _ledger_lock:
        return _ledger


def set_transfer_ledger(
    ledger: TransferLedger | None,
) -> TransferLedger | None:
    """Install a specific ledger (chaos harness, tests); returns the
    previous one so callers can restore it."""
    global _ledger
    with _ledger_lock:
        prev = _ledger
        _ledger = ledger
        return prev


def _reset_transfer_ledger_for_tests() -> None:
    set_transfer_ledger(None)


def ledger_if_enabled() -> TransferLedger | None:
    """The global ledger when CDT_PROFILING is on, else None — the one
    call hot seams make (a disabled plane costs one attribute read and
    a None check)."""
    if not constants.PROFILING_ENABLED:
        return None
    return get_transfer_ledger()


_capture: ProfilerCapture | None = None
_capture_lock = threading.Lock()


def get_profiler_capture() -> ProfilerCapture | None:
    """The process-global capture manager, or None when
    CDT_PROFILE_DIR is unset (the incident-dir idiom: no directory, no
    capture plane). Constructed lazily on first enabled call."""
    global _capture
    with _capture_lock:
        if _capture is not None:
            return _capture
        directory = constants.profile_dir_from_env()
        if not directory:
            return None
        os.makedirs(directory, exist_ok=True)
        _capture = ProfilerCapture(directory)
        return _capture


def set_profiler_capture(
    capture: ProfilerCapture | None,
) -> ProfilerCapture | None:
    global _capture
    with _capture_lock:
        prev = _capture
        _capture = capture
        return prev


def _reset_profiler_capture_for_tests() -> None:
    set_profiler_capture(None)
