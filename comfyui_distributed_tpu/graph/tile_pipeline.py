"""Pipelined, batched tile execution for the elastic USDU tier.

The elastic hot loop used to be fully serial and batch-1: sample one
tile, block on the host readback, PNG-encode, flush over HTTP, and only
then touch the device again. This module decouples those stages:

- **GrantSampler** — runs a placement grant (``tile_idxs``) through a
  vmapped K-tile processor instead of per-tile ``process`` calls.
  Batch-1 convs leave most of a TPU's 128x128 systolic array idle.
  Grant sizes are padded up to a bounded set of shape buckets (powers of two plus
  K_max — ``ops.upscale.grant_buckets``) via the wraparound-duplicate
  trick with folded keys, so a ragged tail never triggers a fresh
  compile mid-job.
- **TilePipeline** — a three-stage pipeline over any grant source:
  pull prefetch (one grant ahead), device sampling (dispatch runs
  ahead of the I/O stage by a bounded number of batches), and host
  readback + encode + submit flush on a dedicated I/O thread. The next
  grant's sampling is dispatched while the previous grant's results
  are read back and shipped — time that previously sat squarely
  between device dispatches. Heartbeats
  flow from the I/O stage — including while a device batch is in
  flight — rather than from per-tile compute.

Determinism: batching and pipelining change WHEN and HOW MANY tiles
share a dispatch, never the per-tile inputs — keys fold the GLOBAL
tile index and the deterministic blend canvas is order-independent, so
the canvas stays bit-identical to the serial path (asserted by
tests/test_chaos_usdu.py parity scenarios).

Interrupt semantics: an interrupted in-flight grant must requeue
cleanly. Claimed-but-unsubmitted tiles are handed to the ``release``
callback on interrupt (InterruptedError by default) so they return to
the pending queue immediately; any other death leaves them to the
master's heartbeat-timeout / watchdog requeue path, exactly like a
crashed worker process.
"""

from __future__ import annotations

import contextlib
import queue
import threading
import time
import weakref
from typing import Any, Callable, Optional, Sequence

from ..telemetry import current_trace_id, get_tracer
from ..telemetry.instruments import (
    pipeline_batches_total,
    pipeline_inflight,
    pipeline_padded_tiles_total,
    tile_stage_seconds,
)
from ..telemetry.profiling import (
    D2H,
    H2D,
    STAGE_HOST_BUCKETS,
    ledger_if_enabled,
    transfer_nbytes,
)
from ..utils.constants import (
    HEARTBEAT_INTERVAL_SECONDS,
    PIPELINE_DEPTH,
    PIPELINE_PREFETCH,
)
from ..utils.logging import debug_log


@contextlib.contextmanager
def stage_span(stage: str, role: str, tile_idx: int | None = None, **attrs):
    """Span + latency histogram around one tile pipeline stage
    (pull | sample | readback | encode | submit | decode | blend). The
    span clock is the tracer's (injectable, deterministic in chaos
    runs); the histogram always uses the wall monotonic clock.

    A pull that drains empty (caller sets ``outcome="empty"`` on the
    yielded span) is excluded from the histogram: empty polls last the
    full poll timeout by construction and would drag the pull stage's
    p95 toward the timeout instead of the real dequeue latency (the
    store's pulls_total{outcome="empty"} counter tracks them)."""
    span_attrs: dict[str, Any] = {"stage": stage, "role": role, **attrs}
    if tile_idx is not None:
        span_attrs["tile_idx"] = int(tile_idx)
    started = time.monotonic()
    span = None
    try:
        with get_tracer().span(f"tile.{stage}", **span_attrs) as span:
            yield span
    finally:
        if span is None or span.attrs.get("outcome") != "empty":
            elapsed = time.monotonic() - started
            tile_stage_seconds().observe(elapsed, stage=stage, role=role)
            # host-tax attribution: readback/encode/submit wall rides
            # into the transfer ledger's gather/encode/ship buckets —
            # ONE seam instruments both execution tiers (the cross-job
            # executor emits the same stage vocabulary)
            bucket = STAGE_HOST_BUCKETS.get(stage)
            if bucket is not None:
                ledger = ledger_if_enabled()
                if ledger is not None:
                    ledger.note_host(bucket, elapsed)


# One batched program per compiled tile processor, shared by every
# GrantSampler built around it. The elastic tier makes a sampler per
# job; a fresh jax.jit per job re-traced and re-fetched a program that
# costs ~90 s of host time on a v5e chip for SDXL (PERF.md, PR 21) — a
# managed worker never got a tile of a 12-second job. Weak keys: the
# program goes when its processor does.
_BATCHED_PROGRAMS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _batched_program(process):
    import jax

    batched = _BATCHED_PROGRAMS.get(process)
    if batched is None:
        batched = jax.jit(
            jax.vmap(process, in_axes=(None, 0, 0, None, None, 0))
        )
        _BATCHED_PROGRAMS[process] = batched
    return batched


class GrantSampler:
    """Bucketed vmapped K-tile processor over a prepared tile set.

    ``process(params, tile, key, pos, neg, yx)`` is the per-tile
    processor (jitted or not — the chaos harness substitutes a stub).
    ``sample(idxs)`` returns the processed tiles ``[n, B, th, tw, C]``:
    serially for ``k_max == 1`` (reference numerics, one dispatch per
    tile) or as ONE vmapped dispatch padded to the grant bucket for
    ``k_max > 1``. Wraparound duplicates share the folded keys of their
    originals, so they compute identical results and the surplus is
    sliced off — numerics never depend on the padding.

    ``mesh``: a local device mesh (parallel/mesh.py) turns each
    bucketed dispatch into a mesh-parallel one — the batch axis is
    sharded across the mesh's data axis with ``NamedSharding``, so a
    D-chip worker computes D tiles' worth of the bucket concurrently
    (and the caller scales ``k_max`` by D: ``tile_scan_batch() × D``).
    Buckets are rounded up to multiples of D so every participant holds
    an equal slice; the extra padding rides the same wraparound-
    duplicate/folded-key idiom, so compile counts stay bounded and
    per-tile outputs stay bit-identical to the single-device path
    (asserted by tests/parallel/test_mesh_tiles.py). ``collect``
    gathers a sharded result host-side via
    ``parallel/collective.host_collect``.
    """

    def __init__(
        self,
        process: Callable,
        params: Any,
        extracted: Any,
        base_key: Any,
        positions: Any,
        pos: Any,
        neg: Any,
        k_max: int = 1,
        role: str = "worker",
        mesh: Any = None,
        job_id: str = "",
        tenant: str = "",
        usage_meter: Any = None,
    ) -> None:
        import jax

        from ..ops.upscale import grant_buckets
        from ..utils.constants import USAGE_ENABLED

        self.process = process
        self.params = params
        self.extracted = extracted
        self.base_key = base_key
        self.positions = positions
        self.pos = pos
        self.neg = neg
        self.k_max = max(1, int(k_max))
        self.role = role
        self.mesh = mesh
        # chip-time attribution (telemetry/usage.py): every sample()
        # dispatch emits a slot-exact usage record charging this job
        # (None = metering disabled)
        self.job_id = str(job_id)
        self.tenant = str(tenant)
        if usage_meter is not None:
            self.usage = usage_meter
        elif USAGE_ENABLED:
            from ..telemetry.usage import get_usage_meter

            self.usage = get_usage_meter()
        else:
            self.usage = None
        self.data_parallel = 1
        self._data_shardings: Optional[tuple] = None
        if mesh is not None:
            from ..parallel.mesh import data_axis_size, mesh_summary
            from ..telemetry.instruments import mesh_devices

            self.data_parallel = max(1, data_axis_size(mesh))
            # gauge the full mesh shape for ANY mesh — a TP-only mesh
            # (data=1, model>1: the over-HBM sharded checkpoint) must
            # still show up on /distributed/metrics
            summary = mesh_summary(mesh)
            for axis in ("data", "model"):
                mesh_devices().set(summary[axis], role=role, axis=axis)
            mesh_devices().set(summary["devices"], role=role, axis="total")
            if self.data_parallel > 1:
                from jax.sharding import NamedSharding, PartitionSpec as P

                from ..parallel.mesh import DATA_AXIS

                # every dispatch must give each participant at least
                # one tile; callers normally pass K x D already
                self.k_max = max(self.k_max, self.data_parallel)
                # batched tiles keep extracted's rank (leading axis
                # becomes the bucket); shard that leading axis only
                ndim = len(getattr(extracted, "shape", (0, 0, 0, 0)))
                self._data_shardings = (
                    NamedSharding(mesh, P(DATA_AXIS, *([None] * (ndim - 1)))),
                    NamedSharding(mesh, P(DATA_AXIS)),  # folded keys
                    NamedSharding(mesh, P(DATA_AXIS, None)),  # yx positions
                )
        if self.data_parallel > 1:
            # round every bucket up to a multiple of the data-axis
            # width so the NamedSharding splits evenly; the set stays
            # bounded (≤ the original bucket count) and the extra
            # padding is wraparound duplicates, numerics-free
            dp = self.data_parallel
            self.buckets = tuple(
                sorted({max(dp, -(-b // dp) * dp) for b in grant_buckets(self.k_max)})
            )
        else:
            self.buckets = grant_buckets(self.k_max)
        # observability + the shape-bucket test: which compiled shapes
        # this job actually exercised, and how much padding it cost
        self.buckets_used: set[int] = set()
        self.padded_tiles = 0
        # device/host attribution (telemetry/profiling.py): a compiled
        # processor's dispatch is device-execute time; an eager stub
        # (chaos harness) never touched a chip, so its dispatches stay
        # out of device_ns and the run's host-tax reads 1.0
        self._device = hasattr(process, "lower")
        self._batched = None
        if self.k_max > 1:
            # jit the batched program only when the per-tile processor
            # is itself a compiled function (production — it always
            # is). Raw Python stubs (the chaos harness) stay eager:
            # XLA's divide-by-constant rewrite perturbs the last ulp
            # relative to the eager serial path, which would break the
            # bit-identical parity the chaos suite asserts.
            self._batched = (
                _batched_program(process)
                if hasattr(process, "lower")
                else jax.vmap(process, in_axes=(None, 0, 0, None, None, 0))
            )

    # --- helpers ----------------------------------------------------------

    def chunks(self, grant: Sequence[int]) -> list[list[int]]:
        """Split a grant into dispatch-sized chunks (<= k_max each)."""
        grant = [int(t) for t in grant]
        return [
            grant[i : i + self.k_max] for i in range(0, len(grant), self.k_max)
        ]

    def _keys_for(self, idxs: Sequence[int]):
        import jax
        import jax.numpy as jnp

        return jax.vmap(lambda g: jax.random.fold_in(self.base_key, g))(
            jnp.asarray(list(idxs))
        )

    def _bucket_for(self, n: int) -> int:
        """Smallest of this sampler's buckets that fits ``n`` tiles
        (mesh-aware: buckets are pre-rounded to multiples of the
        data-axis width)."""
        from ..ops.upscale import bucket_for

        return bucket_for(n, self.k_max, self.buckets)

    def _place(self, tiles, keys, yxs):
        """Pin the batch inputs' leading axis across the mesh's data
        axis. Placement must be identical between warmup and sample —
        jit caches on input shardings, so a replicated warmup would
        compile a program sample() never runs."""
        if self._data_shardings is None:
            return tiles, keys, yxs
        import jax

        tile_s, key_s, yx_s = self._data_shardings
        started = time.monotonic()
        placed = (
            jax.device_put(tiles, tile_s),
            jax.device_put(keys, key_s),
            jax.device_put(yxs, yx_s),
        )
        ledger = ledger_if_enabled()
        if ledger is not None:
            nbytes = sum(transfer_nbytes(a) for a in (tiles, keys, yxs))
            ledger.note_transfer(H2D, nbytes, time.monotonic() - started)
        return placed

    def collect(self, result, keep_device: bool = False):
        """Materialise a sample() result on the host. Sharded results
        gather via parallel/collective.host_collect (cross-device over
        ICI, cross-process over DCN); unsharded results take the plain
        numpy path. Wired as the TilePipeline's ``to_host`` stage.

        ``keep_device=True`` is the device-canvas route (master-local
        grants composite on-device; the flush pays ONE composited d2h
        instead of one readback per tile): the device array is handed
        straight back. Only honoured for unsharded results — a sharded
        result must gather across the mesh regardless."""
        if keep_device and self.data_parallel <= 1:
            return result
        ledger = ledger_if_enabled()
        if self.data_parallel <= 1:
            from ..utils import image as img_utils

            started = time.monotonic()
            host = img_utils.ensure_numpy(result)  # cdt: noqa[CDT007] - the ledger-bracketed readback seam
            if ledger is not None:
                ledger.note_transfer(
                    D2H,
                    int(getattr(host, "nbytes", 0)),
                    time.monotonic() - started,
                )
            return host
        from ..parallel.collective import host_collect
        from ..telemetry.instruments import mesh_gather_seconds

        started = time.monotonic()
        # host_collect notes the d2h transfer on the ledger itself (the
        # seam is shared with nodes_distributed) — no second note here.
        host = host_collect(result)
        mesh_gather_seconds().observe(
            time.monotonic() - started, role=self.role
        )
        return host

    # --- usage attribution ------------------------------------------------

    def _dispatch_span(self, idxs: Sequence[int], real: int, bucket: int):
        """One ``tile.dispatch`` span per device dispatch — the same
        vocabulary the cross-job executor emits, so perf_report's
        batch-fill and --usage columns read both tiers uniformly."""
        attrs: dict[str, Any] = {
            "real": int(real), "bucket": int(bucket), "jobs": 1,
            "device": bool(self._device),
        }
        if self.job_id:
            attrs["slot_jobs"] = {self.job_id: int(real)}
        if self.tenant:
            attrs["slot_tenants"] = {self.tenant: int(real)}
        return stage_span("dispatch", self.role, int(idxs[0]), **attrs)

    def _note_usage(self, elapsed_s: float, real: int, bucket: int) -> None:
        """Slot-exact attribution record for one dispatch: ``real``
        slots charge this job (a scan-tier slot runs a full
        trajectory), wraparound-padding slots charge the padding waste
        bucket; the scan tier has no step granularity, so tiles count
        here too (each real slot IS a finished tile)."""
        if self.usage is None:
            return
        from ..telemetry.usage import SLOT_PADDING, SLOT_REAL

        slots = [{"job_id": self.job_id, "kind": SLOT_REAL}] * int(real) + [
            {"job_id": "", "kind": SLOT_PADDING}
        ] * int(bucket - real)
        self.usage.note_dispatch(
            tier="scan",
            role=self.role,
            elapsed_s=elapsed_s,
            chips=self.data_parallel,
            slots=slots,
        )
        self.usage.note_tiles(self.role, self.job_id, int(real))

    def _note_profiling(self, elapsed_s: float, real: int) -> None:
        """Feed the transfer ledger: dispatch wall goes to device time
        only when a compiled program ran — eager stubs (chaos harness)
        are host work, so they honestly read host_tax = 1.0."""
        ledger = ledger_if_enabled()
        if ledger is None:
            return
        ledger.note_dispatch(
            elapsed_s, tier="scan", role=self.role, device=self._device
        )
        ledger.note_tiles(int(real))

    # --- execution --------------------------------------------------------

    def sample(self, idxs: Sequence[int]):
        """Process ``idxs`` (one chunk, len <= k_max) -> [n, B, ...]."""
        import jax.numpy as jnp

        idxs = [int(t) for t in idxs]
        n = len(idxs)
        # the batches metric records the COMPILED SHAPE that ran (the
        # runbook's recompile-storm triage reads it as "which shapes
        # exist"), not the raw chunk size — ragged chunks pad up to
        # their bucket before dispatch
        if self._batched is None:
            import jax

            pipeline_batches_total().inc(n, role=self.role, bucket="1")
            # direct fold_in (not the vmapped form): byte-identical to
            # the historical serial loop's key derivation
            started = time.monotonic()
            with self._dispatch_span(idxs, real=n, bucket=n):
                outs = [
                    self.process(
                        self.params,
                        self.extracted[i],
                        jax.random.fold_in(self.base_key, i),
                        self.pos,
                        self.neg,
                        self.positions[i],
                    )
                    for i in idxs
                ]
                if self._device and ledger_if_enabled() is not None:
                    # profiling wants honest device-execute wall: JAX
                    # dispatch is async, so block inside the bracket
                    outs = jax.block_until_ready(outs)  # cdt: noqa[CDT007]
            elapsed = time.monotonic() - started
            self._note_usage(elapsed, real=n, bucket=n)
            self._note_profiling(elapsed, real=n)
            self.buckets_used.add(1)
            return jnp.stack(outs, axis=0)
        bucket = self._bucket_for(n)
        reps = -(-bucket // n)
        padded = (idxs * reps)[:bucket]
        sel = jnp.asarray(padded)
        tiles = jnp.take(self.extracted, sel, axis=0)
        keys = self._keys_for(padded)
        yxs = jnp.take(self.positions, sel, axis=0)
        tiles, keys, yxs = self._place(tiles, keys, yxs)
        started = time.monotonic()
        with self._dispatch_span(idxs, real=n, bucket=bucket):
            out = self._batched(
                self.params, tiles, keys, self.pos, self.neg, yxs
            )
            if self._device and ledger_if_enabled() is not None:
                import jax

                out = jax.block_until_ready(out)  # cdt: noqa[CDT007]
        elapsed = time.monotonic() - started
        self._note_usage(elapsed, real=n, bucket=bucket)
        self._note_profiling(elapsed, real=n)
        self.buckets_used.add(bucket)
        pipeline_batches_total().inc(role=self.role, bucket=str(bucket))
        if self.data_parallel > 1:
            from ..telemetry.instruments import mesh_batch_share

            mesh_batch_share().set(
                bucket // self.data_parallel, role=self.role
            )
        if bucket > n:
            self.padded_tiles += bucket - n
            pipeline_padded_tiles_total().inc(bucket - n, role=self.role)
        return out[:n]

    # --- warmup -----------------------------------------------------------

    def warmup(self, buckets: Sequence[int] | None = None) -> None:
        """Compile the tile processor ahead of the first pull (run
        during the worker's ready-poll window, so with a warm
        persistent cache the first grant starts sampling immediately).
        AOT-lowers when the processor supports it; otherwise executes
        one throwaway dispatch per shape. Failures are non-fatal — the
        first real grant just pays the compile like before."""
        import jax.numpy as jnp

        if buckets is None:
            # largest bucket = the steady-state grant shape; 1 = the
            # serial path every deadline/recovery fallback uses
            buckets = (self.buckets[-1],) if self._batched else (1,)
        for bucket in buckets:
            try:
                if self._batched is not None:
                    idxs = [0] * int(bucket)
                    sel = jnp.asarray(idxs)
                    tiles, keys, yxs = self._place(
                        jnp.take(self.extracted, sel, axis=0),
                        self._keys_for(idxs),
                        jnp.take(self.positions, sel, axis=0),
                    )
                    args = (self.params, tiles, keys, self.pos, self.neg, yxs)
                    fn = self._batched
                else:
                    args = (
                        self.params,
                        self.extracted[0],
                        self._keys_for([0])[0],
                        self.pos,
                        self.neg,
                        self.positions[0],
                    )
                    fn = self.process
                lower = getattr(fn, "lower", None)
                if lower is not None:
                    lower(*args).compile()
                else:
                    fn(*args)
            except Exception as exc:  # noqa: BLE001 - warmup is best effort
                debug_log(f"tile-processor warmup (bucket {bucket}) failed: {exc}")


class _Stop:
    pass


_STOP = _Stop()


class TilePipeline:
    """Staged executor over a grant source; see module docstring.

    Callbacks:
      pull()                -> list[int] | None   (None/[] = drained)
      sample(idxs)          -> device result [n, B, ...] (dispatch)
      to_host(result)       -> host ndarray (default: asks the result)
      emit(tile_idx, arr)   per-tile encode/queue (arr is [B, h, w, C])
      flush(final: bool)    submit pending results (thresholds inside)
      heartbeat()           optional liveness ping (I/O stage owns it)
      check_interrupted()   optional; raising stops the pipeline
      release(idxs)         optional; claimed-but-unsubmitted tiles on
                            interrupt (interrupt_types exceptions only)
    """

    def __init__(
        self,
        *,
        pull: Callable[[], Optional[Sequence[int]]],
        sample: Callable[[Sequence[int]], Any],
        emit: Callable[[int, Any], None],
        flush: Callable[[bool], None],
        chunks: Callable[[Sequence[int]], list[list[int]]] | None = None,
        to_host: Callable[[Any], Any] | None = None,
        heartbeat: Callable[[], None] | None = None,
        check_interrupted: Callable[[], None] | None = None,
        release: Callable[[list[int]], None] | None = None,
        interrupt_types: tuple = (InterruptedError,),
        depth: int | None = None,
        prefetch: bool | None = None,
        threaded: bool = True,
        role: str = "worker",
        span_attrs: dict[str, Any] | None = None,
        heartbeat_interval: float | None = None,
    ) -> None:
        self._pull = pull
        self._sample = sample
        self._emit = emit
        self._flush = flush
        self._chunks = chunks or (lambda grant: [list(grant)])
        self._to_host = to_host or self._default_to_host
        self._heartbeat = heartbeat
        self._check_interrupted = check_interrupted
        self._release = release
        self._interrupt_types = tuple(interrupt_types)
        self.depth = max(1, depth if depth is not None else PIPELINE_DEPTH)
        self.threaded = bool(threaded)
        self.prefetch = (
            (PIPELINE_PREFETCH if prefetch is None else bool(prefetch))
            and self.threaded
        )
        self.role = role
        self.span_attrs = dict(span_attrs or {})
        self.heartbeat_interval = (
            heartbeat_interval
            if heartbeat_interval is not None
            else HEARTBEAT_INTERVAL_SECONDS
        )
        self._stop = threading.Event()
        self._errors: list[BaseException] = []
        self._error_lock = threading.Lock()
        self._claimed: list[int] = []
        self._emitted: set[int] = set()
        self.batches = 0
        self.tiles = 0

    # --- plumbing ---------------------------------------------------------

    @staticmethod
    def _default_to_host(result):
        from ..utils import image as img_utils

        # the I/O stage's readback — bracketed by _drain_item's
        # stage_span("readback"), which rides the ledger's host buckets
        return img_utils.ensure_numpy(result)  # cdt: noqa[CDT007]

    def _record_error(self, exc: BaseException) -> None:
        with self._error_lock:
            self._errors.append(exc)
        self._stop.set()

    def _first_error(self) -> Optional[BaseException]:
        with self._error_lock:
            return self._errors[0] if self._errors else None

    def _put(self, q: queue.Queue, item: Any) -> bool:
        """Bounded put that stays responsive to stop; False = stopped."""
        while not self._stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    # --- stages -----------------------------------------------------------

    def _pull_grant(self) -> Optional[list[int]]:
        with stage_span("pull", self.role, **self.span_attrs) as span:
            grant = self._pull()
            if not grant:
                span.attrs["outcome"] = "empty"
                return None
            grant = [int(t) for t in grant]
            span.attrs["tile_idx"] = grant[0]
            if len(grant) > 1:
                span.attrs["batch"] = list(grant)
        return grant

    def _puller_body(self, grant_q: queue.Queue, trace_token: Any) -> None:
        tracer = get_tracer()
        token = tracer.activate(trace_token) if trace_token else None
        try:
            while not self._stop.is_set():
                grant = self._pull_grant()
                if grant is None:
                    self._put(grant_q, _STOP)
                    return
                self._claimed.extend(grant)
                if not self._put(grant_q, grant):
                    return
        except BaseException as exc:  # noqa: BLE001 - forwarded to run()
            self._record_error(exc)
            with contextlib.suppress(queue.Full):
                grant_q.put_nowait(_STOP)
        finally:
            if token is not None:
                tracer.deactivate(token)

    def _io_body(self, work_q: queue.Queue, trace_token: Any) -> None:
        tracer = get_tracer()
        token = tracer.activate(trace_token) if trace_token else None
        try:
            while True:
                try:
                    item = work_q.get(timeout=self.heartbeat_interval)
                except queue.Empty:
                    # drained + stopping (the STOP sentinel can be lost
                    # to a full queue during an abort): exit
                    if self._stop.is_set():
                        return
                    # the device stage is mid-batch (or the puller is
                    # waiting on the master): keep liveness flowing so
                    # a long compile or a big batch never reads as a
                    # dead worker
                    if self._heartbeat is not None:
                        self._heartbeat()
                    continue
                if isinstance(item, _Stop):
                    return
                idxs, result = item
                # +1: the batch just popped is dispatched-but-not-read-
                # back — exactly what this gauge counts; qsize() alone
                # would read 0 through a fully loaded depth-1 pipeline
                pipeline_inflight().set(work_q.qsize() + 1, role=self.role)
                try:
                    self._drain_item(idxs, result)
                finally:
                    work_q.task_done()
                    pipeline_inflight().set(work_q.qsize(), role=self.role)
        except BaseException as exc:  # noqa: BLE001 - forwarded to run()
            self._record_error(exc)
        finally:
            if token is not None:
                tracer.deactivate(token)

    def _drain_item(self, idxs: list[int], result: Any) -> None:
        """Readback + per-tile encode + flush for one device batch.
        The flush callback is consulted after EVERY tile (it applies
        its size thresholds internally), exactly like the historical
        serial loop — consulting it once per K-tile batch would let a
        payload overshoot the size budget by up to K-1 tiles."""
        with stage_span(
            "readback", self.role, idxs[0], batch=list(idxs),
            **self.span_attrs,
        ):
            host = self._to_host(result)
        for i, tile_idx in enumerate(idxs):
            with stage_span(
                "encode", self.role, tile_idx, **self.span_attrs
            ):
                self._emit(tile_idx, host[i])
            self._emitted.add(int(tile_idx))
            self.tiles += 1
            if self._heartbeat is not None:
                self._heartbeat()
            self._flush(False)

    def _sample_chunk(self, chunk: list[int]) -> Any:
        # the cdt_pipeline_batches_total metric is incremented by the
        # GrantSampler (which knows the COMPILED bucket a ragged chunk
        # padded up to); the pipeline only tracks its own batch count
        with stage_span(
            "sample", self.role, chunk[0], batch=list(chunk),
            **self.span_attrs,
        ):
            result = self._sample(chunk)
        self.batches += 1
        return result

    # --- main loop --------------------------------------------------------

    def _run_sync(self) -> None:
        """CDT_PIPELINE=0 fallback: the same stages, strictly serial on
        the calling thread — the historical loop shape, batching aside."""
        while True:
            if self._check_interrupted is not None:
                self._check_interrupted()
            grant = self._pull_grant()
            if grant is None:
                return
            self._claimed.extend(grant)
            for chunk in self._chunks(grant):
                if self._check_interrupted is not None:
                    self._check_interrupted()
                result = self._sample_chunk(chunk)
                self._drain_item(list(chunk), result)

    def _run_threaded(self) -> None:
        trace_token = current_trace_id()
        work_q: queue.Queue = queue.Queue(maxsize=self.depth)
        io_thread = threading.Thread(
            target=self._io_body,
            args=(work_q, trace_token),
            name="cdt-tile-io",
            daemon=True,
        )
        io_thread.start()
        grant_q: queue.Queue = queue.Queue(maxsize=1)
        puller: Optional[threading.Thread] = None
        if self.prefetch:
            puller = threading.Thread(
                target=self._puller_body,
                args=(grant_q, trace_token),
                name="cdt-tile-pull",
                daemon=True,
            )
            puller.start()
        try:
            while True:
                if self._check_interrupted is not None:
                    self._check_interrupted()
                if self._first_error() is not None:
                    break
                if puller is not None:
                    try:
                        grant = grant_q.get(timeout=0.1)
                    except queue.Empty:
                        continue
                    if isinstance(grant, _Stop):
                        break
                else:
                    grant = self._pull_grant()
                    if grant is None:
                        break
                    self._claimed.extend(grant)
                for chunk in self._chunks(grant):
                    if self._check_interrupted is not None:
                        self._check_interrupted()
                    if self._first_error() is not None:
                        break
                    result = self._sample_chunk(chunk)
                    if not self._put(work_q, (list(chunk), result)):
                        break
                if self._first_error() is not None:
                    break
        except BaseException as exc:
            self._record_error(exc)
        finally:
            self._stop.set()
            # deliver the sentinel even when the queue is momentarily
            # full — losing it would stall shutdown for a whole idle
            # heartbeat interval
            while io_thread.is_alive():
                try:
                    work_q.put(_STOP, timeout=0.1)
                    break
                except queue.Full:
                    continue
            io_thread.join(timeout=30)
            if puller is not None:
                puller.join(timeout=30)
            pipeline_inflight().set(0, role=self.role)

    def run(self) -> dict[str, Any]:
        """Run the pipeline until the grant source drains; returns
        summary stats. Raises the first stage error (a puller fault, an
        I/O submit failure, an interrupt) after shutting the stages
        down; on interrupt-type errors, claimed-but-unsubmitted tiles
        are handed to ``release`` first so they requeue immediately."""
        if self.threaded:
            self._run_threaded()
        else:
            try:
                self._run_sync()
            except BaseException as exc:  # noqa: BLE001 - unified exit below
                self._record_error(exc)

        error = self._first_error()
        if error is None:
            # drained cleanly: the final flush marks this worker done
            self._flush(True)
            return {"batches": self.batches, "tiles": self.tiles}
        if isinstance(error, self._interrupt_types):
            # Graceful interrupt: ship what is already encoded (those
            # tiles count as emitted), then hand every claimed-but-
            # unsubmitted tile back so the master requeues it NOW
            # instead of waiting out the heartbeat timeout. Any other
            # death (crash, fault) leaves recovery to the master's
            # requeue/watchdog paths, exactly like a dead process.
            try:
                self._flush(True)
            except Exception as exc:  # noqa: BLE001 - best effort
                debug_log(f"final flush after interrupt failed: {exc}")
            if self._release is not None:
                orphaned = sorted(set(self._claimed) - self._emitted)
                if orphaned:
                    try:
                        self._release(orphaned)
                    except Exception as exc:  # noqa: BLE001 - best effort
                        debug_log(f"grant release after interrupt failed: {exc}")
        raise error
