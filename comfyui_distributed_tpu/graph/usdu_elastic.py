"""Elastic-tier USDU: master/worker tile-queue loops over HTTP.

The cross-host protocol of the reference (reference
upscale/modes/static.py + upscale/worker_comms.py), for participants
that are NOT part of the local mesh (other hosts, heterogeneous
boxes, cloud pods):

  worker: poll job ready (warming the tile-processor compile in the
          background) → pipelined pull/sample/encode/submit stages
          (graph/tile_pipeline.py): placement grants run as vmapped
          K-tile device batches, the next grant's sampling dispatches
          while the previous grant's results ride the tunnel back,
          heartbeats flow from the I/O stage → final flush
  master: init queue → pull speed-sized grants, batch-sample, blend
          locally while draining worker results → on drain, collection
          phase with heartbeat-timeout requeue (busy-probe grace) →
          local fallback for requeued tiles → blend

Because per-tile noise keys fold the global tile index
(ops/upscale.py), a tile re-run after requeue is bit-identical — no
seam drift from fault recovery; batching/pipelining change WHO and
WHEN, never the per-tile inputs.

The worker side talks through a WorkClient so hermetic tests can
script the exchange without sockets (the reference's fake-comms test
pattern, reference tests/test_static_mode.py).
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models import pipeline as pl
from ..ops import samplers as smp
from ..ops import tiles as tile_ops
from ..ops import upscale as upscale_ops
from ..utils import image as img_utils
from ..utils.async_helpers import run_async_in_server_loop
from ..utils.constants import (
    FLEET_SNAPSHOT_SECONDS,
    MAX_PAYLOAD_SIZE,
    MAX_TILE_BATCH,
    PAYLOAD_HEADROOM,
    PIPELINE_ENABLED,
    PUSH_GRANTS_ENABLED,
    PUSH_WAIT_SECONDS,
    QUEUE_POLL_INTERVAL_SECONDS,
    SCHED_MAX_PULL_BATCH,
    WARM_COMPILE,
    tile_scan_batch,
)
from ..resilience.policy import (
    http_policy,
    poll_ready_policy,
    retry_async,
    transport_errors,
    work_pull_policy,
)
from ..telemetry import TRACE_HEADER, current_trace_id
from ..telemetry.instruments import tiles_processed_total
from ..utils.exceptions import TransientServerError, WorkerError
from ..utils.logging import debug_log, log
from ..utils.network import (
    build_worker_url,
    get_client_session,
    parse_master_urls,
    probe_worker,
)
from .tile_pipeline import GrantSampler, TilePipeline, stage_span as _stage


# --------------------------------------------------------------------------
# worker side
# --------------------------------------------------------------------------


# Heartbeat suppression schedule after consecutive failures (satellite
# of the failover PR): a master outage must not turn every worker into
# a 1-failure-per-tile log/request flood while the pull path is already
# doing the patient retrying.
HEARTBEAT_BACKOFF_BASE_SECONDS = 1.0
HEARTBEAT_BACKOFF_CAP_SECONDS = 30.0


class HTTPWorkClient:
    """Worker → master RPCs (reference upscale/worker_comms.py).

    Every RPC retries through the shared RetryPolicy
    (resilience/policy.py): fixed-interval for the readiness poll,
    patient capped exponential for the work pull, and the default HTTP
    policy for submissions (safe — the master drops duplicate results,
    so a retried submit whose first attempt actually landed is a no-op).

    High availability:

    - `master_url` may be a comma-separated address list (active first,
      standbys after). `FAILOVER_AFTER_ERRORS` (2) consecutive transport/5xx
      failures against the current address re-point to another — the
      re-pointed worker's next pull/heartbeat re-advertises its
      capacity, so the promoted master's placement policy re-learns the
      fleet with no extra registration RPC. Address health is tracked
      PER URL (scheduler/router.EndpointRotation): a failed address
      sits out an exponential backoff window and re-pointing prefers
      the address that last reported the highest fencing epoch, so a
      dead/lagging shard address can't throttle pulls against healthy
      ones — the old single rotation cursor punished the whole list
      for one address's outage;
    - every RPC response carries the master's fencing `epoch`; the
      client remembers the highest seen and stamps it on every mutating
      RPC. A 409 `stale_epoch` rejection (our authority predates a
      takeover) refreshes the epoch from the rejection body and lets
      the retry policy re-send — live workers heal in one round-trip,
      while a zombie master that REFUSES to adopt the new epoch stays
      rejected (jobs/store.py `_check_epoch`).
    """

    def __init__(
        self, master_url: str, job_id: str, worker_id: str, devices: int = 1
    ):
        from ..scheduler.router import EndpointRotation, ShardRouter

        self.urls = parse_master_urls(master_url) or [str(master_url)]
        # Region mode (CDT_SHARDS on the worker): this job's shard is a
        # pure function of its id, so the client re-binds to the shard's
        # own address list (active + standby) — a worker running jobs
        # from different shards multiplexes pulls across masters, and
        # one shard's outage backs off only that shard's endpoints.
        shard_router = ShardRouter.from_env()
        if shard_router.enabled:
            self.urls = parse_master_urls(
                shard_router.addresses_for(job_id)
            ) or self.urls
        self._endpoints = EndpointRotation(self.urls)
        self.job_id = job_id
        self.worker_id = worker_id
        # Advertised grant capacity (the worker mesh's data-axis width):
        # rides every pull and heartbeat so the master's placement
        # policy scales this worker's grants by its chip count.
        self.devices = max(1, int(devices))
        # Captured at construction (on the executor thread, where the
        # dispatched prompt's trace is active); RPCs run on the server
        # loop where that context is NOT set.
        self.trace_id = current_trace_id()
        # Fencing epoch: learned from responses, monotonic, attached to
        # every mutating RPC. None until the master reports one.
        self.epoch: Optional[int] = None
        # Lifecycle armor: flipped when the master reports the job
        # cancelled (a pull response with `cancelled: true`); the
        # worker loop's interrupt check reads it so an in-flight
        # pipeline aborts between batches instead of draining grants.
        self.job_cancelled = False
        self.cancel_reason = ""
        # Step-level preemption (xjob tier): flipped when a pull or
        # heartbeat response carries `preempt: true` — the executor
        # checkpoints + releases this job's in-flight tiles at the next
        # step boundary; cleared when a response stops carrying it.
        self.preempt_requested = False
        self.preempt_reason = ""
        # Remaining end-to-end deadline (seconds) as of the last pull
        # response; None = no deadline on this job.
        self.deadline_remaining: Optional[float] = None
        # Adapter plane: the job's resolved wire plan ([{name, strength,
        # content_hash}]) captured from the readiness poll. The worker
        # re-resolves it against its local catalog (hash-verified) and
        # samples with the segmented/patched params. [] = base model.
        self.adapters: list = []
        self.failovers = 0
        # Heartbeat backoff state (consecutive failures → suppression
        # window); guarded by nothing — heartbeats run on one thread
        # (the pipeline's I/O stage).
        self._hb_failures = 0
        self._hb_suppressed_until = 0.0
        # Fleet telemetry piggyback: a compact versioned snapshot of
        # this process's metrics rides at most one pull/heartbeat per
        # FLEET_SNAPSHOT_SECONDS (telemetry/fleet.local_snapshot).
        # <= 0 disables the piggyback entirely.
        self._telemetry_interval = FLEET_SNAPSHOT_SECONDS
        self._telemetry_last = float("-inf")  # the first RPC carries one

    @property
    def master_url(self) -> str:
        return self._endpoints.current

    def _maybe_telemetry(self) -> Optional[dict]:
        """The fleet snapshot to piggyback on this RPC, or None when
        one rode recently (or the piggyback is disabled). Runs on the
        single RPC-issuing thread; building the snapshot is a pure
        metrics-registry read. Never raises — telemetry must not break
        the work protocol."""
        if self._telemetry_interval <= 0:
            return None
        now = time.monotonic()
        if now - self._telemetry_last < self._telemetry_interval:
            return None
        self._telemetry_last = now
        try:
            from ..telemetry.fleet import local_snapshot

            return local_snapshot(role="worker")
        except Exception as exc:  # noqa: BLE001 - advisory payload only
            debug_log(f"fleet snapshot build failed: {exc}")
            return None

    def _learn_epoch(self, value) -> None:
        try:
            epoch = int(value)
        except (TypeError, ValueError):
            return
        if epoch <= 0:
            return
        # per-URL: the rotation remembers which address reported which
        # epoch, so re-pointing prefers the freshest (promoted) master
        self._endpoints.learn_epoch(epoch)
        if self.epoch is None or epoch > self.epoch:
            self.epoch = epoch

    def _learn_preempt(self, out: dict) -> None:
        """Track the master's per-job preemption flag from any RPC
        response that carries it (pull + heartbeat); absence clears —
        the flag is live scheduling pressure, not a latch."""
        self.preempt_requested = bool(out.get("preempt"))
        self.preempt_reason = str(out.get("preempt_reason", ""))

    def _count_error(self, op: str) -> None:
        """One master-RPC failure: counted per operation, and after
        FAILOVER_AFTER_ERRORS consecutive failures against the current
        address the rotation re-points (no-op with a single address).
        The failed address enters its per-URL backoff window, so the
        rotation won't land back on it while a healthy address exists."""
        from ..telemetry.instruments import (
            failover_total,
            worker_master_errors_total,
        )

        worker_master_errors_total().inc(op=op)
        previous = self.master_url
        if self._endpoints.note_failure():
            self.failovers += 1
            failover_total().inc(role="worker")
            log(
                f"worker {self.worker_id}: master {previous} unreachable "
                f"({op}); re-pointing to {self.master_url}"
            )

    async def _post(self, path: str, payload: dict, op: str = "transport") -> dict:
        session = await get_client_session()
        headers = {TRACE_HEADER: self.trace_id} if self.trace_id else {}
        if self.epoch is not None:
            payload = {**payload, "epoch": self.epoch}
        try:
            async with session.post(
                f"{self.master_url}{path}", json=payload, headers=headers
            ) as resp:
                if resp.status == 409:
                    # stale fencing epoch: a takeover happened. Refresh
                    # from the rejection and let the retry policy
                    # re-send with the new epoch (one extra round-trip).
                    try:
                        body = await resp.json()
                    except Exception:  # noqa: BLE001 - non-JSON 409
                        body = {}
                    if body.get("error") == "stale_epoch":
                        # the address answered: healthy, just ahead of us
                        self._endpoints.note_success()
                        self._learn_epoch(body.get("current_epoch"))
                        raise TransientServerError(
                            f"{path} -> stale epoch (refreshed to "
                            f"{self.epoch})", self.worker_id,
                        )
                    raise WorkerError(
                        f"{path} -> HTTP {resp.status}", self.worker_id
                    )
                if resp.status >= 500:
                    self._count_error(op)
                    raise TransientServerError(
                        f"{path} -> HTTP {resp.status}", self.worker_id
                    )
                if resp.status != 200:
                    raise WorkerError(f"{path} -> HTTP {resp.status}", self.worker_id)
                out = await resp.json()
        except transport_errors() as exc:
            self._count_error(op)
            raise exc
        self._endpoints.note_success()
        if isinstance(out, dict):
            self._learn_epoch(out.get("epoch"))
        return out

    def poll_ready(self) -> bool:
        async def attempt():
            out = await self._post(
                "/distributed/job_status",
                {"job_id": self.job_id, "worker_id": self.worker_id},
                op="status",
            )
            if not out.get("ready"):
                raise WorkerError(f"job {self.job_id} not ready", self.worker_id)
            self.adapters = list(out.get("adapters") or [])
            return True

        async def poll():
            try:
                return await retry_async(
                    attempt, poll_ready_policy(),
                    label=f"poll_ready:{self.job_id}",
                )
            except Exception:  # noqa: BLE001 - not-ready maps to False
                return False

        return run_async_in_server_loop(poll(), timeout=None)

    def request_tile(self, batch_max: int = 1) -> Optional[dict]:
        """Pull next work item; None when drained (or the master stayed
        unreachable through the whole pull policy). `batch_max` > 1
        opts into the master's speed-weighted batch pulls — the
        response then carries `tile_idxs` (placement-sized, ≤
        batch_max) alongside the compatible single `tile_idx`."""

        async def pull():
            payload = {
                "job_id": self.job_id,
                "worker_id": self.worker_id,
                "devices": self.devices,
            }
            if batch_max > 1:
                payload["batch_max"] = int(batch_max)
            snapshot = self._maybe_telemetry()
            if snapshot is not None:
                payload["telemetry"] = snapshot
            try:
                return await retry_async(
                    lambda: self._post(
                        "/distributed/request_image", payload, op="pull"
                    ),
                    work_pull_policy(),
                    # patient with an unreachable or failing master; a
                    # 4xx is its verdict. "No such job" comes after the
                    # master's own init grace, so it means the job is
                    # over — a worker that arrives late (still loading
                    # its model while a warm master finished alone) must
                    # leave at once, not hold its prompt queue for ten
                    # backed-off retries while the next job goes by
                    retryable=self._retryable_failures(),
                    label=f"request_tile:{self.worker_id}",
                )
            except Exception as exc:  # noqa: BLE001 - exhausted retries
                debug_log(f"request_tile gave up: {exc}")
                return None

        out = run_async_in_server_loop(pull(), timeout=None)
        if out is None:
            return None
        if out.get("cancelled"):
            self.job_cancelled = True
            self.cancel_reason = str(out.get("cancel_reason", ""))
            return None
        self._learn_preempt(out)
        if "deadline_remaining" in out:
            try:
                self.deadline_remaining = float(out["deadline_remaining"])
            except (TypeError, ValueError):
                pass
        if out.get("tile_idx") is None and out.get("image_idx") is None:
            return None
        return out

    # Pulls and submits retry transport failures and 5xx answers only —
    # a 4xx is the master's verdict (bad job id, malformed entry) and
    # re-sending the same payload can't change it.
    def _retryable_failures(self):
        return transport_errors() + (TransientServerError,)

    def submit_tiles(self, entries: list[dict], is_final: bool) -> None:
        async def send():
            await retry_async(
                lambda: self._post(
                    "/distributed/submit_tiles",
                    {
                        "job_id": self.job_id,
                        "worker_id": self.worker_id,
                        "tiles": entries,
                        "is_final_flush": is_final,
                    },
                    op="submit",
                ),
                http_policy(),
                retryable=self._retryable_failures(),
                label=f"submit_tiles:{self.worker_id}",
            )

        run_async_in_server_loop(send(), timeout=300)

    def submit_image(self, image_idx: int, data_url: str, is_last: bool) -> None:
        """Dynamic mode: push one whole processed frame."""

        async def send():
            await retry_async(
                lambda: self._post(
                    "/distributed/submit_image",
                    {
                        "job_id": self.job_id,
                        "worker_id": self.worker_id,
                        "image_idx": image_idx,
                        "image": data_url,
                        "is_last": is_last,
                    },
                    op="submit",
                ),
                http_policy(),
                retryable=self._retryable_failures(),
                label=f"submit_image:{self.worker_id}",
            )

        run_async_in_server_loop(send(), timeout=300)

    def heartbeat(self) -> None:
        """Best-effort liveness beat — with exponential suppression on
        consecutive failures: the pipeline heartbeats once per tile
        plus idle beats, so during a master outage an unsuppressed
        worker fleet is a log/request flood on top of the pull path's
        own (already patient) retrying. After k consecutive failures
        beats are skipped for min(base*2^(k-1), cap) seconds; the first
        success resets the schedule. Failures count into
        cdt_worker_master_errors_total and into the failover rotation
        like any other master RPC error."""
        now = time.monotonic()
        if now < self._hb_suppressed_until:
            return

        async def beat():
            payload = {
                "job_id": self.job_id,
                "worker_id": self.worker_id,
                "devices": self.devices,
            }
            snapshot = self._maybe_telemetry()
            if snapshot is not None:
                payload["telemetry"] = snapshot
            try:
                out = await self._post(
                    "/distributed/heartbeat", payload, op="heartbeat",
                )
                if isinstance(out, dict):
                    # the eviction side-channel: a worker mid-batch may
                    # be many steps from its next pull
                    self._learn_preempt(out)
            except Exception as exc:  # noqa: BLE001 - heartbeats best-effort
                self._hb_failures += 1
                backoff = min(
                    HEARTBEAT_BACKOFF_BASE_SECONDS
                    * (2.0 ** (self._hb_failures - 1)),
                    HEARTBEAT_BACKOFF_CAP_SECONDS,
                )
                self._hb_suppressed_until = time.monotonic() + backoff
                debug_log(
                    f"heartbeat failed ({self._hb_failures} consecutive; "
                    f"suppressing {backoff:.1f}s): {exc}"
                )
            else:
                self._hb_failures = 0
                self._hb_suppressed_until = 0.0

        run_async_in_server_loop(beat(), timeout=30)

    def return_tiles(
        self, tile_idxs: list[int], checkpoints: Optional[dict] = None
    ) -> None:
        """Hand claimed-but-unprocessed tiles back to the master (an
        interrupted in-flight grant, or a preemption eviction) so they
        requeue immediately instead of waiting out the heartbeat
        timeout. ``checkpoints`` (xjob tier) attaches per-tile sampler
        state so a re-granted tile resumes mid-trajectory. Best
        effort: if the master is unreachable, its timeout requeue
        still covers these tiles (recompute-from-0 stays
        bit-identical)."""

        async def send():
            payload: dict = {
                "job_id": self.job_id,
                "worker_id": self.worker_id,
                "tile_idxs": [int(t) for t in tile_idxs],
            }
            if checkpoints:
                payload["checkpoints"] = {
                    str(t): c for t, c in sorted(checkpoints.items())
                }
            try:
                await self._post(
                    "/distributed/return_tiles", payload, op="release",
                )
            except Exception as exc:  # noqa: BLE001 - best effort
                debug_log(f"return_tiles failed: {exc}")

        run_async_in_server_loop(send(), timeout=30)


class GrantSignal:
    """Push-mode grant wakeups (CDT_PUSH_GRANTS): the worker holds the
    master's `/distributed/events` WebSocket (filtered to
    `grant_available`/`job_ready`/`job_complete`) and flips a thread
    Event whenever grants land, so the pull loop wakes the instant work
    exists instead of discovering it on a poll boundary — that is the
    grant-RTT cut — and parks while the queue is dry instead of burning
    empty request_image round-trips — that is the idle-poll cut.

    Strictly an ACCELERATOR over the pull protocol: grants still
    transfer via request_image (push carries availability, never
    assignment, so placement sizing/fencing/first-result-wins are
    untouched), and every failure mode — WS refused, stream dropped,
    master failed over — degrades to exactly the pull behavior. The
    socket follows the client's failover rotation via `url_provider`.
    """

    def __init__(self, url_provider, job_id: str):
        self.url_provider = url_provider
        self.job_id = job_id
        self._event = threading.Event()
        self._stopped = threading.Event()
        self.connected = False
        self._complete = False
        self._cancelled = False
        self._future = None

    # --- worker-thread side ------------------------------------------------

    def wait_for_grant(self, timeout: float) -> bool:
        """Park until a grant_available lands (True) or `timeout`
        passes (False); clears the flag so the next wait needs a new
        push. Never blocks when the stream is down — pull fallback."""
        if not self.connected:
            return False
        fired = self._event.wait(timeout)
        self._event.clear()
        return fired

    @property
    def job_complete(self) -> bool:
        return self._complete

    @property
    def job_cancelled(self) -> bool:
        """A pushed ``job_cancelled`` frame arrived: the worker's
        interrupt check aborts the pipeline between batches (flush
        what's encoded, hand the rest back) without waiting for the
        next pull round-trip."""
        return self._cancelled

    def start(self) -> None:
        from ..utils.async_helpers import get_server_loop

        loop = get_server_loop()
        if loop is None or not loop.is_running():
            return  # no loop, no stream: pure pull mode
        import asyncio as _asyncio

        self._future = _asyncio.run_coroutine_threadsafe(self._run(), loop)

    def stop(self) -> None:
        self._stopped.set()
        future = self._future
        if future is not None:
            future.cancel()
            self._future = None

    # --- server-loop side --------------------------------------------------

    async def _run(self) -> None:
        import asyncio as _asyncio
        import json as _json

        from aiohttp import WSMsgType

        while not self._stopped.is_set():
            url = self.url_provider()
            try:
                session = await get_client_session()
                async with session.ws_connect(
                    f"{url}/distributed/events"
                    "?types=grant_available,job_ready,job_complete,"
                    "job_cancelled",
                    heartbeat=30,
                ) as ws:
                    self.connected = True
                    async for msg in ws:
                        if self._stopped.is_set():
                            return
                        if msg.type != WSMsgType.TEXT:
                            break
                        try:
                            frame = _json.loads(msg.data)
                        except (TypeError, ValueError):
                            continue
                        data = frame.get("data") or {}
                        if data.get("job_id") not in (None, self.job_id):
                            continue
                        kind = frame.get("type")
                        if kind in ("grant_available", "job_ready"):
                            self._event.set()
                        elif kind == "job_cancelled":
                            self._cancelled = True
                            self._complete = True
                            self._event.set()
                            return
                        elif kind == "job_complete":
                            self._complete = True
                            self._event.set()
                            return
            except _asyncio.CancelledError:
                return
            except Exception as exc:  # noqa: BLE001 - degrade to pull
                debug_log(f"grant signal stream to {url} failed: {exc}")
            finally:
                self.connected = False
            if self._stopped.is_set():
                return
            # the pull path keeps working meanwhile; reconnect follows
            # the client's (possibly rotated) master address
            await _asyncio.sleep(1.0)


def _flush_threshold_bytes() -> int:
    return MAX_PAYLOAD_SIZE - PAYLOAD_HEADROOM


def _make_pull(client: Any):
    """Zero-arg pull callable for the worker loop, resolved ONCE per
    client: batched grants when the client's request_tile accepts
    batch_max, plain otherwise (scripted test clients predate it). The
    capability check reads the signature — catching TypeError from the
    call itself would mask a real client bug AND double-pull work the
    master already assigned."""
    import inspect

    try:
        params = inspect.signature(client.request_tile).parameters
        supports_batch = "batch_max" in params or any(
            p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values()
        )
    except (TypeError, ValueError):
        supports_batch = True  # unintrospectable callable: assume current API
    if supports_batch:
        # the pull ceiling scales with advertised capacity: a D-chip
        # worker may claim D x the max grant (the master's placement
        # policy sizes the actual batch; this is just the client cap)
        cap = max(1, int(getattr(client, "devices", 1)))
        return lambda: client.request_tile(batch_max=SCHED_MAX_PULL_BATCH * cap)
    return client.request_tile


def run_worker_loop(
    bundle: pl.PipelineBundle,
    image,
    pos,
    neg,
    job_id: str,
    worker_id: str,
    master_url: str,
    upscale_by: float,
    tile: int,
    padding: int,
    steps: int,
    sampler: str,
    scheduler: str,
    cfg: float,
    denoise: float,
    seed: int,
    upscale_method: str = "bicubic",
    mask_blur: int = 0,
    uniform: bool = True,
    tiled_decode: bool = False,
    tile_h: int | None = None,
    context=None,
    client: Any = None,
    mesh: Any = None,
) -> None:
    """Pull grants until the master's queue drains, through the staged
    tile pipeline (graph/tile_pipeline.py): placement grants execute as
    vmapped K-tile device batches (shape-bucketed so ragged tails never
    recompile), readback/encode/submit overlap the next batch's
    sampling, and results flush in size-aware batches with a heartbeat
    per processed tile (plus idle heartbeats while a device batch is in
    flight). CDT_PIPELINE=0 falls back to fully synchronous staging
    (same callbacks, no prefetch/overlap threads).

    Multi-chip: the worker builds a local device mesh (CDT_MESH_SHAPE /
    CDT_TP_SIZE; default = all local chips on the data axis on
    accelerators) and scales its tile batch by the data-axis width — a
    4-chip worker dispatches K x 4 tiles per sharded batch and
    advertises 4x grant capacity to the master's placement policy.
    Checkpoints over the CDT_MESH_HBM_GB per-chip budget shard their
    parameters along the model axis instead of failing to load."""
    from ..utils.constants import xjob_batch_enabled

    if xjob_batch_enabled():
        from ..ops.stepwise import stepwise_supported

        if stepwise_supported(sampler):
            # cross-job continuous batching (CDT_XJOB_BATCH=1): this
            # job registers with the process-shared executor and its
            # tiles share device batches with every other registered
            # job; unsupported samplers fall through to the scan tier
            from ..ops.stepwise import StepwiseUnsupported
            from .batch_executor import run_worker_xjob

            try:
                return run_worker_xjob(
                    bundle, image, pos, neg, job_id, worker_id, master_url,
                    upscale_by, tile, padding, steps, sampler, scheduler,
                    cfg, denoise, seed, upscale_method=upscale_method,
                    mask_blur=mask_blur, uniform=uniform,
                    tiled_decode=tiled_decode, tile_h=tile_h,
                    context=context, client=client, mesh=mesh,
                )
            except StepwiseUnsupported as exc:
                # the stepwise factory refused (e.g. flow model +
                # ancestral sampler) BEFORE any job state was touched:
                # the scan tier serves the job. Any other error from a
                # RUNNING xjob job propagates — re-running the whole
                # job here would double-compute it.
                debug_log(f"xjob tier unavailable for {job_id}: {exc}")

    from ..parallel.mesh import (
        advertised_capacity,
        data_axis_size,
        note_serving_mesh,
        worker_mesh,
    )
    from ..parallel.sharding import maybe_shard_params, params_byte_size

    params = bundle.params
    if mesh is None:
        mesh = worker_mesh(params_bytes=params_byte_size(params))
    note_serving_mesh(mesh)
    capacity = advertised_capacity(mesh)
    client = client or HTTPWorkClient(
        master_url, job_id, worker_id, devices=capacity
    )
    params = maybe_shard_params(params, mesh)

    _, grid, extracted = upscale_ops.prepare_upscaled_tiles(
        image, upscale_by, tile, padding, upscale_method, tile_h,
        mask_blur=mask_blur, uniform=uniform,
    )
    pos = upscale_ops.prep_cond_for_tiles(pos, grid)
    neg = upscale_ops.prep_cond_for_tiles(neg, grid)
    process = _jit_tile_processor(
        bundle, grid, steps, sampler, scheduler, cfg, denoise, tiled_decode
    )
    key = jax.random.key(seed)
    positions = grid.positions_array()
    data_width = data_axis_size(mesh) if mesh is not None else 1
    grant_sampler = GrantSampler(
        process, params, extracted, key, positions, pos, neg,
        k_max=tile_scan_batch() * data_width, role="worker", mesh=mesh,
        job_id=job_id,
    )

    # Warm the tile-processor compile while the ready poll waits on the
    # master: with the persistent compilation cache hot this turns the
    # first compile into a cache load that finishes before the first
    # grant arrives.
    warm = None
    if WARM_COMPILE:
        warm = threading.Thread(
            target=grant_sampler.warmup, name="cdt-usdu-warmup", daemon=True
        )
        warm.start()
    if not client.poll_ready():
        raise WorkerError(f"job {job_id} never became ready", worker_id)
    if warm is not None:
        warm.join()

    # Adapter plane (whole-grant variant): the readiness poll carried
    # the job's resolved wire plan. Re-resolve against the LOCAL
    # catalog — resolve() hash-verifies master-stamped hashes against
    # local bytes, failing loudly on divergence — then patch the
    # weights once and rebuild the sampler around them. Shapes/dtypes
    # are unchanged, so the warmup's compiled processor is reused.
    adapter_wire = getattr(client, "adapters", None) or []
    if adapter_wire:
        from ..adapters import (
            bundle_target_map,
            get_adapter_catalog,
            operands_for_plan,
            patch_params as _adapter_patch,
            specs_from_wire,
        )
        from ..telemetry.instruments import adapter_jobs_total

        adapter_specs = get_adapter_catalog().resolve(
            specs_from_wire(adapter_wire)
        )
        adapter_ops = operands_for_plan(
            adapter_specs, bundle_target_map(bundle)
        )
        params = _adapter_patch(params, adapter_ops)
        grant_sampler = GrantSampler(
            process, params, extracted, key, positions, pos, neg,
            k_max=tile_scan_batch() * data_width, role="worker", mesh=mesh,
            job_id=job_id,
        )
        adapter_jobs_total().inc(tier="elastic")

    pending: list[dict] = []
    pending_bytes = 0

    def emit(tile_idx: int, arr) -> None:
        """One processed tile (host-side [B, h, w, C]) → pending
        entries. Runs on the pipeline's I/O stage."""
        nonlocal pending_bytes
        for batch_idx in range(arr.shape[0]):
            encoded = img_utils.encode_image_data_url(arr[batch_idx])
            y, x = grid.positions[tile_idx]
            pending.append(
                {
                    "tile_idx": tile_idx,
                    "batch_idx": batch_idx,
                    "global_idx": tile_idx * arr.shape[0] + batch_idx,
                    "x": int(x),
                    "y": int(y),
                    "extracted_w": grid.padded_w,
                    "extracted_h": grid.padded_h,
                    "image": encoded,
                }
            )
            pending_bytes += len(encoded)
        tiles_processed_total().inc(role="worker")

    def flush(is_final: bool) -> None:
        """Size-aware flush: ships when the payload budget or tile
        batch fills, or unconditionally on the final flush (an empty
        final flush still signals this worker done)."""
        nonlocal pending, pending_bytes
        if not is_final and (
            len(pending) < MAX_TILE_BATCH
            and pending_bytes < _flush_threshold_bytes()
        ):
            return
        if pending or is_final:
            # worker_id keys this span to the same (role, worker_id)
            # group as the sample/readback/encode spans — perf_report's
            # overlap column intersects per pipeline, and submit is the
            # I/O stage the overlap mostly consists of
            with _stage("submit", "worker", worker_id=worker_id):
                client.submit_tiles(pending, is_final)
        pending, pending_bytes = [], 0

    # Adaptive pull batches: the master's placement policy sizes each
    # grant by this worker's measured speed (scheduler/placement.py),
    # replacing the fixed per-pull split — a fast worker amortizes the
    # pull RPC over several tiles, a slow one stays at one so a requeue
    # never orphans a big claim. A master without the batch field
    # answers with a single tile_idx and the loop degrades to the
    # historical one-at-a-time pull.
    pull_work = _make_pull(client)

    # Push-mode grants (CDT_PUSH_GRANTS): hold the master's event
    # stream and, after an empty pull, park one PUSH_WAIT on the grant
    # signal before concluding the queue is drained — requeued/
    # speculated tiles reach this worker instead of defaulting to the
    # master's local fallback, and no empty poll requests burn while
    # the queue is dry. Scripted test clients (no master_url) and
    # CDT_PUSH_GRANTS=0 keep the pure pull protocol.
    push: Optional[GrantSignal] = None
    if PUSH_GRANTS_ENABLED and getattr(client, "master_url", None):
        push = GrantSignal(lambda: client.master_url, job_id)
        push.start()

    def _grant_ids(work: dict) -> list[int]:
        return [int(t) for t in (work.get("tile_idxs") or [work["tile_idx"]])]

    def _cancelled() -> bool:
        return bool(
            getattr(client, "job_cancelled", False)
            or (push is not None and push.job_cancelled)
        )

    def pull() -> Optional[list[int]]:
        if _cancelled():
            return None  # cancelled: no push-park, no further claims
        work = pull_work()
        if work is not None:
            return _grant_ids(work)
        if push is not None and not push.job_complete:
            if push.wait_for_grant(PUSH_WAIT_SECONDS):
                work = pull_work()
                if work is not None:
                    return _grant_ids(work)
        return None

    def check_abort() -> None:
        """Interrupt seam between batches: the dispatched prompt's
        interrupt, OR a cooperative job cancellation (pushed over the
        events stream or learned from a pull response). Raising
        InterruptedError routes through the pipeline's graceful path —
        flush what's encoded, hand the claimed remainder back via
        return_tiles — exactly the PR 5 interrupt semantics."""
        if context is not None:
            context.check_interrupted()
        if _cancelled():
            reason = getattr(client, "cancel_reason", "") or "cancelled"
            raise InterruptedError(
                f"job {job_id} cancelled by master ({reason})"
            )

    pipeline = TilePipeline(
        pull=pull,
        sample=grant_sampler.sample,
        chunks=grant_sampler.chunks,
        # sharded batches gather host-side via host_collect; unsharded
        # ones take the plain numpy path (identical to the default)
        to_host=grant_sampler.collect,
        emit=emit,
        flush=flush,
        heartbeat=client.heartbeat,
        check_interrupted=check_abort,
        release=getattr(client, "return_tiles", None),
        role="worker",
        # per-pipeline span grouping: perf_report's overlap column
        # intersects sample/I-O spans per (role, worker_id) so fleet
        # parallelism never reads as pipelining in merged traces
        span_attrs={"worker_id": worker_id} if worker_id else None,
        threaded=PIPELINE_ENABLED,
    )
    try:
        pipeline.run()
    except InterruptedError:
        if not _cancelled():
            raise  # a real interrupt (SIGTERM drain / client abort)
        # cooperative cancellation is a CLEAN exit for the worker: the
        # pipeline already flushed what was encoded and returned the
        # claimed remainder via return_tiles
        log(f"worker {worker_id}: job {job_id} cancelled; aborted cleanly")
    finally:
        if push is not None:
            push.stop()


def _jit_tile_processor(bundle, grid, steps, sampler, scheduler, cfg, denoise,
                        tiled_decode=False):
    """fn(params, tile, key, pos, neg, yx): pos/neg must be prepped via
    ops.upscale.prep_cond_for_tiles (per-tile hint/mask windows are
    sliced at yx inside). One compiled function per signature, kept
    across jobs like the scan tier's: a new jax.jit per job re-traces
    and re-fetches its program in every process for every job."""
    return _tile_processor(
        pl._Static(bundle), grid, int(steps), str(sampler), str(scheduler),
        float(cfg), float(denoise), bool(tiled_decode),
    )


@functools.lru_cache(maxsize=8)
def _tile_processor(bundle_static, grid, steps, sampler, scheduler, cfg,
                    denoise, tiled_decode):
    bundle = bundle_static.value
    param, shift = pl.model_schedule_info(bundle)
    sigmas = smp.get_model_sigmas(
        param, scheduler, steps, denoise=denoise, flow_shift=shift
    )

    @jax.jit
    def process(params, tile, key, pos, neg, yx):
        pos_t = upscale_ops.tile_cond(pos, yx[0], yx[1], grid)
        neg_t = upscale_ops.tile_cond(neg, yx[0], yx[1], grid)
        z = bundle.vae.apply(params["vae"], tile, method="encode")
        noise_key, anc_key = jax.random.split(key)
        x = smp.noise_latents(
            param, z, jax.random.normal(noise_key, z.shape), sigmas[0]
        )
        model_fn = pl.guided_model(bundle, params, cfg)
        z_out = smp.sample(
            model_fn, x, sigmas, (pos_t, neg_t), sampler, anc_key,
            flow=(param == "flow"),
        )
        if tiled_decode:
            from ..ops.tiled_vae import decode_tiled

            return decode_tiled(pl._Static(bundle), params["vae"], z_out)
        return bundle.vae.apply(params["vae"], z_out, method="decode")

    return process


# --------------------------------------------------------------------------
# master side
# --------------------------------------------------------------------------


def run_master_elastic(
    bundle: pl.PipelineBundle,
    image,
    pos,
    neg,
    job_id: str,
    enabled_worker_ids: list[str],
    mesh=None,
    upscale_by: float = 2.0,
    tile: int = 512,
    padding: int = 32,
    steps: int = 20,
    sampler: str = "euler",
    scheduler: str = "karras",
    cfg: float = 7.0,
    denoise: float = 0.35,
    seed: int = 0,
    upscale_method: str = "bicubic",
    mask_blur: int = 0,
    uniform: bool = True,
    tiled_decode: bool = False,
    tile_h: int | None = None,
    context=None,
):
    """Master participates in the tile queue and collects worker tiles.

    Returns the blended [B, H, W, C] image. Fault tolerance: stale
    workers' tiles are requeued (busy-probe grace) and re-run locally.
    """
    from ..utils.constants import xjob_batch_enabled

    if xjob_batch_enabled():
        from ..ops.stepwise import stepwise_supported

        if stepwise_supported(sampler):
            # cross-job continuous batching (CDT_XJOB_BATCH=1): the
            # master's own participation rides the shared executor so
            # its tiles batch with every other registered job's
            from ..ops.stepwise import StepwiseUnsupported
            from .batch_executor import run_master_xjob

            try:
                return run_master_xjob(
                    bundle, image, pos, neg, job_id, enabled_worker_ids,
                    mesh=mesh, upscale_by=upscale_by, tile=tile,
                    padding=padding, steps=steps, sampler=sampler,
                    scheduler=scheduler, cfg=cfg, denoise=denoise,
                    seed=seed, upscale_method=upscale_method,
                    mask_blur=mask_blur, uniform=uniform,
                    tiled_decode=tiled_decode, tile_h=tile_h,
                    context=context,
                )
            except StepwiseUnsupported as exc:
                # raised by _prep_xjob before the job inits; any error
                # from a RUNNING xjob master propagates (the job was
                # already initialized/cleaned — re-running would
                # double-compute it against exited workers)
                debug_log(f"xjob tier unavailable for {job_id}: {exc}")

    from ..utils.config import get_worker_timeout_seconds

    server = context.server
    store = server.job_store
    upscaled, grid, extracted = upscale_ops.prepare_upscaled_tiles(
        image, upscale_by, tile, padding, upscale_method, tile_h,
        mask_blur=mask_blur, uniform=uniform,
    )
    pos = upscale_ops.prep_cond_for_tiles(pos, grid)
    neg = upscale_ops.prep_cond_for_tiles(neg, grid)
    process = _jit_tile_processor(
        bundle, grid, steps, sampler, scheduler, cfg, denoise, tiled_decode
    )
    key = jax.random.key(seed)
    positions = grid.positions_array()

    # HTTP-tier tiles arrive host-side; the native feathered-blend
    # canvas avoids a device round-trip per tile. CDT_DETERMINISTIC_BLEND
    # defers compositing to sorted tile order so the blended output is
    # bit-identical regardless of which participant finished first
    # (chaos tests assert fault-free vs fault-recovered runs equal).
    # Routing rule (CDT_DEVICE_CANVAS=1): master-local grants skip the
    # per-tile readback entirely and composite on-device — one d2h for
    # the whole composited canvas at the end of the run. Remote worker
    # tiles keep the PNG path and upload once into the device canvas.
    # Cache population needs host tile bytes at blend time, so the
    # device canvas only engages while the tile cache is off.
    import os as _os

    from ..cache import get_tile_cache as _get_tile_cache
    from ..utils.constants import device_canvas_enabled as _device_canvas_enabled

    # get_tile_cache (not the env knob alone) so a run-locally
    # installed cache — the chaos harness's swap — also disables it
    device_canvas = _device_canvas_enabled() and _get_tile_cache() is None
    if device_canvas:
        canvas = tile_ops.DeviceCanvas(upscaled, grid)
    elif _os.environ.get("CDT_DETERMINISTIC_BLEND") == "1":
        canvas = tile_ops.DeterministicHostCanvas(upscaled, grid)
    else:
        canvas = tile_ops.HostIncrementalCanvas(upscaled, grid)
    done_tiles: set[int] = set()
    timeout = get_worker_timeout_seconds()

    # Adapter plane: the orchestration parked the resolved wire plan in
    # the store — peek it (non-destructive; init_tile_job pops +
    # journals it) and build the whole-grant operands for this master's
    # own sampling. The plan key joins the cache key below; the PATCHED
    # params feed only the GrantSampler.
    adapter_ops = None
    adapter_key = None
    adapter_wire = run_async_in_server_loop(
        store.peek_job_adapters(job_id), timeout=30
    )
    if adapter_wire:
        from ..adapters import (
            adapter_plan_key,
            bundle_target_map,
            get_adapter_catalog,
            operands_for_plan,
            specs_from_wire,
        )
        from ..telemetry.instruments import adapter_jobs_total

        adapter_specs = get_adapter_catalog().resolve(
            specs_from_wire(adapter_wire)
        )
        adapter_key = adapter_plan_key(adapter_specs)
        adapter_ops = operands_for_plan(
            adapter_specs, bundle_target_map(bundle)
        )
        adapter_jobs_total().inc(tier="elastic")

    # --- content-addressed tile cache (cache/), CDT_CACHE=1 ----------
    # The elastic tier keys on the UNFOLDED base key jax.random.key(seed):
    # per-tile keys fold only the global tile index, so two jobs (any
    # tenant) with identical sampler inputs dedup against each other.
    # UNPATCHED params on purpose: the adapter's identity enters
    # through `adapter=` (the plan key), keeping one params fingerprint
    # per checkpoint while flipping every tile key per plan.
    from ..cache import bind_job_cache, job_key_context, tile_keys_for
    from ..utils.constants import USAGE_ENABLED

    cache_binding = bind_job_cache(
        lambda: tile_keys_for(
            job_key_context(
                bundle.params, pos, neg, key, grid,
                steps=steps, sampler=sampler, scheduler=scheduler,
                cfg=cfg, denoise=denoise, upscale_by=upscale_by,
                upscale_method=upscale_method, mask_blur=mask_blur,
                uniform=uniform, tiled_decode=tiled_decode,
                adapter=adapter_key,
            ),
            extracted, grid,
        )
    )

    def blend_local(tile_idx: int, result) -> None:
        with _stage("blend", "master", tile_idx):
            y, x = grid.positions[tile_idx]
            if cache_binding is not None:
                # one host materialisation serves both the write-back
                # and the host canvas blend below
                result = np.asarray(result)
                cache_binding.populate(tile_idx, result)
            canvas.blend(result, y, x)
            done_tiles.add(tile_idx)

    # Probe BEFORE the job exists, settle ATOMICALLY with its creation
    # (init_tile_job's cache_settled): hits complete in the store
    # (journaled `cache_settle`, pending queue shrunken under the same
    # lock hold) before any puller can observe the job — a warm run's
    # settled count is deterministic, never a race the master usually
    # wins. Hits blend from cached pixels at ~zero chip-time. On a
    # pre-existing job (recovery re-entry) creation ignored the list,
    # so fall back to the standalone op, which excludes tiles workers
    # already completed — those must NOT be blended again (the canvas
    # accumulates weight).
    cached_hits: dict[int, Any] = {}
    if cache_binding is not None:
        with _stage("cache.probe", "master") as probe_span:
            cached_hits = cache_binding.probe()
            probe_span.attrs["hits"] = len(cached_hits)
    job = run_async_in_server_loop(
        store.init_tile_job(
            job_id, list(range(grid.num_tiles)),
            cache_settled=sorted(cached_hits) if cached_hits else None,
        ),
        timeout=30,
    )
    if cached_hits:
        settled = [t for t in sorted(cached_hits) if t in job.cached_tiles]
        if not settled:
            settled = run_async_in_server_loop(
                store.settle_cached(job_id, sorted(cached_hits)), timeout=30
            )
        for tile_idx in settled:
            with _stage("cache.hit", "master", tile_idx):
                y, x = grid.positions[tile_idx]
                canvas.blend(cached_hits[tile_idx], y, x)
                done_tiles.add(tile_idx)
        if settled:
            cache_binding.cache.note_settled(len(settled))
            if USAGE_ENABLED:
                from ..telemetry.usage import get_usage_meter

                get_usage_meter().note_cached(
                    "master", job_id, len(settled)
                )

    def drain_results() -> None:
        async def drain():
            job = await store.get_tile_job(job_id)
            items = []
            while job is not None and not job.results.empty():
                items.append(job.results.get_nowait())
            return items

        for tile_idx, payload in run_async_in_server_loop(drain(), timeout=30):
            if tile_idx in done_tiles:
                continue
            with _stage("decode", "master", tile_idx):
                batch = [
                    img_utils.decode_image_data_url(e["image"])
                    for e in sorted(payload, key=lambda e: e["batch_idx"])
                ]
            blend_local(tile_idx, jnp.asarray(np.stack(batch, axis=0)))

    async def probe_busy(worker_id: str) -> bool:
        config = getattr(context, "config", None) or {}
        worker = next(
            (w for w in config.get("workers", []) if str(w.get("id")) == worker_id),
            None,
        )
        if worker is None:
            return False
        result = await probe_worker(build_worker_url(worker))
        return bool(result["online"] and (result["queue_remaining"] or 0) > 0)

    # --- main pull/process loop ---
    # The master pulls speed-sized grants through the same placement-
    # hooked path workers use (scheduler/placement sizes them; without
    # a policy the batch is 1 — the historical single pull) and runs
    # each grant through the bucketed vmapped K-tile processor. Tiles
    # are recorded via submit_flush so the latency sink sees per-tile
    # AMORTIZED service times, not one per-batch lump followed by
    # near-zero gaps (the watchdog's straggler median and the placement
    # speed EWMA both consume that stream).
    from ..parallel.mesh import data_axis_size as _data_axis_size
    from ..parallel.mesh import note_serving_mesh as _note_serving_mesh

    _note_serving_mesh(mesh)
    master_data_width = _data_axis_size(mesh) if mesh is not None else 1
    # the master's own chip count must reach the placement policy the
    # same way workers' does: its submit_flush latencies are amortized
    # D x lower, so without this per_chip_ratio("master") reads ~D x
    # inflated and batch sizing favors a wide-but-mediocre master.
    # worker_capacity is written only from the server loop (store.py),
    # so hop there like every other store call in this function.
    async def _note_master_capacity() -> None:
        store.note_worker_capacity("master", master_data_width)

    run_async_in_server_loop(_note_master_capacity())
    # Whole-grant adapter application (the scan tier's simpler variant):
    # every tile of every grant wears the same plan, so patch the
    # weights ONCE — same shapes/dtypes, so the compiled tile processor
    # is reused — and sample with the unchanged program.
    master_params = bundle.params
    if adapter_ops is not None:
        from ..adapters import patch_params as _adapter_patch

        master_params = _adapter_patch(master_params, adapter_ops)
    grant_sampler = GrantSampler(
        process, master_params, extracted, key, positions, pos, neg,
        k_max=tile_scan_batch() * master_data_width, role="master",
        mesh=mesh, job_id=job_id,
    )
    empty_pulls = 0
    while empty_pulls < 2:
        if context is not None:
            context.check_interrupted()
        with _stage("pull", "master") as pull_span:
            grant = run_async_in_server_loop(
                store.pull_tasks(
                    job_id, "master", timeout=QUEUE_POLL_INTERVAL_SECONDS
                ),
                timeout=30,
            )
            if not grant:
                pull_span.attrs["outcome"] = "empty"
            else:
                pull_span.attrs["tile_idx"] = int(grant[0])
                if len(grant) > 1:
                    pull_span.attrs["batch"] = [int(t) for t in grant]
        if not grant:
            empty_pulls += 1
            drain_results()
            continue
        empty_pulls = 0
        for chunk in grant_sampler.chunks(grant):
            if context is not None:
                context.check_interrupted()
            with _stage("sample", "master", chunk[0], batch=list(chunk)):
                result = grant_sampler.sample(chunk)
            with _stage("readback", "master", chunk[0], batch=list(chunk)):
                # materialise host-side before blending — sharded
                # results gather across the mesh, single-device ones
                # take the numpy path; either way the d2h transfer is
                # attributed (ledger gather bucket) instead of hiding
                # inside the first blend's implicit conversion. With
                # the device canvas on, unsharded master-local grants
                # stay device-resident (keep_device) and the span reads
                # ~0 — honestly: no readback happened.
                result = grant_sampler.collect(
                    result, keep_device=device_canvas
                )
            run_async_in_server_loop(
                store.submit_flush(
                    job_id, "master",
                    # master blends directly; no payload retained
                    {int(t): None for t in chunk},
                ),
                timeout=30,
            )
            tiles_processed_total().inc(len(chunk), role="master")
            for i, tile_idx in enumerate(chunk):
                blend_local(int(tile_idx), result[i])
            drain_results()

    # --- collection phase ---
    # Lifecycle-aware accounting: poison-quarantined tiles count as
    # SETTLED (the job completes degraded, their region blended from
    # the base image), and a terminal cancellation — client cancel or
    # the deadline sweep — unwinds the loop instead of waiting for
    # tiles that will never arrive.
    from ..utils.exceptions import JobCancelled, JobPoisoned

    def _lifecycle() -> dict:
        state = run_async_in_server_loop(
            store.job_lifecycle(job_id), timeout=30
        )
        return state or {
            "cancelled": False, "cancel_reason": "", "quarantined": [],
        }

    deadline = time.monotonic() + timeout * max(1, len(enabled_worker_ids))
    while True:
        # ONE lifecycle snapshot per iteration: termination reads may
        # be up to a poll interval stale, which only delays exit by
        # that interval — never changes the terminal outcome
        lifecycle = _lifecycle()
        quarantined = set(lifecycle["quarantined"])
        if lifecycle["cancelled"] or (
            len(done_tiles | quarantined) >= grid.num_tiles
        ):
            break
        if context is not None:
            context.check_interrupted()
        # store-side sweep: an overdue deadline cancels the job even
        # with no pull traffic left to trigger the lazy path
        run_async_in_server_loop(store.sweep_deadlines(), timeout=30)
        drain_results()
        if len(done_tiles | quarantined) >= grid.num_tiles:
            break
        requeued = run_async_in_server_loop(
            store.requeue_timed_out(job_id, timeout, probe_busy), timeout=60
        )
        # The pending queue can refill behind our back: heartbeat
        # requeues (above) AND the watchdog's speculative re-dispatch
        # of stalled in-flight tiles both route recovery through it.
        pending_now = run_async_in_server_loop(store.remaining(job_id), timeout=30)
        if requeued or pending_now:
            # Requeued/speculated ids are back in the pending queue;
            # claim them through the same pull path workers use so a
            # surviving worker may still grab some before we do
            # (first result wins; duplicates drop in the store).
            while True:
                with _stage("pull", "master") as pull_span:
                    tile_idx = run_async_in_server_loop(
                        store.pull_task(
                            job_id, "master", timeout=QUEUE_POLL_INTERVAL_SECONDS
                        ),
                        timeout=30,
                    )
                    if tile_idx is None:
                        pull_span.attrs["outcome"] = "empty"
                    else:
                        pull_span.attrs["tile_idx"] = int(tile_idx)
                if tile_idx is None:
                    break
                if tile_idx in done_tiles:
                    continue
                tkey = jax.random.fold_in(key, tile_idx)
                with _stage("sample", "master", tile_idx):
                    result = process(
                        bundle.params, extracted[tile_idx], tkey, pos, neg,
                        positions[tile_idx],
                    )
                run_async_in_server_loop(
                    store.submit_result(job_id, "master", tile_idx, None), timeout=30
                )
                tiles_processed_total().inc(role="master")
                blend_local(tile_idx, result)
        if len(done_tiles | quarantined) >= grid.num_tiles:
            break
        if time.monotonic() > deadline:
            # quarantined tiles are NOT reprocessed locally: a payload
            # that crashed every worker that touched it stays settled
            # degraded rather than taking the master down with it
            missing = sorted(
                set(range(grid.num_tiles)) - done_tiles - quarantined
            )
            log(f"USDU: deadline hit; locally processing {len(missing)} tile(s)")
            for tile_idx in missing:
                tkey = jax.random.fold_in(key, tile_idx)
                with _stage("sample", "master", tile_idx):
                    result = process(
                        bundle.params, extracted[tile_idx], tkey, pos, neg,
                        positions[tile_idx],
                    )
                tiles_processed_total().inc(role="master")
                blend_local(tile_idx, result)
            break
        time.sleep(QUEUE_POLL_INTERVAL_SECONDS)

    lifecycle = _lifecycle()
    run_async_in_server_loop(store.cleanup_tile_job(job_id), timeout=30)
    if lifecycle["cancelled"]:
        # terminal: every pending/in-flight tile was refunded by the
        # cancel; the collector settles with a cancelled status instead
        # of a partial canvas
        raise JobCancelled(job_id, lifecycle["cancel_reason"] or "cancel")
    poisoned = sorted(set(lifecycle["quarantined"]) - done_tiles)
    if poisoned:
        policy = getattr(store, "poison_policy", "degrade")
        if policy == "fail":
            raise JobPoisoned(job_id, poisoned)
        log(
            f"USDU: job {job_id} completes DEGRADED: tile(s) {poisoned} "
            "quarantined (region blended from the base image)"
        )
    if device_canvas:
        # the job's entire master-side pixel traffic rides this ONE
        # composited readback (ledger-attributed); bit-identical to
        # DeterministicHostCanvas by the sorted-compositing guarantee
        from ..telemetry.profiling import D2H as _D2H
        from ..telemetry.profiling import ledger_if_enabled as _ledger_if

        with _stage("readback", "master", tiles=canvas.tile_count):
            started = time.monotonic()
            composited = canvas.result()
            host = np.asarray(composited)  # cdt: noqa[CDT007] - the single composited flush
            ledger = _ledger_if()
            if ledger is not None:
                ledger.note_transfer(
                    _D2H, int(host.nbytes), time.monotonic() - started
                )
        return jnp.asarray(host)
    return canvas.result()


# --------------------------------------------------------------------------
# dynamic (image-queue) mode — large video batches
# --------------------------------------------------------------------------


def _process_whole_image(
    bundle, image_1, pos, neg, grid, process, key, batch_index: int
):  # pos/neg prepped via prep_cond_for_tiles
    """Upscale one [1, H, W, C] frame through all its tiles locally.

    Per-tile keys fold (batch_index, tile_idx) so dynamic mode is
    deterministic per frame regardless of which participant claims it
    (reference upscale/modes/dynamic.py processes a whole image's tiles
    on whichever participant pulled its index).
    """
    extracted = tile_ops.extract_tiles(image_1, grid)
    canvas = tile_ops.IncrementalCanvas(image_1, grid)
    frame_key = jax.random.fold_in(key, batch_index)
    positions = grid.positions_array()
    for tile_idx in range(grid.num_tiles):
        tkey = jax.random.fold_in(frame_key, tile_idx)
        result = process(
            bundle.params, extracted[tile_idx], tkey, pos, neg, positions[tile_idx]
        )
        y, x = grid.positions[tile_idx]
        canvas.blend(result, y, x)
    return canvas.result()


def run_worker_dynamic(
    bundle: pl.PipelineBundle,
    image,
    pos,
    neg,
    job_id: str,
    worker_id: str,
    master_url: str,
    upscale_by: float,
    tile: int,
    padding: int,
    steps: int,
    sampler: str,
    scheduler: str,
    cfg: float,
    denoise: float,
    seed: int,
    upscale_method: str = "bicubic",
    mask_blur: int = 0,
    uniform: bool = True,
    tiled_decode: bool = False,
    tile_h: int | None = None,
    context=None,
    client: Any = None,
) -> None:
    """Pull whole-image indices; process all tiles locally; submit the
    finished frame (reference upscale/modes/dynamic.py:213-313)."""
    client = client or HTTPWorkClient(master_url, job_id, worker_id)
    if not client.poll_ready():
        raise WorkerError(f"job {job_id} never became ready", worker_id)
    upscaled, grid, _ = upscale_ops.prepare_upscaled_tiles(
        image, upscale_by, tile, padding, upscale_method, tile_h,
        mask_blur=mask_blur, uniform=uniform,
    )
    pos = upscale_ops.prep_cond_for_tiles(pos, grid)
    neg = upscale_ops.prep_cond_for_tiles(neg, grid)
    process = _jit_tile_processor(
        bundle, grid, steps, sampler, scheduler, cfg, denoise, tiled_decode
    )
    key = jax.random.key(seed)

    while True:
        if context is not None:
            context.check_interrupted()
        work = client.request_tile()
        if work is None:
            break
        # dynamic jobs return image_idx; HTTPWorkClient.request_tile
        # normalizes on 'tile_idx' absence, so re-read the raw field
        image_idx = int(work.get("image_idx", work.get("tile_idx")))
        frame = upscaled[image_idx : image_idx + 1]
        out = _process_whole_image(
            bundle, frame, pos, neg, grid, process, key, image_idx
        )
        arr = img_utils.ensure_numpy(out)[0]
        client.submit_image(
            image_idx,
            img_utils.encode_image_data_url(arr),
            is_last=int(work.get("estimated_remaining", 0)) == 0,
        )
        client.heartbeat()


def run_master_dynamic(
    bundle: pl.PipelineBundle,
    image,
    pos,
    neg,
    job_id: str,
    enabled_worker_ids: list[str],
    upscale_by: float = 2.0,
    tile: int = 512,
    padding: int = 32,
    steps: int = 20,
    sampler: str = "euler",
    scheduler: str = "karras",
    cfg: float = 7.0,
    denoise: float = 0.35,
    seed: int = 0,
    upscale_method: str = "bicubic",
    mask_blur: int = 0,
    uniform: bool = True,
    tiled_decode: bool = False,
    tile_h: int | None = None,
    context=None,
):
    """Image-queue master loop: master participates in pulls, drains
    worker frames between images, requeues timed-out workers, and
    assembles the output batch in frame order (reference
    upscale/modes/dynamic.py:22-211)."""
    from ..utils.config import get_worker_timeout_seconds

    store = context.server.job_store
    batch = int(image.shape[0])
    upscaled, grid, _ = upscale_ops.prepare_upscaled_tiles(
        image, upscale_by, tile, padding, upscale_method, tile_h,
        mask_blur=mask_blur, uniform=uniform,
    )
    pos = upscale_ops.prep_cond_for_tiles(pos, grid)
    neg = upscale_ops.prep_cond_for_tiles(neg, grid)
    process = _jit_tile_processor(
        bundle, grid, steps, sampler, scheduler, cfg, denoise, tiled_decode
    )
    key = jax.random.key(seed)
    timeout = get_worker_timeout_seconds()

    run_async_in_server_loop(
        store.init_tile_job(job_id, list(range(batch)), batched=False, kind="image"),
        timeout=30,
    )
    frames: dict[int, np.ndarray] = {}

    def drain() -> None:
        async def pop_all():
            job = await store.get_tile_job(job_id)
            items = []
            while job is not None and not job.results.empty():
                items.append(job.results.get_nowait())
            return items

        for image_idx, payload in run_async_in_server_loop(pop_all(), timeout=30):
            if image_idx in frames:
                continue
            frames[image_idx] = img_utils.decode_image_data_url(payload[0]["image"])

    async def probe_busy(worker_id: str) -> bool:
        config = getattr(context, "config", None) or {}
        worker = next(
            (w for w in config.get("workers", []) if str(w.get("id")) == worker_id),
            None,
        )
        if worker is None:
            return False
        result = await probe_worker(build_worker_url(worker))
        return bool(result["online"] and (result["queue_remaining"] or 0) > 0)

    def claim_and_process() -> bool:
        image_idx = run_async_in_server_loop(
            store.pull_task(job_id, "master", timeout=QUEUE_POLL_INTERVAL_SECONDS),
            timeout=30,
        )
        if image_idx is None:
            return False
        out = _process_whole_image(
            bundle, upscaled[image_idx : image_idx + 1], pos, neg, grid,
            process, key, image_idx,
        )
        frames[image_idx] = img_utils.ensure_numpy(out)[0]
        run_async_in_server_loop(
            store.submit_result(job_id, "master", image_idx, None), timeout=30
        )
        drain()
        return True

    while claim_and_process():
        if context is not None:
            context.check_interrupted()

    from ..utils.exceptions import JobCancelled

    deadline = time.monotonic() + timeout * max(1, len(enabled_worker_ids))
    while len(frames) < batch:
        if context is not None:
            context.check_interrupted()
        run_async_in_server_loop(store.sweep_deadlines(), timeout=30)
        state = run_async_in_server_loop(
            store.job_lifecycle(job_id), timeout=30
        )
        if state is not None and state["cancelled"]:
            run_async_in_server_loop(store.cleanup_tile_job(job_id), timeout=30)
            raise JobCancelled(job_id, state["cancel_reason"] or "cancel")
        drain()
        if len(frames) >= batch:
            break
        requeued = run_async_in_server_loop(
            store.requeue_timed_out(job_id, timeout, probe_busy), timeout=60
        )
        # heartbeat requeues or watchdog speculation may have refilled
        # the pending queue; claim through the shared pull path
        pending_now = run_async_in_server_loop(store.remaining(job_id), timeout=30)
        if requeued or pending_now:
            while claim_and_process():
                pass
        if len(frames) >= batch:
            break
        if time.monotonic() > deadline:
            missing = sorted(set(range(batch)) - set(frames))
            log(f"USDU dynamic: deadline hit; processing {len(missing)} frame(s) locally")
            for image_idx in missing:
                out = _process_whole_image(
                    bundle, upscaled[image_idx : image_idx + 1], pos, neg,
                    grid, process, key, image_idx,
                )
                frames[image_idx] = img_utils.ensure_numpy(out)[0]
            break
        time.sleep(QUEUE_POLL_INTERVAL_SECONDS)

    run_async_in_server_loop(store.cleanup_tile_job(job_id), timeout=30)
    stacked = np.stack([frames[i] for i in range(batch)], axis=0)
    return jnp.asarray(stacked)
