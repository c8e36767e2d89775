"""Job routes: orchestration entry + collector result ingestion.

Route parity with reference api/job_routes.py:
    POST /distributed/queue         — REST orchestration entry
    POST /distributed/job_complete  — canonical collector envelope
    POST /distributed/prepare_job   — pre-create a collector queue
    POST /distributed/clear_memory  — drop caches / free device memory
    POST /distributed/check_file    — media-sync hash check
    GET  /distributed/load_image    — serve an input image
"""

from __future__ import annotations

import asyncio
import hashlib
import os
from typing import Any

from aiohttp import web

from ..telemetry.instruments import collector_results_total
from ..utils import audio_payload as audio_utils
from ..utils import image as img_utils
from ..utils.async_helpers import run_blocking
from ..utils.constants import JOB_INIT_GRACE_SECONDS
from ..utils.exceptions import PromptValidationError
from ..utils.logging import debug_log, log
from .queue_request import QueueRequestError, parse_queue_request_payload
from .telemetry_routes import rpc_span


def register(app: web.Application, server) -> None:
    routes = JobRoutes(server)
    app.router.add_post("/distributed/queue", routes.queue)
    app.router.add_post("/distributed/cancel/{job_id}", routes.cancel_job)
    app.router.add_post("/distributed/job_complete", routes.job_complete)
    app.router.add_post("/distributed/prepare_job", routes.prepare_job)
    app.router.add_post("/distributed/clear_memory", routes.clear_memory)
    app.router.add_post("/distributed/check_file", routes.check_file)
    app.router.add_get("/distributed/load_image", routes.load_image)
    app.router.add_post("/upload/image", routes.upload_image)


class JobRoutes:
    def __init__(self, server):
        self.server = server

    async def queue(self, request: web.Request) -> web.Response:
        import time as time_mod

        arrived_at = time_mod.monotonic()
        try:
            body = await request.json()
        except Exception:
            return web.json_response({"error": "invalid json"}, status=400)
        try:
            payload = parse_queue_request_payload(body)
            if payload.deadline_s is None:
                # header form of the end-to-end deadline (proxies and
                # thin clients that can't touch the JSON body)
                from .queue_request import parse_deadline_seconds

                payload.deadline_s = parse_deadline_seconds(
                    request.headers.get("X-CDT-Deadline")
                )
        except QueueRequestError as exc:
            return web.json_response({"error": str(exc)}, status=400)

        if payload.adapters:
            # Resolve adapter names → content hashes NOW, against the
            # master's catalog: an unknown adapter is a client error at
            # admission, never a mid-job worker failure. The stamped
            # hashes are the identity every downstream surface keys on.
            from ..adapters import AdapterError, get_adapter_catalog

            try:
                payload.adapters = get_adapter_catalog().resolve(
                    payload.adapters
                )
            except AdapterError as exc:
                return web.json_response({"error": str(exc)}, status=400)
        if payload.lane is None:
            # Budget tenants with no explicit lane ride the cheap lane
            # (CDT_CHEAP_LANE — the GGUF-quantized tier's admission
            # class, models/gguf.quantized_lane_info).
            from ..utils.constants import budget_tenants, cheap_lane

            if payload.tenant in budget_tenants():
                payload.lane = cheap_lane()

        import asyncio

        from ..scheduler import (
            AdmissionClosed,
            DeadlineUnmeetable,
            SchedulerOverloaded,
            SchedulerSaturated,
        )
        from ..telemetry import get_tracer
        from ..utils.constants import SCHED_GRANT_TIMEOUT_SECONDS
        from ..utils.trace_logger import generate_trace_id
        from .orchestration.queue_orchestration import (
            orchestrate_distributed_execution,
        )

        scheduler = getattr(self.server, "scheduler", None)
        ticket = None
        if scheduler is not None:
            # The trace id is fixed here (not in orchestration) so the
            # sched.wait span and the execution share one span tree —
            # perf_report's queue-wait column pairs them.
            payload.trace_id = payload.trace_id or generate_trace_id()
            try:
                ticket = scheduler.submit_payload(payload)
            except DeadlineUnmeetable as exc:
                return web.json_response(
                    {
                        "error": str(exc),
                        "lane": exc.lane,
                        "reason": "deadline_unmeetable",
                        "deadline_s": exc.deadline_s,
                        "estimated_wait_seconds": round(exc.estimated_wait, 2),
                    },
                    status=429,
                    headers={"Retry-After": str(int(exc.retry_after))},
                )
            except SchedulerOverloaded as exc:
                return web.json_response(
                    {"error": str(exc), "lane": exc.lane, "reason": "shed"},
                    status=429,
                    headers={"Retry-After": str(int(exc.retry_after))},
                )
            except SchedulerSaturated as exc:
                return web.json_response(
                    {"error": str(exc), "lane": exc.lane},
                    status=429,
                    headers={"Retry-After": str(int(exc.retry_after))},
                )
            except AdmissionClosed as exc:
                return web.json_response(
                    {"error": str(exc)},
                    status=503,
                    headers={"Retry-After": str(int(exc.retry_after))},
                )
        # Every exit below — grant timeout, validation error, client
        # disconnect (CancelledError out of the wait or orchestration),
        # even a grant racing the timeout — must hand the ticket back:
        # still-queued tickets are withdrawn, granted ones release
        # their slot. Leaking either would permanently consume one of
        # the max_active grant slots.
        try:
            if ticket is not None:
                try:
                    with get_tracer().span(
                        "sched.wait",
                        trace_id=payload.trace_id,
                        lane=ticket.lane,
                        tenant=ticket.tenant,
                        ticket_id=ticket.ticket_id,
                    ):
                        await asyncio.wait_for(
                            ticket.granted(), SCHED_GRANT_TIMEOUT_SECONDS
                        )
                except asyncio.TimeoutError:
                    return web.json_response(
                        {
                            "error": "grant wait expired; scheduler saturated",
                            "lane": ticket.lane,
                        },
                        status=429,
                        headers={
                            "Retry-After": str(
                                int(
                                    scheduler.queue.estimate_retry_after(
                                        ticket.lane
                                    )
                                )
                            )
                        },
                    )
                if ticket.state == "cancelled":
                    # withdrawn while queued (DELETE ticket route): the
                    # parked request unwinds here instead of waiting
                    # out the grant timeout
                    return web.json_response(
                        {
                            "error": "ticket cancelled before grant",
                            "ticket_id": ticket.ticket_id,
                        },
                        status=409,
                    )

            if payload.deadline_s is not None:
                # the deadline is END-TO-END: time spent queued counts.
                # What rides into the job record is the REMAINDER; a
                # request that burned its whole budget waiting answers
                # 429 without starting doomed work.
                remaining = payload.deadline_s - (
                    time_mod.monotonic() - arrived_at
                )
                if remaining <= 0:
                    return web.json_response(
                        {
                            "error": "deadline expired while queued",
                            "reason": "deadline_expired",
                            "deadline_s": payload.deadline_s,
                        },
                        status=429,
                        headers={"Retry-After": "1"},
                    )
                payload.deadline_s = remaining

            try:
                result = await orchestrate_distributed_execution(
                    self.server, payload
                )
            except PromptValidationError as exc:
                return web.json_response(
                    {"error": str(exc), "node_errors": exc.node_errors},
                    status=400,
                )
            if ticket is not None:
                result["scheduler"] = {
                    "ticket_id": ticket.ticket_id,
                    "tenant": ticket.tenant,
                    "lane": ticket.lane,
                    "queue_wait_seconds": ticket.queue_wait_seconds,
                }
            return web.json_response(result)
        finally:
            if ticket is not None:
                if ticket.state == "queued":
                    scheduler.queue.cancel(ticket)
                else:
                    scheduler.queue.release(ticket)  # no-op unless granted

    async def cancel_job(self, request: web.Request) -> web.Response:
        """POST /distributed/cancel/{job_id} — cooperative cancellation
        of a RUNNING job: journals the terminal cancel record, refunds
        every pending + in-flight tile, notifies workers over the
        events stream (they flush what's encoded and abort between
        batches), and settles the master loop with a terminal
        `cancelled` status. Idempotent; 404 for unknown jobs.

        Pre-admission requests are cancelled through
        DELETE /distributed/queue/{ticket_id} instead."""
        import time as time_mod

        job_id = request.match_info["job_id"]
        reason = "client"
        try:
            body = await request.json()
        except Exception:  # noqa: BLE001 - body optional
            body = None
        if isinstance(body, dict) and body.get("reason"):
            reason = str(body["reason"])
        started = time_mod.monotonic()
        with rpc_span(request, "rpc.cancel_job", job_id=str(job_id)):
            accounting = await self.server.job_store.cancel_job(
                str(job_id), reason=reason
            )
        if accounting is None:
            return web.json_response({"error": "no such job"}, status=404)
        accounting["status"] = "cancelled"
        # cancel-request → all tiles refunded: the reclaim-speed number
        # scripts/lifecycle_soak.py reports
        accounting["cancel_latency_ms"] = round(
            (time_mod.monotonic() - started) * 1000.0, 3
        )
        return web.json_response(accounting)

    async def job_complete(self, request: web.Request) -> web.Response:
        """Canonical envelope {job_id, worker_id, batch_idx, image
        (base64 PNG data URL), is_last, audio?} — one request per image
        (reference api/job_routes.py:273-343)."""
        try:
            body = await request.json()
        except Exception:
            return web.json_response({"error": "invalid json"}, status=400)

        errors = _validate_envelope(body)
        if errors:
            return web.json_response({"error": "; ".join(errors)}, status=400)

        try:
            tensor = img_utils.decode_image_data_url(body["image"])
        except Exception as exc:  # noqa: BLE001 - boundary validation
            return web.json_response(
                {"error": f"undecodable image: {exc}"}, status=400
            )
        audio = None
        if body.get("audio") is not None:
            try:
                audio = audio_utils.decode_audio_payload(body["audio"])
            except Exception as exc:  # noqa: BLE001
                return web.json_response(
                    {"error": f"undecodable audio: {exc}"}, status=400
                )

        with rpc_span(
            request, "rpc.job_complete",
            worker_id=str(body["worker_id"]), job_id=str(body["job_id"]),
            batch_idx=int(body["batch_idx"]),
        ):
            job = await self.server.job_store.wait_for_collector(
                body["job_id"], JOB_INIT_GRACE_SECONDS
            )
            if job is None:
                return web.json_response({"error": "no such job"}, status=404)
            await self.server.job_store.put_collector_result(
                body["job_id"],
                {
                    "tensor": tensor,
                    "worker_id": str(body["worker_id"]),
                    "batch_idx": int(body["batch_idx"]),
                    "is_last": bool(body.get("is_last", False)),
                    "empty": bool(body.get("empty", False)),
                    "audio": audio,
                },
            )
            collector_results_total().inc(worker_id=str(body["worker_id"]))
        return web.json_response({"status": "ok"})

    async def prepare_job(self, request: web.Request) -> web.Response:
        try:
            body = await request.json()
        except Exception:
            return web.json_response({"error": "invalid json"}, status=400)
        job_id = body.get("job_id")
        if not job_id:
            return web.json_response({"error": "missing job_id"}, status=400)
        await self.server.job_store.ensure_collector(str(job_id))
        return web.json_response({"status": "ok"})

    async def clear_memory(self, request: web.Request) -> web.Response:
        """Drop pipeline caches and device buffers (the TPU analog of
        the reference's unload-models + cuda empty_cache)."""
        self.server.execution_context.pipelines.clear()
        # the elastic tier's per-signature processors hold their bundle
        from ..graph.usdu_elastic import _tile_processor

        _tile_processor.cache_clear()
        import gc

        gc.collect()
        try:
            import jax

            jax.clear_caches()
        except Exception as exc:  # noqa: BLE001 - best effort
            debug_log(f"clear_caches failed: {exc}")
        log("cleared pipeline caches and compilation caches")
        return web.json_response({"status": "ok"})

    async def check_file(self, request: web.Request) -> web.Response:
        """{'filename': ..., 'md5'?: ...} → exists/hash-match (media sync)."""
        try:
            body = await request.json()
        except Exception:
            return web.json_response({"error": "invalid json"}, status=400)
        name = body.get("filename")
        if not name:
            return web.json_response({"error": "missing filename"}, status=400)
        from ..graph.io_dirs import get_input_dir, resolve_input_path

        try:
            path = resolve_input_path(str(name), None)
        except Exception:
            return web.json_response({"exists": False})
        if not os.path.isfile(path):
            return web.json_response({"exists": False})
        response: dict[str, Any] = {"exists": True}
        expected = body.get("md5")
        if expected:
            # digesting a multi-MB media file blocks; hash off-loop (CDT001)
            def _digest_file() -> str:
                digest = hashlib.md5()
                with open(path, "rb") as fh:
                    for chunk in iter(lambda: fh.read(1 << 20), b""):
                        digest.update(chunk)
                return digest.hexdigest()

            hexdigest = await run_blocking(_digest_file)
            response["md5"] = hexdigest
            response["matches"] = hexdigest == expected
        return web.json_response(response)

    async def load_image(self, request: web.Request) -> web.Response:
        name = request.query.get("filename", "")
        from ..graph.io_dirs import resolve_input_path

        try:
            path = resolve_input_path(name, None)
        except Exception:
            return web.json_response({"error": "bad path"}, status=400)
        if not os.path.isfile(path):
            return web.json_response({"error": "not found"}, status=404)
        return web.FileResponse(path)

    async def upload_image(self, request: web.Request) -> web.Response:
        """Multipart upload into the input dir (media sync target —
        ComfyUI /upload/image parity)."""
        from ..graph.io_dirs import get_input_dir

        reader = await request.multipart()
        saved = []
        while True:
            part = await reader.next()
            if part is None:
                break
            if part.name in ("image", "file"):
                filename = os.path.basename(part.filename or "upload.bin")
                target_dir = get_input_dir(None)
                os.makedirs(target_dir, exist_ok=True)
                target = os.path.join(target_dir, filename)
                # stream chunk-by-chunk with the open/write/close on the
                # executor: bounded memory for arbitrarily large media
                # files AND no sync file I/O on the loop (CDT001)
                fh = await run_blocking(open, target, "wb")
                try:
                    while True:
                        chunk = await part.read_chunk()
                        if not chunk:
                            break
                        await run_blocking(fh.write, chunk)
                finally:
                    await run_blocking(fh.close)
                saved.append(filename)
        return web.json_response({"name": saved[0] if saved else None, "saved": saved})


def _validate_envelope(body: Any) -> list[str]:
    errors = []
    if not isinstance(body, dict):
        return ["body must be an object"]
    for field in ("job_id", "worker_id", "batch_idx", "image"):
        if field not in body:
            errors.append(f"missing {field!r}")
    if "batch_idx" in body:
        try:
            int(body["batch_idx"])
        except (TypeError, ValueError):
            errors.append("batch_idx must be an int")
    if "image" in body and not isinstance(body["image"], str):
        errors.append("image must be a base64 data-URL string")
    return errors
