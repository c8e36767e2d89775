"""Worker routes: WS dispatch, lifecycle, logs, host/topology info.

Parity with reference api/worker_routes.py (695 LoC there):
    WS   /distributed/worker_ws      — dispatch_prompt/dispatch_ack
    POST /distributed/launch_worker  — spawn a local worker process
    POST /distributed/stop_worker    — stop a managed worker
    GET  /distributed/managed        — managed process table
    GET  /distributed/worker_log/{n} — tail a worker's log file
    GET  /distributed/master_log     — in-memory master log ring
    GET  /distributed/network_info   — candidate IPs, private ranked
    GET  /distributed/system_info    — machine id, path sep, TPU topology
"""

from __future__ import annotations

import asyncio
import json
import os
import socket
from typing import Any

from aiohttp import WSMsgType, web

from ..utils.async_helpers import run_blocking
from ..utils.exceptions import MeshError
from ..utils.logging import log


def register(app: web.Application, server) -> None:
    routes = WorkerRoutes(server)
    app.router.add_get("/distributed/worker_ws", routes.worker_ws)
    app.router.add_post("/distributed/launch_worker", routes.launch_worker)
    app.router.add_post("/distributed/stop_worker", routes.stop_worker)
    app.router.add_post(
        "/distributed/worker/clear_launching", routes.clear_launching
    )
    app.router.add_get("/distributed/managed", routes.managed)
    app.router.add_get("/distributed/worker_log/{name}", routes.worker_log)
    app.router.add_get("/distributed/master_log", routes.master_log)
    app.router.add_get("/distributed/remote_log/{worker_id}", routes.remote_log)
    app.router.add_get("/distributed/network_info", routes.network_info)
    app.router.add_get("/distributed/system_info", routes.system_info)


class WorkerRoutes:
    def __init__(self, server):
        self.server = server

    # --- websocket dispatch ------------------------------------------------

    async def worker_ws(self, request: web.Request) -> web.WebSocketResponse:
        """Server side of WS orchestration (reference
        api/worker_routes.py:43-112): the master connects and sends
        {type: dispatch_prompt, prompt, prompt_id}; we enqueue and ack
        {type: dispatch_ack, prompt_id, ok}."""
        ws = web.WebSocketResponse(heartbeat=30)
        await ws.prepare(request)
        async for msg in ws:
            if msg.type != WSMsgType.TEXT:
                continue
            try:
                data = json.loads(msg.data)
            except json.JSONDecodeError:
                await ws.send_json({"type": "error", "error": "invalid json"})
                continue
            if data.get("type") == "dispatch_prompt":
                prompt_id = data.get("prompt_id", "")
                try:
                    self.server.queue_prompt(
                        data.get("prompt", {}),
                        prompt_id,
                        data.get("extra_data"),
                        trace_id=data.get("trace_id") or None,
                    )
                    await ws.send_json(
                        {"type": "dispatch_ack", "prompt_id": prompt_id, "ok": True}
                    )
                except Exception as exc:  # noqa: BLE001 - reported over WS
                    await ws.send_json(
                        {
                            "type": "dispatch_ack",
                            "prompt_id": prompt_id,
                            "ok": False,
                            "error": str(exc),
                        }
                    )
            elif data.get("type") == "ping":
                await ws.send_json(
                    {"type": "pong", "queue_remaining": self.server.queue_remaining}
                )
        return ws

    # --- lifecycle ---------------------------------------------------------

    async def launch_worker(self, request: web.Request) -> web.Response:
        try:
            body = await request.json()
        except Exception:
            body = {}
        worker_id = str(body.get("worker_id", ""))
        if not worker_id:
            return web.json_response({"error": "worker_id required"}, status=400)
        worker = next(
            (
                w
                for w in self.server.config.get("workers", [])
                if str(w.get("id")) == worker_id
            ),
            None,
        )
        if worker is None:
            return web.json_response({"error": "no such worker"}, status=404)

        from ..workers import get_worker_manager

        manager = get_worker_manager()
        try:
            info = await run_blocking(
                manager.launch_worker, worker, self.server.config_path
            )
        except Exception as exc:  # noqa: BLE001 - reported to client
            return web.json_response({"error": str(exc)}, status=500)
        return web.json_response({"status": "ok", **info})

    async def stop_worker(self, request: web.Request) -> web.Response:
        try:
            body = await request.json()
        except Exception:
            body = {}
        worker_id = str(body.get("worker_id", ""))
        from ..workers import get_worker_manager

        manager = get_worker_manager()
        stopped = await run_blocking(
            manager.stop_worker, worker_id, self.server.config_path
        )
        return web.json_response({"status": "ok", "stopped": stopped})

    async def clear_launching(self, request: web.Request) -> web.Response:
        """Clear a managed worker's 'launching' marker once it is
        confirmed up (reference api/worker_routes.py
        /distributed/worker/clear_launching) so a crashed launch
        cannot wedge the panel's grace state."""
        try:
            body = await request.json()
        except Exception:
            body = {}
        worker_id = str(body.get("worker_id", "")).strip()
        if not worker_id:
            return web.json_response({"error": "worker_id required"}, status=400)
        known = any(
            str(w.get("id")) == worker_id
            for w in self.server.config.get("workers", [])
        )
        if not known:
            return web.json_response({"error": "no such worker"}, status=404)
        from ..workers import get_worker_manager

        cleared = await run_blocking(
            get_worker_manager().clear_launching,
            worker_id,
            self.server.config_path,
        )
        return web.json_response({"status": "success", "cleared": cleared})

    async def managed(self, request: web.Request) -> web.Response:
        from ..workers import get_worker_manager

        return web.json_response(
            {"managed": get_worker_manager().managed_processes(self.server.config_path)}
        )

    # --- logs --------------------------------------------------------------

    async def worker_log(self, request: web.Request) -> web.Response:
        name = request.match_info["name"]
        tail = int(request.query.get("tail", 200))
        from ..workers.process_manager import worker_log_path

        path = worker_log_path(name)
        if not os.path.isfile(path):
            return web.json_response({"error": "no log"}, status=404)
        lines = await run_blocking(_tail_file, path, tail)
        return web.json_response({"name": name, "lines": lines})

    async def master_log(self, request: web.Request) -> web.Response:
        tail = int(request.query.get("tail", 200))
        return web.json_response({"lines": self.server.log_buffer[-tail:]})

    async def remote_log(self, request: web.Request) -> web.Response:
        """Proxy a remote worker's in-memory log so the panel can show
        logs of workers on other hosts (reference remote-log endpoint,
        api/worker_routes.py log proxying)."""
        worker_id = request.match_info["worker_id"]
        tail = request.query.get("tail", "200")
        worker = next(
            (
                w
                for w in self.server.config.get("workers", [])
                if str(w.get("id")) == worker_id
            ),
            None,
        )
        if worker is None:
            return web.json_response({"error": "no such worker"}, status=404)
        from ..utils.network import build_worker_url, get_client_session

        try:
            session = await get_client_session()
            url = build_worker_url(worker, f"/distributed/master_log?tail={tail}")
            async with session.get(url) as resp:
                return web.json_response(await resp.json(), status=resp.status)
        except Exception as exc:  # noqa: BLE001 - proxied errors surface
            return web.json_response({"error": str(exc)}, status=502)

    # --- host info ----------------------------------------------------------

    async def network_info(self, request: web.Request) -> web.Response:
        """Candidate IPs for reaching this host, private IPs ranked
        first (reference api/worker_routes.py network_info)."""
        candidates: list[str] = []
        try:
            hostname = socket.gethostname()
            # getaddrinfo can hit DNS: resolve through the loop's
            # executor so a slow resolver never stalls other requests
            infos = await asyncio.get_running_loop().getaddrinfo(
                hostname, None, family=socket.AF_INET
            )
            for info in infos:
                addr = info[4][0]
                if addr not in candidates:
                    candidates.append(addr)
        except OSError:
            pass
        # UDP-connect trick: the OS picks the outbound interface
        try:
            with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
                s.connect(("10.255.255.255", 1))
                addr = s.getsockname()[0]
                if addr not in candidates:
                    candidates.insert(0, addr)
        except OSError:
            pass
        from ..utils.network import is_private_host

        ranked = sorted(
            (a for a in candidates if a != "127.0.0.1"),
            key=lambda a: (not is_private_host(a), a),
        )
        return web.json_response(
            {"candidates": ranked or candidates, "recommended": (ranked or ["127.0.0.1"])[0]}
        )

    async def system_info(self, request: web.Request) -> web.Response:
        """Machine identity + accelerator topology (the reference
        reports CUDA devices via nvidia-smi; we report the jax device
        mesh — reference api/worker_routes.py:237-274)."""
        from ..workers.detection import get_machine_id, is_docker

        info: dict[str, Any] = {
            "machine_id": get_machine_id(),
            "path_separator": os.sep,
            "platform": os.name,
            "docker": is_docker(),
            "is_worker": self.server.is_worker,
        }
        # Live telemetry snapshot for the control panel: queue depths,
        # in-flight tiles, and breaker states without making the panel
        # parse the Prometheus text surface.
        from ..resilience.health import get_health_registry

        stats = await self.server.job_store.stats()
        info["status"] = {
            "queue_remaining": self.server.queue_remaining,
            "tile_jobs": stats["tile_jobs"],
            "collector_jobs": stats["collectors"],
            "tile_queue_depth": stats["queue_depth"],
            "in_flight_tiles": stats["in_flight"],
            "breakers": get_health_registry().snapshot(),
            # advertised chip counts per worker (mesh data-axis width,
            # carried on pull/heartbeat) — the placement policy's
            # capacity inputs, surfaced for the panel and operators
            "worker_capacity": dict(self.server.job_store.worker_capacity),
        }
        # Event-bus consumer accounting: per-subscriber queue depth +
        # cumulative drops, plus the installed synchronous taps — the
        # flight recorder is an always-on tap, and its ring drops must
        # be visible here, not silent (docs/observability.md §Incidents)
        from ..telemetry import get_event_bus, peek_flight_recorder

        info["status"]["event_bus"] = get_event_bus().stats()
        recorder = peek_flight_recorder()
        info["status"]["flight"] = (
            recorder.status() if recorder is not None else {"installed": False}
        )
        incidents = getattr(self.server, "incidents", None)
        if incidents is not None:
            info["status"]["incidents"] = incidents.status()
        # Device enumeration off the loop: the CLI brings the backend up
        # before it listens, but an embedded server may not have, and a
        # first jax.devices() blocks for seconds. The error stays in the
        # answer — chip_smoke.py and the panel both read it as a failure.
        from ..parallel.mesh import describe_topology, serving_mesh_summary

        try:
            info["topology"] = await run_blocking(describe_topology)
        except RuntimeError as exc:
            info["topology"] = {"error": str(exc)}
        else:
            # the mesh this process serves with (recorded at start-up /
            # by the elastic loop; knob-only resolution before either)
            try:
                info["topology"]["mesh"] = await run_blocking(
                    serving_mesh_summary
                )
            except (RuntimeError, MeshError) as exc:
                info["topology"]["mesh"] = {"error": str(exc)}
        from .. import native

        info["data_plane"] = await run_blocking(native.backend)
        # Tokenizer fidelity: with the committed prose-trained stand-in
        # vocab, real SD/SDXL checkpoints get wrong token ids. The
        # reference inherits the exact tokenizer from ComfyUI's bundled
        # assets (reference upscale/tile_ops.py:168); we surface the
        # degraded state so the panel can show it instead of burying it
        # in a log line (round-3 verdict item 5).
        try:
            from ..models.clip_bpe import get_bpe

            info["clip_vocab_canonical"] = await run_blocking(
                lambda: get_bpe().is_canonical
            )
        except Exception as exc:  # noqa: BLE001 - best effort
            info["clip_vocab_canonical"] = None
            info["clip_vocab_error"] = str(exc)
        # Same fidelity surface for the T5 side: Flux/SD3/WAN condition
        # through sentencepiece vocabs; without CDT_T5_SPM the fallback
        # CLIP-BPE ids are deterministic placeholders (and get folded
        # into the embedding range — models/t5_encoder.py).
        try:
            from ..models.t5_encoder import t5_vocab_canonical

            # actual tokenizer state, like the CLIP branch (and cached
            # like it — this endpoint is panel-polled)
            info["t5_vocab_canonical"] = await run_blocking(
                t5_vocab_canonical
            )
        except Exception as exc:  # noqa: BLE001 - best effort
            info["t5_vocab_canonical"] = None
            info["t5_vocab_error"] = str(exc)
        return web.json_response(info)


def _tail_file(path: str, n_lines: int) -> list[str]:
    """Tail-read a potentially large file without loading it whole."""
    avg = 200
    with open(path, "rb") as fh:
        fh.seek(0, os.SEEK_END)
        size = fh.tell()
        window = min(size, max(4096, n_lines * avg))
        fh.seek(size - window)
        data = fh.read().decode("utf-8", errors="replace")
    lines = data.splitlines()
    return lines[-n_lines:]
