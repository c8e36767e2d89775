"""Distributed workflow nodes.

Parity set with the reference's node inventory (reference
nodes/utilities.py + nodes/collector.py): DistributedSeed,
DistributedValue, DistributedModelName, Image/AudioBatchDivider,
DistributedEmptyImage, DistributedCollector. Roles:

- On a mesh run, DistributedSeed emits a per-participant SeedSpec and
  the collector just materialises the participant-major sharded batch
  (the all-gather IS the collection).
- On the elastic (HTTP) tier, the same nodes behave like the
  reference's: workers POST per-image envelopes to the master's
  /distributed/job_complete; the master's collector drains its job
  queue with sliced waits, busy-probe grace on stalls, dedup, and
  deterministic master-first ordering.
"""

from __future__ import annotations

import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.collective import host_collect, reorder_participant_first
from ..utils import audio_payload as audio_utils
from ..utils import image as img_utils
from ..utils.async_helpers import run_async_in_server_loop
from ..utils.constants import (
    COLLECTOR_WAIT_SLICES,
    JOB_INIT_GRACE_SECONDS,
    REQUEST_RETRY_BACKOFF,
    REQUEST_RETRY_COUNT,
)
from ..utils.logging import debug_log, log
from ..utils.network import build_worker_url, get_client_session, probe_worker
from .nodes_core import SeedSpec
from .registry import register_node


@register_node
class DistributedSeed:
    """Master passes the seed through; worker i gets seed + i + 1
    (reference nodes/utilities.py:52-75). On mesh runs emits a
    per-participant SeedSpec so KSampler runs one SPMD program."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {"seed": ("INT", {"default": 0})},
            "hidden": {
                "is_worker": ("BOOLEAN", {"default": False}),
                "worker_index": ("INT", {"default": -1}),
            },
        }

    RETURN_TYPES = ("INT",)
    FUNCTION = "get_seed"

    def get_seed(self, seed, is_worker=False, worker_index=-1,
                 enabled_worker_ids=None, context=None):
        mesh = getattr(context, "mesh", None) if context is not None else None
        if not is_worker and mesh is not None:
            from ..parallel.mesh import data_axis_size

            if data_axis_size(mesh) > 1:
                return (SeedSpec(base_seed=int(seed), per_participant=True),)
        if is_worker and worker_index >= 0:
            return (SeedSpec(base_seed=int(seed), worker_index=int(worker_index)),)
        return (SeedSpec(base_seed=int(seed)),)


@register_node
class DistributedValue:
    """Typed per-worker value override: master keeps `value`; worker i
    looks up overrides[str(i+1)] coerced to overrides['_type']
    (reference nodes/utilities.py:86-162). The override application
    happens at prompt-rewrite time (graph/prompt.py); this node just
    surfaces the resolved value."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {"value": ("STRING", {"default": ""})},
            "optional": {"overrides": ("DICT", {"default": None})},
            "hidden": {
                "is_worker": ("BOOLEAN", {"default": False}),
                "worker_index": ("INT", {"default": -1}),
            },
        }

    RETURN_TYPES = ("*",)
    FUNCTION = "get_value"

    def get_value(self, value, overrides=None, is_worker=False, worker_index=-1,
                  enabled_worker_ids=None, context=None):
        return (value,)


@register_node
class DistributedModelName:
    """Stringify a model reference so delegate-only masters can patch
    model names into workflows they don't execute themselves
    (reference nodes/utilities.py:164-224)."""

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"model": ("MODEL",)}}

    RETURN_TYPES = ("STRING",)
    FUNCTION = "name_of"
    OUTPUT_NODE = True

    def name_of(self, model, context=None):
        name = getattr(model, "model_name", str(model))
        return (name,)


def _chunk_bounds(total: int, parts: int) -> list[tuple[int, int]]:
    """Contiguous near-equal split (divmod distribution, reference
    nodes/utilities.py:7-20)."""
    parts = max(1, min(parts, total)) if total > 0 else 1
    base, extra = divmod(total, parts)
    bounds = []
    start = 0
    for i in range(parts):
        size = base + (1 if i < extra else 0)
        bounds.append((start, start + size))
        start += size
    return bounds


MAX_DIVIDER_OUTPUTS = 10


@register_node
class ImageBatchDivider:
    """Split an IMAGE batch into up to 10 contiguous chunks (reference
    nodes/utilities.py:235-268) — the video-frame fan-out primitive."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "images": ("IMAGE",),
                "divide_by": ("INT", {"default": 2}),
            }
        }

    RETURN_TYPES = tuple(["IMAGE"] * MAX_DIVIDER_OUTPUTS)
    FUNCTION = "divide"

    def divide(self, images, divide_by=2, context=None):
        parts = max(1, min(int(divide_by), MAX_DIVIDER_OUTPUTS))
        total = images.shape[0]
        outs = []
        for start, end in _chunk_bounds(total, parts):
            outs.append(images[start:end])
        while len(outs) < MAX_DIVIDER_OUTPUTS:
            outs.append(images[0:0])
        return tuple(outs)


@register_node
class AudioBatchDivider:
    """Split AUDIO samples into up to 10 contiguous chunks along the
    sample axis (reference nodes/utilities.py:271-329). AUDIO contract:
    {"waveform": [B, C, S], "sample_rate": int}."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "audio": ("AUDIO",),
                "divide_by": ("INT", {"default": 2}),
            }
        }

    RETURN_TYPES = tuple(["AUDIO"] * MAX_DIVIDER_OUTPUTS)
    FUNCTION = "divide"

    def divide(self, audio, divide_by=2, context=None):
        wave = audio["waveform"]
        rate = audio["sample_rate"]
        parts = max(1, min(int(divide_by), MAX_DIVIDER_OUTPUTS))
        outs = []
        for start, end in _chunk_bounds(wave.shape[-1], parts):
            outs.append({"waveform": wave[..., start:end], "sample_rate": rate})
        empty = {"waveform": wave[..., 0:0], "sample_rate": rate}
        while len(outs) < MAX_DIVIDER_OUTPUTS:
            outs.append(dict(empty))
        return tuple(outs)


@register_node
class DistributedEmptyImage:
    """Zero-batch IMAGE placeholder feeding delegate-mode collectors
    (reference nodes/utilities.py:332-354)."""

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {}}

    RETURN_TYPES = ("IMAGE",)
    FUNCTION = "empty"

    def empty(self, context=None):
        return (jnp.zeros((0, 64, 64, 3)),)


# --------------------------------------------------------------------------


@register_node
class DistributedCollector:
    """THE gather op (reference nodes/collector.py).

    Worker role: serialize each image to a base64-PNG envelope and POST
    to the master per image (is_last marks the final one). Master role:
    mesh-tier results are materialised directly from the sharded array;
    elastic-tier results are drained from the job queue with sliced
    waits, worker probes on stall (busy ⇒ grace), dedup, and
    deterministic ordering (master batch first, then enabled workers in
    configured order, then stragglers sorted)."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {"images": ("IMAGE",)},
            "optional": {
                "audio": ("AUDIO", {"default": None}),
                "pass_through": ("BOOLEAN", {"default": False}),
                "load_balance": ("BOOLEAN", {"default": False}),
            },
            "hidden": {
                "is_worker": ("BOOLEAN", {"default": False}),
                "worker_id": ("STRING", {"default": ""}),
                "master_url": ("STRING", {"default": ""}),
                "job_id": ("STRING", {"default": ""}),
            },
        }

    RETURN_TYPES = ("IMAGE", "AUDIO")
    FUNCTION = "run"
    NEVER_CACHE = True  # network gather; reference forces re-exec

    def run(
        self,
        images,
        audio=None,
        pass_through=False,
        load_balance=False,
        is_worker=False,
        worker_id="",
        master_url="",
        job_id="",
        enabled_worker_ids=None,
        context=None,
    ):
        if pass_through:
            return (images, audio)
        if is_worker:
            self._send_to_master(images, audio, worker_id, master_url, job_id)
            return (images, audio)
        return self._collect_master(
            images, audio, job_id, enabled_worker_ids or [], context
        )

    # --- worker side ------------------------------------------------------

    def _send_to_master(self, images, audio, worker_id, master_url, job_id):
        arr = img_utils.ensure_numpy(images)
        batch = arr.shape[0]
        # Capture the active trace on the executor thread: send_all runs
        # on the server loop, where the context is not set.
        from ..telemetry import TRACE_HEADER, current_trace_id

        trace_id = current_trace_id()
        headers = {TRACE_HEADER: trace_id} if trace_id else {}

        async def send_all():
            session = await get_client_session()
            if batch == 0:
                # An empty batch still needs an is_last envelope or the
                # master waits a full timeout for this worker. The 1px
                # placeholder satisfies envelope validation; "empty"
                # tells the collector to discard the tensor.
                envelope: dict[str, Any] = {
                    "job_id": job_id,
                    "worker_id": worker_id,
                    "batch_idx": 0,
                    "image": img_utils.encode_image_data_url(
                        np.zeros((1, 1, 3), np.float32)
                    ),
                    "is_last": True,
                    "empty": True,
                }
                if audio is not None:
                    envelope["audio"] = audio_utils.encode_audio_payload(
                        audio["waveform"], audio["sample_rate"]
                    )
                await self._post_with_retry(
                    session, f"{master_url}/distributed/job_complete", envelope,
                    headers,
                )
                return
            for idx in range(batch):
                envelope = {
                    "job_id": job_id,
                    "worker_id": worker_id,
                    "batch_idx": idx,
                    "image": img_utils.encode_image_data_url(arr[idx]),
                    "is_last": idx == batch - 1,
                }
                if audio is not None and idx == batch - 1:
                    envelope["audio"] = audio_utils.encode_audio_payload(
                        audio["waveform"], audio["sample_rate"]
                    )
                await self._post_with_retry(
                    session, f"{master_url}/distributed/job_complete", envelope,
                    headers,
                )

        run_async_in_server_loop(send_all(), timeout=300)

    @staticmethod
    async def _post_with_retry(session, url, payload, headers=None):
        last_exc: Exception | None = None
        for attempt in range(REQUEST_RETRY_COUNT):
            try:
                async with session.post(url, json=payload, headers=headers or {}) as resp:
                    if resp.status == 200:
                        return
                    last_exc = RuntimeError(f"HTTP {resp.status}")
            except Exception as exc:  # noqa: BLE001 - retried
                last_exc = exc
            await __import__("asyncio").sleep(REQUEST_RETRY_BACKOFF * (2**attempt))
        raise last_exc if last_exc else RuntimeError("send failed")

    # --- master side --------------------------------------------------------

    def _collect_master(self, images, audio, job_id, enabled_worker_ids, context):
        server = getattr(context, "server", None) if context is not None else None
        alone = not enabled_worker_ids or server is None

        # Nobody to gather from and nothing to gather: an array that
        # lies whole on one device IS the collected batch. It stays
        # there, and whoever needs its bytes (SaveImage) waits for it.
        if alone and isinstance(images, jax.Array) and len(images.sharding.device_set) == 1:
            return (images, audio)

        # Mesh tier: the sharded participant-major array IS the collected
        # batch — just materialise it.
        from ..telemetry import get_tracer

        with get_tracer().device_wait() as wait:
            mesh_collected = host_collect(images) if isinstance(images, jax.Array) else (
                img_utils.ensure_numpy(images)
            )
            wait.attrs["bytes"] = int(mesh_collected.nbytes)

        if alone:
            combined_audio = audio
            return (jnp.asarray(mesh_collected), combined_audio)

        # Elastic tier: drain the HTTP job queue for remote workers.
        collected = self._drain_worker_results(
            server, job_id, enabled_worker_ids, context
        )
        batches: dict[int, np.ndarray] = {0: mesh_collected}
        audio_parts: list[tuple[np.ndarray, int]] = []
        if audio is not None:
            audio_parts.append(
                (img_utils.ensure_numpy(audio["waveform"]), audio["sample_rate"])
            )
        order: dict[str, int] = {
            wid: i + 1 for i, wid in enumerate(enabled_worker_ids)
        }
        per_worker: dict[str, list[tuple[int, np.ndarray]]] = {}
        for item in collected:
            wid = str(item["worker_id"])
            if item.get("audio") is not None:
                audio_parts.append(item["audio"])
            if item.get("empty"):
                continue  # zero-batch marker: worker finished, no images
            per_worker.setdefault(wid, []).append(
                (int(item.get("batch_idx", 0)), item["tensor"])
            )
        next_straggler = len(enabled_worker_ids) + 1
        for wid in sorted(per_worker, key=lambda w: order.get(w, 10**6)):
            imgs = [t for _, t in sorted(per_worker[wid], key=lambda p: p[0])]
            idx = order.get(wid)
            if idx is None:
                idx = next_straggler
                next_straggler += 1
            batches[idx] = np.stack(imgs, axis=0)

        ordered = reorder_participant_first(batches, list(range(1, next_straggler)))
        nonempty = [a for a in ordered if a.size]
        sizes = {a.shape[1:] for a in nonempty}
        if len(sizes) > 1:
            # keep the majority/first NON-empty size (the master batch may
            # be an empty delegate placeholder whose nominal size is moot)
            target = nonempty[0].shape[1:]
            log(f"collector: mismatched image sizes {sizes}; keeping {target}")
            nonempty = [a for a in nonempty if a.shape[1:] == target]
        if nonempty:
            combined = np.concatenate(nonempty, axis=0)
        else:
            # every participant returned empty (or all workers dropped):
            # surface the master's (possibly zero-batch) images unchanged
            combined = mesh_collected

        combined_audio = None
        if audio_parts:
            wave, rate = audio_utils.combine_audio(audio_parts)
            combined_audio = {"waveform": wave, "sample_rate": rate}
        return (jnp.asarray(combined), combined_audio)

    def _drain_worker_results(self, server, job_id, enabled_worker_ids, context):
        """Sliced-wait drain with busy-probe grace (reference
        nodes/collector.py:322-440)."""
        from ..utils.config import get_worker_timeout_seconds

        timeout = get_worker_timeout_seconds()
        slice_timeout = max(timeout / COLLECTOR_WAIT_SLICES, 0.05)
        expected = set(map(str, enabled_worker_ids))
        collected: list[dict[str, Any]] = []
        deadline_stall = time.monotonic() + timeout
        seen_keys: set[tuple[str, int]] = set()

        async def get_one(slice_s: float):
            import asyncio

            job = await server.job_store.wait_for_collector(
                job_id, JOB_INIT_GRACE_SECONDS
            )
            try:
                return await asyncio.wait_for(job.queue.get(), slice_s), job
            except asyncio.TimeoutError:
                return None, job

        while True:
            if context is not None:
                context.check_interrupted()
            item, job = run_async_in_server_loop(
                get_one(slice_timeout), timeout=slice_timeout + JOB_INIT_GRACE_SECONDS + 5
            )
            if item is not None:
                deadline_stall = time.monotonic() + timeout
                key = (str(item.get("worker_id")), int(item.get("batch_idx", 0)))
                if key in seen_keys:
                    debug_log(f"collector dedup {key}")
                    continue
                seen_keys.add(key)
                collected.append(item)
            finished = job.finished_workers & expected
            if finished == expected:
                break
            if time.monotonic() >= deadline_stall:
                missing = expected - finished
                busy = self._probe_any_busy(missing, context)
                if busy:
                    debug_log(f"collector stall: {missing} busy; extending grace")
                    deadline_stall = time.monotonic() + timeout
                    continue
                log(f"collector: giving up on workers {sorted(missing)}")
                break
        return collected

    @staticmethod
    def _probe_any_busy(worker_ids, context) -> bool:
        config = getattr(context, "config", None) or {}
        workers = {str(w.get("id")): w for w in config.get("workers", [])}

        async def probe_all():
            for wid in worker_ids:
                worker = workers.get(str(wid))
                if worker is None:
                    continue
                result = await probe_worker(build_worker_url(worker))
                if result["online"] and (result["queue_remaining"] or 0) > 0:
                    return True
            return False

        try:
            return run_async_in_server_loop(probe_all(), timeout=30)
        except Exception:
            return False
