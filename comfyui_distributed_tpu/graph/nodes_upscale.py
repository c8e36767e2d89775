"""UltimateSDUpscaleDistributed node.

Facade over ops/upscale.py mirroring the reference's node surface
(reference nodes/distributed_upscale.py): image + model/conditioning/
vae + sampling knobs + tile geometry in, upscaled image out. Mode
routing (reference _determine_processing_mode):

- mesh participants available → static tile sharding over ICI
  (ops/upscale.upscale_mesh) — one SPMD program;
- no participants → local scan over tiles;
- elastic HTTP workers → master/worker tile-queue loops
  (graph/usdu_elastic.py) with heartbeats and requeue.

The 4n+1 video-batch constraint of WAN-style models is validated here
like the reference does (reference nodes/distributed_upscale.py:131-142).
"""

from __future__ import annotations

from typing import Any

import jax

from ..models import pipeline as pl
from ..ops import upscale as upscale_ops
from ..utils.logging import log
from .nodes_core import annotate_attention
from .registry import register_node


@register_node
class UltimateSDUpscaleDistributed:
    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "image": ("IMAGE",),
                "model": ("MODEL",),
                "positive": ("CONDITIONING",),
                "negative": ("CONDITIONING",),
                "vae": ("VAE",),
                "seed": ("INT", {"default": 0}),
                "steps": ("INT", {"default": 20}),
                "cfg": ("FLOAT", {"default": 7.0}),
                "sampler_name": ("STRING", {"default": "euler"}),
                "scheduler": ("STRING", {"default": "karras"}),
                "denoise": ("FLOAT", {"default": 0.35}),
                "upscale_by": ("FLOAT", {"default": 2.0}),
                "tile_width": ("INT", {"default": 512}),
                "tile_height": ("INT", {"default": 512}),
                "tile_padding": ("INT", {"default": 32}),
            },
            "optional": {
                "upscale_method": ("STRING", {"default": "bicubic"}),
                "mask_blur": ("INT", {"default": 8}),
                "tiled_decode": ("BOOLEAN", {"default": False}),
                "force_uniform_tiles": ("BOOLEAN", {"default": True}),
                "dynamic_threshold": ("INT", {"default": 8}),
                "upscale_model": ("UPSCALE_MODEL", {"default": None}),
            },
            "hidden": {
                "is_worker": ("BOOLEAN", {"default": False}),
                "worker_id": ("STRING", {"default": ""}),
                "master_url": ("STRING", {"default": ""}),
                "job_id": ("STRING", {"default": ""}),
            },
        }

    RETURN_TYPES = ("IMAGE",)
    FUNCTION = "run"
    # IS_CHANGED = nan parity: the reference forces re-execution every
    # queue; NEVER_CACHE opts out of the executor's cross-run cache.
    NEVER_CACHE = True

    def run(
        self,
        image,
        model: pl.PipelineBundle,
        positive,
        negative,
        vae,
        seed=0,
        steps=20,
        cfg=7.0,
        sampler_name="euler",
        scheduler="karras",
        denoise=0.35,
        upscale_by=2.0,
        tile_width=512,
        tile_height=512,
        tile_padding=32,
        upscale_method="bicubic",
        mask_blur=8,
        tiled_decode=False,
        force_uniform_tiles=True,
        dynamic_threshold=8,
        upscale_model=None,
        is_worker=False,
        worker_id="",
        master_url="",
        job_id="",
        enabled_worker_ids=None,
        context=None,
        **_extra: Any,
    ):
        from ..ops.samplers import SAMPLER_NAMES

        seed = getattr(seed, "base_seed", seed)  # accept SeedSpec links
        if sampler_name not in SAMPLER_NAMES:
            raise ValueError(f"unknown sampler {sampler_name!r}")
        if vae is not None and vae.vae is not model.vae:
            # a standalone VAE (VAELoader) replaces the checkpoint's
            # bundled one for the tile encode/decode — ops/upscale
            # reads the VAE off the model bundle, so graft it on
            import dataclasses

            model = dataclasses.replace(
                model,
                vae=vae.vae,
                params={**model.params, "vae": vae.params["vae"]},
                latent_channels=vae.latent_channels,
                latent_scale=vae.latent_scale,
            )
        # force_uniform_tiles=False keeps the reference's non-uniform
        # seam positions (reference upscale/tile_ops.py:73-78) but with
        # static tile shapes: edge tiles overhang into an edge-extended
        # canvas strip that blending crops (ops/tiles.py module doc).
        batch = int(image.shape[0])
        if batch > 1 and (batch - 1) % 4 != 0:
            # WAN-family video models require 4n+1 frame batches
            log(f"USDU: batch {batch} is not 4n+1; video models may reject it")

        tile = int(tile_width)
        tile_h = int(tile_height)
        mesh = getattr(context, "mesh", None) if context is not None else None
        enabled = enabled_worker_ids or []

        if upscale_model is not None:
            # model-based pre-upscale to the exact target, then tiles
            # refine at 1x (reference USDU upscale_model semantics).
            # Deterministic per model name, so every participant
            # reproduces the identical pre-upscaled image.
            b, h, w, c = image.shape
            target_h = int(round(h * float(upscale_by) / 8)) * 8
            target_w = int(round(w * float(upscale_by) / 8)) * 8
            image = upscale_model.upscale(image)
            if image.shape[1] != target_h or image.shape[2] != target_w:
                image = jax.image.resize(
                    image, (b, target_h, target_w, c), method="cubic"
                )
            upscale_by = 1.0

        # Mode selection, decided identically on master and workers from
        # shared inputs (reference _determine_processing_mode): dynamic
        # (whole-image queue) for large video batches, static (tile
        # queue) otherwise.
        dynamic = batch > 1 and batch >= int(dynamic_threshold)
        common = dict(
            bundle=model, image=image, pos=positive, neg=negative,
            upscale_by=float(upscale_by), tile=tile, tile_h=tile_h,
            padding=int(tile_padding), steps=int(steps),
            sampler=sampler_name, scheduler=scheduler, cfg=float(cfg),
            denoise=float(denoise), seed=int(seed),
            upscale_method=upscale_method, context=context,
            mask_blur=int(mask_blur), tiled_decode=bool(tiled_decode),
            uniform=bool(force_uniform_tiles),
        )

        if is_worker:
            from .usdu_elastic import run_worker_dynamic, run_worker_loop

            worker_fn = run_worker_dynamic if dynamic else run_worker_loop
            worker_fn(
                job_id=job_id, worker_id=worker_id, master_url=master_url,
                **common,
            )
            return (image,)

        if enabled and getattr(context, "server", None) is not None:
            from .usdu_elastic import run_master_dynamic, run_master_elastic

            if dynamic:
                return (
                    run_master_dynamic(
                        job_id=job_id, enabled_worker_ids=list(enabled), **common
                    ),
                )
            return (
                run_master_elastic(
                    job_id=job_id, enabled_worker_ids=list(enabled),
                    mesh=mesh, **common,
                ),
            )

        from ..telemetry import get_tracer

        with annotate_attention():
            out = upscale_ops.run_upscale(
                bundle=model, image=image, pos=positive, neg=negative, mesh=mesh,
                upscale_by=float(upscale_by), tile=tile, tile_h=tile_h,
                padding=int(tile_padding),
                steps=int(steps), sampler=sampler_name, scheduler=scheduler,
                cfg=float(cfg), denoise=float(denoise), seed=int(seed),
                upscale_method=upscale_method,
                mask_blur=int(mask_blur), tiled_decode=bool(tiled_decode),
                uniform=bool(force_uniform_tiles),
            )
        # named as the jitted function that ran (ops/upscale.run_upscale)
        get_tracer().device_span(
            "upscale_single" if len(out.devices()) == 1 else "upscale_mesh", out
        )
        return (out,)
