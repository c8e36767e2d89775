"""Core workflow nodes (checkpoint → encode → sample → decode → save).

The minimum node set the reference's bundled workflows assume from
ComfyUI (reference workflows/*.json: CheckpointLoaderSimple,
CLIPTextEncode, EmptyLatentImage, KSampler, VAEDecode/Encode,
SaveImage/PreviewImage, LoadImage, ImageScale). Data contracts:

    MODEL / CLIP / VAE — views over a models.pipeline.PipelineBundle
    CONDITIONING       — jnp array [B, T, context_dim]
    LATENT             — {"samples": [B, h, w, C]} dict (ComfyUI parity)
    IMAGE              — [B, H, W, C] float array in [0, 1]

A `SeedSpec` flows out of DistributedSeed in mesh-parallel runs: it
tells KSampler to generate one sample per mesh participant in a single
SPMD program instead of replaying the graph N times (the TPU-native
collapse of the reference's prompt replication).
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..models import pipeline as pl
from ..ops import samplers as smp
from ..ops.attention import route_log as attention_route_log
from ..ops.tiled_vae import vae_apply
from ..parallel.mesh import (
    DATA_AXIS,
    data_axis_size,
    replicated,
    shard_map_compat,
)
from ..utils import image as img_utils
from ..utils.logging import log
from .registry import register_node


@dataclasses.dataclass(frozen=True)
class SeedSpec:
    """A seed plus how to spread it across participants."""

    base_seed: int
    per_participant: bool = False  # True ⇒ fold over the mesh data axis
    worker_index: int = -1         # elastic tier: fixed offset applied

    def effective_seed(self) -> int:
        """The single-device seed: base plus the elastic-tier worker
        offset (reference DistributedSeed's seed + worker_index + 1;
        master / non-worker runs use the base seed unchanged). The one
        place the offset rule lives — every sampler node calls this."""
        return self.base_seed + (
            self.worker_index + 1 if self.worker_index >= 0 else 0
        )


def resolve_seed(seed: Any) -> SeedSpec:
    if isinstance(seed, SeedSpec):
        return seed
    return SeedSpec(base_seed=int(seed))


def _get_bundle(context, model_name: str) -> pl.PipelineBundle:
    if model_name not in context.pipelines:
        log(f"loading pipeline {model_name!r}")
        context.pipelines[model_name] = pl.load_pipeline(model_name)
    return context.pipelines[model_name]


def _require_part(bundle, part: str, node: str) -> None:
    """A bundle need not hold every part (a language-model checkpoint
    has no denoiser, VAE or text encoder; UNETLoader's has no VAE): say
    which is missing instead of failing inside the model code."""
    if getattr(bundle, part, None) is None:
        held = ", ".join(sorted(bundle.params)) or "nothing"
        raise ValueError(
            f"{node} needs a bundle with a {part} part; "
            f"{bundle.model_name!r} holds {held}"
        )


@register_node
class CheckpointLoaderSimple:
    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"ckpt_name": ("STRING", {"default": "tiny-unet"})}}

    RETURN_TYPES = ("MODEL", "CLIP", "VAE")
    FUNCTION = "load"

    def load(self, ckpt_name: str, context=None):
        from .nodes_loaders import _stem  # no extension; a registry name ("ouro-2.6b") whole
        name = _stem(ckpt_name)
        bundle = _get_bundle(context, name)
        _annotate_load(bundle)
        return (bundle, bundle, bundle)


def _annotate_load(bundle) -> None:
    """What the bundle holds, on the `node.CheckpointLoaderSimple`
    span: per component (`unet`, `vae`, `te`, `te2`, ...) its parameter
    count and its bytes as stored, and the fullest device's
    `peak_bytes` after the load where the backend reports one."""
    from ..parallel.sharding import params_byte_size
    from ..telemetry import get_tracer

    attrs = {}
    for part, tree in bundle.params.items():
        attrs[f"{part}_params"] = sum(
            int(np.prod(np.shape(leaf)))
            for leaf in jax.tree_util.tree_leaves(tree)
        )
        attrs[f"{part}_bytes"] = params_byte_size(tree)
    peaks = [
        (device.memory_stats() or {}).get("peak_bytes_in_use")
        for device in jax.local_devices()
    ]
    if any(peaks):
        attrs["peak_bytes"] = int(max(p for p in peaks if p))
    get_tracer().annotate(**attrs)


@register_node
class LoraLoader:
    """Merge a kohya-format LoRA into the model + text-encoder weights
    (ComfyUI LoraLoader parity; the reference free-rides on ComfyUI
    for this). LoRA files resolve from CDT_LORA_DIR (or an absolute
    path). Merging clones the bundle so other graph branches keep the
    unpatched weights."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "model": ("MODEL",),
                "clip": ("CLIP",),
                "lora_name": ("STRING", {"default": ""}),
                "strength_model": ("FLOAT", {"default": 1.0}),
                "strength_clip": ("FLOAT", {"default": 1.0}),
            }
        }

    RETURN_TYPES = ("MODEL", "CLIP")
    FUNCTION = "load_lora"

    @staticmethod
    def _resolve_lora_path(name: str) -> str:
        """LoRA file resolution shared with LoraLoaderModelOnly:
        absolute path, or CDT_LORA_DIR/<name>[.safetensors]."""
        path = str(name)
        if not os.path.isabs(path):
            root = os.environ.get("CDT_LORA_DIR", "")
            candidate = os.path.join(root, path) if root else path
            if not os.path.exists(candidate) and not candidate.endswith(
                ".safetensors"
            ):
                candidate += ".safetensors"
            path = candidate
        if not os.path.exists(path):
            raise FileNotFoundError(f"LoRA not found: {path}")
        return path

    def load_lora(self, model: pl.PipelineBundle, clip, lora_name,
                  strength_model=1.0, strength_clip=1.0, context=None):
        from ..models import get_config
        from ..models.lora import apply_lora, read_lora
        from ..models.registry import DUAL_TEXT_ENCODERS

        path = self._resolve_lora_path(str(lora_name))
        lora_sd = read_lora(path)
        # UNet weights come from the MODEL input, text-encoder weights
        # from the CLIP input — the two may be different bundles
        # (ComfyUI semantics: each output patches its own input). The
        # bundle records the encoder registry names it was built with;
        # the name heuristics only cover bundles from older callers.
        te_name = clip.te_name
        te2_name = clip.te2_name
        if te_name is None:
            dual = DUAL_TEXT_ENCODERS.get(clip.model_name)
            if dual:
                te_name, te2_name = dual
            else:
                te_name = ("tiny-te" if clip.model_name.startswith("tiny")
                           else "clip-l")
        parts = {"unet": model.params["unet"], "te": clip.params["te"]}
        has_te2 = te2_name is not None and "te2" in clip.params
        if has_te2:
            parts["te2"] = clip.params["te2"]
        patched, unmatched = apply_lora(
            parts,
            lora_sd,
            get_config(model.model_name),
            get_config(te_name),
            te2_cfg=get_config(te2_name) if has_te2 else None,
            strength=float(strength_model),
            te_strength=float(strength_clip),
        )
        if unmatched:
            log(f"LoRA {os.path.basename(path)}: {len(unmatched)} "
                f"unmatched module(s), e.g. {unmatched[:3]}")
        model_params = dict(model.params)
        model_params["unet"] = patched["unet"]
        clip_params = dict(clip.params)
        clip_params["te"] = patched["te"]
        if has_te2:
            clip_params["te2"] = patched["te2"]
        return (
            dataclasses.replace(model, params=model_params),
            dataclasses.replace(clip, params=clip_params),
        )


@register_node
class CLIPTextEncode:
    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "text": ("STRING", {"default": ""}),
                "clip": ("CLIP",),
            }
        }

    RETURN_TYPES = ("CONDITIONING",)
    FUNCTION = "encode"

    def encode(self, text: str, clip: pl.PipelineBundle, context=None):
        # Conditioning carrying the pooled vector: SDXL-class adm and
        # Flux-class vector_in models consume it; families without
        # pooled conditioning ignore the field (pipeline._make_model_fn)
        from ..telemetry import get_tracer

        _require_part(clip, "text_encoder", "CLIPTextEncode")
        cond = pl.encode_text_pooled(clip, [str(text)])
        get_tracer().device_span("text_encode", cond.context)
        return (cond,)


@register_node
class CLIPTextEncodeFlux:
    """Flux dual-prompt encoding (ComfyUI CLIPTextEncodeFlux parity):
    t5xxl text feeds the T5 context, clip_l text the CLIP pooled
    vector, and guidance rides on the conditioning exactly like the
    FluxGuidance node writes it (pipeline.encode_text_pooled_flux)."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "clip": ("CLIP",),
                "clip_l": ("STRING", {"default": ""}),
                "t5xxl": ("STRING", {"default": ""}),
                "guidance": ("FLOAT", {"default": 3.5}),
            }
        }

    RETURN_TYPES = ("CONDITIONING",)
    FUNCTION = "encode"

    def encode(self, clip, clip_l="", t5xxl="", guidance=3.5, context=None):
        return (
            pl.encode_text_pooled_flux(
                clip, [str(t5xxl)], [str(clip_l)], guidance=float(guidance)
            ),
        )


@register_node
class CLIPTextEncodeSDXL:
    """SDXL dual-prompt encoding (ComfyUI CLIPTextEncodeSDXL parity):
    text_l feeds the CLIP-L tower, text_g the CLIP-G tower, and the
    six size ints ride on the conditioning as the adm Fourier size
    embeddings (orig h/w, crop t/l, target h/w) — overriding the
    KSampler default of zero crops + latent-derived sizes."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "clip": ("CLIP",),
                "width": ("INT", {"default": 1024}),
                "height": ("INT", {"default": 1024}),
                "crop_w": ("INT", {"default": 0}),
                "crop_h": ("INT", {"default": 0}),
                "target_width": ("INT", {"default": 1024}),
                "target_height": ("INT", {"default": 1024}),
                "text_g": ("STRING", {"default": ""}),
                "text_l": ("STRING", {"default": ""}),
            }
        }

    RETURN_TYPES = ("CONDITIONING",)
    FUNCTION = "encode"

    def encode(self, clip: pl.PipelineBundle, width=1024, height=1024,
               crop_w=0, crop_h=0, target_width=1024, target_height=1024,
               text_g="", text_l="", context=None):
        size_cond = (
            int(height), int(width), int(crop_h), int(crop_w),
            int(target_height), int(target_width),
        )
        return (
            pl.encode_text_pooled_sdxl(
                clip, [str(text_g)], [str(text_l)], size_cond=size_cond
            ),
        )


@register_node
class ConditioningConcat:
    """Concatenate two conditionings along the TOKEN axis (ComfyUI
    ConditioningConcat parity): the model cross-attends over both
    prompts' tokens in one pass. Everything else (pooled, hints,
    masks) rides from conditioning_to."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "conditioning_to": ("CONDITIONING",),
                "conditioning_from": ("CONDITIONING",),
            }
        }

    RETURN_TYPES = ("CONDITIONING",)
    FUNCTION = "concat"

    def concat(self, conditioning_to, conditioning_from, context=None):
        from ..ops.conditioning import as_conditioning, map_conditioning

        src = conditioning_from
        if isinstance(src, (list, tuple)):
            src = src[0]  # reference behavior: first `from` entry
        from_c = as_conditioning(src)

        def patch(to_c):
            to_c.context = jnp.concatenate(
                [to_c.context, from_c.context], axis=1
            )
            return to_c

        return (map_conditioning(conditioning_to, patch),)


@register_node
class ImageBatch:
    """Batch-concatenate two images (ComfyUI ImageBatch parity): the
    second image resizes to the first's geometry when they differ."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {"image1": ("IMAGE",), "image2": ("IMAGE",)}
        }

    RETURN_TYPES = ("IMAGE",)
    FUNCTION = "batch"

    def batch(self, image1, image2, context=None):
        if image1.shape[1:3] != image2.shape[1:3]:
            # reference semantics: center-crop to the target aspect,
            # THEN bilinear-resize (common_upscale 'center') — a raw
            # stretch would squash aspect-mismatched frames
            from ..ops import upscale as up_ops

            h, w = image1.shape[1], image1.shape[2]
            (image2,) = up_ops.center_crop_to_aspect([image2], h, w)
            image2 = up_ops.resize_image(image2, h, w, "bilinear")
        return (jnp.concatenate([image1, image2], axis=0),)


@register_node
class ImageCrop:
    """Crop a pixel region (ComfyUI ImageCrop parity): x/y clamp into
    the frame, width/height clamp to the remaining extent."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "image": ("IMAGE",),
                "width": ("INT", {"default": 512}),
                "height": ("INT", {"default": 512}),
                "x": ("INT", {"default": 0}),
                "y": ("INT", {"default": 0}),
            }
        }

    RETURN_TYPES = ("IMAGE",)
    FUNCTION = "crop"

    def crop(self, image, width=512, height=512, x=0, y=0, context=None):
        h, w = image.shape[1], image.shape[2]
        x0 = min(max(int(x), 0), w - 1)
        y0 = min(max(int(y), 0), h - 1)
        x1 = min(x0 + max(int(width), 1), w)
        y1 = min(y0 + max(int(height), 1), h)
        return (image[:, y0:y1, x0:x1, :],)


@register_node
class LatentComposite:
    """Paste one latent into another at a pixel offset (ComfyUI
    LatentComposite parity): offsets are pixels, converted to latent
    cells by the nominal 8x node convention; `feather` blends a linear
    ramp that many pixels into the pasted region's interior edges."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "samples_to": ("LATENT",),
                "samples_from": ("LATENT",),
                "x": ("INT", {"default": 0}),
                "y": ("INT", {"default": 0}),
                "feather": ("INT", {"default": 0}),
            }
        }

    RETURN_TYPES = ("LATENT",)
    FUNCTION = "composite"

    def composite(self, samples_to: dict, samples_from: dict, x=0, y=0,
                  feather=0, context=None):
        dst = samples_to["samples"]
        src = samples_from["samples"]
        lx = max(int(x), 0) // 8
        ly = max(int(y), 0) // 8
        fe = max(int(feather), 0) // 8
        h = min(src.shape[1], dst.shape[1] - ly)
        w = min(src.shape[2], dst.shape[2] - lx)
        out = dict(samples_to)
        if h <= 0 or w <= 0:
            return (out,)
        region = src[:, :h, :w, :]
        if fe > 0:
            # linear ramp into the pasted interior; an edge flush with
            # the destination border keeps full weight (the reference
            # skips the ramp there). Opposing edges MULTIPLY (the
            # reference composes each edge's factor), so a region
            # narrower than 2*fe blends weaker than either ramp alone
            ramp_y = jnp.ones((h,), jnp.float32)
            ramp_x = jnp.ones((w,), jnp.float32)
            idx_y = jnp.arange(h, dtype=jnp.float32)
            idx_x = jnp.arange(w, dtype=jnp.float32)
            if ly > 0:
                ramp_y = ramp_y * jnp.clip((idx_y + 1) / fe, 0.0, 1.0)
            if ly + h < dst.shape[1]:
                ramp_y = ramp_y * jnp.clip((h - idx_y) / fe, 0.0, 1.0)
            if lx > 0:
                ramp_x = ramp_x * jnp.clip((idx_x + 1) / fe, 0.0, 1.0)
            if lx + w < dst.shape[2]:
                ramp_x = ramp_x * jnp.clip((w - idx_x) / fe, 0.0, 1.0)
            mask = (ramp_y[:, None] * ramp_x[None, :])[None, :, :, None]
        else:
            mask = 1.0
        patch = dst[:, ly:ly + h, lx:lx + w, :]
        blended = region * mask + patch * (1.0 - mask)
        out["samples"] = dst.at[:, ly:ly + h, lx:lx + w, :].set(blended)
        return (out,)


@register_node
class RepeatLatentBatch:
    """Repeat latents along the batch axis (ComfyUI RepeatLatentBatch
    parity); the noise_mask repeats with them."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "samples": ("LATENT",),
                "amount": ("INT", {"default": 1}),
            }
        }

    RETURN_TYPES = ("LATENT",)
    FUNCTION = "repeat"

    def repeat(self, samples: dict, amount=1, context=None):
        n = max(1, int(amount))
        out = dict(samples)
        out["samples"] = jnp.concatenate([samples["samples"]] * n, axis=0)
        mask = samples.get("noise_mask")
        if mask is not None and getattr(mask, "ndim", 0) >= 3 and (
            mask.shape[0] == samples["samples"].shape[0]
        ):
            out["noise_mask"] = jnp.concatenate([mask] * n, axis=0)
        return (out,)


@register_node
class CLIPSetLastLayer:
    """Clip-skip (ComfyUI CLIPSetLastLayer parity): stop the CLIP
    tower stop_at_clip_layer blocks from the end when producing the
    conditioning context (-1 = the full stack, -2 = the classic
    "clip skip 2", ...). Applies to every CLIP tower in the bundle;
    T5-class towers are unaffected. The pooled vector always comes
    from the full stack (reference semantics)."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "clip": ("CLIP",),
                "stop_at_clip_layer": ("INT", {"default": -1}),
            }
        }

    RETURN_TYPES = ("CLIP",)
    FUNCTION = "set_last_layer"

    def set_last_layer(self, clip: pl.PipelineBundle,
                       stop_at_clip_layer=-1, context=None):
        stop = int(stop_at_clip_layer)
        if stop >= 0:
            raise ValueError(
                "stop_at_clip_layer counts from the end and must be "
                "negative (-1 = last layer)"
            )
        return (dataclasses.replace(clip, clip_skip=-stop - 1),)


@register_node
class EmptyLatentImage:
    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "width": ("INT", {"default": 512}),
                "height": ("INT", {"default": 512}),
                "batch_size": ("INT", {"default": 1}),
            }
        }

    RETURN_TYPES = ("LATENT",)
    FUNCTION = "generate"

    def generate(self, width: int, height: int, batch_size: int, context=None):
        # latent geometry fixed at the SD 8x factor; KSampler rescales
        # PLACEHOLDER latents (the "empty" marker) against the bundle's
        # actual latent layout if it differs — real content (VAEEncode,
        # chained samplers, LatentUpscale) is never rebuilt
        return (
            {
                "samples": jnp.zeros(
                    (int(batch_size), int(height) // 8, int(width) // 8, 4)
                ),
                "width": int(width),
                "height": int(height),
                "empty": True,
            },
        )


@register_node
class KSampler:
    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "model": ("MODEL",),
                "seed": ("INT", {"default": 0}),
                "steps": ("INT", {"default": 20}),
                "cfg": ("FLOAT", {"default": 7.0}),
                "sampler_name": ("STRING", {"default": "euler"}),
                "scheduler": ("STRING", {"default": "karras"}),
                "positive": ("CONDITIONING",),
                "negative": ("CONDITIONING",),
                "latent_image": ("LATENT",),
                "denoise": ("FLOAT", {"default": 1.0}),
            }
        }

    RETURN_TYPES = ("LATENT",)
    FUNCTION = "sample"

    def sample(
        self,
        model: pl.PipelineBundle,
        seed,
        steps: int,
        cfg: float,
        sampler_name: str,
        scheduler: str,
        positive,
        negative,
        latent_image: dict,
        denoise: float = 1.0,
        context=None,
    ):
        from ..telemetry import get_tracer

        spec = resolve_seed(seed)
        bundle = model
        _require_part(bundle, "unet", "KSampler")
        latents, noise_mask, extras = _prep_latents(bundle, latent_image)
        fixed = bool(latent_image.get("batch_index_fixed", False))
        _annotate_sampling(
            bundle, latents, positive, int(steps), float(cfg), sampler_name
        )

        mesh = getattr(context, "mesh", None) if context is not None else None
        with annotate_attention():
            if (
                spec.per_participant
                and mesh is not None
                and data_axis_size(mesh) > 1
            ):
                _reject_fixed_on_mesh(fixed)
                param, shift = pl.model_schedule_info(bundle)
                sigmas = smp.get_model_sigmas(
                    param, scheduler, int(steps), denoise=float(denoise),
                    flow_shift=shift,
                )
                result = _sample_mesh(
                    bundle, mesh, spec, sigmas, cfg, sampler_name,
                    positive, negative, latents, noise_mask,
                )
            else:
                result = {
                    "samples": pl.img2img_latents(
                        bundle,
                        latents,
                        positive,
                        negative,
                        steps=int(steps),
                        sampler=sampler_name,
                        scheduler=scheduler,
                        cfg_scale=float(cfg),
                        denoise=float(denoise),
                        seed=int(spec.effective_seed()),
                        noise_mask=noise_mask,
                        batch_fixed_noise=fixed,
                    )
                }
        get_tracer().device_span("sampler", result["samples"])
        return ({**extras, **result},)


@contextlib.contextmanager
def annotate_attention():
    """Around the call that may trace a node's program: the node's span
    gets `attention`, every `dot_product_attention` call made inside
    with the implementation it took (`ops/attention.route_log`). Only
    the request that traces the program has any."""
    from ..telemetry import get_tracer

    with attention_route_log() as routes:
        yield
    if routes:
        get_tracer().annotate(attention=", ".join(sorted(set(routes))))


def _annotate_sampling(
    bundle, latents, positive, steps: int, cfg: float, sampler_name: str
) -> None:
    """What the request asked of the denoiser, on the `node.KSampler`
    span: the model `family`, `tokens` (the longest sequence its
    attention sees: the latent's patches, plus the text tokens where
    the family attends to both jointly) and `evals` (model evaluations:
    sigma pairs x the sampler's evaluations a pair x two where a
    negative is evaluated beside the positive)."""
    from ..models import get_config
    from ..models.registry import model_family
    from ..ops.conditioning import as_conditioning
    from ..telemetry import get_tracer

    family = model_family(bundle.model_name)
    patch = getattr(get_config(bundle.model_name), "patch_size", 1)
    per_token = patch ** 2 if isinstance(patch, int) else int(np.prod(patch))
    tokens = int(np.prod(latents.shape[1:-1])) // per_token
    if family in ("mmdit", "sd3"):
        first = positive[0] if isinstance(positive, (list, tuple)) else positive
        tokens += as_conditioning(first).context.shape[1]
    evals = smp.model_evals_per_scan(sampler_name, steps) * (
        1 if cfg == 1.0 else 2
    )
    get_tracer().annotate(family=family, tokens=int(tokens), evals=int(evals))


def _prep_latents(bundle, latent_image: dict):
    """Shared KSampler/KSamplerAdvanced input normalization: rebuild
    PLACEHOLDER latents to the bundle's real latent layout (honor the
    requested pixel geometry / channel count when the bundle's VAE
    differs from the nominal 8x 4-channel layout EmptyLatentImage
    assumes — Flux-class VAEs are 8x but 16ch; real content from
    chained samplers / VAEEncode / LatentUpscale is never replaced),
    normalize the noise_mask to latent resolution, and collect the
    extras the output dict must carry forward (ComfyUI common_ksampler
    parity: chained inpaint passes stay masked; the 'empty' marker does
    NOT propagate)."""
    latents = latent_image["samples"]
    if latent_image.get("empty") and "width" in latent_image and (
        bundle.latent_scale != 8
        or latents.shape[-1] != bundle.latent_channels
    ):
        lh = latent_image["height"] // bundle.latent_scale
        lw = latent_image["width"] // bundle.latent_scale
        if (
            latents.shape[1],
            latents.shape[2],
            latents.shape[3],
        ) != (lh, lw, bundle.latent_channels):
            latents = jnp.zeros(
                (latents.shape[0], lh, lw, bundle.latent_channels)
            )
    noise_mask = latent_image.get("noise_mask")
    if noise_mask is not None:
        noise_mask = _mask_to_latent(
            noise_mask, latents.shape[1], latents.shape[2]
        )
    extras = {
        k: v for k, v in latent_image.items()
        if k not in ("samples", "empty")
    }
    return latents, noise_mask, extras


def _reject_fixed_on_mesh(fixed: bool) -> None:
    """LatentBatchSeedBehavior 'fixed' + per-participant mesh fan-out
    is contradictory (participants exist to render DIFFERENT noise);
    silently honoring one of the two would read as the other
    working."""
    if fixed:
        raise ValueError(
            "LatentBatchSeedBehavior 'fixed' cannot combine with "
            "per-participant mesh fan-out (DistributedSeed); use a "
            "plain INT seed or seed_behavior='random'"
        )


def _sample_mesh(
    bundle, mesh, spec, sigmas, cfg, sampler_name,
    positive, negative, latents, noise_mask=None,
) -> dict:
    """One SPMD program: every participant samples its folded seed over
    the given sigma grid. Output batch = participants x input batch,
    participant-major, sharded over the data axis (the collector
    materialises it). Shared by KSampler (full/denoise-truncated grid)
    and KSamplerAdvanced (windowed grid). Always noise-adding: a
    no-noise pass is deterministic in its input, so the nodes route it
    to the single-device batched path instead of fanning out."""
    from ..parallel.seeds import participant_keys
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = data_axis_size(mesh)
    keys = participant_keys(jax.random.key(spec.base_seed), n)
    keys = jax.device_put(keys, NamedSharding(mesh, P(DATA_AXIS)))
    everywhere = replicated(mesh)
    params = jax.device_put(bundle.params, everywhere)
    pos = jax.device_put(positive, everywhere)
    neg = jax.device_put(negative, everywhere)
    base = jax.device_put(latents, everywhere)
    extra = ()
    if noise_mask is not None:
        extra = (
            jax.device_put(
                jnp.clip(noise_mask.astype(jnp.float32), 0.0, 1.0), everywhere
            ),
        )
    out = _sample_mesh_jit(
        pl._Static(bundle), pl._Static(mesh),
        tuple(float(s) for s in np.asarray(sigmas)), float(cfg), sampler_name,
        keys, params, pos, neg, base, *extra,
    )
    return {"samples": out, "participant_major": True}


@partial(
    jax.jit,
    static_argnames=(
        "bundle_static", "mesh_static", "sigmas_t", "cfg", "sampler_name",
    ),
)
def _sample_mesh_jit(
    bundle_static, mesh_static, sigmas_t: tuple, cfg: float,
    sampler_name: str, keys, params, pos, neg, base, *maybe_mask,
):
    """The compiled half of _sample_mesh, keyed on (bundle, mesh, sigma
    grid, cfg, sampler) so a second request with another seed reuses
    the program instead of tracing and building it again. sigmas_t is
    a static tuple for the same reason as in pipeline._custom_sigmas_jit:
    multistep samplers precompute numpy coefficients from the grid."""
    from jax.sharding import PartitionSpec as P

    bundle = bundle_static.value
    sigmas = jnp.asarray(sigmas_t, jnp.float32)
    param, _shift = pl.model_schedule_info(bundle)

    def per_chip(keys_shard, params, pos, neg, base, *maybe_mask):
        mask_arr = maybe_mask[0] if maybe_mask else None
        key = keys_shard[0]
        noise_key, anc_key = jax.random.split(key)
        noise = jax.random.normal(noise_key, base.shape)
        x = smp.noise_latents(param, base, noise, sigmas[0])
        model_fn = pl.guided_model(bundle, params, cfg)
        if mask_arr is not None:
            model_fn = smp.masked_inpaint_model(
                model_fn, param, base, noise, mask_arr
            )

        out = smp.sample(
            model_fn, x, sigmas, (pos, neg), sampler_name, anc_key,
            flow=(param == "flow"),
        )
        if mask_arr is not None:
            out = out * mask_arr + base * (1.0 - mask_arr)
        return out

    in_specs = (P(DATA_AXIS),) + (P(),) * (4 + len(maybe_mask))
    return shard_map_compat(
        per_chip,
        mesh=mesh_static.value,
        in_specs=in_specs,
        out_specs=P(DATA_AXIS),
        check=False,
    )(keys, params, pos, neg, base, *maybe_mask)


@register_node
class KSamplerAdvanced:
    """Windowed-schedule sampler (ComfyUI KSamplerAdvanced parity):
    sample steps [start_at_step, end_at_step] of the full schedule,
    optionally without adding noise (the refine pass of a two-pass
    workflow consuming a leftover-noise latent) and optionally leaving
    leftover noise for a later pass
    (return_with_leftover_noise="enable")."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "model": ("MODEL",),
                "add_noise": ("STRING", {"default": "enable"}),
                "noise_seed": ("INT", {"default": 0}),
                "steps": ("INT", {"default": 20}),
                "cfg": ("FLOAT", {"default": 7.0}),
                "sampler_name": ("STRING", {"default": "euler"}),
                "scheduler": ("STRING", {"default": "karras"}),
                "positive": ("CONDITIONING",),
                "negative": ("CONDITIONING",),
                "latent_image": ("LATENT",),
                "start_at_step": ("INT", {"default": 0}),
                "end_at_step": ("INT", {"default": 10000}),
                "return_with_leftover_noise": ("STRING", {"default": "disable"}),
            }
        }

    RETURN_TYPES = ("LATENT",)
    FUNCTION = "sample"

    def sample(
        self,
        model: pl.PipelineBundle,
        add_noise,
        noise_seed,
        steps: int,
        cfg: float,
        sampler_name: str,
        scheduler: str,
        positive,
        negative,
        latent_image: dict,
        start_at_step: int = 0,
        end_at_step: int = 10000,
        return_with_leftover_noise="disable",
        context=None,
    ):
        def flag(value, name):
            value = str(value)
            if value not in ("enable", "disable"):
                raise ValueError(f"{name} must be 'enable' or 'disable'")
            return value == "enable"

        do_noise = flag(add_noise, "add_noise")
        force_full = not flag(
            return_with_leftover_noise, "return_with_leftover_noise"
        )
        spec = resolve_seed(noise_seed)
        bundle = model
        latents, noise_mask, extras = _prep_latents(bundle, latent_image)
        fixed = bool(latent_image.get("batch_index_fixed", False))

        mesh = getattr(context, "mesh", None) if context is not None else None
        # mesh fan-out only when noise IS added: participant diversity
        # comes from per-chip folded noise keys. A no-noise refine pass
        # is deterministic in its input — replicating it across chips
        # would stack identical copies and square the batch; the
        # single-device path below processes the (participant-major)
        # input batch in one batched program instead.
        if (
            spec.per_participant
            and mesh is not None
            and data_axis_size(mesh) > 1
            and do_noise
        ):
            _reject_fixed_on_mesh(fixed)
            param, shift = pl.model_schedule_info(bundle)
            sigmas = pl.advanced_window_sigmas(
                param, scheduler, int(steps), int(start_at_step),
                int(end_at_step), force_full, shift,
            )
            result = _sample_mesh(
                bundle, mesh, spec, sigmas, cfg, sampler_name,
                positive, negative, latents, noise_mask,
            )
            return ({**extras, **result},)

        effective_seed = spec.effective_seed()
        out = pl.img2img_latents_advanced(
            bundle,
            latents,
            positive,
            negative,
            steps=int(steps),
            sampler=sampler_name,
            scheduler=scheduler,
            cfg_scale=float(cfg),
            seed=int(effective_seed),
            start_at_step=int(start_at_step),
            end_at_step=int(end_at_step),
            add_noise=do_noise,
            force_full_denoise=force_full,
            noise_mask=noise_mask,
            batch_fixed_noise=fixed,
        )
        return ({**extras, "samples": out},)


@register_node
class VAELoader:
    """Load a standalone VAE (ComfyUI VAELoader parity): a registry
    VAE name (vae-sd, vae-flux, vae-sd3, ...) whose real weights
    resolve through CDT_CHECKPOINT_DIR/<name>.{safetensors,ckpt} —
    standalone bare-key files and full-checkpoint first_stage_model
    layouts both map. The output plugs into any VAE input, replacing
    the checkpoint's bundled VAE."""

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"vae_name": ("STRING", {"default": "vae-sd"})}}

    RETURN_TYPES = ("VAE",)
    FUNCTION = "load_vae"

    def load_vae(self, vae_name: str, context=None):
        # real ComfyUI workflows carry filenames ("vae-sd.safetensors")
        # — resolve by stem like CheckpointLoaderSimple
        return (pl.load_vae(os.path.splitext(str(vae_name))[0]),)


@register_node
class VAEDecode:
    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"samples": ("LATENT",), "vae": ("VAE",)}}

    RETURN_TYPES = ("IMAGE",)
    FUNCTION = "decode"

    def decode(self, samples: dict, vae: pl.PipelineBundle, context=None):
        mesh = getattr(context, "mesh", None) if context is not None else None
        if (
            samples.get("participant_major")
            and mesh is not None
            and data_axis_size(mesh) > 1
        ):
            return (_decode_mesh(vae, mesh, samples["samples"]),)
        return (_vae_pass(vae, samples["samples"], "decode"),)


def _vae_pass(vae, x, method: str) -> jax.Array:
    """A whole decode or encode of `x` through the bundle's VAE as one
    dispatched program (ops.tiled_vae.vae_apply). The `node.*` span it
    runs under says which path the request took: `programs` 1 here,
    `mesh_programs` 1 in `_decode_mesh`."""
    from ..telemetry import get_tracer

    _require_part(vae, "vae", "VAEDecode" if method == "decode" else "VAEEncode")
    tracer = get_tracer()
    tracer.annotate(programs=1)
    out = vae_apply(vae.vae, vae.params["vae"], x, method=method)
    tracer.device_span(f"vae_{method}", out)
    return out


def _decode_mesh(vae, mesh, latents) -> jax.Array:
    """Decode a participant-major batch where it lies: each chip
    decodes its own images under shard_map and the result stays
    sharded over the data axis for the collector. Decoding the sharded
    batch with plain ops instead asks XLA to partition the VAE
    mid-block's Pallas kernel, which it cannot ("Mosaic kernels cannot
    be automatically partitioned" — the first four-chip txt2img run)."""
    from ..telemetry import get_tracer

    get_tracer().annotate(mesh_programs=1)
    params = jax.device_put(vae.params["vae"], replicated(mesh))
    return _decode_mesh_jit(pl._Static(vae), pl._Static(mesh), params, latents)


@partial(jax.jit, static_argnames=("vae_static", "mesh_static"))
def _decode_mesh_jit(vae_static, mesh_static, params, latents):
    from jax.sharding import PartitionSpec as P

    vae = vae_static.value
    return shard_map_compat(
        lambda p, z: vae.vae.apply(p, z, method="decode"),
        mesh=mesh_static.value,
        in_specs=(P(), P(DATA_AXIS)),
        out_specs=P(DATA_AXIS),
        check=False,
    )(params, latents)


@register_node
class VAEEncode:
    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"pixels": ("IMAGE",), "vae": ("VAE",)}}

    RETURN_TYPES = ("LATENT",)
    FUNCTION = "encode"

    def encode(self, pixels, vae: pl.PipelineBundle, context=None):
        return ({"samples": _vae_pass(vae, pixels, "encode")},)


def _mask_to_latent(mask, lh: int, lw: int) -> jax.Array:
    """MASK ([H,W], [B,H,W] or [B,H,W,1]; 1 = regenerate) →
    [B, lh, lw, 1]."""
    m = jnp.asarray(mask, jnp.float32)
    if m.ndim == 4:
        m = m[..., 0]
    if m.ndim == 2:
        m = m[None]
    if m.shape[1:] != (lh, lw):
        m = jax.image.resize(m, (m.shape[0], lh, lw), method="linear")
    return jnp.clip(m, 0.0, 1.0)[..., None]


@register_node
class VAEEncodeForInpaint:
    """Encode pixels for inpainting (reference-substrate ComfyUI node):
    the masked region is neutralized to mid-gray before encoding, the
    mask is grown by `grow_mask_by` pixels of context and attached at
    latent resolution as the latent's noise_mask (1 = regenerate;
    consumed by KSampler's pinned-region sampling)."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "pixels": ("IMAGE",),
                "vae": ("VAE",),
                "mask": ("MASK",),
            },
            "optional": {"grow_mask_by": ("INT", {"default": 6})},
        }

    RETURN_TYPES = ("LATENT",)
    FUNCTION = "encode"

    def encode(self, pixels, vae: pl.PipelineBundle, mask, grow_mask_by=6,
               context=None):
        b, h, w, _ = pixels.shape
        m = jnp.asarray(mask, jnp.float32)
        if m.ndim == 2:
            m = m[None]
        if m.shape[1:] != (h, w):
            m = jax.image.resize(m, (m.shape[0], h, w), method="linear")
        m = jnp.clip(m, 0.0, 1.0)
        # Pixels are neutralized with the UN-grown rounded mask; only
        # the emitted noise_mask is dilated, with a g x g max window
        # (~radius g/2) — the reference-stack kernel. Growing the
        # gray-filled region too would erase usable context around the
        # mask boundary (ADVICE r4).
        hard = (m > 0.5).astype(jnp.float32)
        g = int(grow_mask_by)
        grown = hard
        if g > 0:
            # reference convs with padding=ceil((g-1)/2) then crops to
            # [:h,:w]: output pixel i covers [i-ceil((g-1)/2),
            # i+floor((g-1)/2)] — for even g that's one extra pixel
            # toward -y/-x, which SAME padding would mirror
            lo, hi = (g - 1 + 1) // 2, (g - 1) // 2
            grown = jax.lax.reduce_window(
                hard, -jnp.inf, jax.lax.max, (1, g, g), (1, 1, 1),
                ((0, 0), (lo, hi), (lo, hi)),
            )
        neutral = pixels * (1.0 - hard[..., None]) + 0.5 * hard[..., None]
        z = _vae_pass(vae, neutral, "encode")
        return (
            {
                "samples": z,
                "noise_mask": _mask_to_latent(grown, z.shape[1], z.shape[2]),
                "width": int(w),
                "height": int(h),
            },
        )


@register_node
class ImagePadForOutpaint:
    """Pad an image for outpainting (reference-substrate ComfyUI
    node): extends the canvas with edge-replicated pixels and emits
    the matching MASK — 1 over the new region, with a squared
    feathering ramp reaching `feathering` pixels into the original
    image so the inpaint transition blends."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "image": ("IMAGE",),
                "left": ("INT", {"default": 0}),
                "top": ("INT", {"default": 0}),
                "right": ("INT", {"default": 0}),
                "bottom": ("INT", {"default": 0}),
                "feathering": ("INT", {"default": 40}),
            }
        }

    RETURN_TYPES = ("IMAGE", "MASK")
    FUNCTION = "expand"

    def expand(self, image, left=0, top=0, right=0, bottom=0,
               feathering=40, context=None):
        lf, tp, rt, bt = int(left), int(top), int(right), int(bottom)
        fe = int(feathering)
        padded = jnp.pad(
            image, ((0, 0), (tp, bt), (lf, rt), (0, 0)), mode="edge"
        )
        b, h, w, _ = padded.shape
        mask = np.ones((h, w), np.float32)
        y0, y1 = tp, h - bt
        x0, x1 = lf, w - rt
        inner = np.zeros((y1 - y0, x1 - x0), np.float32)
        if fe > 0:
            # distance of each original pixel to the nearest NEW edge
            yy = np.arange(y1 - y0, dtype=np.float32)[:, None]
            xx = np.arange(x1 - x0, dtype=np.float32)[None, :]
            d = np.full(inner.shape, np.inf, np.float32)
            if tp:
                d = np.minimum(d, yy)
            if bt:
                d = np.minimum(d, (y1 - y0 - 1) - yy)
            if lf:
                d = np.minimum(d, xx)
            if rt:
                d = np.minimum(d, (x1 - x0 - 1) - xx)
            ramp = np.clip((fe - d) / fe, 0.0, 1.0)
            inner = (ramp**2).astype(np.float32)
        mask[y0:y1, x0:x1] = inner
        return (padded, jnp.broadcast_to(jnp.asarray(mask)[None], (b, h, w)))


@register_node
class SetLatentNoiseMask:
    """Attach an inpainting mask to existing latents (reference
    substrate: ComfyUI SetLatentNoiseMask)."""

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"samples": ("LATENT",), "mask": ("MASK",)}}

    RETURN_TYPES = ("LATENT",)
    FUNCTION = "set_mask"

    def set_mask(self, samples: dict, mask, context=None):
        z = samples["samples"]
        out = dict(samples)
        out["noise_mask"] = _mask_to_latent(mask, z.shape[1], z.shape[2])
        return (out,)


@register_node
class ImageScale:
    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "image": ("IMAGE",),
                "upscale_method": ("STRING", {"default": "bilinear"}),
                "width": ("INT", {"default": 1024}),
                "height": ("INT", {"default": 1024}),
                "crop": ("STRING", {"default": "disabled"}),
            }
        }

    RETURN_TYPES = ("IMAGE",)
    FUNCTION = "scale"

    def scale(self, image, upscale_method, width, height, crop="disabled", context=None):
        from ..ops import upscale as up_ops

        height, width = up_ops.resolve_resize_dims(
            image.shape[1], image.shape[2], int(width), int(height)
        )
        if str(crop) == "center":
            (image,) = up_ops.center_crop_to_aspect([image], height, width)
        elif str(crop) != "disabled":
            raise ValueError(f"unknown crop mode {crop!r}; use disabled|center")
        out = up_ops.resize_image(image, height, width, str(upscale_method))
        return (jnp.clip(out, 0.0, 1.0),)


@register_node
class ImageScaleBy:
    """Scale an image by a factor (ComfyUI ImageScaleBy parity)."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "image": ("IMAGE",),
                "upscale_method": ("STRING", {"default": "bilinear"}),
                "scale_by": ("FLOAT", {"default": 1.0}),
            }
        }

    RETURN_TYPES = ("IMAGE",)
    FUNCTION = "scale"

    def scale(self, image, upscale_method="bilinear", scale_by=1.0,
              context=None):
        from ..ops import upscale as up_ops

        h, w = up_ops.scale_dims(image.shape[1], image.shape[2], scale_by)
        return ImageScale().scale(image, upscale_method, w, h)


@register_node
class ImageInvert:
    """Invert pixel values (ComfyUI ImageInvert parity)."""

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"image": ("IMAGE",)}}

    RETURN_TYPES = ("IMAGE",)
    FUNCTION = "invert"

    def invert(self, image, context=None):
        return (1.0 - image,)


@register_node
class LatentUpscale:
    """Resize latents to a target pixel size (the hi-res-fix substrate;
    ComfyUI LatentUpscale parity — latent grid = pixels/8 by the node
    convention, independent of the bundle's actual VAE factor)."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "samples": ("LATENT",),
                "upscale_method": ("STRING", {"default": "nearest-exact"}),
                "width": ("INT", {"default": 1024}),
                "height": ("INT", {"default": 1024}),
                "crop": ("STRING", {"default": "disabled"}),
            }
        }

    RETURN_TYPES = ("LATENT",)
    FUNCTION = "upscale"

    def upscale(self, samples: dict, upscale_method="nearest-exact",
                width=1024, height=1024, crop="disabled", context=None):
        from ..ops import upscale as up_ops

        z = samples["samples"]
        mask = samples.get("noise_mask")
        h, w = z.shape[1], z.shape[2]
        # latent cells = pixels // 8 (the node convention); 0 stays 0
        # so resolve_resize_dims applies the preserve-aspect rule
        lh, lw = up_ops.resolve_resize_dims(
            h, w, int(width) // 8, int(height) // 8
        )
        if str(crop) == "center":
            # the crop path slices mask and latents together, so the
            # mask normalizes to the source grid first (the no-crop
            # path resizes it once, directly to the target)
            if mask is not None:
                mask = _mask_to_latent(mask, h, w)
                z, mask = up_ops.center_crop_to_aspect([z, mask], lh, lw)
            else:
                (z,) = up_ops.center_crop_to_aspect([z], lh, lw)
        elif str(crop) != "disabled":
            raise ValueError(f"unknown crop mode {crop!r}; use disabled|center")
        out = dict(samples)
        out["samples"] = up_ops.resize_image(z, lh, lw, str(upscale_method))
        out["width"] = lw * 8
        out["height"] = lh * 8
        if mask is not None:
            out["noise_mask"] = _mask_to_latent(mask, lh, lw)
        return (out,)


@register_node
class LatentUpscaleBy:
    """Scale latents by a factor (ComfyUI LatentUpscaleBy parity)."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "samples": ("LATENT",),
                "upscale_method": ("STRING", {"default": "nearest-exact"}),
                "scale_by": ("FLOAT", {"default": 1.5}),
            }
        }

    RETURN_TYPES = ("LATENT",)
    FUNCTION = "upscale"

    def upscale(self, samples: dict, upscale_method="nearest-exact",
                scale_by=1.5, context=None):
        from ..ops import upscale as up_ops

        z = samples["samples"]
        lh, lw = up_ops.scale_dims(z.shape[1], z.shape[2], scale_by)
        return LatentUpscale().upscale(
            samples, upscale_method, width=lw * 8, height=lh * 8
        )


@register_node
class LoadImage:
    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"image": ("STRING", {"default": ""})}}

    RETURN_TYPES = ("IMAGE", "MASK")
    FUNCTION = "load"
    NEVER_CACHE = True  # backing file can change between runs

    def load(self, image: str, context=None):
        from .io_dirs import resolve_input_path

        path = resolve_input_path(str(image), context)
        arr = img_utils.pil_to_array(__import__("PIL.Image", fromlist=["Image"]).open(path))
        rgb = arr[..., :3]
        # mask = 1 - alpha (the ComfyUI convention the bundled inpaint
        # workflow depends on: transparent hole -> 1 -> regenerate,
        # matching the noise_mask polarity); no alpha -> all zeros
        # (nothing to regenerate)
        mask = (
            1.0 - arr[..., 3]
            if arr.shape[-1] == 4
            else np.zeros(arr.shape[:2], np.float32)
        )
        return (jnp.asarray(rgb)[None], jnp.asarray(mask)[None])


@register_node
class SaveImage:
    """PNG per image as <prefix>_NNNNN.png, the counter reserved when the
    node runs; a served prompt is done when its files are written.

    In a served prompt the read-back, the encode and the write run on
    the server's saver thread while the executor thread walks the next
    prompt: the images leave the device once, there."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "images": ("IMAGE",),
                "filename_prefix": ("STRING", {"default": "output"}),
            }
        }

    RETURN_TYPES = ()
    FUNCTION = "save"
    OUTPUT_NODE = True

    def save(self, images, filename_prefix="output", context=None):
        from .io_dirs import get_output_dir, reserve_counter

        out_dir = get_output_dir(context)
        os.makedirs(out_dir, exist_ok=True)
        # resume numbering after existing files so runs never clobber
        # each other (ComfyUI counter-scan behavior); reserved, because
        # an earlier prompt's file may not be written yet
        start = reserve_counter(out_dir, filename_prefix, "png", len(images))
        saved = [f"{filename_prefix}_{start + i:05d}.png" for i in range(len(images))]
        save = partial(_save_pngs, images, [os.path.join(out_dir, n) for n in saved])
        # A served request hands read-back, encode and write to the
        # server's saver thread: this thread waits for nothing and goes
        # on to the next prompt, whose programs queue on the device
        # behind this one's; the prompt is done when the files are
        # written. Anywhere else: here.
        defer = getattr(context, "defer", None)
        if defer is None:
            save()
        else:
            defer(save)
        return ({"ui": {"images": saved}, "images": images},)


def _save_pngs(images, paths, overlapped=lambda: False, landed=lambda: None) -> None:
    """Read `images` back and write one PNG per image. The thread parks
    in the read-back until the device has finished everything the
    images depend on, then calls `landed()`; `overlapped()` says whether
    the executor has taken another prompt since the hand-off (what
    `png.encode` says of it). From the read-back's end on this is the
    `tail_s` of the job's record (telemetry/job_record.py)."""
    from ..telemetry import get_tracer

    tracer = get_tracer()
    with tracer.device_wait() as wait:
        arr = img_utils.ensure_numpy(images)
        wait.attrs["bytes"] = int(arr.nbytes)
    landed()
    for image, path in zip(arr, paths):
        with tracer.span("png.encode") as encode:
            png = img_utils.encode_png(image, compress_level=4)
            encode.attrs["bytes"] = len(png)
        with tracer.span("file.write", bytes=len(png)):
            with open(path, "wb") as fh:
                fh.write(png)
        # read once the file is there: taken meanwhile, it was hidden
        encode.attrs["overlapped"] = int(overlapped())


@register_node
class PreviewImage(SaveImage):
    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"images": ("IMAGE",)}}

    FUNCTION = "preview"
    OUTPUT_NODE = True

    def preview(self, images, context=None):
        # terminal sink; nothing persisted (worker-side pruned graphs end here)
        return ({"ui": {"images": []}, "images": images},)


@register_node
class UpscaleModelLoader:
    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"model_name": ("STRING", {"default": "4x-generic"})}}

    RETURN_TYPES = ("UPSCALE_MODEL",)
    FUNCTION = "load"

    def load(self, model_name: str, context=None):
        from ..models.upscaler import load_upscale_model

        cache_key = f"upscaler:{model_name}"
        cache = getattr(context, "pipelines", {}) if context is not None else {}
        if cache_key not in cache:
            cache[cache_key] = load_upscale_model(str(model_name))
        return (cache[cache_key],)


@register_node
class ImageUpscaleWithModel:
    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "upscale_model": ("UPSCALE_MODEL",),
                "image": ("IMAGE",),
            }
        }

    RETURN_TYPES = ("IMAGE",)
    FUNCTION = "upscale"

    def upscale(self, upscale_model, image, context=None):
        return (upscale_model.upscale(image),)


@register_node
class VAEEncodeTiled(VAEEncode):
    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "pixels": ("IMAGE",),
                "vae": ("VAE",),
                "tile_size": ("INT", {"default": 512}),
            }
        }

    FUNCTION = "encode_tiled"

    def encode_tiled(self, pixels, vae, tile_size=512, context=None):
        from ..ops.tiled_vae import encode_tiled

        pixel_tile = max(64, int(tile_size))
        z = encode_tiled(
            pl._Static(vae), vae.params["vae"], pixels,
            tile=pixel_tile, overlap=max(16, pixel_tile // 8),
        )
        return ({"samples": z},)


@register_node
class LatentFromBatch:
    """Slice a contiguous run out of a latent batch (ComfyUI
    LatentFromBatch parity); the noise_mask follows when it is
    per-sample."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "samples": ("LATENT",),
                "batch_index": ("INT", {"default": 0}),
                "length": ("INT", {"default": 1}),
            }
        }

    RETURN_TYPES = ("LATENT",)
    FUNCTION = "frombatch"

    def frombatch(self, samples: dict, batch_index=0, length=1, context=None):
        z = samples["samples"]
        b = z.shape[0]
        i0 = min(max(int(batch_index), 0), b - 1)
        i1 = min(i0 + max(int(length), 1), b)
        out = dict(samples)
        out["samples"] = z[i0:i1]
        mask = samples.get("noise_mask")
        if mask is not None and getattr(mask, "ndim", 0) >= 3 and (
            mask.shape[0] == b
        ):
            out["noise_mask"] = mask[i0:i1]
        return (out,)


@register_node
class LatentBatch:
    """Batch-concatenate two latents (ComfyUI LatentBatch parity): the
    second resizes to the first's spatial grid when they differ."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "samples1": ("LATENT",),
                "samples2": ("LATENT",),
            }
        }

    RETURN_TYPES = ("LATENT",)
    FUNCTION = "batch"

    def batch(self, samples1: dict, samples2: dict, context=None):
        from ..ops import upscale as up_ops

        z1, z2 = samples1["samples"], samples2["samples"]
        if z1.shape[1:3] != z2.shape[1:3]:
            z2 = up_ops.resize_image(z2, z1.shape[1], z1.shape[2], "bilinear")
        out = dict(samples1)
        out["samples"] = jnp.concatenate([z1, z2], axis=0)
        out.pop("noise_mask", None)  # per-sample masks no longer align
        return (out,)


def _gaussian_blur(image, radius: int, sigma: float):
    """Shared separable Gaussian kernel (ops/filters.gaussian_blur):
    ImageBlur / ImageSharpen here, the SAG degraded pass in
    ops/samplers."""
    from ..ops.filters import gaussian_blur

    return gaussian_blur(image, radius, sigma)


@register_node
class ImageBlur:
    """Gaussian blur (ComfyUI ImageBlur parity)."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "image": ("IMAGE",),
                "blur_radius": ("INT", {"default": 1}),
                "sigma": ("FLOAT", {"default": 1.0}),
            }
        }

    RETURN_TYPES = ("IMAGE",)
    FUNCTION = "blur"

    def blur(self, image, blur_radius=1, sigma=1.0, context=None):
        if int(blur_radius) <= 0:
            return (image,)
        return (_gaussian_blur(image, blur_radius, sigma),)


@register_node
class ImageSharpen:
    """Unsharp-mask sharpening (ComfyUI ImageSharpen parity):
    img + alpha * (img - blur)."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "image": ("IMAGE",),
                "sharpen_radius": ("INT", {"default": 1}),
                "sigma": ("FLOAT", {"default": 1.0}),
                "alpha": ("FLOAT", {"default": 1.0}),
            }
        }

    RETURN_TYPES = ("IMAGE",)
    FUNCTION = "sharpen"

    def sharpen(self, image, sharpen_radius=1, sigma=1.0, alpha=1.0,
                context=None):
        if int(sharpen_radius) <= 0:
            return (image,)
        blurred = _gaussian_blur(image, sharpen_radius, sigma)
        return (
            jnp.clip(image + float(alpha) * (image - blurred), 0.0, 1.0),
        )


@register_node
class LoraLoaderModelOnly:
    """LoRA merge into the diffusion weights only (ComfyUI
    LoraLoaderModelOnly parity) — for UNETLoader bundles that carry no
    text encoders. Text-encoder modules in the file are reported as
    unmatched, not fatal (partial-LoRA semantics)."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "model": ("MODEL",),
                "lora_name": ("STRING", {"default": ""}),
                "strength_model": ("FLOAT", {"default": 1.0}),
            }
        }

    RETURN_TYPES = ("MODEL",)
    FUNCTION = "load_lora_model_only"

    def load_lora_model_only(self, model: pl.PipelineBundle, lora_name,
                             strength_model=1.0, context=None):
        from ..models import get_config
        from ..models.lora import apply_lora, read_lora

        path = LoraLoader._resolve_lora_path(str(lora_name))
        lora_sd = read_lora(path)
        patched, unmatched = apply_lora(
            {"unet": model.params["unet"]},
            lora_sd,
            get_config(model.model_name),
            strength=float(strength_model),
        )
        if unmatched:
            log(f"LoRA {os.path.basename(path)}: {len(unmatched)} "
                f"unmatched module(s), e.g. {unmatched[:3]}")
        model_params = dict(model.params)
        model_params["unet"] = patched["unet"]
        return (dataclasses.replace(model, params=model_params),)


@register_node
class InpaintModelConditioning:
    """Conditioning assembly for inpaint-specialized checkpoints
    (ComfyUI InpaintModelConditioning parity; sd15-inpaint-class
    9-channel UNets): the masked-out pixels are neutralized and
    encoded as the concat channels (mask ++ masked-image latents,
    joined to the model input at every step), the original pixels
    encode as the starting latents, and the mask optionally rides as
    the latent noise_mask."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "positive": ("CONDITIONING",),
                "negative": ("CONDITIONING",),
                "vae": ("VAE",),
                "pixels": ("IMAGE",),
                "mask": ("MASK",),
            },
            "optional": {"noise_mask": ("BOOLEAN", {"default": True})},
        }

    RETURN_TYPES = ("CONDITIONING", "CONDITIONING", "LATENT")
    RETURN_NAMES = ("positive", "negative", "latent")
    FUNCTION = "encode"

    def encode(self, positive, negative, vae: pl.PipelineBundle, pixels,
               mask, noise_mask=True, context=None):
        from ..ops.conditioning import map_conditioning

        b, h, w, _ = pixels.shape
        # MASK contract: [H,W], [B,H,W] or [B,H,W,1] (same preamble as
        # _mask_to_latent)
        m = jnp.asarray(mask, jnp.float32)
        if m.ndim == 4:
            m = m[..., 0]
        if m.ndim == 2:
            m = m[None]
        if m.shape[1:] != (h, w):
            m = jax.image.resize(m, (m.shape[0], h, w), method="linear")
        m = jnp.clip(m, 0.0, 1.0)
        hard = (m > 0.5).astype(jnp.float32)
        # reference pixel neutralization: (p - 0.5) * keep + 0.5
        neutral = (pixels - 0.5) * (1.0 - hard[..., None]) + 0.5
        z_orig = _vae_pass(vae, pixels, "encode")
        z_masked = _vae_pass(vae, neutral, "encode")
        mask_lat = _mask_to_latent(m, z_orig.shape[1], z_orig.shape[2])
        concat = jnp.concatenate([mask_lat, z_masked], axis=-1)

        def patch(cond):
            cond.concat_latent = concat
            return cond

        latent = {"samples": z_orig, "width": int(w), "height": int(h)}
        if noise_mask:
            latent["noise_mask"] = mask_lat
        return (
            map_conditioning(positive, patch),
            map_conditioning(negative, patch),
            latent,
        )


@register_node
class VAEDecodeTiled(VAEDecode):
    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "samples": ("LATENT",),
                "vae": ("VAE",),
                "tile_size": ("INT", {"default": 512}),
            }
        }

    FUNCTION = "decode_tiled"

    def decode_tiled(self, samples, vae, tile_size=512, context=None):
        from ..ops.tiled_vae import decode_tiled

        latent_tile = max(16, int(tile_size) // vae.latent_scale)
        out = decode_tiled(
            pl._Static(vae), vae.params["vae"], samples["samples"],
            tile=latent_tile, overlap=max(4, latent_tile // 8),
        )
        return (out,)
