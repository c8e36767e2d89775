"""Standalone component loaders + model-sampling patch nodes.

The real Flux/SD3 distribution format ships the diffusion transformer,
text encoders, and VAE as separate files; published workflows load
them with UNETLoader / CLIPLoader / DualCLIPLoader / TripleCLIPLoader
and patch schedule shape with the ModelSampling* nodes. The reference
free-rides on ComfyUI for this whole surface (SURVEY §2: zero model
code of its own); here it is built on models/pipeline.load_unet /
load_clip and per-bundle schedule overrides (PipelineBundle
.flow_shift_override / .parameterization_override — a replaced bundle
recompiles the jitted samplers exactly once, the jit-friendly analog
of ComfyUI's model_sampling object patch).
"""

from __future__ import annotations

import dataclasses
import math
import os

import jax.numpy as jnp

from ..models import pipeline as pl
from .registry import register_node


def _stem(name: str) -> str:
    """Workflow values carry filenames ('clip_l.safetensors'); registry
    names are stems. A registry name is taken whole ('ouro-2.6b' ends in
    no extension). Underscores normalize to the registry's hyphens only
    when the literal name is unknown."""
    from ..models.registry import MODEL_REGISTRY

    if str(name) in MODEL_REGISTRY:
        return str(name)
    base = os.path.splitext(str(name))[0]
    if base in MODEL_REGISTRY:
        return base
    hyphenated = base.replace("_", "-")
    return hyphenated if hyphenated in MODEL_REGISTRY else base


@register_node
class UNETLoader:
    """Load a diffusion backbone only (ComfyUI UNETLoader parity).
    weight_dtype accepts the ComfyUI values; on TPU the compute dtype
    is the XLA program's concern, so anything but 'default' is a
    no-op recorded for workflow compatibility."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "unet_name": ("STRING", {"default": "tiny-unet"}),
                "weight_dtype": ("STRING", {"default": "default"}),
            }
        }

    RETURN_TYPES = ("MODEL",)
    FUNCTION = "load_unet"

    def load_unet(self, unet_name, weight_dtype="default", context=None):
        name = _stem(unet_name)
        cache_key = f"unet:{name}"
        cache = getattr(context, "pipelines", {}) if context is not None else {}
        if cache_key not in cache:
            cache[cache_key] = pl.load_unet(name)
        return (cache[cache_key],)


def _load_clip_cached(names: list[str], layout: str, context):
    cache_key = f"clip:{layout}:" + ",".join(names)
    cache = getattr(context, "pipelines", {}) if context is not None else {}
    if cache_key not in cache:
        cache[cache_key] = pl.load_clip(names, layout=layout)
    return cache[cache_key]


# ComfyUI type values → load_clip layout names
_CLIP_TYPE_MAP = {
    "stable_diffusion": "sd",
    "sdxl": "sdxl",
    "flux": "flux",
    "sd3": "sd3",
}


@register_node
class CLIPLoader:
    """Load a single text encoder (ComfyUI CLIPLoader parity)."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "clip_name": ("STRING", {"default": "clip-l"}),
                "type": ("STRING", {"default": "stable_diffusion"}),
            }
        }

    RETURN_TYPES = ("CLIP",)
    FUNCTION = "load_clip"

    def load_clip(self, clip_name, type="stable_diffusion", context=None):
        if str(type) != "stable_diffusion":
            raise ValueError(
                "CLIPLoader loads one encoder; type must be "
                "'stable_diffusion' (use DualCLIPLoader/TripleCLIPLoader "
                "for sdxl/flux/sd3 layouts)"
            )
        return (_load_clip_cached([_stem(clip_name)], "sd", context),)


@register_node
class DualCLIPLoader:
    """Load two text encoders (ComfyUI DualCLIPLoader parity):
    type sdxl (CLIP-L + CLIP-G), flux (CLIP + T5, either order), or
    sd3 (CLIP-L + CLIP-G, T5-less low-memory mode)."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "clip_name1": ("STRING", {"default": "clip-l"}),
                "clip_name2": ("STRING", {"default": "clip-g"}),
                "type": ("STRING", {"default": "sdxl"}),
            }
        }

    RETURN_TYPES = ("CLIP",)
    FUNCTION = "load_clip"

    def load_clip(self, clip_name1, clip_name2, type="sdxl", context=None):
        layout = _CLIP_TYPE_MAP.get(str(type))
        if layout is None or layout == "sd":
            raise ValueError(
                f"DualCLIPLoader type must be sdxl, flux, or sd3; "
                f"got {type!r}"
            )
        names = [_stem(clip_name1), _stem(clip_name2)]
        return (_load_clip_cached(names, layout, context),)


@register_node
class TripleCLIPLoader:
    """Load the full SD3 encoder set (ComfyUI TripleCLIPLoader parity:
    CLIP-L + CLIP-G + T5; the T5 is sniffed by family, so argument
    order beyond the two CLIPs doesn't matter)."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "clip_name1": ("STRING", {"default": "clip-l-sd3"}),
                "clip_name2": ("STRING", {"default": "clip-g"}),
                "clip_name3": ("STRING", {"default": "t5-xxl-sd3"}),
            }
        }

    RETURN_TYPES = ("CLIP",)
    FUNCTION = "load_clip"

    def load_clip(self, clip_name1, clip_name2, clip_name3, context=None):
        names = [_stem(clip_name1), _stem(clip_name2), _stem(clip_name3)]
        return (_load_clip_cached(names, "sd3", context),)


@register_node
class EmptySD3LatentImage:
    """16-channel empty latents (ComfyUI EmptySD3LatentImage parity —
    the SD3/Flux workflow starting point). Carries the same PLACEHOLDER
    marker EmptyLatentImage uses, so KSampler still rebuilds against
    the actual bundle's latent layout if it differs."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "width": ("INT", {"default": 1024}),
                "height": ("INT", {"default": 1024}),
                "batch_size": ("INT", {"default": 1}),
            }
        }

    RETURN_TYPES = ("LATENT",)
    FUNCTION = "generate"

    def generate(self, width=1024, height=1024, batch_size=1, context=None):
        return (
            {
                "samples": jnp.zeros(
                    (int(batch_size), int(height) // 8, int(width) // 8, 16)
                ),
                "width": int(width),
                "height": int(height),
                "empty": True,
            },
        )


def _patch_freeu(model, b1, b2, s1, s2, v2: bool):
    from ..models.registry import model_family
    from ..models.unet import UNet

    if model_family(model.model_name) != "unet":
        raise ValueError(
            "FreeU patches SD-class UNets (skip-connection joins); "
            f"{model.model_name!r} is not one"
        )
    # patch the LIVE module's config (keeps any earlier config-level
    # patches), not the registry's pristine copy
    cfg = dataclasses.replace(
        model.unet.config,
        freeu=(float(b1), float(b2), float(s1), float(s2), bool(v2)),
    )
    # same weights, new module: the patch adds no parameters, so the
    # existing param tree applies unchanged and the jitted samplers
    # recompile exactly once for the patched bundle
    return dataclasses.replace(model, unet=UNet(cfg))


@register_node
class FreeU:
    """FreeU backbone/skip re-weighting (ComfyUI FreeU parity): at the
    model_channels*4 / *2 up-path joins, the first half of the
    backbone channels scales by b1/b2 and the skip's low-frequency
    Fourier box scales by s1/s2."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "model": ("MODEL",),
                "b1": ("FLOAT", {"default": 1.1}),
                "b2": ("FLOAT", {"default": 1.2}),
                "s1": ("FLOAT", {"default": 0.9}),
                "s2": ("FLOAT", {"default": 0.2}),
            }
        }

    RETURN_TYPES = ("MODEL",)
    FUNCTION = "patch"

    def patch(self, model, b1=1.1, b2=1.2, s1=0.9, s2=0.2, context=None):
        return (_patch_freeu(model, b1, b2, s1, s2, v2=False),)


@register_node
class FreeU_V2:
    """FreeU v2 (ComfyUI FreeU_V2 parity): the backbone scale adapts
    per pixel via the normalized hidden-mean map instead of a
    constant."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "model": ("MODEL",),
                "b1": ("FLOAT", {"default": 1.3}),
                "b2": ("FLOAT", {"default": 1.4}),
                "s1": ("FLOAT", {"default": 0.9}),
                "s2": ("FLOAT", {"default": 0.2}),
            }
        }

    RETURN_TYPES = ("MODEL",)
    FUNCTION = "patch"

    def patch(self, model, b1=1.3, b2=1.4, s1=0.9, s2=0.2, context=None):
        return (_patch_freeu(model, b1, b2, s1, s2, v2=True),)


@register_node
class PerturbedAttentionGuidance:
    """PAG model patch (ComfyUI PerturbedAttentionGuidance parity,
    Ahn et al. 2024): each step gains scale * (cond - cond with the
    middle-block self-attention replaced by identity). UNet family
    only — DiT-class models use SkipLayerGuidance instead."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "model": ("MODEL",),
                "scale": ("FLOAT", {"default": 3.0}),
            }
        }

    RETURN_TYPES = ("MODEL",)
    FUNCTION = "patch"

    def patch(self, model, scale=3.0, context=None):
        from ..models.registry import model_family

        family = model_family(model.model_name)
        if family != "unet":
            raise ValueError(
                f"PerturbedAttentionGuidance patches UNet self-attention; "
                f"{model.model_name!r} is {family}-family (use "
                "SkipLayerGuidanceSD3 for DiT-class models)"
            )
        pl.reject_existing_guidance_patches(
            model, "PerturbedAttentionGuidance"
        )
        return (
            dataclasses.replace(model, pag=pl.PAGSpec(scale=float(scale))),
        )


@register_node
class SelfAttentionGuidance:
    """SAG model patch (ComfyUI SelfAttentionGuidance parity, Hong et
    al. 2023): gaussian-blur the uncond x0 estimate where the
    middle-block self-attention concentrates, re-noise, and guide away
    from the degraded prediction (ops/samplers.sag_cfg_model). UNet
    family only."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "model": ("MODEL",),
                "scale": ("FLOAT", {"default": 0.5}),
                "blur_sigma": ("FLOAT", {"default": 2.0}),
            }
        }

    RETURN_TYPES = ("MODEL",)
    FUNCTION = "patch"

    def patch(self, model, scale=0.5, blur_sigma=2.0, context=None):
        from ..models.registry import model_family

        family = model_family(model.model_name)
        if family != "unet":
            raise ValueError(
                f"SelfAttentionGuidance captures UNet middle-block "
                f"attention; {model.model_name!r} is {family}-family"
            )
        pl.reject_existing_guidance_patches(model, "SelfAttentionGuidance")
        return (
            dataclasses.replace(
                model,
                sag=pl.SAGSpec(
                    scale=float(scale), blur_sigma=float(blur_sigma)
                ),
            ),
        )


@register_node
class RescaleCFG:
    """Std-rescaled guidance (ComfyUI RescaleCFG parity): the guided
    x0 prediction rescales to the cond-only prediction's per-sample
    std, lerped by `multiplier` — the standard companion to
    v-prediction/zero-terminal-SNR finetunes. Implemented as a bundle
    patch composed in pipeline.guided_model; combining with
    SkipLayerGuidanceSD3 is rejected (the two patches both own the
    guidance composition)."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "model": ("MODEL",),
                "multiplier": ("FLOAT", {"default": 0.7}),
            }
        }

    RETURN_TYPES = ("MODEL",)
    FUNCTION = "patch"

    def patch(self, model, multiplier=0.7, context=None):
        pl.reject_existing_guidance_patches(model, "RescaleCFG")
        return (
            dataclasses.replace(model, cfg_rescale=float(multiplier)),
        )


@register_node
class ModelSamplingDiscrete:
    """Override the VP parameterization (ComfyUI ModelSamplingDiscrete
    parity): eps or v_prediction. zsnr rescaling is not implemented —
    it errors rather than silently sampling the wrong schedule."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "model": ("MODEL",),
                "sampling": ("STRING", {"default": "eps"}),
                "zsnr": ("BOOLEAN", {"default": False}),
            }
        }

    RETURN_TYPES = ("MODEL",)
    FUNCTION = "patch"

    def patch(self, model, sampling="eps", zsnr=False, context=None):
        mapping = {"eps": "eps", "v_prediction": "v"}
        if str(sampling) not in mapping:
            raise ValueError(
                f"sampling must be one of {sorted(mapping)}; got {sampling!r}"
            )
        if zsnr:
            raise ValueError(
                "zsnr rescaling is not implemented in this framework"
            )
        return (
            dataclasses.replace(
                model, parameterization_override=mapping[str(sampling)]
            ),
        )


def _require_flow(model, node: str):
    if pl.model_schedule_info(model)[0] != "flow":
        raise ValueError(
            f"{node} patches flow-matching models (Flux/SD3 class); "
            f"{model.model_name!r} is not one"
        )


@register_node
class ModelSamplingSD3:
    """Set the rectified-flow shift (ComfyUI ModelSamplingSD3 parity;
    also the AuraFlow-style plain-shift knob)."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "model": ("MODEL",),
                "shift": ("FLOAT", {"default": 3.0}),
            }
        }

    RETURN_TYPES = ("MODEL",)
    FUNCTION = "patch"

    def patch(self, model, shift=3.0, context=None):
        _require_flow(model, "ModelSamplingSD3")
        return (
            dataclasses.replace(model, flow_shift_override=float(shift)),
        )


@register_node
class ModelSamplingFlux:
    """Resolution-dependent flow shift (ComfyUI ModelSamplingFlux
    parity): mu interpolates linearly in image-token count between
    base_shift at 256 tokens and max_shift at 4096, and the effective
    multiplicative shift is exp(mu) — Flux's time_shift(mu, t) equals
    the shifted-sigma form sigma' = s*t/(1+(s-1)t) with s = e^mu."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "model": ("MODEL",),
                "max_shift": ("FLOAT", {"default": 1.15}),
                "base_shift": ("FLOAT", {"default": 0.5}),
                "width": ("INT", {"default": 1024}),
                "height": ("INT", {"default": 1024}),
            }
        }

    RETURN_TYPES = ("MODEL",)
    FUNCTION = "patch"

    def patch(self, model, max_shift=1.15, base_shift=0.5, width=1024,
              height=1024, context=None):
        _require_flow(model, "ModelSamplingFlux")
        # image tokens at the 2x2-patch latent grid (pixels/16 per side)
        seq = (int(width) // 16) * (int(height) // 16)
        mu = float(base_shift) + (float(max_shift) - float(base_shift)) * (
            (seq - 256) / (4096 - 256)
        )
        return (
            dataclasses.replace(model, flow_shift_override=math.exp(mu)),
        )


@register_node
class CLIPVisionLoader:
    """Load a standalone CLIP-vision tower (ComfyUI CLIPVisionLoader
    parity): a registry name (clip-vision-h, tiny-clip-vision) whose
    real weights resolve through CDT_CHECKPOINT_DIR, exactly like the
    WAN i2v bundled path (models/clip_vision.load_clip_vision)."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "clip_name": ("STRING", {"default": "clip-vision-h"}),
            }
        }

    RETURN_TYPES = ("CLIP_VISION",)
    FUNCTION = "load_clip"

    def load_clip(self, clip_name: str, context=None):
        from ..models.clip_vision import load_clip_vision

        name = _stem(clip_name)
        cache_key = f"clip_vision:{name}"
        cache = getattr(context, "pipelines", {}) if context is not None else {}
        if cache_key not in cache:
            cache[cache_key] = load_clip_vision(name)
        return (cache[cache_key],)


@dataclasses.dataclass(frozen=True)
class ClipVisionOutput:
    """A CLIP_VISION_OUTPUT value: hidden-state tokens [B, T, width],
    class token first. Deliberately NO `pooled`/`image_embeds`
    accessor: the default towers run penultimate_hidden=True (no
    final block, post-LN, or projection — clip_vision.py), so a raw
    class token would be a plausible-but-wrong stand-in for the CLIP
    pooled embedding. Add the projected path before exposing one."""

    tokens: object


@register_node
class CLIPVisionEncode:
    """Encode an image batch through a CLIP-vision tower (ComfyUI
    CLIPVisionEncode parity). The tower preprocesses internally
    (short-side scale + center crop + CLIP normalization — see
    ClipVisionEncoder.__call__), which matches the 'center' crop
    convention; crop='none' is rejected rather than silently behaving
    like center."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "clip_vision": ("CLIP_VISION",),
                "image": ("IMAGE",),
                "crop": ("STRING", {"default": "center"}),
            }
        }

    RETURN_TYPES = ("CLIP_VISION_OUTPUT",)
    FUNCTION = "encode"

    def encode(self, clip_vision, image, crop="center", context=None):
        if str(crop) != "center":
            raise ValueError(
                "only crop='center' is implemented (the tower's "
                "preprocessing is short-side scale + center crop)"
            )
        return (ClipVisionOutput(tokens=clip_vision.encode(image)),)


@register_node(name="unCLIPConditioning")
class UnCLIPConditioning:
    """Attach CLIP-vision image embeds to conditioning (ComfyUI
    unCLIPConditioning shape). NOTE: no registered backbone has an
    unCLIP adm head yet, so sampling with this conditioning raises at
    trace time (ops/samplers._reject_unsupported_cond) instead of
    silently dropping the image condition — the node exists so
    unCLIP workflows load and fail with a clear message, and so the
    conditioning plumbing is ready when an unCLIP backbone lands."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "conditioning": ("CONDITIONING",),
                "clip_vision_output": ("CLIP_VISION_OUTPUT",),
                "strength": ("FLOAT", {"default": 1.0}),
                "noise_augmentation": ("FLOAT", {"default": 0.0}),
            }
        }

    RETURN_TYPES = ("CONDITIONING",)
    FUNCTION = "apply_adm"

    def apply_adm(self, conditioning, clip_vision_output, strength=1.0,
                  noise_augmentation=0.0, context=None):
        from ..ops.conditioning import map_conditioning

        def patch(cond):
            cond.unclip_embeds = clip_vision_output.tokens
            cond.unclip_strength = float(strength)
            cond.unclip_noise_aug = float(noise_augmentation)
            return cond

        return (map_conditioning(conditioning, patch),)


def _merge_trees(t1, t2, ratio: float, what: str):
    """ratio * t1 + (1 - ratio) * t2 over matching param trees (the
    ComfyUI merge convention: ratio 1.0 = pure model1). Mismatched
    architectures fail on treedef/shape, loudly."""
    import jax

    d1 = jax.tree_util.tree_structure(t1)
    d2 = jax.tree_util.tree_structure(t2)
    if d1 != d2:
        raise ValueError(
            f"{what}: param trees differ — merging needs two checkpoints "
            "of the same architecture"
        )
    r = float(ratio)

    def lerp(a, b):
        if a.shape != b.shape:
            raise ValueError(
                f"{what}: shape mismatch {a.shape} vs {b.shape}"
            )
        if jnp.issubdtype(a.dtype, jnp.floating):
            return (a.astype(jnp.float32) * r
                    + b.astype(jnp.float32) * (1.0 - r)).astype(a.dtype)
        return a

    return jax.tree_util.tree_map(lerp, t1, t2)


@register_node
class ModelMergeSimple:
    """Weighted average of two diffusion backbones (ComfyUI
    ModelMergeSimple parity): ratio weights model1. The merged bundle
    keeps model1's config/patches — only the unet params blend."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "model1": ("MODEL",),
                "model2": ("MODEL",),
                "ratio": ("FLOAT", {"default": 1.0}),
            }
        }

    RETURN_TYPES = ("MODEL",)
    FUNCTION = "merge"

    def merge(self, model1, model2, ratio=1.0, context=None):
        merged = _merge_trees(
            model1.params["unet"], model2.params["unet"], ratio,
            "ModelMergeSimple",
        )
        params = dict(model1.params)
        params["unet"] = merged
        return (dataclasses.replace(model1, params=params),)


@register_node
class CLIPMergeSimple:
    """Weighted average of two text-encoder stacks (ComfyUI
    CLIPMergeSimple parity): every te/te2/te3 part present in clip1
    blends with clip2's matching part."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "clip1": ("CLIP",),
                "clip2": ("CLIP",),
                "ratio": ("FLOAT", {"default": 1.0}),
            }
        }

    RETURN_TYPES = ("CLIP",)
    FUNCTION = "merge"

    def merge(self, clip1, clip2, ratio=1.0, context=None):
        params = dict(clip1.params)
        for part in ("te", "te2", "te3"):
            if part in clip1.params:
                if part not in clip2.params:
                    raise ValueError(
                        f"CLIPMergeSimple: clip2 has no {part!r} part"
                    )
                params[part] = _merge_trees(
                    clip1.params[part], clip2.params[part], ratio,
                    f"CLIPMergeSimple[{part}]",
                )
        return (dataclasses.replace(clip1, params=params),)
