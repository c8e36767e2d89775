"""Latent-diffusion UNet (SD1.5 / SDXL class), flax.linen, NHWC.

Architecture-faithful to the SD UNet family the reference drives via
ComfyUI's `common_ksampler` (reference upscale/tile_ops.py:239-287):
timestep + optional pooled-vector conditioning, down/mid/up ResBlock
stacks with spatial transformers cross-attending to text context, skip
connections across the U. Config-driven so SD1.5 (320ch, 768-d ctx),
SDXL (2048-d ctx, deep mid transformers) and tiny test variants are
the same code.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from .layers import (
    Downsample,
    GroupNorm32,
    ResBlock,
    SpatialTransformer,
    Upsample,
    timestep_embedding,
)


@dataclasses.dataclass(frozen=True)
class UNetConfig:
    in_channels: int = 4
    out_channels: int = 4
    model_channels: int = 320
    channel_mult: Sequence[int] = (1, 2, 4, 4)
    num_res_blocks: int = 2
    # transformer depth per resolution level (0 = no attention there)
    transformer_depth: Sequence[int] = (1, 1, 1, 0)
    context_dim: int = 768
    num_heads: int = 8
    # fixed per-head width (SDXL's num_head_channels=64 convention):
    # when set, each level uses out_ch // head_dim heads, overriding
    # num_heads — required for real SDXL attention semantics
    head_dim: Optional[int] = None
    # SDXL-style pooled text + size conditioning vector (0 = disabled)
    adm_in_channels: int = 0
    # what the network predicts: "eps" (noise; SD1.x/SDXL base) or "v"
    # (velocity; SD2.x-768 and v-pred finetunes). The pipeline converts
    # v outputs to the sampler's eps contract exactly.
    parameterization: str = "eps"
    dtype: str = "bfloat16"
    # rematerialise attention blocks: trades recompute for HBM, the
    # standard lever for big latents on 16GB chips
    remat: bool = False
    # FreeU patch (the FreeU / FreeU_V2 nodes): (b1, b2, s1, s2, v2)
    # — backbone-half scaling + Fourier low-pass skip scaling at the
    # model_channels*4 / *2 up-path joins. None = unpatched. Carried
    # on the config so the patched module recompiles exactly once and
    # adds zero cost when absent.
    freeu: Optional[tuple] = None

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)


def _fourier_lowpass_scale(x: jax.Array, threshold: int, scale) -> jax.Array:
    """Scale the centered low-frequency box of a [B, H, W, C] plane
    (the reference stack's Fourier_filter: fft2 → shift → scale the
    (2*threshold)^2 center → inverse). Computed in float32 — FFT of a
    bf16 plane would quantize the whole spectrum."""
    xf = jnp.fft.fftn(x.astype(jnp.float32), axes=(1, 2))
    xf = jnp.fft.fftshift(xf, axes=(1, 2))
    b, hh, ww, c = x.shape
    crow, ccol = hh // 2, ww // 2
    mask = jnp.ones((1, hh, ww, 1), jnp.float32)
    y0, y1 = max(0, crow - threshold), min(hh, crow + threshold)
    x0, x1 = max(0, ccol - threshold), min(ww, ccol + threshold)
    mask = mask.at[:, y0:y1, x0:x1, :].set(scale)
    xf = xf * mask
    xf = jnp.fft.ifftshift(xf, axes=(1, 2))
    return jnp.fft.ifftn(xf, axes=(1, 2)).real.astype(x.dtype)


def _apply_freeu(cfg, ch: int, h: jax.Array, skip: jax.Array):
    """FreeU at one up-path join: backbone half-channel scaling (b) +
    Fourier low-pass scaling of the skip (s), keyed on the backbone
    width exactly like the reference patch (model_channels*4 → b1/s1,
    model_channels*2 → b2/s2). v2 scales adaptively by the normalized
    per-pixel hidden mean instead of a constant."""
    b1, b2, s1, s2, v2 = cfg.freeu
    scale_map = {ch * 4: (b1, s1), ch * 2: (b2, s2)}
    pair = scale_map.get(h.shape[-1])
    if pair is None:
        return h, skip
    b, s = pair
    half = h.shape[-1] // 2
    if v2:
        hidden_mean = jnp.mean(h.astype(jnp.float32), axis=-1, keepdims=True)
        hmax = jnp.max(hidden_mean, axis=(1, 2), keepdims=True)
        hmin = jnp.min(hidden_mean, axis=(1, 2), keepdims=True)
        hidden_mean = (hidden_mean - hmin) / jnp.maximum(hmax - hmin, 1e-8)
        factor = ((b - 1.0) * hidden_mean + 1.0).astype(h.dtype)
    else:
        factor = jnp.asarray(b, h.dtype)
    h = jnp.concatenate([h[..., :half] * factor, h[..., half:]], axis=-1)
    skip = _fourier_lowpass_scale(skip, 1, s)
    return h, skip


class UNet(nn.Module):
    config: UNetConfig

    @nn.compact
    def __call__(
        self,
        x: jax.Array,            # [B, H, W, C_in] noisy latents
        timesteps: jax.Array,    # [B]
        context: jax.Array,      # [B, T, context_dim] text tokens
        y: Optional[jax.Array] = None,  # [B, adm_in_channels] pooled cond
        control: Optional[jax.Array] = None,  # [B, H, W, model_channels]
        pag: bool = False,  # identity self-attention in the middle
        # block (the PAG perturbed pass; ComfyUI's simple-PAG patches
        # exactly the middle-block attn1)
        sag_capture: bool = False,  # sow the middle-block attn1
        # softmax probs (SAG capture pass); apply with
        # mutable=["intermediates"] to harvest them
    ) -> jax.Array:
        cfg = self.config
        dt = cfg.compute_dtype
        ch = cfg.model_channels
        SpatialT = (
            nn.remat(SpatialTransformer, static_argnums=())
            if cfg.remat
            else SpatialTransformer
        )

        def head_split(width: int) -> tuple[int, int]:
            if cfg.head_dim:
                return width // cfg.head_dim, cfg.head_dim
            return cfg.num_heads, width // cfg.num_heads

        emb = nn.Dense(ch * 4, dtype=dt, name="time_embed_0")(
            timestep_embedding(timesteps, ch).astype(dt)
        )
        emb = nn.Dense(ch * 4, dtype=dt, name="time_embed_2")(nn.silu(emb))
        if cfg.adm_in_channels:
            if y is None:
                y = jnp.zeros((x.shape[0], cfg.adm_in_channels), dt)
            label = nn.Dense(ch * 4, dtype=dt, name="label_embed_0")(y.astype(dt))
            label = nn.Dense(ch * 4, dtype=dt, name="label_embed_2")(nn.silu(label))
            emb = emb + label

        context = context.astype(dt)
        x = x.astype(dt)

        h = nn.Conv(ch, (3, 3), dtype=dt, name="input_conv")(x)
        if control is not None:
            # ControlNet residual injection (hint encoder output at
            # latent resolution, zero-init ⇒ identity when untrained)
            h = h + control.astype(dt)
        skips = [h]

        # --- down path ---
        # Block-level scopes (down_N / mid / up_N, attn inside) land in
        # every operation's metadata, above the module names flax adds,
        # so a device trace can be grouped by block.
        for level, mult in enumerate(cfg.channel_mult):
            out_ch = ch * mult
            with jax.named_scope(f"down_{level}"):
                for i in range(cfg.num_res_blocks):
                    h = ResBlock(out_ch, dt, name=f"down_{level}_res_{i}")(h, emb)
                    if cfg.transformer_depth[level] > 0:
                        heads, hdim = head_split(out_ch)
                        with jax.named_scope("attn"):
                            h = SpatialT(
                                heads,
                                hdim,
                                cfg.transformer_depth[level],
                                dt,
                                name=f"down_{level}_attn_{i}",
                            )(h, context)
                    skips.append(h)
                if level != len(cfg.channel_mult) - 1:
                    h = Downsample(dt, name=f"down_{level}_ds")(h)
                    skips.append(h)

        # --- middle ---
        mid_ch = ch * cfg.channel_mult[-1]
        mid_depth = max(cfg.transformer_depth[-1], 1)
        mid_heads, mid_hdim = head_split(mid_ch)
        # capture bypasses remat for the mid block only: sown
        # intermediates don't survive nn.remat, and the mid block's
        # activations are 1/64 of the latent tokens anyway
        MidT = SpatialTransformer if sag_capture else SpatialT
        with jax.named_scope("mid"):
            h = ResBlock(mid_ch, dt, name="mid_res_0")(h, emb)
            with jax.named_scope("attn"):
                h = MidT(
                    mid_heads, mid_hdim, mid_depth, dt, pag=pag,
                    sow_attn=sag_capture, name="mid_attn",
                )(h, context)
            h = ResBlock(mid_ch, dt, name="mid_res_1")(h, emb)

        # --- up path ---
        for level, mult in reversed(list(enumerate(cfg.channel_mult))):
            out_ch = ch * mult
            with jax.named_scope(f"up_{level}"):
                for i in range(cfg.num_res_blocks + 1):
                    skip = skips.pop()
                    if cfg.freeu is not None:
                        h, skip = _apply_freeu(cfg, ch, h, skip)
                    h = jnp.concatenate([h, skip], axis=-1)
                    h = ResBlock(out_ch, dt, name=f"up_{level}_res_{i}")(h, emb)
                    if cfg.transformer_depth[level] > 0:
                        heads, hdim = head_split(out_ch)
                        with jax.named_scope("attn"):
                            h = SpatialT(
                                heads,
                                hdim,
                                cfg.transformer_depth[level],
                                dt,
                                name=f"up_{level}_attn_{i}",
                            )(h, context)
                if level != 0:
                    # land exactly on the next skip's spatial dims (small /
                    # odd latents don't round-trip through stride-2 convs)
                    target = skips[-1].shape[1:3]
                    h = Upsample(dt, name=f"up_{level}_us")(h, target)

        h = GroupNorm32(name="out_norm")(h)
        h = nn.silu(h)
        h = nn.Conv(
            cfg.out_channels,
            (3, 3),
            dtype=jnp.float32,
            kernel_init=nn.initializers.zeros,
            name="out_conv",
        )(h.astype(jnp.float32))
        return h
