"""KL autoencoder (VAE) for latent diffusion — flax.linen, NHWC.

The encode/decode pair the reference reaches through ComfyUI's
VAEEncode/VAEDecode nodes (reference upscale/tile_ops.py:168). 8x
spatial compression, 4-channel latents, GroupNorm/SiLU ResBlocks with
a mid self-attention, `scaling_factor` applied at the latent boundary
so samplers see unit-variance latents.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from .layers import GroupNorm32
from ..ops.attention import dot_product_attention


@dataclasses.dataclass(frozen=True)
class VAEConfig:
    in_channels: int = 3
    latent_channels: int = 4
    base_channels: int = 128
    channel_mult: Sequence[int] = (1, 2, 4, 4)
    num_res_blocks: int = 2
    scaling_factor: float = 0.18215
    # Flux-class AE boundary: z = (mean - shift) * scale; the published
    # flux autoencoder also drops the SD 1x1 quant/post_quant convs
    shift_factor: float = 0.0
    use_quant_conv: bool = True
    dtype: str = "bfloat16"

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def downscale(self) -> int:
        return 2 ** (len(self.channel_mult) - 1)


class _VAEResBlock(nn.Module):
    out_channels: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        h = GroupNorm32(epsilon=1e-6, name="norm1")(x)
        h = nn.silu(h)
        h = nn.Conv(self.out_channels, (3, 3), dtype=self.dtype, name="conv1")(h)
        h = GroupNorm32(epsilon=1e-6, name="norm2")(h)
        h = nn.silu(h)
        h = nn.Conv(self.out_channels, (3, 3), dtype=self.dtype, name="conv2")(h)
        if x.shape[-1] != self.out_channels:
            x = nn.Conv(self.out_channels, (1, 1), dtype=self.dtype, name="skip")(x)
        return x + h


class _MidAttention(nn.Module):
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        b, hh, ww, c = x.shape
        h = GroupNorm32(epsilon=1e-6, name="norm")(x)
        tokens = h.reshape(b, hh * ww, c)
        q = nn.Dense(c, dtype=self.dtype, name="q")(tokens)
        k = nn.Dense(c, dtype=self.dtype, name="k")(tokens)
        v = nn.Dense(c, dtype=self.dtype, name="v")(tokens)
        out = dot_product_attention(
            q[:, :, None, :], k[:, :, None, :], v[:, :, None, :]
        )[:, :, 0, :]
        out = nn.Dense(c, dtype=self.dtype, name="proj")(out)
        return x + out.reshape(b, hh, ww, c)


class Encoder(nn.Module):
    config: VAEConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        dt = cfg.compute_dtype
        x = x.astype(dt)
        h = nn.Conv(cfg.base_channels, (3, 3), dtype=dt, name="conv_in")(x)
        for level, mult in enumerate(cfg.channel_mult):
            out_ch = cfg.base_channels * mult
            for i in range(cfg.num_res_blocks):
                h = _VAEResBlock(out_ch, dt, name=f"down_{level}_res_{i}")(h)
            if level != len(cfg.channel_mult) - 1:
                h = nn.Conv(
                    out_ch, (3, 3), strides=(2, 2), dtype=dt, name=f"down_{level}_ds"
                )(h)
        h = _VAEResBlock(h.shape[-1], dt, name="mid_res_0")(h)
        h = _MidAttention(dt, name="mid_attn")(h)
        h = _VAEResBlock(h.shape[-1], dt, name="mid_res_1")(h)
        h = GroupNorm32(epsilon=1e-6, name="norm_out")(h)
        h = nn.silu(h)
        # mean + logvar
        return nn.Conv(
            2 * cfg.latent_channels, (3, 3), dtype=jnp.float32, name="conv_out"
        )(h.astype(jnp.float32))


class Decoder(nn.Module):
    config: VAEConfig

    @nn.compact
    def __call__(self, z: jax.Array) -> jax.Array:
        cfg = self.config
        dt = cfg.compute_dtype
        z = z.astype(dt)
        ch = cfg.base_channels * cfg.channel_mult[-1]
        h = nn.Conv(ch, (3, 3), dtype=dt, name="conv_in")(z)
        h = _VAEResBlock(ch, dt, name="mid_res_0")(h)
        h = _MidAttention(dt, name="mid_attn")(h)
        h = _VAEResBlock(ch, dt, name="mid_res_1")(h)
        for level, mult in reversed(list(enumerate(cfg.channel_mult))):
            out_ch = cfg.base_channels * mult
            for i in range(cfg.num_res_blocks + 1):
                h = _VAEResBlock(out_ch, dt, name=f"up_{level}_res_{i}")(h)
            if level != 0:
                b, hh, ww, c = h.shape
                h = jax.image.resize(h, (b, hh * 2, ww * 2, c), method="nearest")
                h = nn.Conv(c, (3, 3), dtype=dt, name=f"up_{level}_us")(h)
        h = GroupNorm32(epsilon=1e-6, name="norm_out")(h)
        h = nn.silu(h)
        return nn.Conv(cfg.in_channels, (3, 3), dtype=jnp.float32, name="conv_out")(
            h.astype(jnp.float32)
        )


class VAE(nn.Module):
    """Encode/decode with method switching:
    `apply(params, x, method="encode")` → latents (mean, scaled);
    `apply(params, z, method="decode")` → images in [0, 1]."""

    config: VAEConfig

    def setup(self):
        self.encoder = Encoder(self.config)
        self.decoder = Decoder(self.config)
        # 1x1 moment/latent projections from the SD AutoencoderKL
        # (quant_conv / post_quant_conv) so real checkpoints map 1:1;
        # Flux-class AEs ship without them
        if self.config.use_quant_conv:
            self.quant_conv = nn.Conv(
                2 * self.config.latent_channels, (1, 1), dtype=jnp.float32,
                name="quant_conv",
            )
            self.post_quant_conv = nn.Conv(
                self.config.latent_channels, (1, 1), dtype=jnp.float32,
                name="post_quant_conv",
            )

    def encode(self, x: jax.Array, rng: jax.Array | None = None) -> jax.Array:
        """[B,H,W,3] in [0,1] → [B,H/8,W/8,C] scaled latents (mean; pass
        rng to sample from the posterior instead)."""
        with jax.named_scope("vae_encode"):
            moments = self.encoder(x * 2.0 - 1.0)
            if self.config.use_quant_conv:
                moments = self.quant_conv(moments)
            mean, logvar = jnp.split(moments, 2, axis=-1)
            if rng is not None:
                std = jnp.exp(0.5 * jnp.clip(logvar, -30.0, 20.0))
                mean = mean + std * jax.random.normal(rng, mean.shape)
            return (mean - self.config.shift_factor) * self.config.scaling_factor

    def decode(self, z: jax.Array) -> jax.Array:
        """[B,h,w,C] scaled latents → [B,H,W,3] images in [0,1]."""
        with jax.named_scope("vae_decode"):
            z = z / self.config.scaling_factor + self.config.shift_factor
            if self.config.use_quant_conv:
                z = self.post_quant_conv(z)
            x = self.decoder(z)
            return jnp.clip((x + 1.0) / 2.0, 0.0, 1.0)

    def __call__(self, x: jax.Array) -> jax.Array:
        return self.decode(self.encode(x))
