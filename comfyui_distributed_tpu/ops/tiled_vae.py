"""Tiled VAE encode/decode for images larger than VMEM/HBM comfort.

The reference exposes a tiled-VAE toggle on USDU (ComfyUI's
VAEEncodeTiled/VAEDecodeTiled); this is the JAX equivalent: the
latent/pixel plane is processed in overlapping tiles through the same
VAE params and feather-blended with the existing order-independent
blend, so arbitrarily large images decode in bounded memory.

Approximation note (inherent to all tiled VAEs): GroupNorm statistics
are computed per tile instead of globally, so results deviate from the
full pass near strong statistics shifts; overlap feathering hides the
seams. Use the full path when memory allows.
"""

from __future__ import annotations

from functools import partial

import jax

from . import tiles as tile_ops


@partial(jax.jit, static_argnames=("module", "method"))
def vae_apply(module, params, x: jax.Array, method: str) -> jax.Array:
    """One whole pass of a VAE (`method` "decode" or "encode") as one
    program, for callers outside any jit (the graph's VAE nodes).
    Called eagerly the same pass is ~800 one-operation programs with
    the device idle between them. The flax module is the static key
    (it hashes by its config, so bundles rebuilt or patched around the
    same VAE share a program per input shape and dtype); the weights
    are an argument, never constants of the executable, and nothing is
    donated (the executor caches the input)."""
    return module.apply(params, x, method=method)


@partial(jax.jit, static_argnames=("vae_static", "tile", "overlap"))
def decode_tiled(
    vae_static, params, latents: jax.Array, tile: int = 64, overlap: int = 8
) -> jax.Array:
    """[B, h, w, C] latents → [B, H, W, 3] via overlapping latent tiles.

    `tile`/`overlap` are in latent pixels; output tiles blend with the
    raised-cosine feather. Equivalent to full decode up to boundary
    feathering (exact in tile cores).
    """
    vae = vae_static.value
    b, h, w, c = latents.shape
    if h <= tile and w <= tile:
        return vae.vae.apply(params, latents, method="decode")

    grid = tile_ops.calculate_tiles(h, w, min(tile, h), min(tile, w), overlap)
    extracted = tile_ops.extract_tiles(latents, grid)  # [T, B, th+2o, tw+2o, C]

    def body(_, tile_lat):
        return None, vae.vae.apply(params, tile_lat, method="decode")

    _, decoded = jax.lax.scan(body, None, extracted)
    # decoded tiles are upscale-factor larger; blend on a pixel grid
    factor = decoded.shape[2] // extracted.shape[2]
    pixel_grid = tile_ops.TileGrid(
        image_h=h * factor,
        image_w=w * factor,
        tile_h=grid.tile_h * factor,
        tile_w=grid.tile_w * factor,
        padding=grid.padding * factor,
        rows=grid.rows,
        cols=grid.cols,
        positions=tuple((y * factor, x * factor) for y, x in grid.positions),
    )
    return tile_ops.blend_tiles(decoded, pixel_grid)


@partial(jax.jit, static_argnames=("vae_static", "tile", "overlap"))
def encode_tiled(
    vae_static, params, pixels: jax.Array, tile: int = 512, overlap: int = 64
) -> jax.Array:
    """[B, H, W, 3] → [B, h, w, C] via overlapping pixel tiles."""
    vae = vae_static.value
    b, h, w, c = pixels.shape
    if h <= tile and w <= tile:
        return vae.vae.apply(params, pixels, method="encode")

    grid = tile_ops.calculate_tiles(h, w, min(tile, h), min(tile, w), overlap)
    extracted = tile_ops.extract_tiles(pixels, grid)

    def body(_, tile_px):
        return None, vae.vae.apply(params, tile_px, method="encode")

    _, encoded = jax.lax.scan(body, None, extracted)
    factor = extracted.shape[2] // encoded.shape[2]
    latent_grid = tile_ops.TileGrid(
        image_h=h // factor,
        image_w=w // factor,
        tile_h=grid.tile_h // factor,
        tile_w=grid.tile_w // factor,
        padding=grid.padding // factor,
        rows=grid.rows,
        cols=grid.cols,
        positions=tuple((y // factor, x // factor) for y, x in grid.positions),
    )
    return tile_ops.blend_tiles(encoded, latent_grid)
