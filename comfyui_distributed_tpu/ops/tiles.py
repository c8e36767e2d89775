"""Tile grid math for distributed upscaling — pure jnp, static shapes.

Re-designs the reference's tile pipeline (upscale/tile_ops.py:
calculate_tiles / extract_tile_with_padding / create_tile_mask /
blend_tile) for XLA: the tile grid is computed statically in Python
(shapes must be trace-time constants), extraction is a vmapped
dynamic_slice over a reflect-padded image, and blending is an
order-independent feathered weighted average so tiles can be produced
by any participant in any order with a numerically equivalent result
(identical up to float accumulation order, ~1 ULP).

Every tile has the same static shape in BOTH grid modes — the TPU
re-design of the reference's uniform/non-uniform choice
(upscale/tile_ops.py:73-78):

- uniform (`force_uniform_tiles=True`, default): edge-tile origins are
  clamped so the last row/column overlaps its neighbor instead of
  shrinking.
- non-uniform (`force_uniform_tiles=False`): tile origins stay on the
  plain ceil grid (the reference's smaller-edge-tile boundaries), and
  instead of shrinking the edge tiles — dynamic shapes, poison for XLA
  — the canvas is edge-extended to full grid coverage; the out-of-image
  strip edge tiles produce is cropped away after blending. Same seam
  positions as the reference, same static shapes as the uniform path.
"""

from __future__ import annotations

import dataclasses
import math
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class TileGrid:
    """Static description of a tiling of an image plane."""

    image_h: int
    image_w: int
    tile_h: int
    tile_w: int
    padding: int
    rows: int
    cols: int
    # [T, 2] int32 (y, x) origins of the *unpadded* tile regions.
    positions: tuple[tuple[int, int], ...]
    # feather-ramp width in pixels (reference USDU `mask_blur`);
    # 0 = full padding width. Clamped to the padding ring.
    mask_blur: int = 0
    # False = ceil-grid origins without clamping (reference
    # force_uniform_tiles=False seam positions); edge tiles then extend
    # past the image into an edge-padded strip that blending crops.
    uniform: bool = True

    @property
    def feather(self) -> int:
        if self.mask_blur > 0:
            return min(self.mask_blur, self.padding)
        return self.padding

    @property
    def num_tiles(self) -> int:
        return self.rows * self.cols

    @property
    def coverage_h(self) -> int:
        """Canvas height the grid actually covers (≥ image_h when
        non-uniform edge tiles overhang the image)."""
        return max(self.image_h, max(y for y, _ in self.positions) + self.tile_h)

    @property
    def coverage_w(self) -> int:
        return max(self.image_w, max(x for _, x in self.positions) + self.tile_w)

    @property
    def padded_h(self) -> int:
        return self.tile_h + 2 * self.padding

    @property
    def padded_w(self) -> int:
        return self.tile_w + 2 * self.padding

    def positions_array(self) -> jnp.ndarray:
        return jnp.asarray(self.positions, dtype=jnp.int32)


def calculate_tiles(
    image_h: int,
    image_w: int,
    tile_h: int,
    tile_w: int,
    padding: int = 32,
    mask_blur: int = 0,
    uniform: bool = True,
) -> TileGrid:
    """Ceil-grid tiling, every tile exactly (tile_h, tile_w).

    Parity with reference upscale/tile_ops.py `calculate_tiles` (ceil
    grid). uniform=True shifts the last row/column left/up so it
    overlaps its neighbor; uniform=False keeps the reference's
    non-uniform seam positions (plain r*tile_h origins) with edge
    tiles overhanging into an edge-extended canvas strip.
    """
    tile_h = min(tile_h, image_h)
    tile_w = min(tile_w, image_w)
    rows = max(1, math.ceil(image_h / tile_h))
    cols = max(1, math.ceil(image_w / tile_w))
    positions = []
    for r in range(rows):
        y = r * tile_h if not uniform else min(r * tile_h, image_h - tile_h)
        for c in range(cols):
            x = c * tile_w if not uniform else min(c * tile_w, image_w - tile_w)
            positions.append((y, x))
    return TileGrid(
        image_h=image_h,
        image_w=image_w,
        tile_h=tile_h,
        tile_w=tile_w,
        padding=padding,
        rows=rows,
        cols=cols,
        positions=tuple(positions),
        mask_blur=mask_blur,
        uniform=uniform,
    )


def pad_image_for_grid(images: jax.Array, grid: TileGrid) -> jax.Array:
    """Pad [B, H, W, C] so padded tile extraction never clips: a
    reflect ring of `padding`, plus (non-uniform grids) edge-replicated
    bottom/right strips out to the grid's coverage."""
    p = grid.padding
    extra_h = grid.coverage_h - grid.image_h
    extra_w = grid.coverage_w - grid.image_w
    if p == 0 and extra_h == 0 and extra_w == 0:
        return images
    out = images
    # Edge-extend FIRST so the overhang strip replicates the true image
    # edge; reflect-padding first would make the strip copy a reflected
    # interior row instead.
    if extra_h or extra_w:
        out = jnp.pad(
            out, ((0, 0), (0, extra_h), (0, extra_w), (0, 0)), mode="edge"
        )
    if p > 0:
        out = jnp.pad(out, ((0, 0), (p, p), (p, p), (0, 0)), mode="reflect")
    return out


@partial(jax.jit, static_argnames=("tile_h", "tile_w"))
def _extract_one(
    padded: jax.Array, y: jax.Array, x: jax.Array, tile_h: int, tile_w: int
) -> jax.Array:
    return jax.lax.dynamic_slice(
        padded,
        (0, y, x, 0),
        (padded.shape[0], tile_h, tile_w, padded.shape[3]),
    )


def extract_tiles(images: jax.Array, grid: TileGrid) -> jax.Array:
    """[B, H, W, C] → [T, B, th+2p, tw+2p, C] padded tiles.

    Positions index the padded image, so the padded tile is centered on
    the unpadded region (reference extract_tile_with_padding semantics).
    """
    padded = pad_image_for_grid(images, grid)
    pos = grid.positions_array()
    return jax.vmap(
        lambda p: _extract_one(padded, p[0], p[1], grid.padded_h, grid.padded_w)
    )(pos)


@lru_cache(maxsize=64)
def _feather_mask_np(padded_h: int, padded_w: int, padding: int) -> np.ndarray:
    def ramp(n: int, pad: int) -> np.ndarray:
        w = np.ones(n, dtype=np.float64)
        if pad > 0:
            t = (np.arange(pad) + 0.5) / pad  # 0..1 across the ring
            edge = 0.5 - 0.5 * np.cos(np.pi * t)
            w[:pad] = np.maximum(edge, 1e-4)
            w[-pad:] = np.maximum(edge[::-1], 1e-4)
        return w

    return np.outer(ramp(padded_h, padding), ramp(padded_w, padding))


def feather_mask(grid: TileGrid, dtype=jnp.float32) -> jnp.ndarray:
    """[th+2p, tw+2p] feathering weights, 1.0 in the core, smooth
    raised-cosine falloff across the padding ring.

    Replaces the reference's Gaussian-blurred rectangle mask
    (upscale/tile_ops.py `create_tile_mask`): the raised cosine is
    separable, needs no conv, and sums smoothly where tiles overlap.
    Every weight is strictly positive so the normalising weight map
    never divides by zero. Cached per (shape, feather width). The ramp
    width follows `grid.mask_blur` (reference USDU `mask_blur` knob)
    clamped to the padding ring; 0 = the full padding width.
    """
    return jnp.asarray(
        _feather_mask_np(grid.padded_h, grid.padded_w, grid.feather), dtype=dtype
    )


def blend_tiles(tiles: jax.Array, grid: TileGrid) -> jax.Array:
    """[T, B, th+2p, tw+2p, C] processed tiles → [B, H, W, C] blended.

    Order-independent (up to float accumulation order): weighted
    accumulation into a padded canvas plus a weight map, then normalize
    and crop. Which participant produced which tile doesn't matter —
    the property the reference has to engineer with sorted sequential
    blending (upscale/modes/static.py:521-553).

    A sequential scan of windowed canvas updates: one
    dynamic_update_slice pair per tile, float32 accumulation.
    """
    batch, channels = int(tiles.shape[1]), int(tiles.shape[4])
    p = grid.padding
    ph, pw = grid.coverage_h + 2 * p, grid.coverage_w + 2 * p
    mask = feather_mask(grid, dtype=tiles.dtype)[None, :, :, None]
    pos = grid.positions_array()

    canvas = jnp.zeros((batch, ph, pw, channels), dtype=jnp.float32)
    weights = jnp.zeros((1, ph, pw, 1), dtype=jnp.float32)

    def body(carry, inputs):
        canvas, weights = carry
        tile, yx = inputs
        weighted = (tile * mask).astype(jnp.float32)
        canvas = jax.lax.dynamic_update_slice(
            canvas,
            jax.lax.dynamic_slice(
                canvas, (0, yx[0], yx[1], 0),
                (batch, grid.padded_h, grid.padded_w, channels),
            )
            + weighted,
            (0, yx[0], yx[1], 0),
        )
        weights = jax.lax.dynamic_update_slice(
            weights,
            jax.lax.dynamic_slice(
                weights, (0, yx[0], yx[1], 0), (1, grid.padded_h, grid.padded_w, 1)
            )
            + mask.astype(jnp.float32),
            (0, yx[0], yx[1], 0),
        )
        return (canvas, weights), None

    (canvas, weights), _ = jax.lax.scan(body, (canvas, weights), (tiles, pos))
    blended = canvas / jnp.maximum(weights, 1e-8)
    return blended[:, p : p + grid.image_h, p : p + grid.image_w, :].astype(
        tiles.dtype
    )


class IncrementalCanvas:
    """Alpha-composite tiles one at a time onto a canvas padded once.

    The elastic-tier blend path, where tiles arrive incrementally over
    HTTP (reference upscale/tile_ops.py `blend_tile`): pad the base
    image once, composite each arriving tile into the padded canvas
    with the cached feather mask, crop once at the end — O(tile) work
    per tile instead of O(image).
    """

    def __init__(self, images: jax.Array, grid: TileGrid):
        self.grid = grid
        self.padded = pad_image_for_grid(images, grid)
        self._mask = feather_mask(grid, dtype=images.dtype)[None, :, :, None]

    def blend(self, tile: jax.Array, y, x) -> None:
        """Composite one [B, th+2p, tw+2p, C] tile at unpadded origin (y, x)."""
        region = jax.lax.dynamic_slice(
            self.padded,
            (0, y, x, 0),
            (self.padded.shape[0], self.grid.padded_h, self.grid.padded_w,
             self.padded.shape[3]),
        )
        blended = region * (1.0 - self._mask) + tile * self._mask
        self.padded = jax.lax.dynamic_update_slice(self.padded, blended, (0, y, x, 0))

    def result(self) -> jax.Array:
        p = self.grid.padding
        return self.padded[
            :, p : p + self.grid.image_h, p : p + self.grid.image_w, :
        ]


class HostIncrementalCanvas:
    """numpy/native twin of IncrementalCanvas for the HTTP tier.

    Elastic-tier tiles arrive host-side (decoded from PNG envelopes),
    so compositing on the host via the native feathered-blend kernel
    (native/blendlib.cpp) avoids a device round-trip per tile; the
    canvas moves to device once, in result(). Bit-equal in math to
    IncrementalCanvas (same feather mask, same lerp) — pinned by test.
    """

    def __init__(self, images: jax.Array, grid: TileGrid):
        import numpy as np

        self.grid = grid
        self.padded = np.ascontiguousarray(
            np.asarray(pad_image_for_grid(images, grid), dtype=np.float32)
        )
        self._mask = np.asarray(
            feather_mask(grid, dtype=jnp.float32), dtype=np.float32
        )

    def blend(self, tile, y, x) -> None:
        import numpy as np

        from ..native import feathered_blend_inplace

        feathered_blend_inplace(
            self.padded, np.asarray(tile, dtype=np.float32), self._mask,
            int(y), int(x),
        )

    def result(self) -> jax.Array:
        p = self.grid.padding
        return jnp.asarray(
            self.padded[:, p : p + self.grid.image_h, p : p + self.grid.image_w, :]
        )


class DeterministicHostCanvas:
    """Order-canonical twin of HostIncrementalCanvas.

    Sequential feathered lerp is order-dependent where tiles overlap,
    and in the elastic tier the blend order follows result ARRIVAL
    order — a race. This canvas buffers every tile and composites in
    sorted (y, x) order at `result()`, so two runs that produced
    identical per-tile outputs produce bit-identical images no matter
    which participant finished which tile first (the property the
    chaos tests assert across fault-free and fault-recovered runs).
    Costs one decoded tile set of host memory; enabled per-run via
    CDT_DETERMINISTIC_BLEND=1.
    """

    def __init__(self, images: jax.Array, grid: TileGrid):
        import numpy as np

        self.grid = grid
        self._base = images
        self._tiles: dict[tuple[int, int], "np.ndarray"] = {}

    def blend(self, tile, y, x) -> None:
        import numpy as np

        # (y, x) is unique per tile in the grid, so the dict also
        # deduplicates a tile blended twice (last write wins, and
        # identical payloads make the choice immaterial).
        self._tiles[(int(y), int(x))] = np.asarray(tile, dtype=np.float32)

    def result(self) -> jax.Array:
        inner = HostIncrementalCanvas(self._base, self.grid)
        for (y, x), tile in sorted(self._tiles.items()):
            inner.blend(tile, y, x)
        return inner.result()


class DeviceCanvas:
    """Device-resident twin of DeterministicHostCanvas.

    Master-local tiles never leave the device: each blended tile is
    buffered as a device float32 array and composited in sorted (y, x)
    order at `result()` with the same feathered lerp the host canvas
    uses, so the flush transfers ONE composited canvas instead of one
    image per tile (the d2h seam the transfer ledger attributes per
    tile today). Compositing runs eagerly (op-by-op) on purpose: each
    primitive rounds individually, exactly like the numpy / native
    (-ffp-contract=off) host path, so DeviceCanvas ≡
    DeterministicHostCanvas is a BIT-IDENTITY guarantee, not a
    tolerance — pinned by test and by the chaos harness.

    `sharding` optionally places the padded canvas (batch-axis sharding
    is the safe choice: the per-tile dynamic slices span full H/W rows
    so only the batch dim may be split without cross-shard gathers).
    Enabled per-run via CDT_DEVICE_CANVAS=1 on the master-local grant
    path; remote workers keep the PNG path (their tiles arrive
    host-side by construction).
    """

    def __init__(self, images: jax.Array, grid: TileGrid, sharding=None):
        self.grid = grid
        base = jnp.asarray(images, dtype=jnp.float32)
        if sharding is not None:
            base = jax.device_put(base, sharding)
        self._base = base
        self._sharding = sharding
        self._tiles: dict[tuple[int, int], jax.Array] = {}

    def blend(self, tile, y, x) -> None:
        # (y, x) is unique per tile in the grid: the dict deduplicates
        # a tile blended twice (last write wins; identical payloads —
        # the determinism invariant — make the choice immaterial).
        t = jnp.asarray(tile, dtype=jnp.float32)
        if self._sharding is not None:
            t = jax.device_put(t, self._sharding)
        self._tiles[(int(y), int(x))] = t

    @property
    def tile_count(self) -> int:
        return len(self._tiles)

    def result(self) -> jax.Array:
        """Composite buffered tiles in sorted order; stays on device.

        The caller owns the single d2h transfer (and its ledger note).
        """
        grid = self.grid
        padded = pad_image_for_grid(self._base, grid)
        mask = feather_mask(grid, dtype=jnp.float32)[None, :, :, None]
        inv = 1.0 - mask
        b, c = padded.shape[0], padded.shape[3]
        for (y, x), tile in sorted(self._tiles.items()):
            region = jax.lax.dynamic_slice(
                padded, (0, y, x, 0), (b, grid.padded_h, grid.padded_w, c)
            )
            blended = region * inv + tile * mask
            padded = jax.lax.dynamic_update_slice(padded, blended, (0, y, x, 0))
        p = grid.padding
        return padded[:, p : p + grid.image_h, p : p + grid.image_w, :]


def blend_single_tile(
    canvas: jax.Array, tile: jax.Array, y: int, x: int, grid: TileGrid
) -> jax.Array:
    """One-shot convenience wrapper over IncrementalCanvas (prefer the
    class when blending many tiles — it pads the canvas only once)."""
    inc = IncrementalCanvas(canvas, grid)
    inc.blend(tile, y, x)
    return inc.result()


def upscale_nearest(images: jax.Array, scale: int) -> jax.Array:
    """Cheap integer-factor spatial upscale [B,H,W,C] used before tiled
    re-diffusion (the reference delegates this to an upscale model or
    PIL resize; lanczos/bicubic/area live in ops/upscale.resize_image)."""
    b, h, w, c = images.shape
    return jax.image.resize(images, (b, h * scale, w * scale, c), method="nearest")
