"""Tiled re-diffusion upscaling (Ultimate-SD-Upscale class) — compute core.

The reference's USDU pipeline (reference upscale/tile_ops.py:
upscale → tile grid → per-tile VAEEncode → KSampler → VAEDecode →
feathered blend) rebuilt TPU-first:

- single-participant path: one lax.scan over tiles, everything jitted;
- mesh path: the tile axis is sharded over the data axis under
  shard_map — each chip scans its contiguous tile slice, an all-gather
  returns the full tile set, and the order-independent blend
  reassembles the image. This replaces the reference's HTTP tile queue
  (reference upscale/job_store.py + api/usdu_routes.py) inside a slice.

Per-tile noise keys fold the GLOBAL tile index, so results are
bit-identical regardless of which participant processed which tile —
the property that makes elastic requeue safe.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models import pipeline as pl
from ..parallel.mesh import DATA_AXIS, data_axis_size, shard_map_compat
from ..utils.constants import tile_scan_batch
from . import samplers as smp
from . import tiles as tile_ops


# jax.image.resize method names for the user-facing upscale_method
# knob; "area" has no jax.image equivalent and gets an exact adaptive
# box-average implementation below (torch F.interpolate mode='area'
# semantics)
RESIZE_METHODS = {
    "bicubic": "cubic",
    "bilinear": "linear",
    "nearest": "nearest",
    "nearest-exact": "nearest",
    "lanczos": "lanczos3",
}


def _area_weights(n_out: int, n_in: int) -> jnp.ndarray:
    """[n_out, n_in] row-stochastic box weights: output cell i averages
    input cells overlapping [i*n_in/n_out, (i+1)*n_in/n_out) with
    fractional edge coverage — exact adaptive-average-pool semantics."""
    import numpy as np

    scale = n_in / n_out
    w = np.zeros((n_out, n_in), dtype=np.float32)
    for i in range(n_out):
        lo, hi = i * scale, (i + 1) * scale
        j0, j1 = int(np.floor(lo)), int(np.ceil(hi))
        for j in range(j0, min(j1, n_in)):
            cover = min(hi, j + 1) - max(lo, j)
            if cover > 0:
                w[i, j] = cover
        w[i] /= max(w[i].sum(), 1e-12)
    return jnp.asarray(w)


def area_resize(image: jax.Array, out_h: int, out_w: int) -> jax.Array:
    """[B, H, W, C] → [B, out_h, out_w, C] by exact box averaging —
    two dense matmuls, MXU-friendly."""
    wh = _area_weights(out_h, image.shape[1])
    ww = _area_weights(out_w, image.shape[2])
    return jnp.einsum(
        "oh,bhwc,pw->bopc", wh, image.astype(jnp.float32), ww
    )


def resize_image(
    image: jax.Array, out_h: int, out_w: int, method_name: str
) -> jax.Array:
    """Route a user-facing resize-method name to the right kernel.
    Unknown names raise (a typo silently coerced to bicubic rings on
    latents where the user chose nearest-exact on purpose); identical
    target dims return the input untouched."""
    if method_name != "area" and method_name not in RESIZE_METHODS:
        raise ValueError(
            f"unknown upscale_method {method_name!r}; use "
            f"{sorted(RESIZE_METHODS) + ['area']}"
        )
    if (image.shape[1], image.shape[2]) == (out_h, out_w):
        return image
    if method_name == "area":
        return area_resize(image, out_h, out_w)
    b, _, _, c = image.shape
    return jax.image.resize(
        image, (b, out_h, out_w, c), method=RESIZE_METHODS[method_name]
    )


def resolve_resize_dims(
    h: int, w: int, target_w: int, target_h: int
) -> tuple[int, int]:
    """(out_h, out_w) under the ComfyUI common_upscale convention: a 0
    target dimension preserves the source aspect (0/0 = identity)."""
    if target_w == 0 and target_h == 0:
        return h, w
    if target_w == 0:
        return target_h, max(1, round(w * target_h / h))
    if target_h == 0:
        return max(1, round(h * target_w / w)), target_w
    return target_h, target_w


def scale_dims(h: int, w: int, factor: float) -> tuple[int, int]:
    """(out_h, out_w) for a by-factor resize (the *UpscaleBy nodes):
    round-to-nearest, floored at 1 — one place for the convention."""
    return (
        max(1, int(round(h * float(factor)))),
        max(1, int(round(w * float(factor)))),
    )


def center_crop_to_aspect(arrs: list, out_h: int, out_w: int) -> list:
    """Center-crop [B, H, W, ...] planes to the (out_h, out_w) aspect
    (the common_upscale crop='center' rule); all planes share the
    leading spatial geometry and are sliced identically."""
    h, w = arrs[0].shape[1], arrs[0].shape[2]
    new_aspect = out_w / out_h
    if w / h > new_aspect:
        cw = max(1, round(h * new_aspect))
        x0 = (w - cw) // 2
        return [a[:, :, x0:x0 + cw] for a in arrs]
    if w / h < new_aspect:
        ch = max(1, round(w / new_aspect))
        y0 = (h - ch) // 2
        return [a[:, y0:y0 + ch] for a in arrs]
    return list(arrs)


def plan_grid(
    image_h: int,
    image_w: int,
    upscale_by: float,
    tile_w: int,
    padding: int,
    tile_h: int | None = None,
    mask_blur: int = 0,
    uniform: bool = True,
) -> tuple[int, int, tile_ops.TileGrid]:
    """Target size + tile grid for an upscale run. Tile geometry is
    clamped to the image and snapped to the VAE factor (8) so latent
    shapes stay integral. Non-square tiles supported (tile_h defaults
    to tile_w)."""
    out_h = int(round(image_h * upscale_by / 8)) * 8
    out_w = int(round(image_w * upscale_by / 8)) * 8
    tile_h = tile_h if tile_h is not None else tile_w
    tile_w = max(64, (int(tile_w) // 8) * 8)
    tile_h = max(64, (int(tile_h) // 8) * 8)
    padding = max(8, (padding // 8) * 8)
    grid = tile_ops.calculate_tiles(
        out_h, out_w, tile_h, tile_w, padding, mask_blur=mask_blur,
        uniform=uniform,
    )
    return out_h, out_w, grid


def prepare_upscaled_tiles(
    image: jax.Array,
    upscale_by: float,
    tile_w: int,
    padding: int,
    upscale_method: str = "bicubic",
    tile_h: int | None = None,
    mask_blur: int = 0,
    uniform: bool = True,
) -> tuple[jax.Array, tile_ops.TileGrid, jax.Array]:
    """Shared preamble for every USDU path (local / mesh / elastic
    master / elastic worker): resize, clip, extract. All participants
    MUST use this same function — bit-identical tile inputs are what
    makes cross-participant requeue seamless."""
    b, h, w, c = image.shape
    out_h, out_w, grid = plan_grid(
        h, w, upscale_by, tile_w, padding, tile_h, mask_blur=mask_blur,
        uniform=uniform,
    )
    upscaled = jnp.clip(
        resize_image(image, out_h, out_w, upscale_method), 0.0, 1.0
    )
    return upscaled, grid, tile_ops.extract_tiles(upscaled, grid)


def _pad_plane_for_grid(arr: jax.Array, grid: tile_ops.TileGrid) -> jax.Array:
    """Reflect-pad a [B, H, W(, C)] plane by the grid padding plus the
    coverage overhang (non-uniform grids) — the conditioning twin of
    tile_ops.pad_image_for_grid."""
    p = grid.padding
    extra_h = grid.coverage_h - grid.image_h
    extra_w = grid.coverage_w - grid.image_w
    tail = ((0, 0),) * (arr.ndim - 3)
    out = arr
    # edge-extend before the reflect ring (tile_ops.pad_image_for_grid
    # ordering) so the overhang replicates the true plane edge
    if extra_h or extra_w:
        out = jnp.pad(
            out, ((0, 0), (0, extra_h), (0, extra_w)) + tail, mode="edge"
        )
    return jnp.pad(out, ((0, 0), (p, p), (p, p)) + tail, mode="reflect")


def prep_cond_for_tiles(cond, grid: tile_ops.TileGrid):
    """Resize any ControlNet hint / mask to the upscaled image and pad
    by the grid padding, so per-tile windows can be sliced at the same
    origins the image tiles use (reference crop_cond preprocessing).
    Multi-entry conditioning (ConditioningCombine) preps per entry;
    area restrictions are rejected here — tile origins are traced in
    the mesh USDU scan, so a static area intersection per tile is
    impossible and applying the full-image area to a tile crop would
    be silently wrong coordinates."""
    from .conditioning import as_conditioning

    if isinstance(cond, (list, tuple)):
        return [prep_cond_for_tiles(c, grid) for c in cond]
    c = as_conditioning(cond).clone()
    if c.area is not None:
        raise ValueError(
            "area-restricted conditioning is not supported by the USDU "
            "tile path; remove the ConditioningSetArea restriction for "
            "upscaling"
        )
    if c.concat_latent is not None:
        # tile origins are traced; windowing the inpaint concat plane
        # per tile needs the same canvas prep as reference_latents but
        # at the BUNDLE's latent scale, which this grid doesn't know —
        # reject loudly rather than let the model squash the full plane
        raise ValueError(
            "inpaint-model concat conditioning (InpaintModelConditioning)"
            " is not supported by the USDU tile path; use the standard "
            "inpaint flow (VAEEncodeForInpaint / SetLatentNoiseMask) for "
            "tiled upscaling"
        )
    p = grid.padding
    if c.control_hint is not None:
        hint = c.control_hint
        if hint.shape[1] != grid.image_h or hint.shape[2] != grid.image_w:
            hint = jax.image.resize(
                hint,
                (hint.shape[0], grid.image_h, grid.image_w, hint.shape[3]),
                method="linear",
            )
        c.control_hint = _pad_plane_for_grid(hint, grid)
    if c.mask is not None:
        mask = c.mask
        if mask.shape[1] != grid.image_h or mask.shape[2] != grid.image_w:
            mask = jax.image.resize(
                mask, (mask.shape[0], grid.image_h, grid.image_w), method="linear"
            )
        c.mask = _pad_plane_for_grid(mask, grid)
    if c.model_patches is not None:
        patched = {}
        for name, patch in c.model_patches.items():
            if patch.shape[1] != grid.image_h or patch.shape[2] != grid.image_w:
                patch = jax.image.resize(
                    patch,
                    (patch.shape[0], grid.image_h, grid.image_w, patch.shape[3]),
                    method="linear",
                )
            patched[name] = _pad_plane_for_grid(patch, grid)
        c.model_patches = patched
    if c.reference_latents is not None:
        # same convention as the image planes above: resize to the
        # CANVAS latent grid, then edge-pad by the grid padding (in
        # latent units), so a tile's latent window at (y//8, x//8)
        # covers exactly the image region the tile covers — squeezing
        # the ref into the padded canvas instead would shift and
        # shrink every tile's reference crop
        k = 8
        pk = p // k
        cov_h, cov_w = grid.coverage_h // k, grid.coverage_w // k
        prepped = []
        for lat in c.reference_latents:
            if lat.shape[1:3] != (cov_h, cov_w):
                lat = jax.image.resize(
                    lat, (lat.shape[0], cov_h, cov_w, lat.shape[3]),
                    method="linear",
                )
            prepped.append(
                jnp.pad(
                    lat, ((0, 0), (pk, pk), (pk, pk), (0, 0)), mode="edge"
                )
            )
        c.reference_latents = prepped
    return c


def tile_cond(cond, y, x, grid: tile_ops.TileGrid):
    """Slice a tile's window out of conditioning prepped by
    prep_cond_for_tiles; (y, x) may be traced (scan body)."""
    from .conditioning import Conditioning

    if isinstance(cond, (list, tuple)):
        return [tile_cond(c, y, x, grid) for c in cond]
    if not isinstance(cond, Conditioning):
        return cond
    c = cond.clone()
    if c.control_hint is not None:
        c.control_hint = jax.lax.dynamic_slice(
            c.control_hint,
            (0, y, x, 0),
            (c.control_hint.shape[0], grid.padded_h, grid.padded_w,
             c.control_hint.shape[3]),
        )
    if c.mask is not None:
        c.mask = jax.lax.dynamic_slice(
            c.mask, (0, y, x), (c.mask.shape[0], grid.padded_h, grid.padded_w)
        )
    if c.model_patches is not None:
        c.model_patches = {
            name: jax.lax.dynamic_slice(
                patch, (0, y, x, 0),
                (patch.shape[0], grid.padded_h, grid.padded_w, patch.shape[3]),
            )
            for name, patch in c.model_patches.items()
        }
    if c.reference_latents is not None:
        k = 8
        th, tw = max(1, grid.padded_h // k), max(1, grid.padded_w // k)
        c.reference_latents = [
            jax.lax.dynamic_slice(
                lat, (0, y // k, x // k, 0), (lat.shape[0], th, tw, lat.shape[3])
            )
            for lat in c.reference_latents
        ]
    return c


def _process_tile_fn(bundle, grid, steps, sampler, scheduler, cfg, denoise,
                     tiled_decode=False):
    """Returns fn(params, tile, key, pos, neg, yx) → processed tiles.
    pos/neg must already be prepped via prep_cond_for_tiles; yx is the
    tile origin [2] (traced ok)."""
    param, shift = pl.model_schedule_info(bundle)
    sigmas = smp.get_model_sigmas(
        param, scheduler, steps, denoise=denoise, flow_shift=shift
    )

    def fn(params, tile, key, pos, neg, yx):
        pos_t = tile_cond(pos, yx[0], yx[1], grid)
        neg_t = tile_cond(neg, yx[0], yx[1], grid)
        z = bundle.vae.apply(params["vae"], tile, method="encode")
        noise_key, anc_key = jax.random.split(key)
        x = smp.noise_latents(
            param, z, jax.random.normal(noise_key, z.shape), sigmas[0]
        )
        model_fn = pl.guided_model(bundle, params, cfg)
        z_out = smp.sample(
            model_fn, x, sigmas, (pos_t, neg_t), sampler, anc_key,
            flow=(param == "flow"),
        )
        if tiled_decode:
            from .tiled_vae import decode_tiled

            return decode_tiled(pl._Static(bundle), params["vae"], z_out)
        return bundle.vae.apply(params["vae"], z_out, method="decode")

    return fn


def _wraparound_pad(arrs, total: int):
    """Pad leading axes to `total` by wrapping — duplicates later share
    folded keys (idx % t) so they compute identical results and the
    surplus is sliced off."""
    t = arrs[0].shape[0]
    reps = -(-total // t)
    return [jnp.concatenate([a] * reps, axis=0)[:total] for a in arrs]


def grant_buckets(k_max: int) -> tuple[int, ...]:
    """The bounded set of compiled tile-batch shapes for grants up to
    `k_max`: powers of two plus k_max itself — at most
    ceil(log2(k_max)) + 1 sizes. The elastic tier pads every ragged
    grant up to its bucket (wraparound duplicates with folded keys,
    surplus sliced off) so a job's worth of varying grant sizes never
    triggers a fresh compile mid-run."""
    k_max = max(1, int(k_max))
    sizes = []
    b = 1
    while b < k_max:
        sizes.append(b)
        b *= 2
    sizes.append(k_max)
    return tuple(sizes)


def bucket_for(
    n: int, k_max: int, buckets: tuple[int, ...] | None = None
) -> int:
    """Smallest grant bucket that fits `n` tiles (n clamped to the
    largest bucket). `buckets` overrides the default grant_buckets
    set — the mesh-parallel sampler passes its data-width-rounded
    buckets so one first-fit implementation serves both tiers."""
    if buckets is None:
        buckets = grant_buckets(k_max)
    n = max(1, min(int(n), buckets[-1]))
    for size in buckets:
        if size >= n:
            return size
    return buckets[-1]


def _scan_tiles(one, extracted, keys, positions, tile_batch: int):
    """Scan the tile axis in groups of `tile_batch`, vmapping
    one(tile, key, yx) across each group. K=1 is the reference scan;
    K>1 turns the batch-1 UNet/VAE convs into batch-K programs — the
    MXU-idiomatic shape (one tile's batch-1 matmuls leave most of the
    systolic array idle). A remainder of num % K tiles runs as one
    smaller vmapped group (a second compiled shape) rather than as
    full-cost wraparound duplicates. Results are tile-batch-
    independent: keys are folded from GLOBAL tile indices by the
    caller, grouping only changes how many tiles share one dispatch."""
    num = extracted.shape[0]
    k = max(1, min(tile_batch, num))
    if k == 1:
        def body(_, inp):
            return None, one(*inp)

        _, out = jax.lax.scan(body, None, (extracted, keys, positions))
        return out

    n_full = num // k
    split = n_full * k
    outs = []
    if n_full:
        grouped = (
            extracted[:split].reshape(n_full, k, *extracted.shape[1:]),
            # keep trailing dims: legacy uint32 PRNGKeys are [T, 2]
            keys[:split].reshape(n_full, k, *keys.shape[1:]),
            positions[:split].reshape(n_full, k, *positions.shape[1:]),
        )

        def body(_, inp):
            return None, jax.vmap(one)(*inp)

        _, full = jax.lax.scan(body, None, grouped)
        outs.append(full.reshape(split, *full.shape[2:]))
    if split < num:
        outs.append(
            jax.vmap(one)(extracted[split:], keys[split:], positions[split:])
        )
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)


@partial(
    jax.jit,
    static_argnames=(
        "bundle_static", "grid", "steps", "sampler", "scheduler", "cfg",
        "denoise", "tiled_decode", "tile_batch",
    ),
)
def upscale_single(
    bundle_static,
    params,
    upscaled,            # [B, H, W, C] pre-upscaled image
    pos,
    neg,
    key,
    grid: tile_ops.TileGrid,
    steps: int,
    sampler: str,
    scheduler: str,
    cfg: float,
    denoise: float,
    tiled_decode: bool = False,
    tile_batch: int = 1,
):
    """All tiles processed on the local device via lax.scan."""
    bundle = bundle_static.value
    extracted = tile_ops.extract_tiles(upscaled, grid)  # [T, B, th, tw, C]
    pos = prep_cond_for_tiles(pos, grid)
    neg = prep_cond_for_tiles(neg, grid)
    process = _process_tile_fn(
        bundle, grid, steps, sampler, scheduler, cfg, denoise, tiled_decode
    )
    keys = jax.vmap(lambda g: jax.random.fold_in(key, g))(
        jnp.arange(grid.num_tiles)
    )

    def one(tile, tkey, yx):
        return process(params, tile, tkey, pos, neg, yx)

    processed = _scan_tiles(
        one, extracted, keys, grid.positions_array(), tile_batch
    )
    with jax.named_scope("tile_blend"):
        return tile_ops.blend_tiles(processed, grid)


@partial(
    jax.jit,
    static_argnames=(
        "bundle_static", "mesh_static", "grid", "steps", "sampler",
        "scheduler", "cfg", "denoise", "tiled_decode", "tile_batch",
    ),
)
def upscale_mesh(
    bundle_static,
    mesh_static,
    params,
    upscaled,
    pos,
    neg,
    key,
    grid: tile_ops.TileGrid,
    steps: int,
    sampler: str,
    scheduler: str,
    cfg: float,
    denoise: float,
    tiled_decode: bool = False,
    tile_batch: int = 1,
):
    """Tile axis sharded over the mesh data axis; all-gather + blend.

    Static sharding (every chip gets ceil(T/n) tiles) is the TPU fast
    path — the reference's dynamic work-stealing only pays off for
    heterogeneous participants, which inside a slice don't exist.
    tile_batch groups each chip's scan the same way as the local path
    (the per-chip program is _scan_tiles with num_tiles=shard size).
    """
    bundle = bundle_static.value
    mesh = mesh_static.value
    n = data_axis_size(mesh)
    pos = prep_cond_for_tiles(pos, grid)
    neg = prep_cond_for_tiles(neg, grid)
    process = _process_tile_fn(
        bundle, grid, steps, sampler, scheduler, cfg, denoise, tiled_decode
    )

    extracted = tile_ops.extract_tiles(upscaled, grid)  # [T, B, th, tw, C]
    t = grid.num_tiles
    per_chip = -(-t // n)  # ceil
    total = per_chip * n
    positions = grid.positions_array()
    if total > t:
        # wrap-around padding: works even when t < n (tiny images on
        # wide meshes); padded duplicates are sliced off after gather
        extracted, positions = _wraparound_pad([extracted, positions], total)
    global_idx = jnp.arange(total)

    def per_chip_fn(tiles_shard, idx_shard, yx_shard, params, pos, neg):
        # padded dups share keys: fold the GLOBAL tile index mod t
        keys = jax.vmap(lambda g: jax.random.fold_in(key, g % t))(idx_shard)

        def one(tile, tkey, yx):
            return process(params, tile, tkey, pos, neg, yx)

        processed = _scan_tiles(one, tiles_shard, keys, yx_shard, tile_batch)
        return jax.lax.all_gather(processed, DATA_AXIS, axis=0, tiled=True)

    gathered = shard_map_compat(
        per_chip_fn,
        mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS), P(), P(), P()),
        out_specs=P(),
        check=False,
    )(extracted, global_idx, positions, params, pos, neg)
    with jax.named_scope("tile_blend"):
        return tile_ops.blend_tiles(gathered[:t], grid)


def run_upscale(
    bundle: pl.PipelineBundle,
    image: jax.Array,
    pos: jax.Array,
    neg: jax.Array,
    mesh: Any = None,
    upscale_by: float = 2.0,
    tile: int = 512,
    padding: int = 32,
    steps: int = 20,
    sampler: str = "euler",
    scheduler: str = "karras",
    cfg: float = 7.0,
    denoise: float = 0.35,
    seed: int = 0,
    upscale_method: str = "bicubic",
    tile_h: int | None = None,
    mask_blur: int = 0,
    tiled_decode: bool = False,
    uniform: bool = True,
    tile_batch: int | None = None,
) -> jax.Array:
    """Full upscale: resize then tile-rediffuse. Routes to the mesh
    path when a multi-participant mesh is available.

    tile_batch (or env CDT_TILE_BATCH, default 1) groups the tile scan
    so the diffusion runs batch-K programs — on TPU, batch-1 convs
    leave most of the MXU idle; K=4-8 amortizes dispatch and fills the
    systolic array. K=1 preserves the committed golden numerics
    bit-for-bit; batched grouping is allclose but not bit-identical
    (batched conv reduction order differs)."""
    if tile_batch is None:
        tile_batch = tile_scan_batch()
    upscaled, grid, _ = prepare_upscaled_tiles(
        image, upscale_by, tile, padding, upscale_method, tile_h,
        mask_blur=mask_blur, uniform=uniform,
    )
    key = jax.random.key(seed)
    if mesh is not None and data_axis_size(mesh) > 1:
        params = jax.device_put(bundle.params, NamedSharding(mesh, P()))
        upscaled = jax.device_put(upscaled, NamedSharding(mesh, P()))
        pos_p = jax.device_put(pos, NamedSharding(mesh, P()))
        neg_p = jax.device_put(neg, NamedSharding(mesh, P()))
        return upscale_mesh(
            pl._Static(bundle), pl._Static(mesh), params, upscaled, pos_p,
            neg_p, key, grid, int(steps), sampler, scheduler, float(cfg),
            float(denoise), bool(tiled_decode), int(tile_batch),
        )
    return upscale_single(
        pl._Static(bundle), bundle.params, upscaled, pos, neg, key, grid,
        int(steps), sampler, scheduler, float(cfg), float(denoise),
        bool(tiled_decode), int(tile_batch),
    )
