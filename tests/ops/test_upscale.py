"""USDU compute core: single-device vs mesh-sharded tile paths must
produce identical images (the assignment-independence property), and
denoise=0-ish runs must stay close to the plain resize."""

import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.models import pipeline as pl
from comfyui_distributed_tpu.ops import upscale as up
from comfyui_distributed_tpu.parallel import build_mesh


@pytest.fixture(scope="module")
def bundle():
    return pl.load_pipeline("tiny-unet", seed=0)


def _image():
    rng = np.random.default_rng(3)
    return jnp.asarray(rng.random((1, 64, 64, 3)), dtype=jnp.float32)


def test_plan_grid_snaps_to_vae_factor():
    out_h, out_w, grid = up.plan_grid(100, 100, 2.0, 96, 20)
    assert out_h % 8 == 0 and out_w % 8 == 0
    assert grid.tile_h % 8 == 0 and grid.padding % 8 == 0


def test_single_upscale_shapes(bundle):
    img = _image()
    pos = pl.encode_text(bundle, ["p"])
    neg = pl.encode_text(bundle, [""])
    out = up.run_upscale(
        bundle, img, pos, neg, mesh=None, upscale_by=2.0, tile=64,
        padding=16, steps=2, denoise=0.4, seed=1,
    )
    assert out.shape == (1, 128, 128, 3)
    assert np.isfinite(np.asarray(out)).all()


def test_prep_ref_latents_alignment():
    """Reference latents follow the image-plane convention (canvas
    grid + edge padding, no squeeze), so a tile's latent window covers
    exactly the image region the tile covers."""
    from comfyui_distributed_tpu.ops.conditioning import Conditioning

    _, _, grid = up.plan_grid(64, 64, 2.0, 64, 16)
    k = 8
    pk = grid.padding // k
    cov = (grid.coverage_h // k, grid.coverage_w // k)
    ref = jnp.arange(cov[0] * cov[1], dtype=jnp.float32).reshape(
        1, cov[0], cov[1], 1
    )
    cond = Conditioning(context=jnp.zeros((1, 4, 8)), reference_latents=[ref])
    prepped = up.prep_cond_for_tiles(cond, grid)
    padded = prepped.reference_latents[0]
    assert padded.shape[1:3] == (cov[0] + 2 * pk, cov[1] + 2 * pk)
    # canvas content is padded, never rescaled
    np.testing.assert_array_equal(
        np.asarray(padded[:, pk:-pk, pk:-pk]), np.asarray(ref)
    )
    w = up.tile_cond(prepped, 0, 0, grid).reference_latents[0]
    th, tw = grid.padded_h // k, grid.padded_w // k
    assert w.shape[1:3] == (th, tw)
    np.testing.assert_array_equal(
        np.asarray(w[:, pk:, pk:]),
        np.asarray(ref[:, : th - pk, : tw - pk]),
    )


def test_mesh_matches_single(bundle):
    """Tile sharding over 8 chips must be numerically equivalent to the
    local scan — same folded per-tile keys, same blend."""
    img = _image()
    pos = pl.encode_text(bundle, ["p"])
    neg = pl.encode_text(bundle, [""])
    kwargs = dict(upscale_by=2.0, tile=64, padding=16, steps=2,
                  denoise=0.4, seed=7, tile_batch=1)  # K=1: bit-parity property
    single = up.run_upscale(bundle, img, pos, neg, mesh=None, **kwargs)
    mesh = build_mesh({"data": 8})
    sharded = up.run_upscale(bundle, img, pos, neg, mesh=mesh, **kwargs)
    np.testing.assert_allclose(
        np.asarray(single), np.asarray(sharded), atol=2e-2, rtol=0
    )
    # and the mesh result is deterministic
    again = up.run_upscale(bundle, img, pos, neg, mesh=mesh, **kwargs)
    np.testing.assert_array_equal(np.asarray(sharded), np.asarray(again))


def test_tile_batch_matches_unbatched(bundle):
    """Grouping the tile scan (CDT_TILE_BATCH) must not change the
    image beyond batched-conv reduction-order noise: same folded
    per-tile keys, same blend. K=3 on a 4-tile grid exercises the
    wraparound remainder group; K larger than the grid clamps."""
    img = _image()
    pos = pl.encode_text(bundle, ["p"])
    neg = pl.encode_text(bundle, [""])
    kwargs = dict(upscale_by=2.0, tile=64, padding=16, steps=2,
                  denoise=0.4, seed=7)
    base = np.asarray(
        up.run_upscale(bundle, img, pos, neg, mesh=None, tile_batch=1, **kwargs)
    )
    for k in (3, 99):
        batched = np.asarray(
            up.run_upscale(
                bundle, img, pos, neg, mesh=None, tile_batch=k, **kwargs
            )
        )
        np.testing.assert_allclose(base, batched, atol=2e-2, rtol=0)


def test_tile_batch_accepts_legacy_prngkey(bundle):
    """Direct callers may pass a legacy uint32 PRNGKey ([2]-shaped);
    the grouped keys reshape must preserve trailing dims."""
    import jax

    img = _image()
    pos = pl.encode_text(bundle, ["p"])
    neg = pl.encode_text(bundle, [""])
    upscaled, grid, _ = up.prepare_upscaled_tiles(img, 2.0, 64, 16)
    out = up.upscale_single(
        pl._Static(bundle), bundle.params, upscaled, pos, neg,
        jax.random.PRNGKey(7), grid, 2, "euler", "karras", 7.0, 0.4,
        False, 3,
    )
    assert out.shape == (1, 128, 128, 3)
    assert np.isfinite(np.asarray(out)).all()


def test_tile_batch_mesh_matches_single(bundle):
    img = _image()
    pos = pl.encode_text(bundle, ["p"])
    neg = pl.encode_text(bundle, [""])
    kwargs = dict(upscale_by=2.0, tile=64, padding=16, steps=2,
                  denoise=0.4, seed=7)
    single = up.run_upscale(
        bundle, img, pos, neg, mesh=None, tile_batch=1, **kwargs
    )
    # 2 chips × k=2 over the 4-tile grid: each chip runs one group of 2
    import jax

    mesh = build_mesh({"data": 2}, devices=jax.devices()[:2])
    sharded = up.run_upscale(
        bundle, img, pos, neg, mesh=mesh, tile_batch=2, **kwargs
    )
    np.testing.assert_allclose(
        np.asarray(single), np.asarray(sharded), atol=2e-2, rtol=0
    )


# --- round-2 honest knobs -------------------------------------------------

def test_area_resize_exact_box_average():
    """area = adaptive box averaging (torch F.interpolate mode='area'
    semantics), not a linear alias: integer downscale equals the plain
    block mean exactly."""
    import jax.numpy as jnp
    import numpy as np

    from comfyui_distributed_tpu.ops.upscale import area_resize

    img = jnp.arange(1 * 8 * 8 * 2, dtype=jnp.float32).reshape(1, 8, 8, 2)
    out = area_resize(img, 4, 4)
    expect = np.asarray(img).reshape(1, 4, 2, 4, 2, 2).mean(axis=(2, 4))
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-6)


def test_area_resize_fractional_factors():
    import jax.numpy as jnp
    import numpy as np

    from comfyui_distributed_tpu.ops.upscale import area_resize

    img = jnp.ones((1, 7, 5, 3))
    out = area_resize(img, 3, 2)
    assert out.shape == (1, 3, 2, 3)
    np.testing.assert_allclose(np.asarray(out), 1.0, rtol=1e-6)  # mean-preserving


def test_resize_image_routes_area():
    import jax.numpy as jnp
    import numpy as np

    from comfyui_distributed_tpu.ops.upscale import area_resize, resize_image

    img = jnp.arange(1 * 6 * 6 * 1, dtype=jnp.float32).reshape(1, 6, 6, 1)
    np.testing.assert_allclose(
        np.asarray(resize_image(img, 3, 3, "area")),
        np.asarray(area_resize(img, 3, 3)),
    )


def test_ddim_matches_euler_exactly():
    """The documented eta=0 equivalence, verified numerically."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from comfyui_distributed_tpu.ops import samplers as smp

    def model_fn(x, sigma, cond):
        return 0.1 * x + 0.01 * jnp.tanh(x)

    x = jax.random.normal(jax.random.key(0), (2, 4, 4, 3))
    sigmas = smp.get_sigmas("karras", 6)
    a = smp.sample(model_fn, x, sigmas, None, "ddim")
    b = smp.sample(model_fn, x, sigmas, None, "euler")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_nonuniform_grid_seam_positions_and_coverage():
    """force_uniform_tiles=False parity: origins stay on the plain ceil
    grid (the reference's non-uniform seam positions,
    upscale/tile_ops.py:73-78) and the coverage extends past the image
    for the overhanging edge tiles."""
    from comfyui_distributed_tpu.ops import tiles as tile_ops

    grid = tile_ops.calculate_tiles(96, 160, 64, 64, 16, uniform=False)
    assert grid.positions == (
        (0, 0), (0, 64), (0, 128), (64, 0), (64, 64), (64, 128),
    )
    assert (grid.coverage_h, grid.coverage_w) == (128, 192)
    # the uniform twin clamps instead
    uni = tile_ops.calculate_tiles(96, 160, 64, 64, 16)
    assert uni.positions[-1] == (32, 96)
    assert (uni.coverage_h, uni.coverage_w) == (96, 160)


def test_nonuniform_overhang_replicates_true_edge():
    """The coverage overhang must copy the image's real edge row/col
    (edge-extend BEFORE the reflect ring), not a reflected interior
    pixel — the overhang feeds the edge tile's diffusion context."""
    import jax.numpy as jnp
    import numpy as np

    from comfyui_distributed_tpu.ops import tiles as tile_ops

    h, w, p = 80, 80, 16
    grid = tile_ops.calculate_tiles(h, w, 64, 64, p, uniform=False)
    rng = np.random.default_rng(9)
    img = jnp.asarray(rng.uniform(size=(1, h, w, 3)), jnp.float32)
    padded = np.asarray(tile_ops.pad_image_for_grid(img, grid))
    # rows p+h .. p+coverage_h must all equal the last image row
    strip = padded[:, p + h : p + grid.coverage_h, p : p + w, :]
    np.testing.assert_array_equal(
        strip, np.broadcast_to(np.asarray(img)[:, -1:, :, :], strip.shape)
    )


def test_nonuniform_extract_blend_roundtrip():
    """Extract → blend identity on a gradient image with a non-uniform
    grid: the overhang strip is cropped and the image reconstructs."""
    import jax.numpy as jnp
    import numpy as np

    from comfyui_distributed_tpu.ops import tiles as tile_ops

    h, w = 80, 112  # not multiples of 64 → real overhang
    grid = tile_ops.calculate_tiles(h, w, 64, 64, 16, uniform=False)
    yy, xx = np.meshgrid(np.arange(h), np.arange(w), indexing="ij")
    img = jnp.asarray(
        np.stack([yy / h, xx / w, (yy + xx) / (h + w)], -1), jnp.float32
    )[None]
    tiles = tile_ops.extract_tiles(img, grid)
    out = tile_ops.blend_tiles(tiles, grid)
    assert out.shape == img.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(img), atol=1e-5)


def test_nonuniform_incremental_canvas_matches_batch_blend():
    import jax.numpy as jnp
    import numpy as np

    from comfyui_distributed_tpu.ops import tiles as tile_ops

    h, w = 80, 112
    grid = tile_ops.calculate_tiles(h, w, 64, 64, 16, uniform=False)
    rng = np.random.default_rng(5)
    img = jnp.asarray(rng.uniform(size=(1, h, w, 3)), jnp.float32)
    tiles = tile_ops.extract_tiles(img, grid)
    inc = tile_ops.IncrementalCanvas(jnp.zeros_like(img), grid)
    for i, (y, x) in enumerate(grid.positions):
        inc.blend(tiles[i], y, x)
    np.testing.assert_allclose(
        np.asarray(inc.result()), np.asarray(img), atol=1e-4
    )


def test_mask_blur_narrows_feather():
    """mask_blur controls the feather-ramp width (reference USDU
    mask_blur): a narrower ramp leaves more of the padding ring at
    full weight."""
    import numpy as np

    from comfyui_distributed_tpu.ops import tiles as tile_ops

    wide = tile_ops.calculate_tiles(128, 128, 64, 64, 16)
    narrow = tile_ops.calculate_tiles(128, 128, 64, 64, 16, mask_blur=4)
    assert wide.feather == 16 and narrow.feather == 4
    m_wide = np.asarray(tile_ops.feather_mask(wide))
    m_narrow = np.asarray(tile_ops.feather_mask(narrow))
    # at 8px inside the ring: wide ramp still rising, narrow already 1
    assert m_narrow[8, 48] == 1.0
    assert m_wide[8, 48] < 1.0
    # mask_blur larger than padding clamps
    clamped = tile_ops.calculate_tiles(128, 128, 64, 64, 16, mask_blur=99)
    assert clamped.feather == 16


def test_tiled_decode_runs_and_matches_plain():
    """tiled_decode routes tile decoding through the tiled VAE; for
    tile latents smaller than the VAE tile size it must be exactly the
    plain decode."""
    import jax.numpy as jnp
    import numpy as np

    from comfyui_distributed_tpu.models import pipeline as pl
    from comfyui_distributed_tpu.ops import upscale as up

    bundle = pl.load_pipeline("tiny-unet", seed=0)
    img = jnp.linspace(0, 1, 64 * 64 * 3).reshape(1, 64, 64, 3).astype(jnp.float32)
    pos = pl.encode_text(bundle, ["x"])
    neg = pl.encode_text(bundle, [""])
    kwargs = dict(upscale_by=2.0, tile=64, padding=16, steps=1,
                  denoise=0.3, seed=5)
    plain = up.run_upscale(bundle, img, pos, neg, **kwargs)
    tiled = up.run_upscale(bundle, img, pos, neg, tiled_decode=True, **kwargs)
    np.testing.assert_allclose(
        np.asarray(plain), np.asarray(tiled), atol=1e-5
    )
