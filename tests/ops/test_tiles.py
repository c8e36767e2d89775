"""Tile grid math: extraction/blending invariants the distributed
upscaler depends on (identity round-trip, order independence)."""

import jax.numpy as jnp
import numpy as np

from comfyui_distributed_tpu.ops import tiles


def test_grid_covers_image():
    grid = tiles.calculate_tiles(300, 500, 128, 128, padding=16)
    assert grid.rows == 3 and grid.cols == 4
    covered = np.zeros((300, 500), dtype=bool)
    for y, x in grid.positions:
        assert 0 <= y <= 300 - 128 and 0 <= x <= 500 - 128
        covered[y : y + 128, x : x + 128] = True
    assert covered.all()


def test_grid_small_image_single_tile():
    grid = tiles.calculate_tiles(64, 64, 128, 128, padding=8)
    assert grid.num_tiles == 1
    assert grid.tile_h == 64 and grid.tile_w == 64


def test_extract_shapes():
    grid = tiles.calculate_tiles(100, 140, 64, 64, padding=8)
    imgs = jnp.zeros((2, 100, 140, 3))
    out = tiles.extract_tiles(imgs, grid)
    assert out.shape == (grid.num_tiles, 2, 64 + 16, 64 + 16, 3)


def test_blend_identity_roundtrip():
    """Extract then blend unprocessed tiles ⇒ the original image."""
    rng = np.random.default_rng(0)
    img = jnp.asarray(rng.random((1, 96, 160, 3)), dtype=jnp.float32)
    grid = tiles.calculate_tiles(96, 160, 64, 64, padding=16)
    extracted = tiles.extract_tiles(img, grid)
    blended = tiles.blend_tiles(extracted, grid)
    np.testing.assert_allclose(np.asarray(blended), np.asarray(img), atol=1e-5)


def test_blend_order_independent():
    rng = np.random.default_rng(1)
    img = jnp.asarray(rng.random((1, 96, 96, 3)), dtype=jnp.float32)
    grid = tiles.calculate_tiles(96, 96, 64, 64, padding=8)
    extracted = tiles.extract_tiles(img, grid)
    perm = np.random.default_rng(2).permutation(grid.num_tiles)
    # Permuting tiles requires permuting positions consistently — emulate
    # by blending a permuted grid.
    permuted_grid = tiles.TileGrid(
        image_h=grid.image_h,
        image_w=grid.image_w,
        tile_h=grid.tile_h,
        tile_w=grid.tile_w,
        padding=grid.padding,
        rows=grid.rows,
        cols=grid.cols,
        positions=tuple(grid.positions[i] for i in perm),
    )
    a = tiles.blend_tiles(extracted, grid)
    b = tiles.blend_tiles(extracted[perm], permuted_grid)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_blend_single_tile_composites_core():
    grid = tiles.calculate_tiles(64, 64, 64, 64, padding=8)
    canvas = jnp.zeros((1, 64, 64, 3))
    tile = jnp.ones((1, grid.padded_h, grid.padded_w, 3))
    out = tiles.blend_single_tile(canvas, tile, 0, 0, grid)
    # Tile core (away from feather ring) fully replaces the canvas.
    core = np.asarray(out)[0, 16:48, 16:48, :]
    np.testing.assert_allclose(core, 1.0, atol=1e-6)


def test_upscale_nearest():
    img = jnp.arange(4.0).reshape(1, 2, 2, 1)
    up = tiles.upscale_nearest(img, 2)
    assert up.shape == (1, 4, 4, 1)
    assert float(up[0, 0, 0, 0]) == 0.0 and float(up[0, 3, 3, 0]) == 3.0


def test_tiled_vae_decode_shapes_and_rough_stats():
    """Tiled decode matches full-decode shape; statistics stay in the
    same regime (exact equality is impossible: GroupNorm stats are
    per-tile — the inherent tiled-VAE approximation). The small-input
    fast path must be exact."""
    from comfyui_distributed_tpu.models import pipeline as pl
    from comfyui_distributed_tpu.ops.tiled_vae import decode_tiled, encode_tiled

    bundle = pl.load_pipeline("tiny-unet", seed=0)
    z = jnp.asarray(np.random.default_rng(5).random((1, 24, 24, 4)), jnp.float32)
    full = bundle.vae.apply(bundle.params["vae"], z, method="decode")
    tiled = decode_tiled(pl._Static(bundle), bundle.params["vae"], z,
                         tile=16, overlap=4)
    assert tiled.shape == full.shape
    assert np.isfinite(np.asarray(tiled)).all()
    assert abs(float(tiled.mean()) - float(full.mean())) < 0.2

    # small input takes the exact single-pass fast path
    z_small = z[:, :12, :12, :]
    exact = decode_tiled(pl._Static(bundle), bundle.params["vae"], z_small,
                         tile=16, overlap=4)
    ref = bundle.vae.apply(bundle.params["vae"], z_small, method="decode")
    np.testing.assert_allclose(np.asarray(exact), np.asarray(ref), atol=2e-2)  # jit vs eager bf16 fusion tolerance

    px = jnp.asarray(np.random.default_rng(6).random((1, 96, 96, 3)), jnp.float32)
    enc_full = bundle.vae.apply(bundle.params["vae"], px, method="encode")
    enc_tiled = encode_tiled(pl._Static(bundle), bundle.params["vae"], px,
                             tile=64, overlap=16)
    assert enc_tiled.shape == enc_full.shape


def test_upscale_model_random_init_is_bilinear():
    from comfyui_distributed_tpu.models.upscaler import load_upscale_model
    import jax

    model = load_upscale_model("2x-test")
    assert model.scale == 2
    img = jnp.asarray(np.random.default_rng(7).random((1, 16, 16, 3)), jnp.float32)
    out = model.upscale(img)
    assert out.shape == (1, 32, 32, 3)
    ref = jnp.clip(jax.image.resize(img, (1, 32, 32, 3), method="linear"), 0, 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_host_canvas_matches_jax_canvas():
    """The native/host blend path must be math-identical to the jax
    IncrementalCanvas (the elastic tier swaps between them)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from comfyui_distributed_tpu.ops import tiles as tile_ops

    grid = tile_ops.calculate_tiles(96, 96, 48, 8)
    base = jax.random.uniform(jax.random.key(0), (1, 96, 96, 3))
    jc = tile_ops.IncrementalCanvas(base, grid)
    hc = tile_ops.HostIncrementalCanvas(base, grid)
    for idx, (y, x) in enumerate(grid.positions):
        tile = jax.random.uniform(
            jax.random.key(idx + 1), (1, grid.padded_h, grid.padded_w, 3)
        )
        jc.blend(tile, y, x)
        hc.blend(tile, y, x)
    np.testing.assert_allclose(
        np.asarray(jc.result()), np.asarray(hc.result()), atol=1e-6
    )
