"""Flash-attention kernel numerics vs the reference implementation
(Pallas interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np

from comfyui_distributed_tpu.ops import attention as attn


def _ref_attention(q, k, v):
    return jax.nn.dot_product_attention(q, k, v)


def test_flash_matches_reference_f32():
    key = jax.random.key(0)
    kq, kk, kv = jax.random.split(key, 3)
    shape = (2, 256, 2, 128)  # [B, N, H, D] aligned to blocks
    q = jax.random.normal(kq, shape, jnp.float32)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)
    out = attn.flash_attention(q, k, v, interpret=True)
    ref = _ref_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_cross_attention_lengths():
    key = jax.random.key(1)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (1, 128, 2, 128), jnp.float32)
    k = jax.random.normal(kk, (1, 384, 2, 128), jnp.float32)
    v = jax.random.normal(kv, (1, 384, 2, 128), jnp.float32)
    out = attn.flash_attention(q, k, v, interpret=True)
    ref = _ref_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_dispatch_falls_back_off_tpu():
    # On CPU the router must not pick the compiled flash path.
    q = jnp.ones((1, 64, 2, 32))
    out = attn.dot_product_attention(q, q, q)
    assert out.shape == q.shape


def test_flash_pads_unaligned_head_dim():
    """SD head dims (40/64/80) aren't 128-lane aligned; the padded
    flash path must match reference attention exactly."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from comfyui_distributed_tpu.ops.attention import dot_product_attention

    for d in (40, 64, 80):
        q = jax.random.normal(jax.random.key(0), (1, 128, 2, d))
        k = jax.random.normal(jax.random.key(1), (1, 128, 2, d))
        v = jax.random.normal(jax.random.key(2), (1, 128, 2, d))
        flash = dot_product_attention(
            q, k, v, force_flash=True, interpret=True
        )
        ref = jax.nn.dot_product_attention(q, k, v)
        np.testing.assert_allclose(
            np.asarray(flash), np.asarray(ref), atol=2e-5
        )
