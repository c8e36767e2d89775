"""Flash-attention kernel numerics vs the reference implementation
(Pallas interpret mode on CPU)."""

import chip_smoke
import jax
import jax.numpy as jnp
import numpy as np
import pytest

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


# --- bfloat16 operands at small versions of every served case --------------

# (label, [B, N, H, D] of q, keys M, the (block_q, block_k) it must take):
# the blocks are named so that a case keeps exercising what its label says
# (several q blocks, several k steps of the online softmax, one block)
# when the caps move.
BF16_CASES = [
    ("square, two q blocks", (2, 1024, 2, 128), 1024, (512, 1024)),
    ("n != m, two k steps", (1, 512, 2, 128), 3072, (512, 1536)),
    ("odd multiple 384, one block", (1, 384, 2, 128), 384, (384, 384)),
    ("odd multiple 1152, three q blocks", (1, 1152, 1, 128), 1152, (384, 1152)),
    ("a single block of 256", (2, 256, 2, 128), 256, (256, 256)),
    ("d=40 padded, two k steps", (2, 256, 2, 40), 2048, (256, 1024)),
    ("d=80 padded", (2, 256, 2, 80), 256, (256, 256)),
    ("d=160 padded to 256", (2, 256, 2, 160), 256, (256, 256)),
    ("d=512 at one head, 2 x 2 blocks", (1, 1024, 1, 512), 1024, (512, 512)),
]


@pytest.mark.parametrize(
    "q_shape,m,blocks", [c[1:] for c in BF16_CASES], ids=[c[0] for c in BF16_CASES]
)
def test_flash_bf16_operands_match_f32_reference(q_shape, m, blocks):
    b, n, h, d = q_shape
    assert attn.flash_plan(n, m, d + -d % 128, 2) == (n, m, *blocks)
    kq, kk, kv = jax.random.split(jax.random.key(n * 131 + m * 7 + d), 3)
    q = (2.0 * jax.random.normal(kq, q_shape)).astype(jnp.bfloat16)
    k = jax.random.normal(kk, (b, m, h, d)).astype(jnp.bfloat16)
    v = jax.random.normal(kv, (b, m, h, d)).astype(jnp.bfloat16)
    out = attn.dot_product_attention(q, k, v, force_flash=True, interpret=True)
    assert out.dtype == jnp.bfloat16 and out.shape == q_shape
    with jax.default_matmul_precision("highest"):
        ref = jax.nn.dot_product_attention(
            q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32)
        )
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
    scale = max(1.0, float(jnp.max(jnp.abs(ref))))
    assert err <= chip_smoke.ATTENTION_TOLERANCE * scale, (err, scale)


def test_flash_plan_divides_aligns_and_fits():
    shapes = [
        (q_shape[1], m, q_shape[3]) for _, q_shape, m in chip_smoke.SERVED_SHAPES
    ]
    assert (4608, 4608, 128) in shapes  # FLUX's joint attention
    assert (1296, 1296, 64) in shapes and (324, 77, 64) in shapes  # SDXL's tile
    # plus lengths with awkward divisors and a video-length key sequence
    shapes += [(128 * 37, 128 * 37, 64), (256, 128 * 257, 128), (128, 32768, 128)]
    # and other tile sizes' lengths, one row, one key
    shapes += [(10816, 10816, 64), (2704, 2704, 64), (676, 77, 64), (1, 1, 128), (257, 257, 64)]
    for n, m, d in shapes:
        padded = d + -d % 128
        for itemsize in (2, 4):
            n_pad, m_pad, block_q, block_k = attn.flash_plan(n, m, padded, itemsize)
            assert n_pad % block_q == 0 and m_pad % block_k == 0
            assert block_q % attn.ROW_MULTIPLE == 0 and block_k % 128 == 0
            assert block_q <= attn.MAX_BLOCK_Q and block_k <= attn.MAX_BLOCK_K
            assert (
                attn.flash_vmem_bytes(block_q, block_k, padded, itemsize)
                <= attn.VMEM_BUDGET
            )
            # a multiple of 128 is never padded; any other length by less
            # than one step a block, so the last k block always holds a key
            assert n_pad == n if n % 128 == 0 else 0 <= n_pad - n < 16 * (n_pad // block_q)
            assert m_pad == m if m % 128 == 0 else 0 < m_pad - m < 128 * (m_pad // block_k)
            assert 0 < m - (m_pad - block_k) <= block_k
    # what the sweeps chose for the shapes that carry the benchmark
    assert attn.flash_plan(4608, 4608, 128, 2) == (4608, 4608, 512, 1536)
    assert attn.flash_plan(4096, 4096, 128, 2) == (4096, 4096, 512, 1024)
    assert attn.flash_plan(1296, 1296, 128, 2) == (1296, 1408, 432, 1408)
    assert attn.flash_plan(5184, 5184, 512, 2) == (5280, 5376, 480, 896)


@pytest.mark.parametrize("n,m", [(0, 128), (128, 0)])
def test_flash_refuses_an_empty_axis(n, m):
    with pytest.raises(ValueError, match="needs queries and keys"):
        attn.flash_plan(n, m, 128, 2)
    q = jnp.ones((1, n, 1, 128), jnp.bfloat16)
    k = jnp.ones((1, m, 1, 128), jnp.bfloat16)
    with pytest.raises(ValueError, match="needs queries and keys"):
        attn.flash_attention(q, k, k, interpret=True)
