"""The flash-attention kernel at lengths off the 128 multiple (Pallas
interpret mode on the CPU): padded inside the call, the padded keys
masked in the kernel; an aligned call keeps the program it had; and the
shape rule that sends a call to the kernel or leaves it to XLA."""

import functools

import chip_smoke
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.ops import attention as attn

# (n, m, d as padded, itemsize) -> the blocks the parent of PR 33 took
# (`flash_blocks` at 08263b6): a call whose lengths are multiples of 128
# keeps them and is padded nowhere.
ALIGNED_BLOCKS = [
    ((4608, 4608, 128, 2), (512, 1536)),
    ((4096, 4096, 128, 2), (512, 1024)),
    ((1024, 1024, 128, 2), (512, 1024)),
    ((256, 256, 256, 2), (256, 256)),
    ((4096, 4096, 512, 2), (512, 512)),
    ((16384, 16384, 512, 2), (512, 512)),
    ((4608, 4608, 128, 4), (512, 1152)),
    ((16384, 16384, 512, 4), (512, 256)),
    ((128 * 37, 128 * 37, 128, 2), (128, 128)),
    ((256, 128 * 257, 128, 2), (256, 128)),
    ((128, 32768, 128, 2), (128, 1024)),
    ((1152, 1152, 128, 2), (384, 1152)),
]


@pytest.mark.parametrize("shape,blocks", ALIGNED_BLOCKS, ids=[str(c[0]) for c in ALIGNED_BLOCKS])
def test_an_aligned_call_keeps_the_blocks_it_took(shape, blocks):
    n, m = shape[:2]
    assert attn.flash_plan(*shape) == (n, m, *blocks)


def _primitives(jaxpr, kernels: bool = True) -> set[str]:
    """Names of every primitive in a jaxpr, kernels' bodies included
    unless `kernels` is false."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        if eqn.primitive.name == "pallas_call" and not kernels:
            continue
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                names |= _primitives(inner, kernels)
    return names


def test_an_aligned_call_traces_no_mask_no_pad_and_no_slice():
    aligned = jax.ShapeDtypeStruct((1, 256, 2, 128), jnp.bfloat16)
    ragged = jax.ShapeDtypeStruct((1, 200, 2, 128), jnp.bfloat16)
    flash = functools.partial(attn.dot_product_attention, force_flash=True, interpret=True)
    mask = {"iota", "select_n", "pad"}
    traced = jax.make_jaxpr(flash)(aligned, aligned, aligned).jaxpr
    plain = _primitives(traced)
    assert "pallas_call" in plain and not plain & mask, plain & mask
    # the kernel's body slices its scores into lane tiles (PR 51); around it nothing is sliced
    assert "slice" not in _primitives(traced, kernels=False)
    # the same walk does find them where keys are padded (one k step: no cond)
    masked = _primitives(jax.make_jaxpr(flash)(ragged, ragged, ragged).jaxpr)
    assert {"pallas_call", "iota", "select_n", "pad", "slice"} <= masked
    # padded q rows alone need no mask
    rows = _primitives(jax.make_jaxpr(flash)(ragged, aligned, aligned).jaxpr)
    assert "pad" in rows and not rows & {"iota", "select_n"}


# (label, [B, N, H, D] of q, keys M, the (padded n, padded m, block_q,
# block_k) it must take): SDXL's tile lengths themselves at one head, a
# length one short of and one past a multiple of 128, a padded axis
# beside one that is not, and a masked tail after an unmasked k step.
RAGGED_CASES = [
    ("sdxl self 1,296: three q blocks, no padded row", (1, 1296, 1, 64), 1296, (1296, 1408, 432, 1408)),
    ("sdxl self 324", (2, 324, 2, 64), 324, (336, 384, 336, 384)),
    ("sdxl cross 1,296 x 77", (1, 1296, 1, 64), 77, (1296, 128, 432, 128)),
    ("sdxl cross 324 x 77", (2, 324, 2, 64), 77, (336, 128, 336, 128)),
    ("one short of 128", (1, 127, 2, 128), 127, (128, 128, 128, 128)),
    ("one past 128", (1, 129, 2, 128), 129, (144, 256, 144, 256)),
    ("one past 256, clip vision's 257", (1, 257, 2, 64), 257, (272, 384, 272, 384)),
    ("ragged rows over aligned keys", (1, 100, 2, 64), 256, (112, 256, 112, 256)),
    ("aligned rows over ragged keys, d=40", (2, 256, 2, 40), 200, (256, 256, 256, 256)),
    ("two k steps, the tail masked, d=512", (1, 300, 1, 512), 1700, (304, 1792, 304, 896)),
    ("two q blocks, two k steps", (1, 600, 1, 128), 1600, (608, 1792, 304, 896)),
    # the k step's own state (PR 51): q blocks of 432 rows go as row chunks of 256 and 176, a
    # step's scores are several lane tiles, and the running sum is a partial a lane until the end
    ("1,296 rows as 3 x 432 in two row chunks, five k steps of five lane tiles",
     (1, 1296, 2, 64), 3200, (1296, 3200, 432, 640)),
    ("1,296 rows, two k steps of twelve lane tiles, 72 padded keys in the last",
     (1, 1296, 1, 64), 3000, (1296, 3072, 432, 1536)),
    ("one k step over a block of 128 keys", (1, 384, 2, 128), 128, (384, 128, 384, 128)),
]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize(
    "q_shape,m,plan", [c[1:] for c in RAGGED_CASES], ids=[c[0] for c in RAGGED_CASES]
)
def test_flash_masks_a_ragged_tail(q_shape, m, plan, dtype):
    b, n, h, d = q_shape
    if dtype == jnp.bfloat16:  # the plan named is the served dtype's
        assert attn.flash_plan(n, m, d + -d % 128, 2) == plan
    kq, kk, kv = jax.random.split(jax.random.key(n * 131 + m * 7 + d), 3)
    q = (2.0 * jax.random.normal(kq, q_shape)).astype(dtype)
    k = jax.random.normal(kk, (b, m, h, d)).astype(dtype)
    v = jax.random.normal(kv, (b, m, h, d)).astype(dtype)
    out = attn.dot_product_attention(q, k, v, force_flash=True, interpret=True)
    assert out.dtype == dtype and out.shape == q_shape
    with jax.default_matmul_precision("highest"):
        ref = jax.nn.dot_product_attention(
            q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32)
        )
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
    scale = max(1.0, float(jnp.max(jnp.abs(ref))))
    limit = chip_smoke.ATTENTION_TOLERANCE * scale if dtype == jnp.bfloat16 else 2e-5
    assert err <= limit, (err, scale)


@pytest.mark.parametrize("rows,m", [(64, 77), (64, 200), (64, 1700), (432, 1296), (304, 3000)], ids=[
    "one k step", "one k step, 56 padded", "two k steps",
    "1,296 keys as 1,408: eleven lane tiles, two row chunks", "two k steps of twelve lane tiles"])
def test_padded_keys_weigh_nothing_where_they_would_win_the_softmax(rows, m):
    """Every true score is far below 0, the score of a zero-padded key:
    unmasked, the padding would take the whole softmax and the output
    would be its zero values, not the mean of v. A padded key's `p` is
    an exact zero in its lane's partial sum too."""
    d = 128
    q = jnp.full((1, rows, 1, d), 4.0, jnp.float32)
    k = jnp.full((1, m, 1, d), -4.0, jnp.float32)
    v = jax.random.normal(jax.random.key(m), (1, m, 1, d)) + 3.0
    out = attn.flash_attention(q, k, v, interpret=True)
    want = jnp.broadcast_to(v.mean(axis=1, keepdims=True), out.shape)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5)


# (n, m) -> whether a TPU sends it to the kernel
ROUTES = [
    ((4096, 4096), True), ((256, 256), True), ((4608, 4608), True),  # aligned: as since PR 28
    ((1296, 1296), True), ((5184, 5184), True), ((324, 1024), True), ((100, 512), True),
    ((324, 324), False), ((1296, 77), False), ((324, 77), False), ((4096, 77), False),
    ((64, 64), False), ((257, 257), False), ((0, 128), False), ((128, 0), False),
]


@pytest.mark.parametrize("lengths,wins", ROUTES, ids=[f"{n}x{m}" for (n, m), _ in ROUTES])
def test_route_is_a_function_of_the_lengths(lengths, wins, monkeypatch):
    n, m = lengths
    assert attn.kernel_wins(n, m) is wins
    q = jax.ShapeDtypeStruct((1, n, 1, 64), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, m, 1, 64), jnp.bfloat16)
    assert attn.attention_route(q, k) == "xla"  # off a TPU, as ever
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert attn.attention_route(q, k) == ("flash" if wins else "xla")
    monkeypatch.setenv("CDT_FLASH", "0")  # the kill switch
    assert attn.attention_route(q, k) == "xla"
