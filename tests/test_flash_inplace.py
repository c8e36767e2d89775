"""The flash-attention kernel reads and writes heads where the caller
left them (Pallas interpret mode on the CPU): q, k, v and the output are
[B, N, H*D] to the kernel, the head a block index, so an aligned call
moves nothing in memory around it; no head is taken for another; and the
result is bit for bit what the layout before PR 35 gave."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke
from comfyui_distributed_tpu.ops import attention as attn
from test_flash_ragged import _primitives

flash = functools.partial(attn.dot_product_attention, force_flash=True, interpret=True)


# the kernel as it was called until PR 35, the reference of this file
folded = chip_smoke.transposed(flash)


def _outside_the_kernel(jaxpr):
    """Every equation of a jaxpr that holds no jaxpr of its own, and
    each `pallas_call` whole."""
    for eqn in jaxpr.eqns:
        inner = [getattr(p, "jaxpr", p) for p in eqn.params.values()]
        inner = [j for j in inner if hasattr(j, "eqns")]
        if eqn.primitive.name == "pallas_call" or not inner:
            yield eqn
        else:
            for j in inner:
                yield from _outside_the_kernel(j)


def operands(b, n, m, h, d, dtype=jnp.bfloat16):
    """q, k, v whose heads differ: head i of each is scaled by i + 1, so
    a head read or written in another's place changes the result."""
    kq, kk, kv = jax.random.split(jax.random.key(n * 131 + m * 7 + h * 3 + d), 3)
    scale = (1.0 + jnp.arange(h, dtype=jnp.float32))[:, None]
    q = (jax.random.normal(kq, (b, n, h, d)) * scale / h).astype(dtype)
    k = (jax.random.normal(kk, (b, m, h, d)) * scale / h).astype(dtype)
    v = (jax.random.normal(kv, (b, m, h, d)) * scale).astype(dtype)
    return q, k, v


def assert_close_to_float32(out, q, k, v):
    with jax.default_matmul_precision("highest"):
        ref = jax.nn.dot_product_attention(
            q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32)
        )
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
    scale = max(1.0, float(jnp.max(jnp.abs(ref))))
    assert err <= chip_smoke.ATTENTION_TOLERANCE * scale, (err, scale)
    # every head against its own reference: one head's error may not hide
    # in another's scale
    per_head = jnp.max(jnp.abs(out.astype(jnp.float32) - ref), axis=(0, 1, 3))
    head_scale = jnp.maximum(1.0, jnp.max(jnp.abs(ref), axis=(0, 1, 3)))
    assert bool(jnp.all(per_head <= chip_smoke.ATTENTION_TOLERANCE * head_scale)), per_head


@pytest.mark.parametrize("d", [128, 256, 512])
@pytest.mark.parametrize("h", [1, 3, 8, 24])
def test_heads_are_read_and_written_in_place(h, d):
    q, k, v = operands(2, 256, 384, h, d)
    out = flash(q, k, v)
    assert out.shape == q.shape and out.dtype == q.dtype
    assert_close_to_float32(out, q, k, v)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(folded(q, k, v)))


# (label, [B, N, H, D] of q, keys M): the widths `dot_product_attention`
# pads to the lane width (SD1.5's 40 / 80 / 160, SDXL's 64), and SDXL's
# tile length, which is padded and masked inside the kernel
PADDED_CASES = [
    ("sd15 40-wide", (2, 256, 8, 40), 256),
    ("sdxl 64-wide", (2, 256, 5, 64), 128),
    ("sd15 80-wide", (2, 128, 8, 80), 128),
    ("sd15 160-wide", (2, 128, 3, 160), 256),
    ("sdxl tile self 1,296 at 64-wide", (1, 1296, 2, 64), 1296),
    ("ragged rows and keys at 40-wide", (2, 200, 3, 40), 600),
]


@pytest.mark.parametrize(
    "q_shape,m", [c[1:] for c in PADDED_CASES], ids=[c[0] for c in PADDED_CASES]
)
def test_padded_widths_and_ragged_lengths_keep_their_heads(q_shape, m):
    b, n, h, d = q_shape
    q, k, v = operands(b, n, m, h, d)
    out = flash(q, k, v)
    assert out.shape == q_shape and out.dtype == q.dtype
    assert_close_to_float32(out, q, k, v)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(folded(q, k, v)))


# (label, [B, N, H, D] of q, keys M, k steps): calls whose k step walks its q
# block in row chunks over several lane tiles of scores, the running max
# lane-replicated and the running sum a partial a lane (PR 51); a call of
# one step sets up, updates and divides its state in that one step
STEP_CASES = [
    ("two q blocks of two row chunks, two k steps of twelve lane tiles", (1, 1024, 3, 128), 3072, 2),
    ("a 256-wide accumulator takes the correction tiled", (1, 512, 2, 256), 2560, 2),
    ("37 k steps of one lane tile", (2, 128, 3, 128), 128 * 37, 37),
    ("one k step over a block of 128 keys", (2, 384, 3, 128), 128, 1),
    ("one k step, two row chunks", (1, 512, 2, 128), 1024, 1),
]


@pytest.mark.parametrize(
    "q_shape,m,steps", [c[1:] for c in STEP_CASES], ids=[c[0] for c in STEP_CASES])
def test_a_k_step_in_row_chunks_and_lane_tiles_keeps_every_head_its_own_sum(q_shape, m, steps):
    b, n, h, d = q_shape
    _, _, block_q, block_k = attn.flash_plan(n, m, d, 2)
    assert m // block_k == steps
    q, k, v = operands(b, n, m, h, d)
    out = flash(q, k, v)
    assert out.shape == q_shape and not bool(jnp.any(jnp.isnan(out.astype(jnp.float32))))
    assert_close_to_float32(out, q, k, v)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(folded(q, k, v)))


@pytest.mark.parametrize(
    "q_shape,m",
    [((2, 256, 3, 128), 256), ((2, 200, 3, 64), 200), ((1, 128, 1, 512), 256)],
    ids=["aligned", "ragged at 64-wide", "one 512-wide head"],
)
def test_the_call_under_vmap_over_a_tile_axis(q_shape, m):
    """The scan tier calls the kernel under `jax.vmap` over its tiles
    (`ops/upscale._scan_tiles`): the batching rule puts the tile axis in
    front of the operands and of the index maps, and every tile has to
    come out as it does alone."""
    tiles = 3
    b, n, h, d = q_shape
    q, k, v = operands(tiles * b, n, m, h, d)
    q, k, v = (x.reshape(tiles, b, *x.shape[1:]) for x in (q, k, v))
    out = jax.vmap(flash)(q, k, v)
    assert out.shape == q.shape
    for t in range(tiles):
        np.testing.assert_array_equal(np.asarray(out[t]), np.asarray(flash(q[t], k[t], v[t])))


@pytest.mark.parametrize("h,d", [(2, 128), (8, 128), (24, 128), (3, 256), (1, 512)])
def test_an_aligned_call_moves_nothing_around_the_kernel(h, d):
    """No transposition, pad, slice or copy: the reshapes on either side
    of the kernel merge or split the two minor axes only."""
    operand = jax.ShapeDtypeStruct((2, 256, h, d), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(flash)(operand, operand, operand).jaxpr
    found = _primitives(jaxpr)
    assert "pallas_call" in found and not found & {"transpose", "pad", "copy", "gather"}, found
    # the kernel's body cuts its scores into lane tiles (PR 51); around it nothing is cut
    assert not _primitives(jaxpr, kernels=False) & {"slice", "concatenate"}

    eqns = list(_outside_the_kernel(jaxpr))
    assert [e.primitive.name for e in eqns if e.primitive.name != "reshape"] == ["pallas_call"]
    for eqn in eqns:
        if eqn.primitive.name == "reshape":
            shapes = {eqn.invars[0].aval.shape, eqn.outvars[0].aval.shape}
            assert shapes == {(2, 256, h, d), (2, 256, h * d)}, shapes
    (call,) = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert [v.aval.shape for v in call.invars] == [(2, 256, h * d)] * 3
    assert [v.aval.shape for v in call.outvars] == [(2, 256, h * d)]


def test_route_log_says_which_calls_took_the_kernel_in_place():
    q = jax.ShapeDtypeStruct((1, 256, 4, 128), jnp.bfloat16)
    narrow = jax.ShapeDtypeStruct((2, 256, 8, 40), jnp.bfloat16)
    text = jax.ShapeDtypeStruct((2, 77, 8, 40), jnp.bfloat16)
    force = functools.partial(attn.dot_product_attention, force_flash=True)
    with attn.route_log() as routes:
        jax.eval_shape(force, q, q, q)
        jax.eval_shape(force, narrow, narrow, narrow)
        jax.eval_shape(attn.dot_product_attention, narrow, text, text)
        jax.eval_shape(functools.partial(attn.dot_product_attention, causal=True), q, q, q)
    assert routes == [
        "flash 256x256x128 bq256 bk256 bf16 inplace",
        "flash 256x256x40 bq256 bk256 bf16",  # padded, so folded: not in place
        "xla 256x77x40",
        "xla-causal 256x256x128/128 bq256 bf16",
    ]


def test_a_narrow_head_is_folded_into_the_batch_by_the_copy_that_pads_it():
    """A width off the lane tile is rewritten by its pad anyway; the
    kernel then takes [B*H, N, 128], as until PR 35 (on the chip the
    pad's copy does both in one pass: PERF.md §6)."""
    operand = jax.ShapeDtypeStruct((2, 256, 8, 40), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(flash)(operand, operand, operand).jaxpr
    assert {"pad", "transpose", "pallas_call", "slice"} <= _primitives(jaxpr)

    (call,) = [e for e in _outside_the_kernel(jaxpr) if e.primitive.name == "pallas_call"]
    assert [v.aval.shape for v in call.invars] == [(16, 256, 128)] * 3
