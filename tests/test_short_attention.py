"""`ops/short_attention.py` (Pallas interpret mode on the CPU): whole-key
attention at 64-wide heads, several heads a grid step, read and written
where the caller left them. Against `jax.nn.dot_product_attention` in
float32 at toy sizes of SDXL's four shapes' structure; what a block
holds past its array's end (the interpreter hands NaN) weighs nothing;
no head is touched by its lane-tile neighbour's operands; and the shape
rule that gives the kernel the calls it won on the chip."""

import functools

import chip_smoke
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.ops import attention as attn
from comfyui_distributed_tpu.ops import short_attention as sa
from test_flash_ragged import _primitives

short = functools.partial(sa.short_attention, interpret=True)


def operands(b, n, m, h, dtype):
    kq, kk, kv = jax.random.split(jax.random.key(n * 131 + m * 7 + h), 3)
    q = (2.0 * jax.random.normal(kq, (b, n, h, sa.WIDTH))).astype(dtype)
    k = jax.random.normal(kk, (b, m, h, sa.WIDTH)).astype(dtype)
    v = jax.random.normal(kv, (b, m, h, sa.WIDTH)).astype(dtype)
    return q, k, v


def in_float32(q, k, v):
    with jax.default_matmul_precision("highest"):
        return jax.nn.dot_product_attention(
            q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32))


# (label, batch, rows, keys, heads, lane tiles a step or None for the plan's, the plan):
# rows and keys off the lane tile, rows in several q blocks, one / two / five lane tiles
CASES = [
    ("self 81 x 81, one lane tile", 2, 81, 81, 2, None, (96, 128, 1)),
    ("self 81 x 81, two lane tiles a step", 2, 81, 81, 4, None, (96, 128, 2)),
    ("self 81 x 81, five lane tiles a step", 1, 81, 81, 10, None, (96, 128, 5)),
    ("self 81 x 81, five lane tiles, one a step", 1, 81, 81, 10, 1, (96, 128, 5)),
    ("cross 81 x 77", 2, 81, 77, 4, None, (96, 128, 2)),
    ("cross 600 x 77: two q blocks, the last past the rows' end", 1, 600, 77, 2, None,
     (304, 128, 1)),
    ("self 600 x 600: two q blocks in two row chunks, five lane tiles of keys", 1, 600, 600, 4,
     None, (304, 640, 2)),
    ("aligned 128 x 256: nothing past an array's end", 2, 128, 256, 2, None, (128, 256, 1)),
]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("b,n,m,h,tiles,plan", [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_the_kernel_against_float32(b, n, m, h, tiles, plan, dtype):
    assert sa.plan(n, m, h, 2) == plan
    q, k, v = operands(b, n, m, h, dtype)
    out = short(q, k, v, tiles=tiles)
    assert out.dtype == dtype and out.shape == q.shape
    ref = in_float32(q, k, v)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
    scale = max(1.0, float(jnp.max(jnp.abs(ref))))
    limit = chip_smoke.ATTENTION_TOLERANCE * scale if dtype == jnp.bfloat16 else 2e-5
    assert err <= limit, (err, scale)


@pytest.mark.parametrize("b,n,m,h", [(2, 81, 81, 4), (1, 81, 77, 2), (1, 600, 600, 2)],
                         ids=["81x81", "81x77", "600x600"])
def test_the_arithmetic_is_flash_attention_s_at_one_k_step(b, n, m, h):
    """float32 scores, one max, one `exp`, `p` rounded to v's dtype,
    float32 accumulation: bfloat16 results agree with the streaming
    kernel's to a rounding of the output, where only the order of the
    float32 sums differs."""
    q, k, v = operands(b, n, m, h, jnp.bfloat16)
    ours = np.asarray(short(q, k, v), np.float32)
    theirs = np.asarray(attn.flash_attention(q, k, v, interpret=True), np.float32)
    assert np.abs(ours - theirs).max() <= 2 ** -7 * max(1.0, np.abs(theirs).max())
    assert (ours == theirs).mean() > 0.97


@pytest.mark.parametrize("rows,m,h", [(64, 77, 2), (81, 200, 4), (304, 324, 2)],
                         ids=["77 keys", "200 keys, 56 padded", "324 keys, 60 padded"])
def test_padded_keys_weigh_nothing_where_they_would_win_the_softmax(rows, m, h):
    """Every true score is far below 0; what lies past the keys' end in
    the block (NaN under the interpreter, anything on the chip) would
    take the softmax or poison the sum unmasked. The output is the mean
    of v: a padded key's `p` is an exact zero and a padded row of v is
    never multiplied."""
    q = jnp.full((1, rows, h, sa.WIDTH), 4.0, jnp.float32)
    k = jnp.full((1, m, h, sa.WIDTH), -4.0, jnp.float32)
    v = jax.random.normal(jax.random.key(m), (1, m, h, sa.WIDTH)) + 3.0
    out = short(q, k, v)
    want = jnp.broadcast_to(v.mean(axis=1, keepdims=True), out.shape)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5)


@pytest.mark.parametrize("operand", ["q", "k", "v"])
@pytest.mark.parametrize("tiles", [1, 2], ids=["one tile a step", "two tiles a step"])
def test_a_head_is_untouched_by_its_lane_tile_neighbour(tiles, operand):
    """Heads 2i and 2i + 1 share a lane tile: perturb every odd head's
    q, k or v and every even head's output keeps its bits (and the odd
    heads' change)."""
    q, k, v = operands(2, 81, 77, 4, jnp.bfloat16)
    before = np.asarray(short(q, k, v, tiles=tiles), np.float32)
    bump = {"q": q, "k": k, "v": v}
    bump[operand] = bump[operand].at[:, :, 1::2].multiply(-1.5)
    after = np.asarray(short(bump["q"], bump["k"], bump["v"], tiles=tiles), np.float32)
    np.testing.assert_array_equal(after[:, :, 0::2], before[:, :, 0::2])
    assert np.abs(after[:, :, 1::2] - before[:, :, 1::2]).max() > 0.1


def test_nothing_is_padded_or_moved_around_the_kernel():
    """q, k, v and the output are reshaped, never padded, transposed or
    sliced: the caller's [B, N, H*D] arrays are the kernel's operands."""
    q = jax.ShapeDtypeStruct((2, 81, 4, 64), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((2, 77, 4, 64), jnp.bfloat16)
    outside = _primitives(jax.make_jaxpr(short)(q, k, k).jaxpr, kernels=False)
    assert outside - {"pjit", "jit"} == {"reshape", "pallas_call"}, outside


def test_calls_of_one_shape_share_one_trace():
    """A program that attends 60 times at one shape traces the kernel
    once: `short_attention` is jitted, as `flash_attention` is."""
    q, k, v = operands(1, 81, 81, 2, jnp.bfloat16)

    def three_blocks(q, k, v):
        for _ in range(3):
            q = short(q, k, v)
        return q

    short(q, k, v)  # the trace every later call of the shape finds
    misses = sa.short_attention._cache_size()
    jax.make_jaxpr(three_blocks)(q, k, v)
    assert sa.short_attention._cache_size() == misses


@pytest.mark.parametrize("shape", [
    dict(q=(1, 81, 2, 40), k=(1, 81, 2, 40)), dict(q=(1, 81, 3, 64), k=(1, 81, 3, 64)),
    dict(q=(1, 81, 2, 64), k=(1, 1600, 2, 64)), dict(q=(1, 81, 2, 64), k=(1, 81, 1, 64)),
], ids=["width 40", "three heads", "keys past one block", "fewer key heads"])
def test_a_call_it_has_no_form_for_is_refused(shape):
    q, k = (jnp.zeros(s, jnp.bfloat16) for s in (shape["q"], shape["k"]))
    with pytest.raises(ValueError, match="no plan"):
        short(q, k, k)


# ((n, m, heads, width, dtype), the route a TPU gives it): SDXL's tile shapes that won; then what
# must keep the parent's route, with the entry the parent logged for it
SDXL = [
    ((324, 324, 20, 64, jnp.bfloat16), "short 324x324x64 pad336x384 h20 bq336 bf16 inplace"),
    ((324, 77, 20, 64, jnp.bfloat16), "short 324x77x64 pad336x128 h20 bq336 bf16 inplace"),
    ((1296, 1296, 10, 64, jnp.bfloat16), "short 1296x1296x64 pad1296x1408 h2 bq432 bf16 inplace"),
    # the ranges of keys timed between and around them, at the tile's two row counts
    ((324, 128, 20, 64, jnp.bfloat16), "short 324x128x64 pad336x128 h20 bq336 bf16 inplace"),
    ((324, 1024, 20, 64, jnp.bfloat16), "short 324x1024x64 pad336x1024 h10 bq336 bf16 inplace"),
    ((1296, 128, 10, 64, jnp.bfloat16), "short 1296x128x64 pad1296x128 h10 bq432 bf16 inplace"),
    ((1296, 1536, 10, 64, jnp.bfloat16), "short 1296x1536x64 pad1296x1536 h2 bq432 bf16 inplace"),
]
UNMOVED = [
    ((4096, 4096, 8, 40, jnp.bfloat16), "flash 4096x4096x40 bq512 bk1024 bf16"),
    ((1024, 1024, 8, 80, jnp.bfloat16), "flash 1024x1024x80 bq512 bk1024 bf16"),
    ((256, 256, 8, 160, jnp.bfloat16), "flash 256x256x160 bq256 bk256 bf16"),
    ((64, 64, 8, 160, jnp.bfloat16), "xla 64x64x160"),
    ((4096, 77, 8, 40, jnp.bfloat16), "xla 4096x77x40"),
    ((4608, 4608, 24, 128, jnp.bfloat16), "flash 4608x4608x128 bq512 bk1536 bf16 inplace"),
    ((16384, 16384, 1, 512, jnp.bfloat16), "flash 16384x16384x512 bq512 bk512 bf16 inplace"),
    ((4096, 4096, 1, 512, jnp.bfloat16), "flash 4096x4096x512 bq512 bk512 bf16 inplace"),
    ((5184, 5184, 1, 512, jnp.bfloat16),
     "flash 5184x5184x512 pad5280x5376 bq480 bk896 bf16 inplace"),
    # 64-wide heads at lengths nobody timed, and float32 operands: the parent's routes
    ((257, 257, 16, 64, jnp.bfloat16), "xla 257x257x64"),
    ((512, 512, 64, 64, jnp.bfloat16), "flash 512x512x64 bq512 bk512 bf16"),
    ((324, 1100, 20, 64, jnp.bfloat16), "flash 324x1100x64 pad336x1152 bq336 bk1152 bf16"),
    ((400, 400, 20, 64, jnp.bfloat16), "xla 400x400x64"),
    # timed and not won: SDXL's 1,296-token blocks over the text, 324 rows over 200 keys
    ((1296, 77, 10, 64, jnp.bfloat16), "xla 1296x77x64"),
    ((324, 200, 20, 64, jnp.bfloat16), "xla 324x200x64"),
    ((576, 77, 20, 64, jnp.bfloat16), "xla 576x77x64"),
    ((324, 324, 20, 64, jnp.float32), "xla 324x324x64"),
    ((324, 324, 5, 64, jnp.bfloat16), "xla 324x324x64"),
]


def _logged(shape, monkeypatch):
    """The entry `dot_product_attention` logs for the shape while a
    program is traced on a TPU's routes (nothing runs)."""
    n, m, heads, width, dtype = shape
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = jax.ShapeDtypeStruct((1, n, heads, width), dtype)
    k = jax.ShapeDtypeStruct((1, m, heads, width), dtype)
    with attn.route_log() as routes:
        jax.eval_shape(attn.dot_product_attention, q, k, k)
    assert len(routes) == 1
    return routes[0]


@pytest.mark.parametrize(
    "shape,entry", SDXL + UNMOVED,
    ids=["x".join(map(str, s[:4])) + "-" + s[4].dtype.name for s, _ in SDXL + UNMOVED])
def test_the_rule_names_sdxl_s_shapes_and_leaves_every_other_call_its_route(
        shape, entry, monkeypatch):
    n, m, heads, width, dtype = shape
    assert sa.short_wins(n, m, heads, width, dtype) is entry.startswith("short ")
    q = jax.ShapeDtypeStruct((1, n, heads, width), dtype)
    k = jax.ShapeDtypeStruct((1, m, heads, width), dtype)
    assert attn.attention_route(q, k) == "xla"  # off a TPU, as ever
    assert _logged(shape, monkeypatch) == entry
    assert attn.attention_route(q, k) == entry.split()[0]
    monkeypatch.setenv("CDT_FLASH", "0")  # the kill switch sends everything to XLA
    assert attn.attention_route(q, k) == "xla"


def test_a_causal_call_never_asks_the_rule(monkeypatch):
    """A causal call at 64-wide heads over 324 keys goes where
    `causal_route` sends it: the rule is not consulted."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(sa, "short_wins", lambda *a: pytest.fail("asked"))
    q = jax.ShapeDtypeStruct((1, 324, 20, 64), jnp.bfloat16)
    with attn.route_log() as routes:
        jax.eval_shape(functools.partial(attn.dot_product_attention, causal=True), q, q, q)
    assert routes == ["xla-causal 324x324x64/64 bq256 bf16"]


def test_flash_attention_s_source_lines_stand_where_they_stood():
    """A Mosaic kernel's payload carries the lines of its Python call
    stack, so a shifted line in `ops/attention.py` above the kernel's end
    rebuilds every kernel-carrying program of every cell once (4-7 min a
    cell). The short calls' routing was written into the same number of
    lines and its functions at the file's end: the kernel's `def`, its
    last line and the line that calls it are the parent's (956236a)."""
    import inspect

    lines, first = inspect.getsourcelines(attn.flash_attention.__wrapped__)
    assert (first, first + len(lines) - 1) == (450, 685)
    lines, first = inspect.getsourcelines(attn.dot_product_attention)
    call = [first + at for at, line in enumerate(lines) if "return flash_attention(" in line]
    assert call == [172]
