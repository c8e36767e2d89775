"""Causal `dot_product_attention` with a value width of its own (MLA's
192-wide queries and keys, 128-wide values) against a masked softmax, on
the XLA route it takes: in blocks of query rows, the last one short where
the length is no multiple of the block."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.ops import attention as att


def masked_softmax_attention(q, k, v, scale):
    n, m = q.shape[1], k.shape[1]
    scores = scale * jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    mask = jnp.arange(n)[:, None] + (m - n) >= jnp.arange(m)[None, :]
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))


def operands(n, m, heads, dq, dv, dtype, seed=0):
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    return (
        jax.random.normal(kq, (1, n, heads, dq), dtype),
        jax.random.normal(kk, (1, m, heads, dq), dtype),
        jax.random.normal(kv, (1, m, heads, dv), dtype),
    )


@pytest.mark.parametrize("n, m, block", [
    (512, 512, 256),   # two blocks of 256 rows: the second sees the first's keys
    (768, 768, 256),
    (256, 256, 256),   # one block
    (200, 200, 200),   # shorter than a block: one block of its own length
    (300, 300, 256),   # off the block: 256 rows, then a short block of 44
    (600, 600, 256),   # two whole blocks and a short one of 88
    (300, 500, 256),   # a short last block and fewer queries than keys
    (256, 512, 256),   # fewer queries than keys: the last 256 positions
    (1, 300, 1),       # one query sees every key
])
@pytest.mark.parametrize("dtype, tolerance", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
def test_causal_attention_with_its_own_value_width_matches_a_masked_softmax(
        n, m, block, dtype, tolerance):
    q, k, v = operands(n, m, 4, 192, 128, dtype)
    scale = 0.1147
    with att.route_log() as routes:
        out = att.dot_product_attention(q, k, v, causal=True, scale=scale)
    want = masked_softmax_attention(q, k, v, scale)
    assert out.shape == (1, n, 4, 128) and out.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want), rtol=tolerance, atol=tolerance)
    name = "f32" if dtype == jnp.float32 else "bf16"
    assert routes == [f"xla-causal {n}x{m}x192/128 bq{block} {name}"]


def test_a_length_off_the_block_is_blocked_too(monkeypatch):
    monkeypatch.setattr(att, "CAUSAL_BLOCK_Q", 128)
    q, k, v = operands(200, 200, 4, 192, 128, jnp.float32)
    with att.route_log() as routes:
        out = att.dot_product_attention(q, k, v, causal=True, scale=0.1147)
    want = masked_softmax_attention(q, k, v, 0.1147)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5)
    assert routes == ["xla-causal 200x200x192/128 bq128 f32"]


@pytest.mark.parametrize("n", [2048, 2049, 2303])
def test_the_score_tensor_is_never_whole_in_memory(n):
    """No value of the traced program is larger than one block of rows
    over all keys, whatever the prompt's length."""
    heads = 2
    q, k, v = (jax.ShapeDtypeStruct((1, n, heads, w), jnp.bfloat16) for w in (192, 192, 128))
    jaxpr = jax.make_jaxpr(
        lambda q, k, v: att.dot_product_attention(q, k, v, causal=True))(q, k, v)
    scores = [
        var.aval.size for eqn in jaxpr.eqns for var in eqn.outvars
        if var.aval.ndim == 4 and var.aval.shape[1] == heads  # [B, H, rows, keys]
    ]
    assert scores and max(scores) <= heads * att.CAUSAL_BLOCK_Q * n


def test_causal_attention_defaults_to_the_query_widths_scale():
    q, k, v = operands(256, 256, 2, 64, 64, jnp.float32)
    out = att.dot_product_attention(q, k, v, causal=True)
    want = masked_softmax_attention(q, k, v, 1.0 / math.sqrt(64))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_a_later_key_never_reaches_an_earlier_row():
    q, k, v = operands(512, 512, 2, 192, 128, jnp.float32)
    before = att.dot_product_attention(q, k, v, causal=True)
    k2 = k.at[:, 300:].set(7.0)
    v2 = v.at[:, 300:].set(-3.0)
    after = att.dot_product_attention(q, k2, v2, causal=True)
    np.testing.assert_array_equal(np.asarray(before[:, :300]), np.asarray(after[:, :300]))
    assert not np.allclose(np.asarray(before[:, 300:]), np.asarray(after[:, 300:]))


def test_more_queries_than_keys_is_refused():
    q, k, v = operands(512, 256, 2, 64, 64, jnp.float32)
    with pytest.raises(ValueError, match="512 queries over 256 keys"):
        att.dot_product_attention(q, k, v, causal=True)


@pytest.mark.parametrize("kwargs", [{"scale": 0.5}, {}])
def test_without_causal_a_scale_or_value_width_of_its_own_is_refused(kwargs):
    q, k, v = operands(128, 128, 2, 64, 64 if kwargs else 32, jnp.float32)
    with pytest.raises(NotImplementedError):
        att.dot_product_attention(q, k, v, **kwargs)


def test_a_call_without_causal_lowers_to_what_it_did():
    """FLUX's and SD1.5's calls pass neither `causal` nor `scale`: the
    program they trace is jax.nn.dot_product_attention's on the CPU
    route, operation for operation."""
    q, k, v = operands(144, 144, 2, 16, 16, jnp.float32)
    mine = jax.make_jaxpr(lambda q, k, v: att.dot_product_attention(q, k, v))(q, k, v)
    theirs = jax.make_jaxpr(jax.nn.dot_product_attention)(q, k, v)
    assert str(mine) == str(theirs)


# --- the Pallas kernel under its causal mask (PR 43), interpreted ------------

def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for inner in param if isinstance(param, (tuple, list)) else (param,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def grouped_operands(n, m, heads, kv_heads, dq, dv, dtype, seed=0):
    """q, k, v whose heads differ (head i scaled by i + 1): a key head
    read in another's place changes the result."""
    kq, kk, kv = jax.random.split(jax.random.key(seed + n + 7 * m), 3)
    gain = lambda count: (1.0 + jnp.arange(count, dtype=jnp.float32))[:, None] / count
    return (
        (jax.random.normal(kq, (1, n, heads, dq)) * gain(heads)).astype(dtype),
        (jax.random.normal(kk, (1, m, kv_heads, dq)) * gain(kv_heads)).astype(dtype),
        (jax.random.normal(kv, (1, m, kv_heads, dv)) * gain(kv_heads) * kv_heads).astype(dtype),
    )


# (n, m, query heads, key heads, q/k width, v width, window, scale, the route's entry)
KERNEL_CASES = [
    pytest.param(2048, 2048, 2, 2, 128, 128, None, None,
                 "2048x2048x128/128 g1 bq512 bk1024 {} inplace blocks6/8", id="n=m, 1:1"),
    pytest.param(512, 1280, 2, 2, 128, 128, None, None,
                 "512x1280x128/128 g1 bq512 bk640 {} inplace blocks2/2", id="n<m: the offset m - n"),
    pytest.param(1536, 1536, 8, 1, 128, 128, None, None,
                 "1536x1536x128/128 g8 bq512 bk768 {} inplace blocks5/6", id="8 query heads a key head"),
    pytest.param(1536, 1536, 4, 2, 128, 128, 128, None,
                 "1536x1536x128/128 w128 g2 bq512 bk512 {} inplace blocks5/9",
                 id="a band of 128 over three q blocks, its lower edge inside a block"),
    pytest.param(640, 1152, 2, 2, 128, 128, 200, None,
                 "640x1152x128/128 w200 g1 bq128 bk384 {} inplace blocks8/15",
                 id="a band and the offset: rows whose first blocks lie before their band"),
    pytest.param(1100, 1100, 2, 2, 128, 128, None, None,
                 "1100x1100x128/128 pad1104x1280 g1 bq368 bk640 {} inplace blocks5/6",
                 id="off the 128 multiple: padded keys and rows under the causal mask"),
    pytest.param(1100, 1300, 4, 2, 128, 128, 128, None,
                 "1100x1300x128/128 pad1104x1536 w128 g2 bq368 bk512 {} inplace blocks6/9",
                 id="off the multiple, a band, the offset, grouped"),
    pytest.param(640, 640, 2, 2, 192, 128, None, 0.1147,
                 "640x640x192/128 g1 bq128 bk640 {} inplace blocks5/5",
                 id="a value width and a scale of its own (MLA's 192 beside 128)"),
    pytest.param(1280, 1280, 4, 2, 16, 16, 100, None,
                 "1280x1280x16/16 w100 g2 bq256 bk256 {} blocks9/25",
                 id="narrow heads, folded into the batch, grouped, banded"),
    # the k step's own state (PR 51): row chunks of 256, lane-replicated max, a partial sum a lane
    pytest.param(1536, 1536, 2, 2, 128, 128, 100, None,
                 "1536x1536x128/128 w100 g1 bq512 bk512 {} inplace blocks5/9",
                 id="a band of 100 under q blocks of 512: rows whose first k block is wholly masked"),
    pytest.param(512, 1536, 2, 2, 192, 256, None, 0.1147,
                 "512x1536x192/256 g1 bq512 bk768 {} inplace blocks2/2",
                 id="a value width of 256 beside q and k of 192: the correction tiled over two lane tiles"),
    pytest.param(128, 128, 2, 1, 128, 128, None, None,
                 "128x128x128/128 g2 bq128 bk128 {} inplace blocks1/1",
                 id="one step over a block of 128 keys"),
    pytest.param(1296, 1296, 2, 2, 128, 128, None, None,
                 "1296x1296x128/128 pad1296x1536 g1 bq432 bk768 {} inplace blocks5/6",
                 id="1,296 rows as 3 x 432: a ragged last row chunk under the diagonal"),
]


@pytest.mark.parametrize("dtype, name", [(jnp.float32, "f32"), (jnp.bfloat16, "bf16")])
@pytest.mark.parametrize("n, m, heads, kv_heads, dq, dv, window, scale, entry", KERNEL_CASES)
def test_the_kernel_under_its_mask_matches_the_xla_form(
        n, m, heads, kv_heads, dq, dv, window, scale, entry, dtype, name):
    """`flash_attention(causal=True)` in the interpreter against
    `causal_attention_blocked`, the form it replaces on a TPU: float32
    to rounding; bfloat16 operands both within the tolerance of
    `tests/test_flash_inplace.py` of the float32 result, head by head."""
    import chip_smoke

    q, k, v = grouped_operands(n, m, heads, kv_heads, dq, dv, dtype)
    with att.route_log() as routes:
        out = att.causal_attention(
            q, k, v, scale=scale, window=window, force_flash=True, interpret=True)
    assert routes == ["flash-causal " + entry.format(name)]
    assert out.shape == (1, n, heads, dv) and out.dtype == dtype
    with jax.default_matmul_precision("highest"):
        want = att.causal_attention_blocked(
            *(x.astype(jnp.float32) for x in (q, k, v)), scale=scale, window=window)
    error = jnp.max(jnp.abs(out.astype(jnp.float32) - want), axis=(0, 1, 3))  # a head
    limit = 2e-5 if dtype == jnp.float32 else chip_smoke.ATTENTION_TOLERANCE
    assert bool(jnp.all(error <= limit * jnp.maximum(1.0, jnp.max(jnp.abs(want), axis=(0, 1, 3))))), error


def test_dot_product_attention_forced_to_the_kernel_is_the_causal_call():
    q, k, v = grouped_operands(512, 512, 2, 2, 128, 128, jnp.float32)
    with att.route_log() as routes:
        out = att.dot_product_attention(q, k, v, causal=True, force_flash=True, interpret=True)
    assert routes == ["flash-causal 512x512x128/128 g1 bq512 bk512 f32 inplace blocks1/1"]
    want = masked_softmax_attention(q, k, v, 1.0 / math.sqrt(128))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_the_kernel_never_lets_a_later_key_reach_an_earlier_row():
    """Keys from 700 on are replaced by large ones: rows before 700 are
    bit for bit what they were, under a band too (a skipped block is not
    read, a crossed one is masked)."""
    q, k, v = grouped_operands(1024, 1024, 4, 2, 128, 128, jnp.float32)
    for window in (None, 128):
        kernel = functools.partial(
            att.flash_attention, causal=True, window=window, interpret=True)
        before = kernel(q, k, v)
        after = kernel(q, k.at[:, 700:].set(7.0), v.at[:, 700:].set(-3.0))
        np.testing.assert_array_equal(np.asarray(before[:, :700]), np.asarray(after[:, :700]))
        assert not np.allclose(np.asarray(before[:, 700:]), np.asarray(after[:, 700:]))


def unmasked_body(chunks: int, tiles: int) -> list[str]:
    """The kernel's own equations for a call without a mask, one after
    another (the body of PR 51; until then the parent of PR 43's, traced
    at d34906f): between the two `cond`s of the first and the last k step,
    each of a q block's row chunks takes its scores, the max over their
    `tiles` lane tiles and one row reduction, both `exp`, the partial sums
    a lane, and the second product into the rescaled accumulator."""
    chunk = [
        "get", "get", "dot_general", "mul",
        "get", *["slice"] * tiles, *["max"] * (tiles - 1), "reduce_max", "broadcast_in_dim", "max",
        "sub", "exp", "tile", "sub", "exp",
        "get", "mul", *["slice"] * tiles, *["add"] * tiles, "swap",
        "get", "mul", "convert_element_type", "dot_general", "add", "swap", "swap",
    ]
    return [
        "program_id", "eq", "convert_element_type", "cond", "get",
        *chunk * chunks,
        "eq", "convert_element_type", "cond",
    ]


@pytest.mark.parametrize("label, shape, tiles", [
    ("sd15 self 64x64", (2, 4096, 8, 40), 8), ("flux joint 4608", (1, 4608, 24, 128), 12)])
def test_a_call_without_a_mask_traces_to_the_program_it_traced_to(label, shape, tiles):
    """SD1.5's and FLUX's kernels share `flash_attention` with the causal
    calls: theirs holds no `iota`, no comparison beyond the two `eq` of
    the first and last k step, no clamp in an index map, no operand
    beyond q, k and v, and its body is the unmasked one, equation for
    equation: two row chunks of 256 a q block of 512."""
    operand = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    jaxpr = jax.make_jaxpr(att.flash_attention)(operand, operand, operand).jaxpr
    (call,) = [e for e in _eqns(jaxpr) if e.primitive.name == "pallas_call"]
    assert len(call.invars) == 3 and call.params["name"] == "flash_attention"
    body = call.params["jaxpr"]
    assert [e.primitive.name for e in body.eqns] == unmasked_body(512 // att.ROW_CHUNK, tiles)
    inside = {e.primitive.name for e in _eqns(body)}
    assert not inside & {"iota", "select_n", "lt", "le", "gt", "ge", "min", "and", "or", "not"}
    for mapping in call.params["grid_mapping"].block_mappings:
        in_map = {e.primitive.name for e in _eqns(mapping.index_map_jaxpr.jaxpr)}
        assert not in_map & {"min", "max", "iota"}, in_map


def test_a_causal_call_has_a_name_of_its_own_in_a_trace():
    q = jax.ShapeDtypeStruct((1, 1024, 4, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 1024, 2, 128), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(functools.partial(att.flash_attention, causal=True))(q, kv, kv).jaxpr
    (call,) = [e for e in _eqns(jaxpr) if e.primitive.name == "pallas_call"]
    assert call.params["name"] == "flash_attention_causal"
    # the key heads are read where they lie: nothing but the reshape of the
    # two minor axes stands in front of the kernel
    assert [v.aval.shape for v in call.invars] == [(1, 1024, 512), (1, 1024, 256), (1, 1024, 256)]
    assert {e.primitive.name for e in jaxpr.eqns[0].params["jaxpr"].eqns} == {"reshape", "pallas_call"}


@pytest.mark.parametrize("tokens, window, plan, steps, blocks", [
    (2048, None, (2048, 2048, 512, 1024), 2, "6/8"),
    (8192, None, (8192, 8192, 512, 1024), 8, "72/128"),
    (8192, 128, (8192, 8192, 512, 512), 2, "31/256"),  # keys qi x 512 - 127 .. qi x 512 + 511
])
def test_the_plan_of_a_causal_call_and_the_share_of_the_square_it_computes(
        tokens, window, plan, steps, blocks):
    """The block choice at the four prefills' lengths (Ouro's and
    DeepSeek-V2's 2,048, Solar-Open2's and K-EXAONE's 8,192, the latter's
    window layers), and `blocks` as the route entry prints it."""
    assert att.flash_plan(tokens, tokens, 128, 2, causal=True, window=window) == plan
    _, _, block_q, block_k = plan
    # the inner grid axis is the widest q block's count of k blocks, not the square's
    widest, computed = att.causal_blocks(tokens, block_q, block_k, tokens, tokens, window)
    square = (tokens // block_q) * (tokens // block_k)
    assert (widest, f"{computed}/{square}") == (steps, blocks)
    q = jax.ShapeDtypeStruct((1, tokens, 16, 128), jnp.bfloat16)
    with att.route_log() as routes:
        jax.eval_shape(
            functools.partial(att.causal_attention, window=window, force_flash=True), q, q, q)
    assert routes[0].endswith(f"bq{block_q} bk{block_k} bf16 inplace blocks{blocks}")


@pytest.mark.parametrize("m, v_width, window, wins", [
    (8192, 128, None, True), (2048, 128, None, True), (512, 256, None, True),
    (384, 128, None, False),   # a short prompt: the XLA form's scores are small
    (2048, 64, None, False),   # a value width off the lane tile
    (8192, 64, None, True),    # ... goes padded where its float32 scores would be gigabytes:
    (65536, 64, None, True),   # granite-4.0-h-micro's parts (PERF.md §6, PR 54)
    (8192, 96, None, False),   # 64 is what was timed, nothing else off the tile
    (8192, 64, 128, False),
    (8192, 128, 128, False),   # K-EXAONE's window layers: a band that is all edge (PERF.md §6)
    (8192, 128, 1024, True),
    (8704, 128, 513, True),    # dots3-note-prev's window over a tail: timed, the kernel's (PR 61)
    (8704, 128, 512, False),   # under it nothing has been timed
    (32768, 128, None, True),  # LongCat-Flash-Chat's last part: 8,192 queries, timed (PR 63)
])
def test_the_shapes_a_tpu_sends_to_the_causal_kernel(monkeypatch, m, v_width, window, wins):
    assert att.causal_kernel_wins(m, v_width, window) is wins
    q = jax.ShapeDtypeStruct((1, m, 4, 128), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, m, 4, v_width), jnp.bfloat16)
    assert att.causal_route(q, q, v, window) == "xla"  # this backend is no TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert att.causal_route(q, q, v, window) == ("flash" if wins else "xla")
    monkeypatch.setenv("CDT_FLASH", "0")
    assert att.causal_route(q, q, v, window) == "xla"
