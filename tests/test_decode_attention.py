"""`ops/decode_attention`: the single-query kernel over a cache slot,
interpreted on the CPU, against the einsum form it stands in for; the
plan and the route as tables; the route's entry in the log the graph's
node reads. What the chip's compiler makes of it is in
`tests/test_flash_kernel_v5e.py`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.ops import attention as attn
from comfyui_distributed_tpu.ops import decode_attention as da

D = 128
# the slot read, in a cache with other slots around it
PASSES, LAYERS, SLOT = 2, 2, (1, 0)
TOLERANCE = {"bfloat16": 2e-2, "float32": 2e-5}


def operands(heads, positions, dtype, seed=0):
    kq, kc = jax.random.split(jax.random.key(seed))
    # logits with a standard deviation near 2: a peaked softmax
    q = (2.0 * jax.random.normal(kq, (heads, D))).astype(dtype)
    cache = jax.random.normal(kc, (PASSES, LAYERS, 2, heads, positions, D)).astype(dtype)
    return q, cache


def einsum_form(q, cache, slot, position):
    """`decode_attention_xla` at W = 1: the one query's `valid` row made
    from its position."""
    valid = da.position_valid(jnp.asarray(position, jnp.int32).reshape(1), cache.shape[-2])
    return da.decode_attention_xla(q[None], cache, slot, valid)[0]


def reference(q, cache, slot, position):
    """float64 over the same operands, on the host."""
    keys, values = (np.asarray(x, np.float64) for x in cache[slot])
    scores = np.einsum("hd,hsd->hs", np.asarray(q, np.float64), keys) / np.sqrt(D)
    scores[:, position + 1:] = -np.inf
    probs = np.exp(scores - scores.max(axis=-1, keepdims=True))
    probs /= probs.sum(axis=-1, keepdims=True)
    return np.einsum("hs,hsd->hd", probs, values)


@pytest.mark.parametrize("heads", [2, 16])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("where", ["first", "mid", "last"])
# the smallest slot; the cell's, 33 x 64; a multiple of 8 and of nothing larger
@pytest.mark.parametrize("positions", [64, 2112, 1000])
def test_kernel_matches_the_einsum_form(positions, where, dtype, heads):
    position = {"first": 0, "mid": positions // 2 - 3, "last": positions - 1}[where]
    q, cache = operands(heads, positions, dtype, seed=positions + heads)
    slot = tuple(jnp.int32(i) for i in SLOT)
    got = da.decode_attention(q, cache, slot, jnp.int32(position), interpret=True)
    form = einsum_form(q, cache, slot, jnp.int32(position))
    assert got.shape == form.shape == (heads, D) and got.dtype == form.dtype == cache.dtype
    tolerance = TOLERANCE[jnp.dtype(dtype).name]
    want = reference(q, cache, SLOT, position)
    for out in (got, form):
        assert np.abs(np.asarray(out, np.float64) - want).max() <= tolerance * max(
            1.0, np.abs(want).max())
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(form, np.float32), atol=tolerance, rtol=0)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_positions_past_the_token_weigh_nothing_whatever_they_hold(dtype):
    """A slot's positions past `position` have not been written: the
    kernel's result is the same bytes whether they hold zeros, noise
    or NaN, keys and values alike."""
    heads, positions, position = 2, 64, 20
    q, cache = operands(heads, positions, dtype, seed=5)
    unwritten = jnp.arange(positions)[:, None] > position
    zeros, nans = (
        cache.at[SLOT].set(jnp.where(unwritten, fill, cache[SLOT]).astype(dtype))
        for fill in (0.0, jnp.nan)
    )
    outs = [
        np.asarray(da.decode_attention(q, c, SLOT, position, interpret=True), np.float32)
        for c in (cache, zeros, nans)
    ]
    assert np.isfinite(outs[2]).all()
    assert outs[0].tobytes() == outs[1].tobytes() == outs[2].tobytes()


def test_every_slot_is_its_own():
    """The scalar-prefetched indices pick the (pass, layer) block: four
    slots, four different results, each the einsum form's."""
    q, cache = operands(2, 64, jnp.float32, seed=9)
    outs = {}
    for slot in [(0, 0), (0, 1), (1, 0), (1, 1)]:
        outs[slot] = np.asarray(da.decode_attention(q, cache, slot, 63, interpret=True))
        np.testing.assert_allclose(
            outs[slot], np.asarray(einsum_form(q, cache, slot, 63)), atol=2e-5)
    assert len({out.tobytes() for out in outs.values()}) == 4


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("position", [0, 41, 63])
def test_the_slot_may_be_a_loops_carry(position, dtype):
    """As a model's loop calls it: the pass a scanned value, the layer a
    counter in the inner scan's carry, the position traced, each handed
    to the kernel as a scalar of its own. Every (pass, layer) reads its
    own slot, the bytes a call with plain integers gives."""
    q, cache = operands(2, 64, dtype, seed=11)

    @jax.jit
    def walk(q, cache, position):
        def one_pass(_, step):
            def one_layer(layer, _):
                out = da.decode_attention(q, cache, (step, layer), position, interpret=True)
                return layer + 1, out
            return None, jax.lax.scan(one_layer, jnp.int32(0), None, length=LAYERS)[1]
        return jax.lax.scan(one_pass, None, jnp.arange(PASSES))[1]

    got = np.asarray(walk(q, cache, jnp.int32(position)), np.float32)
    assert got.shape == (PASSES, LAYERS, 2, D)
    for step in range(PASSES):
        for layer in range(LAYERS):
            want = da.decode_attention(q, cache, (step, layer), position, interpret=True)
            assert got[step, layer].tobytes() == np.asarray(want, np.float32).tobytes()
    assert len({got[s, l].tobytes() for s in range(PASSES) for l in range(LAYERS)}) == 4


@pytest.mark.parametrize("heads,positions,d,itemsize,group", [
    (16, 2112, 128, 2, 1),     # the cell: one head a step is 1.08 MB
    (16, 2112, 128, 4, 1),
    (16, 1000, 128, 2, 4),     # 0.51 MB a head: four reach a megabyte
    (16, 512, 128, 2, 4),
    (16, 64, 128, 2, 16),      # all of a small slot in one step
    (2, 64, 128, 4, 2),
    (16, 8192, 128, 2, 1),
    (16, 16384, 128, 2, None),  # a head's 8.4 MB twice over is past the VMEM budget
    (16, 2112, 64, 2, None),   # a width off the lane tile
    (16, 2112, 192, 2, None),
    (16, 1001, 128, 2, None),  # off the sublane tile: the compiler would copy the cache
    (16, 0, 128, 2, None),
])
def test_decode_plan(heads, positions, d, itemsize, group):
    assert da.decode_plan(heads, positions, d, itemsize) == group
    if group is not None:
        assert heads % group == 0
        assert da.decode_vmem_bytes(group, positions, d, itemsize) <= attn.VMEM_BUDGET


@pytest.mark.parametrize("backend,heads,positions,d,dtype,route", [
    ("cpu", 16, 2112, 128, jnp.bfloat16, "decode-xla"),
    ("gpu", 16, 2112, 128, jnp.bfloat16, "decode-xla"),
    ("tpu", 16, 2112, 128, jnp.bfloat16, "decode-kernel"),
    ("tpu", 16, 2112, 128, jnp.float32, "decode-kernel"),
    ("tpu", 16, 2112, 64, jnp.bfloat16, "decode-xla"),
    ("tpu", 4, 2056, 16, jnp.float32, "decode-xla"),       # tiny-ouro
    ("tpu", 16, 16384, 128, jnp.bfloat16, "decode-xla"),
    ("tpu", 16, 1001, 128, jnp.bfloat16, "decode-xla"),
])
def test_decode_attention_route(monkeypatch, backend, heads, positions, d, dtype, route):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert da.decode_attention_route(heads, positions, d, dtype) == route


@pytest.mark.parametrize("backend,entry", [
    ("cpu", "decode-xla 16x2112x128"),
    ("tpu", "decode-kernel 16x2112x128 h1 bf16"),
])
def test_attend_writes_one_route_log_entry(monkeypatch, backend, entry):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    q = jax.ShapeDtypeStruct((16, D), jnp.bfloat16)
    cache = jax.ShapeDtypeStruct((4, 48, 2, 16, 2112, D), jnp.bfloat16)
    index = jax.ShapeDtypeStruct((), jnp.int32)
    with attn.route_log() as routes:
        out = jax.eval_shape(
            lambda q, cache, t, l, p: da.attend(q, cache, (t, l), p), q, cache, index, index, index)
    assert routes == [entry]
    assert (out.shape, out.dtype) == ((16, D), jnp.bfloat16)
    # outside a `route_log` block nothing is collected and nothing fails
    jax.eval_shape(
        lambda q, cache, t, l, p: da.attend(q, cache, (t, l), p), q, cache, index, index, index)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("kv_heads", [4, 2, 1])
def test_one_query_is_row_0_of_two_under_the_positions_valid_rows(kv_heads, dtype):
    """The einsum form has one shape of call: W = 1 with the position's
    `valid` row is what row 0 of a W = 2 call gives, and row 1 is the next
    position's; a key head serves its group of query heads either way."""
    heads, positions, position = 4, 40, 29
    keys = jax.random.split(jax.random.key(7), 2)
    q = jax.random.normal(keys[0], (2, heads, 16), dtype)
    cache = jax.random.normal(keys[1], (3, 2, kv_heads, positions, 16), dtype)
    valid = da.position_valid(jnp.asarray([position, position + 1]), positions)
    assert valid.shape == (2, positions) and valid.sum(axis=1).tolist() == [30, 31]
    both = da.decode_attention_xla(q, cache, (2,), valid)
    assert both.shape == q.shape and both.dtype == cache.dtype
    for row in (0, 1):
        alone = da.decode_attention_xla(q[row:row + 1], cache, (2,), valid[row:row + 1])
        np.testing.assert_allclose(
            np.asarray(alone[0], np.float32), np.asarray(both[row], np.float32),
            atol=TOLERANCE[jnp.dtype(dtype).name], rtol=0)
    assert not np.array_equal(np.asarray(both[0]), np.asarray(both[1]))


@pytest.mark.parametrize("kv_heads, entry", [(16, "decode-xla 16x2112x128"),
                                             (2, "decode-xla 16x2112x128")])
def test_attend_xla_writes_one_route_log_entry_whatever_the_key_heads(kv_heads, entry):
    q = jax.ShapeDtypeStruct((2, 16, D), jnp.bfloat16)
    cache = jax.ShapeDtypeStruct((3, 2, kv_heads, 2112, D), jnp.bfloat16)
    valid = jax.ShapeDtypeStruct((2, 2112), jnp.bool_)
    with attn.route_log() as routes:
        out = jax.eval_shape(lambda q, c, v: da.attend_xla(q, c, (1,), v), q, cache, valid)
    assert routes == [entry] and (out.shape, out.dtype) == ((2, 16, D), jnp.bfloat16)


def test_a_shape_the_plan_refuses_is_an_error_on_the_kernel():
    q, cache = operands(2, 12, jnp.float32)
    with pytest.raises(ValueError, match="no plan"):
        da.decode_attention(q, cache, SLOT, 3, interpret=True)


def test_the_entry_reaches_the_text_node(monkeypatch):
    """`TextGenerate` writes the log into `node.TextGenerate`'s
    `attention` on the request that traces the programs: the decode's
    entry beside the prefill's (the tiny preset, the einsum form)."""
    from comfyui_distributed_tpu.graph.nodes_text import TextGenerate
    from comfyui_distributed_tpu.models import pipeline
    from comfyui_distributed_tpu.telemetry import get_tracer

    bundle = pipeline.load_pipeline("tiny-ouro")
    tracer = get_tracer()
    with tracer.span("node.TextGenerate") as span:
        TextGenerate().generate(bundle, "a terse prompt", 3, max_new_tokens=2, temperature=1.0)
    cfg = bundle.lm.cfg
    tokens = len(bundle.tokenizer.encode("a terse prompt")) + 2
    entries = span.attrs["attention"].split(", ")
    assert entries[0] == f"decode-xla {cfg.num_attention_heads}x{tokens}x{cfg.head_dim}"
    assert entries[1].startswith("xla-causal ") and len(entries) == 2
