"""Causal `dot_product_attention` with a value width of its own (MLA's
192-wide queries and keys, 128-wide values) against a masked softmax, on
the XLA route it takes: in blocks of query rows, the last one short where
the length is no multiple of the block."""

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
