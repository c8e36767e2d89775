"""Granite 4.0-H (granite-4.0-h-micro) against its float32 reference on a
tiny preset with every mechanism (8 layers, attention at 2 and 6, so
Mamba-2 runs of three lengths; 4 Mamba-2 heads of 8 over a state of 16 in
one group, chunks of 8; 4 query heads over 2 key heads of 16 under a scale
of 1/64; a SwiGLU of 96 in every layer; the four multipliers at their
published values; parts of 16 positions): the prefill in parts and the
decode through the state tree against the reference's one pass, the parts
against one part, every Mamba layer's state and tail against the
recurrence, each multiplier, and the published configuration's counts."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.models import granite_hybrid as gh
from comfyui_distributed_tpu.models import lm_common, mamba2
from comfyui_distributed_tpu.models.registry import create_model, get_config
from comfyui_distributed_tpu.reference import granite_hybrid as ref

TINY = get_config("tiny-granite-hybrid")
PUBLISHED = get_config("granite-4.0-h-micro")
PROMPT, STEPS = 43, 9  # two whole parts of 16 and 11 positions of a third
POSITIONS = np.arange(PROMPT - 1, PROMPT + STEPS)
# float32 on both sides, sums in another order (chunks and parts against a token at a
# time, blocks of rows against one mask): a few ulp through 8 layers; measured 3e-7
LOGITS_TOLERANCE = 2e-5
STATE_TOLERANCE = 3e-5


def rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


def leaves(cache, name):
    return np.stack([np.asarray(leaf) for leaf in cache[name]])


@pytest.fixture(scope="module")
def params():
    return gh.init_params(TINY, jax.random.key(1))


@pytest.fixture(scope="module")
def served(params):
    """(the ids of prompt and decode, the logits at the last prompt
    position and after every decoded id, the state the prefill left)."""
    ids = jax.random.randint(jax.random.key(3), (PROMPT,), 0, TINY.vocab_size)
    prefill = gh.prefill(TINY, params, ids, cache_len=PROMPT + STEPS, collect=True)
    left = {name: leaves(prefill.cache, name) for name in ("kv", "ssm", "conv")}
    decode = gh.decode(
        TINY, params, prefill.cache, prefill.logits, jnp.int32(PROMPT), jax.random.key(4),
        jnp.float32(1.0), steps=STEPS, collect=True)
    logits = np.concatenate([np.asarray(prefill.logits)[None], np.asarray(decode.logits)])
    final = leaves(decode.cache, "ssm")
    return np.concatenate([np.asarray(ids), np.asarray(decode.ids)]), logits, left, final


@pytest.fixture(scope="module")
def wanted(params, served):
    return ref.forward(
        ref.Sizes.of(TINY), params, served[0], positions=POSITIONS, state_at=PROMPT)


def test_prefill_in_parts_and_decode_through_the_state_match_the_reference(served, wanted):
    """(a) Two whole parts and a remainder, then nine steps through the
    state: the logits at the last prompt position and at every decoded
    one, and every Mamba layer's state after the last token."""
    _, logits, _, final = served
    assert lm_common.parts_of(PROMPT, TINY.prefill_part) == (2, 11)
    assert rel_l2(logits, wanted[0]).max() < LOGITS_TOLERANCE
    np.testing.assert_allclose(final, np.asarray(wanted[1][1]), rtol=0, atol=STATE_TOLERANCE)


def test_the_prefill_in_parts_is_the_prefill_whole(params, served):
    """(b) One part as long as the prompt: the logits and every layer's
    state, tail, keys and values."""
    full, logits, left, _ = served
    whole = gh.prefill(
        dataclasses.replace(TINY, prefill_part=PROMPT), params, jnp.asarray(full[:PROMPT]),
        cache_len=PROMPT + STEPS)
    np.testing.assert_allclose(logits[0], np.asarray(whole.logits), rtol=0, atol=2e-6)
    for name, mine in left.items():
        np.testing.assert_allclose(mine, leaves(whole.cache, name), rtol=0, atol=5e-6)


def test_every_mamba_layers_state_and_tail_after_the_prefill_are_the_recurrences(served, wanted):
    """(c) And the keys and values the attention layers wrote, at their
    positions, with nothing written past the prompt."""
    _, _, left, _ = served
    np.testing.assert_allclose(left["ssm"], np.asarray(wanted[1][0]), rtol=0, atol=STATE_TOLERANCE)
    np.testing.assert_allclose(left["conv"], np.asarray(wanted[2]), rtol=0, atol=STATE_TOLERANCE)
    written = left["kv"][:, :, :, :PROMPT].transpose(0, 1, 3, 2, 4)  # [layers, 2, T, heads, d]
    np.testing.assert_allclose(
        written, np.asarray(wanted[3])[:, :, :PROMPT], rtol=0, atol=STATE_TOLERANCE)
    assert not left["kv"][:, :, :, PROMPT:].any()


@pytest.mark.parametrize("wrong", [
    {"residual_multiplier": 1.0},
    {"attention_multiplier": 16 ** -0.5},
    {"embedding_multiplier": 1.0},
    {"logits_scaling": 1.0},
    {"gate_before_norm": False},
], ids=lambda wrong: next(iter(wrong)))
def test_each_multiplier_binds(params, served, wrong):
    """(d) The reference with one scalar at its neighbour's value, or with
    the norm before the gate, is another model: outside the tolerance
    (a) passes at every compared position."""
    full, logits, _, _ = served
    other, *_ = ref.forward(
        dataclasses.replace(ref.Sizes.of(TINY), **wrong), params, full, positions=POSITIONS)
    assert rel_l2(logits, other).min() > 100 * LOGITS_TOLERANCE


def test_a_part_that_starts_from_a_zero_state_is_outside_the_tolerance(params, served, wanted):
    """(e) The reference forgetting at the second part's first position:
    its states after the prefill are not the system's; and the system
    whose second part enters with zeros is not the reference."""
    full, _, left, _ = served
    forgot = ref.forward(
        dataclasses.replace(ref.Sizes.of(TINY), zero_state_at=TINY.prefill_part), params, full,
        positions=POSITIONS, state_at=PROMPT)
    assert np.abs(left["ssm"] - np.asarray(forgot[1][0])).max() > 100 * STATE_TOLERANCE
    first = gh.prefill(TINY, params, jnp.asarray(full[:16]), cache_len=PROMPT)
    blind = gh.prefill(TINY, params, jnp.asarray(full[16:PROMPT]), cache_len=PROMPT)
    assert np.abs(leaves(first.cache, "ssm")).max() > 0
    assert np.abs(leaves(blind.cache, "ssm") - np.asarray(wanted[1][0])).max() > (
        100 * STATE_TOLERANCE)


def test_the_published_configuration_counts_what_the_issue_counted():
    """(f)"""
    assert gh.param_count(PUBLISHED) == 3_191_396_096
    assert gh.param_count(PUBLISHED) == (
        36 * 76_182_976 + 4 * 60_821_504 + 100_352 * 2_048 + 2_048)
    assert PUBLISHED.layers_of("attention") == [5, 15, 25, 35]
    assert PUBLISHED.head_dim == 64 and PUBLISHED.conv_channels == 4_352
    model = create_model("granite-4.0-h-micro")
    model.dtype = jnp.dtype(jnp.bfloat16)
    described = model.describe(65_664)
    assert described["cache_bytes"] == 537_919_488 == 65_664 * 8_192
    assert described["state_bytes"] == 76_437_504 == 36 * 2_097_152 + 36 * 3 * 4_352 * 2
    assert (described["layers"], described["mamba_layers"], described["attention_layers"]) == (
        40, 36, 4)
    assert described["tied_head_bytes"] == 100_352 * 2_048 * 2
    report = model.report(65_536, 128, 65_664)
    assert (report["prefill_parts"], report["prefill_chunks"]) == (8, 256)
    assert model.counted(report, 65_536, 128) == {
        "decode_steps": 128, "prefill_layer_passes": 65_536 * 40, "decode_layer_passes": 128 * 40}
    assert model.read_back(None, None) == ()


def test_a_served_request_collects_nothing_draws_the_same_ids_and_gets_its_state_back(
        params, served):
    full = served[0]
    prefill = gh.prefill(TINY, params, jnp.asarray(full[:PROMPT]), cache_len=PROMPT + STEPS)
    decode = gh.decode(
        TINY, params, prefill.cache, prefill.logits, jnp.int32(PROMPT), jax.random.key(4),
        jnp.float32(1.0), steps=STEPS)
    assert decode.logits is None
    assert all(leaf.is_deleted() for leaf in jax.tree_util.tree_leaves(prefill.cache))  # donated
    assert jax.tree_util.tree_structure(decode.cache) == jax.tree_util.tree_structure(
        gh.state_shapes(TINY, PROMPT + STEPS, jnp.float32))
    np.testing.assert_array_equal(np.asarray(decode.ids), full[PROMPT:])


def test_each_whole_part_takes_the_causal_call_of_its_own_key_count(params):
    """The scanned body holds one call a possible count of keys, and the
    remainder one more: what the route log of the request that traces
    the program shows."""
    from comfyui_distributed_tpu.ops.attention import route_log

    ids = jnp.zeros((PROMPT,), jnp.int32)
    with route_log() as routes:
        gh.prefill.__wrapped__(TINY, params, ids, cache_len=PROMPT)
    per_layer = ["xla-causal 16x16x16/16 bq16 f32", "xla-causal 16x32x16/16 bq16 f32"]
    assert [r for r in routes if not r.startswith("ssd-")] == (
        per_layer * 2 + ["xla-causal 11x43x16/16 bq11 f32"] * 2)
    # and the Mamba layers' chunked scans, the XLA form at these widths (PR 55)
    assert {r for r in routes if r.startswith("ssd-")} == {
        "ssd-xla 16x4x8 g1 n16 c8 f32", "ssd-xla 11x4x8 g1 n16 c8 f32"}
    with route_log() as routes:
        gh.decode.__wrapped__(
            TINY, params, gh.zeros(gh.state_shapes(TINY, PROMPT + 2, jnp.float32)),
            jnp.zeros((TINY.vocab_size,)), jnp.int32(PROMPT), jax.random.key(0),
            jnp.float32(1.0), steps=2)
    assert routes == ["decode-xla 4x45x16"] * 2


def test_the_decodes_einsum_form_takes_a_scale_and_keeps_the_inverse_root_without_one():
    """`attend_xla(scale=)`: none given is d^-1/2 bit for bit (what every
    other model's decode traces to), 1/64 is another result. That the
    multiplier reaches both programs is (a) and (d) above."""
    from comfyui_distributed_tpu.ops.decode_attention import attend_xla, position_valid

    q = jax.random.normal(jax.random.key(0), (1, 4, 16))
    kv = jax.random.normal(jax.random.key(1), (1, 2, 2, 24, 16))
    valid = position_valid(jnp.asarray([20]), 24)
    default = attend_xla(q, kv, (0,), valid)
    np.testing.assert_array_equal(
        np.asarray(default), np.asarray(attend_xla(q, kv, (0,), valid, scale=0.25)))
    assert np.abs(np.asarray(default - attend_xla(q, kv, (0,), valid, scale=1 / 64))).max() > 1e-3


def test_the_mixer_is_mamba2s_at_one_group(params):
    """Shared with Nemotron-H letter for letter: the layer calls
    `mamba2.mixer` with this model's sizes and nothing else."""
    layer = params["layers"][0]
    x = jax.random.normal(jax.random.key(5), (20, TINY.hidden_size))
    tail = jnp.zeros((3, TINY.conv_channels))
    state = jnp.zeros((4, 8, 16))
    mine = gh.mamba(TINY, layer["mamba"], x, tail, state)
    theirs = mamba2.mixer(layer["mamba"], x, tail, state, 4, 8, 1, 16, 8, 1e-5)
    for a, b in zip(mine, theirs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
