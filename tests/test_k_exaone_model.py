"""K-EXAONE against its float32 reference on a tiny preset with every
mechanism (a dense layer and one period: window, window, full, window
layers of 4 query heads over 2 key heads, a window of 12 in a ring of 16;
16 experts, 4 a token, 1 shared, rank 0 of 8; the MTP module): the banded
blocks of the causal attention against a full mask, prefill + decode
through rings and caches against the reference's forward pass with and
without drafting, the MTP module's draft logits against the reference's
pass, the ring that is one entry short, the lossless rule, the eight
ranks' shares of a layer against the uncut layer, and that the callers
that were there get from the shared blocks what they got."""

import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.models import k_exaone as ke
from comfyui_distributed_tpu.models import lm_common
from comfyui_distributed_tpu.models import moe as moe_layer
from comfyui_distributed_tpu.models.lm_common import rms_norm, swiglu
from comfyui_distributed_tpu.models.registry import create_model, get_config
from comfyui_distributed_tpu.ops import attention as attention_ops
from comfyui_distributed_tpu.ops import decode_attention
from comfyui_distributed_tpu.parallel.sharding import expert_range
from comfyui_distributed_tpu.reference import k_exaone as ref

TINY = get_config("tiny-k-exaone")
PROMPT, NEW = 53, 40  # 93 positions through a ring of 16: nearly six times round


def rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


def prompt_ids(cfg, seed=1, tokens=PROMPT):
    return jax.random.randint(jax.random.key(seed), (tokens,), 0, cfg.vocab_held)


def generate(cfg, params, ids, draft_tokens, steps=NEW, temperature=1.0, seed=3, collect=True):
    pre = ke.prefill(cfg, params, ids, cache_len=len(ids) + steps, collect=collect)
    logits = pre.logits
    dec = ke.decode(
        cfg, params, pre.cache, logits, jnp.int32(len(ids)), jax.random.key(seed),
        jnp.float32(temperature), steps=steps, collect=collect, draft_tokens=draft_tokens)
    return logits, dec


def reference_of(cfg, params, ids, dec, **sizes):
    """The reference's main and draft logits over the prompt and what
    the decode emitted."""
    every = np.concatenate([np.asarray(ids), np.asarray(dec.ids)])
    of = dataclasses.replace(ref.Sizes.of(cfg), **sizes)
    held = list(cfg.held_experts)
    logits, h, _ = ref.forward(of, params, every, held)
    drafts, _ = ref.mtp_forward(of, params, h, every, held)
    return logits, drafts


def verified(dec):
    """(step, row, position) of every main-model row a drafting decode
    verified: row 0 always (the last emitted token's), row 1 where the
    draft was kept; and (step, position the draft was drawn from)."""
    steps = int(dec.counts[0])
    position = np.asarray(dec.kept["position"])[:steps]
    accepted = np.asarray(dec.kept["accepted"])[:steps]
    rows = [(s, 0, position[s]) for s in range(steps)]
    rows += [(s, 1, position[s] + 1) for s in range(steps) if accepted[s]]
    return rows, [(s, position[s] - 1) for s in range(steps)], accepted


# --- the banded prefill ------------------------------------------------------


def full_mask_attention(q, k, v, window):
    """[B, N, H, D] float32 under a whole [N, N] mask."""
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") / math.sqrt(q.shape[3])
    i, j = jnp.arange(q.shape[1])[:, None], jnp.arange(k.shape[1])[None, :]
    seen = (j <= i) & (i - j < window)
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision="highest")


@pytest.mark.parametrize("n, window", [
    (300, 12), (512, 128), (256, 128), (700, 100), (64, 12), (130, 128), (257, 300), (513, 256)])
def test_banded_blocks_are_the_full_mask(n, window):
    """Lengths that are and are not multiples of the block of 256 rows
    and of the window, a window longer than the sequence, and one as
    long as a block."""
    keys = jax.random.split(jax.random.key(n), 3)
    q = jax.random.normal(keys[0], (1, n, 4, 16))
    k = jax.random.normal(keys[1], (1, n, 2, 16))
    v = jax.random.normal(keys[2], (1, n, 2, 16))
    got = attention_ops.causal_attention_blocked(q, k, v, window=window)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(full_mask_attention(q, k, v, window)), rtol=2e-5, atol=2e-6)


def test_a_band_takes_the_keys_it_reaches_and_no_others():
    """At 1,024 rows and a window of 128 a block of 256 rows multiplies
    by 383 keys (256 where there are no more), not by all before it."""
    q = jnp.zeros((1, 1024, 4, 16))
    k = v = jnp.zeros((1, 1024, 2, 16))
    text = jax.jit(
        lambda q, k, v: attention_ops.causal_attention_blocked(q, k, v, window=128)
    ).lower(q, k, v).as_text()
    # the float32 scores: [batch, key heads, group, 256 rows, keys]
    widths = sorted({int(m) for m in re.findall(r"<1x2x2x256x(\d\d+)xf32>", text)})
    assert widths == [256, 383]


def test_the_route_log_names_the_window_only_where_one_is_given():
    q = jnp.zeros((1, 40, 4, 16))
    k = v = jnp.zeros((1, 40, 2, 16))
    with attention_ops.route_log() as routes:
        attention_ops.causal_attention_blocked(q, k, v)
        attention_ops.causal_attention_blocked(q, k, v, window=12)
    assert routes == ["xla-causal 40x40x16/16 bq40 f32", "xla-causal 40x40x16/16 w12 bq40 f32"]


# --- the shared blocks, for the callers that were there ------------------------


def _causal_attention_blocked_before_pr41(q, k, v, scale=None):
    """`ops/attention.causal_attention_blocked` as PR 40 left it."""
    n, m, d = q.shape[1], k.shape[1], q.shape[3]
    heads, kv_heads = q.shape[2], k.shape[2]
    if kv_heads == heads:
        to_scores, to_out = "bqhd,bkhd->bhqk", "bhqk,bkhd->bqhd"
    else:
        q = q.reshape(q.shape[0], n, kv_heads, heads // kv_heads, d)
        to_scores, to_out = "bqhgd,bkhd->bhgqk", "bhgqk,bkhd->bqhgd"
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    block = min(attention_ops.CAUSAL_BLOCK_Q, n)
    outs = []
    for start in range(0, n, block):
        stop = min(start + block, n)
        last = stop + m - n
        scores = scale * jnp.einsum(
            to_scores, q[:, start:stop], k[:, :last], preferred_element_type=jnp.float32)
        rows = jnp.arange(start, stop)[:, None] + (m - n)
        scores = jnp.where(rows >= jnp.arange(last)[None, :], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        outs.append(jnp.einsum(
            to_out, probs, v[:, :last], preferred_element_type=jnp.float32,
        ).astype(v.dtype))
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
    return out.reshape(out.shape[0], n, heads, v.shape[3])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("caller, heads, kv_heads, d, dv, scale", [
    ("deepseek-v2", 4, 4, 24, 16, 0.21),  # expanded MLA: a value width and a scale of its own
    ("ouro", 4, 4, 16, 16, None),
    ("solar-open2", 4, 2, 16, 16, None),  # grouped queries
])
def test_without_a_window_blocked_causal_attention_is_bit_for_bit_what_it_was(
        caller, heads, kv_heads, d, dv, scale, dtype):
    keys = jax.random.split(jax.random.key(11), 3)
    q = jax.random.normal(keys[0], (1, 300, heads, d), dtype)
    k = jax.random.normal(keys[1], (1, 300, kv_heads, d), dtype)
    v = jax.random.normal(keys[2], (1, 300, kv_heads, dv), dtype)
    got = attention_ops.causal_attention_blocked(q, k, v, scale=scale)
    want = _causal_attention_blocked_before_pr41(q, k, v, scale=scale)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))


def _decode_attention_xla_before_pr41(q, cache, slot, position):
    """`ops/decode_attention.decode_attention_xla` as PR 40 left it."""
    keys, values = cache[slot]
    heads, d = q.shape
    grouped = q.reshape(keys.shape[0], -1, d)
    scores = d ** -0.5 * jnp.einsum(
        "hgd,hsd->hgs", grouped, keys, preferred_element_type=jnp.float32)
    valid = jnp.arange(keys.shape[1]) <= position
    scores = jnp.where(valid[None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(values.dtype)
    return jnp.einsum("hgs,hsd->hgd", probs, values).reshape(heads, d)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kv_heads", [4, 2])
def test_one_query_over_a_slot_is_bit_for_bit_what_it_was(kv_heads, dtype):
    """Solar-Open2's softmax layer (grouped) and Ouro's fallback (equal
    heads): one query, its `valid` row made from its position."""
    keys = jax.random.split(jax.random.key(5), 2)
    q = jax.random.normal(keys[0], (4, 16), dtype)
    cache = jax.random.normal(keys[1], (3, 2, kv_heads, 40, 16), dtype)
    valid = decode_attention.position_valid(jnp.asarray([29]), 40)
    got = decode_attention.decode_attention_xla(q[None], cache, (1,), valid)[0]
    want = _decode_attention_xla_before_pr41(q, cache, (1,), 29)
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))


def test_two_queries_over_a_slot_are_each_query_alone_under_its_own_mask():
    keys = jax.random.split(jax.random.key(6), 2)
    q = jax.random.normal(keys[0], (2, 4, 16))
    cache = jax.random.normal(keys[1], (3, 2, 2, 40, 16))
    valid = jnp.arange(40)[None, :] <= jnp.asarray([29, 30])[:, None]
    got = decode_attention.decode_attention_xla(q, cache, (2,), valid)
    assert got.shape == (2, 4, 16)
    for row, position in enumerate((29, 30)):
        want = _decode_attention_xla_before_pr41(q[row], cache, (2,), position)
        np.testing.assert_allclose(np.asarray(got[row]), np.asarray(want), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("newest", [0, 5, 15, 16, 17, 40, 41])
def test_a_rings_mask_is_the_window_of_each_query_over_what_the_entries_hold(newest):
    """A ring of 16 under a window of 12, two queries at `newest` - 1 and
    `newest`: by hand, entry s holds the newest position <= `newest` that
    is s modulo 16."""
    positions = jnp.asarray([newest - 1, newest])
    got = np.asarray(decode_attention.ring_valid(positions, 16, 12))
    for row, p in enumerate((newest - 1, newest)):
        for s in range(16):
            held = max((j for j in range(newest + 1) if j % 16 == s), default=-1)
            assert got[row, s] == (held >= 0 and p - 12 < held <= p), (row, s)


def _solar_route_before_pr41(cfg, bias, logits):
    """`models/solar_open2.route` as PR 40 left it."""
    scores = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(scores + bias.astype(jnp.float32), cfg.num_experts_per_tok)
    weights = jnp.take_along_axis(scores, ids, axis=-1)
    if cfg.norm_topk_prob:
        weights = weights / weights.sum(axis=-1, keepdims=True)
    return ids, weights * cfg.routed_scaling_factor


def test_the_one_sigmoid_rule_is_solars_to_the_bit_and_this_models_with_its_scale():
    from comfyui_distributed_tpu.models import solar_open2 as so

    solar = get_config("tiny-solar-open2")
    logits = jax.random.normal(jax.random.key(8), (33, 16))
    bias = 0.1 * jax.random.normal(jax.random.key(9), (16,))
    ids, weights = so.route(solar, bias, logits)
    ids_was, weights_was = _solar_route_before_pr41(solar, bias, logits)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids_was))
    np.testing.assert_array_equal(np.asarray(weights), np.asarray(weights_was))
    mine_ids, mine = moe_layer.sigmoid_route(logits, bias, 4, scale=2.5)
    ref_ids, ref_weights = ref.route(ref.Sizes.of(TINY), bias, logits)
    np.testing.assert_array_equal(np.asarray(mine_ids), np.asarray(ref_ids))
    np.testing.assert_allclose(np.asarray(mine), np.asarray(ref_weights), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(mine.sum(axis=-1)), 2.5, rtol=1e-6)


@pytest.mark.parametrize("name", ["tiny-solar-open2", "tiny-deepseek-v2", "tiny-ouro"])
def test_the_other_models_programs_give_what_the_parents_gave(name):
    """Prefill logits and sampled ids of the three models that share the
    changed blocks, against values the parent commit printed for the same
    seeds on the CPU (float32): the routing rule, `window=None` and the
    one-query einsum form leave their programs' outputs as they were."""
    lm = create_model(name)
    params = lm.init(jax.random.key(0))
    ids = jax.random.randint(jax.random.key(1), (75,), 0, 256)
    pre = lm.prefill(params, ids, 75 + 9)
    logits = np.asarray(pre.logits)
    dec = lm.decode(params, pre.cache, pre.logits, 75, jax.random.key(2), 9, 1.0)
    got = (float(logits[:8].astype(np.float64).sum()), np.asarray(dec.ids).tolist())
    want = PARENT_OUTPUTS[name]
    assert got[1] == want[1]
    assert got[0] == pytest.approx(want[0], rel=1e-5)  # equal to the last bit where it was run


# (the sum of the prefill's first eight logits, the nine ids) at commit 58e7105
PARENT_OUTPUTS = {
    "tiny-solar-open2": (5.212018050253391, [211, 0, 175, 59, 229, 477, 114, 52, 391]),
    "tiny-deepseek-v2": (4.700715959072113, [211, 183, 175, 418, 347, 338, 168, 52, 75]),
    "tiny-ouro": (1.8728616684675217, [820, 950, 1899, 738, 1799, 435, 343, 1308, 1587]),
}


# --- prefill and decode against the reference -----------------------------------


@pytest.mark.parametrize("rank, size", [(0, 8), (7, 8), (0, 1)])
def test_prefill_and_plain_decode_through_rings_and_caches_match_the_reference(rank, size):
    """`draft_tokens` 0, every position's logits (not tokens), over a
    sequence nearly six times the ring's length."""
    cfg = dataclasses.replace(TINY, ep_rank=rank, ep_size=size)
    params, ids = ke.init_params(cfg, jax.random.key(0)), prompt_ids(cfg)
    logits, dec = generate(cfg, params, ids, 0)
    want, _ = reference_of(cfg, params, ids, dec)
    assert rel_l2(logits, want[PROMPT - 1]) < 5e-6
    assert rel_l2(dec.kept["logits"], want[PROMPT:]).max() < 5e-6
    assert np.asarray(dec.counts).tolist()[:3] == [NEW, 0, 0]


@pytest.fixture(scope="module")
def drafting():
    params, ids = ke.init_params(TINY, jax.random.key(0)), prompt_ids(TINY)
    logits, dec = generate(TINY, params, ids, 1)
    return params, ids, logits, dec


def test_a_drafting_decode_matches_the_reference_at_every_position_it_verified(drafting):
    params, ids, logits, dec = drafting
    want, drafts = reference_of(TINY, params, ids, dec)
    rows, drawn, accepted = verified(dec)
    # a run in which drafts were both kept and rejected
    assert 0 < accepted.sum() < len(accepted)
    assert rel_l2(logits, want[PROMPT - 1]) < 5e-6
    for step, row, position in rows:
        assert rel_l2(dec.kept["logits"][step, row], want[position]) < 5e-6, (step, row)
    # the draft for x_{n+1} comes from the MTP module at position n - 1
    for step, position in drawn:
        assert rel_l2(dec.kept["draft_logits"][step], drafts[position]) < 5e-6, step


def test_the_counts_are_the_steps_and_the_drafts_kept(drafting):
    _, _, _, dec = drafting
    steps, drafted, accepted, read = np.asarray(dec.counts).tolist()
    _, _, kept = verified(dec)
    assert steps == drafted and accepted == kept.sum()
    # the first id comes from the prefill; a last step may keep a draft it has no room for
    assert 1 + steps + accepted in (NEW, NEW + 1)
    assert np.asarray(dec.kept["position"])[steps:].tolist() == [-1] * (NEW - 1 - steps)
    # held experts a step and sparse layer read: at most both of rank 0's two, five layers
    assert steps <= read <= steps * 2 * 5
    assert int(np.asarray(dec.loads).sum()) >= read


def test_a_ring_of_exactly_the_window_fails_under_drafting_and_not_without():
    """The clobbered key: with 12 entries a step's second write lands on
    the oldest key its first query still sees."""
    cfg = dataclasses.replace(TINY, ring=TINY.sliding_window)
    params, ids = ke.init_params(cfg, jax.random.key(0)), prompt_ids(cfg)
    _, dec = generate(cfg, params, ids, 1)
    want, _ = reference_of(cfg, params, ids, dec)
    rows, _, _ = verified(dec)
    errors = [float(rel_l2(dec.kept["logits"][s, r], want[p])) for s, r, p in rows]
    assert np.median(errors) > 1e-3
    _, dec = generate(cfg, params, ids, 0)
    want, _ = reference_of(cfg, params, ids, dec)
    assert rel_l2(dec.kept["logits"], want[PROMPT:]).max() < 5e-6


def test_a_reference_that_ignores_the_window_is_another_model(drafting):
    params, ids, _, dec = drafting
    want, _ = reference_of(TINY, params, ids, dec, windowed=False)
    rows, _, _ = verified(dec)
    errors = [float(rel_l2(dec.kept["logits"][s, r], want[p])) for s, r, p in rows]
    assert np.median(errors) > 1e-2


def test_bfloat16_stays_near_the_reference_and_float8_does_not():
    params = ke.init_params(TINY, jax.random.key(0), jnp.bfloat16)
    ids = prompt_ids(TINY)
    _, dec = generate(TINY, params, ids, 1)
    every = np.concatenate([np.asarray(ids), np.asarray(dec.ids)])
    sizes, held = ref.Sizes.of(TINY), list(TINY.held_experts)
    want, _, _ = ref.forward(sizes, params, every, held)
    rough, _, _ = ref.forward(sizes, params, every, held, round_to=jnp.float8_e4m3fn)
    rows, _, _ = verified(dec)
    errors = [float(rel_l2(dec.kept["logits"][s, r], want[p])) for s, r, p in rows]
    assert np.median(errors) < 0.05
    assert np.median(rel_l2(rough, want)) > 3 * np.median(errors)


# --- the rule, and what a request can count on -----------------------------------


def test_the_rule_emits_the_main_models_distribution_exactly():
    """Summed over every draft of a small vocabulary: q(d) [a(d) e_d +
    (1 - a(d)) residual] is p, for the system's two functions and for
    the reference's three lines."""
    keys = jax.random.split(jax.random.key(4), 2)
    p = jax.nn.softmax(2.0 * jax.random.normal(keys[0], (11,)))
    q = jax.nn.softmax(2.0 * jax.random.normal(keys[1], (11,)))
    left = lm_common.residual(p, q)
    emitted = jnp.zeros_like(p)
    for draft in range(11):
        a = lm_common.accept_probability(p, q, draft)
        emitted = emitted + q[draft] * (a * jax.nn.one_hot(draft, 11) + (1.0 - a) * left)
    np.testing.assert_allclose(np.asarray(emitted), np.asarray(p), atol=1e-6)
    accept, ref_left, ref_emitted = ref.speculative_rule(p, q)
    np.testing.assert_allclose(np.asarray(ref_emitted), np.asarray(p), atol=1e-6)
    np.testing.assert_allclose(np.asarray(ref_left), np.asarray(left), atol=1e-7)
    np.testing.assert_allclose(
        np.asarray(accept), [float(lm_common.accept_probability(p, q, d)) for d in range(11)], atol=1e-7)
    # equal distributions: every draft is kept, and the residual is p itself
    assert float(lm_common.accept_probability(p, p, 3)) == 1.0
    np.testing.assert_array_equal(np.asarray(lm_common.residual(p, p)), np.asarray(p))


def test_the_step_draws_what_the_rule_says():
    """`verify` over 60,000 keys, each with a draft drawn from q: the
    token after the last emitted one comes out as p, the share kept as
    sum min(p, q), and the second token is a draw from the second row."""
    keys = jax.random.split(jax.random.key(12), 3)
    logits = 1.5 * jax.random.normal(keys[0], (2, 6))
    draft_logits = 1.5 * jax.random.normal(keys[1], (6,))
    temperature = jnp.float32(0.8)

    def one(key):
        key_draft, key_verify = jax.random.split(key)
        draft = lm_common.sample(draft_logits, key_draft, temperature)
        return lm_common.verify(logits, draft_logits, draft, key_verify, temperature)

    kept, first, second = jax.vmap(one)(jax.random.split(keys[2], 60000))
    p = np.asarray(jax.nn.softmax(logits / temperature, axis=-1))
    q = np.asarray(jax.nn.softmax(draft_logits / temperature))
    np.testing.assert_allclose(np.bincount(np.asarray(first), minlength=6) / 60000, p[0], atol=0.01)
    np.testing.assert_allclose(np.bincount(np.asarray(second), minlength=6) / 60000, p[1], atol=0.01)
    assert float(np.mean(np.asarray(kept))) == pytest.approx(np.minimum(p[0], q).sum(), abs=0.01)


@pytest.mark.parametrize("steps", [1, 2, 7, 40])
def test_exactly_as_many_ids_as_asked_for_whatever_was_kept(steps):
    params, ids = ke.init_params(TINY, jax.random.key(0)), prompt_ids(TINY)
    for seed in (3, 4):
        _, dec = generate(TINY, params, ids, 1, steps=steps, seed=seed, collect=False)
        taken, _, accepted, _ = np.asarray(dec.counts).tolist()
        assert dec.ids.shape == (steps,)
        assert 1 + taken + accepted in (steps, steps + 1) and taken <= max(steps - 1, 0)


def test_the_same_seed_gives_the_same_ids_and_drafting_other_draws():
    params, ids = ke.init_params(TINY, jax.random.key(0)), prompt_ids(TINY)
    runs = [np.asarray(generate(TINY, params, ids, d, seed=s, collect=False)[1].ids).tolist()
            for d, s in ((1, 3), (1, 3), (1, 5), (0, 3))]
    assert runs[0] == runs[1] and runs[0] != runs[2] and runs[0] != runs[3]
    assert runs[0][0] == runs[3][0]  # the first id is the prefill's logits' either way


def test_at_temperature_zero_drafting_changes_no_id():
    """Greedy: a draft is kept iff it is the main model's largest, so the
    ids are those of one-token steps, in fewer steps."""
    params, ids = ke.init_params(TINY, jax.random.key(0)), prompt_ids(TINY)
    _, plain = generate(TINY, params, ids, 0, temperature=0.0, collect=False)
    _, drafted = generate(TINY, params, ids, 1, temperature=0.0, collect=False)
    np.testing.assert_array_equal(np.asarray(drafted.ids), np.asarray(plain.ids))
    assert int(drafted.counts[0]) <= NEW - 1


def test_a_served_request_collects_nothing_draws_the_same_ids_and_gets_its_state_back(drafting):
    params, ids, _, collected = drafting
    pre = ke.prefill(TINY, params, ids, cache_len=PROMPT + NEW)
    # the served prefill is the collecting one (PR 64): the decode is what collects nothing
    assert pre.chosen.shape[1:] == (PROMPT, TINY.num_experts_per_tok)
    shapes = ke.state_shapes(TINY, PROMPT + NEW, jnp.float32)
    assert {k: (v.shape, v.dtype) for k, v in pre.cache.items()} == {
        k: (v.shape, v.dtype) for k, v in shapes.items()}
    assert shapes["ring"].shape == (4, 2, 2, 16, 16) and shapes["kv"].shape == (2, 2, 2, 93, 16)
    dec = ke.decode(
        TINY, params, pre.cache, pre.logits, jnp.int32(PROMPT), jax.random.key(3),
        jnp.float32(1.0), steps=NEW, draft_tokens=1)
    assert dec.kept is None and set(dec.cache) == set(shapes)
    np.testing.assert_array_equal(np.asarray(dec.ids), np.asarray(collected.ids))
    np.testing.assert_array_equal(np.asarray(dec.counts), np.asarray(collected.counts))


def test_another_temperature_builds_no_program():
    params, ids = ke.init_params(TINY, jax.random.key(0)), prompt_ids(TINY)
    generate(TINY, params, ids, 1, temperature=0.7, collect=False)
    before = ke.decode._cache_size()
    generate(TINY, params, ids, 1, temperature=0.0, collect=False)
    assert ke.decode._cache_size() == before


def test_two_drafts_a_step_are_refused():
    params, ids = ke.init_params(TINY, jax.random.key(0)), prompt_ids(TINY)
    with pytest.raises(ValueError, match="drafts one token a step"):
        generate(TINY, params, ids, 2, collect=False)


# --- the cut ---------------------------------------------------------------------


def test_the_eight_ranks_shares_of_a_layer_add_up_to_the_uncut_layer():
    """The share test: each rank's expert layer gives the shared expert's
    output plus its own experts' part; summed over the eight ranks with
    the shared expert, the mixer and the residual counted once, that is
    the uncut reference's layer (a window layer: index 1)."""
    whole = dataclasses.replace(TINY, ep_size=1, ep_rank=0)
    params = ke.init_params(whole, jax.random.key(3))
    block = params["layers"][1]
    h = jax.random.normal(jax.random.key(4), (PROMPT, whole.hidden_size))
    want, _ = ref.layer(ref.Sizes.of(whole), block, h, True, list(range(whole.num_experts)))

    x = rms_norm(h, block["mixer_norm"], whole.rms_norm_eps)
    after_mixer = h + ke.mixer_whole(whole, block["attn"], x, True)[0]
    x = rms_norm(after_mixer, block["ffn_norm"], whole.rms_norm_eps)
    shared = swiglu(x, block["moe"]["shared"])
    routed, pairs = 0.0, 0
    for rank in range(8):
        cfg = dataclasses.replace(TINY, ep_size=8, ep_rank=rank)
        mine = expert_range(whole.num_experts, rank, 8)
        part = {"moe": dict(block["moe"], experts=jax.tree_util.tree_map(
            lambda w: w[mine.start:mine.stop], block["moe"]["experts"]))}
        out, _, sizes = ke._feed_forward(cfg, part, x)
        routed = routed + (out - shared)
        pairs += int(sizes.sum())
    got = after_mixer + shared + routed
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    assert pairs == PROMPT * whole.num_experts_per_tok  # every pair fell on exactly one rank


def test_the_cut_holds_the_parameters_the_issue_counted():
    cfg = get_config("k-exaone-ep8-5l")
    assert (cfg.num_hidden_layers, len(cfg.held_experts), cfg.vocab_held) == (5, 16, 19200)
    assert [cfg.is_window(i) for i in range(5)] == [True, True, True, False, True]
    assert [cfg.is_dense(i) for i in range(5)] == [True, False, False, False, False]
    assert (cfg.window_layers, cfg.full_layers, cfg.sparse_layers) == (4, 1, 4)
    assert cfg.ring_positions == 136  # 128 + 1, rounded up to a multiple of 8
    shapes = ke.param_shapes(cfg)
    mixer = ke.count_params(shapes["layers"][1]["attn"])
    assert mixer == 2 * 6144 * 8192 + 2 * 6144 * 1024 + 2 * 128            # 113.25 M
    assert ke.count_params(shapes["layers"][0]["mlp"]) == 3 * 6144 * 18432   # 339.74 M
    sparse = ke.count_params(shapes["layers"][1])
    assert sparse == mixer + 2 * 6144 + 6144 * 128 + 128 + 17 * 3 * 6144 * 2048  # 755.8 M
    assert ke.count_params(shapes["mtp"]) == sparse + 2 * 6144 * 6144 + 3 * 6144
    assert ke.param_count(cfg) == 4_543_318_144                               # 9.09 GB
    # the published model: 236 B without the MTP module, as its name says
    whole = ke.KExaoneConfig()
    total = ke.param_count(whole)
    assert 236.0e9 < total - ke.count_params(ke.param_shapes(whole)["mtp"]) < 237.0e9


def test_a_form_that_is_not_written_is_refused():
    with pytest.raises(ValueError, match="one MTP module"):
        ke.KExaoneConfig(num_nextn_predict_layers=2)
    with pytest.raises(ValueError, match="layer pattern"):
        ke.KExaoneConfig(sliding_window_pattern="LLSG")
