"""GLM-5.2 (`models/glm_dsa.py`, `models/dsa.py`) against its float32
reference (`reference/glm_dsa.py`) at a small size on the CPU, seeded
weights: latent attention with a query latent, the 12 / 8 / 16 split
(192 / 64 / 256 published) and the rotation in pairs against a loop
written out; the indexer and its exact selection in both forms; a
`shared` layer attending by the selection of the `full` layer below it;
the whole model; the prefill in parts against the prefill in one pass;
prefill then decode through both caches, with and without drafting; the
sixteen ranks' shares of a layer against the uncut layer; the keys the
programs count against their closed forms."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.models import dsa, lm_common, mla
from comfyui_distributed_tpu.models import glm_dsa as glm
from comfyui_distributed_tpu.models.lm_common import (
    apply_rope, apply_rope_pairs, rms_norm, rope_tables, swiglu)
from comfyui_distributed_tpu.models.registry import create_model, get_config
from comfyui_distributed_tpu.ops.attention import route_log
from comfyui_distributed_tpu.parallel.sharding import expert_range
from comfyui_distributed_tpu.reference import glm_dsa as ref

TINY = get_config("tiny-glm-dsa")
# 53 positions in parts of 16: three whole parts and five left over; the selection
# (8 positions) binds from the ninth on
PROMPT, NEW = 53, 24


def rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


def prompt_ids(cfg, seed=1, tokens=PROMPT):
    return jax.random.randint(jax.random.key(seed), (tokens,), 0, cfg.vocab_held)


@pytest.fixture(scope="module")
def params():
    return glm.init_params(TINY, jax.random.key(0))


def masks_of(kept, size):
    return [np.asarray(dsa.as_mask(dsa.Selection(*layer), size)) for layer in kept]


# --- the blocks ----------------------------------------------------------------


def test_the_rotation_in_pairs_turns_channels_2i_and_2i_plus_1_together():
    x = jax.random.normal(jax.random.key(1), (5, 3, 8))
    cos, sin = rope_tables(1e4, 8, jnp.arange(5) + 2)
    got = np.asarray(apply_rope_pairs(x, cos, sin))
    for t in range(5):
        for i in range(4):
            angle = (t + 2) * 1e4 ** (-2 * i / 8)
            a, b = np.asarray(x[t, :, 2 * i]), np.asarray(x[t, :, 2 * i + 1])
            np.testing.assert_allclose(got[t, :, 2 * i], a * math.cos(angle) - b * math.sin(angle),
                                       rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(got[t, :, 2 * i + 1], b * math.cos(angle) + a * math.sin(angle),
                                       rtol=1e-5, atol=1e-5)
    # the same rotation as the halves' form on the channels put in its order
    order = np.r_[0:8:2, 1:8:2]
    np.testing.assert_allclose(
        got[..., order], np.asarray(apply_rope(x[..., order], cos, sin)), rtol=1e-6, atol=1e-6)


def test_latent_attention_is_the_loop_written_out(params):
    """One query at a time over every position before it (a selection of
    everything): the query latent under its norm, 12 nope + 8 rope wide
    queries, 16 wide values, scale (12 + 8)^-1/2."""
    cfg, p = TINY, params["layers"][0]["attn"]
    tokens = 7  # under `index_topk`: every visible position is chosen
    x = jax.random.normal(jax.random.key(2), (tokens, cfg.hidden_size))
    positions = jnp.arange(tokens)
    cache = glm.zeros(glm.state_shapes(cfg, tokens, jnp.float32))
    out, cache, _ = glm.attention(cfg, params["layers"][0], x, cache, 0, 0, positions, None)

    rope = rope_tables(cfg.rope_theta, cfg.qk_rope_head_dim, positions)
    c_q = rms_norm(x @ p["w_dq"], p["q_norm"], cfg.rms_norm_eps)
    q = (c_q @ p["w_uq"]).reshape(tokens, cfg.num_attention_heads, 20)
    down = x @ p["w_dkv"]
    c = rms_norm(down[:, :24], p["kv_norm"], cfg.rms_norm_eps)
    r = apply_rope_pairs(down[:, 24:], *rope)
    np.testing.assert_allclose(
        np.asarray(cache["latents"][0]), np.asarray(jnp.concatenate([c, r], -1)), rtol=1e-5, atol=1e-5)
    q_rope = apply_rope_pairs(q[..., 12:], *rope)
    want = np.zeros((tokens, cfg.num_attention_heads, 16), np.float32)
    for t in range(tokens):
        for head in range(cfg.num_attention_heads):
            k_nope = c[:t + 1] @ p["w_uk"][:, head]                     # [t + 1, 12]
            scores = (k_nope @ q[t, head, :12] + r[:t + 1] @ q_rope[t, head]) / math.sqrt(20)
            want[t, head] = jax.nn.softmax(scores) @ (c[:t + 1] @ p["w_uv"][:, head])
    np.testing.assert_allclose(
        np.asarray(out), want.reshape(tokens, -1) @ np.asarray(p["w_o"]), rtol=2e-4, atol=2e-4)


def indexer_inputs(params, tokens, size):
    cfg, block = TINY, params["layers"][0]
    x = jax.random.normal(jax.random.key(5), (size, cfg.hidden_size))
    rope = rope_tables(cfg.rope_theta, cfg.qk_rope_head_dim, jnp.arange(size))
    c_q = rms_norm(x @ block["attn"]["w_dq"], block["attn"]["q_norm"], cfg.rms_norm_eps)
    cached = dsa.keys(block["indexer"], x, rope, cfg.rms_norm_eps)
    q, w = dsa.queries(block["indexer"], c_q, x, rope, cfg.index_n_heads)
    return x, c_q, cached, q[size - tokens:], w[size - tokens:], jnp.arange(size - tokens, size)


@pytest.mark.parametrize("tokens", [1, 2, 40])
def test_the_indexer_and_its_selection_are_the_references_in_either_form(params, tokens):
    """The last `tokens` queries of 40 positions: two or fewer take the
    masked form, forty the gathered one; the reference sorts."""
    x, c_q, cached, q, w, positions = indexer_inputs(params, tokens, 40)
    selection = dsa.select(q, w, cached, positions, TINY.index_topk)
    assert (selection.chosen is None) == (dsa.form(tokens) == "masked") == (tokens <= 2)
    got = np.asarray(dsa.as_mask(selection, 40))
    want = np.asarray(ref.selection(
        ref.Sizes.of(TINY), params["layers"][0]["indexer"], c_q, x, TINY.qk_rope_head_dim,
        row_block=16))[40 - tokens:]
    assert (got == want).all()
    for t, row in zip(np.asarray(positions), got):
        assert row.sum() == min(t + 1, TINY.index_topk)      # exactly, once more are visible
        assert not row[t + 1:].any()                         # never a position after the query's
        if t < TINY.index_topk:
            assert row[:t + 1].all()                         # all of them before


def test_the_gathered_form_sorts_the_shortest_rung_that_holds_the_last_query(params):
    """The ladder's rungs are the powers of two from twice `index_topk`
    up and then the cache whole; a part's queries score and sort the
    first that holds them, and choose what the whole cache would give."""
    assert dsa.length_ladder(32896, 2048) == (4096, 8192, 16384, 32768, 32896)
    assert dsa.length_ladder(32768, 2048) == (4096, 8192, 16384, 32768)
    assert dsa.length_ladder(72, 8) == (16, 32, 64, 72) and dsa.length_ladder(10, 8) == (10,)
    _, _, cached, q, w, _ = indexer_inputs(params, 16, 72)
    whole = jax.jit(lambda q, w, at: dsa.by_rows(
        lambda q, w, at: dsa.top(dsa.scores(q, w, cached, at), 8), 16, q, w, at))
    for last, rung in ((15, 16), (16, 32), (31, 32), (40, 64), (64, 72), (71, 72)):
        positions = jnp.arange(last - 15, last + 1)
        text = jax.jit(dsa.select, static_argnums=4).lower(q, w, cached, positions, 8).as_text()
        assert all(f"x{length}x" in text or f"<{length}x" in text for length in (16, 32, 64, 72))
        got = dsa.select(q, w, cached, positions, 8)
        want = whole(q, w, positions)
        assert (np.asarray(dsa.as_mask(got, 72)) == np.asarray(dsa.as_mask(want, 72))).all(), rung


def test_the_indexers_scores_hold_no_negative_zero():
    """A zero of either sign is one value to the reference's sort and two
    to a comparison of bit patterns: `scores` hands on +0 alone."""
    q = jnp.ones((3, 2, 4)).at[0].set(-1.0)
    index = dsa.scores(q, -jnp.ones((3, 2)), jnp.ones((5, 4)), jnp.arange(2, 5))
    assert float(index[0, 0]) == 0.0 and not np.signbit(np.asarray(index[0, :3])).any()
    assert (np.asarray(index[1, :4]) == -8.0).all() and np.isneginf(np.asarray(index[1, 4]))


def test_equal_scores_go_to_the_lower_position_in_both_forms():
    index = jnp.asarray(np.random.RandomState(1).randint(-2, 3, size=(6, 40)).astype(np.float32))
    index = index.at[:, 30:].set(-jnp.inf).at[0].set(0.0).at[1, 5:].set(-jnp.inf)
    for k in (1, 4, 8, 29, 64):
        gathered = np.asarray(dsa.as_mask(dsa.top(index, k), 40))
        masked = np.asarray(dsa.above_threshold(index, k).counts)
        order = np.argsort(-np.asarray(index), axis=1, kind="stable")[:, :k]
        want = np.zeros((6, 40), bool)
        np.put_along_axis(want, order, True, axis=1)
        want &= np.asarray(index) > -np.inf
        assert (gathered == want).all() and (masked == want).all(), k


@pytest.mark.parametrize("tokens", [2, 40])
def test_attention_over_the_chosen_rows_is_the_masked_form(params, tokens):
    """Every form that stays in the tree against `mla.absorbed` under the
    selection's mask."""
    cfg, p = TINY, params["layers"][0]["attn"]
    _, _, cached, q, w, positions = indexer_inputs(params, tokens, 40)
    selection = dsa.select(q, w, cached, positions, cfg.index_topk)
    keys = jax.random.split(jax.random.key(6), 3)
    q_nope = jax.random.normal(keys[0], (tokens, cfg.num_attention_heads, cfg.qk_nope_head_dim))
    q_rope = jax.random.normal(keys[1], (tokens, cfg.num_attention_heads, cfg.qk_rope_head_dim))
    cache = jax.random.normal(keys[2], (40, cfg.cache_width))
    with route_log() as routes:
        got = dsa.attend(q_nope, q_rope, cache, selection, p["w_uk"], p["w_uv"], 0.25)
    want = mla.absorbed(
        q_nope, q_rope, cache, dsa.as_mask(selection, 40), p["w_uk"], p["w_uv"], 0.25)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    assert routes == [f"dsa-masked 2x40 k40 h4 f32" if tokens == 2 else "dsa-gathered 40x40 k8 h4 f32"]


def test_a_shared_layer_attends_by_the_selection_below_and_holds_no_indexer(params):
    cfg = TINY
    assert [cfg.is_full(i) for i in cfg.layers] == [True, False, False, False, True]
    assert [("indexer" in block) for block in params["layers"]] == [
        True, False, False, False, True]
    assert "indexer" in params["mtp"]["layer"]
    shapes = glm.state_shapes(cfg, 64, jnp.float32)
    assert (len(shapes["latents"]), len(shapes["index"])) == (6, 3)
    # handed another selection, a shared layer gives another output; a full one its own
    h = jax.random.normal(jax.random.key(7), (PROMPT, cfg.hidden_size))
    positions = jnp.arange(PROMPT)
    cache = glm.zeros(glm.state_shapes(cfg, PROMPT, jnp.float32))
    _, cache, below, _, _ = glm._layer(cfg, params["layers"][0], h, cache, 0, 0, positions, None)
    recent = dsa.Selection(
        jnp.maximum(positions[:, None] - jnp.arange(8)[None], 0).astype(jnp.int32),
        (positions[:, None] - jnp.arange(8)[None]) >= 0)
    outs = {}
    for name, handed in (("below", below), ("recent", recent)):
        for slot in (1, 4):
            out, _, used, _, _ = glm._layer(
                cfg, params["layers"][slot], h, cache, slot, 1, positions, handed)
            outs[name, slot] = np.asarray(out)
            assert (used is handed) == (slot == 1)
    assert np.abs(outs["below", 1] - outs["recent", 1]).max() > 1e-3
    np.testing.assert_array_equal(outs["below", 4], outs["recent", 4])


# --- the model against the reference ---------------------------------------------


@pytest.mark.parametrize("rank, size", [(0, 16), (15, 16), (0, 1)])
def test_prefill_in_parts_and_plain_decode_through_both_caches_match_the_reference(rank, size):
    cfg = dataclasses.replace(TINY, ep_rank=rank, ep_size=size)
    params = glm.init_params(cfg, jax.random.key(0))
    ids = prompt_ids(cfg)
    prefill = glm.prefill(cfg, params, ids, cache_len=PROMPT + NEW, collect=True)
    assert prefill.loads.shape[0] == prefill.keys.shape[0] == 4  # three parts and what is left
    decode = glm.decode(
        cfg, params, prefill.cache, prefill.logits, jnp.int32(PROMPT), jax.random.key(9),
        jnp.float32(1.0), steps=NEW, collect=True)
    full = np.concatenate([np.asarray(ids), np.asarray(decode.ids)])
    sizes, held = ref.Sizes.of(cfg), list(cfg.held_experts)
    logits, _, chosen, selections = ref.forward(sizes, params, full, held, row_block=16)
    assert rel_l2(prefill.logits, logits[PROMPT - 1]) < 2e-5
    assert rel_l2(decode.kept["logits"], logits[PROMPT:]).max() < 2e-5
    assert (np.sort(prefill.kept["chosen"], -1) == np.sort(chosen[:, :PROMPT], -1)).all()
    assert (np.sort(decode.kept["chosen"].transpose(1, 0, 2), -1)
            == np.sort(chosen[:, PROMPT:], -1)).all()
    total = PROMPT + NEW
    for mine, later, want in zip(masks_of(prefill.kept["selections"], total),
                                 masks_of(decode.kept["selections"], total), selections):
        assert (mine == np.asarray(want[:PROMPT])).all()
        assert (later == np.asarray(want[PROMPT:])).all()


def test_the_prefill_in_parts_is_the_prefill_in_one_pass(params):
    """At a length that is no whole number of parts: the same logits and
    the same two caches, whatever the part."""
    ids = prompt_ids(TINY)
    parts = glm.prefill(TINY, params, ids, cache_len=PROMPT + 3)
    assert lm_common.parts_of(PROMPT, TINY.prefill_part) == (3, 5)
    for part in (64, 53, 7):
        cfg = dataclasses.replace(TINY, prefill_part=part)
        one = glm.prefill(cfg, params, ids, cache_len=PROMPT + 3)
        np.testing.assert_allclose(
            np.asarray(parts.logits), np.asarray(one.logits), rtol=2e-5, atol=2e-5)
        for a, b in zip(jax.tree_util.tree_leaves(parts.cache),
                        jax.tree_util.tree_leaves(one.cache)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)
        assert int(one.keys[:, 0].sum()) == int(parts.keys[:, 0].sum())
        assert int(one.keys[:, 1].sum()) == int(parts.keys[:, 1].sum())


@pytest.fixture(scope="module")
def drafting(params):
    ids = prompt_ids(TINY)
    prefill = glm.prefill(TINY, params, ids, cache_len=PROMPT + NEW)
    decode = glm.decode(
        TINY, params, prefill.cache, prefill.logits, jnp.int32(PROMPT), jax.random.key(9),
        jnp.float32(1.0), steps=NEW, collect=True, draft_tokens=1)
    full = np.concatenate([np.asarray(ids), np.asarray(decode.ids)])
    return params, full, decode


def test_a_drafting_decode_matches_the_reference_at_every_position_it_verified(drafting):
    """Row 0 of every step, row 1 where the draft was kept, the draft
    logits and the module's own selection: a dropped draft leaves no
    trace in either cache, or the positions after it would differ."""
    params, full, decode = drafting
    sizes, held = ref.Sizes.of(TINY), list(TINY.held_experts)
    logits, h, _, selections = ref.forward(sizes, params, full, held, row_block=16)
    drafts, _, chosen = ref.mtp_forward(sizes, params, h, full, held, row_block=16)
    kept = jax.tree_util.tree_map(np.asarray, decode.kept)
    steps = int(decode.counts[0])
    at, accepted = kept["position"][:steps], kept["accepted"][:steps]
    assert 0 < accepted.sum() < steps  # both fates happened
    assert rel_l2(kept["logits"][:steps, 0], logits[at]).max() < 2e-5
    assert rel_l2(kept["logits"][:steps, 1][accepted], logits[at[accepted] + 1]).max() < 2e-5
    assert rel_l2(kept["draft_logits"][:steps], drafts[at - 1]).max() < 2e-5
    total = PROMPT + NEW
    for layer, want in zip(kept["selections"], selections):
        mine = masks_of([tuple(a[:steps, 0] for a in layer)], total)[0]
        assert (mine == np.asarray(want[at])).all()
    module = masks_of([tuple(a[:steps] for a in kept["draft_selection"])], total)[0]
    assert (module[:, :total - 1] == np.asarray(chosen[at - 1])).all()


def test_the_counts_are_the_steps_the_drafts_kept_and_the_keys_seen(drafting):
    _, _, decode = drafting
    steps, drafted, accepted, read = (int(n) for n in decode.counts)
    assert drafted == steps and 1 + steps + accepted in (NEW, NEW + 1)
    assert 0 < read <= steps * 2 * 2 * (TINY.sparse_layers + 1)
    kept = np.asarray(decode.kept["position"][:steps])
    # two positions a step in five layers, n and n + 1; the module's two end at n - 1 or n
    visible = sum(2 * n + 3 for n in kept)
    assert decode.keys[0, :5].tolist() == [visible] * 5
    assert decode.keys[1, :5].tolist() == [steps * 2 * TINY.index_topk] * 5
    waiting = np.r_[1, 1 + np.asarray(decode.kept["accepted"][:steps - 1])]
    assert int(decode.keys[0, 5]) == sum(
        (n - w + 1) + (n - w + 2) for n, w in zip(kept, waiting))


def test_the_keys_a_request_reports_are_their_closed_forms(params):
    lm = create_model("tiny-glm-dsa")
    lm.init(jax.random.key(0))
    ids = prompt_ids(TINY)
    prefill = lm.prefill(params, ids, PROMPT + NEW)
    decode = lm.decode(params, prefill.cache, prefill.logits, PROMPT, jax.random.key(9), NEW, 1.0)
    said = lm.report(PROMPT, NEW, PROMPT + NEW, *jax.device_get(lm.read_back(prefill, decode)))
    total, k = PROMPT + NEW, TINY.index_topk
    # every position once, in five layers: t + 1 visible, min(t + 1, 8) read
    assert said["keys_visible"] == 5 * total * (total + 1) // 2
    assert said["keys_selected"] == 5 * (k * (k + 1) // 2 + (total - k) * k)
    assert (said["prefill_parts"], said["index_topk"], said["indexer_layers"],
            said["index_shared_layers"], said["prefill_part"]) == (4, 8, 2, 3, 16)
    assert (said["prefill_sparse_attention_form"], said["decode_sparse_attention_form"]) == (
        "gathered", "masked")
    assert said["prefill_selection_form"] == "sort"    # `lax.top_k`, off a TPU
    assert said["cache_bytes"] == total * (6 * 32 + 3 * 16) * 4
    assert said["indexer_cache_bytes"] == total * 3 * 16 * 4 and said["state_bytes"] == 0
    assert said["prefill_routed_pairs"] == PROMPT * 4 * 4
    assert said["decode_layer_passes"] == NEW * 5 and said["decode_steps"] == NEW


@pytest.mark.parametrize("wrong", [
    {"relu": False}, {"index_halves": True}, {"share_above": True}, {"blind_part": 16},
    {"index_topk": 4}])
def test_a_reference_with_one_thing_wrong_is_another_model(params, wrong):
    """The parity check's controls at the small size: each moves the
    logits far beyond what the system's arithmetic does."""
    ids = np.asarray(prompt_ids(TINY))
    held = list(TINY.held_experts)
    right, _, _, _ = ref.forward(ref.Sizes.of(TINY), params, ids, held, row_block=16)
    sizes = dataclasses.replace(ref.Sizes.of(TINY), **wrong)
    got, _, _, _ = ref.forward(sizes, params, ids, held, row_block=16)
    assert np.median(rel_l2(got[TINY.index_topk:], right[TINY.index_topk:])) > 0.05


def test_bfloat16_stays_near_the_reference_and_float8_does_not():
    params = glm.init_params(TINY, jax.random.key(0), jnp.bfloat16)
    ids = prompt_ids(TINY)
    prefill = glm.prefill(TINY, params, ids, cache_len=PROMPT)
    held = list(TINY.held_experts)
    want, _, _, _ = ref.forward(
        ref.Sizes.of(TINY), params, np.asarray(ids), held, row_block=16, positions=[PROMPT - 1])
    low, _, _, _ = ref.forward(
        ref.Sizes.of(TINY), params, np.asarray(ids), held, row_block=16, positions=[PROMPT - 1],
        round_to=jnp.float8_e4m3fn)
    assert rel_l2(prefill.logits, want[0]) < 0.1 < rel_l2(low[0], want[0])


# --- the served contract -----------------------------------------------------------


@pytest.mark.parametrize("steps", [1, 2, 7])
def test_exactly_as_many_ids_as_asked_for_whatever_was_kept(params, steps):
    ids = prompt_ids(TINY)
    prefill = glm.prefill(TINY, params, ids, cache_len=PROMPT + steps)
    decode = glm.decode(
        TINY, params, prefill.cache, prefill.logits, jnp.int32(PROMPT), jax.random.key(3),
        jnp.float32(1.0), steps=steps, draft_tokens=1)
    assert decode.ids.shape == (steps,) and decode.kept is None
    assert all(leaf.shape[0] == PROMPT + steps for leaf in decode.cache["latents"])


def test_at_temperature_zero_drafting_changes_no_id(params):
    ids = prompt_ids(TINY)
    out = []
    for draft_tokens in (0, 1):
        prefill = glm.prefill(TINY, params, ids, cache_len=PROMPT + NEW)
        out.append(np.asarray(glm.decode(
            TINY, params, prefill.cache, prefill.logits, jnp.int32(PROMPT), jax.random.key(3),
            jnp.float32(0.0), steps=NEW, draft_tokens=draft_tokens).ids))
    np.testing.assert_array_equal(*out)


def test_two_drafts_a_step_are_refused():
    lm = create_model("tiny-glm-dsa")
    assert lm.draft_tokens_max == 1
    with pytest.raises(ValueError, match="drafts at most 1 a step"):
        lm.decode(None, None, None, 0, None, 4, 1.0, draft_tokens=2)
    with pytest.raises(ValueError, match="not 2"):
        glm.decode(TINY, None, {}, None, 0, None, 1.0, steps=4, draft_tokens=2)


def test_a_first_layer_that_attends_by_an_index_not_held_is_refused():
    with pytest.raises(ValueError, match="index of a layer that is not held"):
        dataclasses.replace(TINY, first_layer=3)
    with pytest.raises(ValueError, match="one MTP module"):
        dataclasses.replace(TINY, num_nextn_predict_layers=2)


# --- the cut -----------------------------------------------------------------------


def test_the_sixteen_ranks_shares_of_a_layer_add_up_to_the_uncut_layer():
    """The share test: each rank's expert layer gives the shared expert's
    output plus its own experts' part; summed over the sixteen ranks
    with the shared expert, attention and the residual counted once,
    that is the uncut reference's layer (layer 6: sparse, with an
    indexer)."""
    whole = dataclasses.replace(TINY, ep_size=1, ep_rank=0)
    params = glm.init_params(whole, jax.random.key(3))
    block = params["layers"][4]
    h = jax.random.normal(jax.random.key(4), (PROMPT, whole.hidden_size))
    want, _, _ = ref.layer(
        ref.Sizes.of(whole), block, h, None, list(range(whole.n_routed_experts)), row_block=16)

    cache = glm.zeros(glm.state_shapes(whole, PROMPT, jnp.float32))
    x = rms_norm(h, block["attn_norm"], whole.rms_norm_eps)
    out, _, _ = glm.attention(whole, block, x, cache, 4, 1, jnp.arange(PROMPT), None)
    after = h + out
    x = rms_norm(after, block["ffn_norm"], whole.rms_norm_eps)
    shared = swiglu(x, block["moe"]["shared"])
    routed, pairs = 0.0, 0
    for rank in range(16):
        cfg = dataclasses.replace(TINY, ep_size=16, ep_rank=rank)
        mine = expert_range(whole.n_routed_experts, rank, 16)
        part = {"moe": dict(block["moe"], experts=jax.tree_util.tree_map(
            lambda w: w[mine.start:mine.stop], block["moe"]["experts"]))}
        out, _, sizes = glm._feed_forward(cfg, part, x)
        routed = routed + (out - shared)
        pairs += int(sizes.sum())
    got = after + shared + routed
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    assert pairs == PROMPT * whole.num_experts_per_tok  # every pair fell on exactly one rank


def test_the_cut_holds_the_parameters_the_issue_counted():
    cfg = get_config("glm-5.2-ep16-5l")
    assert (cfg.num_hidden_layers, len(cfg.held_experts), cfg.vocab_held) == (5, 16, 19360)
    assert list(cfg.layers) == [2, 3, 4, 5, 6]
    assert [cfg.is_full(i) for i in cfg.layers] == [True, False, False, False, True]
    assert [cfg.is_dense(i) for i in cfg.layers] == [True, False, False, False, False]
    whole = type(cfg)()
    full = [i for i in range(78) if whole.is_full(i)]
    assert full == [0, 1, 2] + list(range(6, 78, 4)) and whole.is_full(78)  # the module's place
    assert glm.param_count(cfg) == 4_774_740_992
    shapes = glm.param_shapes(cfg)
    count = glm.count_params
    assert count(shapes["layers"][0]["attn"]) == 165_022_208
    assert count(shapes["layers"][0]["indexer"]) == 9_371_904
    assert count(shapes["layers"][0]) == 400_898_816
    assert count(shapes["layers"][1]) == 808_336_128 and count(shapes["layers"][4]) == 817_708_032
    assert count(shapes["mtp"]) == 893_223_936
    state = glm.state_shapes(cfg, 32896, jnp.bfloat16)
    per_position = sum(leaf.shape[1] * 2 for leaf in (*state["latents"], *state["index"]))
    assert per_position == 6 * 1152 + 3 * 256 == 7680
