"""dots3-note-prev's text path (`models/dots3.py`) against its float32
reference (`reference/dots3.py`) at a small size on the CPU, seeded
weights: latent attention of two kinds in one model (full layers under an
index of their own, sliding layers under a band), the rescale, the gate a
head; the prefill's logits; prefill then decode through the two caches
and the three rings; the prefill in parts against the prefill in one
part (a sliding layer's tail handed from part to part); a decode past the
ring's length; the ranks' shares of a layer against the uncut layer; the
parameters the issue counted; controls that have to fail; the state tree
as `report` gives it.

Tolerances. Float32 against float32 in another order of operations (the
absorbed form, the ring, blocks of rows): 2e-5 relative L2 of a row of
logits, GLM-5.2's test's, which a float32 model of five layers reads
under by a factor of ten. A control moves the median by more than 0.005."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.models import dots3, dsa, lm_common, mla
from comfyui_distributed_tpu.models.lm_common import apply_rope_pairs, rms_norm, rope_tables, swiglu
from comfyui_distributed_tpu.models.registry import create_model, get_config
from comfyui_distributed_tpu.parallel.sharding import expert_range
from comfyui_distributed_tpu.reference import dots3 as ref

TINY = get_config("tiny-dots3")
# 53 positions in parts of 16: three whole parts and five left over; the selection (8
# positions) binds from the ninth on, the window (5) from the sixth; 24 new tokens wrap the
# ring of 8 three times
PROMPT, NEW = 53, 24
TOLERANCE = 2e-5


def rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


def prompt_ids(cfg, seed=1, tokens=PROMPT):
    return jax.random.randint(jax.random.key(seed), (tokens,), 0, cfg.vocab_held)


@pytest.fixture(scope="module")
def params():
    return dots3.init_params(TINY, jax.random.key(0))


def masks_of(kept, size):
    return [np.asarray(dsa.as_mask(dsa.Selection(*layer), size)) for layer in kept]


@pytest.fixture(scope="module")
def run(params):
    """One request through both programs, everything kept, and the
    reference's one pass over the final ids."""
    ids = prompt_ids(TINY)
    prefill = dots3.prefill(TINY, params, ids, cache_len=PROMPT + NEW, collect=True)
    decode = dots3.decode(
        TINY, params, prefill.cache, prefill.logits, jnp.int32(PROMPT), jax.random.key(9),
        jnp.float32(1.0), steps=NEW, collect=True)
    full = np.concatenate([np.asarray(ids), np.asarray(decode.ids)])
    want = ref.forward(ref.Sizes.of(TINY), params, full, list(TINY.held_experts), row_block=16)
    return prefill, decode, full, want


# --- the blocks ----------------------------------------------------------------


def test_the_two_kinds_differ_in_every_size_inside_one_model(params):
    full, sliding = TINY.full, TINY.sliding
    assert (full.heads, full.rank, full.nope, full.rope, full.value) == (4, 16, 8, 8, 8)
    assert (sliding.heads, sliding.rank, sliding.nope, sliding.rope, sliding.value) == (
        2, 32, 12, 8, 8)
    assert full.theta == 8e7 and sliding.theta == 5e4
    assert full.s_q == sliding.s_q == 2 ** 0.5 and (full.s_kv, sliding.s_kv) == (2.0, 2 ** 0.5)
    assert [TINY.is_full(i) for i in TINY.layers] == [True, True, False, False, False]
    assert ["indexer" in block for block in params["layers"]] == [True, True, False, False, False]
    assert ["mlp" in block for block in params["layers"]] == [True, False, False, False, False]
    published = get_config("dots3-note-prev-ep8-5l")
    assert published.full == (128, 1024, 512, 128, 64, 128, 8e7, 5 ** 0.5, 10 ** 0.5)
    assert published.sliding == (64, 1024, 1024, 192, 64, 128, 5e4, 5 ** 0.5, 5 ** 0.5)
    whole = dots3.Dots3Config()
    assert [i for i in whole.layers if whole.is_full(i)] == [0] + list(range(1, 46, 4))
    assert (whole.full_layers, whole.window_layers) == (13, 33)


@pytest.mark.parametrize("before", [0, 3, 4])
def test_expanded_under_a_window_with_latents_before_the_sequence_is_the_band(params, before):
    """`mla.expanded(window=, before=)`: N queries over `before` + N
    keys, each seeing the 5 positions up to its own, against the loop
    written out."""
    kind, p = TINY.sliding, params["layers"][2]["attn"]
    tokens, window = 9, TINY.sliding_window_size
    keys = jax.random.split(jax.random.key(3), 3)
    rows = jax.random.normal(keys[0], (before + tokens, kind.cache_width))
    q_nope = jax.random.normal(keys[1], (tokens, kind.heads, kind.nope))
    q_rope = jax.random.normal(keys[2], (tokens, kind.heads, kind.rope))
    got = mla.expanded(
        q_nope, q_rope, rows[before:], p["w_uk"], p["w_uv"], 0.25, window=window,
        before=rows[:before] if before else None)
    want = np.zeros((tokens, kind.heads, kind.value), np.float32)
    for t in range(tokens):
        own = before + t
        seen = rows[max(own - window + 1, 0):own + 1]
        for head in range(kind.heads):
            k_nope = seen[:, :kind.rank] @ p["w_uk"][:, head]
            scores = 0.25 * (k_nope @ q_nope[t, head] + seen[:, kind.rank:] @ q_rope[t, head])
            want[t, head] = jax.nn.softmax(scores) @ (seen[:, :kind.rank] @ p["w_uv"][:, head])
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


def test_expanded_without_a_window_traces_to_what_it_was():
    """The three other callers' programs: no `window`, no `before`, the
    same jaxpr as a call of `causal_attention` written out."""
    from comfyui_distributed_tpu.ops.attention import causal_attention

    def as_it_was(q_nope, q_rope, latents, w_uk, w_uv):
        c_kv, k_rope = latents[:, :6], latents[:, 6:]
        k_nope = jnp.einsum("tc,chd->thd", c_kv, w_uk)
        v = jnp.einsum("tc,chd->thd", c_kv, w_uv)
        k_rope = jnp.broadcast_to(k_rope[:, None, :], (*k_nope.shape[:2], k_rope.shape[-1]))
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate([k_nope, k_rope], axis=-1)
        return causal_attention(q[None], k[None], v[None], scale=0.5)[0]

    shapes = [jax.ShapeDtypeStruct(s, jnp.float32) for s in (
        (7, 2, 4), (7, 2, 2), (7, 8), (6, 2, 4), (6, 2, 3))]
    now = jax.make_jaxpr(lambda *a: mla.expanded(*a, 0.5))(*shapes)
    assert str(now) == str(jax.make_jaxpr(as_it_was)(*shapes))


def test_latents_scale_multiplies_the_normed_latent_and_not_the_rope_key(params):
    p, kind = params["layers"][2]["attn"], TINY.sliding
    x = jax.random.normal(jax.random.key(4), (6, TINY.hidden_size))
    rope = rope_tables(kind.theta, kind.rope, jnp.arange(6))
    plain = mla.latents(p, x, rope, 1e-5, rotate=apply_rope_pairs)
    scaled = mla.latents(p, x, rope, 1e-5, rotate=apply_rope_pairs, scale=3.0)
    np.testing.assert_allclose(
        np.asarray(scaled[:, :kind.rank]), 3.0 * np.asarray(plain[:, :kind.rank]), rtol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(scaled[:, kind.rank:]), np.asarray(plain[:, kind.rank:]))


@pytest.mark.parametrize("tokens", [1, 3, 4, 5, 8, 9, 13, 53])
def test_a_ring_after_the_prefill_holds_the_newest_position_of_each_row(tokens):
    """`ring_of`: row s the newest position that is s modulo 8, of the
    4 the tail has (zero elsewhere: positions no later query sees)."""
    width = TINY.sliding.cache_width
    rows = np.arange(1, tokens + 1, dtype=np.float32)[:, None] * np.ones((1, width), np.float32)
    tail = np.zeros((TINY.tail_positions, width), np.float32)
    have = min(tokens, TINY.tail_positions)
    tail[TINY.tail_positions - have:] = rows[tokens - have:]
    ring = np.asarray(dots3.ring_of(TINY, jnp.asarray(tail), tokens))
    for s in range(TINY.ring_positions):
        held = tokens - 1 - (tokens - 1 - s) % TINY.ring_positions
        want = held + 1 if held >= max(tokens - TINY.tail_positions, 0) else 0
        assert (ring[s] == want).all(), (s, held)
    # every position the next query's window reaches is there
    for position in range(max(tokens - TINY.tail_positions, 0), tokens):
        assert (ring[position % TINY.ring_positions] == position + 1).all()


# --- the model against the reference ---------------------------------------------


def test_a_prefills_logits_at_the_last_position_are_the_references(run):
    prefill, _, _, (logits, chosen, selections, _) = run
    assert prefill.loads.shape[0] == prefill.keys.shape[0] == 4  # three parts and what is left
    assert rel_l2(prefill.logits, logits[PROMPT - 1]) < TOLERANCE
    assert (np.sort(prefill.kept["chosen"], -1) == np.sort(chosen[:, :PROMPT], -1)).all()
    for mine, want in zip(masks_of(prefill.kept["selections"], PROMPT + NEW), selections):
        assert (mine == np.asarray(want[:PROMPT])).all()


def test_b_prefill_then_decode_through_caches_and_rings_match_one_forward_pass(run):
    """Logits at every decoded position (not ids), the experts chosen
    and both full layers' selections."""
    _, decode, _, (logits, chosen, selections, _) = run
    assert rel_l2(decode.kept["logits"], logits[PROMPT:]).max() < TOLERANCE
    assert (np.sort(decode.kept["chosen"].transpose(1, 0, 2), -1)
            == np.sort(chosen[:, PROMPT:], -1)).all()
    for mine, want in zip(masks_of(decode.kept["selections"], PROMPT + NEW), selections):
        assert (mine == np.asarray(want[PROMPT:])).all()


@pytest.mark.parametrize("part", [64, 53, 7, 3])
def test_c_the_prefill_in_parts_is_the_prefill_in_one_part(params, part):
    """At a length that is no whole number of parts, and at a part
    shorter than the window (3 of 5: a tail made of two parts'
    latents): the same logits, caches and rings, whatever the part."""
    ids = prompt_ids(TINY)
    parts = dots3.prefill(TINY, params, ids, cache_len=PROMPT + 3)
    assert lm_common.parts_of(PROMPT, TINY.prefill_part) == (3, 5)
    one = dots3.prefill(
        dataclasses.replace(TINY, prefill_part=part), params, ids, cache_len=PROMPT + 3)
    np.testing.assert_allclose(
        np.asarray(parts.logits), np.asarray(one.logits), rtol=2e-5, atol=2e-5)
    assert jax.tree_util.tree_structure(parts.cache) == jax.tree_util.tree_structure(one.cache)
    for a, b in zip(jax.tree_util.tree_leaves(parts.cache), jax.tree_util.tree_leaves(one.cache)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)
    assert int(one.keys[:, 0].sum()) == int(parts.keys[:, 0].sum())
    assert int(one.keys[:, 1].sum()) == int(parts.keys[:, 1].sum())


def test_the_tails_a_body_serves_are_the_distinct_counts_of_positions_before_its_parts():
    cfg = get_config("dots3-note-prev-ep8-5l")
    assert dots3.tails_seen(cfg, 8192, (8192, 16384, 24576, 32768)) == (0, 512)
    assert dots3.tails_seen(cfg, 100, (32868,)) == (512,)
    assert dots3.tails_seen(TINY, 3, (3, 6, 9, 12)) == (0, 3, 4)
    assert dots3.tails_seen(TINY, 5, (5,)) == (0,)


def test_d_a_decode_past_the_rings_length_still_agrees(run):
    """24 steps over rings of 8 rows: every row written three times; the
    last positions' logits are still the reference's, and the rings
    hold the reference's latents at the positions they should."""
    _, decode, full, (logits, _, _, rings) = run
    assert NEW > 2 * TINY.ring_positions
    assert rel_l2(decode.kept["logits"][-TINY.ring_positions:],
                  logits[-TINY.ring_positions:]).max() < TOLERANCE
    total = PROMPT + NEW
    for ring, want in zip(decode.cache["ring"], rings):
        for position in range(total - TINY.sliding_window_size, total):
            np.testing.assert_allclose(
                np.asarray(ring[position % TINY.ring_positions]), np.asarray(want[position]),
                rtol=2e-4, atol=2e-5)


def test_e_the_four_ranks_shares_of_a_layer_add_up_to_the_uncut_layer():
    """The share test: each rank's expert layer gives the shared expert's
    output plus its own experts' part; summed over the four ranks with
    the shared expert, attention and the residual counted once, that is
    the uncut reference's layer (layer 2: sparse, sliding)."""
    whole = dataclasses.replace(TINY, ep_size=1, ep_rank=0)
    params = dots3.init_params(whole, jax.random.key(3))
    block = params["layers"][2]
    h = jax.random.normal(jax.random.key(4), (PROMPT, whole.hidden_size))
    want, _, _, _ = ref.layer(
        ref.Sizes.of(whole), block, h, list(range(whole.n_routed_experts)), row_block=16)

    x = rms_norm(h, block["attn_norm"], whole.rms_norm_eps)
    tail = jnp.zeros((whole.tail_positions, whole.sliding.cache_width))
    out, _ = dots3.window_attention_part(whole, block, x, tail, jnp.arange(PROMPT), (0,))
    after = h + out
    x = rms_norm(after, block["ffn_norm"], whole.rms_norm_eps)
    shared = swiglu(x, block["moe"]["shared"])
    routed, pairs = 0.0, 0
    for rank in range(4):
        cfg = dataclasses.replace(TINY, ep_size=4, ep_rank=rank)
        mine = expert_range(whole.n_routed_experts, rank, 4)
        part = {"moe": dict(block["moe"], experts=jax.tree_util.tree_map(
            lambda w: w[mine.start:mine.stop], block["moe"]["experts"]))}
        out, _, sizes = dots3._feed_forward(cfg, part, x)
        routed = routed + (out - shared)
        pairs += int(sizes.sum())
    got = after + shared + routed
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    assert pairs == PROMPT * whole.num_experts_per_tok  # every pair fell on exactly one rank


def test_f_the_cut_holds_the_parameters_the_issue_counted():
    cfg = get_config("dots3-note-prev-ep8-5l")
    assert (cfg.num_hidden_layers, len(cfg.held_experts), cfg.vocab_held) == (5, 32, 19008)
    assert list(cfg.layers) == [0, 1, 2, 3, 4] and list(cfg.held_experts) == list(range(32))
    assert [cfg.is_dense(i) for i in cfg.layers] == [True, False, False, False, False]
    assert dots3.param_count(cfg) == 4_087_154_176
    assert dots3.param_count(dots3.Dots3Config()) == 279_551_726_592
    shapes, count = dots3.param_shapes(cfg), dots3.count_params
    assert count(shapes["layers"][0]["attn"]) + count(shapes["layers"][0]["indexer"]) == 144_049_920
    assert count(shapes["layers"][0]["indexer"]) == 9_371_904
    assert count(shapes["layers"][2]["attn"]) == 90_834_944
    assert count(shapes["layers"][0]) == 356_396_800
    assert count(shapes["layers"][1]) == 923_938_816 and count(shapes["layers"][2]) == 870_723_840
    uncut = dots3.param_shapes(dots3.Dots3Config())["layers"]
    assert count(uncut[1]) == 6_208_761_856 and count(uncut[2]) == 6_155_546_880
    state = dots3.state_shapes(cfg, 33024, jnp.bfloat16)
    assert [leaf.shape for leaf in state["ring"]] == [(520, 1088)] * 3
    assert sum(leaf.shape[1] * 2 for leaf in (*state["latents"], *state["index"])) == 2816


@pytest.mark.parametrize("wrong", [
    {"window": None}, {"window": 3}, {"gate": False}, {"rescale_q": False},
    {"rescale_kv": False}, {"swa_rope_theta": 8e7}, {"relu": False}, {"index_topk": 4},
    {"blind_part": 16}])
def test_g_a_reference_with_one_thing_wrong_is_another_model(params, wrong):
    """The parity check's controls at the small size: each moves the
    logits by hundreds of times the tolerance the system is held to (at
    this size 257 of 513 positions is 3 of 5; the mildest is the
    rotation base, 1.4e-2: over a window of 5 positions the two bases'
    angles part by little; every other reads 0.05 or more)."""
    ids = np.asarray(prompt_ids(TINY))
    held = list(TINY.held_experts)
    right, _, _, _ = ref.forward(ref.Sizes.of(TINY), params, ids, held, row_block=16)
    sizes = dataclasses.replace(ref.Sizes.of(TINY), **wrong)
    got, _, _, _ = ref.forward(sizes, params, ids, held, row_block=16)
    first = 16 if "blind_part" in wrong else TINY.index_topk
    assert np.median(rel_l2(got[first:], right[first:])) > 0.005 > 100 * TOLERANCE


def test_g_bfloat16_where_float32_is_stated_fails_the_tolerance_and_float8_the_served_one():
    """Float32 is what the test's tolerance states: the reference on
    bfloat16 operands is outside it by orders; bfloat16 is what the
    served configuration states: the system in bfloat16 stays near the
    float32 reference and the reference on float8 operands does not."""
    params = dots3.init_params(TINY, jax.random.key(0), jnp.bfloat16)
    ids = prompt_ids(TINY)
    prefill = dots3.prefill(TINY, params, ids, cache_len=PROMPT)
    held, sizes = list(TINY.held_experts), ref.Sizes.of(TINY)
    at = dict(row_block=16, positions=[PROMPT - 1])
    want, _, _, _ = ref.forward(sizes, params, np.asarray(ids), held, **at)
    half, _, _, _ = ref.forward(sizes, params, np.asarray(ids), held, round_to=jnp.bfloat16, **at)
    low, _, _, _ = ref.forward(
        sizes, params, np.asarray(ids), held, round_to=jnp.float8_e4m3fn, **at)
    assert rel_l2(half[0], want[0]) > 100 * TOLERANCE
    assert rel_l2(prefill.logits, want[0]) < 0.1 < rel_l2(low[0], want[0])


# --- the served contract -----------------------------------------------------------


def test_h_the_state_tree_and_what_a_request_reports(params):
    lm = create_model("tiny-dots3")
    lm.init(jax.random.key(0))
    assert lm.draft_tokens_max == 0 and lm.layer_passes == 5
    ids = prompt_ids(TINY)
    prefill = lm.prefill(params, ids, PROMPT + NEW)
    decode = lm.decode(params, prefill.cache, prefill.logits, PROMPT, jax.random.key(9), NEW, 1.0)
    total, k, window = PROMPT + NEW, TINY.index_topk, TINY.sliding_window_size
    kinds = {name: [leaf.shape for leaf in leaves] for name, leaves in decode.cache.items()}
    assert kinds == {"latents": [(total, 24)] * 2, "index": [(total, 16)] * 2,
                     "ring": [(8, 40)] * 3}
    said = lm.report(PROMPT, NEW, total, *jax.device_get(lm.read_back(prefill, decode)))
    assert said["cache_bytes"] == total * 2 * (24 + 16) * 4
    assert said["indexer_cache_bytes"] == total * 2 * 16 * 4
    assert said["state_bytes"] == 3 * 8 * 40 * 4
    assert (said["layers"], said["full_layers"], said["window_layers"], said["window"],
            said["ring_positions"], said["prefill_parts"], said["index_topk"]) == (
        5, 2, 3, 5, 8, 4, 8)
    # every position once, in two full layers: t + 1 visible, min(t + 1, 8) read
    assert said["keys_visible"] == 2 * total * (total + 1) // 2
    assert said["keys_selected"] == 2 * (k * (k + 1) // 2 + (total - k) * k)
    # the band over the prompt, in three sliding layers: min(t + 1, 5) seen; XLA's blocks of
    # a part's 16 rows multiply the 4 before the part and up to each block's last row
    assert said["prefill_band_keys_seen"] == 3 * (
        window * (window + 1) // 2 + (PROMPT - window) * window)
    assert said["prefill_band_keys_computed"] == 3 * (16 * 16 + 2 * 16 * 20 + 5 * 9)
    assert said["prefill_band_route"] == "xla"
    assert (said["prefill_sparse_attention_form"], said["decode_sparse_attention_form"]) == (
        "gathered", "masked")
    assert said["prefill_routed_pairs"] == PROMPT * 2 * 4
    assert said["decode_routed_pairs"] == NEW * 2 * 4
    assert 0 < said["decode_routed_pairs_held"] <= said["decode_routed_pairs"]
    assert 0 < said["decode_experts_read"] <= NEW * 4 * 2
    assert said["decode_layer_passes"] == NEW * 5 and said["decode_steps"] == NEW
    assert lm.counted(said, PROMPT, NEW) == {
        "decode_steps": NEW, "prefill_layer_passes": PROMPT * 5, "decode_layer_passes": NEW * 5}
    with pytest.raises(ValueError, match="has no draft module"):
        lm.decode(None, None, None, 0, None, 4, 1.0, draft_tokens=1)


def test_the_pairs_a_route_multiplies_are_its_own_blocks():
    from comfyui_distributed_tpu.ops import attention

    # XLA's blocks of 256 rows over 8,704 keys under 513: 255 + 513 keys a row
    assert attention.causal_pairs_computed("xla", 8192, 8704, 256, 128, 2, 513) == 8192 * 768
    # the first part: no keys before it, the first block's rows see its own 256
    assert attention.causal_pairs_computed("xla", 8192, 8192, 256, 128, 2, 513) == (
        256 * 256 + 256 * 512 + 30 * 256 * 768)
    n_pad, _, block_q, block_k = attention.flash_plan(
        8192, 8704, 256, 2, causal=True, window=513)
    blocks = attention.causal_blocks(n_pad, block_q, block_k, 8192, 8704, 513)[1]
    assert attention.causal_pairs_computed("flash", 8192, 8704, 256, 128, 2, 513) == (
        blocks * block_q * block_k)
    # no window: the triangle in blocks of rows
    assert attention.causal_pairs_computed("xla", 512, 512, 64, 64, 2) == 256 * 256 + 256 * 512


@pytest.mark.parametrize("steps", [1, 2, 7])
def test_exactly_as_many_ids_as_asked_for(params, steps):
    ids = prompt_ids(TINY)
    prefill = dots3.prefill(TINY, params, ids, cache_len=PROMPT + steps)
    decode = dots3.decode(
        TINY, params, prefill.cache, prefill.logits, jnp.int32(PROMPT), jax.random.key(3),
        jnp.float32(1.0), steps=steps)
    assert decode.ids.shape == (steps,) and decode.kept is None
    assert all(leaf.shape[0] == PROMPT + steps for leaf in decode.cache["latents"])


def test_another_gate_or_a_layer_type_unknown_is_refused():
    with pytest.raises(ValueError, match="one sigmoid gate a head"):
        dataclasses.replace(TINY, attention_gate_type="elementwise")
    with pytest.raises(ValueError, match="layer_types names 2 layers"):
        dataclasses.replace(TINY, layer_types=("full_attention", "sliding_attention"))
