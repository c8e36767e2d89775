"""SDAR against its float32 reference on a tiny preset with every
mechanism (3 layers, 4 query heads over 2 key heads of 16 with QK norm
before rotation, 8 experts of 32 columns of which 2 a token and no shared
one, 512 ids of which the last is the mask's, blocks of 4 filled in by at
most 4 passes): the served decode teacher-forced against the reference's
`forward` pass by pass, the keys and values that stand after it, the rule
that decides which drawn ids are kept, the mechanisms that bind, the
block mask on both routes of `causal_attention`, an expert layer without
a shared expert, the counts, and the seeds.

Tolerances: both sides are float32 here, so the system and the reference
differ by the order of their sums alone (a cache read in place of a
recomputed key, a grouped product in place of a loop over experts, an
online softmax): a relative L2 of a few 1e-7 of a row of logits; 2e-5 is
fifty times that and a hundred times under the mildest wrong mechanism
below (2e-3 and up)."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.models import lm_common, sdar
from comfyui_distributed_tpu.models import moe as moe_layer
from comfyui_distributed_tpu.models.registry import create_model, get_config
from comfyui_distributed_tpu.ops import attention as attention_ops
from comfyui_distributed_tpu.ops import decode_attention
from comfyui_distributed_tpu.reference import sdar as ref

TINY = get_config("tiny-sdar")
SIZES = ref.Sizes.of(TINY)
BLOCK = TINY.block_length
NEW = 18  # not a whole number of blocks: the last block's tail is drawn and dropped
TOLERANCE = 2e-5


def rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


@pytest.fixture(scope="module")
def params():
    """Seeded weights, the head norms' scales drawn too: at the seeded
    scale of one a head's norm commutes with its rotation, and the order
    of the two could not show."""
    tree = sdar.init_params(TINY, jax.random.key(1))
    for index, block in enumerate(tree["layers"]):
        for offset, name in enumerate(("q_norm", "k_norm")):
            key = jax.random.fold_in(jax.random.key(7), 2 * index + offset)
            block["attn"][name] = jax.random.uniform(key, (TINY.head_dim,), minval=0.5, maxval=1.5)
    return tree


def prompt_ids(tokens, seed=1):
    return jax.random.randint(jax.random.key(seed), (tokens,), 0, 257)


def generate(cfg, params, ids, steps=NEW, temperature=1.0, seed=3, collect=True, decode=None):
    pre = sdar.prefill(cfg, params, ids, cache_len=len(ids) + steps, collect=collect)
    dec = (decode or sdar.decode)(
        cfg, params, pre.cache, pre.logits, jnp.int32(len(ids)), jax.random.key(seed),
        jnp.float32(temperature), steps=steps, collect=collect)
    return pre.logits, dec


def passes_of(kept):
    """(block, pass) of every denoising pass the decode took."""
    position = np.asarray(kept["position"])
    return [(b, s) for b in range(position.shape[0]) for s in range(position.shape[1])
            if position[b, s] >= 0]


def final_ids(ids, kept):
    """The prompt's whole blocks, then each block as its last pass left it."""
    whole = len(ids) - len(ids) % BLOCK
    out = [int(t) for t in ids[:whole]]
    took = {}
    for b, s in passes_of(kept):
        took[b] = s
    for b, s in sorted(took.items()):
        out += np.where(np.asarray(kept["moved"][b, s]), np.asarray(kept["drawn"][b, s]),
                        np.asarray(kept["tokens"][b, s])).tolist()
    return np.asarray(out, np.int32)


def worst_against(sizes, params, ids, prefill_logits, dec, shift=0):
    """The largest relative L2, over the prefill's last logits and every
    masked position of every pass, between what the served decode saw and
    the reference's `forward` over the same ids up to the block's end
    (`shift`: the reference's row read that many positions earlier)."""
    kept = dec.kept
    final = final_ids(ids, kept)
    whole = len(ids) - len(ids) % BLOCK
    logits, _, _ = ref.forward(sizes, params, final[:whole])
    worst = [rel_l2(prefill_logits, logits[whole - 1 - shift])]
    for b, s in passes_of(kept):
        at = int(kept["position"][b, s])
        seen = np.concatenate([final[:at], np.asarray(kept["tokens"][b, s])])
        logits, _, _ = ref.forward(sizes, params, seen)
        masked = np.asarray(kept["masked"][b, s])
        rows = np.asarray(logits)[at - shift:at - shift + BLOCK]
        worst.append(rel_l2(np.asarray(kept["logits"][b, s])[masked], rows[masked]).max())
    return float(np.max(worst))


# --- (a) teacher-forced, pass by pass ---------------------------------------------


@pytest.fixture(scope="module", params=[16, 14], ids=["aligned", "two_left_over"])
def run(request, params):
    ids = prompt_ids(request.param)
    logits, dec = generate(TINY, params, ids)
    return ids, logits, dec


def test_every_pass_logits_are_the_references_over_the_ids_the_pass_saw(run, params):
    ids, logits, dec = run
    assert worst_against(SIZES, params, ids, logits, dec) < TOLERANCE


def test_a_first_block_opens_with_the_prompts_left_over_tokens(run):
    ids, _, dec = run
    left = len(ids) % BLOCK
    kept = dec.kept
    first = np.asarray(kept["tokens"][0, 0])
    np.testing.assert_array_equal(first[:left], np.asarray(ids)[len(ids) - left:])
    assert (first[left:] == TINY.mask_token_id).all()
    np.testing.assert_array_equal(np.asarray(kept["masked"][0, 0]), np.arange(BLOCK) >= left)
    assert int(kept["position"][0, 0]) == len(ids) - left
    # the ids are what follows the prompt in the blocks as they closed
    np.testing.assert_array_equal(
        np.asarray(dec.ids), final_ids(ids, kept)[len(ids):len(ids) + NEW])


def test_the_references_own_generation_draws_the_same_ids(run, params):
    ids, _, dec = run
    theirs, took = ref.generate(SIZES, params, np.asarray(ids), NEW, jax.random.key(3), 1.0)
    np.testing.assert_array_equal(np.asarray(dec.ids), theirs)
    denoise, closing = np.asarray(dec.counts)[:2]
    assert (sum(took), len(took)) == (denoise, closing)


# --- (b) the closing pass's keys and values stand -----------------------------------


def without_closing(cfg, params, cache, tokens, position, close):
    if not close:
        return block_pass(cfg, params, cache, tokens, position, close)
    layers = cfg.num_hidden_layers
    return (None, cache, jnp.zeros((layers, BLOCK, cfg.num_experts_per_tok), jnp.int32),
            jnp.zeros((layers, cfg.num_experts), jnp.int32))


block_pass = sdar.block_pass


def kv_error(params, ids, dec):
    final = final_ids(ids, dec.kept)
    _, _, kv = ref.forward(SIZES, params, final)
    return max(
        float(np.linalg.norm(np.asarray(mine)[:, :, :len(final)] - np.asarray(theirs))
              / np.linalg.norm(np.asarray(theirs)))
        for mine, theirs in zip(dec.cache["kv"], kv))


def test_the_keys_and_values_that_stand_are_the_final_ids(run, params, monkeypatch):
    ids, _, dec = run
    assert kv_error(params, ids, dec) < TOLERANCE
    # a decode whose closing pass is left out keeps the last denoising pass's: one
    # position a block holds the mask's keys
    monkeypatch.setattr(sdar, "block_pass", without_closing)
    fresh = jax.jit(  # a new function object, or JAX hands back the cached trace
        functools.partial(sdar.decode.__wrapped__), static_argnames=("cfg", "steps", "collect"))
    _, left_out = generate(TINY, params, ids, decode=fresh)
    assert kv_error(params, ids, left_out) > 0.1


# --- (c) the rule, and the loop's counts --------------------------------------------


@pytest.mark.parametrize("confidence, masked, n, kept, by_threshold", [
    # all above the threshold: all kept, whatever n
    ([0.9, 0.95, 0.99, 0.86], [1, 1, 1, 1], 1, [1, 1, 1, 1], True),
    # none above: the n largest, ties to the lower index
    ([0.3, 0.5, 0.5, 0.1], [1, 1, 1, 1], 1, [0, 1, 0, 0], False),
    ([0.3, 0.5, 0.5, 0.1], [1, 1, 1, 1], 2, [0, 1, 1, 0], False),
    ([0.2, 0.2, 0.2, 0.2], [1, 1, 1, 1], 3, [1, 1, 1, 0], False),
    # some above but fewer than n: the n largest, not those above alone
    ([0.9, 0.5, 0.6, 0.1], [1, 1, 1, 1], 2, [1, 0, 1, 0], False),
    # as many above as n: those above
    ([0.9, 0.5, 0.95, 0.1], [1, 1, 1, 1], 2, [1, 0, 1, 0], True),
    # an unmasked position never, however confident, and it does not count towards n
    ([0.99, 0.5, 0.6, 0.1], [0, 1, 1, 1], 1, [0, 0, 1, 0], False),
    ([0.99, 0.9, 0.6, 0.1], [0, 1, 1, 1], 1, [0, 1, 0, 0], True),
    # fewer masked than n: those that are
    ([0.99, 0.5, 0.6, 0.1], [0, 0, 0, 1], 2, [0, 0, 0, 1], False),
])
def test_transfer_keeps_those_above_the_threshold_or_the_n_most_confident(
        confidence, masked, n, kept, by_threshold):
    got, decided = lm_common.transfer(
        jnp.asarray(confidence, jnp.float32), jnp.asarray(masked, bool), n, 0.85)
    assert np.asarray(got).tolist() == [bool(k) for k in kept]
    assert bool(decided) == by_threshold
    # the reference's own rule agrees (n = 1 is a pass of the published 4 in 4)
    if n == 1:
        theirs = ref.transferred(SIZES, np.asarray(confidence), np.asarray(masked, bool), 0)
        assert theirs.tolist() == [bool(k) for k in kept]


@pytest.mark.parametrize("threshold, passes, by_threshold", [(0.0, 1, True), (1.5, 4, False)])
def test_the_threshold_decides_how_many_passes_a_block_takes(
        params, threshold, passes, by_threshold):
    cfg = dataclasses.replace(TINY, confidence_threshold=threshold)
    _, dec = generate(cfg, params, prompt_ids(16), steps=16, collect=False)
    denoise, closing, above, floor, read = np.asarray(dec.counts).tolist()
    assert (denoise, closing) == (4 * passes, 4)          # 1 + 1 passes a block, or S + 1
    assert (above, floor) == ((16, 0) if by_threshold else (0, 16))
    lm = create_model("tiny-sdar")
    said = lm.report(16, 16, 32, np.ones((3, 8)), np.asarray(dec.loads), np.asarray(dec.counts))
    assert (said["decode_steps"], said["denoise_passes"], said["closing_passes"]) == (
        4 * passes + 4, 4 * passes, 4)
    assert said["decode_layer_passes"] == 4 * (4 * passes * 3 + 4 * 2)
    assert said["decode_routed_pairs"] == said["decode_routed_pairs_held"] == int(
        np.asarray(dec.loads).sum())
    # distinct experts a pass and expert layer read: 2 to 8 of 8 for four positions' two each
    bodies = 4 * passes * 3 + 4 * 2
    assert said["decode_experts_read"] == read and 2 * bodies <= read <= 8 * bodies
    assert lm.counted(said, 16, 16)["decode_steps"] == 4 * passes + 4


def test_sample_with_confidence_is_the_draws_probability(params):
    logits = jax.random.normal(jax.random.key(5), (512,)) * 3
    key = jax.random.key(9)
    drawn, confidence = lm_common.sample_with_confidence(logits, key, jnp.float32(0.7))
    assert int(drawn) == int(lm_common.sample(logits, key, jnp.float32(0.7)))
    assert float(confidence) == pytest.approx(float(jax.nn.softmax(logits / 0.7)[drawn]), rel=1e-5)
    # at temperature 0: the largest, and its probability at temperature 1
    drawn, confidence = lm_common.sample_with_confidence(logits, key, jnp.float32(0.0))
    assert int(drawn) == int(jnp.argmax(logits))
    assert float(confidence) == pytest.approx(float(jax.nn.softmax(logits).max()), rel=1e-5)


# --- (d) each of these binds ---------------------------------------------------------


@pytest.mark.parametrize("wrong", [
    {"block_mask": False},                      # a plain causal mask
    {"norm_then_rotate": False},                # rotation before the norm
    {"norm_topk_prob": False},                  # the chosen weights not renormalised
    {"score_scale": 1.0},                       # scale 1 in place of d^-1/2
], ids=["causal_mask", "rotation_before_norm", "weights_not_renormalised", "scale_one"])
def test_a_reference_with_another_mechanism_is_outside_the_tolerance(run, params, wrong):
    ids, logits, dec = run
    sizes = dataclasses.replace(SIZES, **wrong)
    assert worst_against(sizes, params, ids, logits, dec) > 100 * TOLERANCE


def test_a_logit_is_of_its_own_position_and_not_of_the_next(run, params):
    ids, logits, dec = run
    assert worst_against(SIZES, params, ids, logits, dec, shift=1) > 100 * TOLERANCE


# --- (e) the block mask on both routes ------------------------------------------------


def dense_block_attention(q, k, v, block):
    n, m = q.shape[1], k.shape[1]
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = q.shape[-1] ** -0.5 * jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    own = jnp.arange(n)[:, None] + (m - n)
    seen = jnp.arange(m)[None, :] // block <= own // block
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))


def operands(n, m, heads, kv_heads, d, dtype=jnp.float32, seed=0):
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(kq, (1, n, heads, d), dtype),
            jax.random.normal(kk, (1, m, kv_heads, d), dtype),
            jax.random.normal(kv, (1, m, kv_heads, d), dtype))


@pytest.mark.parametrize("n, m, block", [
    (512, 512, 4), (300, 300, 4), (256, 600, 8), (4, 64, 4), (44, 300, 2), (96, 96, 32)])
def test_the_blocked_form_under_a_block_mask_matches_a_dense_masked_softmax(n, m, block):
    q, k, v = operands(n, m, 4, 2, 16)
    with attention_ops.route_log() as routes:
        out = attention_ops.causal_attention(q, k, v, block=block)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(dense_block_attention(q, k, v, block)), rtol=2e-5, atol=2e-5)
    assert routes == [f"xla-causal {n}x{m}x16/16 b{block} bq{min(n, 256)} f32"]
    # and it is another result than the plain causal call's
    plain = attention_ops.causal_attention(q, k, v)
    assert float(jnp.abs(plain - out).max()) > 1e-3


@pytest.mark.parametrize("n, m, heads, kv_heads", [(640, 640, 4, 2), (256, 640, 8, 1)])
def test_the_kernel_under_a_block_mask_matches_the_blocked_form(n, m, heads, kv_heads):
    """Interpreted, at a length past `MIN_RAGGED_KEYS` and off the caps
    (640 = 5 x 128: q blocks of 128, k blocks of 640), and with fewer
    queries than keys: the diagonal tile's mask is the block's."""
    assert m >= attention_ops.MIN_RAGGED_KEYS
    q, k, v = operands(n, m, heads, kv_heads, 128, jnp.bfloat16, seed=2)
    with attention_ops.route_log() as routes:
        out = attention_ops.causal_attention(q, k, v, block=4, force_flash=True, interpret=True)
    want = attention_ops.causal_attention_blocked(q, k, v, block=4)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), rtol=2e-2, atol=2e-2)
    assert routes[0].startswith(f"flash-causal {n}x{m}x128/128 b4 g{heads // kv_heads} ")
    plain = attention_ops.causal_attention(q, k, v, force_flash=True, interpret=True)
    assert float(jnp.abs(plain.astype(jnp.float32) - out.astype(jnp.float32)).max()) > 1e-2


def test_the_tiles_a_causal_call_computes_do_not_change_under_a_block():
    """A block of positions never straddles two k tiles (tiles are
    multiples of 128 and a block divides 128), so the k blocks a q block
    sees are the causal call's own and the plan has no `block`."""
    for n, m in ((2048, 2048), (640, 640), (256, 640), (8192, 8192)):
        n_pad, m_pad, block_q, block_k = attention_ops.flash_plan(n, m, 128, 2, causal=True)
        first, last = attention_ops.key_block_range(
            np, np.arange(n_pad // block_q), block_q, block_k, n, m, None)
        for block in (2, 4, 32, 128):
            rows = np.minimum(np.arange(n_pad), n - 1) + (m - n)
            reach = (rows | (block - 1)).reshape(-1, block_q).max(axis=1) // block_k
            np.testing.assert_array_equal(reach, last)
            assert (first == 0).all()


@pytest.mark.parametrize("block, window, m", [(3, None, 12), (256, None, 512), (4, 8, 16),
                                              (4, None, 14), (0, None, 8)])
def test_a_block_that_is_no_power_of_two_or_meets_a_window_is_refused(block, window, m):
    q, k, v = operands(4, m, 2, 2, 16)
    with pytest.raises(ValueError, match="block mask"):
        attention_ops.causal_attention(q, k, v, block=block, window=window)


def test_block_valid_lets_every_row_see_every_entry_below_the_blocks_end():
    seen = np.asarray(decode_attention.block_valid(jnp.int32(8), 16, 4))
    assert seen.shape == (4, 16)
    assert (seen == (np.arange(16) < 12)[None, :]).all()
    assert (np.asarray(decode_attention.position_valid(jnp.arange(8, 12), 16))[-1] == seen[0]).all()


# --- (f) an expert layer without a shared expert ---------------------------------------


def dense_experts(p, x, route):
    logits = x.astype(jnp.float32) @ p["w_g"].astype(jnp.float32)
    ids, weights = route(logits)
    out = jnp.zeros_like(x)
    for expert in range(p["w_g"].shape[1]):
        weight = jnp.sum(jnp.where(ids == expert, weights, 0.0), axis=-1, keepdims=True)
        one = {"w_gate_up": p["experts"]["w_gate_up"][expert],
               "w_down": p["experts"]["w_down"][expert]}
        out = out + weight * lm_common.swiglu(x, one)
    return out


@pytest.mark.parametrize("tokens", [4, 300])
def test_expert_layer_without_a_shared_expert_is_the_dense_loop_over_experts(params, tokens):
    p = params["layers"][1]["moe"]
    x = jax.random.normal(jax.random.key(4), (tokens, TINY.hidden_size))
    route = lambda logits: sdar.softmax_route(TINY, logits)
    out, ids, sizes = moe_layer.expert_layer(p, x, TINY.held_experts, route)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(dense_experts(p, x, route)), rtol=2e-5, atol=2e-5)
    assert ids.shape == (tokens, 2) and int(sizes.sum()) == 2 * tokens
    # the reference's own rule chooses the same experts and weights
    theirs, weights = ref.route(SIZES, x @ p["w_g"])
    np.testing.assert_array_equal(np.sort(np.asarray(ids)), np.sort(np.asarray(theirs)))
    # with a shared expert the layer's result is what it was: the routed part and the shared one
    shared = {"w_gate_up": p["experts"]["w_gate_up"][0], "w_down": p["experts"]["w_down"][0]}
    both, _, _ = moe_layer.expert_layer({**p, "shared": shared}, x, TINY.held_experts, route)
    np.testing.assert_allclose(
        np.asarray(both), np.asarray(out + lm_common.swiglu(x, shared)), rtol=2e-5, atol=2e-5)


# --- (g) the counts ------------------------------------------------------------------------


def test_the_parameters_are_the_published_30_5_billion_and_the_held_4_36():
    assert sdar.param_count(sdar.SdarConfig()) == 30_532_122_624
    held = get_config("sdar-30b-a3b-pp8-6l")
    assert sdar.param_count(held) == 4_361_055_744
    assert held == sdar.SdarConfig(num_hidden_layers=6)
    lm = create_model("sdar-30b-a3b-pp8-6l")
    lm.dtype = jnp.dtype(jnp.bfloat16)
    described = lm.describe(2560)
    assert (described["cache_bytes"], described["state_bytes"], described["layers"]) == (
        31_457_280, 0, 6)
    assert (described["experts_held"], described["experts_total"]) == (128, 128)
    # a length that is no whole number of blocks holds the last block whole
    assert lm.describe(2558)["cache_bytes"] == 31_457_280
    assert lm.draft_tokens_max == 0


def test_a_mask_id_outside_the_vocabulary_is_refused():
    with pytest.raises(ValueError, match="mask id"):
        dataclasses.replace(TINY, mask_token_id=512)


# --- (h) seeds, and exactly `steps` ids ----------------------------------------------------


@pytest.mark.parametrize("steps", [1, 4, 7])
def test_equal_seeds_give_equal_ids_and_exactly_steps_of_them(params, steps):
    ids = prompt_ids(13)
    _, one = generate(TINY, params, ids, steps=steps, collect=False)
    _, again = generate(TINY, params, ids, steps=steps, collect=False)
    _, other = generate(TINY, params, ids, steps=steps, seed=4, collect=False)
    assert one.ids.shape == (steps,) and one.ids.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(one.ids), np.asarray(again.ids))
    assert not np.array_equal(np.asarray(one.ids), np.asarray(other.ids)) or steps == 1
    assert one.kept is None
    # the blocks the ids needed, one closing pass each
    assert int(one.counts[1]) == -(-(13 % BLOCK + steps) // BLOCK)


def test_the_collecting_program_draws_the_served_programs_ids(params):
    ids = prompt_ids(14)
    _, served = generate(TINY, params, ids, collect=False)
    _, collecting = generate(TINY, params, ids, collect=True)
    np.testing.assert_array_equal(np.asarray(served.ids), np.asarray(collecting.ids))
    np.testing.assert_array_equal(np.asarray(served.counts), np.asarray(collecting.counts))


def test_a_prompt_shorter_than_a_block_runs_no_prefill_layer(params):
    ids = prompt_ids(3)
    logits, dec = generate(TINY, params, ids, steps=5)
    assert not np.asarray(logits).any()
    np.testing.assert_array_equal(np.asarray(dec.kept["tokens"][0, 0])[:3], np.asarray(ids))
    theirs, _ = ref.generate(SIZES, params, np.asarray(ids), 5, jax.random.key(3), 1.0)
    np.testing.assert_array_equal(np.asarray(dec.ids), theirs)


def test_at_temperature_zero_the_ids_are_the_references_greedy_ones(params):
    ids = prompt_ids(12)
    _, dec = generate(TINY, params, ids, steps=8, temperature=0.0, collect=False)
    theirs, _ = ref.generate(SIZES, params, np.asarray(ids), 8, jax.random.key(3), 0.0)
    np.testing.assert_array_equal(np.asarray(dec.ids), theirs)


# --- the loop by itself, under a pass that is arithmetic ---------------------------------------


@pytest.mark.parametrize("collect", [False, True])
def test_the_denoise_loop_threads_the_cache_and_sums_and_keeps_what_a_pass_hands_over(collect):
    """A pass whose logits make position p's largest id p + 1 with
    certainty, over a cache that counts the passes: at temperature 0 and
    a threshold under 1 every block takes one denoising pass and a
    closing one, the ids are the positions + 1, and the loop's kept rows
    are the passes' in order, the model's own for every `kept_stride`-th
    block."""
    def a_pass(cache, tokens, position, close):
        if close:
            return None, cache + 100, (jnp.int32(0), tokens.sum()), None
        want = (position + jnp.arange(4) + 1) % 64
        logits = jnp.zeros((4, 64)).at[jnp.arange(4), want].set(50.0)
        kept = {"at": position} if collect else None
        return logits, cache + 1, (jnp.int32(1), jnp.int32(0)), kept

    opening = jnp.asarray([41, 42, 0, 0], jnp.int32)
    cache, ids, counts, (ran, closed), rows = jax.jit(lambda: lm_common.denoise_loop(
        a_pass, jnp.int32(0), opening, jnp.int32(10), jax.random.key(0), jnp.float32(0.0), 9,
        4, 4, 0.85, 63, kept_stride=2))()
    # positions 10 .. 18: block 8-11 opens with 41, 42 at 8, 9; three blocks walked
    assert np.asarray(ids).tolist() == list(range(11, 20))
    assert int(cache) == 3 + 300 and np.asarray(counts).tolist() == [3, 3, 2 + 4 + 4, 0]
    assert int(ran) == 3 and int(closed) == (41 + 42 + 11 + 12) + sum(range(13, 21))
    if not collect:
        assert rows is None
        return
    assert np.asarray(rows["position"])[:, 0].tolist() == [8, 12, 16]
    assert (np.asarray(rows["position"])[:, 1:] == -1).all()
    assert np.asarray(rows["tokens"][0, 0]).tolist() == [41, 42, 63, 63]
    assert np.asarray(rows["masked"][0, 0]).tolist() == [False, False, True, True]
    assert np.asarray(rows["moved"][1, 0]).all()
    assert np.asarray(rows["at"]).shape == (2, 4) and np.asarray(rows["at"])[:, 0].tolist() == [
        8, 16]
