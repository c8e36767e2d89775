"""DeepSeek-V2 against its float32 reference on a tiny preset with every
mechanism (a dense layer + 2 expert layers, 16 experts in 4 groups, 2
groups and 3 experts a token, 1 shared, YaRN on): prefill + decode through
the latent cache against the reference's forward pass, the four ranks'
shares of a layer against the uncut layer, the absorbed form against the
expanded, and the router on a hand-made case."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.models import deepseek_v2 as ds
from comfyui_distributed_tpu.models.lm_common import ByteTokenizer
from comfyui_distributed_tpu.models.registry import get_config
from comfyui_distributed_tpu.parallel.sharding import expert_range
from comfyui_distributed_tpu.reference import deepseek_v2 as ref

TINY = get_config("tiny-deepseek-v2")
PROMPT, STEPS = 128, 12


sizes_of = ref.Sizes.of


def generate(cfg, params, seed=1, temperature=1.0):
    ids = jax.random.randint(jax.random.key(5), (PROMPT,), 0, cfg.vocab_held)
    prefill = ds.prefill(cfg, params, ids, cache_len=PROMPT + STEPS, collect=True)
    decode = ds.decode(
        cfg, params, prefill.cache, prefill.logits, jnp.int32(PROMPT), jax.random.key(seed),
        jnp.float32(temperature), steps=STEPS, collect=True,
    )
    full = jnp.concatenate([ids, decode.ids])
    mine = jnp.concatenate([prefill.logits[None], decode.logits])
    chosen = np.concatenate(
        [np.asarray(prefill.chosen), np.asarray(decode.chosen).transpose(1, 0, 2)], axis=1)
    return full, mine, chosen, (np.asarray(prefill.loads), np.asarray(decode.loads))


def reference_logits(cfg, params, full, **kwargs):
    return ref.forward(
        sizes_of(cfg), params, full, list(cfg.held_experts),
        positions=np.arange(PROMPT - 1, PROMPT + STEPS), **kwargs,
    )


def rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


@pytest.mark.parametrize("rank, size", [(0, 4), (3, 4), (1, 2), (0, 1)])
def test_prefill_and_decode_through_the_cache_match_the_reference_in_float32(rank, size):
    """Float32 weights and activations: only the order of the sums
    differs (the grouped product, the absorbed attention, blocked
    softmax), so the logits agree to float32 rounding, ~1e-6 of logits
    of order 4. 2e-5 leaves room for a longer sum and would not pass
    bfloat16 anywhere (1e-2 at the least, below)."""
    cfg = dataclasses.replace(TINY, ep_rank=rank, ep_size=size)
    params = ds.init_params(cfg, jax.random.key(0))
    full, mine, chosen, loads = generate(cfg, params)
    want, _, chosen_ref = reference_logits(cfg, params, full)
    assert rel_l2(mine, want).max() < 2e-5
    assert np.abs(np.asarray(mine) - np.asarray(want)).max() < 2e-5 * np.abs(want).max() * 4
    assert (np.sort(chosen, -1) == np.sort(np.asarray(chosen_ref), -1)).all()
    # the pairs counted on the held experts are the reference's choices that fall there
    held = np.isin(np.asarray(chosen_ref), list(cfg.held_experts))
    assert loads[0].sum() == held[:, :PROMPT].sum()
    assert loads[1].sum() == held[:, PROMPT:].sum()


def test_bfloat16_stays_near_the_reference_and_float8_does_not():
    """bfloat16 weights and activations against the float32 reference on
    the same (bfloat16-valued) weights. A bfloat16 rounding is 2^-9 =
    2e-3 relative, and three layers of a dozen rounded products each
    bring the logits' median relative L2 to 8e-3..9e-3 at this tiny
    width (three weight seeds); 1-2 % of (token, layer) pairs flip their
    expert set on a near tie, and such a token moves by one expert's
    whole output, which is why the limit is on the median over positions
    and the flips are limited apart. The same reference with float8
    operands, one precision down, reads 1.1e-1..1.3e-1 and 14-18 %
    flips. The limits (3e-2, 5 %) lie between the two readings with a
    factor of three on either side, so float8 fails both."""
    params = ds.init_params(TINY, jax.random.key(0), jnp.bfloat16)
    full, mine, chosen, _ = generate(TINY, params)
    want, _, chosen_ref = reference_logits(TINY, params, full)
    flips = np.mean(np.any(np.sort(chosen, -1) != np.sort(np.asarray(chosen_ref), -1), axis=-1))
    assert 1e-3 < np.median(rel_l2(mine, want)) < 3e-2
    assert flips < 0.05
    low, _, chosen_low = reference_logits(TINY, params, full, round_to=jnp.float8_e4m3fn)
    flips_low = np.mean(
        np.any(np.sort(np.asarray(chosen_low), -1) != np.sort(np.asarray(chosen_ref), -1), axis=-1))
    assert np.median(rel_l2(low, want)) > 3e-2
    assert flips_low > 0.05


def test_the_four_ranks_shares_of_a_layer_add_up_to_the_uncut_layer():
    """The share test: each rank's expert layer gives the shared expert's
    output plus its own experts' part; summed over the four ranks with
    the shared expert and the residual counted once, that is the uncut
    reference's layer."""
    whole = dataclasses.replace(TINY, ep_size=1, ep_rank=0)
    params = ds.init_params(whole, jax.random.key(3))
    block = params["layers"][1]
    h = jax.random.normal(jax.random.key(4), (PROMPT, whole.hidden_size))
    rope = ds.rope_tables(whole, jnp.arange(PROMPT))
    want, _ = ref.layer(sizes_of(whole), block, h, list(range(whole.n_routed_experts)))

    x = ds.rms_norm(h, block["attn_norm"], whole.rms_norm_eps)
    after_attention = h + ds.mla_expanded(whole, block["attn"], x, rope)[0]
    x = ds.rms_norm(after_attention, block["ffn_norm"], whole.rms_norm_eps)
    shared = ds.swiglu(x, block["moe"]["shared"])
    routed, pairs = 0.0, 0
    for rank in range(4):
        cfg = dataclasses.replace(TINY, ep_size=4, ep_rank=rank)
        mine = expert_range(whole.n_routed_experts, rank, 4)
        part = dict(block["moe"], experts=jax.tree_util.tree_map(
            lambda w: w[mine.start:mine.stop], block["moe"]["experts"]))
        out, _, sizes = ds.moe(cfg, part, x)
        routed = routed + (out - shared)
        pairs += int(sizes.sum())
    got = after_attention + shared + routed
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    assert pairs == PROMPT * whole.num_experts_per_tok  # every pair fell on exactly one rank


def test_absorbed_attention_is_the_expanded_attention():
    params = ds.init_params(TINY, jax.random.key(1))
    p = params["layers"][0]["attn"]
    x = jax.random.normal(jax.random.key(2), (PROMPT, TINY.hidden_size))
    positions = jnp.arange(PROMPT)
    rope = ds.rope_tables(TINY, positions)
    expanded, latents = ds.mla_expanded(TINY, p, x, rope)
    valid = positions[:, None] >= positions[None, :]
    absorbed = ds.mla_absorbed(TINY, p, x, rope, latents, valid)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded), rtol=1e-5, atol=1e-5)
    assert latents.shape == (PROMPT, TINY.kv_lora_rank + TINY.qk_rope_head_dim)


def test_the_router_on_a_hand_made_case_with_a_tie_across_groups():
    """8 experts in 4 groups of 2, 2 groups and 3 experts a token. Groups
    1 and 2 tie for second place behind group 3: the lower group stays.
    Inside the kept groups the three largest scores are chosen, and a tie
    between experts goes to the lower index."""
    cfg = dataclasses.replace(
        TINY, n_routed_experts=8, n_group=4, topk_group=2, num_experts_per_tok=3,
        routed_scaling_factor=16.0)
    scores = jnp.array([
        # g0        g1          g2          g3
        [0.01, 0.02, 0.20, 0.05, 0.03, 0.20, 0.30, 0.19],
        # the largest scores are in groups 0 and 2; experts 4 and 5 tie
        [0.25, 0.01, 0.02, 0.03, 0.22, 0.22, 0.10, 0.15],
    ], jnp.float32)
    ids, weights = ds.route(cfg, scores)
    assert ids.tolist() == [[6, 2, 7], [0, 4, 5]]
    np.testing.assert_allclose(
        np.asarray(weights), 16.0 * np.array([[0.30, 0.20, 0.19], [0.25, 0.22, 0.22]]), rtol=1e-6)
    ids_ref, weights_ref = ref.route(sizes_of(cfg), scores)
    assert ids_ref.tolist() == ids.tolist()
    np.testing.assert_allclose(np.asarray(weights_ref), np.asarray(weights), rtol=1e-6)


def test_yarn_frequencies_and_the_softmax_scale_at_the_published_sizes():
    cfg = get_config("deepseek-v2-ep4-5l")
    inv_freq = ds.yarn_inv_freq(cfg)
    plain = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    # correction dims of 32 and 1 rotations over 4,096 positions: 10.3 -> 10, 22.4 -> 23
    np.testing.assert_allclose(inv_freq[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv_freq[23:], plain[23:] / 40.0, rtol=1e-6)
    assert (inv_freq[11:23] < plain[11:23]).all() and (inv_freq[11:23] > plain[11:23] / 40).all()
    m = 0.1 * 0.707 * np.log(40.0) + 1.0
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * m * m)
    cos, sin = ds.rope_tables(cfg, jnp.arange(4))
    np.testing.assert_allclose(np.asarray(cos[0]), 1.0)  # mscale / mscale_all_dim = 1
    assert cfg.held_experts == range(0, 40) and cfg.vocab_held == 25600 and cfg.cache_width == 576


def test_the_cut_holds_the_parameters_the_issue_counted():
    cfg = get_config("deepseek-v2-ep4-5l")
    assert ds.param_count(cfg) == 5_163_975_680
    attention = ds.param_shapes(cfg)["layers"][1]["attn"]
    assert sum(int(np.prod(s)) for s, fan in attention.values() if fan) == 149_225_472


@pytest.mark.parametrize("n, rank, size, want", [
    (160, 0, 4, range(0, 40)), (160, 3, 4, range(120, 160)), (16, 1, 2, range(8, 16)),
    (16, 0, 1, range(0, 16)),
])
def test_expert_range_gives_each_rank_a_contiguous_run(n, rank, size, want):
    assert expert_range(n, rank, size) == want


@pytest.mark.parametrize("n, rank, size", [(160, 4, 4), (160, 0, 3), (16, -1, 2), (16, 0, 0)])
def test_expert_range_refuses_what_does_not_divide(n, rank, size):
    with pytest.raises(ValueError):
        expert_range(n, rank, size)


def test_sampling_is_a_function_of_the_seed_and_greedy_at_temperature_zero():
    params = ds.init_params(TINY, jax.random.key(0))
    a, _, _, _ = generate(TINY, params, seed=7)
    b, _, _, _ = generate(TINY, params, seed=7)
    c, _, _, _ = generate(TINY, params, seed=8)
    assert a.tolist() == b.tolist() and a.tolist() != c.tolist()
    full, logits, _, _ = generate(TINY, params, temperature=0.0)
    assert full[PROMPT:].tolist() == np.argmax(np.asarray(logits[:-1]), -1).tolist()


def test_a_served_request_collects_nothing_and_draws_the_same_ids():
    """Without `collect` the two programs return no logits a step and no
    chosen experts (26 MB a request at the cell's sizes that no request
    reads), and draw the ids the collecting programs draw."""
    params = ds.init_params(TINY, jax.random.key(0))
    full, _, _, loads = generate(TINY, params, seed=3)
    prefill = ds.prefill(TINY, params, full[:PROMPT], cache_len=PROMPT + STEPS)
    decode = ds.decode(
        TINY, params, prefill.cache, prefill.logits, jnp.int32(PROMPT), jax.random.key(3),
        jnp.float32(1.0), steps=STEPS,
    )
    # the served prefill is the collecting one (PR 64): the decode is what collects nothing
    assert prefill.chosen is not None and decode.logits is None and decode.chosen is None
    assert decode.ids.tolist() == full[PROMPT:].tolist()
    np.testing.assert_array_equal(np.asarray(prefill.loads), loads[0])
    np.testing.assert_array_equal(np.asarray(decode.loads), loads[1])


def test_another_temperature_builds_no_program():
    params = ds.init_params(TINY, jax.random.key(0))
    generate(TINY, params, temperature=1.0)
    built = ds.decode._cache_size()
    a, _, _, _ = generate(TINY, params, temperature=0.7)
    b, _, _, _ = generate(TINY, params, temperature=1.0)
    assert ds.decode._cache_size() == built and a.tolist() != b.tolist()


@pytest.mark.parametrize("text", ["a cat", "", "déjà vu", "x" * 300])
def test_the_stand_in_tokenizer_is_bytes_in_and_words_out(text):
    tok = ByteTokenizer()
    ids = tok.encode(text)
    assert ids[0] == 0 and len(ids) == 1 + len(text.encode("utf-8")) and max(ids) <= 256
    kept = "".join(ch for ch in text if 32 <= ord(ch) < 127)
    assert tok.decode(ids) == kept.strip()


def test_the_tokenizer_turns_any_id_of_the_slice_into_lower_case_words():
    tok = ByteTokenizer()
    assert tok.decode([0, 66, 67, 257, 258, 283, 25599]) == "AB a b ab smlb"
    text = tok.decode(range(25600))
    assert set(text) <= set("abcdefghijklmnopqrstuvwxyz ") | {chr(c) for c in range(32, 127)}
    from comfyui_distributed_tpu.models.text_encoder import Tokenizer

    clip = Tokenizer().encode(tok.decode([300, 9000, 25599, 12345]))
    assert clip[0] == Tokenizer.BOS and (clip != Tokenizer.EOS).sum() > 4
