"""Solar-Open2 against its float32 reference on a tiny preset with every
mechanism (one period: a gated NoPE layer of 4 query heads over 2 key
heads, then three KDA layers of 4 heads; 16 experts, 4 a token, 1 shared,
rank 0 of 8; a chunk of 32 tokens): the chunked delta rule against the
recurrence, prefill + decode through the state tree against the
reference's forward pass, the state a prefill leaves against as many
recurrent steps, the eight ranks' shares of a layer against the uncut
layer, and grouped queries in both forms of the attention."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.models import kda
from comfyui_distributed_tpu.models import solar_open2 as so
from comfyui_distributed_tpu.models.lm_common import rms_norm, swiglu
from comfyui_distributed_tpu.models.registry import get_config
from comfyui_distributed_tpu.ops import attention as attention_ops
from comfyui_distributed_tpu.ops import decode_attention
from comfyui_distributed_tpu.parallel.sharding import expert_range
from comfyui_distributed_tpu.reference import solar_open2 as ref

TINY = get_config("tiny-solar-open2")
PROMPT, STEPS = 75, 9  # two chunks and 11 tokens of a third


sizes_of = ref.Sizes.of


def rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


def rule_inputs(tokens, heads=3, d=16, decay=1.0, seed=0):
    """q, k (unit length), v, a log-decay of the given strength, beta in
    (0, 2) and a state to start from."""
    keys = jax.random.split(jax.random.key(seed), 6)
    q = kda._l2norm(jax.random.normal(keys[0], (tokens, heads, d))) * d ** -0.5
    k = kda._l2norm(jax.random.normal(keys[1], (tokens, heads, d)))
    v = jax.random.normal(keys[2], (tokens, heads, d))
    g = -decay * jax.nn.softplus(jax.random.normal(keys[3], (tokens, heads, d)))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(keys[4], (tokens, heads)))
    return q, k, v, g, beta, jax.random.normal(keys[5], (heads, d, d))


def recurrence(q, k, v, g, beta, state):
    def token(state, xs):
        o, state = kda.kda_step(*xs, state)
        return state, o

    state, o = jax.lax.scan(token, state, (q, k, v, g, beta))
    return o, state


@pytest.mark.parametrize("tokens", [64, 75, 5, 32])
@pytest.mark.parametrize("decay", [0.02, 1.0, 40.0])
def test_the_chunked_delta_rule_is_the_recurrence(tokens, decay):
    """Lengths that are and are not multiples of the chunk of 32 (and one
    shorter than a chunk), a decay that forgets nothing within a chunk
    and one that forgets all of it in a token (exp(-40 x 0.7 x 16) is 0
    in float32; its reciprocal, which a form that divides by the
    cumulative decay would make, is infinite): float32 rounding, ~3e-7
    of values of order one."""
    q, k, v, g, beta, state = rule_inputs(tokens, decay=decay)
    o, after = kda.kda_chunked(q, k, v, g, beta, state, 32)
    o_want, after_want = recurrence(q, k, v, g, beta, state)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(after)).all()
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_want), rtol=1e-5, atol=3e-6)
    np.testing.assert_allclose(np.asarray(after), np.asarray(after_want), rtol=1e-5, atol=3e-6)


@pytest.mark.parametrize("sub", [4, 16, 64])
def test_decay_products_are_the_pairwise_sums_whatever_the_block(sub):
    """`sub` 64 forms every pair's difference itself; 4 and 16 send most
    pairs through a block's first row."""
    q, k, _, g, _, _ = rule_inputs(64, decay=3.0, seed=2)
    x, k, decay = (a.transpose(1, 0, 2) for a in (q, k, jnp.cumsum(g, axis=0)))
    got = kda.decay_products(x, k, decay, sub)
    ratio = jnp.exp(decay[:, :, None, :] - decay[:, None, :, :])
    want = jnp.where(
        jnp.tril(jnp.ones((64, 64), bool)),
        jnp.einsum("hic,hjc,hijc->hij", x, k, jnp.where(ratio < jnp.inf, ratio, 0.0)), 0.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-7)


def test_the_block_solve_is_the_triangular_solve():
    a = jnp.tril(jax.random.normal(jax.random.key(0), (3, 64, 64)), -1) * 0.3
    rhs = jax.random.normal(jax.random.key(1), (3, 64, 8))
    want = jax.scipy.linalg.solve_triangular(
        a + jnp.eye(64), rhs, lower=True, unit_diagonal=True)
    np.testing.assert_allclose(
        np.asarray(kda.unit_lower_solve(a, rhs)), np.asarray(want), rtol=2e-5, atol=2e-5)


def generate(cfg, params, seed=1, temperature=1.0):
    ids = jax.random.randint(jax.random.key(5), (PROMPT,), 0, cfg.vocab_held)
    prefill = so.prefill(cfg, params, ids, cache_len=PROMPT + STEPS, collect=True)
    left = jax.tree_util.tree_map(np.asarray, prefill.cache)  # the decode takes it by donation
    decode = so.decode(
        cfg, params, prefill.cache, prefill.logits, jnp.int32(PROMPT), jax.random.key(seed),
        jnp.float32(temperature), steps=STEPS, collect=True,
    )
    full = jnp.concatenate([ids, decode.ids])
    mine = jnp.concatenate([prefill.logits[None], decode.logits])
    chosen = np.concatenate(
        [np.asarray(prefill.chosen), np.asarray(decode.chosen).transpose(1, 0, 2)], axis=1)
    return full, mine, chosen, left, decode


def reference_of(cfg, params, full, **kwargs):
    return ref.forward(
        sizes_of(cfg), params, full, list(cfg.held_experts),
        positions=np.arange(PROMPT - 1, PROMPT + STEPS), state_at=PROMPT, **kwargs,
    )


@pytest.mark.parametrize("rank, size", [(0, 8), (7, 8), (1, 2), (0, 1)])
def test_prefill_and_decode_through_the_state_tree_match_the_reference_in_float32(rank, size):
    """Float32 weights and activations: only the order of the sums differs
    (the chunked rule, the grouped product, blocked softmax), so the
    logits agree to float32 rounding, ~1e-6 of logits of order 4. 2e-5
    would not pass bfloat16 anywhere (below)."""
    cfg = dataclasses.replace(TINY, ep_rank=rank, ep_size=size)
    params = so.init_params(cfg, jax.random.key(1))
    full, mine, chosen, left, _ = generate(cfg, params)
    want, _, chosen_ref, states = reference_of(cfg, params, full)
    assert rel_l2(mine, want).max() < 2e-5
    assert (np.sort(chosen, -1) == np.sort(np.asarray(chosen_ref), -1)).all()
    # what the prefill left of each KDA layer is the reference's state
    # after the prompt
    np.testing.assert_allclose(left["state"], np.asarray(states), rtol=1e-4, atol=1e-5)


def test_the_state_after_a_prefill_is_the_state_after_as_many_recurrent_steps():
    """The chunked prefill of T tokens and T decode steps from an empty
    state over the same ids leave the same matrix states, convolution
    tails and keys and values."""
    params = so.init_params(TINY, jax.random.key(1))
    ids = jax.random.randint(jax.random.key(5), (PROMPT,), 0, TINY.vocab_held)
    whole = so.prefill(TINY, params, ids, cache_len=PROMPT)
    cache = {
        name: jnp.zeros(s.shape, s.dtype)
        for name, s in so.state_shapes(TINY, PROMPT, jnp.float32).items()}

    @jax.jit
    def walk(cache):
        def one(position, carry):
            logits, cache, _, _ = so.decode_step(TINY, params, carry[1], ids[position], position)
            return logits, cache

        return jax.lax.fori_loop(0, PROMPT, one, (jnp.zeros((TINY.vocab_held,)), cache))

    logits, stepped = walk(cache)
    for name in ("state", "conv", "kv"):
        np.testing.assert_allclose(
            np.asarray(whole.cache[name]), np.asarray(stepped[name]), rtol=1e-4, atol=1e-5,
            err_msg=name)
    assert rel_l2(whole.logits, logits) < 2e-5
    # the tail is the convolutions' last three inputs: nothing else of the prompt
    assert whole.cache["conv"].shape == (3, 3, 3 * TINY.linear_width)


@pytest.mark.parametrize("allow", [True, False])
def test_beta_doubled_or_not_each_matches_its_reference_and_they_differ(allow):
    cfg = dataclasses.replace(TINY, kda_allow_neg_eigval=allow)
    params = so.init_params(cfg, jax.random.key(1))
    full, mine, _, left, _ = generate(cfg, params)
    want, _, _, states = reference_of(cfg, params, full)
    assert sizes_of(cfg).kda_allow_neg_eigval is allow
    assert rel_l2(mine, want).max() < 2e-5
    other = ref.forward(
        dataclasses.replace(sizes_of(cfg), kda_allow_neg_eigval=not allow), params, full,
        list(cfg.held_experts), positions=np.arange(PROMPT - 1, PROMPT + STEPS),
        state_at=PROMPT)
    assert rel_l2(mine, other[0]).min() > 0.05
    assert rel_l2(left["state"].reshape(3, -1), np.asarray(other[3]).reshape(3, -1)).min() > 0.05


def test_bfloat16_stays_near_the_reference_and_float8_does_not():
    """Storage and compute in bfloat16, the KDA state float32: the logits
    stay within a few per cent of the float32 reference's, while the
    reference on float8 operands is tens of per cent away."""
    params = so.init_params(TINY, jax.random.key(1), jnp.bfloat16)
    assert params["layers"][1]["kda"]["a_log"].dtype == jnp.float32
    full, mine, _, left, _ = generate(TINY, params)
    assert left["state"].dtype == np.float32 and left["kv"].dtype == jnp.bfloat16
    want, _, _, _ = reference_of(TINY, params, full)
    low, _, _, _ = reference_of(TINY, params, full, round_to=jnp.float8_e4m3fn)
    assert np.median(rel_l2(mine, want)) < 0.05
    assert np.median(rel_l2(low, want)) > 0.15


def test_a_served_request_collects_nothing_draws_the_same_ids_and_gets_its_state_back():
    params = so.init_params(TINY, jax.random.key(1))
    ids = jax.random.randint(jax.random.key(5), (PROMPT,), 0, TINY.vocab_held)
    _, _, _, _, collected = generate(TINY, params)
    prefill = so.prefill(TINY, params, ids, cache_len=PROMPT + STEPS)
    # the served prefill is the collecting one (PR 64): the decode is what collects nothing
    assert prefill.chosen.shape[1:] == (PROMPT, TINY.num_experts_per_tok)
    given = prefill.cache
    decode = so.decode(
        TINY, params, given, prefill.logits, jnp.int32(PROMPT), jax.random.key(1),
        jnp.float32(1.0), steps=STEPS)
    assert decode.logits is None and decode.chosen is None
    np.testing.assert_array_equal(np.asarray(decode.ids), np.asarray(collected.ids))
    # donated whole: every kind of the state is gone from the caller's
    # hands and comes back in the decode's
    assert all(leaf.is_deleted() for leaf in jax.tree_util.tree_leaves(given))
    assert {name: a.shape for name, a in decode.cache.items()} == {
        name: s.shape for name, s in so.state_shapes(TINY, PROMPT + STEPS, jnp.float32).items()}
    np.testing.assert_allclose(
        np.asarray(decode.cache["state"]), np.asarray(collected.cache["state"]), rtol=1e-6)


def test_another_temperature_builds_no_program():
    params = so.init_params(TINY, jax.random.key(1))
    ids = jax.random.randint(jax.random.key(5), (PROMPT,), 0, TINY.vocab_held)

    def run(temperature):
        prefill = so.prefill(TINY, params, ids, cache_len=PROMPT + STEPS)
        return so.decode(
            TINY, params, prefill.cache, prefill.logits, jnp.int32(PROMPT), jax.random.key(1),
            jnp.float32(temperature), steps=STEPS).ids

    run(1.0)
    before = so.decode._cache_size()
    greedy = run(0.0)
    assert so.decode._cache_size() == before
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(run(0.0)))


def test_the_eight_ranks_shares_of_a_layer_add_up_to_the_uncut_layer():
    """The share test: each rank's expert layer gives the shared expert's
    output plus its own experts' part; summed over the eight ranks with
    the shared expert, the mixer and the residual counted once, that is
    the uncut reference's layer (a KDA layer: index 1)."""
    whole = dataclasses.replace(TINY, ep_size=1, ep_rank=0)
    params = so.init_params(whole, jax.random.key(3))
    block = params["layers"][1]
    h = jax.random.normal(jax.random.key(4), (PROMPT, whole.hidden_size))
    want, _, _ = ref.layer(sizes_of(whole), 1, block, h, list(range(whole.n_routed_experts)))

    x = rms_norm(h, block["mixer_norm"], whole.rms_norm_eps)
    tail = jnp.zeros((3, 3 * whole.linear_width))
    state = jnp.zeros((whole.linear_num_heads, whole.linear_head_dim, whole.linear_head_dim))
    after_mixer = h + so.kda_whole(whole, block["kda"], x, tail, state)[0]
    x = rms_norm(after_mixer, block["ffn_norm"], whole.rms_norm_eps)
    shared = swiglu(x, block["moe"]["shared"])
    routed, pairs = 0.0, 0
    for rank in range(8):
        cfg = dataclasses.replace(TINY, ep_size=8, ep_rank=rank)
        mine = expert_range(whole.n_routed_experts, rank, 8)
        part = dict(block["moe"], experts=jax.tree_util.tree_map(
            lambda w: w[mine.start:mine.stop], block["moe"]["experts"]))
        out, _, sizes = so.moe(cfg, part, x)
        routed = routed + (out - shared)
        pairs += int(sizes.sum())
    got = after_mixer + shared + routed
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    assert pairs == PROMPT * whole.num_experts_per_tok  # every pair fell on exactly one rank


def test_the_router_on_a_hand_made_case_with_a_bias_and_a_tie():
    """Sigmoid scores; the bias chooses and does not weigh; a tie goes to
    the lower index; the chosen scores are renormalised."""
    cfg = dataclasses.replace(TINY, n_routed_experts=8, num_experts_per_tok=3, ep_size=1)
    logits = jnp.asarray([[2.0, 2.0, 0.0, 1.0, 1.0, -1.0, -1.0, 1.0]])
    bias = jnp.zeros((8,)).at[5].set(5.0)
    ids, weights = so.route(cfg, bias, logits)
    assert ids.tolist() == [[5, 0, 1]]
    scores = jax.nn.sigmoid(logits)[0, jnp.asarray([5, 0, 1])]
    np.testing.assert_allclose(np.asarray(weights[0]), np.asarray(scores / scores.sum()), rtol=1e-6)
    ids_ref, weights_ref = ref.route(sizes_of(cfg), bias, logits)
    assert ids_ref.tolist() == ids.tolist()
    np.testing.assert_allclose(np.asarray(weights_ref), np.asarray(weights), rtol=1e-6)
    no_bias, _ = so.route(cfg, jnp.zeros((8,)), logits)
    assert no_bias.tolist() == [[0, 1, 3]]  # 3, 4 and 7 tie: the lowest


def test_grouped_attention_whole_is_grouped_attention_cached():
    """The prefill's form over T tokens and the decode's form token by
    token over the cache the first wrote give the same outputs, and key
    head j serves query heads 2 j and 2 j + 1."""
    params = so.init_params(TINY, jax.random.key(1))
    p = params["layers"][0]["gqa"]
    x = jax.random.normal(jax.random.key(2), (PROMPT, TINY.hidden_size))
    whole, kv = so.gqa_whole(TINY, p, x)
    cache = jnp.zeros(so.state_shapes(TINY, PROMPT, jnp.float32)["kv"].shape).at[0].set(kv)
    for position in (0, 1, PROMPT // 2, PROMPT - 1):
        one, after = so.gqa_cached(TINY, p, x[position:position + 1], cache, 0, position)
        np.testing.assert_allclose(
            np.asarray(one[0]), np.asarray(whole[position]), rtol=2e-5, atol=2e-6)
        # the token's own key and value, written where the prefill had put them
        np.testing.assert_allclose(np.asarray(after), np.asarray(cache), rtol=1e-5, atol=2e-6)
    # against each query head with its own copy of its key head
    q, k, v, _ = so._gqa_projections(TINY, p, x)
    repeated = attention_ops.causal_attention_blocked(
        q[None], jnp.repeat(k, 2, axis=1)[None], jnp.repeat(v, 2, axis=1)[None])
    grouped = attention_ops.causal_attention_blocked(q[None], k[None], v[None])
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(repeated), rtol=1e-5, atol=1e-6)


def _causal_attention_blocked_before_pr38(q, k, v, scale=None):
    """`ops/attention.causal_attention_blocked` as PR 37 left it."""
    import math

    n, m, d = q.shape[1], k.shape[1], q.shape[3]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    block = min(attention_ops.CAUSAL_BLOCK_Q, n)
    outs = []
    for start in range(0, n, block):
        stop = min(start + block, n)
        last = stop + m - n
        scores = scale * jnp.einsum(
            "bqhd,bkhd->bhqk", q[:, start:stop], k[:, :last],
            preferred_element_type=jnp.float32)
        rows = jnp.arange(start, stop)[:, None] + (m - n)
        scores = jnp.where(rows >= jnp.arange(last)[None, :], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        outs.append(jnp.einsum(
            "bhqk,bkhd->bqhd", probs, v[:, :last], preferred_element_type=jnp.float32,
        ).astype(v.dtype))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n, m, heads, d, dv", [(300, 300, 4, 24, 16), (64, 64, 2, 16, 16),
                                                (40, 297, 3, 8, 8)])
def test_at_equal_heads_blocked_causal_attention_is_bit_for_bit_what_it_was(
        n, m, heads, d, dv, dtype):
    """Today's callers (DeepSeek-V2's expanded MLA with a value width of
    its own, Ouro's layers) have as many key heads as query heads: the
    grouped form must leave their results as they were, to the bit."""
    keys = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(keys[0], (2, n, heads, d), dtype)
    k = jax.random.normal(keys[1], (2, m, heads, d), dtype)
    v = jax.random.normal(keys[2], (2, m, heads, dv), dtype)
    got = attention_ops.causal_attention_blocked(q, k, v, scale=0.3)
    want = _causal_attention_blocked_before_pr38(q, k, v, scale=0.3)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))


def test_blocked_causal_attention_refuses_key_heads_that_do_not_divide():
    q = jnp.zeros((1, 8, 4, 8))
    with pytest.raises(ValueError, match="4 query heads over 3"):
        attention_ops.causal_attention_blocked(q, jnp.zeros((1, 8, 3, 8)), jnp.zeros((1, 8, 3, 8)))


@pytest.mark.parametrize("kv_heads", [4, 2, 1])
def test_the_decodes_einsum_form_serves_a_group_of_queries_from_one_key_head(kv_heads):
    """`decode_attention_xla` with a group axis: 4 query heads over 4, 2
    or 1 key heads, against each query head over its own copy."""
    heads, positions, d, position = 4, 40, 16, 29
    keys = jax.random.split(jax.random.key(3), 2)
    q = jax.random.normal(keys[0], (heads, d))
    cache = jax.random.normal(keys[1], (2, 2, kv_heads, positions, d))
    valid = decode_attention.position_valid(jnp.asarray([position]), positions)
    got = decode_attention.decode_attention_xla(q[None], cache, (1,), valid)[0]
    repeated = jnp.repeat(cache, heads // kv_heads, axis=2)
    want = decode_attention.decode_attention_xla(q[None], repeated, (1,), valid)[0]
    assert got.shape == (heads, d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)


def test_the_cut_holds_the_parameters_the_issue_counted():
    cfg = get_config("solar-open2-ep8-4l")
    assert (cfg.num_hidden_layers, len(cfg.held_experts), cfg.vocab_held) == (4, 40, 24576)
    assert [cfg.is_full(layer) for layer in range(4)] == [True, False, False, False]
    assert so.param_count(cfg) == 3_308_353_344
    # the published model: 250.3 B
    assert abs(so.param_count(so.SolarOpen2Config()) / 1e9 - 250.3) < 0.1
    shapes = so.param_shapes(cfg)["layers"]

    def count(tree):
        return so.count_params(tree)

    assert count(shapes[0]["gqa"]) == 109_051_904
    assert count(shapes[1]["kda"]) == 137_732_288
    assert count(shapes[1]["moe"]["experts"]) == 40 * 15_728_640
    state = so.state_shapes(cfg, 8448, jnp.bfloat16)
    assert state["kv"].shape == (1, 2, 8, 8448, 128)          # 4,096 B a position
    assert state["state"].shape == (3, 64, 128, 128) and state["state"].dtype == jnp.float32
    assert state["conv"].shape == (3, 3, 24576)


@pytest.mark.parametrize("field", ["use_rope", "kda_use_full_proj"])
def test_a_form_that_is_not_written_is_refused(field):
    with pytest.raises(ValueError, match="only the published form"):
        so.SolarOpen2Config(**{field: True})
    with pytest.raises(ValueError, match="only the published form"):
        so.SolarOpen2Config(use_gqa_gate=False)
