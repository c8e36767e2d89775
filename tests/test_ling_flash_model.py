"""Ling-3.0-flash against its float32 reference on a tiny preset with every
mechanism (published layers 1-7: a dense KDA layer, then a whole group of
six, KDA, KDA, KDA, MLA, KDA, KDA, sparse; 4 heads of 16, a latent of 24 +
8; 32 experts in 8 groups, 4 groups and 4 experts a token, rank 0 of 8
holding group 0; limits low enough that the clamps act; the MTP module):
every layer kind, the model and the MTP module, the two forms of the delta
rule under the bounded gate and of the latent attention, the grouped
router against a written-out loop, the clamp on both routes of the grouped
products, the eight ranks' shares against the uncut layer, and what a
self-speculative step leaves in the recurrent layers whether its draft was
kept or dropped."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.models import kda, lm_common, mla, moe
from comfyui_distributed_tpu.models import ling_flash as lf
from comfyui_distributed_tpu.models.lm_common import rms_norm, swiglu
from comfyui_distributed_tpu.models.registry import get_config
from comfyui_distributed_tpu.ops import decode_attention
from comfyui_distributed_tpu.ops import expert_matvec as em
from comfyui_distributed_tpu.parallel.sharding import expert_range
from comfyui_distributed_tpu.reference import ling_flash as ref

TINY = get_config("tiny-ling-flash")
PROMPT, NEW = 75, 40  # two chunks of 32 and 11 tokens of a third


def rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


def prompt_ids(cfg, seed=1, tokens=PROMPT):
    return jax.random.randint(jax.random.key(seed), (tokens,), 0, cfg.vocab_held)


def generate(cfg, params, ids, draft_tokens, steps=NEW, temperature=1.0, seed=3, collect=True):
    pre = lf.prefill(cfg, params, ids, cache_len=len(ids) + steps, collect=collect)
    logits = pre.logits
    dec = lf.decode(
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
    verified: row 0 always, row 1 where the draft was kept; (step,
    position the draft was drawn from); and which drafts were kept."""
    steps = int(dec.counts[0])
    position = np.asarray(dec.kept["position"])[:steps]
    accepted = np.asarray(dec.kept["accepted"])[:steps]
    rows = [(s, 0, position[s]) for s in range(steps)]
    rows += [(s, 1, position[s] + 1) for s in range(steps) if accepted[s]]
    return rows, [(s, position[s] - 1) for s in range(steps)], accepted


# --- the delta rule under the bounded gate ------------------------------------


@pytest.mark.parametrize("tokens", [32, 75])
def test_the_chunked_delta_rule_is_the_recurrence_under_the_bounded_gate(tokens):
    """`kda.kda_chunked` against `kda.kda_step` on this model's own
    gates: g in (-5, 0) from `kda_inputs`, beta in (0, 1), from a state
    that is not zero."""
    params = lf.init_params(TINY, jax.random.key(0))
    p = params["layers"][0]["kda"]
    x = jax.random.normal(jax.random.key(5), (tokens, TINY.hidden_size))
    tail = jnp.zeros((3, 3 * TINY.linear_width))
    q, k, v, g, beta, _, window = lf.kda_inputs(TINY, p, x, tail)
    assert float(g.max()) < 0 and float(g.min()) > TINY.kda_lower_bound
    assert 0 < float(beta.min()) and float(beta.max()) < 1
    assert window.shape == (3 + tokens, 3 * TINY.linear_width)
    state = 0.1 * jax.random.normal(jax.random.key(6), (4, 16, 16))
    want, walked = [], state
    for t in range(tokens):
        o, walked = kda.kda_step(q[t], k[t], v[t], g[t], beta[t], walked)
        want.append(o)
    got, after = kda.kda_chunked(q, k, v, g, beta, state, TINY.kda_chunk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(jnp.stack(want)), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(after), np.asarray(walked), rtol=2e-4, atol=2e-5)


def test_seeded_gates_hold_tens_to_hundreds_of_tokens():
    """Under `DT_BIAS_SHIFT` the decay's median is a memory of some
    scores of tokens; without it the state forgets within a token."""
    cfg = get_config("ling-flash-ep8-7l")
    keys = jax.random.split(jax.random.key(0), 3)
    a = jnp.exp(jax.random.normal(keys[0], (32, 1)))
    drawn = jax.random.normal(keys[1], (32, 128)) + jax.random.normal(keys[2], (32, 128))
    alpha = jnp.exp(cfg.kda_lower_bound * jax.nn.sigmoid(a * (drawn - lf.DT_BIAS_SHIFT)))
    assert 0.95 < float(jnp.median(alpha)) < 0.999
    unshifted = jnp.exp(cfg.kda_lower_bound * jax.nn.sigmoid(a * drawn))
    assert float(jnp.median(unshifted)) < 0.2


# --- the two forms of the latent attention -------------------------------------


def test_expanded_and_absorbed_latent_attention_agree_with_a_gate_a_head():
    params = lf.init_params(TINY, jax.random.key(0))
    (block,) = [b for b in params["layers"] if "mla" in b]
    p = block["mla"]
    x = jax.random.normal(jax.random.key(7), (PROMPT, TINY.hidden_size))
    positions = jnp.arange(PROMPT)
    expanded, latents = lf.mla_whole(TINY, p, x, lf._rope(TINY, positions))
    assert latents.shape == (PROMPT, 24 + 8)
    cache = {"latents": jnp.zeros((2, PROMPT + 5, 32)).at[0, :PROMPT].set(latents)}
    for first in (0, PROMPT - 2):  # every position at once, and a step's two
        at = positions[first:]
        got, written = lf.mla_cached(TINY, p, x[first:], cache, 0, at)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(expanded[first:]), rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(
            np.asarray(written["latents"]), np.asarray(cache["latents"]), rtol=1e-6, atol=1e-7)


def test_the_two_forms_are_the_shared_modules_whoever_calls_them():
    """Under both importers the bodies are `models/mla.py`'s over plain
    operands: DeepSeek's wrappers give what a direct call gives."""
    from comfyui_distributed_tpu.models import deepseek_v2 as ds

    cfg = get_config("tiny-deepseek-v2")
    p = ds.init_params(cfg, jax.random.key(0))["layers"][0]["attn"]
    x = jax.random.normal(jax.random.key(1), (20, cfg.hidden_size))
    rope = ds.rope_tables(cfg, jnp.arange(20))
    out, latents = ds.mla_expanded(cfg, p, x, rope)
    q_nope, q_rope = ds._queries(cfg, p, x, *rope)
    direct = mla.expanded(q_nope, q_rope, latents, p["w_uk"], p["w_uv"], cfg.softmax_scale)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(direct.reshape(20, -1) @ p["w_o"]))
    np.testing.assert_array_equal(
        np.asarray(latents), np.asarray(mla.latents(p, x, rope, cfg.rms_norm_eps)))


# --- the router: groups before experts ------------------------------------------


def route_by_hand(logits, bias, k, scale, n_group, topk_group):
    """The rule as a loop over tokens, groups and experts."""
    scores = 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))
    biased = scores + np.asarray(bias, np.float64)
    per = biased.shape[1] // n_group
    ids, weights = [], []
    for t in range(biased.shape[0]):
        of_group = []
        for group in range(n_group):
            mine = sorted(biased[t, group * per:(group + 1) * per], reverse=True)
            of_group.append(mine[0] + mine[1])
        best = sorted(range(n_group), key=lambda gr: (-of_group[gr], gr))[:topk_group]
        among = [e for e in range(biased.shape[1]) if e // per in best]
        chosen = sorted(among, key=lambda e: (-biased[t, e], e))[:k]
        total = sum(scores[t, e] for e in chosen)
        ids.append(chosen)
        weights.append([scale * scores[t, e] / total for e in chosen])
    return np.asarray(ids), np.asarray(weights)


@pytest.mark.parametrize("ties", [False, True])
def test_the_grouped_rule_is_the_loop_with_ties_and_a_bias(ties):
    logits = jax.random.normal(jax.random.key(8), (41, 32))
    if ties:  # few distinct values: equal scores among experts and among groups
        logits = jnp.round(logits)
    bias = 0.2 * jax.random.normal(jax.random.key(9), (32,))
    if ties:
        bias = jnp.round(4 * bias) / 4
    ids, weights = moe.sigmoid_route(logits, bias, 4, scale=2.5, n_group=8, topk_group=4)
    want_ids, want = route_by_hand(logits, bias, 4, 2.5, 8, 4)
    np.testing.assert_array_equal(np.asarray(ids), want_ids)
    np.testing.assert_allclose(np.asarray(weights), want, rtol=1e-5)
    ref_ids, ref_weights = ref.route(ref.Sizes.of(TINY), bias, logits)
    np.testing.assert_array_equal(np.asarray(ref_ids), want_ids)
    np.testing.assert_allclose(np.asarray(ref_weights), want, rtol=1e-5)
    # every chosen expert lies in one of four groups
    assert all(len({e // 4 for e in row}) <= 4 for row in want_ids)


def test_one_group_is_the_rule_without_groups_to_the_bit():
    logits = jax.random.normal(jax.random.key(8), (33, 16))
    bias = 0.1 * jax.random.normal(jax.random.key(9), (16,))
    plain = moe.sigmoid_route(logits, bias, 4, scale=2.5)
    grouped = moe.sigmoid_route(logits, bias, 4, scale=2.5, n_group=1, topk_group=1)
    for a, b in zip(plain, grouped):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and groups change the choice: the best four overall need not lie in four groups of eight
    wide = jax.random.normal(jax.random.key(10), (200, 32))
    free, _ = moe.sigmoid_route(wide, jnp.zeros((32,)), 4)
    held, _ = moe.sigmoid_route(wide, jnp.zeros((32,)), 4, n_group=8, topk_group=2)
    assert np.any(np.asarray(free) != np.asarray(held))


# --- the clamp, on both routes of the grouped products ----------------------------


def clamped_layer_by_hand(p, x, held, ids, weights, limit, shared_limit):
    def mlp(w_gate_up, w_down, rows, cap):
        gate, up = np.split(rows @ w_gate_up, 2, axis=-1)
        if cap:
            gate, up = np.minimum(gate, cap), np.clip(up, -cap, cap)
        return (gate / (1.0 + np.exp(-gate)) * up) @ w_down

    x = np.asarray(x, np.float64)
    out = mlp(np.asarray(p["shared"]["w_gate_up"], np.float64),
              np.asarray(p["shared"]["w_down"], np.float64), x, shared_limit)
    for row, expert in enumerate(held):
        weight = np.where(np.asarray(ids) == expert, np.asarray(weights), 0.0).sum(-1)
        out = out + weight[:, None] * mlp(
            np.asarray(p["experts"]["w_gate_up"][row], np.float64),
            np.asarray(p["experts"]["w_down"][row], np.float64), x, limit)
    return out


@pytest.mark.parametrize("route", ["xla", "kernel"])
@pytest.mark.parametrize("limit, shared_limit", [(0.0, 0.0), (4.0, 7.0), (7.0, 4.0), (4.0, 0.0)])
def test_the_clamp_holds_gate_and_up_on_either_route(limit, shared_limit, route, monkeypatch):
    """Inputs large enough that an unclamped gate and up pass 7: the
    layer under a limit is the written-out one, on `ragged_dot` and on
    the kernel (interpreted) alike, and a limit of 0 clamps nothing."""
    if route == "kernel":
        monkeypatch.setattr(moe, "expert_matvec_route", lambda *shape: "kernel")
        monkeypatch.setattr(
            moe, "expert_matvec", functools.partial(em.expert_matvec, interpret=True))
    cfg = dataclasses.replace(TINY, hidden_size=128, moe_intermediate_size=64,
                              moe_shared_expert_intermediate_size=64)
    p = lf.init_params(cfg, jax.random.key(3))["layers"][-1]["moe"]
    x = 6.0 * jax.random.normal(jax.random.key(4), (2, 128))
    rule = functools.partial(
        moe.sigmoid_route, bias=p["bias"], k=4, scale=2.5, n_group=8, topk_group=4)
    # a rule that sends every pair to held experts, so the routed clamp is reached
    to_held = lambda logits: (rule(logits)[0] % 4, rule(logits)[1])
    jaxpr = str(jax.make_jaxpr(
        lambda x: moe.expert_layer(p, x, cfg.held_experts, to_held, limit, shared_limit))(x))
    assert ("pallas_call" in jaxpr) == (route == "kernel")
    out, ids, sizes = moe.expert_layer(p, x, cfg.held_experts, to_held, limit, shared_limit)
    assert int(sizes.sum()) == 2 * 4
    want = clamped_layer_by_hand(
        p, x, cfg.held_experts, ids, to_held(x.astype(jnp.float32) @ p["w_g"])[1],
        limit, shared_limit)
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-4, atol=2e-4)
    unclamped, _, _ = moe.expert_layer(p, x, cfg.held_experts, to_held)
    assert (limit or shared_limit) == 0 or not np.allclose(
        np.asarray(out), np.asarray(unclamped), rtol=1e-3)


def test_a_layers_limits_are_read_at_its_published_index_and_the_modules_are_the_last():
    cfg = get_config("ling-flash-ep8-7l")
    assert [cfg.limits(layer) for layer in cfg.layers] == [(0.0, 0.0)] * 7
    assert cfg.limits(-1) == (4.0, 7.0)
    whole = lf.LingFlashConfig()
    assert [layer for layer in whole.layers if whole.limits(layer)[0]] == list(range(35, 42))
    assert [whole.limits(layer)[1] for layer in range(33, 42)] == [0, 5, 5, 5, 5, 5, 5, 7, 7]
    assert TINY.limits(5) == (0.5, 0.75) and TINY.limits(-1) == (0.5, 1.0)


# --- every layer kind, the model, the MTP module ---------------------------------


@pytest.mark.parametrize("layer", list(TINY.layers))
def test_a_layer_of_each_kind_is_the_references(layer):
    """Published layers 1-7, each alone over a random residual stream:
    KDA with a dense part (1), KDA with the mixture (2-4, 6-7; 5-7 under
    limits), MLA with the mixture (5)."""
    params = lf.init_params(TINY, jax.random.key(0))
    block = params["layers"][layer - TINY.first_layer]
    assert ("mla" in block) == (layer == 5) and ("mlp" in block) == (layer == 1)
    h = jax.random.normal(jax.random.key(layer), (PROMPT, TINY.hidden_size))
    rope = lf._rope(TINY, jnp.arange(PROMPT))

    def mixer(kind, p, x):
        if kind == "mla":
            return lf.mla_whole(TINY, p, x, rope)
        out, tail, state = lf.kda_whole(
            TINY, p, x, jnp.zeros((3, 3 * TINY.linear_width)), jnp.zeros((4, 16, 16)))
        return out, (state, tail)

    got, _, ids, _ = lf._layer(TINY, block, h, layer, mixer)
    want, want_ids = ref.layer(ref.Sizes.of(TINY), layer, block, h, list(TINY.held_experts))
    assert float(rel_l2(got, want).max()) < 2e-5
    if ids is not None:
        np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_ids))


def test_the_clamps_act_in_the_tiny_models_last_layers():
    """The reference without limits is another model at layers 5-7."""
    params = lf.init_params(TINY, jax.random.key(0))
    h = jax.random.normal(jax.random.key(7), (PROMPT, TINY.hidden_size))
    sizes, held = ref.Sizes.of(TINY), list(TINY.held_experts)
    free = dataclasses.replace(sizes, expert_limits=(0,) * 8, shared_limits=(0,) * 8)
    want, _ = ref.layer(sizes, 7, params["layers"][-1], h, held)
    other, _ = ref.layer(free, 7, params["layers"][-1], h, held)
    assert float(np.median(rel_l2(other, want))) > 1e-3


@pytest.mark.parametrize("rank, size", [(0, 8), (7, 8), (0, 1)])
def test_prefill_and_plain_decode_through_the_state_tree_match_the_reference(rank, size):
    """`draft_tokens` 0, every position's logits (not tokens)."""
    cfg = dataclasses.replace(TINY, ep_rank=rank, ep_size=size)
    params, ids = lf.init_params(cfg, jax.random.key(0)), prompt_ids(cfg)
    logits, dec = generate(cfg, params, ids, 0)
    want, _ = reference_of(cfg, params, ids, dec)
    assert rel_l2(logits, want[PROMPT - 1]) < 2e-5
    assert rel_l2(dec.kept["logits"], want[PROMPT:]).max() < 2e-5
    assert np.asarray(dec.counts).tolist()[:3] == [NEW, 0, 0]
    assert int(dec.cache["slot"]) == 0  # one slot is read and written over


@pytest.fixture(scope="module")
def drafting():
    params, ids = lf.init_params(TINY, jax.random.key(0)), prompt_ids(TINY)
    logits, dec = generate(TINY, params, ids, 1)
    return params, ids, logits, dec


def test_a_drafting_decode_matches_the_reference_at_every_position_it_verified(drafting):
    """The main model and the MTP module: after kept and dropped drafts
    alike the recurrent state is the confirmed tokens' and no other's."""
    params, ids, logits, dec = drafting
    want, drafts = reference_of(TINY, params, ids, dec)
    rows, drawn, accepted = verified(dec)
    assert 0 < accepted.sum() < len(accepted)  # drafts were both kept and dropped
    assert rel_l2(logits, want[PROMPT - 1]) < 2e-5
    for step, row, position in rows:
        assert rel_l2(dec.kept["logits"][step, row], want[position]) < 2e-5, (step, row)
    for step, position in drawn:
        assert rel_l2(dec.kept["draft_logits"][step], drafts[position]) < 2e-5, step


def test_keeping_every_draft_in_the_state_is_another_model(drafting, monkeypatch):
    """The fault the two slots are there to prevent: the slot that holds
    the state after the draft stands whatever the draft's fate."""
    params, ids, _, _ = drafting
    monkeypatch.setattr(lf, "standing", lambda slot, kept: slot)

    def again(cfg, *operands, **options):  # a function of its own: no trace of `decode` is reused
        return lf.decode.__wrapped__(cfg, *operands, **options)

    always = jax.jit(
        again, static_argnums=0, static_argnames=("steps", "collect", "draft_tokens"))
    pre = lf.prefill(TINY, params, ids, cache_len=PROMPT + NEW, collect=True)
    dec = always(
        TINY, params, pre.cache, pre.logits, jnp.int32(PROMPT), jax.random.key(3),
        jnp.float32(1.0), steps=NEW, collect=True, draft_tokens=1)
    want, _ = reference_of(TINY, params, ids, dec)
    rows, _, accepted = verified(dec)
    assert 0 < accepted.sum() < len(accepted)
    errors = [float(rel_l2(dec.kept["logits"][s, r], want[p])) for s, r, p in rows]
    assert np.median(errors) > 1e-3


@pytest.mark.parametrize("control", [{"bounded_decay": False}, {"grouped": False}])
def test_a_reference_with_another_gate_or_router_is_another_model(drafting, control):
    params, ids, _, dec = drafting
    want, _ = reference_of(TINY, params, ids, dec, **control)
    rows, _, _ = verified(dec)
    errors = [float(rel_l2(dec.kept["logits"][s, r], want[p])) for s, r, p in rows]
    assert np.median(errors) > 1e-2


def test_bfloat16_stays_near_the_reference_and_float8_does_not():
    params = lf.init_params(TINY, jax.random.key(0), jnp.bfloat16)
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
    assert dec.cache["state"][0].dtype == jnp.float32 and dec.cache["conv"].dtype == jnp.bfloat16


# --- the keep rule: what a step leaves in the recurrent layers ----------------------


def one_step(cfg, params, cache, tokens, position, kept=None):
    """`main_step` over a copy of `cache`, and after two positions the
    slot told whether the draft was kept."""
    _, _, cache, _, _ = lf.main_step(
        cfg, params, dict(cache), jnp.asarray(tokens, jnp.int32), jnp.int32(position))
    if kept is not None:
        cache = {**cache, "slot": lf.standing(cache["slot"], jnp.asarray(kept))}
    return cache


@pytest.mark.parametrize("slot", [0, 1])
def test_a_dropped_draft_leaves_a_one_position_steps_state_and_a_kept_one_two_steps(slot):
    """The keep test, from either slot standing: after a dropped draft
    every KDA layer's state and tail are those of a step that ran the last
    token alone, bit for bit the same whatever the draft was; after a kept
    one, those of two one-position steps. Against the one-position
    programs the comparison is to float32's last digits and not to the
    bit: a product of two rows and a product of one round differently on
    the CPU, in the layers before the state as well."""
    params, ids = lf.init_params(TINY, jax.random.key(0)), prompt_ids(TINY)
    cache = dict(lf.prefill(TINY, params, ids, cache_len=PROMPT + 4).cache)
    if slot:  # what stands moved to the other slot, the first left with rubbish
        cache["state"] = tuple(jnp.flip(held, axis=0).at[0].set(7.0) for held in cache["state"])
        cache["conv"] = jnp.flip(cache["conv"], axis=1).at[:, 0].set(7.0)
        cache["slot"] = jnp.int32(1)
    step = jax.jit(one_step, static_argnums=0)
    last, draft, other = 11, 23, 301
    alone = step(TINY, params, cache, [last], PROMPT)
    then = step(TINY, params, alone, [draft], PROMPT + 1)
    dropped = step(TINY, params, cache, [last, draft], PROMPT, False)
    dropped_other = step(TINY, params, cache, [last, other], PROMPT, False)
    kept = step(TINY, params, cache, [last, draft], PROMPT, True)
    assert int(alone["slot"]) == slot == int(kept["slot"]) and int(dropped["slot"]) == 1 - slot
    for mine, theirs in zip(lf.standing_state(dropped), lf.standing_state(dropped_other)):
        np.testing.assert_array_equal(np.asarray(mine), np.asarray(theirs))
    for got, want in ((dropped, alone), (kept, then)):
        for mine, theirs in zip(lf.standing_state(got), lf.standing_state(want)):
            assert mine.shape[0] == TINY.kda_layers == 6
            np.testing.assert_allclose(np.asarray(mine), np.asarray(theirs), rtol=0, atol=5e-6)
    # the draft did reach the state it was multiplied into: the two differ
    assert not np.array_equal(
        np.asarray(lf.standing_state(kept)[0]), np.asarray(lf.standing_state(dropped)[0]))
    # and the latents a dropped draft wrote lie past the confirmed positions
    np.testing.assert_allclose(
        np.asarray(dropped["latents"][:1, :PROMPT + 1]),
        np.asarray(alone["latents"][:1, :PROMPT + 1]), rtol=0, atol=5e-6)


def test_at_temperature_zero_drafting_changes_no_id():
    """Greedy: a draft is kept iff it is the main model's largest, so the
    ids are those of one-token steps."""
    params, ids = lf.init_params(TINY, jax.random.key(0)), prompt_ids(TINY)
    _, plain = generate(TINY, params, ids, 0, temperature=0.0, collect=False)
    _, drafted = generate(TINY, params, ids, 1, temperature=0.0, collect=False)
    np.testing.assert_array_equal(np.asarray(drafted.ids), np.asarray(plain.ids))
    assert int(drafted.counts[0]) <= NEW - 1


def test_the_counts_are_the_steps_and_the_drafts_kept(drafting):
    _, _, _, dec = drafting
    steps, drafted, accepted, read = np.asarray(dec.counts).tolist()
    _, _, kept = verified(dec)
    assert steps == drafted and accepted == kept.sum()
    assert 1 + steps + accepted in (NEW, NEW + 1)
    # held experts a step and sparse layer read: at most all four of rank 0's, seven layers
    assert 0 < read <= steps * 4 * 7
    assert int(np.asarray(dec.loads).sum()) >= read


@pytest.mark.parametrize("steps", [1, 2, 7, 40])
def test_exactly_as_many_ids_as_asked_for_whatever_was_kept(steps):
    params, ids = lf.init_params(TINY, jax.random.key(0)), prompt_ids(TINY)
    for seed in (3, 4):
        _, dec = generate(TINY, params, ids, 1, steps=steps, seed=seed, collect=False)
        taken, _, accepted, _ = np.asarray(dec.counts).tolist()
        assert dec.ids.shape == (steps,)
        assert 1 + taken + accepted in (steps, steps + 1) and taken <= max(steps - 1, 0)


def test_a_served_request_collects_nothing_draws_the_same_ids_and_gets_its_state_back(drafting):
    params, ids, _, collected = drafting
    pre = lf.prefill(TINY, params, ids, cache_len=PROMPT + NEW)
    # the served prefill is the collecting one (PR 64): the decode is what collects nothing
    assert pre.chosen.shape[1:] == (PROMPT, TINY.num_experts_per_tok)
    shapes = lf.state_shapes(TINY, PROMPT + NEW, jnp.float32)
    of = lambda tree: jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), tree)
    assert of(pre.cache) == of(shapes)
    assert shapes["latents"].shape == (2, PROMPT + NEW, 32)
    assert [s.shape for s in shapes["state"]] == [(2, 4, 16, 16)] * 6
    assert shapes["conv"].shape == (6, 2, 3, 192)
    dec = lf.decode(
        TINY, params, pre.cache, pre.logits, jnp.int32(PROMPT), jax.random.key(3),
        jnp.float32(1.0), steps=NEW, draft_tokens=1)
    assert dec.kept is None and set(dec.cache) == set(shapes)
    np.testing.assert_array_equal(np.asarray(dec.ids), np.asarray(collected.ids))
    np.testing.assert_array_equal(np.asarray(dec.counts), np.asarray(collected.counts))


def test_two_drafts_a_step_are_refused():
    params, ids = lf.init_params(TINY, jax.random.key(0)), prompt_ids(TINY)
    with pytest.raises(ValueError, match="drafts one token a step"):
        generate(TINY, params, ids, 2, collect=False)


def test_the_drafting_rule_is_the_one_both_drafting_models_import():
    """The rule and the loop around it are `lm_common.py`'s for the three
    drafting models, the prompt's walk in parts for the two that cut it:
    no model's file holds a loop of its own for either."""
    import inspect

    from comfyui_distributed_tpu.models import glm_dsa, granite_hybrid, k_exaone

    assert lm_common.draft_loop.__globals__["verify"] is lm_common.verify
    for module in (lf, k_exaone, glm_dsa):
        assert module.draft_loop is lm_common.draft_loop and module.drafts is lm_common.drafts
        assert module.drafting_report is lm_common.drafting_report
        assert "while_loop" not in inspect.getsource(module), module.__name__
    for module in (glm_dsa, granite_hybrid):
        assert module.prefill_in_parts is lm_common.prefill_in_parts
        assert module.parts_of is lm_common.parts_of
        assert "lax.scan" not in inspect.getsource(module.prefill.__wrapped__), module.__name__
    # the drafting loop's, and since PR 58 the denoising passes' of `denoise_loop`
    assert inspect.getsource(lm_common).count("lax.while_loop(") == 2
    assert lf.kda_step is kda.kda_step and lf.kda_chunked is kda.kda_chunked
    assert lf.expert_layer is moe.expert_layer and lf.sigmoid_route is moe.sigmoid_route


# --- the cut ---------------------------------------------------------------------


def test_the_eight_ranks_shares_of_a_layer_add_up_to_the_uncut_layer():
    """The shares test: each rank's expert layer gives the shared expert's
    output plus its own experts' part (rank r holds routing group r whole);
    summed over the eight ranks with the shared expert, the mixer and the
    residual counted once, that is the uncut reference's layer (published
    layer 6: KDA, the mixture under both limits)."""
    whole = dataclasses.replace(TINY, ep_size=1, ep_rank=0)
    params = lf.init_params(whole, jax.random.key(3))
    layer = 6
    block = params["layers"][layer - whole.first_layer]
    h = jax.random.normal(jax.random.key(4), (PROMPT, whole.hidden_size))
    want, _ = ref.layer(ref.Sizes.of(whole), layer, block, h, list(range(whole.num_experts)))

    x = rms_norm(h, block["mixer_norm"], whole.rms_norm_eps)
    mixed, _, _ = lf.kda_whole(
        whole, block["kda"], x, jnp.zeros((3, 3 * whole.linear_width)), jnp.zeros((4, 16, 16)))
    after_mixer = h + mixed
    x = rms_norm(after_mixer, block["ffn_norm"], whole.rms_norm_eps)
    shared = swiglu(x, block["moe"]["shared"], whole.limits(layer)[1])
    routed, pairs = 0.0, 0
    for rank in range(8):
        cfg = dataclasses.replace(TINY, ep_size=8, ep_rank=rank)
        mine = expert_range(whole.num_experts, rank, 8)
        assert list(mine) == list(range(4 * rank, 4 * rank + 4))  # a routing group
        part = {"moe": dict(block["moe"], experts=jax.tree_util.tree_map(
            lambda w: w[mine.start:mine.stop], block["moe"]["experts"]))}
        out, _, sizes = lf._feed_forward(cfg, part, x, layer)
        routed = routed + (out - shared)
        pairs += int(sizes.sum())
    got = after_mixer + shared + routed
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    assert pairs == PROMPT * whole.num_experts_per_tok  # every pair fell on exactly one rank


def test_the_cut_holds_the_parameters_the_issue_counted():
    cfg = get_config("ling-flash-ep8-7l")
    assert (cfg.num_hidden_layers, len(cfg.held_experts), cfg.vocab_held) == (7, 64, 19648)
    assert list(cfg.layers) == [1, 2, 3, 4, 5, 6, 7]
    assert [cfg.is_mla(i) for i in cfg.layers] == [False] * 4 + [True] + [False] * 2
    assert [cfg.is_dense(i) for i in cfg.layers] == [True] + [False] * 6
    assert (cfg.kda_layers, cfg.mla_layers, cfg.sparse_layers) == (6, 1, 6)
    assert list(cfg.held_experts) == list(range(64))  # routing group 0 whole
    shapes = lf.param_shapes(cfg)
    kda_mixer = lf.count_params(shapes["layers"][0]["kda"])
    assert kda_mixer == 2560 * (12288 + 2 * 4096 + 32) + 4 * 12288 + 4096 * 2560 + 32 + 4096 + 128
    mla_mixer = lf.count_params(shapes["layers"][4]["mla"])
    assert mla_mixer == 2560 * (6144 + 576 + 32) + 512 + 2 * 512 * 4096 + 4096 * 2560
    assert lf.count_params(shapes["layers"][0]["mlp"]) == 3 * 2560 * 6144          # 47.19 M
    sparse = lf.count_params(shapes["layers"][1]["moe"])
    assert sparse == 2560 * 512 + 512 + 65 * 3 * 2560 * 768                        # 384.7 M
    assert lf.count_params(shapes["mtp"]) == (
        2 * 2560 * 2560 + 3 * 2560 + 2 * 2560 + mla_mixer + sparse)                # 429.8 M
    assert lf.param_count(cfg) == 3_296_050_624                                    # 6.59 GB
    # the published model: "~125B", 124.4 B by these equations without the MTP module
    whole = lf.LingFlashConfig()
    total = lf.param_count(whole) - lf.count_params(lf.param_shapes(whole)["mtp"])
    assert 124.0e9 < total < 125.0e9
    assert sum(whole.is_mla(i) for i in whole.layers) == 7  # 35 KDA : 7 MLA, 5 : 1


def test_a_form_that_is_not_written_is_refused():
    with pytest.raises(ValueError, match="one MTP module"):
        lf.LingFlashConfig(num_nextn_predict_layers=2)
    with pytest.raises(ValueError, match="do not reach layer 7"):
        lf.LingFlashConfig(num_hidden_layers=7, first_layer=1, expert_swiglu_limit_list=(0,) * 7)


def test_two_queries_over_the_latents_are_each_query_alone_under_its_own_mask():
    keys = jax.random.split(jax.random.key(6), 5)
    q_nope = jax.random.normal(keys[0], (2, 4, 16))
    q_rope = jax.random.normal(keys[1], (2, 4, 8))
    cache = jax.random.normal(keys[2], (40, 32))
    w_uk = jax.random.normal(keys[3], (24, 4, 16))
    w_uv = jax.random.normal(keys[4], (24, 4, 16))
    valid = decode_attention.position_valid(jnp.asarray([29, 30]), 40)
    got = mla.absorbed(q_nope, q_rope, cache, valid, w_uk, w_uv, 0.2)
    for row, position in enumerate((29, 30)):
        alone = mla.absorbed(
            q_nope[row:row + 1], q_rope[row:row + 1], cache[:position + 1],
            jnp.ones((1, position + 1), bool), w_uk, w_uv, 0.2)
        np.testing.assert_allclose(np.asarray(got[row]), np.asarray(alone[0]), rtol=2e-5, atol=2e-5)
