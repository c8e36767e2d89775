"""LongCat-Flash-Chat (`models/longcat_flash.py`) against its float32
reference (`reference/longcat_flash.py`) at a small size on the CPU,
seeded weights: the double layer with its expert branch on the shortcut,
a router wider than its experts, identities, the rescale; the prefill in
parts (the expert branch in blocks) then the decode through the eight
caches against the reference's one pass; a prompt that is no whole
number of parts; the ranks' shares of a layer against the uncut layer;
tokens all or none of whose chosen ids are identities; the parameters
the issue counted; the ladder; controls that have to fail; what `report`
says of a request.

Tolerances. Float32 against float32 in another order of operations (the
absorbed form, parts, blocks, the grouped product): 2e-5 relative L2 of
a row of logits, dots3's test's. A control moves the median by more than
0.005."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.models import longcat_flash as lf
from comfyui_distributed_tpu.models import moe
from comfyui_distributed_tpu.models.registry import create_model, get_config
from comfyui_distributed_tpu.parallel.sharding import expert_range
from comfyui_distributed_tpu.reference import longcat_flash as ref

TINY = get_config("tiny-longcat-flash")
# 53 positions in parts of 16: three whole parts (two blocks of 8 each) and five left over
PROMPT, NEW = 53, 12
TOLERANCE = 2e-5
CONTROL = 0.005


def rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


def prompt_ids(cfg, seed=1, tokens=PROMPT):
    return jax.random.randint(jax.random.key(seed), (tokens,), 0, cfg.vocab_held)


@pytest.fixture(scope="module")
def params():
    return lf.init_params(TINY, jax.random.key(0))


def served(cfg, params, ids, new=NEW):
    """One request through both programs, everything kept: (prefill,
    decode, the final ids, the logits at the last prompt position and
    every decoded one)."""
    prefill = lf.prefill(cfg, params, ids, cache_len=len(ids) + new, collect=True)
    decode = lf.decode(
        cfg, params, prefill.cache, prefill.logits, jnp.int32(len(ids)), jax.random.key(9),
        jnp.float32(1.0), steps=new, collect=True)
    full = np.concatenate([np.asarray(ids), np.asarray(decode.ids)])
    logits = np.concatenate([np.asarray(prefill.logits)[None], np.asarray(decode.kept["logits"])])
    return prefill, decode, full, logits


@pytest.fixture(scope="module")
def run(params):
    prefill, decode, full, logits = served(TINY, params, prompt_ids(TINY))
    want = ref.forward(ref.Sizes.of(TINY), params, full, list(TINY.held_experts), row_block=16)
    return prefill, decode, full, logits, want


def kept_rows(full):
    """The rows of the reference's pass that the system's logits stand at."""
    return np.arange(PROMPT - 1, len(full))


# --- the sizes -------------------------------------------------------------


def test_the_published_sizes_and_the_held_cut_count_what_the_issue_counted():
    published = lf.LongcatFlashConfig()
    assert lf.param_count(published) == 560_664_980_480
    held = get_config("longcat-flash-chat-ep64-4l")
    assert lf.param_count(held) == 3_964_789_760
    assert (held.num_layers, list(held.held_experts), held.vocab_held) == (4, list(range(8)), 16384)
    assert (held.router_width, held.zero_expert_num, held.moe_topk) == (768, 256, 12)
    assert (held.s_q, round(held.s_kv, 4)) == (2.0, 3.4641)
    shapes = lf.param_shapes(dataclasses.replace(published, num_layers=1))["layers"][0]
    outside = lf.count_params({k: v for k, v in shapes.items() if k != "moe"}) + (
        6144 * 768 + 768)
    assert outside == 638_874_368
    assert lf.count_params(shapes["sub"][0]["attn"]) == 90_572_800
    assert lf.count_params(shapes["sub"][0]["mlp"]) == 226_492_416


def test_the_ladder_is_a_blocks_pairs_over_the_routers_width():
    """12 x 1,024 pairs a block at 8 of 768: the even share is 128 rows,
    under the lowest rung of one tile; a part of 8,192 whole would climb
    to 98,304 rows."""
    assert moe.row_ladder(12 * 1024, 8, 768) == (256, 512, 768, 1536, 3072, 6144, 12288)
    assert moe.row_ladder(12 * 8192, 8, 768)[-1] == 98304
    assert moe.row_ladder(12, 8, 768) == (12,)
    cfg = get_config("longcat-flash-chat-ep64-4l")
    assert lf.expert_blocks(cfg, 8192) == [1024] * 8
    assert lf.expert_blocks(cfg, 2500) == [1024, 1024, 452]
    assert lf.expert_blocks(TINY, 16) == [8, 8] and lf.expert_blocks(TINY, 5) == [5]


# --- both programs against the reference's one pass ---------------------------


def test_prefill_in_parts_then_decode_through_the_caches_is_the_references_one_pass(run):
    prefill, decode, full, logits, (want, chosen, caches) = run
    assert np.max(rel_l2(logits, np.asarray(want)[kept_rows(full)])) < TOLERANCE
    system = np.concatenate(
        [np.asarray(prefill.chosen), np.asarray(decode.kept["chosen"]).transpose(1, 0, 2)], axis=1)
    assert np.array_equal(np.sort(system, -1), np.sort(np.asarray(chosen), -1))
    assert len(decode.cache["latents"]) == len(caches) == 2 * TINY.num_layers == 4
    for mine, theirs in zip(decode.cache["latents"], caches):
        np.testing.assert_allclose(
            np.asarray(mine), np.asarray(theirs), rtol=2e-4, atol=2e-5)
    # a layer's two attentions keep caches of their own
    assert not np.allclose(decode.cache["latents"][0], decode.cache["latents"][1])


@pytest.mark.parametrize("tokens", [5, 16, 21, 48])
def test_a_prompt_that_is_no_whole_number_of_parts_or_blocks(params, tokens):
    """5: one short block; 16: one part; 21: a part and five; 48: three parts."""
    ids = prompt_ids(TINY, seed=3, tokens=tokens)
    prefill = lf.prefill(TINY, params, ids, cache_len=tokens + 2)
    want, _, caches = ref.forward(
        ref.Sizes.of(TINY), params, np.asarray(ids), list(TINY.held_experts), row_block=16)
    assert rel_l2(prefill.logits, np.asarray(want)[-1]) < TOLERANCE
    np.testing.assert_allclose(
        np.asarray(prefill.cache["latents"][-1])[:tokens], np.asarray(caches[-1]),
        rtol=2e-4, atol=2e-5)
    parts = -(-tokens // TINY.prefill_part)
    assert prefill.loads.shape == (parts, TINY.num_layers, min(tokens, 16) // 8 or 1, 4)
    assert int(np.sum(prefill.real)) == tokens * TINY.num_layers


def test_parts_and_blocks_change_no_number_against_one_part_and_one_block(params):
    ids = prompt_ids(TINY, seed=4, tokens=40)
    whole = dataclasses.replace(TINY, prefill_part=64, expert_block=64, attention_heads_a_call=4)
    a = lf.prefill(TINY, params, ids, cache_len=40)
    b = lf.prefill(whole, params, ids, cache_len=40)
    assert rel_l2(a.logits, b.logits) < TOLERANCE
    assert np.array_equal(np.sum(a.loads, axis=(0, 2)), np.sum(b.loads, axis=(0, 2)))
    assert np.array_equal(np.sum(a.real, axis=0), np.sum(b.real, axis=0))


# --- the shares add up --------------------------------------------------------


def test_the_ranks_routed_parts_the_identities_once_and_the_dense_path_once_are_the_uncut_layer():
    uncut = dataclasses.replace(TINY, ep_size=1)
    params = lf.init_params(uncut, jax.random.key(5))
    block = params["layers"][0]
    h = jax.random.normal(jax.random.key(6), (11, TINY.hidden_size))
    sizes = ref.Sizes.of(uncut)
    seen = np.tril(np.ones((11, 11), bool))
    y, m, ids, _ = ref.layer(sizes, block, h, list(range(8)), jnp.arange(11), seen)
    # u as the reference has it: the branch's input
    first = block["sub"][0]
    x = ref._rms_norm(h, first["attn_norm"], sizes.rms_norm_eps)
    with jax.default_matmul_precision("highest"):
        out, _ = ref.attention(sizes, first["attn"], x, jnp.arange(11), seen)
        u = ref._rms_norm(h + out, first["ffn_norm"], sizes.rms_norm_eps)
    route = lambda logits: lf.route(uncut, block["moe"]["bias"], logits)  # noqa: E731
    routed = []
    for rank in range(2):
        held = expert_range(8, rank, 2)
        mine = {**block["moe"], "experts": jax.tree_util.tree_map(
            lambda w: w[held.start:held.stop], block["moe"]["experts"])}
        routed.append(moe.expert_layer(mine, u, held, route)[0])
        with_identities = moe.expert_layer(mine, u, held, route, identities=8)[0]
        identity = with_identities - routed[-1]  # the same on either rank: no chip's share
    assert np.any(np.asarray(ids) >= 8) and float(jnp.linalg.norm(identity)) > 0
    total = (y - m) + routed[0] + routed[1] + identity
    assert np.max(rel_l2(total, y)) < TOLERANCE
    # and the layer with every expert held is the system's own
    got = lf.walk(
        dataclasses.replace(uncut, num_layers=1), {"layers": [block]},
        (jnp.zeros((11, TINY.cache_width)),) * 2, h,
        lambda p, x, cache: lf.attention_part(uncut, (11,), p, x, cache, jnp.arange(11)))[0]
    assert np.max(rel_l2(got, y)) < TOLERANCE


@pytest.mark.parametrize("bias, real", [(+1.0, 0), (-1.0, 3)])
def test_a_token_all_of_whose_ids_are_identities_and_one_none_of_whose_are(params, bias, real):
    """Built by hand through the selection bias: +1 on the four
    identities (a softmax's score is under 1), or -1."""
    by_hand = jax.tree_util.tree_map(lambda a: a, params)
    for block in by_hand["layers"]:
        block["moe"] = {**block["moe"], "bias": jnp.zeros((12,)).at[8:].set(bias)}
    ids = prompt_ids(TINY, seed=7, tokens=20)
    prefill = lf.prefill(TINY, by_hand, ids, cache_len=20, collect=True)
    assert np.all(np.sum(np.asarray(prefill.chosen) < 8, axis=-1) == real)
    assert int(np.sum(prefill.real, axis=0)[real]) == 20 * TINY.num_layers
    want, _, _ = ref.forward(
        ref.Sizes.of(TINY), by_hand, np.asarray(ids), list(TINY.held_experts), row_block=16)
    assert rel_l2(prefill.logits, np.asarray(want)[-1]) < TOLERANCE
    if not real:
        assert int(np.sum(prefill.loads)) == 0


# --- controls ----------------------------------------------------------------


WRONG = {
    "no rescale of the query": dict(rescale_q=False),
    "no rescale of the latent": dict(rescale_kv=False),
    "renormalised weights": dict(renormalise=True),
    "factor 1": dict(routed_scaling_factor=1.0),
    "identities dropped": dict(identities=False),
    "the branch read from x": dict(branch_from_x=True),
    "the branch added after the first feed-forward": dict(branch_after_first=True),
    "rotation by halves": dict(rotate_halves=True),
}


@pytest.mark.parametrize("name", sorted(WRONG))
def test_a_reference_with_a_wrong_mechanism_is_outside_the_tolerance(run, params, name):
    _, _, full, logits, _ = run
    wrong, _, _ = ref.forward(
        ref.Sizes.of(TINY, **WRONG[name]), params, full, list(TINY.held_experts), row_block=16)
    assert np.median(rel_l2(logits, np.asarray(wrong)[kept_rows(full)])) > CONTROL


def test_one_cache_shared_by_a_layers_two_attentions_is_outside_the_tolerance(
        run, params, monkeypatch):
    """The system with a wrong mechanism: a layer's second attention
    writes and reads the first's cache. Traced anew (blocks of 4, which
    change no number: no cached program)."""
    _, _, full, _, (want, _, _) = run
    walk = lf.walk

    def shared(cfg, params, caches, h, attend):
        held = []

        def through(p, x, cache):
            out, rows = attend(p, x, held[-1] if len(held) % 2 else cache)
            held.append(rows)
            return out, rows

        return walk(cfg, params, caches, h, through)

    monkeypatch.setattr(lf, "walk", shared)
    _, _, _, logits = served(dataclasses.replace(TINY, expert_block=4), params, full[:PROMPT])
    got = rel_l2(logits, np.asarray(want)[kept_rows(full)])
    assert got[0] > CONTROL and np.median(got) > CONTROL


# --- what the node says -------------------------------------------------------


def test_report_counts_the_routing_of_a_request_by_hand(run):
    prefill, decode, full, _, (_, chosen, _) = run
    model = create_model("tiny-longcat-flash")
    report = model.report(PROMPT, NEW, PROMPT + NEW, *model.read_back(prefill, decode))
    chosen = np.asarray(chosen)
    real = np.sum(chosen < 8, axis=-1)                       # [layers, T]
    layers, k = TINY.num_layers, TINY.moe_topk
    assert report["prefill_routed_pairs"] == PROMPT * k * layers
    assert report["decode_routed_pairs"] == NEW * k * layers
    assert report["prefill_zero_pairs"] == int(np.sum(k - real[:, :PROMPT]))
    assert report["decode_zero_pairs"] == int(np.sum(k - real[:, PROMPT:]))
    assert report["prefill_routed_pairs_held"] == int(np.sum(chosen[:, :PROMPT] < 4))
    assert report["decode_routed_pairs_held"] == int(np.sum(chosen[:, PROMPT:] < 4))
    assert report["real_experts_per_token_mean"] == pytest.approx(float(np.mean(real)))
    assert report["real_experts_per_token_min"] == int(np.min(real))
    assert report["real_experts_per_token_max"] == int(np.max(real))
    assert report["decode_experts_read"] == sum(
        len(set(row[row < 4])) for layer in chosen[:, PROMPT:] for row in layer)
    assert report["cache_bytes"] == 4 * (PROMPT + NEW) * 24 * 4 and report["state_bytes"] == 0
    assert (report["layers"], report["attention_sublayers"], report["prefill_parts"]) == (2, 4, 4)
    assert report["decode_expert_route"] == "xla"
    # the ladder a block: seven blocks (two a whole part, one left over) a layer, one rung each
    assert report["prefill_expert_rows"] == layers * (6 * 8 * k + 5 * k)
    assert model.counted(report, PROMPT, NEW) == {
        "decode_steps": NEW, "prefill_layer_passes": PROMPT * 2, "decode_layer_passes": NEW * 2}


def test_the_cells_cache_is_eight_caches_of_576(run):
    model = create_model("longcat-flash-chat-ep64-4l")
    model.dtype = jnp.dtype(jnp.bfloat16)
    says = model.describe(32896)
    assert says["cache_bytes"] == 8 * 32896 * 576 * 2 == 303_169_536
    assert (says["layers"], says["attention_sublayers"], says["state_bytes"]) == (4, 8, 0)
