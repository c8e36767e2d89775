"""Nemotron-H (NVIDIA-Nemotron-3-Nano) against its float32 reference on a
tiny preset with every mechanism (13 blocks `MEMEM*EMEMEME`: all three
kinds and `EM` runs of two lengths; 4 Mamba-2 heads of 8 over a state of
16 in 2 groups, chunks of 32; 4 query heads over 1 key head; 16 experts
of 24 columns without a gate, 3 a token, 1 shared, rank 0 of 8): every
block kind and the whole model, the chunked form of the state-space
layer against the recurrence, its groups and its gated norm, the expert's
form on both routes of the grouped products, the expert kernel at a
width off the lane tile, the eight ranks' shares of a sparse block,
prefill + decode through the state tree (a leaf a state-space block,
which a compiled decode step copies nowhere), and the block walker
against the published string, against the blocks one by one, and
against the walk it replaced, runs of pairs under `lax.scan` over
stacked weights and states."""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.models import mamba2, moe
from comfyui_distributed_tpu.models import nemotron_h as nh
from comfyui_distributed_tpu.models.lm_common import relu2_mlp, rms_norm
from comfyui_distributed_tpu.models.registry import get_config
from comfyui_distributed_tpu.ops import expert_matvec as em
from comfyui_distributed_tpu.parallel.sharding import expert_range
from comfyui_distributed_tpu.reference import nemotron_h as ref

TINY = get_config("tiny-nemotron3-nano")
SERVED = get_config("nemotron3-nano-ep16-52l")
PROMPT, STEPS = 75, 9  # two chunks and 11 tokens of a third

sizes_of = ref.Sizes.of


def rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


@pytest.fixture(scope="module")
def params():
    return nh.init_params(TINY, jax.random.key(1))


# --- the state-space layer -------------------------------------------------------


def scan_inputs(tokens, heads=4, width=8, groups=2, n=16, seed=0, rate=1.0):
    keys = jax.random.split(jax.random.key(seed), 6)
    u = jax.random.normal(keys[0], (tokens, heads, width))
    b = jax.random.normal(keys[1], (tokens, groups, n))
    c = jax.random.normal(keys[2], (tokens, groups, n))
    step = rate * jax.nn.softplus(jax.random.normal(keys[3], (tokens, heads)) - 2.0)
    a = -jax.random.uniform(keys[4], (heads,), jnp.float32, 1.0, 16.0)
    return u, b, c, step, a, jax.random.normal(keys[5], (heads, width, n))


def recurrence(u, b, c, step, a, state):
    def token(state, xs):
        y, state = mamba2.ssm_step(*xs, a, state)
        return state, y

    state, y = jax.lax.scan(token, state, (u, b, c, step))
    return y, state


@pytest.mark.parametrize("tokens", [64, 75, 5, 32])
@pytest.mark.parametrize("rate", [0.01, 1.0, 60.0])
def test_the_chunked_scan_is_the_recurrence(tokens, rate):
    """Lengths that are and are not whole chunks of 32 (and one shorter
    than a chunk), **a state carried in**, a step so small that a chunk
    forgets nothing and one so large that a token forgets everything
    (exp(-60 x 0.1 x 16) is 0 in float32; its reciprocal, which a form
    that divides by the running decay would make, is infinite)."""
    u, b, c, step, a, state = scan_inputs(tokens, rate=rate)
    y, after = mamba2.ssd_chunked(u, b, c, step, a, state, 32)
    y_want, after_want = recurrence(u, b, c, step, a, state)
    assert np.isfinite(np.asarray(y)).all() and np.isfinite(np.asarray(after)).all()
    for got, want in ((y, y_want), (after, after_want)):  # float32 rounding of sums of this size
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5,
            atol=2e-5 * max(1.0, float(np.abs(np.asarray(want)).max())))


def test_a_head_reads_b_and_c_of_its_group_of_consecutive_heads():
    """Head h reads group h // (H / G): with 4 heads in 2 groups, heads 0
    and 1 read group 0. Changing group 1's B and C moves heads 2 and 3
    only, in both forms; a layer that read group h mod G would move
    heads 1 and 3."""
    u, b, c, step, a, state = scan_inputs(40, seed=3)
    other_b, other_c = b.at[:, 1].add(1.0), c.at[:, 1].multiply(-2.0)
    for form in (functools.partial(mamba2.ssd_chunked, chunk=32), recurrence):
        y, after = form(u, b, c, step, a, state)
        y_other, after_other = form(u, other_b, other_c, step, a, state)
        moved = np.abs(np.asarray(y_other - y)).max(axis=(0, 2)) > 1e-3
        assert moved.tolist() == [False, False, True, True]
        np.testing.assert_array_equal(np.asarray(after[:2]), np.asarray(after_other[:2]))


def test_the_gate_comes_before_the_group_norm():
    """rms_group(y silu(z)) w, groups of inner / G channels: written out
    here, and not the norm first."""
    y = jax.random.normal(jax.random.key(0), (5, 32))
    z = jax.random.normal(jax.random.key(1), (5, 32))
    scale = 1.0 + 0.1 * jax.random.normal(jax.random.key(2), (32,))
    got = np.asarray(mamba2.gated_norm(y, z, scale, 2, 1e-5))
    gated = np.asarray(y * jax.nn.silu(z)).reshape(5, 2, 16)
    want = gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(got, want.reshape(5, 32) * np.asarray(scale), rtol=1e-5, atol=1e-6)
    normed = np.asarray(y).reshape(5, 2, 16)
    first = normed / np.sqrt((normed ** 2).mean(-1, keepdims=True) + 1e-5)
    other = first.reshape(5, 32) * np.asarray(jax.nn.silu(z)) * np.asarray(scale)
    assert np.abs(got - other).max() > 0.1


def test_seeded_decays_hold_from_one_to_a_thousand_tokens():
    """The published initialisation, no bias shifted: A in [1, 16], a
    step in [0.001, 0.1], so a token's decay exp(-step A) lies between
    e^-1.6 and e^-0.001; D ones; all float32 in a bfloat16 tree."""
    params = nh.init_params(TINY, jax.random.key(5), jnp.bfloat16)
    seen = 0
    for block in nh.unstacked(TINY, params)["blocks"]:
        if "mamba" not in block:
            continue
        p = block["mamba"]
        assert {p[name].dtype for name in ("a_log", "dt_bias", "d")} == {jnp.dtype(jnp.float32)}
        a, step = np.exp(np.asarray(p["a_log"])), np.asarray(jax.nn.softplus(p["dt_bias"]))
        assert (a >= 1).all() and (a <= 16).all()
        assert (step >= 0.001 * 0.999).all() and (step <= 0.1 * 1.001).all()
        assert (np.exp(-step * a) >= np.exp(-1.6) * 0.999).all()
        np.testing.assert_array_equal(np.asarray(p["d"]), 1.0)
        seen += 1
    assert seen == 6


# --- every block kind, and the model -------------------------------------------


@pytest.mark.parametrize("index", [0, 1, 2, 5, 12])
def test_a_block_of_each_kind_is_the_references(index, params):
    """Published blocks 0 (M alone), 1 and 2 (the first pair of a run, out
    of its stack), 5 (attention) and 12 (the trailing E): the system's
    own part under the block's one norm, from a zero state."""
    block = nh.unstacked(TINY, params)["blocks"][index]
    h = jax.random.normal(jax.random.key(10 + index), (PROMPT, TINY.hidden_size))
    want, ids_want, states = ref.block(sizes_of(TINY), index, block, h, list(TINY.held_experts))
    x = rms_norm(h, block["norm"], TINY.layer_norm_epsilon)
    kind = TINY.hybrid_override_pattern[index]
    if kind == "M":
        out, _, state = nh.mamba(
            TINY, block["mamba"], x, jnp.zeros((3, TINY.conv_channels)),
            jnp.zeros((TINY.mamba_num_heads, TINY.mamba_head_dim, TINY.ssm_state_size)))
        np.testing.assert_allclose(np.asarray(state), np.asarray(states[1]), rtol=2e-5, atol=2e-5)
    elif kind == "*":
        out, kv = nh.attn_whole(TINY, block["attn"], x, jnp.zeros((2, 1, PROMPT + 3, 16)))
        assert not np.asarray(kv[:, :, PROMPT:]).any()
    else:
        out, ids, _ = nh.moe(TINY, block["moe"], x)
        np.testing.assert_array_equal(np.sort(np.asarray(ids)), np.sort(np.asarray(ids_want)))
    np.testing.assert_allclose(np.asarray(h + out), np.asarray(want), rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("rank, size", [(0, 8), (3, 8), (0, 1)])
def test_prefill_and_decode_through_the_state_tree_match_the_reference_in_float32(rank, size):
    """The served path's two programs on seeded weights against the
    reference's full forward pass over the ids they emitted: the logits
    at the last prompt position and at every decoded one, the experts
    chosen in every sparse block, and every Mamba-2 block's state after
    the prefill and after the last token."""
    cfg = dataclasses.replace(TINY, ep_rank=rank, ep_size=size)
    params = nh.init_params(cfg, jax.random.key(2))
    ids = jax.random.randint(jax.random.key(3), (PROMPT,), 0, cfg.vocab_held)
    prefill = nh.prefill(cfg, params, ids, cache_len=PROMPT + STEPS, collect=True)
    left = np.concatenate([np.asarray(s).reshape(-1, *s.shape[-3:]) for s in prefill.cache["ssm"]])
    decode = nh.decode(
        cfg, params, prefill.cache, prefill.logits, jnp.int32(PROMPT), jax.random.key(4),
        jnp.float32(1.0), steps=STEPS, collect=True)
    full = np.concatenate([np.asarray(ids), np.asarray(decode.ids)])
    logits, chosen, states = ref.forward(
        sizes_of(cfg), nh.unstacked(cfg, params), full, list(cfg.held_experts),
        positions=np.arange(PROMPT - 1, PROMPT + STEPS), state_at=PROMPT)
    got = np.concatenate([np.asarray(prefill.logits)[None], np.asarray(decode.logits)])
    assert rel_l2(got, logits).max() < 2e-5
    mine = np.concatenate(
        [np.asarray(prefill.chosen), np.asarray(decode.chosen).transpose(1, 0, 2)], axis=1)
    np.testing.assert_array_equal(np.sort(mine), np.sort(np.asarray(chosen)))
    final = np.concatenate([np.asarray(s).reshape(-1, *s.shape[-3:]) for s in decode.cache["ssm"]])
    np.testing.assert_allclose(left, np.asarray(states[0]), rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(final, np.asarray(states[1]), rtol=3e-5, atol=3e-5)
    assert prefill.loads.shape == (6, len(cfg.held_experts)) == decode.loads.shape
    assert int(decode.loads.sum()) == int((np.asarray(decode.chosen) < len(cfg.held_experts)).sum()
                                         ) if rank == 0 else True


def served(params, collect, steps=STEPS):
    ids = jax.random.randint(jax.random.key(3), (PROMPT,), 0, TINY.vocab_held)
    prefill = nh.prefill(TINY, params, ids, cache_len=PROMPT + STEPS, collect=collect)
    return prefill, nh.decode(
        TINY, params, prefill.cache, prefill.logits, jnp.int32(PROMPT), jax.random.key(4),
        jnp.float32(1.0), steps=steps, collect=collect)


def test_a_served_request_collects_nothing_draws_the_same_ids_and_gets_its_state_back(params):
    prefill, decode = served(params, False)
    # the served prefill is the collecting one (PR 64): the decode is what collects nothing
    assert prefill.chosen is not None and decode.logits is None and decode.chosen is None
    assert all(leaf.is_deleted() for leaf in jax.tree_util.tree_leaves(prefill.cache))  # donated
    assert jax.tree_util.tree_structure(decode.cache) == jax.tree_util.tree_structure(
        nh.state_shapes(TINY, PROMPT + STEPS, jnp.float32))
    np.testing.assert_array_equal(np.asarray(decode.ids), np.asarray(served(params, True)[1].ids))


def scanned_walk(cfg, blocks, h, cache, attn):
    """The walk until PR 49, for the comparison below: a run of `EM`
    pairs under one `lax.scan` over the pairs' weights, tails and states
    stacked along a leading axis, the states handed back as the scan's
    `ys`; every other block on its own."""
    eps = cfg.layer_norm_epsilon
    kv, ssm, conv = (list(cache[name]) for name in ("kv", "ssm", "conv"))
    chosen, loads, kv_at, ssm_at = [], [], 0, 0
    stacked = lambda trees: jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), *trees)

    def sparse(block, h):
        out, ids, sizes = nh.moe(cfg, block["moe"], rms_norm(h, block["norm"], eps))
        return h + out, ids, sizes

    def state_space(block, h, tail, state):
        out, tail, state = nh.mamba(
            cfg, block["mamba"], rms_norm(h, block["norm"], eps), tail, state)
        return h + out, tail, state

    def pair(h, xs):
        e, m, tail, state = xs
        h, ids, sizes = sparse(e, h)
        h, tail, state = state_space(m, h, tail, state)
        return h, (tail, state, ids, sizes)

    for segment in nh.plan(cfg.hybrid_override_pattern):
        mine = blocks[segment.first:segment.first + segment.blocks]
        if segment.pairs:
            held = slice(ssm_at, ssm_at + segment.pairs)
            h, (tails, states, ids, sizes) = jax.lax.scan(pair, h, (
                stacked(mine[0::2]), stacked(mine[1::2]), jnp.stack(conv[held]),
                jnp.stack(ssm[held])))
            conv[held], ssm[held], ssm_at = list(tails), list(states), ssm_at + segment.pairs
            chosen.extend(ids)
            loads.extend(sizes)
        elif segment.kind == "M":
            h, conv[ssm_at], ssm[ssm_at] = state_space(mine[0], h, conv[ssm_at], ssm[ssm_at])
            ssm_at += 1
        elif segment.kind == "*":
            out, kv[kv_at] = attn(
                mine[0]["attn"], rms_norm(h, mine[0]["norm"], eps), kv[kv_at])
            h, kv_at = h + out, kv_at + 1
        else:
            h, ids, sizes = sparse(mine[0], h)
            chosen.append(ids)
            loads.append(sizes)
    cache = {"kv": tuple(kv), "ssm": tuple(ssm), "conv": tuple(conv)}
    return h, cache, jnp.stack(chosen), jnp.stack(loads)


def test_the_one_token_walk_over_a_leaf_a_block_is_the_scanned_walk_bit_for_bit(
        params, monkeypatch):
    """A served decode (block after block, a leaf of state a block)
    against the same decode with `scanned_walk` in `walk`'s place: the
    ids drawn, the loads, and every leaf of the state they end in, the
    same float32 bits. One step count a form: the walk is chosen while
    the program is traced."""
    _, served_form = served(params, False, steps=STEPS - 1)
    monkeypatch.setattr(nh, "walk", scanned_walk)
    _, scanned = served(params, False, steps=STEPS - 2)
    monkeypatch.undo()
    np.testing.assert_array_equal(
        np.asarray(served_form.ids)[:STEPS - 2], np.asarray(scanned.ids))
    _, again = served(params, False, steps=STEPS - 2)
    np.testing.assert_array_equal(np.asarray(again.ids), np.asarray(scanned.ids))
    np.testing.assert_array_equal(np.asarray(again.loads), np.asarray(scanned.loads))
    for got, want in zip(jax.tree_util.tree_leaves(again.cache),
                         jax.tree_util.tree_leaves(scanned.cache)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def loop_bodies(text):
    """{name: lines} of the computations that are a `while`'s body in a
    compiled module's text."""
    bodies, found, name = set(re.findall(r"body=(%?[\w.\-]+)", text)), {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?(%?[\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            name = head.group(1)
        elif name in bodies:
            found.setdefault(name, []).append(line)
    return found


COPY = re.compile(r" = \(?(\w+)\[([\d,]*)\]\S* (?:copy|copy-start)\(")


def state_copies(text, cfg):
    """The shapes of the copies, in the loops' bodies, of a state-space
    state, of a stack of them or of a stack of convolution tails."""
    state = [cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size]
    tail = [cfg.conv_kernel - 1, cfg.conv_channels]
    found = []
    for lines in loop_bodies(text).values():
        for line in lines:
            copied = COPY.search(line)
            if copied:
                shape = [int(d) for d in copied.group(2).split(",") if d]
                if shape[-3:] == state or (len(shape) == 3 and shape[1:] == tail):
                    found.append(shape)
    return found


def stacked_form(stack):
    """The form until PR 49 in small: a loop that carries a run's stacked
    states, its step a `lax.scan` that takes them as `xs` and hands them
    back as `ys`."""
    def step(h, state):
        state = 0.5 * state + h
        return h + state.sum(), state

    def body(_, carry):
        return jax.lax.scan(step, carry[0], carry[1])

    return jax.lax.fori_loop(0, 5, body, (jnp.float32(1.0), stack))


def test_a_compiled_decode_step_copies_no_state_space_state(params):
    """The optimised decode program (CPU, 5 steps; copy insertion runs
    before any backend's own passes): no body of a loop holds a `copy`
    of a `[4, 8, 16]` state, of a stack of them or of a stack of tails.
    What a step hands back as a new array its loop copies into the
    carry: `stacked_form` holds that copy of its stack, and the decode
    held four until PR 49, one a run's states and one a run's tails
    (its runs were scans over stacks: `scanned_walk`). The
    `[3, 96]` tails themselves are still copied, 1 KB each: the shifted
    window reads what it overwrites."""
    prefill = nh.prefill(TINY, params, jnp.zeros((PROMPT,), jnp.int32), cache_len=PROMPT + 8)
    text = nh.decode.lower(
        TINY, params, prefill.cache, prefill.logits, jnp.int32(PROMPT), jax.random.key(4),
        jnp.float32(1.0), steps=5).compile().as_text()
    assert " while(" in text and loop_bodies(text)
    assert state_copies(text, TINY) == []
    control = jax.jit(stacked_form).lower(jnp.zeros((2, 4, 8, 16))).compile().as_text()
    assert state_copies(control, TINY) == [[2, 4, 8, 16]]


def test_prefill_and_decode_hand_on_the_tree_state_shapes_describes(params):
    """A leaf a block: 6 states and 6 tails beside the one cache, the
    prefill's tree and the decode's the one `state_shapes` gives, shape
    and dtype; and what `describe` counts of it from shapes alone at the
    served sizes is what it was when a run's states were one stack."""
    described = nh.state_shapes(TINY, PROMPT + STEPS, jnp.float32)
    assert [len(described[name]) for name in ("kv", "ssm", "conv")] == [1, 6, 6]
    assert {s.shape for s in described["ssm"]} == {(4, 8, 16)}
    assert {s.shape for s in described["conv"]} == {(3, 96)}
    prefill, decode = served(params, True)
    for cache in (prefill.cache, decode.cache):
        assert jax.tree_util.tree_structure(cache) == jax.tree_util.tree_structure(described)
        for leaf, spec in zip(jax.tree_util.tree_leaves(cache),
                              jax.tree_util.tree_leaves(described)):
            assert (leaf.shape, leaf.dtype) == (spec.shape, spec.dtype)
    lm = nh.NemotronH(SERVED)
    lm.dtype = jnp.dtype(jnp.bfloat16)
    said = lm.describe(8704)
    assert said["state_bytes"] == 23 * (64 * 64 * 128 * 4 + 3 * 6144 * 2) == 49_082_368
    assert said["cache_bytes"] == 8704 * 6144


def test_bfloat16_stays_near_the_reference_and_float8_does_not(params):
    low = jax.tree_util.tree_map(
        lambda w: w.astype(jnp.bfloat16) if w.ndim > 1 else w, params)
    ids = jax.random.randint(jax.random.key(3), (PROMPT,), 0, TINY.vocab_held)
    got = nh.prefill(TINY, low, ids, cache_len=PROMPT).logits
    args = (sizes_of(TINY), nh.unstacked(TINY, low), ids, list(TINY.held_experts))
    want = ref.forward(*args, positions=[PROMPT - 1])[0][0]
    rough = ref.forward(*args, positions=[PROMPT - 1], round_to=jnp.float8_e4m3fn)[0][0]
    assert rel_l2(got, want) < 0.05 < rel_l2(rough, want)


# --- the expert's form -----------------------------------------------------------


def written_out(block, x, held, cfg):
    """relu(x W_up)^2 W_down expert by expert in a Python loop over the
    tokens' chosen experts, and the shared expert."""
    p = block["moe"]
    ids, weights = nh.route(cfg, p["bias"], jnp.dot(
        x, p["w_g"], precision=jax.lax.Precision.HIGHEST))
    out = np.zeros(x.shape, np.float64)
    for t in range(x.shape[0]):
        for e, weight in zip(np.asarray(ids[t]), np.asarray(weights[t])):
            if e in held:
                row = e - held.start
                middle = np.maximum(np.asarray(x[t], np.float64) @ np.asarray(
                    p["experts"]["w_up"][row], np.float64).T, 0.0) ** 2
                out[t] += weight * (middle @ np.asarray(p["experts"]["w_down"][row], np.float64))
    shared = np.maximum(np.asarray(x, np.float64) @ np.asarray(p["shared"]["w_up"], np.float64), 0)
    return out + shared ** 2 @ np.asarray(p["shared"]["w_down"], np.float64), ids


def kernel_route(monkeypatch):
    monkeypatch.setattr(moe, "expert_matvec_route", lambda *shape, **how: "kernel")
    monkeypatch.setattr(moe, "expert_matvec", functools.partial(em.expert_matvec, interpret=True))


@pytest.mark.parametrize("route", ["xla", "kernel"])
def test_an_expert_is_relu_squared_between_two_matrices_on_both_routes(route, monkeypatch):
    """A decode step's one token and a few more, through `ragged_dot` and
    through the kernel (interpreted; hidden 128 so that it tiles, the
    width of 24 off every tile): the same sums as a written-out loop, no
    gate, no SiLU."""
    cfg = dataclasses.replace(TINY, hidden_size=128, ep_size=4)
    block = nh.unstacked(cfg, nh.init_params(cfg, jax.random.key(6)))["blocks"][1]
    assert not moe.gated(block["moe"]["experts"]) and not moe.gated(block["moe"]["shared"])
    assert block["moe"]["experts"]["w_up"].shape == (4, 24, 128)  # out by in
    if route == "kernel":
        kernel_route(monkeypatch)
    for tokens in (1, 5):
        x = jax.random.normal(jax.random.key(tokens), (tokens, 128))
        want, ids_want = written_out(block, x, cfg.held_experts, cfg)
        layer = lambda x: nh.moe(cfg, block["moe"], x)  # a function no trace of which is kept
        out, ids, sizes = layer(x)
        np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids_want))
        np.testing.assert_allclose(np.asarray(out), want, rtol=2e-5, atol=2e-5)
        assert int(sizes.sum()) == int((np.asarray(ids) < 4).sum())
        calls = str(jax.make_jaxpr(layer)(x)).count("pallas_call")
        assert calls == (2 if route == "kernel" else 0)


def test_a_stacks_experts_are_read_out_of_it_by_the_layers_index(monkeypatch):
    """`moe.expert_layer` with `index`: the routed experts' stacks of
    several layers stay whole and the grouped products take the layer's
    index (the kernel's operand cannot be a slice), which is the same
    block as the one alone. No program of this model stacks its blocks
    since PR 49; the capability is `models/moe.py`'s."""
    cfg = dataclasses.replace(TINY, hidden_size=128)
    blocks = nh.init_params(cfg, jax.random.key(7))["blocks"]
    pair = [blocks[1]["moe"], blocks[3]["moe"]]   # the first run's two sparse blocks
    stack = jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), *pair)["experts"]
    x = jax.random.normal(jax.random.key(8), (1, 128))
    for how in ("xla", "kernel"):
        if how == "kernel":
            kernel_route(monkeypatch)
        for index, alone in enumerate(pair):
            want = nh.moe(cfg, alone, x)[0]
            mixed = {**alone, "experts": stack}
            got = (lambda x: moe.expert_layer(
                mixed, x, cfg.held_experts, functools.partial(nh.route, cfg, mixed["bias"]),
                index=jnp.int32(index)))(x)[0]
            np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)


def test_the_eight_ranks_shares_of_a_sparse_block_add_up_to_the_uncut_block():
    """The share test: each rank's expert layer gives the shared expert's
    output plus its own experts' part; summed over the eight ranks with
    the shared expert and the residual counted once, that is the uncut
    reference's block."""
    whole = dataclasses.replace(TINY, ep_size=1, ep_rank=0)
    block = nh.unstacked(whole, nh.init_params(whole, jax.random.key(3)))["blocks"][12]
    h = jax.random.normal(jax.random.key(4), (PROMPT, whole.hidden_size))
    want, _, _ = ref.block(sizes_of(whole), 12, block, h, list(range(whole.n_routed_experts)))

    x = rms_norm(h, block["norm"], whole.layer_norm_epsilon)
    shared = relu2_mlp(x, block["moe"]["shared"])
    routed, pairs = 0.0, 0
    for rank in range(8):
        cfg = dataclasses.replace(TINY, ep_size=8, ep_rank=rank)
        mine = expert_range(whole.n_routed_experts, rank, 8)
        part = dict(block["moe"], experts=jax.tree_util.tree_map(
            lambda w: w[mine.start:mine.stop], block["moe"]["experts"]))
        out, _, sizes = nh.moe(cfg, part, x)
        routed = routed + (out - shared)
        pairs += int(sizes.sum())
    np.testing.assert_allclose(
        np.asarray(h + shared + routed), np.asarray(want), rtol=2e-5, atol=2e-5)
    assert pairs == PROMPT * whole.num_experts_per_tok  # every pair fell on exactly one rank


def test_the_router_is_the_one_sigmoid_rule_without_a_group_step():
    """Scores are sigmoids, the bias chooses and does not weigh, a tie goes
    to the lower index, the chosen scores are renormalised and scaled by
    2.5; `n_group` 1: every expert stands."""
    cfg = dataclasses.replace(TINY, n_routed_experts=8, num_experts_per_tok=3, ep_size=1)
    logits = jnp.asarray([[2.0, 2.0, 0.0, 1.0, 1.0, -1.0, -1.0, 1.0]])
    bias = jnp.zeros((8,)).at[5].set(1.0)
    ids, weights = nh.route(cfg, bias, logits)
    assert np.asarray(ids).tolist() == [[5, 0, 1]]  # sigmoid(-1) + 1 = 1.27 leads; 0 before 1
    scores = np.asarray(jax.nn.sigmoid(logits))[0, [5, 0, 1]]
    np.testing.assert_allclose(np.asarray(weights)[0], 2.5 * scores / scores.sum(), rtol=1e-6)
    assert nh.sigmoid_route is moe.sigmoid_route and nh.expert_layer is moe.expert_layer


# --- the expert kernel at a width off the lane tile --------------------------------


@pytest.mark.parametrize("stacked", [False, True])
@pytest.mark.parametrize("sizes", [[2, 0, 3, 0, 1], [0, 0, 0, 0, 0], [0, 6, 0, 0, 0], [0, 0, 2, 0, 1]])
@pytest.mark.parametrize("k, n, dtype, blocks", [
    (256, 720, jnp.float32, 1), (128, 24, jnp.float32, 1), (256, 200, jnp.bfloat16, None),
    (640, 1856, jnp.float32, 4)])
def test_the_kernel_walks_an_out_by_in_stack_of_any_width(k, n, dtype, blocks, sizes, stacked):
    """`expert_matvec` (interpreted) over weights stored [groups, N, K]
    with N off the lane tile against `ragged_dot` over their transposes:
    several blocks of rows of N an expert (1,856 = 4 x 464), rows past
    the held pairs zero, no held pair at all, and a layer read out of a
    stack by its index."""
    if blocks is None:  # 200 is no multiple of bfloat16's 16 sublanes
        assert em.matvec_plan(6, k, n, 2, out_major=True) is None
        return
    padded, block = em.matvec_plan(6, k, n, jnp.dtype(dtype).itemsize, out_major=True)
    assert n // block == blocks and padded == 8
    rows = jax.random.normal(jax.random.key(0), (6, k), dtype)
    w = k ** -0.5 * jax.random.normal(jax.random.key(1), (3, 5, n, k), dtype)
    layer = jnp.int32(2) if stacked else None
    weights = w if stacked else w[2]
    got = em.expert_matvec(rows, weights, jnp.asarray(sizes), layer, out_major=True, interpret=True)
    want = jax.lax.ragged_dot(rows, w[2].swapaxes(1, 2), jnp.asarray(sizes))
    held = sum(sizes)
    np.testing.assert_allclose(np.asarray(got[:held]), np.asarray(want[:held]), rtol=1e-4, atol=1e-4)
    assert not np.asarray(got[held:]).any()
    same = em.grouped_xla(rows, weights, jnp.asarray(sizes), layer, out_major=True)
    np.testing.assert_allclose(np.asarray(same[:held]), np.asarray(want[:held]), rtol=1e-4, atol=1e-4)


# (rows, hidden, width) of a decode step of the four models with SwiGLU experts
OTHERS = [(6, 5120, 1536), (8, 4096, 1280), (16, 6144, 2048), (8, 6144, 2048), (16, 2560, 768),
          (8, 2560, 768)]


def test_the_plan_is_what_it_was_for_the_other_four_models_and_takes_this_ones(monkeypatch):
    """`matvec_plan` answers the other models' shapes as it did (the
    blocks PR 42 and PR 45 measured), refuses 1,856 columns on the walk
    by columns as it did, and takes them out by in: [464, 2688] blocks of
    2.5 MB, four trips an expert; the down-projection [1856, 2688] walks
    by columns in seven blocks of 384."""
    was = {(5120, 3072): 128, (1536, 5120): 512, (4096, 2560): 128, (1280, 4096): 512,
           (6144, 4096): 128, (2048, 6144): 256, (2560, 1536): 256, (768, 2560): 1280}
    for rows, hidden, width in OTHERS:
        for k, n in ((hidden, 2 * width), (width, hidden)):
            assert em.matvec_plan(rows, k, n, 2) == (16, was[k, n]), (k, n)
    assert em.matvec_plan(6, 2688, 1856, 2) is None
    assert em.matvec_plan(6, 2688, 1856, 2, out_major=True) == (16, 464)
    assert em.matvec_plan(6, 1856, 2688, 2) == (16, 384)
    assert moe.decode_route(6, 2688, 1856, jnp.bfloat16, with_gate=False) == "xla"  # off a TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert moe.decode_route(6, 2688, 1856, jnp.bfloat16, with_gate=False) == "kernel"
    assert moe.decode_route(6, 2688, 1856, jnp.bfloat16) == "xla"  # a SwiGLU of that width
    for rows, hidden, width in OTHERS:
        assert moe.decode_route(rows, hidden, width, jnp.bfloat16) == "kernel"


# --- the walk ----------------------------------------------------------------------


def test_the_published_string_is_23_mamba_23_sparse_and_6_attention_blocks():
    cfg = nh.NemotronHConfig()
    assert cfg.hybrid_override_pattern == (
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")
    assert cfg.num_hidden_layers == 52
    assert (len(cfg.blocks_of("M")), len(cfg.blocks_of("E")), len(cfg.blocks_of("*"))) == (
        23, 23, 6)
    assert cfg.blocks_of("*") == [5, 12, 19, 26, 33, 42]
    segments = nh.plan(cfg.hybrid_override_pattern)
    assert [s.pairs for s in segments if s.pairs] == [2, 3, 3, 3, 3, 4, 4]
    assert [s.kind for s in segments] == ["M"] + ["EM", "*"] * 6 + ["EM", "E"]
    assert sum(s.blocks for s in segments) == 52 and len(segments) == 15
    at = 0
    for s in segments:  # the segments tile the string in order
        assert s.first == at and cfg.hybrid_override_pattern[at:at + s.blocks] == (
            "EM" * s.pairs or s.kind)
        at += s.blocks
    # a state entry only for what keeps one: 6 caches, 23 states and 23 tails, a leaf a block
    shapes = nh.state_shapes(SERVED, 8704, jnp.bfloat16)
    assert len(shapes["kv"]) == 6 and len(shapes["ssm"]) == len(shapes["conv"]) == 23
    assert {s.shape for s in shapes["ssm"]} == {(64, 64, 128)}
    assert {s.shape for s in shapes["conv"]} == {(3, 6144)}
    with pytest.raises(ValueError, match="M, E or"):
        nh.NemotronHConfig(hybrid_override_pattern="ME-")


def test_a_pattern_without_runs_or_with_a_single_pair_is_walked_block_by_block():
    assert [tuple(s) for s in nh.plan("EM*M")] == [("E", 0, 0), ("M", 1, 0), ("*", 2, 0),
                                                   ("M", 3, 0)]
    assert [tuple(s) for s in nh.plan("EMEMEM")] == [("EM", 0, 3)]
    assert [tuple(s) for s in nh.plan("MEMEE")] == [  # one pair is no run
        ("M", 0, 0), ("E", 1, 0), ("M", 2, 0), ("E", 3, 0), ("E", 4, 0)]


@pytest.mark.parametrize("tokens", [PROMPT, 1])
def test_either_walk_is_the_published_blocks_one_after_another(params, tokens):
    """`walk` over a sequence (the prefill's: runs of 2 and 3 pairs under
    `lax.scan` over weights stacked there, the experts read out of the
    stack by index) and over one token (the decode's: block after block,
    the Mamba and the sparse block jitted) against the 13 blocks' parts
    called one after another here: the residual stream, the keys and
    values, every state and tail, the chosen experts and the loads."""
    ids = jax.random.randint(jax.random.key(9), (tokens,), 0, TINY.vocab_held)
    h0 = params["embed"][ids]
    cache = nh.zeros(nh.state_shapes(TINY, PROMPT, jnp.float32))
    h, after, chosen, loads = nh.walk(
        TINY, params["blocks"], h0, cache, functools.partial(nh.attn_whole, TINY))

    h_want, states, tails, kvs, ids_want, loads_want = h0, [], [], [], [], []
    for index, block in enumerate(nh.unstacked(TINY, params)["blocks"]):
        x = rms_norm(h_want, block["norm"], TINY.layer_norm_epsilon)
        kind = TINY.hybrid_override_pattern[index]
        if kind == "M":
            out, tail, state = nh.mamba(
                TINY, block["mamba"], x, jnp.zeros((3, TINY.conv_channels)), jnp.zeros((4, 8, 16)))
            states.append(state)
            tails.append(tail)
        elif kind == "*":
            out, kv = nh.attn_whole(TINY, block["attn"], x, jnp.zeros((2, 1, PROMPT, 16)))
            kvs.append(kv)
        else:
            out, ids_b, sizes = nh.moe(TINY, block["moe"], x)
            ids_want.append(ids_b)
            loads_want.append(sizes)
        h_want = h_want + out
    # float32 rounding over 13 blocks: here each operation is a program, there a block is one
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_want), rtol=3e-5, atol=3e-5)
    flat = lambda entries, trailing: np.concatenate(
        [np.asarray(e).reshape(-1, *e.shape[-trailing:]) for e in entries])
    np.testing.assert_allclose(flat(after["ssm"], 3), np.stack(states), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(flat(after["conv"], 2), np.stack(tails), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(after["kv"][0]), np.asarray(kvs[0]), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(chosen), np.stack(ids_want))
    np.testing.assert_array_equal(np.asarray(loads), np.stack(loads_want))


def test_one_token_through_the_cached_forms_is_the_whole_sequences_last_row(params):
    """The decode's forms of the three parts (the recurrence, a single
    query over the cache, the expert layer at one row) after a prefill of
    T - 1 tokens give the prefill of T tokens' last logits."""
    ids = jax.random.randint(jax.random.key(11), (PROMPT,), 0, TINY.vocab_held)
    whole = nh.prefill(TINY, params, ids, cache_len=PROMPT)
    before = nh.prefill(TINY, params, ids[:-1], cache_len=PROMPT)
    logits, cache, _, _ = nh.decode_step(TINY, params, before.cache, ids[-1], jnp.int32(PROMPT - 1))
    assert rel_l2(logits, whole.logits) < 2e-5
    for got, want in zip(jax.tree_util.tree_leaves(cache), jax.tree_util.tree_leaves(whole.cache)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-5, atol=3e-5)


def test_the_cut_holds_the_parameters_the_issue_counted():
    """All 52 blocks, 8 of 128 experts, an eighth of the vocabulary:
    3,422,495,040 parameters (the eight-way cut the issue counted first,
    16 experts: 5,258,420,544); the whole model 31.58 B, as
    `described_as` says (31.6B)."""
    assert nh.param_count(SERVED) == 3_422_495_040
    assert nh.param_count(dataclasses.replace(SERVED, ep_size=8)) == 5_258_420_544
    assert nh.param_count(nh.NemotronHConfig()) == 31_577_940_288
    shapes = nh.param_shapes(SERVED)
    count = lambda tree: nh.count_params(tree)
    assert len(shapes["blocks"]) == 52 and [sorted(set(b) - {"norm"})[0] for b in shapes[
        "blocks"][:6]] == ["mamba", "moe", "mamba", "moe", "mamba", "attn"]
    assert count(shapes["blocks"][0]) == 38_742_208 + 2688    # a Mamba block and its norm
    # 8 experts, the shared one, the router and its bias, the norm
    assert count(shapes["blocks"][1]) == 8 * 9_977_856 + 19_955_712 + 344_064 + 128 + 2688
    assert count(shapes["blocks"][5]) == 23_396_352 + 2688    # an attention block
    assert (len(SERVED.held_experts), SERVED.vocab_held) == (8, 16384)
