"""Ouro (a looped language model) against its float32 reference on the
tiny preset (4 passes kept, 3 layers, narrow): the prefill and every decode
step through the (pass, layer) cache against the reference's full forward;
the loop as weight sharing and nothing else; the cache as one slot a pass
and layer; the two programs' structure (the layer body once, the cache
carried and never copied); what is refused."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.models import ouro
from comfyui_distributed_tpu.models.registry import get_config
from comfyui_distributed_tpu.reference import ouro as ref

TINY = get_config("tiny-ouro")
PROMPT, STEPS = 40, 6
POSITIONS = np.arange(PROMPT - 1, PROMPT + STEPS)


def generate(cfg, params, seed=1, temperature=1.0, collect=True):
    """(every id, the system's logits / h_t / p(t) at POSITIONS, the
    prefill's own cache before the decode took it)."""
    ids = jax.random.randint(jax.random.key(5), (PROMPT,), 0, cfg.vocab_size)
    prefill = ouro.prefill(cfg, params, ids, cache_len=PROMPT + STEPS, collect=collect)
    cache = np.asarray(prefill.cache)
    decode = ouro.decode(
        cfg, params, prefill.cache, prefill.logits, jnp.int32(PROMPT), jax.random.key(seed),
        jnp.float32(temperature), steps=STEPS, collect=collect,
    )
    full = jnp.concatenate([ids, decode.ids])
    mine = (
        jnp.concatenate([prefill.logits[None], decode.logits]),
        jnp.concatenate([prefill.hidden[None], decode.hidden]).transpose(1, 0, 2),
        jnp.concatenate([prefill.exits[None], decode.exits]).T,
    ) if collect else None
    return full, mine, cache, (prefill, decode)


def reference(cfg, params, full, **kwargs):
    return ref.forward(
        ref.Sizes.of(cfg), ouro.unstacked(params), full, positions=POSITIONS, **kwargs)


def rel_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


# --- (a) the served programs against the reference -------------------------


@pytest.mark.parametrize("seed, temperature", [(1, 1.0), (2, 1.0), (3, 0.0), (4, 0.7)])
def test_prefill_and_decode_through_the_cache_match_the_reference_in_float32(seed, temperature):
    """Float32 weights and activations: only the order of the sums
    differs (blocked softmax, the cached form of the attention), so the
    logits, every pass's h_t and the exit distribution agree to float32
    rounding, ~1e-6. 2e-5 leaves room for a longer sum and would not pass
    bfloat16 anywhere (2e-2, below)."""
    params = ouro.init_params(TINY, jax.random.key(0))
    full, (logits, hidden, exits), _, _ = generate(TINY, params, seed, temperature)
    want_logits, want_hidden, want_exits = reference(TINY, params, full)
    assert rel_l2(logits, want_logits).max() < 2e-5
    assert rel_l2(hidden, want_hidden).max() < 2e-5          # h_1 .. h_4, each position
    assert np.abs(np.asarray(exits) - np.asarray(want_exits)).max() < 2e-6
    # p(t) is a distribution over the passes at every position
    assert np.allclose(np.asarray(exits).sum(axis=0), 1.0, atol=1e-6)


def test_the_summed_exit_distribution_counts_every_token():
    params = ouro.init_params(TINY, jax.random.key(0))
    full, (_, _, exits), _, (prefill, decode) = generate(TINY, params)
    assert float(prefill.exit.sum()) == pytest.approx(PROMPT, rel=1e-5)
    assert float(decode.exit.sum()) == pytest.approx(STEPS, rel=1e-5)
    # the decode's sum is the sum of what `collect` kept of its steps
    assert np.allclose(decode.exit, np.asarray(exits)[:, 1:].sum(axis=1), atol=1e-5)
    want = ref.forward(ref.Sizes.of(TINY), ouro.unstacked(params), full[:PROMPT])[2]
    assert np.allclose(prefill.exit, np.asarray(want).sum(axis=1), atol=1e-4)


def test_bfloat16_stays_near_the_reference_and_float8_does_not():
    """bfloat16 weights and activations against the float32 reference on
    the same (bfloat16-valued) weights: twelve layer passes of a dozen
    rounded products each bring the logits' relative L2 to ~2e-2 at this
    width; float8 e4m3 operands read 0.3-0.6. 0.08 lies between."""
    params = ouro.init_params(TINY, jax.random.key(0), jnp.bfloat16)
    full, (logits, _, _), _, _ = generate(TINY, params)
    want = reference(TINY, params, full)[0]
    low = reference(TINY, params, full, round_to=jnp.float8_e4m3fn)[0]
    assert np.median(rel_l2(logits, want)) < 0.08 < np.median(rel_l2(low, want))


def test_the_collect_programs_sample_the_served_programs_ids():
    params = ouro.init_params(TINY, jax.random.key(0))
    served = generate(TINY, params, collect=False)[0]
    assert np.array_equal(served, generate(TINY, params, collect=True)[0])
    assert not np.array_equal(served, generate(TINY, params, seed=2, collect=False)[0])


# --- (b) the loop is weight sharing ------------------------------------------


def plain_transformer(sizes, params, blocks_by_pass, ids):
    """A sandwich-norm transformer over the listed layers, the final norm
    after each group: logits at the last position."""
    x = jnp.asarray(params["embed"], jnp.float32)[ids]
    for blocks in blocks_by_pass:
        for block in blocks:
            x, _ = ref.layer(sizes, block, x)
        x = ref._rms_norm(x, params["final_norm"], sizes.rms_norm_eps)
    with jax.default_matmul_precision("highest"):
        return x[-1] @ jnp.asarray(params["head"], jnp.float32)


@pytest.mark.parametrize("passes", [1, 2])
def test_t_passes_over_l_layers_are_one_pass_over_the_list_written_t_times(passes):
    """T = 1 is a plain sandwich-norm transformer; T = 2 is the reference
    run once over the 2L-layer list with the weights written twice and the
    final norm between: nothing belongs to a pass but its cache slots."""
    cfg = dataclasses.replace(TINY, total_ut_steps=passes)
    params = ouro.init_params(cfg, jax.random.key(2))
    ids = jax.random.randint(jax.random.key(6), (PROMPT,), 0, cfg.vocab_size)
    mine = ouro.prefill(cfg, params, ids, cache_len=PROMPT).logits
    blocks = list(ouro.unstacked(params)["layers"])
    assert len(blocks) == cfg.num_hidden_layers
    want = plain_transformer(ref.Sizes.of(cfg), params, [blocks] * passes, ids)
    assert rel_l2(mine, want) < 2e-5
    # and the reference's own loop says the same
    looped = ref.forward(ref.Sizes.of(cfg), ouro.unstacked(params), ids)[0][-1]
    assert rel_l2(looped, want) < 1e-6


# --- (c) the cache is per pass -------------------------------------------------


def test_the_passes_slots_of_one_layer_differ():
    params = ouro.init_params(TINY, jax.random.key(0))
    _, _, cache, (_, decode) = generate(TINY, params)
    assert cache.shape == TINY.cache_shape(PROMPT + STEPS) == (4, 3, 2, 4, PROMPT + STEPS, 16)
    for layer in range(TINY.num_hidden_layers):
        for a in range(TINY.total_ut_steps):
            for b in range(a + 1, TINY.total_ut_steps):
                gap = rel_l2(cache[a, layer, :, :, :PROMPT].reshape(-1),
                             cache[b, layer, :, :, :PROMPT].reshape(-1))
                assert gap > 0.5, (layer, a, b)
    # the prefill wrote the prompt's positions and nothing after them; the
    # decode filled the rest of every slot and left the prompt's alone
    assert not cache[..., PROMPT:, :].any()
    after = np.asarray(decode.cache)
    assert np.array_equal(after[..., :PROMPT, :], cache[..., :PROMPT, :])
    assert np.abs(after[..., PROMPT:, :]).reshape(4, 3, -1).max(axis=-1).min() > 0


def test_a_cache_shared_between_passes_is_a_different_result():
    """Every pass reading pass 1's keys and values (one slot a layer) moves
    the logits by more than their own size: far outside any tolerance a
    precision could explain."""
    params = ouro.init_params(TINY, jax.random.key(0))
    full, (logits, hidden, _), _, _ = generate(TINY, params)
    shared_logits, shared_hidden, _ = reference(TINY, params, full, shared_cache=True)
    assert rel_l2(logits, shared_logits).min() > 0.5
    # pass 1 reads its own slots either way; the fault enters at pass 2
    assert rel_l2(hidden[0], shared_hidden[0]).max() < 2e-5
    assert rel_l2(hidden[1], shared_hidden[1]).min() > 0.3


# --- (d) structure -------------------------------------------------------------


def sub_jaxprs(eqn):
    for value in eqn.params.values():
        for item in value if isinstance(value, (tuple, list)) else (value,):
            inner = getattr(item, "jaxpr", item)
            if hasattr(inner, "eqns"):
                yield inner


def walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in sub_jaxprs(eqn):
            yield from walk(inner)


def programs(cfg):
    """The jaxprs of the prefill and of the decode, on shapes alone."""
    params = jax.eval_shape(lambda: ouro.init_params(cfg, jax.random.key(0)))
    ids = jax.ShapeDtypeStruct((PROMPT,), jnp.int32)
    cache_len = PROMPT + STEPS
    prefill = jax.make_jaxpr(
        lambda p, i: ouro.prefill(cfg, p, i, cache_len=cache_len))(params, ids)
    cache = jax.ShapeDtypeStruct(cfg.cache_shape(cache_len), jnp.float32)
    decode = jax.make_jaxpr(
        lambda p, c, l, k: ouro.decode(
            cfg, p, c, l, jnp.int32(PROMPT), k, jnp.float32(1.0), steps=STEPS)
    )(params, cache, jax.ShapeDtypeStruct((cfg.vocab_size,), jnp.float32), jax.random.key(0))
    return prefill.jaxpr, decode.jaxpr


def count(jaxpr, primitive):
    return sum(eqn.primitive.name == primitive for eqn in walk(jaxpr))


@pytest.mark.parametrize("dtype, tolerance", [(jnp.float32, 1e-6), (jnp.bfloat16, 1e-2)])
def test_one_tokens_layer_is_a_row_of_the_whole_sequences(dtype, tolerance):
    """A layer's second half takes one token's SwiGLU on the vector and a
    sequence's on the matrix: the same sums, so the one token alone is
    its row of the sequence."""
    params = ouro.init_params(TINY, jax.random.key(2), dtype)
    p = ouro.unstacked(params)["layers"][1]
    x = jax.random.normal(jax.random.key(3), (5, TINY.hidden_size))
    out = jax.random.normal(jax.random.key(4), (5, TINY.hidden_size)).astype(dtype)
    whole = ouro._after_attention(TINY, p, x, out)
    one = ouro._after_attention(TINY, p, x[2:3], out[2:3])
    assert one.shape == (1, TINY.hidden_size) and one.dtype == whole.dtype == jnp.float32
    assert rel_l2(one, whole[2:3]).max() <= tolerance


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_the_layer_index_is_a_counter_in_the_carry(program):
    """Neither program scans an array of layer indices beside the stacked
    weights (sliced a layer, that is a device operation in a body one
    token runs 192 times): the inner scan scans the stack's eight leaves
    and carries a counter."""
    jaxpr = dict(zip(("prefill", "decode"), programs(TINY)))[program]
    (scan,) = [e for e in walk(jaxpr) if e.primitive.name == "scan"
               and e.params["length"] == TINY.num_hidden_layers]
    consts, carry = scan.params["num_consts"], scan.params["num_carry"]
    carried, scanned = scan.invars[consts:consts + carry], scan.invars[consts + carry:]
    assert len(scanned) == 8
    assert [v.aval.shape for v in carried if v.aval.dtype == jnp.int32] == [()]


@pytest.mark.parametrize("layers, passes", [(6, 4), (2, 1), (6, 1)])
def test_both_programs_hold_the_layer_body_once(layers, passes):
    """As many products at 6 layers as at 2 and at 4 passes as at 1:
    nothing is unrolled, the 192 layer passes of the published sizes are
    one body in each program."""
    small = programs(dataclasses.replace(TINY, num_hidden_layers=2, total_ut_steps=4))
    other = programs(dataclasses.replace(TINY, num_hidden_layers=layers, total_ut_steps=passes))
    # the loop over passes and the loop over layers; the decode's steps
    # are a third (a `fori_loop` of a known length is a scan too)
    for mine, theirs, loops in zip(small, other, (2, 3)):
        assert count(mine, "dot_general") == count(theirs, "dot_general") > 0
        assert count(mine, "scan") + count(mine, "while") == loops
        assert count(theirs, "scan") + count(theirs, "while") == loops


def test_the_decode_carries_the_cache_and_makes_no_other_value_of_its_size():
    cfg = TINY
    _, decode = programs(cfg)
    shape = cfg.cache_shape(PROMPT + STEPS)
    slot = shape[2:]                                       # keys and values of one (pass, layer)

    def holds_slots(var) -> bool:
        """More than one slot's worth of cache-shaped values."""
        dims = getattr(var.aval, "shape", ())
        return dims[-len(slot):] == slot and int(np.prod(dims)) > int(np.prod(slot))

    makers = set()
    for eqn in walk(decode):
        for var in eqn.outvars:
            if getattr(var.aval, "shape", None) == shape:
                makers.add(eqn.primitive.name)
        if eqn.primitive.name == "scan":
            carried = eqn.params["num_consts"] + eqn.params["num_carry"]
            scanned = list(eqn.invars[carried:]) + list(eqn.outvars[eqn.params["num_carry"]:])
            # no stack of slots goes in as xs or comes out as ys
            assert not any(holds_slots(v) for v in scanned)
    # the cache's own size is only ever the carry: a loop's result, or the
    # in-place write of one token's keys and values
    assert makers <= {"scan", "while", "jit", "pjit", "dynamic_update_slice",
                      "layout_constraint"}, makers
    assert "dynamic_update_slice" in makers and makers & {"scan", "while"}
    assert count(decode, "dynamic_update_slice") >= 1
    # one slot is read a layer pass, never a pass's or the whole cache's worth
    for eqn in walk(decode):
        if eqn.primitive.name in ("dynamic_slice", "gather", "slice"):
            assert not any(holds_slots(v) for v in eqn.outvars)


def test_the_decode_donates_the_cache_and_hands_it_back():
    params = ouro.init_params(TINY, jax.random.key(0))
    ids = jnp.arange(PROMPT, dtype=jnp.int32)
    prefill = ouro.prefill(TINY, params, ids, cache_len=PROMPT + STEPS)
    decode = ouro.decode(
        TINY, params, prefill.cache, prefill.logits, jnp.int32(PROMPT), jax.random.key(1),
        jnp.float32(1.0), steps=STEPS)
    assert prefill.cache.is_deleted()
    assert decode.cache.shape == TINY.cache_shape(PROMPT + STEPS)


# --- (e) what is refused, and what builds nothing -----------------------------


def test_a_threshold_below_one_is_refused_and_says_why():
    with pytest.raises(ValueError, match="cache slots"):
        dataclasses.replace(TINY, early_exit_threshold=0.9)
    with pytest.raises(ValueError, match="grouped queries"):
        dataclasses.replace(TINY, num_key_value_heads=2)


def test_another_temperature_builds_no_program():
    params = ouro.init_params(TINY, jax.random.key(0))
    generate(TINY, params, temperature=1.0, collect=False)
    built = ouro.decode._cache_size(), ouro.prefill._cache_size()
    generate(TINY, params, temperature=0.3, collect=False)
    generate(TINY, params, seed=9, temperature=0.0, collect=False)
    assert (ouro.decode._cache_size(), ouro.prefill._cache_size()) == built


def test_the_published_sizes_count_2_667_974_657_parameters():
    cfg = get_config("ouro-2.6b")
    assert ouro.param_count(cfg) == 2_667_974_657
    assert cfg.layer_passes == 192
    # 1,572,864 bytes a token in bfloat16
    assert int(np.prod(cfg.cache_shape(1))) * 2 == 1_572_864
    shapes = jax.eval_shape(lambda: ouro.init_params(cfg, jax.random.key(0), jnp.bfloat16))
    assert shapes["layers"]["w_qkv"].shape == (48, 2048, 6144)
    assert shapes["layers"]["w_gate_up"].shape == (48, 2048, 11264)
    assert shapes["gate"]["b"].shape == ()


def test_the_gates_bias_is_drawn_so_that_leaving_it_out_shows():
    params = ouro.init_params(TINY, jax.random.key(0))
    assert float(params["gate"]["b"]) != 0.0
    assert np.all(np.asarray(params["final_norm"]) == 1.0)
    # the layers' weights differ from layer to layer
    stack = np.asarray(params["layers"]["w_o"])
    assert not np.allclose(stack[0], stack[1])


def test_exit_distribution_is_the_published_rule():
    lam = jnp.asarray([[0.5], [0.5], [0.25], [0.9]])
    want = [0.5, 0.25, 0.0625, 0.1875]                       # the last pass takes what is left
    assert np.allclose(ouro.exit_distribution(lam)[:, 0], want)
    assert np.allclose(ref.exit_distribution(list(lam))[:, 0], want)
