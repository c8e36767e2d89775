"""The expert layer's ladder of static row counts (`models/moe.py`): the
grouped products, the row gather and the way back run over a prefix of
the sorted token-expert pairs that covers every pair on a held expert,
picked on the device. Every rung has to give what the computation over
all `T x k` pairs gives (`whole`, the layer as it was before the ladder,
kept here as the reference), the top rung and a ladder of one rung are
that computation itself, and the rung the host reports is the rung the
device took."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.models import moe
from comfyui_distributed_tpu.models.lm_common import swiglu

HIDDEN, WIDTH, EXPERTS, K = 32, 16, 16, 4
TOKENS = 512  # 2,048 pairs: four rungs where 2 of 16 experts are held
HELD = range(4, 6)
LADDER = (256, 512, 1024, 2048)


def params(held=HELD, experts=EXPERTS, seed=0):
    keys = jax.random.split(jax.random.key(seed), 5)
    normal = jax.random.normal
    return {
        "w_g": normal(keys[0], (HIDDEN, experts)) * 0.3,
        "experts": {
            "w_gate_up": normal(keys[1], (len(held), HIDDEN, 2 * WIDTH)) * 0.2,
            "w_down": normal(keys[2], (len(held), WIDTH, HIDDEN)) * 0.2,
        },
        "shared": {
            "w_gate_up": normal(keys[3], (HIDDEN, 2 * WIDTH)) * 0.2,
            "w_down": normal(keys[4], (WIDTH, HIDDEN)) * 0.2,
        },
    }


def tokens(n=TOKENS, seed=1):
    return jax.random.normal(jax.random.key(seed), (n, HIDDEN))


def biased(bias, k=K):
    """A routing rule: the `k` largest of softmax scores + `bias`, the
    chosen scores renormalised."""
    def route(logits):
        scores = jax.nn.softmax(logits, axis=-1)
        _, ids = jax.lax.top_k(scores + bias, k)
        chosen = jnp.take_along_axis(scores, ids, axis=-1)
        return ids, chosen / chosen.sum(axis=-1, keepdims=True)
    return route


def prescribed(held_pairs, n=TOKENS, k=K, held=HELD, experts=EXPERTS):
    """A routing rule that puts exactly the first `held_pairs` of the
    `n x k` pairs on held experts, whatever the logits."""
    flat = np.arange(n * k)
    absent = [e for e in range(experts) if e not in held]
    ids = np.where(
        flat < held_pairs, held.start + flat % len(held), np.take(absent, flat % len(absent)))
    weights = 0.1 + (flat % 7) / 10.0
    return lambda logits: (
        jnp.asarray(ids.reshape(n, k), jnp.int32), jnp.asarray(weights.reshape(n, k), jnp.float32))


def whole(p, x, held, route):
    """The layer over every one of the `T x k` pairs: `expert_layer` as
    it was before the ladder."""
    logits = jnp.dot(
        x.astype(jnp.float32), p["w_g"].astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
    ids, weights = route(logits)
    count, k = ids.shape
    local = ids.reshape(-1) - held.start
    here = (local >= 0) & (local < len(held))
    slot = jnp.where(here, local, len(held))
    order = jnp.argsort(slot, stable=True)
    sizes = jnp.zeros((len(held),), jnp.int32).at[slot].add(1, mode="drop")
    rows = x[order // k]
    gate, up = jnp.split(
        jax.lax.ragged_dot(rows, p["experts"]["w_gate_up"], sizes), 2, axis=-1)
    out = jax.lax.ragged_dot(jax.nn.silu(gate) * up, p["experts"]["w_down"], sizes)
    out = jnp.where(here[order][:, None], out, 0).astype(jnp.float32)
    out = out * weights.reshape(-1)[order][:, None]
    routed = out[jnp.argsort(order)].reshape(count, k, -1).sum(axis=1)
    return swiglu(x, p["shared"]) + routed.astype(x.dtype), ids, sizes


def both(p, x, held, route):
    got = jax.jit(lambda p, x: moe.expert_layer(p, x, held, route))(p, x)
    want = jax.jit(lambda p, x: whole(p, x, held, route))(p, x)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))
    return np.asarray(got[0]), np.asarray(want[0]), int(got[2].sum())


@pytest.fixture
def rows_run(monkeypatch):
    """The row counts of the grouped products that ran: of the branch the
    device took, not of those that were only traced."""
    seen = []
    real = jax.lax.ragged_dot

    def counting(lhs, rhs, group_sizes, **kwargs):
        jax.debug.callback(lambda: seen.append(lhs.shape[0]))
        return real(lhs, rhs, group_sizes, **kwargs)

    monkeypatch.setattr(jax.lax, "ragged_dot", counting)

    def read():
        jax.effects_barrier()
        taken = sorted(set(seen))
        seen.clear()
        return taken

    return read


@pytest.mark.parametrize("pairs, held, experts, want", [
    (8192 * 8, 40, 320, (8192, 16384, 32768, 65536)),   # Solar-Open2's prefill
    (2048 * 6, 40, 160, (3072, 6144, 12288)),           # DeepSeek-V2's
    (8, 40, 320, (8,)),                                  # their decode steps
    (6, 40, 160, (6,)),
    (TOKENS * K, len(HELD), EXPERTS, LADDER),
    (2048, 16, 16, (2048,)),                             # every expert held
    (8000 * 8, 40, 320, (8192, 16128, 32000, 64000)),   # a length off every tile
    (256, 1, 64, (256,)),                                # a tile or less: one rung
    (257, 1, 64, (256, 257)),
    (384, 4, 16, (256, 384)),                            # tiny-deepseek-v2's 128-token prompt
    # LongCat-Flash's block of 1,024 tokens, 12 pairs each, 8 held of a router 768 wide (512
    # experts and 256 identities): the even share is 128 rows, under the lowest rung
    (1024 * 12, 8, 768, (256, 512, 768, 1536, 3072, 6144, 12288)),
    (12, 8, 768, (12,)),                                 # its decode step
])
def test_the_ladder_of_a_shape(pairs, held, experts, want):
    assert moe.row_ladder(pairs, held, experts) == want


@pytest.mark.parametrize("held, experts", [(40, 320), (40, 160), (1, 256), (3, 7), (8, 8)])
def test_a_ladder_ends_at_all_pairs_in_steps_of_at_most_two_on_whole_tiles(held, experts):
    for pairs in (1, 255, 256, 1000, 4097, 12288, 50001, 65536):
        ladder = moe.row_ladder(pairs, held, experts)
        assert ladder[-1] == pairs and list(ladder) == sorted(set(ladder))
        for below, above in zip(ladder, ladder[1:]):
            assert below % moe.ROW_TILE == 0 and above <= 2 * below
        # nothing under the share that even routing gives, but the next rung up
        assert ladder[0] * experts >= pairs * held
        assert len(ladder) == 1 or (ladder[0] - moe.ROW_TILE) * experts < pairs * held * 2


@pytest.mark.parametrize("bias, rung", [(-0.05, 0), (0.03, 1), (0.1, 2)])
def test_every_rung_gives_what_all_the_pairs_give(bias, rung, rows_run):
    """The router's bias on the held experts skews the routing until the
    held pairs need that rung; the output is the whole computation's to
    float32 rounding (a prefix adds a token's rows in the sorted pairs'
    order, the whole computation in the token's own). Two held experts
    take at most half the pairs: the top rung is a later test's."""
    p, x = params(), tokens()
    route = biased(jnp.zeros((EXPERTS,)).at[HELD.start:HELD.stop].set(bias))
    got, want, held_pairs = both(p, x, HELD, route)
    assert moe.rung_index(LADDER, held_pairs) == rung, held_pairs
    assert [r for r in rows_run() if r != TOKENS * K] == [LADDER[rung]]
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("lanes", [8, 12, 32])
def test_a_prefix_is_added_to_its_tokens_in_blocks_of_columns(lanes, monkeypatch):
    """The way back of a prefix is one scatter-add a block of columns:
    blocks that divide the width (4 of 8), that do not (12, 12, 8) and
    the whole width give the same sums, to the bit."""
    p, x = params(), tokens()
    route = biased(jnp.zeros((EXPERTS,)))
    want = np.asarray(jax.jit(lambda p, x: moe.expert_layer(p, x, HELD, route))(p, x)[0])
    monkeypatch.setattr(moe, "SCATTER_LANES", lanes)
    jaxpr = jax.make_jaxpr(lambda p, x: moe.expert_layer(p, x, HELD, route))(p, x).jaxpr
    (eqn,) = _conditionals(jaxpr)
    scatters = [e for e in eqn.params["branches"][0].jaxpr.eqns if e.primitive.name == "scatter-add"]
    assert [e.outvars[0].aval.shape for e in scatters] == [
        (TOKENS, min(lanes, HIDDEN - at)) for at in range(0, HIDDEN, lanes)]
    got = np.asarray(jax.jit(lambda p, x: moe.expert_layer(p, x, HELD, route))(p, x)[0])
    np.testing.assert_array_equal(got, want)


def test_with_every_pair_held_the_top_rung_drops_none_and_is_the_whole_computation(rows_run):
    """No capacity factor at any routing: a bias that sends each token's
    four pairs to the four held experts needs all 2,048 rows, and gets
    the output the layer gave before there was a ladder."""
    held = range(4, 8)
    p, x = params(held), tokens()
    route = biased(jnp.zeros((EXPERTS,)).at[held.start:held.stop].set(10.0))
    got, want, held_pairs = both(p, x, held, route)
    assert held_pairs == TOKENS * K
    assert rows_run() == [TOKENS * K]
    np.testing.assert_array_equal(got, want)
    routed = got - np.asarray(swiglu(x, p["shared"]))
    assert (np.abs(routed).max(axis=1) > 0).all()  # every token got its experts' part


@pytest.mark.parametrize("count", [1, 16, 64])
def test_a_ladder_of_one_rung_is_the_program_as_it_was(count):
    """A decode step and a tiny model: no conditional, and the same
    operations in the same order as the computation over all pairs, so
    the same bits."""
    p, x = params(), tokens(count)
    route = biased(jnp.zeros((EXPERTS,)))
    assert len(moe.row_ladder(count * K, len(HELD), EXPERTS)) == 1
    got, want, _ = both(p, x, HELD, route)
    np.testing.assert_array_equal(got, want)
    mine = jax.make_jaxpr(lambda p, x: moe.expert_layer(p, x, HELD, route))(p, x)
    theirs = jax.make_jaxpr(lambda p, x: whole(p, x, HELD, route))(p, x)
    assert _primitives(mine.jaxpr) == _primitives(theirs.jaxpr)
    assert "cond" not in _primitives(mine.jaxpr)


def test_a_rank_whose_experts_nobody_chose_returns_the_shared_expert_alone(rows_run):
    p, x = params(), tokens()
    route = biased(jnp.zeros((EXPERTS,)).at[HELD.start:HELD.stop].set(-10.0))
    got, want, held_pairs = both(p, x, HELD, route)
    assert held_pairs == 0
    assert [r for r in rows_run() if r != TOKENS * K] == [LADDER[0]]
    np.testing.assert_array_equal(got, np.asarray(jax.jit(swiglu)(x, p["shared"])))
    np.testing.assert_array_equal(got, want)


EDGES = sorted({0, TOKENS * K} | {rows + over for rows in LADDER[:-1] for over in (0, 1)})


@pytest.mark.parametrize("held_pairs", EDGES)
def test_the_rung_the_host_reports_is_the_rung_the_device_took(held_pairs, rows_run):
    """Loads on each side of every rung's edge: `report_loads` reads the
    rung from the loads that came back, the device from the same sum."""
    p, x = params(), tokens()
    out, _, sizes = jax.jit(
        lambda p, x: moe.expert_layer(p, x, HELD, prescribed(held_pairs)))(p, x)
    want = next(rows for rows in LADDER if rows >= held_pairs)
    assert rows_run() == [want]
    loads = np.stack([np.asarray(sizes)] * 3)  # three expert layers alike
    step = np.asarray([[1, 0], [0, 2], [1, 1]])
    attrs = moe.report_loads(K, EXPERTS, TOKENS, 5, loads, step, "xla")
    assert attrs["prefill_expert_rows"] == 3 * want
    assert attrs["prefill_routed_pairs"] == 3 * TOKENS * K
    assert attrs["prefill_routed_pairs_held"] == 3 * held_pairs
    assert attrs["decode_expert_rows"] == attrs["decode_routed_pairs"] == 5 * K * 3
    assert attrs["decode_routed_pairs_held"] == 5
    assert attrs["prefill_expert_load_max"] == int(sizes.max())
    # and the output is what all the pairs give
    ref, _, _ = jax.jit(lambda p, x: whole(p, x, HELD, prescribed(held_pairs)))(p, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6, atol=1e-6)


def test_the_layers_of_one_prefill_report_a_rung_each():
    loads = np.zeros((4, 40), np.int64)
    loads[:, 0] = [6000, 8192, 8193, 40000]
    attrs = moe.report_loads(8, 320, 8192, 256, loads, np.ones((4, 40), np.int64), "kernel")
    assert attrs["prefill_expert_rows"] == 8192 + 8192 + 16384 + 65536
    assert attrs["prefill_routed_pairs"] == 4 * 65536
    assert attrs["decode_expert_rows"] == attrs["decode_routed_pairs"] == 256 * 8 * 4
    assert attrs["decode_expert_route"] == "kernel"


def _primitives(jaxpr) -> list:
    return [eqn.primitive.name for eqn in jaxpr.eqns]


def _conditionals(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _conditionals(inner)


@pytest.mark.parametrize("count, rungs", [(1, 1), (64, 1), (TOKENS, 4)])
def test_only_a_ladder_of_several_rungs_puts_a_conditional_in_the_program(count, rungs):
    p, x = params(), tokens(count)
    jaxpr = jax.make_jaxpr(
        lambda p, x: moe.expert_layer(p, x, HELD, biased(jnp.zeros((EXPERTS,)))))(p, x).jaxpr
    found = list(_conditionals(jaxpr))
    assert len(found) == (rungs > 1)
    for eqn in found:
        assert len(eqn.params["branches"]) == rungs


def test_the_lowest_rung_forms_no_array_of_more_rows_than_it_has():
    """Inside the lowest branch nothing two-dimensional is longer than
    the rung (here 256 rows, under the 512 tokens, whose `[T, hidden]`
    result alone is longer); one-dimensional index arrays over the pairs
    stay."""
    p, x = params(), tokens()
    jaxpr = jax.make_jaxpr(
        lambda p, x: moe.expert_layer(p, x, HELD, biased(jnp.zeros((EXPERTS,)))))(p, x).jaxpr
    (eqn,) = _conditionals(jaxpr)
    lowest = eqn.params["branches"][0].jaxpr
    rows = [
        (eqn.primitive.name, var.aval.shape)
        for eqn in lowest.eqns for var in eqn.outvars if len(var.aval.shape) >= 2]
    assert any(name == "ragged_dot_general" or name == "ragged_dot" for name, _ in rows)
    longer = [(name, shape) for name, shape in rows if shape[0] > max(LADDER[0], TOKENS)]
    assert not longer, longer
    top = eqn.params["branches"][-1].jaxpr
    assert any(
        var.aval.shape[0] == TOKENS * K for eqn in top.eqns for var in eqn.outvars
        if len(var.aval.shape) >= 2)


# --- a router wider than its experts: identities (PR 63) -----------------------


def softmax_route(logits, k=K, scale=6.0):
    """LongCat-Flash's rule: softmax over the whole width, the k largest,
    the weights the chosen scores times `scale`, not renormalised."""
    scores = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(scores, k)
    return ids, weights * scale


@pytest.mark.parametrize("count", [16, TOKENS])
def test_an_identity_adds_its_weight_times_the_input_and_is_never_a_row(count):
    """A router of 16 over 12 experts (ids 12-15 identities), 2 held: the
    layer is the layer without the argument plus, a token, the chosen
    identities' weights' sum times the token; the loads and the rung are
    those of the layer without it."""
    p, x = params(), tokens(count)
    p = {name: value for name, value in p.items() if name != "shared"}
    plain, ids, sizes = moe.expert_layer(p, x, HELD, softmax_route)
    got, ids_2, sizes_2 = moe.expert_layer(p, x, HELD, softmax_route, identities=12)
    np.testing.assert_array_equal(ids, ids_2)
    np.testing.assert_array_equal(sizes, sizes_2)
    _, weights = softmax_route(x @ p["w_g"])
    kept = jnp.sum(jnp.where(ids >= 12, weights, 0.0), axis=-1, keepdims=True)
    assert float(jnp.max(kept)) > 0 and int(jnp.sum(ids >= 12)) > 0
    np.testing.assert_allclose(np.asarray(got), np.asarray(plain + kept * x), rtol=1e-5, atol=1e-6)
    # an identity is no absent chip's expert either: with every expert held it is still added
    everyone = params(held=range(12), experts=EXPERTS)
    everyone.pop("shared")
    whole_layer, ids_3, _ = moe.expert_layer(everyone, x, range(12), softmax_route, identities=12)
    without, _, _ = moe.expert_layer(everyone, x, range(12), softmax_route)
    _, weights = softmax_route(x @ everyone["w_g"])
    kept = jnp.sum(jnp.where(ids_3 >= 12, weights, 0.0), axis=-1, keepdims=True)
    np.testing.assert_allclose(
        np.asarray(whole_layer), np.asarray(without + kept * x), rtol=1e-5, atol=1e-6)


def test_report_loads_counts_the_pairs_that_chose_an_identity_a_phase():
    loads = np.array([[3, 1], [0, 2]])
    said = moe.report_loads(12, 768, 64, 4, loads, loads // 2, "xla", (500, 31))
    assert (said["prefill_zero_pairs"], said["decode_zero_pairs"]) == (500, 31)
    assert said["prefill_routed_pairs"] == 64 * 12 * 2
    assert "prefill_zero_pairs" not in moe.report_loads(12, 768, 64, 4, loads, loads // 2, "xla")


def as_it_was(p: dict, x: jax.Array, held: range, route,
              limit: float = 0.0, shared_limit: float = 0.0, index=None):
    """`moe.expert_layer` as it stood before it learnt of identities (PR
    62), line for line."""
    with jax.named_scope("router"):
        logits = jnp.dot(
            x.astype(jnp.float32), p["w_g"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        ids, weights = route(logits)
    with jax.named_scope("experts"):
        tokens, k = ids.shape
        local = ids.reshape(-1) - held.start
        here = (local >= 0) & (local < len(held))
        # sort the token-expert pairs by held expert, the pairs of absent
        # experts last: each held expert's rows are then one segment, and
        # the held pairs are the first `sizes.sum()` rows
        slot = jnp.where(here, local, len(held))
        order = jnp.argsort(slot, stable=True)
        sizes = jnp.zeros((len(held),), jnp.int32).at[slot].add(1, mode="drop")

        # a decode step's few rows: each chosen held expert's weights
        # read once where they lie (`ops/moe.expert_matvec`)
        experts = p["experts"]
        w_down = experts["w_down"]
        how = moe.decode_route(
            tokens * k, w_down.shape[-1], w_down.shape[-2], w_down.dtype, moe.gated(experts))
        grouped = moe.expert_matvec if how == "kernel" else moe.grouped_xla

        def over(rows_n: int):
            """The held experts' part [T, hidden] float32 from the first
            `rows_n` sorted pairs, which has to cover every held one."""
            top = order[:rows_n]
            token = top // k
            rows = x[token]
            if moe.gated(experts):
                gate, up = jnp.split(
                    grouped(rows, experts["w_gate_up"], sizes, index), 2, axis=-1)
                middle = moe.clamped_silu_product(gate, up, limit)
            else:
                middle = jnp.square(jax.nn.relu(
                    grouped(rows, experts["w_up"], sizes, index, out_major=True)))
            out = grouped(middle, w_down, sizes, index)
            # rows past the last segment are absent experts' pairs: weight 0
            out = jnp.where(here[top][:, None], out, 0).astype(jnp.float32)
            out = out * weights.reshape(-1)[top][:, None]
            if rows_n == tokens * k:
                # every pair: back to the pairs' own order, then the sum
                # over a token's experts
                return out[jnp.argsort(order)].reshape(tokens, k, -1).sum(axis=1)
            # a prefix: each row is added to its token's, a block of
            # columns at a time (4,608 float32 rows of 5,120 cost a v5e
            # 4.2 ms whole and 0.7 ms in four blocks: PERF.md §6, PR 40)
            edges = list(range(moe.SCATTER_LANES, out.shape[1], moe.SCATTER_LANES))
            return jnp.concatenate([
                jnp.zeros((tokens, part.shape[1]), jnp.float32).at[token].add(part)
                for part in jnp.split(out, edges, axis=1)
            ], axis=1)

        ladder = moe.row_ladder(tokens * k, len(held), p["w_g"].shape[1])
        if len(ladder) == 1:
            routed = over(ladder[0])
        else:
            routed = jax.lax.switch(
                moe.rung_index(ladder, sizes.sum()), [partial(over, rows_n) for rows_n in ladder])
    if "shared" not in p:
        return routed.astype(x.dtype), ids, sizes
    with jax.named_scope("shared"):
        shared = (moe.swiglu(x, p["shared"], shared_limit) if moe.gated(p["shared"])
                  else moe.relu2_mlp(x, p["shared"]))
    return shared + routed.astype(x.dtype), ids, sizes


OTHERS = ["deepseek_v2", "solar_open2", "k_exaone", "ling_flash", "nemotron_h", "glm_dsa", "sdar",
          "dots3"]
TINY = {"deepseek_v2": "tiny-deepseek-v2", "solar_open2": "tiny-solar-open2",
        "k_exaone": "tiny-k-exaone", "ling_flash": "tiny-ling-flash",
        "nemotron_h": "tiny-nemotron3-nano", "glm_dsa": "tiny-glm-dsa", "sdar": "tiny-sdar",
        "dots3": "tiny-dots3"}


@pytest.mark.parametrize("name", OTHERS)
def test_the_other_expert_models_programs_are_what_they_were_before_the_argument(
        name, monkeypatch):
    """The eight models that never hand `identities`: their prefill and
    their decode trace to the same jaxprs, text for text, with the
    layer as it stands and with the layer as it stood (`as_it_was`, put
    in the module's own name for the second trace)."""
    import importlib

    from comfyui_distributed_tpu.models.registry import create_model

    module = importlib.import_module(f"comfyui_distributed_tpu.models.{name}")
    lm = create_model(TINY[name])
    cfg = lm.cfg
    weights = jax.eval_shape(lambda: lm.init(jax.random.key(0)))
    ids = jax.ShapeDtypeStruct((40,), jnp.int32)

    def programs():
        prefill = jax.make_jaxpr(
            lambda w, i: module.prefill.__wrapped__(cfg, w, i, cache_len=48))(weights, ids)
        made = jax.eval_shape(
            lambda w, i: module.prefill.__wrapped__(cfg, w, i, cache_len=48), weights, ids)
        decode = jax.make_jaxpr(
            lambda w, cache, logits: module.decode.__wrapped__(
                cfg, w, cache, logits, jnp.int32(40), jax.random.key(1), jnp.float32(1.0),
                steps=4))(weights, made.cache, made.logits)
        return str(prefill), str(decode)

    now = programs()
    monkeypatch.setattr(module, "expert_layer", as_it_was)
    assert programs() == now
    assert "zero_experts" not in now[0] + now[1]
