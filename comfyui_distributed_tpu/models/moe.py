"""The expert layer of one chip, as the language models with a mixture
of experts share it (`deepseek_v2.py`, `solar_open2.py`, `k_exaone.py`,
`ling_flash.py`, `nemotron_h.py`, `glm_dsa.py`, `sdar.py`, `dots3.py`,
`longcat_flash.py`), and the routing rule of those that score by
sigmoids (`sigmoid_route`, with or without groups chosen first).

The layer is told which routed experts it holds (`held`, a contiguous
run: `parallel.sharding.expert_range` of the chip's rank), routes every
token over the router's whole width by the model's own rule, and computes
its own experts' part as one grouped product (`jax.lax.ragged_dot`) with
no dropped token and no capacity factor. An id outside `held` is an
absent chip's expert, whose part is left out, or, in a model whose router
is wider than its experts (`identities`: LongCat-Flash's 768 outputs over
512 experts), an identity: no chip's weights, its weight times the
layer's own input, added here whole. A `shared` expert in the tree (seven
of the nine models; SDAR and LongCat-Flash have none) sees every token.

Sorted by held expert, the pairs a chip holds are the first rows and
every row after them is an absent expert's, so the row gather, the
grouped products and the weighted way back to `[T, hidden]` run over a
static prefix of the sorted pairs: the smallest rung of `row_ladder`
that holds them, which the device picks from the routing's own count
(`lax.switch`; nothing is read back). The top rung is all `T x k` pairs,
so no routing, however skewed, drops a pair. Where the pairs are a tile
or less (a decode step: a ladder of one rung) the two grouped products
run, on a TPU, in the Pallas kernel of `ops/expert_matvec.py`, which
reads each chosen held expert's weights once, out of the stacked array
(`decode_route`); a prefill's lowest rung: `ops/grouped_matmul.py`.

Parameters: `w_g` [hidden, router width], `experts` and, where there is
one, `shared` in the expert's form, which the tree says (`gated`): a SwiGLU,
{`w_gate_up` [held, hidden, 2 x width], `w_down` [held, width, hidden]}
and `shared` one such, or two matrices without a gate, relu(x W_up)^2
W_down (Nemotron-H's): {`w_up` [held, width, hidden], stored out by in
as a checkpoint stores a projection, because the width may be off the
lane tile and the hidden size is not (`ops/expert_matvec`), `w_down`
[held, width, hidden]} and `shared` {`w_up` [hidden, width], `w_down`};
whatever else the model's rule reads (a selection bias) stays with the
rule. A scan over stacked layers hands the stacks whole with `index`.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.expert_matvec import expert_matvec, expert_matvec_route, grouped_xla  # noqa: F401
from ..ops.grouped_matmul import grouped_rows, rung_route
from .lm_common import clamped_silu_product, relu2_mlp, swiglu

# A rung of the ladder below the top is a whole number of these rows.
ROW_TILE = 256
# The columns one scatter-add of a prefix's rows takes.
SCATTER_LANES = 1024


def row_ladder(pairs: int, held: int, experts: int) -> tuple[int, ...]:
    """The static row counts a layer of `pairs` token-expert pairs may
    run its held experts' products over, ascending: `pairs` itself last,
    below it `pairs` halved again and again (rounded up to whole tiles)
    down to the share `held` of `experts` (the router's width, identities
    among them) take when the routing is even. One rung where `pairs` is
    a tile or less (a decode step), or where every expert is held."""
    rungs = [pairs]
    while True:
        rows = -(-pairs // (ROW_TILE << len(rungs))) * ROW_TILE
        if rows >= rungs[0] or rows * experts < pairs * held:
            return tuple(rungs)
        rungs.insert(0, rows)


def rung_index(ladder: tuple[int, ...], pairs_held):
    """Which rung holds `pairs_held` rows: the smallest that is no
    smaller. The device branches on it (a traced scalar) and
    `report_loads` reads the same from the loads on the host."""
    return sum(pairs_held > rows for rows in ladder[:-1])


def sigmoid_route(logits: jax.Array, bias: jax.Array, k: int, scale: float = 1.0,
                  renormalise: bool = True, n_group: int = 1, topk_group: int = 1):
    """Over float32 router logits [T, experts]: scores are their
    sigmoids, the `k` largest of score + `bias` (a selection bias an
    expert) are chosen, ties to the lower index, and the weights are the
    chosen scores, without the bias, over their sum (`renormalise`)
    times `scale`. With `n_group` > 1 the experts are that many runs of
    equal length and groups are chosen first: a group's score is the sum
    of its two largest score + bias, the `topk_group` best groups stay
    (ties to the lower index), and the `k` are chosen among their
    experts. Returns (ids [T, k], weights [T, k])."""
    scores = jax.nn.sigmoid(logits)
    biased = scores + bias.astype(jnp.float32)
    if n_group > 1:
        tokens, experts = biased.shape
        groups = biased.reshape(tokens, n_group, experts // n_group)
        _, best = jax.lax.top_k(jax.lax.top_k(groups, 2)[0].sum(axis=-1), topk_group)
        stays = jnp.zeros((tokens, n_group), bool).at[jnp.arange(tokens)[:, None], best].set(True)
        biased = jnp.where(stays[:, :, None], groups, -jnp.inf).reshape(tokens, experts)
    _, ids = jax.lax.top_k(biased, k)
    weights = jnp.take_along_axis(scores, ids, axis=-1)
    if renormalise:
        weights = weights / weights.sum(axis=-1, keepdims=True)
    return ids, weights * scale


def gated(expert: dict) -> bool:
    """The form of an expert's tree (routed stack or shared expert): a
    SwiGLU (`w_gate_up`, `w_down`), else two matrices without a gate
    (`w_up`, `w_down`: relu squared between them)."""
    return "w_gate_up" in expert


def decode_route(rows: int, hidden: int, width: int, dtype, with_gate: bool = True) -> str:
    """How a layer of `rows` token-expert pairs runs its two grouped
    products (`[hidden, 2 x width]`, or `[hidden, width]` for an expert
    without a gate, then `[width, hidden]`): "kernel"
    where the ladder has one rung because the pairs are a tile or less
    (a decode step) and `ops/expert_matvec` takes both shapes on this
    backend, else "xla" (`jax.lax.ragged_dot`). The layer asks while it
    is traced, a model's `report` afterwards."""
    if rows > ROW_TILE:
        return "xla"
    up = (expert_matvec_route(rows, hidden, 2 * width, dtype) if with_gate
          else expert_matvec_route(rows, hidden, width, dtype, out_major=True))
    routes = {up, expert_matvec_route(rows, width, hidden, dtype)}
    return "kernel" if routes == {"kernel"} else "xla"


def expert_layer(p: dict, x: jax.Array, held: range, route: Callable, limit: float = 0.0,
                 shared_limit: float = 0.0, index=None, identities: int | None = None):
    """x [T, hidden] through the layer. `route(logits)` takes the
    router's float32 logits [T, width] and returns (ids [T, k], weights
    [T, k] float32). `limit` and `shared_limit` clamp the routed experts'
    and the shared expert's SwiGLU (`lm_common.clamped_silu_product`; 0:
    none), on either route; an expert without a gate has none. With
    `index` (a traced scalar: a scan's body) `p["experts"]` holds stacks
    `[layers, held, ...]` of which this layer is that one, read where it
    lies. Ids from `identities` on are identity experts (the module's
    docstring): never held, never rows. A tree without `shared` has no
    shared expert. Returns (output, chosen ids [T, k], pairs on each
    held expert [held])."""
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

        # a decode step's few rows: each chosen held expert's weights read once
        # where they lie (`ops/expert_matvec`); a prefill's: `ops/grouped_matmul`
        experts, w_down = p["experts"], p["experts"]["w_down"]
        ladder = row_ladder(tokens * k, len(held), p["w_g"].shape[1])
        how = decode_route(
            tokens * k, w_down.shape[-1], w_down.shape[-2], w_down.dtype, gated(experts))
        grouped = expert_matvec if how == "kernel" else grouped_rows(ladder[0])

        def over(rows_n: int):
            """The held experts' part [T, hidden] float32 from the first
            `rows_n` sorted pairs, which has to cover every held one."""
            top = order[:rows_n]
            token = top // k
            rows = x[token]
            if gated(experts):
                gate, up = jnp.split(
                    grouped(rows, experts["w_gate_up"], sizes, index), 2, axis=-1)
                middle = clamped_silu_product(gate, up, limit)
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
            edges = list(range(SCATTER_LANES, out.shape[1], SCATTER_LANES))
            return jnp.concatenate([
                jnp.zeros((tokens, part.shape[1]), jnp.float32).at[token].add(part)
                for part in jnp.split(out, edges, axis=1)
            ], axis=1)

        # the device picks the rung from the routing's own count
        if len(ladder) == 1:
            routed = over(ladder[0])
        else:
            routed = jax.lax.switch(
                rung_index(ladder, sizes.sum()), [partial(over, rows_n) for rows_n in ladder])
    if identities is not None:
        with jax.named_scope("zero_experts"):
            # an identity's pair: its weight times the layer's input, in float32
            kept = jnp.sum(jnp.where(ids >= identities, weights, 0.0), axis=-1, keepdims=True)
            routed = routed + kept * x.astype(jnp.float32)
    if "shared" not in p:
        return routed.astype(x.dtype), ids, sizes
    with jax.named_scope("shared"):
        shared = (swiglu(x, p["shared"], shared_limit) if gated(p["shared"])
                  else relu2_mlp(x, p["shared"]))
    return shared + routed.astype(x.dtype), ids, sizes


def prefill_route(tokens: int, k: int, held: int, experts: int, hidden: int, width: int, dtype,
                  with_gate: bool = True) -> str:
    """How a layer over `tokens` tokens (a prefill, a part, a block: more
    than a tile of pairs) runs the two grouped products of its ladder's
    lowest rung, which is where an evenly routed request lands or just over:
    "kernel" where `ops/grouped_matmul.rung_route` takes both shapes of
    the lowest rung on this backend, else "xla" (`jax.lax.ragged_dot`,
    which the rungs above keep whatever this says). `experts` is the
    router's width. The layer's `grouped_rows` asks the same function
    while it is traced, a model's `report` afterwards."""
    rows = row_ladder(tokens * k, held, experts)[0]
    up = (rung_route(rows, rows, hidden, 2 * width, held, dtype) if with_gate
          else rung_route(rows, rows, hidden, width, held, dtype, out_major=True))
    return "kernel" if {up, rung_route(rows, rows, width, hidden, held, dtype)} == {
        "kernel"} else "xla"


def report_loads(k: int, experts: int, prompt_tokens: int, new_tokens: int,
                 prefill_loads, decode_loads, decode_expert_route: str,
                 zero_pairs: tuple[int, int] | None = None, *,
                 prefill_expert_route: str = "xla") -> dict:
    """`node.TextGenerate`'s attributes of the routing, per phase: the
    token-expert pairs the router made (`k` a token and expert layer),
    those that fell on held experts (`loads` [expert layers, held], as
    read back), the fullest held expert's, and the rows the grouped
    products were run over: for the prefill each layer's rung, read from
    its load as the device read it; a decode step's `k` pairs are under
    a tile, a ladder of one rung, so its rows are its pairs, and
    `decode_expert_route` (the model's `decode_route` of a step's pairs)
    says what multiplied them, `prefill_expert_route` (its `prefill_route`
    of a call's tokens) the prefill's lowest rung. `experts` is the
    router's width. A model with identity experts hands the pairs that
    chose one, (the prefill's, the decode's), as it read them back:
    `<phase>_zero_pairs`."""
    layers, held = np.shape(prefill_loads)
    attrs = {}
    for phase, tokens, loads in (
        ("prefill", prompt_tokens, prefill_loads), ("decode", new_tokens, decode_loads)
    ):
        attrs[f"{phase}_routed_pairs"] = tokens * k * layers
        attrs[f"{phase}_routed_pairs_held"] = int(np.sum(loads))
        attrs[f"{phase}_expert_load_max"] = int(np.max(loads))
    if zero_pairs is not None:
        attrs["prefill_zero_pairs"], attrs["decode_zero_pairs"] = map(int, zero_pairs)
    ladder = row_ladder(prompt_tokens * k, held, experts)
    attrs["prefill_expert_rows"] = sum(
        ladder[rung_index(ladder, int(n))] for n in np.sum(prefill_loads, axis=1))
    attrs["decode_expert_rows"] = attrs["decode_routed_pairs"]
    attrs["decode_expert_route"] = decode_expert_route
    attrs["prefill_expert_route"] = prefill_expert_route
    return attrs
