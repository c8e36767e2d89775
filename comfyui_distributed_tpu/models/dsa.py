"""Learned sparse attention over a latent cache (DeepSeek sparse
attention, DSA), as `glm_dsa.py` and `dots3.py` use it: an **indexer** scores every
visible position for a query, the `index_topk` best are **selected**,
exactly, and latent attention (`models/mla.py`) reads the chosen rows of
the cache and no others.

The indexer is a second, small attention with a cache of its own: `heads`
query heads of width `d` made of the query latent, one key of width `d` a
position made of the layer's input under a LayerNorm, the first `rope` of
each rotated in pairs, and a weight a head made of the layer's input:

    I_ts = sum_j w_tj relu(qI_tj . kI_s)      for s <= t, float32
    S_t  = the `index_topk` positions s <= t with the largest I_ts
           (all of them while t + 1 <= `index_topk`); ties to the lower s

`keys` makes the rows the indexer's cache holds, `queries` a part's
queries and weights, `scores` I with what a query may not see at minus
infinity, `select` S and `attend` latent attention over it. A layer that
computes no index of its own attends by the `Selection` handed to it
(GLM-5.2's `shared` layers); a model may as well give every attending
layer an index of its own, at its own count of heads (dots3-note-prev's
full layers: 64 index heads, 128 attention heads).

S has two forms, by the number of queries alone (`form`). **gathered**
(a part of a prompt): the chosen positions a block of query rows at a
time (`BLOCK_ROWS`), their rows of the latent cache brought together
and `mla.absorbed`'s products run over them. How a block's positions
are picked is the backend's (`selection_form`, `ops/dsa_select.route`):
on a TPU by one Pallas kernel that holds the block's scores in VMEM,
finds the k-th largest by bisection and turns the mask into ascending
positions by a compress network (`top_compacted`; a part of 8,192
queries over 32,768 keys 15 ms on a v5e against the sort's 110);
elsewhere by `lax.top_k` (`top`, positions by score). Where the rows
are brought together is the backend's too (`ops/dsa_attend.route`): on
a TPU inside one Pallas kernel that holds the layer's cache in VMEM and
copies a query's rows beside one another on chip (`attend_kernel`; a
part of 8,192 queries over 32,896 rows 41 ms on a v5e against 124);
elsewhere, and for a cache the kernel has no plan for, in HBM by XLA's
gather, a block of `ATTEND_ROWS` query rows at a time
(`attend_gathered`), so that neither the heads' products `[T, heads,
S]` nor the gathered rows `[T, index_topk, width]` are ever whole in
memory. **masked** (a decode
step's one or two queries): the `index_topk`-th largest score by
bisection on the scores' bit patterns, the mask `I >= threshold` under
the tie rule, and `mla.absorbed` over the whole cache under it: a
step's few queries read the cache once either way, and neither a sort
nor a gather is in the step. Both choose the same set.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..ops import dsa_attend, dsa_select
from . import mla
from .lm_common import apply_rope_pairs

# Query rows a block of `select` takes: the heads' products of a block
# are [rows, heads, S] float32 (0.5 GB at 128 rows, 32 heads and 32,768
# keys); on a v5e the sort behind `lax.top_k` read 211 ms a part of 8,192
# queries at 128 rows a block and 280 at 32 or 64 (PERF.md section 6).
BLOCK_ROWS = 128
# Query rows a block of `attend` takes: its gathered rows are [rows,
# index_topk, width] (0.15 GB at 64 rows of 2,048 latents 576 wide in
# bfloat16); 124 ms a part at 64 rows, 134 at 128.
ATTEND_ROWS = 64
# Query rows a call of the `dsa_attend` kernel takes: what lives beside a
# part's own arrays is a block's folded queries and latent outputs
# (0.27 GB at 2,048 rows of 64 heads), not the part's (1.1 GB), and the
# cache's words come into VMEM once a block (62 us of a call's 10 ms).
KERNEL_ROWS = 2048
# Queries up to which a selection is a mask over the whole cache.
MASKED_ROWS = 8


class Selection(NamedTuple):
    """S_t of T queries. Gathered: `chosen` [T, k] positions and `counts`
    [T, k], which of them count (a query with fewer than k visible
    positions has them all, the rest of its k do not). Masked: `chosen`
    None and `counts` [T, S], true at the chosen positions. Either way
    the true entries of `counts` are the keys the queries read."""

    chosen: jax.Array | None
    counts: jax.Array


def form(queries: int) -> str:
    """Which form the selection and attention of `queries` queries take."""
    return "masked" if queries <= MASKED_ROWS else "gathered"


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    normed = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (normed * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def _rotate_front(x: jax.Array, rope) -> jax.Array:
    """The first channels of the last axis rotated in pairs by `rope`
    (cos, sin [T, rope / 2]), the others as they are."""
    width = 2 * rope[0].shape[-1]
    return jnp.concatenate([apply_rope_pairs(x[..., :width], *rope), x[..., width:]], axis=-1)


def keys(p: dict, x: jax.Array, rope, eps: float) -> jax.Array:
    """What the indexer's cache holds of x [T, hidden]: [T, d]."""
    return _rotate_front(layer_norm(x @ p["w_k"], p["k_scale"], p["k_bias"], eps), rope)


def queries(p: dict, c_q: jax.Array, x: jax.Array, rope, heads: int):
    """The indexer's queries [T, heads, d] of the query latent c_q [T,
    q_lora_rank], and a weight a head [T, heads] float32 of the layer's
    input x, with both scales (heads^-1/2, d^-1/2) folded in."""
    q = (c_q @ p["w_q"]).reshape(c_q.shape[0], heads, -1)
    w = jnp.dot(x, p["w_w"], preferred_element_type=jnp.float32)
    return _rotate_front(q, rope), w * (heads * q.shape[-1]) ** -0.5


def by_rows(fn, rows: int, *arrays):
    """`fn` over blocks of `rows` rows of the arrays' first axis (the
    last block padded with zeros and its padding dropped), one after
    another (`lax.map`). `fn` may return one array or a tuple."""
    total = arrays[0].shape[0]
    rows = min(rows, total)
    pad = -total % rows

    def blocks(a):
        a = jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
        return a.reshape(-1, rows, *a.shape[1:])

    out = jax.lax.map(lambda block: fn(*block), tuple(blocks(a) for a in arrays))
    return jax.tree_util.tree_map(lambda a: a.reshape(-1, *a.shape[2:])[:total], out)


def scores(q: jax.Array, w: jax.Array, cached: jax.Array, positions: jax.Array) -> jax.Array:
    """I [T, S] float32 of the queries q [T, heads, d] with weights w [T,
    heads] at `positions` [T] over the indexer's cache `cached` [S, d]
    (row s position s); minus infinity where s > t. One block of rows:
    callers go through `select`."""
    products = jnp.einsum("thd,sd->ths", q, cached, preferred_element_type=jnp.float32)
    index = jnp.sum(jax.nn.relu(products) * w[:, :, None], axis=1)
    visible = jnp.arange(cached.shape[0])[None, :] <= positions[:, None]
    # a zero of either sign is one value to a comparison of floats, two to one of bit
    # patterns and to `lax.top_k`'s total order: only +0 leaves here
    return jnp.where(visible, jnp.where(index == 0, 0.0, index), -jnp.inf)


def top(index: jax.Array, k: int) -> Selection:
    """The `k` largest of each row of I [T, S] (as `scores` gives it) as
    positions, ties to the lower position (`lax.top_k` is stable)."""
    values, chosen = jax.lax.top_k(index, min(k, index.shape[1]))
    return Selection(chosen.astype(jnp.int32), values > -jnp.inf)


def top_compacted(index: jax.Array, k: int, interpret: bool = False) -> Selection:
    """`top`'s set through `ops/dsa_select`, one Pallas kernel and no
    sort: the k-th largest by bisection, the mask under the tie rule,
    and the mask made positions by a compress network, a block's scores
    in VMEM throughout. A query's positions come **ascending**, not by
    score; those that do not count point at row 0."""
    return Selection(*dsa_select.dsa_select(index, k=k, interpret=interpret))


def above_threshold(index: jax.Array, k: int) -> Selection:
    """The `k` largest of each row of I [T, S] (as `scores` gives it) as a
    mask: the k-th largest value by bisection on the bit patterns (32 passes of compare
    and count over keys that order as the floats do), every position
    above it, and of the positions equal to it the lowest that are
    still needed."""
    bits = jax.lax.bitcast_convert_type(index, jnp.int32)
    ordered = jnp.where(bits >= 0, bits, bits ^ 0x7FFFFFFF)
    u = jax.lax.bitcast_convert_type(ordered, jnp.uint32) ^ jnp.uint32(0x80000000)
    wanted = jnp.minimum(k, jnp.count_nonzero(index > -jnp.inf, axis=1))

    def narrow(i, tau):
        higher = tau | (jnp.uint32(0x80000000) >> i.astype(jnp.uint32))
        enough = jnp.count_nonzero(u >= higher[:, None], axis=1) >= wanted
        return jnp.where(enough, higher, tau)

    tau = jax.lax.fori_loop(0, 32, narrow, jnp.zeros(index.shape[:1], jnp.uint32))[:, None]
    above, equal = u > tau, u == tau
    left = wanted - jnp.count_nonzero(above, axis=1)
    return Selection(None, above | (equal & (jnp.cumsum(equal, axis=1) <= left[:, None])))


def length_ladder(rows: int, k: int) -> tuple[int, ...]:
    """The static lengths of a cache of `rows` rows that `select`'s
    gathered form may score and sort, ascending: the powers of two from
    twice `k` up that are shorter than the cache, then the cache whole.
    (A sort's cost is its length's, rounded up to a power of two: 21 /
    48 / 111 ms a part of 8,192 queries over 8,192 / 16,384 / 32,768
    rows on a v5e, and 211 over 32,896.)"""
    lengths, length = [], 1 << (2 * k - 1).bit_length()
    while length < rows:
        lengths.append(length)
        length *= 2
    return (*lengths, rows)


def selection_form(queries: int, rows: int, k: int) -> str:
    """How `select` picks the `k` best of `rows` positions for `queries`
    queries: "bisection" (the masked form), or the gathered form's
    "kernel" (`top_compacted`, on a TPU for a length `ops/dsa_select`
    has a plan for) or "sort" (`top`)."""
    if form(queries) == "masked":
        return "bisection"
    return dsa_select.route(min(queries, BLOCK_ROWS), rows, k)


def select(q: jax.Array, w: jax.Array, cached: jax.Array, positions: jax.Array,
           k: int) -> Selection:
    """S_t of every query (at `positions`, one after another) in the form
    their number gives (`form`). The gathered form scores the shortest
    rung of `length_ladder` that holds the last query's position, which
    the device picks (`lax.switch`): what lies past it no query of the
    call may see; and picks a rung's k best as `selection_form` says,
    which a traced call logs a rung (`ops/dsa_select.log_route`)."""
    def block(choose, cached, q, w, positions):
        with jax.named_scope("scores"):
            index = scores(q, w, cached, positions)
        with jax.named_scope("select"):
            return choose(index, k)

    def rung(length):
        how = selection_form(q.shape[0], length, k)
        dsa_select.log_route(how, q.shape[0], length, k)
        choose = top_compacted if how == "kernel" else top
        return partial(by_rows, partial(block, choose, cached[:length]), BLOCK_ROWS)

    if form(q.shape[0]) == "masked":
        return block(above_threshold, cached, q, w, positions)
    ladder = length_ladder(cached.shape[0], k)
    rungs = [rung(length) for length in ladder]
    if len(rungs) == 1:
        return rungs[0](q, w, positions)
    at = sum(positions[-1] >= length for length in ladder[:-1])
    return jax.lax.switch(at, rungs, q, w, positions)


def attend_gathered(q_nope: jax.Array, q_rope: jax.Array, cache: jax.Array,
                    selection: Selection, w_uk: jax.Array, w_uv: jax.Array,
                    scale: float) -> jax.Array:
    """`attend` over a gathered selection, left to XLA: the chosen rows
    brought together (`cache[chosen]`, written out and read back by the
    two products) a block of `ATTEND_ROWS` query rows at a time. What
    every backend but a TPU runs, and what the kernel is held against."""
    rank = w_uk.shape[0]

    def block(q_nope, q_rope, chosen, counts):
        rows = cache[chosen]                                       # [R, k, rank + rope]
        q_lat = jnp.einsum("thd,chd->thc", q_nope, w_uk)
        q = jnp.concatenate([q_lat, q_rope], axis=-1)
        dots = scale * jnp.einsum("thc,tkc->thk", q, rows, preferred_element_type=jnp.float32)
        dots = jnp.where(counts[:, None, :], dots, -jnp.inf)
        probs = jax.nn.softmax(dots, axis=-1).astype(cache.dtype)
        o_lat = jnp.einsum("thk,tkc->thc", probs, rows[..., :rank])
        return jnp.einsum("thc,chd->thd", o_lat, w_uv)

    return by_rows(block, ATTEND_ROWS, q_nope, q_rope, *selection)


def attend_kernel(q_nope: jax.Array, q_rope: jax.Array, cache: jax.Array,
                  selection: Selection, w_uk: jax.Array, w_uv: jax.Array,
                  scale: float, interpret: bool = False) -> jax.Array:
    """`attend` over a gathered selection through `ops/dsa_attend`: the
    same products, the chosen rows brought together inside the kernel
    (the layer's cache resident in VMEM, laid out for it once a call)
    and never in HBM; a block of `KERNEL_ROWS` query rows at a time,
    W_uk folded into the block's queries before the kernel and W_uv
    applied after it."""
    words = dsa_attend.table(cache, w_uk.shape[0])

    def block(q_nope, q_rope, chosen, counts):
        q_lat = jnp.einsum("thd,chd->thc", q_nope, w_uk)
        o_lat = dsa_attend.dsa_attend(
            q_lat, q_rope, words, chosen, counts, scale=scale, interpret=interpret)
        return jnp.einsum("thc,chd->thd", o_lat, w_uv)

    return by_rows(block, KERNEL_ROWS, q_nope, q_rope, *selection)


def attend(q_nope: jax.Array, q_rope: jax.Array, cache: jax.Array, selection: Selection,
           w_uk: jax.Array, w_uv: jax.Array, scale: float) -> jax.Array:
    """Latent attention of the queries ([T, heads, nope], [T, heads,
    rope] rotated) over the positions of the latent cache [S, rank +
    rope] that `selection` holds. Masked: `mla.absorbed` under the mask.
    Gathered: the same products (W_uk folded into the query, the
    weighted sum over the latents, W_uv after it) over the chosen rows
    brought together: on a TPU inside the `dsa_attend` kernel for a shape
    it has a plan for (`ops/dsa_attend.route`: `attend_kernel`), else by
    XLA (`attend_gathered`); scores and softmax float32, the
    probabilities rounded to the cache's dtype. Returns the heads'
    outputs [T, heads, v]. A traced call logs `dsa-<masked, kernel or
    gathered> <queries>x<cache rows> k<keys a query reads at most>
    h<heads> <dtype>` in `ops/attention.route_log`."""
    rank, (queries, heads) = w_uk.shape[0], q_nope.shape[:2]
    most = selection.counts.shape[1]  # the cache's rows, or the positions chosen
    form = "masked" if selection.chosen is None else dsa_attend.route(
        heads, cache.shape[1], rank, most, cache.shape[0], cache.dtype)
    dsa_attend.log_route(form, queries, cache.shape[0], most, heads, cache.dtype)
    if form == "masked":
        return mla.absorbed(q_nope, q_rope, cache, selection.counts, w_uk, w_uv, scale)
    gathered = attend_kernel if form == "kernel" else attend_gathered
    return gathered(q_nope, q_rope, cache, selection, w_uk, w_uv, scale)


def as_mask(selection: Selection, size: int) -> jax.Array:
    """A selection in the masked form over `size` positions: [T, size],
    true at the chosen positions that count."""
    if selection.chosen is None:
        return selection.counts
    rows = jnp.arange(selection.chosen.shape[0])[:, None]
    return jnp.zeros((rows.shape[0], size), bool).at[rows, selection.chosen].max(selection.counts)


def as_positions(selection: Selection, k: int):
    """A selection in the gathered form's arrays, (positions [T, k], which
    count [T, k]); a masked one's positions ascending. For a caller that
    keeps selections of both forms side by side (a parity check)."""
    if selection.chosen is not None:
        return tuple(selection)
    size = selection.counts.shape[1]
    marks, chosen = jax.lax.top_k(jnp.where(selection.counts, size - jnp.arange(size), 0), k)
    return chosen.astype(jnp.int32), marks > 0
