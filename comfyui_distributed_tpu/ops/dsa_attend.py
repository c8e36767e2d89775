"""Latent attention over the rows a selection chose, the chosen rows
brought together on chip and never written to HBM.

`models/dsa.attend`'s gathered form reads, for every query, the
`index_topk` rows of the layer's latent cache its indexer chose. Left
to XLA that is `cache[chosen]` written to HBM a block of query rows at
a time, `[64, 2048, 576]` bfloat16, and read back by the two products
that follow: 75 GB a part of 8,192 queries, 124 ms of a v5e at 74 % of
what that traffic allows (PERF.md section 6, PR 53). `dsa_attend` is
the same arithmetic as one Pallas kernel. The layer's whole cache (37.9
MB at 32,896 positions) lies in VMEM for the whole call, in one buffer;
a query's rows are copied beside one another, a load and a store a row,
at the positions an SMEM block of `chosen` holds; its scores `[heads,
k]` are one product in float32, masked by `counts`, softmaxed in
float32 over all k at once, rounded to the cache's dtype, and the
weighted sum is a second product over the same copy of the rows. W_uk
is folded into the queries and W_uv applied to the result by the
caller, as in the XLA form. Elsewhere that form stays (`route`).

How the cache lies there (`table`, made by XLA once a call, 0.1 ms). A
load at a row the program computes moves whole 32-bit sublanes, and a
bfloat16 row is half of one; so the resident copy holds words. A row's
columns are first laid as the products want them, the values in whole
lane tiles and the rotated channels in whole lane tiles after them (512
+ 128 at GLM-5.2's 512 + 64); word j of a row is then column j in its
low half and column `lanes + j` in its high half, and a half shifted to
the top of a word is the float32 of the same value, whose rounding to
bfloat16 is exact: the low halves and the high halves side by side are
the row again. A float32 cache's words are its columns. And the words
of a row are a run of `lanes / 128` sublanes of a `[rows x lanes / 128,
128]` array, so that a row is one load of that many sublanes at a
sublane address, which is what the SMEM block holds (the position times
the sublanes a row takes), and one store.

What bounds it on a v5e is neither product nor the copies but the
copying loop's scalar side: a position read from SMEM a row, ~2 cycles
each, 2.7 of a query's 5 us (PERF.md section 6, PR 53; one DMA a row
from HBM was ten times slower: 33.6 us a query).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .attention import _DTYPE_NAMES, _ROUTE_LOG, ROUTE_MULTIPLE

# Queries a grid step takes: their positions are one SMEM block (8 x
# 2,048 int32 = 64 KB, of which the pipeline holds two).
QUERY_ROWS = 8
# Rows a trip of the copying loop brings, its body that many independent
# load-store pairs; a multiple of the lane tile, and the positions a
# query reads are padded to whole trips. On a v5e the loop takes 2.11
# cycles a row at 32 rows a trip, 1.86 at 128 (PERF.md section 6, PR 53).
COPY_ROWS = 128
# What a call may hold in VMEM by `Plan.vmem_bytes`: a v5e has 128 MiB
# (`pltpu.get_tpu_info().vmem_capacity_bytes` on the chip, PR 53), the
# compiler's own temporaries come on top, and the call raises its scoped
# limit (16 MiB by default) to what it counts plus `VMEM_HEADROOM`.
VMEM_RESIDENT_BUDGET = 96 * 2**20
VMEM_HEADROOM = 16 * 2**20
# What the two SMEM blocks of positions may take.
SMEM_BUDGET = 256 * 2**10


class Plan(NamedTuple):
    """What a call pads its operands to, and what it holds: the heads
    to the dtype's sublane tile, the positions a query reads to whole
    trips of `COPY_ROWS` (the padding never counts), the 32-bit words of a
    resident row, and the columns of a row as the products take it: the
    values in whole lane tiles, then the rotated channels in whole lane
    tiles (`value_width`, `key_width` with both)."""

    heads: int
    keys: int
    lanes: int
    key_width: int
    value_width: int
    vmem_bytes: int


def _up(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def _row_layout(width: int, rank: int, itemsize: int) -> tuple[int, int, int]:
    """(`Plan.lanes`, `.key_width`, `.value_width`) of rows `width` wide
    whose first `rank` columns are the values."""
    value_width = _up(rank, ROUTE_MULTIPLE)
    key_width = value_width + _up(width - rank, ROUTE_MULTIPLE)
    return (key_width if itemsize == 4 else _up(key_width // 2, ROUTE_MULTIPLE),
            key_width, value_width)


def plan(heads: int, width: int, rank: int, k: int, rows: int, itemsize: int) -> Plan | None:
    """The padded sizes of a call of `heads` query heads `width` wide
    over `k` chosen rows of a cache of `rows` rows whose first `rank`
    columns are the values, from the shape alone. None where the kernel
    does not apply: an itemsize it has no words for, a cache that does
    not fit `VMEM_RESIDENT_BUDGET` beside a query's rows and scores, or
    more positions a query than `SMEM_BUDGET` holds two blocks of."""
    if itemsize not in (2, 4) or not 0 < rank < width or min(heads, k, rows) <= 0:
        return None
    lanes, key_width, value_width = _row_layout(width, rank, itemsize)
    heads, keys = _up(heads, 32 // itemsize), _up(k, COPY_ROWS)
    resident = _up(rows, 8) * lanes * 4
    gathered = keys * (lanes * 4 + key_width * itemsize)         # the words, and the rows again
    scores = heads * keys * (4 + 4 + itemsize)                   # scores, exponentials, probabilities
    blocks = 2 * QUERY_ROWS * (heads * (key_width + value_width) * itemsize + keys * 4)
    vmem_bytes = resident + gathered + scores + blocks
    if vmem_bytes > VMEM_RESIDENT_BUDGET or 2 * QUERY_ROWS * keys * 4 > SMEM_BUDGET:
        return None
    return Plan(heads, keys, lanes, key_width, value_width, vmem_bytes)


def route(heads: int, width: int, rank: int, k: int, rows: int, dtype) -> str:
    """"kernel" on a TPU for a bfloat16 or float32 cache whose shape
    `plan` takes, else "gathered" (`models/dsa.attend_gathered`, XLA's)."""
    dtype = jnp.dtype(dtype)
    if jax.default_backend() != "tpu" or dtype not in (jnp.bfloat16, jnp.float32):
        return "gathered"
    return "kernel" if plan(heads, width, rank, k, rows, dtype.itemsize) else "gathered"


def log_route(form: str, queries: int, rows: int, k: int, heads: int, dtype) -> None:
    """One entry in `ops/attention.route_log` a traced call: `dsa-kernel
    8192x32896 k2048 h64 bf16` (queries x cache rows, the keys a query
    reads at most, the heads, the cache's dtype), `dsa-gathered ...` or
    `dsa-masked ...`."""
    log = _ROUTE_LOG.get()
    if log is not None:
        dtype = jnp.dtype(dtype)
        log.append(f"dsa-{form} {queries}x{rows} k{k} h{heads} "
                   f"{_DTYPE_NAMES.get(dtype.name, dtype.name)}")


def table(cache: jax.Array, rank: int) -> jax.Array:
    """The cache [S, width] as the kernel holds it, for all the calls
    over one cache to share: [tiles x (S up to a multiple of 8), 128],
    row s the run of `tiles` sublanes from `tiles` x s, a lane tile of
    its 32-bit words each. A row's columns are first laid as the
    products take them (`Plan.key_width`: the `rank` values, zeros to
    whole lane tiles, the other channels, zeros to whole lane tiles). A
    float32 cache's words are those columns; a 16-bit one's are uint32,
    column j under column `lanes + j`, zeros after the last."""
    rows, width = cache.shape
    lanes, _, value_width = _row_layout(width, rank, cache.dtype.itemsize)
    wide = lanes * 4 // cache.dtype.itemsize
    laid = jnp.concatenate([
        jnp.pad(cache[:, :rank], ((0, -rows % 8), (0, value_width - rank))),
        jnp.pad(cache[:, rank:], ((0, -rows % 8), (0, wide - value_width - (width - rank)))),
    ], axis=1)
    if cache.dtype.itemsize == 2:
        bits = jax.lax.bitcast_convert_type(laid, jnp.uint16).astype(jnp.uint32)
        laid = bits[:, :lanes] | (bits[:, lanes:] << 16)
    return laid.reshape(-1, ROUTE_MULTIPLE)


def _rows_of(tiles: list, dtype, key_width: int) -> jax.Array:
    """A query's rows [k, key_width] in the cache's dtype, of the lane
    tiles [k, 128] of their words."""
    if tiles[0].dtype != jnp.uint32:
        return jnp.concatenate(tiles, axis=1)
    as_float = functools.partial(jax.lax.bitcast_convert_type, new_dtype=jnp.float32)
    low = [as_float(words << 16).astype(dtype) for words in tiles]
    high = [as_float(words & jnp.uint32(0xFFFF0000)).astype(dtype)
            for words in tiles[:key_width // ROUTE_MULTIPLE - len(tiles)]]
    return jnp.concatenate(low + high, axis=1)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def dsa_attend(q_lat: jax.Array, q_rope: jax.Array, words: jax.Array, chosen: jax.Array,
               counts: jax.Array, *, scale: float, interpret: bool = False) -> jax.Array:
    """`models/dsa.attend`'s gathered form between its two outer
    products: q_lat [T, heads, rank] (W_uk folded in) and q_rope [T,
    heads, rope] the queries, `words` the latent cache [S, rank + rope]
    as `table` lays it, `chosen` [T, k] int32 positions and `counts` [T,
    k], which of them count. Returns o_lat [T, heads, rank] in the
    queries' dtype, which is the cache's: softmax(scale q . rows, over
    the positions that count) . rows[:, :rank]; operands as stored,
    float32 scores, softmax and sums, the probabilities rounded to the
    cache's dtype before the second product.

    Grid: blocks of `QUERY_ROWS` queries, in order. The cache's words
    are one VMEM operand, whole, brought once. A step walks its
    queries: the k rows copied out of the resident words into a
    buffer, `COPY_ROWS` a trip, at the sublanes `chosen`'s SMEM block
    gives (the positions times the sublanes a row takes, so that a
    position is an address); the buffer's lane tiles unpacked; the two
    products over them. Operands are padded to `plan`'s sizes by zeros
    that change nothing (a padded position does not count, a padded
    head, query or column is dropped).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (queries, heads, rank), rope, dtype = q_lat.shape, q_rope.shape[2], q_lat.dtype
    k = chosen.shape[1]
    tiles = _row_layout(rank + rope, rank, dtype.itemsize)[0] // ROUTE_MULTIPLE
    sizes = plan(heads, rank + rope, rank, k, words.shape[0] // tiles, dtype.itemsize)
    held = jnp.uint32 if dtype.itemsize == 2 else dtype
    if (sizes is None or q_rope.dtype != dtype or words.dtype != held
            or words.shape[0] % (8 * tiles) or words.shape[1:] != (ROUTE_MULTIPLE,)):
        raise ValueError(
            f"dsa_attend: no plan for queries {q_lat.shape} + {q_rope.shape} {dtype} over {k} "
            f"rows of a cache held as {words.shape} {words.dtype}")
    total = _up(queries, QUERY_ROWS)
    rows_pad, keys_pad, heads_pad = total - queries, sizes.keys - k, sizes.heads - heads
    q_lat = jnp.pad(q_lat, ((0, rows_pad), (0, heads_pad), (0, sizes.value_width - rank)))
    q_rope = jnp.pad(
        q_rope, ((0, rows_pad), (0, heads_pad), (0, sizes.key_width - sizes.value_width - rope)))
    sublanes = jnp.pad(tiles * chosen.astype(jnp.int32), ((0, rows_pad), (0, keys_pad)))
    counts = jnp.pad(counts.astype(jnp.int32), ((0, rows_pad), (0, keys_pad)))

    def kernel(at_ref, q_lat_ref, q_rope_ref, counts_ref, words_ref, o_ref, buf):
        def query(r, carry):
            def bring(trip, carry):
                first = r * sizes.keys + trip * COPY_ROWS
                run = buf.at[pl.ds(pl.multiple_of(trip * (tiles * COPY_ROWS), 8), tiles * COPY_ROWS)]
                for j in range(COPY_ROWS):
                    run[pl.ds(tiles * j, tiles), :] = words_ref[pl.ds(at_ref[first + j], tiles), :]
                return carry

            jax.lax.fori_loop(0, sizes.keys // COPY_ROWS, bring, None)
            rows = _rows_of(
                [buf[pl.ds(j, sizes.keys, stride=tiles), :] for j in range(tiles)],
                dtype, sizes.key_width)                                   # [k, key_width]
            q = jnp.concatenate([q_lat_ref[r], q_rope_ref[r]], axis=1)
            dots = scale * jax.lax.dot_general(
                q, rows, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            dots = jnp.where(counts_ref[pl.ds(r, 1), :] != 0, dots, -jnp.inf)
            weights = jnp.exp(dots - jnp.max(dots, axis=1, keepdims=True))
            probs = (weights / jnp.sum(weights, axis=1, keepdims=True)).astype(dtype)
            o_ref[r] = jnp.dot(
                probs, rows[:, :sizes.value_width], preferred_element_type=jnp.float32,
            ).astype(o_ref.dtype)
            return carry

        jax.lax.fori_loop(0, QUERY_ROWS, query, None)

    by_queries = lambda *block: pl.BlockSpec((QUERY_ROWS, *block), lambda i: (i,) + (0,) * len(block))
    out = pl.pallas_call(
        kernel,
        grid=(total // QUERY_ROWS,),
        in_specs=[
            pl.BlockSpec((QUERY_ROWS * sizes.keys,), lambda i: (i,), memory_space=pltpu.SMEM),
            by_queries(sizes.heads, sizes.value_width),
            by_queries(sizes.heads, sizes.key_width - sizes.value_width),
            by_queries(sizes.keys),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=by_queries(sizes.heads, sizes.value_width),
        out_shape=jax.ShapeDtypeStruct((total, sizes.heads, sizes.value_width), dtype),
        scratch_shapes=[pltpu.VMEM((tiles * sizes.keys, ROUTE_MULTIPLE), words.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=sizes.vmem_bytes + VMEM_HEADROOM),
        interpret=interpret,
        name="dsa_attend",  # the kernel's name in a device trace
    )(sublanes.reshape(-1), q_lat, q_rope, counts, words)
    return out[:queries, :heads, :rank]
