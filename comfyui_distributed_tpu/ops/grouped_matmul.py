"""A prefill's grouped product: row tiles walked group by group, an
expert's columns read once a sweep.

An expert layer over a prefill (or a prefill's part or block) multiplies
thousands of rows, sorted by held expert, with the stacked weights
`[held, K, N]` of the experts this chip holds:
`jax.lax.ragged_dot(rows, weights, sizes)`. Left to XLA on a TPU that is
the compiler's own grouped kernel (`ragged-dot`), which streams at the
HBM's rate where every group's rows fit one of its row tiles
(LongCat-Flash's 8 groups of ~34 rows: 604 MB in 0.77 ms) and falls to
200-300 GB/s, a quarter of the MXU's peak, at 77-512 rows a group over
16-40 groups (PERF.md §6, PR 40, PR 64). `grouped_matmul` is the walk
the two floors ask for, as `jax.experimental.pallas.ops.tpu.megablox`
has it, as one Pallas kernel: the column tiles outermost; inside a sweep
the (row tile, group) pairs in row order from a prefetched table, a tile
that straddles groups visited once a group under a row mask; an expert's
`[K, columns]` block is fetched when the group changes and not before,
so once a sweep however many row tiles its group has; K whole (2,048 to
6,144 here), float32 accumulation over all of it, rounded once.

A row tile is `Plan.tile` rows in `Plan.rows`-row blocks: a visit
multiplies only the blocks in which its group has rows, so a group is
padded to the blocks it touches (128 or 256 rows and not more) while a
grid step and its copies cover a whole tile. Rows past `sizes.sum()` are
an absent chip's pairs: their tiles are not read and not multiplied; the
steps the static grid has left over (a grid of tiles + groups - 1 steps
holds every visit) write zeros there, so that what a caller masks is
finite. A stack of layers `[layers, groups, ...]` is taken whole with
the layer's index, prefetched beside the table and read in the weight's
`index_map`: the layer's block is copied from where it lies. Weights
stored out by in, `[groups, N, K]` (`out_major`: Nemotron-H's `w_up`),
are walked by blocks of their rows and multiplied over the last axis of
both.

`route` says which calls take it, from the shapes and the backend alone;
`grouped_rows` is what `models/moe.expert_layer` calls.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .attention import _DTYPE_NAMES, _ROUTE_LOG, ROUTE_MULTIPLE
from .expert_matvec import MAX_ROWS, grouped_xla

# Rows a block of a row tile has: what a visit pads a group to. The MXU
# takes a weight tile for every 128 rows that stream through it whatever
# the block; at 256 the padding of a group of ~256 rows (two blocks and
# a straddled third) costs more than the second pass saves (PERF.md §6,
# PR 64: the leg's sweep).
BLOCK_ROWS = 128
# Rows a grid step covers, at most: a step's fixed cost (0.35 us) and its
# row copy are paid once a tile.
TILE_ROWS = 512
# What a call's blocks may take of a v5e's 128 MiB of VMEM (two buffers
# each of a row tile, a weight block and an output tile, and a block's
# float32 product); the call raises its scoped limit (16 MiB by default)
# to what it counts plus `VMEM_HEADROOM`, as `ops/dsa_attend` does.
VMEM_BLOCK_BUDGET = 40 * 2**20
VMEM_HEADROOM = 16 * 2**20


class Plan(NamedTuple):
    """A call's tiles: `tile` rows a grid step in blocks of `rows`,
    `columns` of N a sweep, and the VMEM that takes."""

    tile: int
    rows: int
    columns: int
    vmem_bytes: int


def vmem_bytes(tile: int, rows: int, k: int, columns: int, itemsize: int) -> int:
    """VMEM one call holds: two buffers each of the row tile, the weight
    block and the output tile, and two blocks' float32 products."""
    return 2 * (tile * k + k * columns + tile * columns) * itemsize + 2 * rows * columns * 4


def plan(rows: int, k: int, n: int, groups: int, itemsize: int,
         out_major: bool = False) -> Plan | None:
    """The tiles of `rows` [rows, k] against weights [groups, k, n] (out
    by in: [groups, n, k]), from the shape alone: a row tile of
    `TILE_ROWS` (one of `BLOCK_ROWS` where the rows are no more), and
    the most columns a sweep, of `n`'s divisors that are whole lane
    tiles, that fit `VMEM_BLOCK_BUDGET` with K whole: the rows are read
    once a sweep, so the fewer sweeps the better. An `n` off the lane
    tile is one sweep or nothing (a block's last axis is whole lane
    tiles or the whole axis). None where the kernel does not apply: a
    tile or fewer rows (`ops/expert_matvec`'s), no group, an itemsize it
    has no tile for, a `k` off the sublane tile, or a `k` so long that
    not even one lane tile of columns fits."""
    tile_rows, block_rows, budget = TILE_ROWS, BLOCK_ROWS, VMEM_BLOCK_BUDGET
    sublanes = {4: 8, 2: 16}.get(itemsize)
    if sublanes is None or rows <= MAX_ROWS or groups <= 0 or k <= 0 or k % sublanes or n <= 0:
        return None
    tile = tile_rows if rows > tile_rows else -(-rows // block_rows) * block_rows
    widths = [n] if n % ROUTE_MULTIPLE else [
        c for c in range(n, 0, -ROUTE_MULTIPLE) if n % c == 0]
    for columns in widths:
        held = vmem_bytes(tile, block_rows, k, columns, itemsize)
        if held <= budget:
            return Plan(tile, block_rows, columns, held)
    return None


def route(rows: int, k: int, n: int, groups: int, dtype, out_major: bool = False) -> str:
    """"kernel" on a TPU for more than a tile of bfloat16 or float32
    rows whose shape `plan` takes, else "xla" (`jax.lax.ragged_dot`): a
    function of the shapes and the backend, nothing else. No table of
    shapes beside it: between the row gather and the way back the kernel
    won by 23 % to 2.7 x at every rung of every served model it has a
    plan for (`chip_smoke.py --legs experts`; PERF.md §6, PR 64)."""
    dtype = jnp.dtype(dtype)
    if jax.default_backend() != "tpu" or dtype not in (jnp.bfloat16, jnp.float32):
        return "xla"
    return "xla" if plan(rows, k, n, groups, dtype.itemsize, out_major) is None else "kernel"


def log_route(form: str, rows: int, k: int, n: int, groups: int, dtype,
              out_major: bool = False) -> None:
    """One entry in `ops/attention.route_log` a traced call: `gmm-kernel
    8192x5120x3072 g32 bf16` (rows x K x N, the groups, the operands'
    dtype; `out-major` after it for weights stored out by in) or
    `gmm-xla ...`."""
    log = _ROUTE_LOG.get()
    if log is not None:
        name = jnp.dtype(dtype).name
        log.append(f"gmm-{form} {rows}x{k}x{n} g{groups} {_DTYPE_NAMES.get(name, name)}"
                   + " out-major" * out_major)


def rung_route(lowest: int, rows: int, k: int, n: int, groups: int, dtype,
               out_major: bool = False) -> str:
    """Where a rung of `rows` rows goes in a ladder whose lowest rung
    (the even routing's share) is `lowest`: where `route` sends its
    shape if it is the lowest, else "xla". Every rung is a call site of
    its own under the layer's `lax.switch`, and every traced kernel call
    costs a cached start (45 ms of tracing and lowering here, four times
    that on the chip's host; Nemotron-H's runs of layers are stacks of
    four lengths, so sixteen distinct calls with two rungs): with the
    lowest two rungs on the kernel Nemotron's cached `setup_s` rose 4.7 %
    (PERF.md §6, PR 64), so the kernel takes the one rung an evenly
    routed request's layers land on about half the time and the rungs
    above keep `ragged_dot`; where the lowest is a tile or less
    (LongCat-Flash's blocks of 1,024 tokens) the whole ladder keeps it
    (docs/performance.md, "The prefill's grouped products"). The one
    place the rule lives: `grouped_rows` asks while a layer is traced,
    `models/moe.prefill_route` for a model's `report`."""
    if lowest <= MAX_ROWS or rows > lowest:
        return "xla"
    return route(rows, k, n, groups, dtype, out_major)


def grouped_rows(lowest: int):
    """The grouped product of an expert layer's rungs, with
    `expert_matvec`'s arguments, for a ladder whose lowest rung is
    `lowest` rows: each rung goes where `rung_route` sends it (operands
    of two dtypes to `grouped_xla`), an entry in the route log a call."""

    def grouped(rows: jax.Array, weights: jax.Array, sizes: jax.Array, layer=None, *,
                out_major: bool = False) -> jax.Array:
        count, k = rows.shape
        groups, n = weights.shape[-3], weights.shape[-2 if out_major else -1]
        how = "xla"
        if rows.dtype == weights.dtype:
            how = rung_route(lowest, count, k, n, groups, rows.dtype, out_major)
        if count > MAX_ROWS:
            log_route(how, count, k, n, groups, rows.dtype, out_major)
        product = grouped_matmul if how == "kernel" else grouped_xla
        return product(rows, weights, sizes, layer, out_major=out_major)

    return grouped


def visits(sizes: jax.Array, rows: int, tile: int) -> jax.Array:
    """What the kernel prefetches, as one int32 array [6 x steps] of
    `steps = tiles + groups - 1` grid steps: a step's row tile, its
    group, the first row of that group and the row after its last,
    whether the step is the first on its tile, and the tile of rows it
    reads. The (tile, group) pairs that share rows come first, in row
    order; then a step each for the tiles past the last row of any
    group, whose range of rows is empty (the kernel writes zeros there);
    the steps left over stay on the last tile and do nothing. Steps that
    multiply nothing keep the last group that has rows and the last tile
    of rows that was read, so nothing is fetched for them."""
    groups, tiles = sizes.shape[0], -(-rows // tile)
    steps = tiles + groups - 1
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    has = sizes > 0
    first_tile = starts // tile
    on = jnp.where(has, (ends - 1) // tile - first_tile + 1, 0)       # a group's visits
    upto = jnp.cumsum(on)
    step = jnp.arange(steps, dtype=jnp.int32)
    visiting = step < upto[-1]
    last = jnp.max(jnp.where(has, jnp.arange(groups, dtype=jnp.int32), 0))
    group = jnp.where(
        visiting, jnp.sum(step[:, None] >= upto[None, :], axis=1, dtype=jnp.int32), last)
    touched = -(-ends[-1] // tile)
    at = jnp.where(
        visiting, first_tile[group] + step - (upto - on)[group],
        jnp.minimum(touched + step - upto[-1], tiles - 1))
    first = jnp.concatenate([jnp.ones((1,), bool), at[1:] != at[:-1]])
    return jnp.concatenate([
        at, group, jnp.where(visiting, starts[group], 0), jnp.where(visiting, ends[group], 0),
        first.astype(jnp.int32), jnp.where(visiting, at, jnp.maximum(touched - 1, 0)),
    ]).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("out_major", "interpret"))
def grouped_matmul(rows: jax.Array, weights: jax.Array, sizes: jax.Array, layer=None, *,
                   out_major: bool = False, interpret: bool = False) -> jax.Array:
    """`jax.lax.ragged_dot(rows, weights, sizes)` for more than a tile of
    rows: rows [R, K] sorted by group, weights [groups, K, N] (stored out
    by in, `out_major`: [groups, N, K]), `sizes` [groups] the rows on
    each group in that order; a row past `sizes.sum()` comes back zero.
    With `layer` (a traced scalar) the weights are a stack [layers,
    groups, ...] of which that layer is read. Operands as they are
    stored, float32 accumulation over all of K, the result [R, N] in the
    rows' dtype.

    Grid: (N / columns, tiles + groups - 1), the sweeps outermost. A
    step's blocks come from `visits`' table: the row tile and the `[K,
    columns]` block of the step's group (each fetched only when it
    differs from the step before's), the output tile, which stays in
    VMEM while consecutive steps share it. The step multiplies each block of its
    tile in which its group has rows and keeps the product for that
    group's rows; the first step on a tile puts zeros everywhere else.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, k = rows.shape
    stacked = layer is not None
    groups, width_k, n = weights.shape[1 if stacked else 0:]
    if out_major:
        width_k, n = n, width_k
    taken = plan(r, k, n, groups, weights.dtype.itemsize, out_major)
    if taken is None or width_k != k or rows.dtype != weights.dtype:
        raise ValueError(
            f"grouped_matmul: no plan for rows {rows.shape} {rows.dtype} over "
            f"weights {weights.shape} {weights.dtype}")
    tile, block, columns, held = taken
    steps = -(-r // tile) + groups - 1
    contract = (((1,), (1 if out_major else 0,)), ((), ()))

    def kernel(table_ref, *refs):
        x_ref, w_ref, o_ref = refs[-3:]
        s = pl.program_id(1)
        base = table_ref[s] * tile
        low, high = table_ref[2 * steps + s], table_ref[3 * steps + s]
        first = table_ref[4 * steps + s] == 1
        for b in range(tile // block):
            start = base + b * block
            mine = (low < start + block) & (high > start)
            at = pl.ds(b * block, block)

            @pl.when(mine)
            def _():
                product = jax.lax.dot_general(
                    x_ref[at, :], w_ref[...], contract, preferred_element_type=jnp.float32)
                row = start + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
                before = jnp.where(first, jnp.zeros_like(o_ref[at, :]), o_ref[at, :])
                o_ref[at, :] = jnp.where(
                    (row >= low) & (row < high), product.astype(o_ref.dtype), before)

            @pl.when(first & ~mine)
            def _():
                o_ref[at, :] = jnp.zeros_like(o_ref[at, :])

    def weight_block(j, s, table_ref, *layer_ref):
        at = (table_ref[steps + s], j, 0) if out_major else (table_ref[steps + s], 0, j)
        return (layer_ref[0][0], *at) if stacked else at

    prefetched = [visits(sizes, r, tile)]
    if stacked:
        prefetched.append(jnp.asarray(layer, jnp.int32).reshape(1))
    lead = (None,) * (2 if stacked else 1)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetched),
            grid=(-(-n // columns), steps),
            in_specs=[
                pl.BlockSpec((tile, k), lambda j, s, table_ref, *_: (table_ref[5 * steps + s], 0)),
                pl.BlockSpec((*lead, columns, k) if out_major else (*lead, k, columns),
                             weight_block),
            ],
            out_specs=pl.BlockSpec((tile, columns), lambda j, s, table_ref, *_: (table_ref[s], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((r, n), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=held + VMEM_HEADROOM),
        interpret=interpret,
        name="grouped_matmul",  # the kernel's name in a device trace
    )(*prefetched, rows, weights)
