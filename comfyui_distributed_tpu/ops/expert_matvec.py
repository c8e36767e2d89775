"""A decode step's grouped product, each chosen expert's weights read
once where they lie.

A decode step of a model with a mixture of experts multiplies a tile or
fewer rows (its `tokens x k` token-expert pairs, sorted by held expert)
with the stacked weights `[held, K, N]` of the experts this chip holds:
`jax.lax.ragged_dot(rows, weights, sizes)`. Left to XLA on a TPU that is
the compiler's own grouped kernel (`ragged-dot`) at a row count that is
a multiple of the sublane tile of 8 and, off it (DeepSeek-V2's 6 rows),
a product with every held expert under a mask, 515 GB/s (PERF.md §6,
PR 42). `expert_matvec` is the one pass the bytes ask for, as a Pallas
kernel: the stack stays in HBM, the ids of the experts the step's rows
chose are scalar-prefetched, and the kernel walks (chosen expert, block
of columns) in one loop of exactly that many trips, fetching a `[K,
columns]` block into one of two VMEM buffers while it multiplies the
other with all of the step's rows. An expert nobody chose is not
touched, one that two rows share is fetched once, and a step with no
held pair fetches nothing and returns zeros. Elsewhere `ragged_dot`
stays (`expert_matvec_route`).

Two things an expert layer may ask besides. An `N` off the lane tile
(Nemotron-H's experts of 1,856 columns: 14.5 tiles) has no column block
of whole tiles, a slice of an array's last axis has to be whole tiles,
and the device's own layout of such an array puts the tiled axis last;
so that projection is stored out by in, `[groups, N, K]` (`out_major`),
and walked by blocks of its rows, `[rows of N, K]`: one contiguous run
of HBM a block, multiplied with the step's rows over the last axis of
both, each block's product final as on the walk by columns. And a
stack of layers `[layers, groups, ...]` that a `lax.scan` walks is
taken whole with the layer's index (a custom call's operand cannot be
a slice of an array: XLA would copy the layer's 160 MB out of the
stack first); the index is prefetched beside the ids.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .attention import ROUTE_MULTIPLE, VMEM_BUDGET

# The most rows the kernel takes: every chosen expert's block is
# multiplied with all of them (a decode step's pairs cost the MXU the
# same 8 or 16 sublanes whichever expert they chose), which is free only
# while they are few. `models/moe.ROW_TILE` is the caller's own bound: a
# ladder of one rung.
MAX_ROWS = 256
# What a block of columns should at least hold, as `ops/decode_attention`
# has it: from about a megabyte on a trip's fixed cost hides behind its
# own DMA, and the smaller the block, the less waits at a call's two
# ends (the first block's fetch, the last block's product).
MIN_BLOCK_BYTES = 2**20


def matvec_vmem_bytes(rows: int, k: int, n: int, block_n: int, itemsize: int) -> int:
    """VMEM one call holds: the two weight buffers, the padded rows, the
    whole output, and a block's float32 product."""
    return 2 * k * block_n * itemsize + rows * (k + n) * itemsize + rows * block_n * 4


def matvec_plan(rows: int, k: int, n: int, itemsize: int,
                out_major: bool = False) -> tuple[int, int] | None:
    """(padded rows, columns a block takes) for `rows` [rows, k] against
    weights [groups, k, n]: the rows padded to the dtype's sublane tile
    (8 float32, 16 bfloat16), the fewest columns, of `n`'s divisors that
    are multiples of the lane tile, whose `[k, columns]` block reaches
    `MIN_BLOCK_BYTES` (all of `n` where none does). Against weights
    stored out by in, [groups, n, k] (`out_major`), the block is
    `[columns, k]`, rows of the stored array: `n`'s divisors that are
    multiples of the sublane tile then, and `k` whole lane tiles. None
    where the kernel does not apply: more rows than `MAX_ROWS`, a `k`
    off the sublane tile or an `n` off the lane tile (out by in: a `k`
    off the lane tile or an `n` off the sublane tile), an itemsize it
    has no tile for, or buffers that do not fit `VMEM_BUDGET`."""
    sublanes = {4: 8, 2: 16}.get(itemsize)
    k_tile, n_tile = (ROUTE_MULTIPLE, sublanes) if out_major else (sublanes, ROUTE_MULTIPLE)
    if sublanes is None or not 0 < rows <= MAX_ROWS or k % k_tile or n % n_tile:
        return None
    padded = -(-rows // sublanes) * sublanes
    block_n = next(
        (b for b in range(n_tile, n, n_tile)
         if n % b == 0 and k * b * itemsize >= MIN_BLOCK_BYTES),
        n,
    )
    if matvec_vmem_bytes(padded, k, n, block_n, itemsize) > VMEM_BUDGET:
        return None
    return padded, block_n


def expert_matvec_route(rows: int, k: int, n: int, dtype, out_major: bool = False) -> str:
    """"kernel" on a TPU for a shape `matvec_plan` takes, else "xla"
    (`jax.lax.ragged_dot`)."""
    if jax.default_backend() != "tpu":
        return "xla"
    return "kernel" if matvec_plan(rows, k, n, jnp.dtype(dtype).itemsize, out_major) else "xla"


# `jax.lax.ragged_dot` against weights stored out by in, [groups, N, K]
OUT_MAJOR = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((1,), (2,)), ((), ())), lhs_ragged_dimensions=[0],
    rhs_group_dimensions=[0])


def grouped_xla(rows: jax.Array, weights: jax.Array, sizes: jax.Array, layer=None, *,
                out_major: bool = False) -> jax.Array:
    """`expert_matvec`'s arguments on XLA's own grouped product, for any
    number of rows: `jax.lax.ragged_dot` over `weights` [groups, K, N]
    (out by in: [groups, N, K]), of a stack of layers the layer `layer`."""
    if layer is not None:
        weights = jax.lax.dynamic_index_in_dim(weights, layer, keepdims=False)
    if out_major:
        return jax.lax.ragged_dot_general(rows, weights, sizes, OUT_MAJOR)
    return jax.lax.ragged_dot(rows, weights, sizes)


def chosen_groups(sizes: jax.Array, most: int) -> jax.Array:
    """From the rows on each group `sizes` [groups], what the kernel
    prefetches, as one int32 array [1 + 3 x most]: how many groups have
    rows, then their ids ascending (at most `most`: the row count holds
    them all), the first row of each, and the row count of each. Entries
    past the count are zeros and never read. One comparison of every
    group's rank among the chosen with every entry: no sort and no
    scatter for a few dozen numbers."""
    sizes = sizes.astype(jnp.int32)
    chosen = sizes > 0
    rank = jnp.cumsum(chosen) - 1
    entry = chosen[None, :] & (rank[None, :] == jnp.arange(most)[:, None])   # [most, groups]
    pick = lambda values: jnp.sum(jnp.where(entry, values[None, :], 0), axis=1, dtype=jnp.int32)
    count = jnp.minimum(chosen.sum(dtype=jnp.int32), most)
    return jnp.concatenate([
        count[None], pick(jnp.arange(sizes.shape[0])), pick(jnp.cumsum(sizes) - sizes),
        pick(sizes)])


@functools.partial(jax.jit, static_argnames=("out_major", "interpret"))
def expert_matvec(rows: jax.Array, weights: jax.Array, sizes: jax.Array, layer=None, *,
                  out_major: bool = False, interpret: bool = False) -> jax.Array:
    """`jax.lax.ragged_dot(rows, weights, sizes)` for a tile or fewer
    rows: rows [R, K] sorted by group, weights [groups, K, N] (stored
    out by in, `out_major`: [groups, N, K]), `sizes` [groups] the rows
    on each group in that order; a row past `sizes.sum()` comes back
    zero. With `layer` (a traced scalar) the weights are a stack
    [layers, groups, ...] of which that layer is read. Operands as they
    are stored, float32 accumulation, the result [R, N] in the rows'
    dtype.

    One grid step; the weights are left in HBM. The kernel's loop runs
    `chosen groups x (N / block)` trips: trip s waits for its block
    `weights[group, :, block]` (out by in: `weights[group, block, :]`)
    in buffer s mod 2, has started trip s + 1's copy into the other
    before that, multiplies all rows with the block and keeps the result
    for the rows of that group. The output is held in VMEM as `[N /
    block, rows, block]`, a block an index of the leading axis, and put
    in order by the wrapper (a few kilobytes).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, k = rows.shape
    stacked = layer is not None
    groups, width_k, n = weights.shape[1 if stacked else 0:]
    if out_major:
        width_k, n = n, width_k
    plan = matvec_plan(r, k, n, weights.dtype.itemsize, out_major)
    if plan is None or width_k != k or rows.dtype != weights.dtype:
        raise ValueError(
            f"expert_matvec: no plan for rows {rows.shape} {rows.dtype} over "
            f"weights {weights.shape} {weights.dtype}")
    padded, block_n = plan
    blocks = n // block_n

    most = min(r, groups)
    contract = (((1,), (1 if out_major else 0,)), ((), ()))

    def kernel(chosen_ref, *refs):
        layer_ref, (x_ref, w_ref, o_ref, buf, sem) = (
            (refs[0], refs[1:]) if stacked else (None, refs))
        trips = chosen_ref[0] * blocks

        def copy(s, slot):
            g, b = s // blocks, s % blocks
            block = pl.ds(pl.multiple_of(b * block_n, block_n), block_n)
            at = (chosen_ref[1 + g], block, slice(None)) if out_major else (
                chosen_ref[1 + g], slice(None), block)
            if stacked:
                at = (layer_ref[0], *at)
            return pltpu.make_async_copy(w_ref.at[at], buf.at[slot], sem.at[slot])

        @pl.when(trips > 0)
        def _():
            copy(0, 0).start()

        o_ref[...] = jnp.zeros_like(o_ref)
        row = jax.lax.broadcasted_iota(jnp.int32, (padded, 1), 0)

        def trip(s, carry):
            slot = s % 2

            @pl.when(s + 1 < trips)
            def _():
                copy(s + 1, 1 - slot).start()

            copy(s, slot).wait()
            g, b = s // blocks, s % blocks
            product = jax.lax.dot_general(
                x_ref[...], buf[slot], contract, preferred_element_type=jnp.float32)
            first = chosen_ref[1 + most + g]
            mine = (row >= first) & (row < first + chosen_ref[1 + 2 * most + g])
            o_ref[b] = jnp.where(mine, product.astype(o_ref.dtype), o_ref[b])
            return carry

        jax.lax.fori_loop(0, trips, trip, None)

    prefetched = [chosen_groups(sizes, most)]
    if stacked:
        prefetched.append(jnp.asarray(layer, jnp.int32).reshape(1))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetched),
            grid=(1,),
            in_specs=[
                pl.BlockSpec((padded, k), lambda i, *_: (0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((blocks, padded, block_n), lambda i, *_: (0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, block_n, k) if out_major else (2, k, block_n), weights.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((blocks, padded, block_n), rows.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="expert_matvec",  # the kernel's name in a device trace
    )(*prefetched, jnp.pad(rows, ((0, padded - r), (0, 0))), weights)
    return out.swapaxes(0, 1).reshape(padded, n)[:r]
