"""A prefill's chunked Mamba-2 scan, a chunk's Q x Q weights formed and
used in VMEM and the matrix state held there from the first chunk to
the last.

`models/mamba2.ssd_chunked_xla` forms, for every chunk and head at once,
the float32 matrix of decay ratios exp(L_t - L_s), multiplies it by the
steps and by the group's scores C_t . B_s, rounds it, and only then
hands it to the product with u: on a TPU each of those passes is a
fusion that reads and writes `[chunks, H, Q, Q]` through HBM (537 MB a
pass at granite-4.0-h-micro's 32 x 64 x 256 x 256; PERF.md §6, PR 55),
for a product whose operands are 70 MB. `ssd_chunk` is the same
arithmetic as one Pallas kernel: the grid walks (block of heads, chunk),
the chunk axis in order; a step takes its heads' u as one `[Q, heads a
step x P]` block (a head is a run of P lanes, which is how the callers
hold u), forms the group's scores once and each head's weights a block
of rows at a time, and multiplies them by u there. The state S, held
transposed `[N, heads a step x P]` float32, stays in VMEM across the
chunks, so that what the tokens read of the state that entered their
chunk (C S) and what the chunk adds to it (B^T of the stepped u) are one
wide product each for all the step's heads. Elsewhere the XLA form stays
(`ssd_route`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .attention import _DTYPE_NAMES, _ROUTE_LOG, ROUTE_MULTIPLE, VMEM_BUDGET

# Rows of a chunk whose weights are formed and multiplied at once: a
# lane tile, so that a block of rows reads only the columns up to its
# own (the blocks above the diagonal are never formed).
ROWS = ROUTE_MULTIPLE
# The most heads a grid step takes (all of one group).
MAX_HEADS = 8


def chunk_vmem_bytes(block: int, width: int, n: int, chunk: int, itemsize: int) -> int:
    """VMEM one grid step holds: the u and y blocks and B and C (double-
    buffered by the pipeline), the steps and their running sums a row a
    head, the state coming in and the resident state going out, the
    scores and B^T, and a lane tile's float32 terms (what the tokens
    read of the entering state, the two scalings, u and the stepped u)
    beside a block of rows' weights of the tile's heads."""
    lanes, tile = block * width, max(width, ROUTE_MULTIPLE)
    blocks = 2 * chunk * (lanes * (itemsize + 4) + 2 * n * itemsize)
    rows = 2 * 2 * max(block, 8) * chunk * 4
    states = 4 * n * lanes * 4
    terms = chunk * (chunk * 4 + n * itemsize) + 5 * chunk * tile * 4 + 4 * ROWS * chunk * 4
    return blocks + rows + states + terms


def tiles_a_group(block: int, per: int, width: int) -> bool:
    """Whether steps of `block` heads walk a group's `per` heads in whole
    steps of whole lane tiles."""
    return per % block == 0 and block * width % ROUTE_MULTIPLE == 0


def chunk_plan(heads: int, width: int, groups: int, n: int, chunk: int,
               itemsize: int) -> int | None:
    """Heads a grid step takes, all of one group: the largest count up
    to `MAX_HEADS` that divides a group's heads into whole lane tiles
    and fits `VMEM_BUDGET`. None where the kernel does not apply: heads
    that are no whole groups, a head width that is neither half a lane
    tile nor whole ones, a state or a chunk off the lane tile, no such
    count, or an itemsize it has no tile for."""
    if groups <= 0 or heads % groups or itemsize not in (2, 4):
        return None
    if width <= 0 or not (2 * width == ROUTE_MULTIPLE or width % ROUTE_MULTIPLE == 0):
        return None
    if n <= 0 or n % ROUTE_MULTIPLE or chunk <= 0 or chunk % ROWS:
        return None
    per = heads // groups
    for block in range(min(per, MAX_HEADS), 0, -1):
        if tiles_a_group(block, per, width) and chunk_vmem_bytes(
                block, width, n, chunk, itemsize) <= VMEM_BUDGET:
            return block
    return None


def ssd_route(heads: int, width: int, groups: int, n: int, chunk: int, dtype) -> str:
    """"kernel" on a TPU for a shape `chunk_plan` takes, else "xla"
    (`models/mamba2.ssd_chunked_xla`)."""
    if jax.default_backend() != "tpu":
        return "xla"
    plan = chunk_plan(heads, width, groups, n, chunk, jnp.dtype(dtype).itemsize)
    return "kernel" if plan else "xla"


def log_route(form: str, tokens: int, heads: int, width: int, groups: int, n: int,
              chunk: int, dtype) -> None:
    """One entry in `ops/attention.route_log` a traced call: `ssd-kernel
    8192x64x64 g1 n128 c256 hb8 bf16` (tokens x heads x width, the
    groups, the state, the chunk, the heads a grid step takes, the
    storage dtype) or `ssd-xla 8192x64x64 g1 n128 c256 bf16`."""
    log = _ROUTE_LOG.get()
    if log is not None:
        dtype = jnp.dtype(dtype)
        plan = chunk_plan(heads, width, groups, n, chunk, dtype.itemsize)
        step = f" hb{plan}" if form == "kernel" else ""
        log.append(f"ssd-{form} {tokens}x{heads}x{width} g{groups} n{n} c{chunk}{step} "
                   f"{_DTYPE_NAMES.get(dtype.name, dtype.name)}")


def _down_the_rows(rows, width: int):
    """[Q, a lane tile or a head's whole tiles] float32 from one `[1,
    Q]` row a head: entry t of a head's row over that head's run of
    `width` lanes of row t. The rows are laid one under the other, each
    over `width` sublanes, and the tile transposed: what a step reads of
    its heads' steps and decays comes by row alone (a `[T, heads a
    step]` array would lie in HBM a lane tile wide)."""
    span = min(width, ROUTE_MULTIPLE)
    stacked = [jnp.broadcast_to(row, (span, row.shape[1])) for row in rows]
    down = (jnp.concatenate(stacked, axis=0) if len(stacked) > 1 else stacked[0]).T
    return jnp.concatenate([down] * (width // span), axis=1) if width > span else down


def _chunk_terms(u_ref, b, c, decay_ref, step_ref, held_ref, y_ref, live, dtype, width: int):
    """A grid step's heads, one chunk against the states before it:
    `u_ref` [Q, heads x P], b and c [Q, N] in the storage dtype, the
    steps D and their running sums L (times A) `[heads, Q]` float32,
    `held_ref` [N, heads x P] float32 the states transposed, which it
    updates; writes y [Q, heads x P] float32. `live` [Q, 1] says which
    rows of u, b and c hold a token (None: all). A lane tile of u at a time
    (two heads of half a tile, or a head's whole tiles), so that no term
    is wider than that. `models/mamba2.ssd_chunked_xla` has the algebra,
    and this its order of operations and its roundings."""
    chunk, lanes = u_ref.shape
    tile = max(width, ROUTE_MULTIPLE)      # lanes of u a product takes
    together = tile // width               # heads in them
    last_dims = (((1,), (1,)), ((), ()))   # a @ b.T without the transpose
    # what a block holds past the last token is undefined: such a token changes nothing
    held_to = (lambda x: x) if live is None else (lambda x: jnp.where(live, x, jnp.zeros_like(x)))
    b, c = held_to(b), held_to(c)

    # scores C_t . B_s of the group; B^T for what the chunk adds to the states
    scores = jax.lax.dot_general(c, b, last_dims, preferred_element_type=jnp.float32)
    b_t = b.T
    decay = decay_ref[...]
    into_row = jnp.exp(decay)                                                # from its start
    out_of_row = jnp.exp(decay[:, chunk - 1:chunk] - decay) * step_ref[...]  # to its end
    diagonal = (jax.lax.broadcasted_iota(jnp.int32, (ROWS, ROWS), 0)
                >= jax.lax.broadcasted_iota(jnp.int32, (ROWS, ROWS), 1))
    first = jax.lax.broadcasted_iota(jnp.int32, (ROWS, tile), 1) < width
    for t in range(lanes // tile):
        at = slice(t * tile, (t + 1) * tile)
        heads = range(t * together, (t + 1) * together)
        u = held_to(u_ref[:, at])
        held = held_ref[:, at]
        # what each token reads of the entering states, and what the chunk adds to them
        into = _down_the_rows([into_row[j:j + 1] for j in heads], width)
        out_of = _down_the_rows([out_of_row[j:j + 1] for j in heads], width)
        read = into * jnp.dot(c, held.astype(dtype), preferred_element_type=jnp.float32)
        stepped = (u.astype(jnp.float32) * out_of).astype(dtype)
        held_ref[:, at] = into[chunk - 1:chunk, :] * held + jnp.dot(
            b_t, stepped, preferred_element_type=jnp.float32)
        # the chunk's own part: the scores decayed and stepped a head, a block of
        # rows against the columns up to its own, the mask on the diagonal block only
        for r in range(chunk // ROWS):
            rows = slice(r * ROWS, (r + 1) * ROWS)
            weights = []
            for j in heads:
                since = _down_the_rows([decay_ref[j:j + 1, rows]], ROWS)
                blocks = []
                for k in range(r + 1):
                    cols = slice(k * ROWS, (k + 1) * ROWS)
                    gap = since - decay_ref[j:j + 1, cols]
                    ratio = jnp.exp(gap if k < r else jnp.where(diagonal, gap, -jnp.inf))
                    blocks.append(
                        ((ratio * step_ref[j:j + 1, cols]) * scores[rows, cols]).astype(dtype))
                weights.append(jnp.concatenate(blocks, axis=1) if r else blocks[0])
            # the heads of one lane tile of u in one product, a head a block of rows
            own = jnp.dot(
                jnp.concatenate(weights, axis=0) if together > 1 else weights[0],
                u[:(r + 1) * ROWS], preferred_element_type=jnp.float32)
            if together > 1:
                own = jnp.where(first, own[:ROWS], own[ROWS:])
            y_ref[rows, at] = own + read[rows]


@functools.partial(jax.jit, static_argnames=("chunk", "block", "interpret"))
def ssd_chunk(u, b, c, step, a, state, *, chunk: int, block: int | None = None,
              interpret: bool = False):
    """`models/mamba2.ssd_chunked` as one kernel: u [T, H, P], b and c
    [T, G, N] in the storage dtype, `step` [T, H] and `a` [H] float32,
    `state` [H, P, N] float32. Returns (y [T, H, P] float32, without the
    skip, and the state after the last token). `block` (heads a grid
    step; `chunk_plan`'s where None) divides a group's heads.

    Grid: (H / block, chunks), the chunk axis in order. The steps and
    their running sums are formed here by XLA as the XLA form has them
    (2 MB), a row a head and chunk. The state's output block has the
    same index at every chunk of a head block, so it stays in VMEM:
    loaded from `state` at chunk 0, updated a chunk, written to HBM
    once; the wrapper's two transpositions of it are of a few megabytes.
    Rows past T in a short last chunk are tokens that change nothing (u,
    B, C 0 by a select: what a block holds there is undefined; a step 0).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tokens, heads, width = u.shape
    groups, n = b.shape[1:]
    dtype = u.dtype
    plan = chunk_plan(heads, width, groups, n, chunk, dtype.itemsize)
    if plan is None:
        raise ValueError(
            f"ssd_chunk: no plan for {u.shape} {dtype}, B {b.shape}, in chunks of {chunk}")
    block = block or plan
    per = heads // groups
    if not tiles_a_group(block, per, width):
        raise ValueError(f"ssd_chunk: {block} heads a step do not tile {per} heads a group")
    head_blocks, blocks_a_group = heads // block, per // block
    count = -(-tokens // chunk)
    short = tokens % chunk

    step = jnp.pad(step, ((0, count * chunk - tokens), (0, 0)))
    by_row = jnp.moveaxis(step.reshape(count, chunk, heads), 1, 2)       # [chunks, H, Q]
    decay = jnp.cumsum(by_row * a[None, :, None], axis=-1)               # L, never increasing
    rows = lambda t: t.reshape(count, head_blocks, block, chunk)

    def kernel(u_ref, b_ref, c_ref, decay_ref, step_ref, state_ref, y_ref, held_ref):
        at = pl.program_id(1)

        @pl.when(at == 0)
        def _():
            held_ref[...] = state_ref[...]

        live = (jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) < tokens - at * chunk
                if short else None)
        _chunk_terms(u_ref, b_ref[...], c_ref[...], decay_ref, step_ref, held_ref, y_ref, live,
                     dtype, width)

    lanes = block * width
    tokens_by_heads = pl.BlockSpec((chunk, lanes), lambda i, at: (at, i))
    of_the_group = pl.BlockSpec((chunk, n), lambda i, at: (at, i // blocks_a_group))
    by_rows = pl.BlockSpec((None, None, block, chunk), lambda i, at: (at, i, 0, 0))
    state_spec = pl.BlockSpec((n, lanes), lambda i, at: (0, i))
    y, held = pl.pallas_call(
        kernel,
        grid=(head_blocks, count),
        in_specs=[tokens_by_heads, of_the_group, of_the_group, by_rows, by_rows, state_spec],
        out_specs=[tokens_by_heads, state_spec],
        out_shape=[
            jax.ShapeDtypeStruct((tokens, heads * width), jnp.float32),
            jax.ShapeDtypeStruct((n, heads * width), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_chunk",  # the kernel's name in a device trace
    )(u.reshape(tokens, heads * width), b.reshape(tokens, groups * n),
      c.reshape(tokens, groups * n), rows(decay), rows(by_row),
      jnp.moveaxis(state, 2, 0).reshape(n, heads * width))
    return (y.reshape(tokens, heads, width),
            jnp.moveaxis(held.reshape(n, heads, width), 0, 2))
