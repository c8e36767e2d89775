"""Whole-key attention, several heads a grid step (Pallas).

`ops/attention.flash_attention` streams keys a block at a time and
carries an online softmax across them, one (batch, head) a grid step.
For a non-causal call whose keys all fit one block that machinery is
the cost: SDXL's tile program attends 60 times an evaluation over 324
tokens, 20 heads of 64, and a grid step of one 336 x 384 block is all
fixed cost (the pipeline's turn, the state's set-up and its divide, the
copy that pads 64 lanes to 128 and folds the heads in front of the
tokens): 320 steps of 1.68 us, 0.538 ms a call, where XLA's float32
scores take 0.639 (PERF.md section 6, PR 60).

`short_attention` is the kernel those shapes want. Heads `WIDTH` = 64
wide lie two to a lane tile in the `[B, N, H*D]` arrays the caller's
linears wrote; a grid step takes a (batch, group of `tiles` lane
tiles, q block) and *all* keys, reads both where they lie and writes
the output where the caller reads it. No operand is padded, folded or
copied in HBM: a q block is a multiple of 16 rows that may reach past
the array's end, the key block is the keys rounded up to the lane
tile, and what a block holds past the array's extent is whatever VMEM
held (the interpreter hands NaN), so the kernel masks it: scores of
padded keys to -inf before the max, as `flash_attention` does, the
padded rows of v to zero (0 x NaN is NaN), and rows past the queries'
end are computed and never written back.

With one key block the softmax is the plain one: float32 scores, one
max, one `exp`, one sum over the row, `p` rounded to v's dtype before
the second product, float32 accumulation, one divide. No state is
carried over a grid axis, so every axis is `parallel`.

Two heads share a lane tile but not a product (each has its own P).
Head A's scores are `dot(where(lane < 64, q_tile, 0), k_tile^T)`: one
128-deep pass, as the zero-padded head's is in `flash_attention`. Its
`p @ v_tile` is right in A's 64 lanes and holds `p_A @ v_B` in B's;
the tile's output takes each head's own lanes by a select, so v is
never masked by head.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .attention import (
    _DTYPE_NAMES,
    _tile,
    MAX_BLOCK_K,
    MAX_BLOCK_Q,
    ROUTE_MULTIPLE,
    ROW_CHUNK,
    ROW_MULTIPLE,
    VMEM_BUDGET,
)

# The head width the kernel is written for: two heads fill a lane tile.
WIDTH = 64
# A q block goes through a head's softmax whole where its float32 scores are
# at most this many (336 x 384, 432 x 128), else `ROW_CHUNK` rows at a time
# as in `flash_attention`: a chunk pushes the key tiles into the MXU again,
# and a step's bundles at 324 keys are 12,647 whole, 15,065 as 256 + 80 rows,
# 17,971 in chunks of 128 (`scripts/kernel_bundles.py`; PERF.md section 6, PR 60).
WHOLE_BLOCK_SCORES = 2**17
# What the compiler may give a grid step: `plan` counts the blocks and a
# chunk's scores under `VMEM_BUDGET`, and the compiler keeps temporaries of
# its own for every lane tile of the step (336 rows over 400 keys at ten lane
# tiles: 11.3 MB by `vmem_bytes`, 16.9 MB allocated, past the default 16 MiB).
VMEM_LIMIT = 32 * 2**20


def chunk_rows(block_q: int, m_pad: int) -> int:
    """Rows of a q block a head's softmax takes at a time."""
    return block_q if block_q * m_pad <= WHOLE_BLOCK_SCORES else min(block_q, ROW_CHUNK)


def vmem_bytes(block_q: int, m_pad: int, tiles: int, itemsize: int) -> int:
    """VMEM one grid step holds: the q, k, v and output blocks
    (double-buffered by the pipeline) and a row chunk's float32 scores,
    their `exp`, and `p` in the operands' dtype, for the two heads of a
    lane tile."""
    blocks = 2 * (2 * block_q + 2 * m_pad) * tiles * ROUTE_MULTIPLE * itemsize
    scores = 2 * chunk_rows(block_q, m_pad) * m_pad * (4 + 4 + itemsize)
    return blocks + scores


def plan(n: int, m: int, heads: int, itemsize: int) -> tuple[int, int, int] | None:
    """(block_q, padded keys, lane tiles a step) for q of n rows over m
    keys at `heads` heads of `WIDTH`, or None where the kernel has no
    form: heads that do not pair up into lane tiles, or keys past one
    block. Rows go in the fewest blocks `MAX_BLOCK_Q` allows, each a
    multiple of `ROW_MULTIPLE` (324 as 1 x 336, 1,296 as 3 x 432); a
    step takes as many lane tiles as divide the row and fit
    `VMEM_BUDGET` (1,280 columns at 324 keys: all ten, so a step is a
    batch entry's whole attention)."""
    m_pad = m + -m % ROUTE_MULTIPLE
    if n <= 0 or m <= 0 or heads % 2 or m_pad > MAX_BLOCK_K:
        return None
    _, block_q = _tile(n, MAX_BLOCK_Q, ROW_MULTIPLE)
    lane_tiles = heads * WIDTH // ROUTE_MULTIPLE
    for tiles in range(lane_tiles, 0, -1):
        if lane_tiles % tiles == 0 and vmem_bytes(block_q, m_pad, tiles, itemsize) <= VMEM_BUDGET:
            return block_q, m_pad, tiles
    return None


# ((fewest, most) rows, (fewest, most) keys) of the calls this kernel was timed
# at on a v5e and won, batch 16 in bfloat16, at the two row counts of SDXL's
# 576-pixel tile (PERF.md section 6, PR 60; ms a call standing alone, XLA /
# `flash_attention` / this kernel). 324 rows of 20 heads: over the 77 text keys
# 0.272 / 0.426 / 0.257 (between a block's linears 0.485 / 0.774 / 0.402; SDXL's
# cell reads 13 ms a job less with it here), 128 keys 0.270 / 0.415 / 0.243; 256
# keys 0.372 / 0.492 / 0.271, over themselves 0.631 / 0.535 / 0.301, 484 keys
# 0.912 / 0.654 / 0.385, 1,024 keys 1.595 / 1.384 / 0.478, and 400, 576, 768
# between them. 1,296 rows of 10 heads: 128 keys 0.490 / 0.566 / 0.216, 324 keys
# 1.034 / 0.750 / 0.436, over themselves 3.477 / 1.822 / 1.016, 1,536 keys 3.964
# / 1.713 / 1.023, and 576, 1,024 between them. Timed and not won: 324 rows over
# 200 keys (0.254 / 0.488 / 0.258), and 1,296 rows over the 77 text keys
# (0.212-0.240 / 0.56 / 0.218-0.241 alone, 0.376 / 0.746 / 0.396 in a block;
# SDXL's cell reads 12 ms a job less with that call on XLA). Other row counts
# have no timing in a block.
WINNING_SHAPES = (
    ((324, 324), (77, 128)),
    ((324, 324), (256, 1024)),
    ((1296, 1296), (128, 1536)),
)


def short_wins(n: int, m: int, heads: int, width: int, dtype) -> bool:
    """Whether a non-causal call of q [., n, heads, width] over m keys
    goes to this kernel on a TPU: a function of what the call shows and
    nothing else. Heads `WIDTH` wide that pair up into lane tiles,
    bfloat16 operands (what was timed), a plan, and rows and keys the
    chip's timings name (`WINNING_SHAPES`); every other call keeps the
    route `attention.kernel_wins` gives it."""
    if width != WIDTH or jnp.dtype(dtype) != jnp.bfloat16 or plan(n, m, heads, 2) is None:
        return False
    return any(
        rows[0] <= n <= rows[1] and keys[0] <= m <= keys[1] for rows, keys in WINNING_SHAPES)


def entry(n: int, m: int, heads: int, dtype) -> str:
    """A call's line in `attention.route_log`: `short NxMx64 pad<rows the
    q blocks cover>x<padded keys> h<heads a grid step> bq<rows a q block>
    <dtype> inplace` (`short 324x324x64 pad336x384 h20 bq336 bf16
    inplace`); `inplace` as in `flash_attention`'s entry: heads read and
    written where the caller left them."""
    block_q, m_pad, tiles = plan(n, m, heads, jnp.dtype(dtype).itemsize)
    name = jnp.dtype(dtype).name
    return (f"short {n}x{m}x{WIDTH} pad{-(-n // block_q) * block_q}x{m_pad} h{2 * tiles} "
            f"bq{block_q} {_DTYPE_NAMES.get(name, name)} inplace")


@functools.partial(jax.jit, static_argnames=("interpret", "tiles"))
def short_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, interpret: bool = False, tiles: int | None = None,
) -> jax.Array:
    """Non-causal attention of q [B, N, H, 64] over k, v [B, M, H, 64],
    all M keys one block; returns [B, N, H, 64]. The module's docstring
    has the form; `plan` the blocks. `tiles` (lane tiles a grid step)
    overrides the plan's for the chip's sweep and the tests. Jitted
    here so that a program's calls of one shape share one trace of the
    kernel."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, rows, heads, width = q.shape
    keys = k.shape[1]
    sizes = plan(rows, keys, heads, q.dtype.itemsize)
    if width != WIDTH or sizes is None or k.shape != (b, keys, heads, width) or v.shape != k.shape:
        raise ValueError(f"short_attention: no plan for q {q.shape} over k {k.shape}, v {v.shape}")
    block_q, m_pad, planned = sizes
    tiles = planned if tiles is None else tiles
    lane_tiles = heads * width // ROUTE_MULTIPLE
    if lane_tiles % tiles:
        raise ValueError(f"{tiles} lane tiles a step do not divide {lane_tiles}")
    scale = 1.0 / math.sqrt(width)
    columns = tiles * ROUTE_MULTIPLE
    rows_a_chunk = chunk_rows(block_q, m_pad)
    q, k, v = (x.reshape(b, x.shape[1], heads * width) for x in (q, k, v))
    contract_last = (((1,), (1,)), ((), ()))  # q @ k.T without the transpose

    def lane_tiles_of(x):
        return [x[:, at:at + ROUTE_MULTIPLE] for at in range(0, x.shape[1], ROUTE_MULTIPLE)]

    def kernel(q_ref, k_ref, v_ref, o_ref):
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, ROUTE_MULTIPLE), 1)
        first = lane < width  # the lanes of a tile's first head
        for tile in range(tiles):
            cols = slice(tile * ROUTE_MULTIPLE, (tile + 1) * ROUTE_MULTIPLE)
            kt, vt = k_ref[0, :, cols], v_ref[0, :, cols]        # [m_pad, 128]
            if m_pad > keys:  # past the keys' end the block holds no data
                key_row = jax.lax.broadcasted_iota(jnp.int32, vt.shape, 0)
                vt = jnp.where(key_row < keys, vt, jnp.zeros_like(vt))
            for start in range(0, block_q, rows_a_chunk):
                chunk = slice(start, min(start + rows_a_chunk, block_q))
                # 1/8 is a power of two: scaling q is exact, and scales the float32 scores exactly
                qt = q_ref[0, chunk, cols]
                qt = (qt * scale).astype(qt.dtype)               # [chunk, 128]
                accs, totals = [], []
                for own in (first, ~first):
                    scores = lane_tiles_of(jax.lax.dot_general(
                        jnp.where(own, qt, jnp.zeros_like(qt)), kt, contract_last,
                        preferred_element_type=jnp.float32))     # [chunk, m_pad]
                    if m_pad > keys:  # only the last lane tile holds padded keys
                        scores[-1] = jnp.where(
                            lane < keys - (m_pad - ROUTE_MULTIPLE), scores[-1], -jnp.inf)
                    row_max = functools.reduce(jnp.maximum, scores).max(axis=-1, keepdims=True)
                    p = [jnp.exp(s - row_max) for s in scores]
                    totals.append(functools.reduce(jnp.add, p).sum(axis=-1, keepdims=True))
                    accs.append(jnp.dot(
                        jnp.concatenate(p, axis=1).astype(vt.dtype), vt,
                        preferred_element_type=jnp.float32))     # [chunk, 128]
                out = jnp.where(first, accs[0], accs[1]) / jnp.where(first, totals[0], totals[1])
                o_ref[0, chunk, cols] = out.astype(o_ref.dtype)

    def q_map(bi, gi, qi):
        return bi, qi, gi

    def kv_map(bi, gi, qi):
        return bi, 0, gi

    out = pl.pallas_call(
        kernel,
        grid=(b, lane_tiles // tiles, -(-rows // block_q)),
        in_specs=[
            pl.BlockSpec((1, block_q, columns), q_map),
            pl.BlockSpec((1, m_pad, columns), kv_map),
            pl.BlockSpec((1, m_pad, columns), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, columns), q_map),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
        name="short_attention",  # the kernel's name in a device trace
    )(q, k, v)
    return out.reshape(b, rows, heads, width)
