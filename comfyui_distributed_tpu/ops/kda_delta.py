"""A prefill's chunked delta rule, the matrix state held in VMEM from
the first chunk to the last.

`models/kda.kda_chunked` walks a sequence a chunk at a time: what a
chunk's tokens write into the state S is the solution of a unit
lower-triangular system of the chunk's own keys and decays, and S is
carried from chunk to chunk. Left to XLA on a TPU that is a `lax.map`
of small fusions followed by a `lax.scan` of 128 dependent steps, each
of which reads S from HBM and writes it back, between copies that pad
and transpose q, k, v, g and o into `[chunks, H, chunk, d]` (PERF.md
§6, PR 46). `kda_delta` is the same arithmetic as one Pallas kernel:
the grid walks (block of heads, chunk), the chunk axis in order, S
`[heads a step, d, d]` float32 stays in VMEM across it, and a chunk's
terms (the cumulative decays, the pairwise products, the triangular
inverse) are formed in VMEM and never leave it. q, k, v, g and o are
`[T, H d]` to the kernel, which is how the callers hold them: a head is
a run of d lanes of a `[chunk, heads a step x d]` block. Elsewhere the
XLA form stays (`kda_delta_route`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .attention import _DTYPE_NAMES, _ROUTE_LOG, ROUTE_MULTIPLE, VMEM_BUDGET

# Rows of a chunk whose pairwise decays are formed pair by pair; a row
# block's products with the columns of earlier blocks go through the
# decay at the block's first row (`models/kda.KDA_SUBCHUNK` is this).
SUBCHUNK = 16
# The most heads a grid step takes. A head's chain of small float32
# products (the inverse: ten, each waiting for the one before) is
# latency, so a step holds several heads' side by side, the heads the
# batch axis of every product, for the scheduler to interleave. On a
# v5e at 8,192 x 32 x 128 (PERF.md §6, PR 46) a (head, chunk) takes
# 2.72 us at one head a step, 1.62 at two, 1.34 at four, 1.27 at eight;
# sixteen do not fit the compiler's 16 MiB of VMEM.
MAX_HEADS = 8


def delta_vmem_bytes(group: int, d: int, chunk: int, itemsize: int) -> int:
    """VMEM one grid step holds: q, k, v, g, beta and o blocks (double-
    buffered by the pipeline), the state coming in and the resident
    state going out, and the heads' float32 terms (a dozen arrays the
    size of the solve's right-hand side live at once: eight heads
    compile for a v5e and sixteen do not)."""
    blocks = 2 * chunk * group * (d * (3 * itemsize + 4 + 4) + 4)
    states = 4 * group * d * d * 4
    terms = 12 * group * chunk * max(2 * d, chunk) * 4
    return blocks + states + terms


def delta_plan(heads: int, d: int, chunk: int, itemsize: int) -> int | None:
    """Heads a grid step takes: `MAX_HEADS`, or all of fewer, halved
    until the step fits `VMEM_BUDGET`. None where the kernel does not
    apply: a width off the lane tile, a chunk that is no multiple of
    `SUBCHUNK`, or an itemsize it has no tile for."""
    if d % ROUTE_MULTIPLE or chunk <= 0 or chunk % SUBCHUNK or itemsize not in (2, 4):
        return None
    group = min(heads, MAX_HEADS)
    while group > 1 and delta_vmem_bytes(group, d, chunk, itemsize) > VMEM_BUDGET:
        group //= 2
    return group if delta_vmem_bytes(group, d, chunk, itemsize) <= VMEM_BUDGET else None


def kda_delta_route(heads: int, d: int, chunk: int, dtype) -> str:
    """"kernel" on a TPU for a shape `delta_plan` takes, else "scan"
    (`models/kda.kda_chunked`'s XLA body)."""
    if jax.default_backend() != "tpu":
        return "scan"
    return "kernel" if delta_plan(heads, d, chunk, jnp.dtype(dtype).itemsize) else "scan"


def log_route(form: str, tokens: int, heads: int, d: int, chunk: int, dtype) -> None:
    """One entry in `ops/attention.route_log` a traced call: `kda-kernel
    8192x32x128 c64 hb8 bf16` (tokens x heads x width, the chunk, the
    heads a grid step takes, the storage dtype) or `kda-scan 8192x32x128
    c64 bf16`."""
    log = _ROUTE_LOG.get()
    if log is not None:
        dtype = jnp.dtype(dtype)
        step = f" hb{delta_plan(heads, d, chunk, dtype.itemsize)}" if form == "kernel" else ""
        log.append(f"kda-{form} {tokens}x{heads}x{d} c{chunk}{step} "
                   f"{_DTYPE_NAMES.get(dtype.name, dtype.name)}")


def _chunk_terms(q, k, v, g, beta, held_t, dtype):
    """A grid step's heads, one chunk against the states before it: q,
    k, v, g [heads, C, d] float32, beta [heads, C, 1], `held_t` [heads,
    d, d] float32 the states transposed (value channel first). Returns
    (o [heads, C, d] float32, the states after the chunk, transposed).
    Every product has the heads as its batch axis, so that the heads'
    chains of small dependent products lie side by side for the
    scheduler. `models/kda.kda_chunked` has the algebra."""
    heads, chunk, d = q.shape
    blocks = chunk // SUBCHUNK
    product = functools.partial(
        jax.lax.dot_general, dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    highest = functools.partial(product, precision=jax.lax.Precision.HIGHEST)
    last_dims = (((2,), (2,)), ((0,), (0,)))   # a @ b.T without the transpose
    row = jax.lax.broadcasted_iota(jnp.int32, (heads, chunk, chunk), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (heads, chunk, chunk), 2)

    def of_each_block(a, j):
        """[heads, C, d]: row j of each block of `SUBCHUNK` rows, over the block."""
        return jnp.concatenate([
            jnp.broadcast_to(a[:, n * SUBCHUNK + j:n * SUBCHUNK + j + 1, :], (heads, SUBCHUNK, d))
            for n in range(blocks)], axis=1)

    # G, the cumulative log-decays from the chunk's start: a triangular product
    decay = highest((row >= col).astype(jnp.float32), g)
    into = jnp.exp(decay)                                        # from the chunk's start
    out_of = jnp.exp(decay[:, chunk - 1:chunk, :] - decay)       # to the chunk's end

    # kk[i, j] = sum_c k_ic k_jc exp(G_ic - G_jc), qk the same of q, j <= i.
    # Blocks below the diagonal: through the decay at the row block's first row
    first = of_each_block(decay, 0)
    since = jnp.exp(decay - first)                               # <= 1
    k_since, q_since = k * since, q * since
    nothing = jnp.zeros((heads, SUBCHUNK, chunk), jnp.float32)
    kk, qk = [nothing], [nothing]
    for n in range(1, blocks):
        rows = slice(n * SUBCHUNK, (n + 1) * SUBCHUNK)
        columns = k * jnp.exp(jnp.minimum(first[:, rows][:, :1] - decay, 0.0))
        below = jax.lax.dot_general(
            jnp.concatenate([k_since[:, rows], q_since[:, rows]], axis=1), columns, last_dims,
            precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32)
        kk.append(below[:, :SUBCHUNK])
        qk.append(below[:, SUBCHUNK:])
    kk, qk = jnp.concatenate(kk, axis=1), jnp.concatenate(qk, axis=1)
    # the blocks on it: pair by pair, column j of every block at once
    in_block = col - (row - row % SUBCHUNK)
    for j in range(SUBCHUNK):
        pairs = of_each_block(k, j) * jnp.exp(jnp.minimum(decay - of_each_block(decay, j), 0.0))
        here = in_block == j
        kk = jnp.where(here, jnp.sum(k * pairs, axis=2, keepdims=True), kk)
        qk = jnp.where(here, jnp.sum(q * pairs, axis=2, keepdims=True), qk)
    a = jnp.where(row > col, beta * kk, 0.0)
    qk = jnp.where(row >= col, qk, 0.0)

    # (I + A)^-1: the diagonal blocks' as the finite product (I - D)(I + D^2)
    # (I + D^4)..., all blocks in one block-diagonal matrix; then pairs of
    # blocks merged, [[P, 0], [Q, R]]^-1 = [[P^-1, 0], [-R^-1 Q P^-1, R^-1]]
    eye = (row == col).astype(jnp.float32)
    power = jnp.where((in_block >= 0) & (in_block < SUBCHUNK), a, 0.0)
    inverse = eye - power
    for _ in range((SUBCHUNK - 1).bit_length() - 1):
        power = highest(power, power)
        inverse = highest(inverse, eye + power)
    size = SUBCHUNK
    while size < chunk:
        pair = row // (2 * size) == col // (2 * size)
        lower_left = jnp.where(pair & (row // size > col // size), a, 0.0)
        inverse = inverse - highest(highest(inverse, lower_left), inverse)
        size *= 2
    solved = highest(inverse, beta * jnp.concatenate([k * into, v], axis=2))
    w, u0 = solved[:, :, :d].astype(dtype), solved[:, :, d:]

    # the carry: four products, operands in the storage dtype, float32 sums
    held = held_t.astype(dtype)
    u = u0 - jax.lax.dot_general(w, held, last_dims, preferred_element_type=jnp.float32)
    u_stored = u.astype(dtype)
    o = jax.lax.dot_general(
        (q * into).astype(dtype), held, last_dims, preferred_element_type=jnp.float32,
    ) + product(qk.astype(dtype), u_stored)
    written = jax.lax.dot_general(
        u_stored, (k * out_of).astype(dtype), (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)                      # [heads, value, key]
    return o, into[:, chunk - 1:chunk, :] * held_t + written


@functools.partial(jax.jit, static_argnames=("chunk", "group", "interpret"))
def kda_delta(q, k, v, g, beta, state, *, chunk: int, group: int | None = None,
              interpret: bool = False):
    """`models/kda.kda_chunked` as one kernel: q, k, v [T, H, d] in the
    storage dtype, g [T, H, d] and beta [T, H] float32, `state` [H, d,
    d] float32. Returns (o [T, H, d] float32, the state after the last
    token). `group` (heads a grid step; `delta_plan`'s where None) need
    not divide H.

    Grid: (H / group, chunks), the head axis parallel, the chunk axis
    in order; a step takes its heads' `[chunk, d]` runs of lanes out of
    one `[chunk, group x d]` block an operand and computes them as one
    batch (`_chunk_terms`). The state's output block has the same index at every
    chunk of a head block, so it stays in VMEM: loaded from `state` at
    chunk 0, updated a chunk, written to HBM once. It is held
    transposed (value channel first), which makes the decay to the
    chunk's end a scaling of lanes and both products that read it
    `a @ b.T`; the wrapper's two transpositions are of a few megabytes.
    Rows past T in a short last chunk are tokens that change nothing
    (q, k, v, g, beta 0), set by a select since what the block holds
    there is undefined.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tokens, heads, d = q.shape
    dtype = q.dtype
    plan = delta_plan(heads, d, chunk, dtype.itemsize)
    if plan is None:
        raise ValueError(f"kda_delta: no plan for {q.shape} {dtype} in chunks of {chunk}")
    group = min(group or plan, heads)
    head_blocks = -(-heads // group)
    count = -(-tokens // chunk)
    short = tokens % chunk

    def kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, state_ref, o_ref, held_ref):
        c = pl.program_id(1)

        @pl.when(c == 0)
        def _():
            held_ref[...] = state_ref[...]

        def heads_of(ref, width=d):  # [C, group x width] -> [group, C, width] float32
            a = jnp.stack([ref[:, h * width:(h + 1) * width] for h in range(group)])
            a = a.astype(jnp.float32)
            if short:  # what the block holds past the last token is undefined
                rows = jax.lax.broadcasted_iota(jnp.int32, (1, chunk, 1), 1)
                a = jnp.where(rows < tokens - c * chunk, a, 0.0)
            return a

        o, held = _chunk_terms(
            *map(heads_of, (q_ref, k_ref, v_ref, g_ref)), heads_of(beta_ref, 1),
            held_ref[...], dtype)
        for h in range(group):
            o_ref[:, h * d:(h + 1) * d] = o[h]
        held_ref[...] = held

    def tokens_by_heads(width):
        return pl.BlockSpec((chunk, group * width), lambda i, c: (c, i))

    state_spec = pl.BlockSpec((group, d, d), lambda i, c: (i, 0, 0))
    flat = lambda a: a.reshape(tokens, heads * d)
    # beta [T, H] -> [head blocks, T, group]: a step's heads are its block's lanes
    by_block = jnp.pad(beta, ((0, 0), (0, head_blocks * group - heads))).reshape(
        tokens, head_blocks, group).swapaxes(0, 1)
    o, held = pl.pallas_call(
        kernel,
        grid=(head_blocks, count),
        in_specs=[
            tokens_by_heads(d), tokens_by_heads(d), tokens_by_heads(d), tokens_by_heads(d),
            pl.BlockSpec((None, chunk, group), lambda i, c: (i, c, 0)),
            state_spec,
        ],
        out_specs=[tokens_by_heads(d), state_spec],
        out_shape=[
            jax.ShapeDtypeStruct((tokens, heads * d), jnp.float32),
            jax.ShapeDtypeStruct((heads, d, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="kda_delta",  # the kernel's name in a device trace
    )(flat(q), flat(k), flat(v), flat(g), by_block, state.swapaxes(1, 2))
    return o.reshape(tokens, heads, d), held.swapaxes(1, 2)
