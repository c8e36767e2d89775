"""One new token's attention over a cache slot, read where it lies.

A decode step of a model that keeps a cache slot for every (pass,
layer) attends, a layer pass, with one query a head over that slot's
keys and values: `[heads, positions, d]` each, a corner of the carried
cache `[passes, layers, 2, heads, positions, d]`. Left to XLA on a TPU
the slot is first staged by a `dynamic-slice` fusion, the scores then
read its keys from HBM a second time, and the softmax and the weighted
sum run as further fusions (PERF.md §6, PR 37). `decode_attention` is
the one pass the arithmetic needs, as a Pallas kernel: the slot and the
position are scalar-prefetched indices, a grid step takes a group of
heads' keys and values out of the whole cache by one block whose every
head is a contiguous run, and nothing of the cache is copied in XLA.
Elsewhere the einsum form stays (`decode_attention_route`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .attention import _DTYPE_NAMES, _ROUTE_LOG, ROUTE_MULTIPLE, VMEM_BUDGET

# A cache whose positions are no multiple of the sublane tile is padded
# in its tiled layout, and the compiler then copies all of it in front
# of the call (3.2 GB of temporary at 1,001 positions, compiled for a
# described v5e; none at 1,000 or 2,112).
POSITION_MULTIPLE = 8
# What a grid step's block should at least hold: from about a megabyte
# on, a step's fixed cost (a few tenths of a microsecond) hides behind
# its own DMA, and the fewer heads a step takes, the less arithmetic is
# left when the last block has arrived. On a v5e at 16 x 2,112 x 128
# bfloat16 (192 calls in one loop; PERF.md §6, PR 37) a call takes 25.9
# us at one head a step (1.08 MB), 26.2 at two, 27.3 at four, 29.1 at
# eight; a kernel that only moves the blocks 24.9-25.2 at any of them.
MIN_BLOCK_BYTES = 2**20


def decode_vmem_bytes(group: int, positions: int, d: int, itemsize: int) -> int:
    """VMEM one grid step of the kernel holds: a group of heads' keys
    and values (double-buffered by the pipeline) and, a head at a time,
    the float32 scores, their `exp` and `p` in the operands' dtype, on
    the eight sublanes a one-row operand takes."""
    blocks = 2 * 2 * group * positions * d * itemsize
    scores = 8 * positions * (4 + 4 + itemsize)
    return blocks + scores


def decode_plan(heads: int, positions: int, d: int, itemsize: int) -> int | None:
    """Heads a grid step takes, each with all of its positions (the
    whole axis is one block, so a length that is no multiple of 128 is
    taken as it is and no tail is padded): the fewest, of `heads`'
    divisors, whose keys and values reach `MIN_BLOCK_BYTES`, all of
    them where none does. None where the kernel does not apply: a width
    off the lane tile, a length off `POSITION_MULTIPLE`, or a step that
    does not fit `VMEM_BUDGET` (a slot longer than VMEM holds would
    need key blocks and an online softmax, which nothing served has)."""
    if d % ROUTE_MULTIPLE or positions <= 0 or positions % POSITION_MULTIPLE:
        return None
    head_bytes = 2 * positions * d * itemsize
    group = next(
        (g for g in range(1, heads + 1) if heads % g == 0 and g * head_bytes >= MIN_BLOCK_BYTES),
        heads,
    )
    return group if decode_vmem_bytes(group, positions, d, itemsize) <= VMEM_BUDGET else None


def decode_attention_route(heads: int, positions: int, d: int, dtype) -> str:
    """"decode-kernel" on a TPU for a shape `decode_plan` takes, else
    "decode-xla" (`attend_xla`)."""
    if jax.default_backend() != "tpu":
        return "decode-xla"
    plan = decode_plan(heads, positions, d, jnp.dtype(dtype).itemsize)
    return "decode-kernel" if plan else "decode-xla"


def decode_attention_xla(q: jax.Array, cache: jax.Array, slot, valid, scale=None) -> jax.Array:
    """The einsum form: q [W, heads, d], the queries of W positions of
    one step (one: `q[None]`), over `cache[slot]`, with `valid` [W, S]
    saying which of the slot's S entries each may see (`position_valid`
    for a cache that grows, `ring_valid` for a ring). The slot may hold
    fewer key and value heads than there are queries, a divisor of their
    count: key head j serves the query heads j x group .. (j + 1) x group
    - 1 (one wide where the counts are equal), and the W queries lie
    beside a key head's group, so the slot is read once. Scores (times `scale`,
    d^-1/2 where None), softmax and the sum's accumulation float32, the probabilities
    rounded to the cache's dtype. Returns [W, heads, d] in the cache's dtype."""
    keys, values = cache[slot]                                   # [kv heads, S, d] each
    heads, d = q.shape[-2:]
    group = heads // keys.shape[0]
    grouped = q.reshape(-1, keys.shape[0], group, d).swapaxes(0, 1).reshape(
        keys.shape[0], -1, d)                                    # [kv heads, W x group, d]
    scores = (d ** -0.5 if scale is None else scale) * jnp.einsum(
        "hgd,hsd->hgs", grouped, keys, preferred_element_type=jnp.float32)
    scores = jnp.where(jnp.repeat(valid, group, axis=0)[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(values.dtype)
    out = jnp.einsum("hgs,hsd->hgd", probs, values)
    return out.reshape(keys.shape[0], q.shape[0], -1, d).swapaxes(0, 1).reshape(q.shape)


def position_valid(positions: jax.Array, size: int) -> jax.Array:
    """[W, size]: which entries of a cache that grows (entry j position
    j) the queries at `positions` [W] may see: every j <= p."""
    return jnp.arange(size)[None, :] <= positions[:, None]


def block_valid(position, size: int, block: int) -> jax.Array:
    """[block, size]: which entries of a cache that grows the `block`
    queries at `position` .. `position` + `block` - 1 (one whole block of
    a mask that is causal over blocks and full inside one) may see: all
    of them every entry below the block's end, their own among them, so
    the block's keys and values are written before they are attended."""
    seen = jnp.arange(size) < position + block
    return jnp.broadcast_to(seen[None, :], (block, size))


def ring_valid(positions: jax.Array, ring: int, window: int) -> jax.Array:
    """[W, ring]: which entries of a ring the queries at `positions` [W]
    (ascending, the last the newest position written) may see. Entry s
    holds the newest written position that is s modulo `ring`; a query at
    p sees the positions p - `window` < j <= p, so never an entry a later
    position of the same step has written over, and never one unwritten
    (a position below 0)."""
    newest = positions[-1]
    held = newest - (newest - jnp.arange(ring)) % ring           # the position in each entry
    seen = (held[None, :] <= positions[:, None]) & (held[None, :] > positions[:, None] - window)
    return seen & (held[None, :] >= 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention(
    q: jax.Array, cache: jax.Array, slot, position, *, interpret: bool = False,
) -> jax.Array:
    """q [heads, d] over the slot `(pass, layer)` of cache [passes,
    layers, 2, heads, positions, d], positions 0..`position`. Returns
    [heads, d] in the cache's dtype.

    Grid: (heads / group,), the group from `decode_plan`. A step's one
    block is `cache[pass, layer, :, group of heads]`: keys and values,
    each head's positions one run of HBM. The slot's whole length is
    in VMEM, so the softmax is not online: scores in float32 from the
    operands as they are stored, positions past `position` set to -inf
    by a select and their values to zero (they are unwritten: whatever
    they hold, a NaN too, weighs nothing; the selects hide behind the
    next block's read), max, `exp` and sum in float32, the
    probabilities divided before they are rounded to the cache's dtype
    for the weighted sum, which accumulates in float32: the einsum
    form's precisions."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    heads, d = q.shape
    positions = cache.shape[4]
    group = decode_plan(heads, positions, d, cache.dtype.itemsize)
    if group is None or cache.shape[2:4] != (2, heads) or cache.shape[5] != d:
        raise ValueError(f"decode_attention: no plan for q {q.shape} over a cache {cache.shape}")
    scale = d ** -0.5
    contract_last = (((1,), (1,)), ((), ()))  # q @ k.T without the transpose

    def kernel(pass_ref, layer_ref, position_ref, q_ref, kv_ref, o_ref):
        position = position_ref[0]
        cols = jax.lax.broadcasted_iota(jnp.int32, (1, positions), 1)
        rows = jax.lax.broadcasted_iota(jnp.int32, (positions, 1), 0)
        for g in range(group):
            # one row a head; the MXU's operand is padded to a sublane tile anyway
            scores = scale * jax.lax.dot_general(                # [1, positions]
                q_ref[0, g:g + 1, :], kv_ref[0, g], contract_last,
                preferred_element_type=jnp.float32,
            )
            scores = jnp.where(cols <= position, scores, -jnp.inf)
            e = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
            p = e / e.sum(axis=-1, keepdims=True)
            # a weight of zero does not silence a NaN in the product
            values = kv_ref[1, g]
            values = jnp.where(rows <= position, values, jnp.zeros_like(values))
            o_ref[0, g:g + 1, :] = jnp.dot(
                p.astype(values.dtype), values, preferred_element_type=jnp.float32,
            ).astype(o_ref.dtype)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(heads // group,),
            in_specs=[
                pl.BlockSpec((1, group, d), lambda gi, *_: (gi, 0, 0)),
                pl.BlockSpec(
                    (None, None, 2, group, positions, d),
                    lambda gi, pass_, layer, position: (pass_[0], layer[0], 0, gi, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, group, d), lambda gi, *_: (gi, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((heads // group, group, d), cache.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="decode_attention",  # the kernel's name in a device trace
    )(
        # the slot's indices apart: stacked, they cost a device operation a call
        *(jnp.asarray(i, jnp.int32).reshape(1) for i in (*slot, position)),
        q.astype(cache.dtype).reshape(heads // group, group, d), cache,
    )
    return out.reshape(heads, d)


def _log_route(entry: str) -> None:
    log = _ROUTE_LOG.get()
    if log is not None:
        log.append(entry)


def attend_xla(q: jax.Array, cache: jax.Array, slot, valid: jax.Array, scale=None) -> jax.Array:
    """`decode_attention_xla` as a model calls it: with one entry in
    `ops/attention.route_log`, `decode-xla 16x2112x128` (query heads x
    the slot's entries x width)."""
    heads, d = q.shape[-2:]
    _log_route(f"decode-xla {heads}x{cache.shape[-2]}x{d}")
    return decode_attention_xla(q, cache, slot, valid, scale)


def attend(q: jax.Array, cache: jax.Array, slot, position) -> jax.Array:
    """One query a head, q [heads, d], over the slot's positions up to
    `position`: `decode_attention` where `decode_attention_route` gives
    the kernel (its entry in the route log: `decode-kernel 16x2112x128 h1
    bf16`, the heads a grid step takes and the cache's dtype last), else
    `attend_xla`."""
    heads, d = q.shape
    positions = cache.shape[4]
    if decode_attention_route(heads, positions, d, cache.dtype) == "decode-kernel":
        group = decode_plan(heads, positions, d, cache.dtype.itemsize)
        _log_route(f"decode-kernel {heads}x{positions}x{d} h{group} "
                   f"{_DTYPE_NAMES.get(cache.dtype.name, cache.dtype.name)}")
        return decode_attention(q, cache, slot, position)
    valid = position_valid(jnp.asarray(position).reshape(1), positions)
    return attend_xla(q[None], cache, slot, valid)[0]
