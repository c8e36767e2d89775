"""Per-head RMS norm and rotary embedding of a query or key, in place.

An MMDiT block normalises every head of q and k over its width and
rotates adjacent pairs before attention. Left to XLA on a TPU, the
norm's reduction over the minor axis makes the compiler keep the
tokens on the lanes from the statistic through the rotation, and turn
q and k around twice on the way to the attention kernel: seven passes
over a tensor where the arithmetic needs one (PERF.md §6, PR 35).
`norm_rope` is that one pass as a Pallas kernel: it reads the heads
out of the projection's [B, N, W] output where the linear left them
and writes [B, N, H*D], which `ops/attention.flash_attention` reads as
it is. Elsewhere the model keeps its XLA operations (`norm_rope_route`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .attention import ROUTE_MULTIPLE, ROW_MULTIPLE

# Rows and lanes one grid step takes: a step's fixed cost is a few
# tenths of a microsecond, and the wider a row of the block, the longer
# the run of whole tiles one DMA descriptor covers (16 KB at 1,024
# lanes of a 16-bit operand).
MAX_BLOCK_ROWS = 512
MAX_BLOCK_LANES = 1024


def norm_rope_plan(n: int, heads: int, d: int, offset: int) -> tuple[int, int] | None:
    """(rows, heads) of one block for `n` tokens of `heads` heads `d`
    wide that start at lane `offset`, or None where the kernel does not
    apply: a width off the lane tile, an odd offset, or a token count
    with no divisor that is a multiple of `ROW_MULTIPLE`."""
    if d % ROUTE_MULTIPLE or n <= 0 or offset % d:
        return None
    rows = next(
        (r for r in range(min(MAX_BLOCK_ROWS, n), 0, -1)
         if n % r == 0 and r % ROW_MULTIPLE == 0),
        None,
    )
    if rows is None:
        return None
    group = next(
        g for g in range(heads, 0, -1)
        if heads % g == 0 and (g * d <= MAX_BLOCK_LANES or g == 1) and offset % (g * d) == 0
    )
    return rows, group


def norm_rope_route(n: int, heads: int, d: int, offset: int = 0) -> str:
    """"pallas" on a TPU for a shape `norm_rope_plan` tiles, else "xla"
    (the caller's own operations)."""
    if jax.default_backend() != "tpu":
        return "xla"
    return "pallas" if norm_rope_plan(n, heads, d, offset) else "xla"


def rope_tables(freqs: jax.Array) -> tuple[jax.Array, jax.Array]:
    """freqs [N, D/2, 2] (cos, sin of an adjacent pair) as two [N, D]
    tables over lanes: cos for both lanes of a pair, and the sine with
    the sign the pair's other lane takes (-sin on the even lane, +sin
    on the odd one)."""
    n, half, _ = freqs.shape
    cos = jnp.repeat(freqs[..., 0], 2, axis=-1)
    sin = jnp.stack([-freqs[..., 1], freqs[..., 1]], axis=-1).reshape(n, 2 * half)
    return cos.astype(jnp.float32), sin.astype(jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("heads", "offset", "epsilon", "interpret")
)
def norm_rope(
    x: jax.Array, scale: jax.Array, freqs: jax.Array, *,
    heads: int, offset: int = 0, epsilon: float = 1e-6, interpret: bool = False,
) -> jax.Array:
    """x [B, N, W], of which the `heads * D` lanes from `offset` on are
    `heads` heads of D = 2 * freqs.shape[1]; scale [D]; freqs [N, D/2, 2].
    Returns [B, N, heads * D] in x's dtype: each head times
    rsqrt(mean of its squares + epsilon) * scale in float32, rounded to
    x's dtype as the model rounds it between the two steps, then every
    adjacent pair (a, b) rotated to (a cos - b sin, a sin + b cos) in
    float32 and rounded again."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, n, _ = x.shape
    d = 2 * freqs.shape[1]
    plan = norm_rope_plan(n, heads, d, offset)
    if plan is None:
        raise ValueError(
            f"norm_rope: no block for {n} tokens of {heads} heads {d} wide at lane {offset}"
        )
    rows, group = plan
    cos, sin = rope_tables(freqs)
    first = offset // (group * d)

    def kernel(x_ref, scale_ref, cos_ref, sin_ref, o_ref):
        cos, sin, gain = cos_ref[...], sin_ref[...], scale_ref[...]
        even = jax.lax.broadcasted_iota(jnp.int32, cos.shape, 1) % 2 == 0
        for g in range(group):
            lanes = slice(g * d, (g + 1) * d)
            h = x_ref[0, :, lanes].astype(jnp.float32)
            mean_sq = jnp.mean(h * h, axis=-1, keepdims=True)
            h = (h * (jax.lax.rsqrt(mean_sq + epsilon) * gain)).astype(o_ref.dtype)
            h = h.astype(jnp.float32)
            # the pair's other lane: the next one on an even lane, the
            # one before on an odd lane
            other = jnp.where(even, pltpu.roll(h, d - 1, 1), pltpu.roll(h, 1, 1))
            o_ref[0, :, lanes] = (h * cos + other * sin).astype(o_ref.dtype)

    return pl.pallas_call(
        kernel,
        grid=(b, n // rows, heads // group),
        in_specs=[
            pl.BlockSpec((1, rows, group * d), lambda bi, ri, gi: (bi, ri, first + gi)),
            pl.BlockSpec((1, d), lambda bi, ri, gi: (0, 0)),
            # the tables' block does not change with the innermost axis,
            # so a row block's heads share one fetch
            pl.BlockSpec((rows, d), lambda bi, ri, gi: (ri, 0)),
            pl.BlockSpec((rows, d), lambda bi, ri, gi: (ri, 0)),
        ],
        out_specs=pl.BlockSpec((1, rows, group * d), lambda bi, ri, gi: (bi, ri, gi)),
        out_shape=jax.ShapeDtypeStruct((b, n, heads * d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="qk_norm_rope",  # the kernel's name in a device trace
    )(x, scale.astype(jnp.float32).reshape(1, d), cos, sin)
