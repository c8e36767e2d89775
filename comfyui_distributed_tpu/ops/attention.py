"""Attention kernels.

`dot_product_attention(q, k, v)` with [B, N, H, D] layout routes to:
- a Pallas flash-attention kernel on TPU (tiled online-softmax — the
  memory-bound op worth hand-writing; everything else is left to XLA),
- `jax.nn.dot_product_attention` elsewhere (other backends, tiny
  shapes, and shapes that don't tile cleanly).

The reference has no attention code at all (torch/ComfyUI provides
it); this is new TPU-native surface.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import math
import os

import jax
import jax.numpy as jnp

# Flash kernel tiling. Block sizes keep the (Bq x D) @ (D x Bk) matmuls on
# MXU-friendly 128 boundaries. Env-tunable (CDT_FLASH_BQ / CDT_FLASH_BK)
# so the block sweep can re-run on real hardware without edits.
BLOCK_Q = int(os.environ.get("CDT_FLASH_BQ", 128))
BLOCK_K = int(os.environ.get("CDT_FLASH_BK", 128))


_ROUTE_LOG: contextvars.ContextVar = contextvars.ContextVar(
    "attention_route_log", default=None
)


@contextlib.contextmanager
def route_log():
    """Collect ("flash" | "xla", n, m, d) for every
    `dot_product_attention` call made inside the block. Calls happen
    while a program is traced, so a block around a jitted call fills
    only on the request that builds the program; the graph's sampler
    node reads it into its span."""
    routes: list[tuple[str, int, int, int]] = []
    token = _ROUTE_LOG.set(routes)
    try:
        yield routes
    finally:
        _ROUTE_LOG.reset(token)


def dot_product_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    force_flash: bool | None = None, interpret: bool = False,
) -> jax.Array:
    """[B, N, H, D] attention; returns [B, N, H, D].

    `force_flash` overrides backend routing and `interpret` runs the
    Pallas kernel in the interpreter: both exist for tests that pin the
    kernel's numerics on the CPU and are never inferred — on a TPU the
    kernel is compiled, and a kernel that does not compile is an error.

    Head dims that aren't lane-aligned (SD1.5 uses 40/80/160) are
    zero-padded to the 128 lane width before the kernel — the MXU pads
    those lanes anyway, so this costs nothing extra — with the softmax
    scale pinned to the ORIGINAL head dim and the output sliced back.
    """
    use_flash = (
        attention_route(q, k) == "flash" if force_flash is None else force_flash
    )
    log = _ROUTE_LOG.get()
    if log is not None:
        log.append(
            ("flash" if use_flash else "xla", q.shape[1], k.shape[1], q.shape[3])
        )
    if use_flash:
        d = q.shape[3]
        if d % 128 != 0:
            pad = -d % 128
            widths = ((0, 0), (0, 0), (0, 0), (0, pad))
            out = flash_attention(
                jnp.pad(q, widths), jnp.pad(k, widths), jnp.pad(v, widths),
                scale=1.0 / math.sqrt(d), interpret=interpret,
            )
            return out[..., :d]
        return flash_attention(q, k, v, interpret=interpret)
    return jax.nn.dot_product_attention(q, k, v)


def attention_route(q: jax.Array, k: jax.Array) -> str:
    """The implementation `dot_product_attention` gives these operands:
    "flash" (the Pallas kernel: a TPU backend and both sequence lengths
    whole multiples of the block) or "xla"."""
    if os.environ.get("CDT_FLASH") == "0":  # kill switch
        return "xla"
    if jax.default_backend() != "tpu":
        return "xla"
    n, m = q.shape[1], k.shape[1]
    if n % BLOCK_Q == 0 and m % BLOCK_K == 0 and n >= BLOCK_Q:
        return "flash"
    return "xla"


@functools.partial(jax.jit, static_argnames=("interpret", "scale"))
def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    scale: float | None = None, interpret: bool = False,
) -> jax.Array:
    """Tiled online-softmax attention (Pallas).

    Grid: (batch*heads, N/BLOCK_Q, M/BLOCK_K) with K/V STREAMED one
    (BLOCK_K, D) block per grid step — VMEM holds one K and one V block
    at a time regardless of sequence length (long-video sequences
    would blow VMEM if the whole K/V were block-resident). The online
    max/denominator/accumulator live in VMEM scratch carried across
    the innermost (sequential, "arbitrary") grid dimension; the output
    block is written on the last K step.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, n, h, d = q.shape
    m = k.shape[1]
    if n % BLOCK_Q != 0 or m % BLOCK_K != 0:
        # fail loudly: a zero-length inner grid would silently return
        # an UNWRITTEN output buffer (the finalize step never fires)
        raise ValueError(
            f"flash_attention needs N%{BLOCK_Q}==0 and M%{BLOCK_K}==0, "
            f"got N={n}, M={m}; route via dot_product_attention instead"
        )
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    # Fold batch and heads; kernel works on [N, D] per (bh, qblock).
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, n, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, m, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, m, d)

    num_k_blocks = m // BLOCK_K

    def kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, max_ref, sum_ref):
        ki = pl.program_id(2)

        @pl.when(ki == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            max_ref[...] = jnp.full_like(max_ref, -jnp.inf)
            sum_ref[...] = jnp.zeros_like(sum_ref)

        qb = q_ref[0].astype(jnp.float32) * scale   # [BLOCK_Q, D]
        kb = k_ref[0].astype(jnp.float32)           # [BLOCK_K, D]
        vb = v_ref[0].astype(jnp.float32)
        scores = jnp.dot(qb, kb.T, preferred_element_type=jnp.float32)
        row_max = max_ref[...]
        new_max = jnp.maximum(row_max, scores.max(axis=-1, keepdims=True))
        correction = jnp.exp(row_max - new_max)
        p = jnp.exp(scores - new_max)
        acc_ref[...] = acc_ref[...] * correction + jnp.dot(
            p, vb, preferred_element_type=jnp.float32
        )
        sum_ref[...] = sum_ref[...] * correction + p.sum(
            axis=-1, keepdims=True
        )
        max_ref[...] = new_max

        @pl.when(ki == num_k_blocks - 1)
        def _finalize():
            o_ref[0] = (acc_ref[...] / sum_ref[...]).astype(o_ref.dtype)

    out = pl.pallas_call(
        kernel,
        grid=(b * h, n // BLOCK_Q, num_k_blocks),
        in_specs=[
            pl.BlockSpec((1, BLOCK_Q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, BLOCK_K, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, BLOCK_K, d), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, BLOCK_Q, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, n, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((BLOCK_Q, d), jnp.float32),  # acc
            pltpu.VMEM((BLOCK_Q, 1), jnp.float32),  # running max
            pltpu.VMEM((BLOCK_Q, 1), jnp.float32),  # running sum
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention",  # the kernel's name in a device trace
    )(qf, kf, vf)

    return out.reshape(b, h, n, d).transpose(0, 2, 1, 3)
