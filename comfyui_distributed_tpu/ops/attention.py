"""Attention kernels.

`dot_product_attention(q, k, v)` with [B, N, H, D] layout routes to:
- a Pallas flash-attention kernel on TPU (tiled online-softmax); lengths
  off the 128 multiple are padded to lengths the kernel tiles and the
  padded keys masked inside it; or, for the short calls at 64-wide heads
  that `short_wins` names, `ops/short_attention.py`'s whole-key kernel,
- `jax.nn.dot_product_attention` elsewhere (other backends, and the
  shapes `short_wins` and `kernel_wins` leave to XLA).

A causal call (`causal_attention`: a language model's prefill) takes the
same kernel under a causal mask, with a band where the layer has a
window, on a TPU for the shapes `causal_kernel_wins` names, and
`causal_attention_blocked`, XLA operations over blocks of query rows,
elsewhere.

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

# Every block the kernel uses is a multiple of this (the MXU's edge and
# the lane width); a sequence length that is not is padded up to one
# (`flash_plan`) inside `flash_attention`.
ROUTE_MULTIPLE = 128

# Block caps, from the sweep on a v5e (PERF.md §6, PR 28): a grid step has
# a fixed cost of a few tenths of a microsecond (the pipeline's turn, and
# a q block's first and last step set up and divide the carried state), so
# a step should cover as much of the problem as VMEM takes. Wider than
# this the kernel stopped gaining (block_k 2304, 2048) or lost.
MAX_BLOCK_Q = 512
MAX_BLOCK_K = 1536
# Rows of a q block a k step takes at a time: a chunk's scores, max, `exp`,
# partial sums and second product before the next chunk's, so that its
# float32 scores pass through VMEM once. What stretched a step over the
# MXU's time was the one vector-store slot, three stores in four of them
# register spills of scores swept whole four times, and two cross-lane
# reductions a step (`scripts/kernel_bundles.py`; PERF.md §6, PR 51). On a
# v5e, ms a call at no chunks / 256 / 128: SD1.5's 4,096 x 40 1.059 / 0.983
# / 1.162 (1.136 before), FLUX's 4,608 x 128 2.151 / 2.051 / 2.179 (2.314),
# 8,192 causal at 64 over 8 heads 11.15 / 10.59 / 12.14 (11.90): under 256
# the K tiles go into the MXU again for every chunk and the chunks serialise.
ROW_CHUNK = 256
# What one grid step may hold in VMEM by `flash_vmem_bytes`' count. The
# compiler's scoped limit is 16 MiB, and it keeps temporaries of its own.
VMEM_BUDGET = 12 * 2**20
# A q block of a length off `ROUTE_MULTIPLE` is a multiple of this, the
# sublane tile of 16-bit operands (two of 32-bit): q rows only stream
# through the MXU, so 1,296 rows go as 3 x 432 with no padded row at all
# (1.74 ms against 2.04 as 3 x 512 of 1,536; PERF.md §6, PR 33).
ROW_MULTIPLE = 16
# Off the multiple, the kernel takes a call from this many keys on: XLA's
# cost is the float32 scores, 12 bytes a key for every q row, the kernel's
# the lane padding and the copy that pads a narrow head, about the same
# for every row. On a v5e at 64-wide heads (batch 16, PR 60) this kernel
# reads 0.541 ms at 324 keys for XLA's 0.633, and `short_attention`, which
# `short_wins` gives that call and SDXL's other short ones first, 0.307.
MIN_RAGGED_KEYS = 512
# (block_q, block_k) caps of a causal call and of one under a window,
# swept on a v5e (PERF.md §6, PR 43, on the k step as it was until PR 51:
# its stores and reductions cost per q row whatever the k block's width).
# The widest k block wins although the triangle then computes more of the
# square: 8,192 tokens at 64 heads take 16.9 ms as 512 x 512 (136 of 256
# blocks), 11.5 as 512 x 1,024 (72 of 128), 30.3 as 512 x 256. A band is
# best at 512 x 512: 128 keys a row 2.62 ms, 513 over a tail 3.32 (PR 61).
CAUSAL_CAPS = (512, 1024)
BAND_CAPS = (512, 512)
# Under a window shorter than this a causal call stays on XLA: every
# block the kernel computes is crossed by an edge. Two points are timed
# on a v5e, ms a call, kernel / `causal_attention_blocked`: a window of
# 128 over 8,192 tokens (64 heads of 128 over 8 key heads) 2.62 / 2.10
# (3.74 / 2.10 at PR 43), of 31 blocks of 512 x 512 an eighth seen; a
# window of 513 over 8,192 queries and 8,704 keys (64 heads of 256, v
# 128: PR 61) 3.32 / 5.53, of 32 blocks half seen where XLA multiplies
# 255 + 513 keys a row. Between 128 and 513 nothing has been timed.
MIN_BAND_WINDOW = 513


_ROUTE_LOG: contextvars.ContextVar = contextvars.ContextVar(
    "attention_route_log", default=None
)


@contextlib.contextmanager
def route_log():
    """Collect one entry for every `dot_product_attention` call made
    inside the block: `xla NxMxD`, or `flash NxMxD [pad<N'>x<M'>]
    bq<block_q> bk<block_k> <operand dtype> [inplace]` with what the
    kernel chose for the shape (`pad` only where a length was padded,
    `inplace` where it reads the heads where the caller left them, a
    width that is a multiple of 128: `flash 4608x4608x128 bq512 bk1536
    bf16 inplace`), or `short 324x324x64 pad336x384 h20 bq336 bf16
    inplace` (`short_attention.entry`); a causal call logs `flash-causal
    ...` (`causal_attention` has its grammar) or `xla-causal NxMxDq/Dv
    [w<window>] bq<rows> <dtype>`. Calls happen while a
    program is traced, so a block around a jitted call fills only on
    the request that builds the program; the graph's sampler and
    upscale nodes read it into their spans."""
    routes: list[str] = []
    token = _ROUTE_LOG.set(routes)
    try:
        yield routes
    finally:
        _ROUTE_LOG.reset(token)


_DTYPE_NAMES = {"bfloat16": "bf16", "float16": "f16", "float32": "f32"}


def dot_product_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    force_flash: bool | None = None, interpret: bool = False,
    causal: bool = False, scale: float | None = None,
) -> jax.Array:
    """[B, N, H, D] attention; returns [B, N, H, D].

    `causal` (query i sees keys 0..i + M - N), with a value width or a
    `scale` of its own where given, is `causal_attention`'s call: the
    kernel under its mask on a TPU for the shapes `causal_kernel_wins`
    names, else `causal_attention_blocked`, an XLA form.

    `force_flash` overrides backend routing and `interpret` runs the
    Pallas kernel in the interpreter: both exist for tests that pin the
    kernel's numerics on the CPU and are never inferred — on a TPU the
    kernel is compiled, and a kernel that does not compile is an error.

    Head dims that aren't lane-aligned (SD1.5 uses 40/80/160, SDXL 64)
    are zero-padded to the 128 lane width inside `flash_attention`, with
    the softmax scale pinned to the ORIGINAL head dim and the output
    sliced back. The padded lanes cost the MXU nothing: a 40-wide
    head's QK^T is one 128-deep pass and its P.V one 128-wide output
    tile, as a 128-wide head's, and heads cannot share a pass since each
    has its own P. What a narrow head pays is the copy that pads and
    folds it, about 0.16 of a 1.18 ms call at SD1.5's 4,096 x 40
    (ROADMAP S2 has what dropping it would give back; PERF.md §6, PR 51).
    """
    if causal:
        return causal_attention(
            q, k, v, scale=scale, force_flash=force_flash, interpret=interpret)
    if scale is not None or v.shape[-1] != q.shape[-1]:
        raise NotImplementedError(
            "a scale or a value width of its own is implemented for causal attention only"
        )
    route = attention_route(q, k) if force_flash is None else "flash" if force_flash else "xla"
    if route == "short":  # `ops/short_attention.py`: its own kernel, its own entry in the log
        return short_attend(q, k, v, interpret)
    n, m, d = q.shape[1], k.shape[1], q.shape[3]
    pad = -d % ROUTE_MULTIPLE
    log = _ROUTE_LOG.get()
    if log is not None:
        entry = f"{route} {n}x{m}x{d}"
        if route == "flash":
            n_pad, m_pad, block_q, block_k = flash_plan(n, m, d + pad, q.dtype.itemsize)
            name = _DTYPE_NAMES.get(q.dtype.name, q.dtype.name)
            if (n_pad, m_pad) != (n, m):
                entry += f" pad{n_pad}x{m_pad}"
            entry += f" bq{block_q} bk{block_k} {name}" + ("" if pad else " inplace")
        log.append(entry)
    if route != "flash":
        return jax.nn.dot_product_attention(q, k, v)
    return flash_attention(q, k, v, interpret=interpret)


def _check_causal(q: jax.Array, k: jax.Array, v: jax.Array, window=None, block=None) -> None:
    n, m, heads, kv_heads = q.shape[1], k.shape[1], q.shape[2], k.shape[2]
    if n > m:
        raise ValueError(f"causal attention of {n} queries over {m} keys")
    if heads % kv_heads or v.shape[2] != kv_heads:
        raise ValueError(f"{heads} query heads over {kv_heads} / {v.shape[2]} key / value heads")
    if block is not None and (
            window is not None or block < 1 or block & (block - 1) or ROUTE_MULTIPLE % block
            or m % block):
        raise ValueError(
            f"a block mask of {block} positions over {m} keys (window {window}): written for "
            f"a power of two that divides {ROUTE_MULTIPLE} and the keys, and for no window")


def causal_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, scale: float | None = None,
    window: int | None = None, force_flash: bool | None = None, interpret: bool = False,
    block: int | None = None,
) -> jax.Array:
    """Causal attention, q/k [B, N|M, H|H_kv, Dq] and v [B, M, H_kv, Dv]:
    query i sees keys up to i + M - N, under a `window` the last `window`
    of them; under a `block` (a power of two) the mask is causal over
    blocks of that many positions and full inside one: the query whose
    own key is p sees every key j <= p | (block - 1), the rest of its own
    block among them (a model that fills a block in by unmasking: SDAR).
    One computation on two routes, chosen from the operands and
    the backend alone (`causal_route`): `flash_attention` under its causal
    mask, or `causal_attention_blocked`, the form every other backend
    keeps and the kernel's tests compare with. `force_flash` and
    `interpret` are the tests', as in `dot_product_attention`. A call
    that takes the kernel logs `flash-causal NxMxDq/Dv [pad<N'>x<M'>]
    [w<window>] g<query heads a key head> bq<block_q> bk<block_k> <dtype>
    [b<block>] [inplace] blocks<computed>/<square>`: the last is how much
    of the square of blocks the grid computes."""
    _check_causal(q, k, v, window, block)
    if force_flash is None:
        force_flash = causal_route(q, k, v, window) == "flash"
    if not force_flash:
        return causal_attention_blocked(q, k, v, scale=scale, window=window, block=block)
    log = _ROUTE_LOG.get()
    if log is not None:
        n, m, d, dv = q.shape[1], k.shape[1], q.shape[3], v.shape[3]
        pad, pad_v = -d % ROUTE_MULTIPLE, -dv % ROUTE_MULTIPLE
        n_pad, m_pad, block_q, block_k = flash_plan(
            n, m, max(d + pad, dv + pad_v), q.dtype.itemsize, causal=True, window=window)
        _, computed = causal_blocks(n_pad, block_q, block_k, n, m, window)
        entry = f"flash-causal {n}x{m}x{d}/{dv}"
        if (n_pad, m_pad) != (n, m):
            entry += f" pad{n_pad}x{m_pad}"
        if window is not None:
            entry += f" w{window}"
        if block is not None:
            entry += f" b{block}"
        entry += f" g{q.shape[2] // k.shape[2]} bq{block_q} bk{block_k}"
        entry += f" {_DTYPE_NAMES.get(q.dtype.name, q.dtype.name)}"
        entry += "" if pad and pad_v else " inplace"
        log.append(f"{entry} blocks{computed}/{n_pad // block_q * (m_pad // block_k)}")
    return flash_attention(
        q, k, v, scale=scale, interpret=interpret, causal=True, window=window, block=block)


# Query rows a block of `causal_attention_blocked` takes: its float32
# scores are heads x this x M at most (128 heads over 2,048 keys: 268 MB).
CAUSAL_BLOCK_Q = 256


def causal_attention_blocked(
    q: jax.Array, k: jax.Array, v: jax.Array, *, scale: float | None = None,
    window: int | None = None, block: int | None = None,
) -> jax.Array:
    """Causal attention, q/k [B, N|M, H, Dq] and v [B, M, H, Dv] with a
    width of its own, as XLA operations over blocks of query rows: a
    block of rows takes only the keys up to its last row (the blocks
    above the diagonal are skipped, the block on it is masked), so the
    score tensor is never whole in memory. Under a `window` a row sees
    only the last `window` keys up to its own (itself among them), and a
    block takes only the keys its band reaches, `window` - 1 before its
    first row: the rest are skipped, not masked. Under a `block` a row
    sees up to the end of its own key's block of that many positions, and
    a block of rows takes the keys up to its last row's. Scores, softmax
    and both accumulations are float32; the probabilities are rounded to v's
    dtype for the second product, as the kernel does. k and v may have
    fewer heads than q, a divisor of its count: key head j then serves
    the query heads j x group .. (j + 1) x group - 1, and is read where
    it lies, not repeated."""
    _check_causal(q, k, v, window, block)
    n, m, d = q.shape[1], k.shape[1], q.shape[3]
    heads, kv_heads = q.shape[2], k.shape[2]
    ahead = 0 if block is None else block - 1  # `own | ahead`: the last key of own's block
    if kv_heads == heads:
        to_scores, to_out = "bqhd,bkhd->bhqk", "bhqk,bkhd->bqhd"
    else:  # a group axis g beside the key head h
        q = q.reshape(q.shape[0], n, kv_heads, heads // kv_heads, d)
        to_scores, to_out = "bqhgd,bkhd->bhgqk", "bhgqk,bkhd->bqhgd"
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    rows_q = min(CAUSAL_BLOCK_Q, n)  # the last block is short where n is no multiple
    log = _ROUTE_LOG.get()
    if log is not None:
        name = _DTYPE_NAMES.get(q.dtype.name, q.dtype.name)
        banded = "" if window is None else f" w{window}"
        banded += "" if block is None else f" b{block}"
        log.append(f"xla-causal {n}x{m}x{d}/{v.shape[3]}{banded} bq{rows_q} {name}")
    outs = []
    for start in range(0, n, rows_q):
        stop = min(start + rows_q, n)
        last = (stop + m - n - 1 | ahead) + 1  # keys the block's last row sees
        # the first key the block's first row sees
        first = 0 if window is None else max(start + m - n - window + 1, 0)
        scores = scale * jnp.einsum(
            to_scores, q[:, start:stop], k[:, first:last], preferred_element_type=jnp.float32)
        rows = jnp.arange(start, stop)[:, None] + (m - n)
        cols = jnp.arange(first, last)[None, :]
        seen = (rows | ahead if ahead else rows) >= cols
        if window is not None:
            seen &= rows - cols < window
        scores = jnp.where(seen, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        outs.append(jnp.einsum(
            to_out, probs, v[:, first:last], preferred_element_type=jnp.float32,
        ).astype(v.dtype))
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
    return out.reshape(out.shape[0], n, heads, v.shape[3])


def attention_route(q: jax.Array, k: jax.Array) -> str:
    """The implementation `dot_product_attention` gives these operands:
    "short" or "flash" (a Pallas kernel: a TPU backend and a shape that
    `short_wins`, asked first, or `kernel_wins` names) or "xla"."""
    if not _kernel_allowed():
        return "xla"
    return short_route(q, k) or ("flash" if kernel_wins(q.shape[1], k.shape[1]) else "xla")


def _kernel_allowed() -> bool:
    if os.environ.get("CDT_FLASH") == "0":  # kill switch
        return False
    return jax.default_backend() == "tpu"


def causal_route(q: jax.Array, k: jax.Array, v: jax.Array, window: int | None = None) -> str:
    """The implementation `causal_attention` gives these operands: "flash"
    (a TPU backend and a shape `causal_kernel_wins` names) or "xla"."""
    if not _kernel_allowed():
        return "xla"
    return "flash" if causal_kernel_wins(k.shape[1], v.shape[3], window) else "xla"


def causal_kernel_wins(m: int, v_width: int, window: int | None = None) -> bool:
    """Whether a causal call over m keys with values `v_width` wide goes
    to the kernel on a TPU: a value width on the lane tile (the output is
    written where the caller reads it; q and k of another width are padded
    where they lie) from `MIN_RAGGED_KEYS` keys on, where the XLA form's
    float32 scores cost more than the kernel's steps (PERF.md §6, PR 43),
    a narrower one from `narrow_keys(v_width)` on (PR 54, at this file's
    end), and no window shorter than `MIN_BAND_WINDOW`."""
    if window is not None and window < MIN_BAND_WINDOW:
        return False
    return m >= (MIN_RAGGED_KEYS if v_width % ROUTE_MULTIPLE == 0 else narrow_keys(v_width))


def kernel_wins(n: int, m: int) -> bool:
    """Whether q of n rows over m keys goes to the kernel on a TPU: both
    lengths whole multiples of `ROUTE_MULTIPLE` (as since PR 28: nothing
    is padded), or `MIN_RAGGED_KEYS` keys or more. SD1.5's 77-key
    cross-attentions stay on XLA by this (SDXL's short calls: `short_wins`)."""
    if n <= 0 or m <= 0:
        return False
    return (n % ROUTE_MULTIPLE == 0 and m % ROUTE_MULTIPLE == 0) or m >= MIN_RAGGED_KEYS


def _largest_block(length: int, cap: int) -> int:
    """The largest multiple of `ROUTE_MULTIPLE` that divides `length`
    and is at most `cap` (`length` is such a multiple itself)."""
    for block in range(min(cap, length), 0, -ROUTE_MULTIPLE):
        if length % block == 0:
            return block
    raise ValueError(f"{length} is not a multiple of {ROUTE_MULTIPLE}")


def flash_vmem_bytes(block_q: int, block_k: int, d: int, itemsize: int) -> int:
    """VMEM one grid step of the kernel holds: the q, k, v and output
    blocks (double-buffered by the pipeline), the float32 accumulator,
    the running max and sum (a lane tile wide each), and the step's
    float32 scores, their `exp`, and `p` in the operands' dtype (a bound:
    since PR 51 a step holds them a `ROW_CHUNK` of rows at a time)."""
    blocks = 2 * (2 * block_q + 2 * block_k) * d * itemsize
    carried = block_q * (d + 2 * ROUTE_MULTIPLE) * 4
    scores = block_q * block_k * (4 + 4 + itemsize)
    return blocks + carried + scores


def _tile(length: int, cap: int, step: int) -> tuple[int, int]:
    """(padded length, block) of one axis under `cap`. A multiple of
    `ROUTE_MULTIPLE` is never padded and takes its largest divisor, as
    since PR 28. Any other length takes the fewest blocks the cap
    allows, each the smallest multiple of `step` that covers its share,
    so the padding is less than one `step` a block."""
    if length % ROUTE_MULTIPLE == 0:
        return length, _largest_block(length, cap)
    blocks = -(-length // cap)
    block = -(-length // (blocks * step)) * step
    return blocks * block, block


def flash_plan(
    n: int, m: int, d: int, itemsize: int, causal: bool = False, window: int | None = None,
) -> tuple[int, int, int, int]:
    """(padded n, padded m, block_q, block_k) for q of n rows, k/v of m
    rows, heads d wide (as padded) and operands of `itemsize` bytes:
    each axis tiled (`_tile`) under the caps the sweep found, the caps
    shrunk (k first: it is only streamed) until a step fits
    `VMEM_BUDGET`. 1,296 x 1,296 goes as 1,296 x 1,408 (3 x 432 by 1 x
    1,408), the VAE's 5,184 x 5,184 at d 512 as 5,280 x 5,376 (11 x 480
    by 6 x 896). Depends on nothing else, so VMEM never grows with m.
    A causal call's caps are its own (`CAUSAL_CAPS`, under a window
    `BAND_CAPS`): its grid covers a triangle or a band, not a square."""
    if n <= 0 or m <= 0:
        # fail loudly: a zero-length inner grid would silently return
        # an UNWRITTEN output buffer (the finalize step never fires)
        raise ValueError(f"flash_attention needs queries and keys, got N={n}, M={m}")
    cap_q, cap_k = MAX_BLOCK_Q, MAX_BLOCK_K
    if causal:
        cap_q, cap_k = CAUSAL_CAPS if window is None else BAND_CAPS
    while True:
        n_pad, block_q = _tile(n, cap_q, ROW_MULTIPLE)
        m_pad, block_k = _tile(m, cap_k, ROUTE_MULTIPLE)
        if flash_vmem_bytes(block_q, block_k, d, itemsize) <= VMEM_BUDGET:
            return n_pad, m_pad, block_q, block_k
        if block_k > ROUTE_MULTIPLE:
            cap_k = block_k - ROUTE_MULTIPLE
        elif block_q > ROUTE_MULTIPLE:
            cap_q = block_q - ROUTE_MULTIPLE
        else:
            raise ValueError(
                f"no flash_attention block of head width {d} x {itemsize} "
                f"bytes fits {VMEM_BUDGET} bytes of VMEM"
            )


# What a causal call's kernel writes over a score no row of the block may
# see. Finite, so a row whose first blocks lie wholly before its band
# (a window shorter than the q block) carries a finite running max until
# its own keys come: `exp(MASKED - real)` is an exact zero, which wipes
# what the masked blocks summed, and no `-inf - -inf` is ever formed.
MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)


def key_block_range(xp, qi, block_q: int, block_k: int, rows: int, keys: int,
                    window: int | None):
    """(first, last) k block that q block `qi` of a causal call sees:
    query i of `rows` sees keys max(0, i + keys - rows - window + 1) ..
    i + keys - rows, so the block's first row gives the first key and
    its last row (no later than the last true key) the last. `xp` is
    numpy for the plan's counts and jax.numpy inside the kernel and its
    index maps: the same arithmetic on whole numbers or traced ones."""
    offset = keys - rows
    last = xp.minimum(qi * block_q + block_q - 1 + offset, keys - 1) // block_k
    if window is None:
        return 0 * last, last
    return xp.maximum(qi * block_q + offset - window + 1, 0) // block_k, last


def causal_blocks(n: int, block_q: int, block_k: int, rows: int, keys: int,
                  window: int | None) -> tuple[int, int]:
    """(k blocks the widest q block sees: the inner grid's extent; blocks
    computed over all q blocks) of a causal call padded to n rows."""
    import numpy as np

    first, last = key_block_range(
        np, np.arange(n // block_q), block_q, block_k, rows, keys, window)
    seen = last - first + 1
    return int(seen.max()), int(seen.sum())


@functools.partial(
    jax.jit, static_argnames=("interpret", "scale", "causal", "window", "block"))
def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    scale: float | None = None, interpret: bool = False,
    causal: bool = False, window: int | None = None, block: int | None = None,
) -> jax.Array:
    """Tiled online-softmax attention (Pallas).

    Grid: (batch*heads, N/block_q, M/block_k), the blocks chosen from
    the shape by `flash_plan`, with K/V STREAMED one (block_k, D)
    block per grid step — VMEM holds one K and one V block at a time
    regardless of sequence length (long-video sequences would blow VMEM
    if the whole K/V were block-resident). The online max/denominator/
    accumulator live in VMEM scratch carried across the innermost
    (sequential, "arbitrary") grid dimension; the output block is
    written on the last K step.

    A k step walks its q block `ROW_CHUNK` rows at a time, and carries
    its softmax state a lane tile wide: every lane of a row of the
    running max holds the row's max (the VPU's max over the step's lane
    tiles of scores, then the one cross-lane reduction a step, which
    comes back replicated), so the correction multiplies accumulator
    and sum without a broadcast; lane l of the running sum holds the sum
    of `p` over the columns l, l + 128, ... seen so far, added a lane
    tile at a time on the VPU, and is reduced across lanes once a q
    block, before the divide. Float32 throughout, as before: only the
    order of the float32 sum differs.

    Heads are read and written where the caller left them: q, k, v and
    the output are [B, N, H*D] to the kernel (a reshape of the two minor
    axes, no data moves) and grid index `bh` takes the (1, block, D)
    block at (bh // H, row block, bh % H), so nothing is transposed in
    HBM on either side. A block's rows are then D lanes out of H*D: on
    a v5e the kernel itself runs 6-11 % slower for it at FLUX's 24 x 128
    (2.07-2.18 ms a call for 1.96), and the four transpositions it no
    longer needs cost 0.36 ms (PERF.md §6, PR 35). A width off the
    lane tile is another case: its pad rewrites every byte anyway, and
    the copy that pads also puts the heads in front of the tokens (a
    tiled layout holds 40 lanes in 128, so the compiler pads for free
    while it transposes), after which the kernel takes [B*H, N, D] and
    reads whole rows. In place such a head costs three passes an operand
    instead of one (SD1.5's 4,096 x 40: 1.36 ms a call in place, 1.15
    folded; its closed2 cell 3.5 % slower), so `fold` below follows
    from the widths and nothing else.

    Both dots take their operands in the dtype they arrive in (bfloat16
    on every served path, whose products are exact in float32) and
    accumulate in float32; `p` is rounded to v's dtype for the second.
    Scale, max, `exp`, sum and correction stay float32.

    Lengths that are no multiples of `ROUTE_MULTIPLE` are zero-padded
    here to the lengths `flash_plan` gives, a narrow head's lanes in
    the same pad; `scale` defaults to the true width's. Padded query rows are
    computed and sliced away. Padded keys are masked inside the kernel:
    the scores of the columns from the true length on are set to -inf
    before the running max, so a padded key weighs an exact zero (the
    select costs nothing measurable: 1.727 ms with it, 1.739 without,
    PR 33). Mask, pad and slice are emitted for such a call only.

    `causal`: query i of N sees keys up to i + M - N, under a `window`
    only the last `window` of them (its own among them). The inner grid
    axis then counts the k blocks a q block sees, from the first one
    (`key_block_range`): a step past the q block's last one does no
    arithmetic and fetches nothing, since the k/v index maps hold the
    last block's index there and a block index that repeats is not
    copied again. Of the blocks computed, only those the diagonal or the
    band's lower edge crosses build the comparison (`MASKED` says why
    its value is finite); a block every row sees whole runs the code a
    call without a mask runs. k and v may have fewer heads than q, a
    divisor of its count (query head h reads key head h // group through
    the index maps: nothing is repeated in HBM), and v a width of its
    own, which is the output's: q and k of a width off the lane tile are
    padded where they lie when v's is on it (DeepSeek-V2's 192 beside
    128). Under a `block` (`causal_attention` says which: a power of two
    that divides the lane tile, so that a block of positions never
    straddles two k blocks) a row sees to the end of its own key's block:
    the k blocks a q block sees (`key_block_range`) and those the diagonal
    crosses are the causal call's own, and only the comparison in a
    crossed block differs. Clamp, comparison, head map and the second
    width are emitted for a call that has them only: every other call traces to the
    program it traced to before they existed (tests/test_causal_attention.py).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, rows, h, width = q.shape
    keys, kv_heads, v_width = k.shape[1], k.shape[2], v.shape[3]
    group = h // kv_heads
    d = width + -width % ROUTE_MULTIPLE
    dv = v_width + -v_width % ROUTE_MULTIPLE
    n, m, block_q, block_k = flash_plan(
        rows, keys, max(d, dv), q.dtype.itemsize, causal=causal, window=window)
    if scale is None:
        scale = 1.0 / math.sqrt(width)
    if (n, d) != (rows, width):
        q = jnp.pad(q, ((0, 0), (0, n - rows), (0, 0), (0, d - width)))
    if (m, d) != (keys, width):
        k = jnp.pad(k, ((0, 0), (0, m - keys), (0, 0), (0, d - width)))
    if (m, dv) != (keys, v_width):
        v = jnp.pad(v, ((0, 0), (0, m - keys), (0, 0), (0, dv - v_width)))

    # padded heads go in front of the tokens: the docstring says why
    fold = d > width and dv > v_width
    if fold:
        q, k, v = (
            x.transpose(0, 2, 1, 3).reshape(b * x.shape[2], x.shape[1], x.shape[3])
            for x in (q, k, v))
    else:
        q, k, v = (x.reshape(b, x.shape[1], x.shape[2] * x.shape[3]) for x in (q, k, v))
    lanes = 1 if fold else h  # heads side by side in a row of the kernel's operands

    num_k_blocks = m // block_k
    if causal:
        span = (block_q, block_k, rows, keys, window)
        num_k_blocks, _ = causal_blocks(n, *span)
    contract_last = (((1,), (1,)), ((), ()))  # q @ k.T without the transpose

    def kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, max_ref, sum_ref):
        ki = pl.program_id(2)

        @pl.when(ki == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            max_ref[...] = jnp.full_like(max_ref, -jnp.inf)
            sum_ref[...] = jnp.zeros_like(sum_ref)

        def lane_tiles(x):
            return [x[:, at:at + ROUTE_MULTIPLE] for at in range(0, x.shape[1], ROUTE_MULTIPLE)]

        def lanes_wide(x, width):  # a lane-replicated [rows, 128] as [rows, width]
            return x if width == ROUTE_MULTIPLE else jnp.tile(x, (1, width // ROUTE_MULTIPLE))

        def update(mask):
            """One k block into the running max, sum and accumulator;
            `mask(scores, first row)` where some score of the block is not
            seen."""
            vb = v_ref[0]                                # [block_k, Dv]
            for start in range(0, block_q, ROW_CHUNK):
                chunk = slice(start, min(start + ROW_CHUNK, block_q))
                scores = scale * jax.lax.dot_general(    # [chunk, block_k]
                    q_ref[0, chunk], k_ref[0], contract_last,
                    preferred_element_type=jnp.float32,
                )
                if mask is not None:
                    scores = mask(scores, start)
                row_max = max_ref[chunk]                 # every lane the row's
                new_max = jnp.maximum(row_max, functools.reduce(
                    jnp.maximum, lane_tiles(scores)).max(axis=-1, keepdims=True))
                correction = jnp.exp(row_max - new_max)
                p = jnp.exp(scores - lanes_wide(new_max, block_k))
                sum_ref[chunk] = sum_ref[chunk] * correction + functools.reduce(
                    jnp.add, lane_tiles(p))
                acc_ref[chunk] = acc_ref[chunk] * lanes_wide(correction, dv) + jnp.dot(
                    p.astype(vb.dtype), vb, preferred_element_type=jnp.float32)
                max_ref[chunk] = new_max

        def padded_keys(scores, start):
            # the last k block's tail is padding; it always holds a key too
            # (`_tile` pads less than a block), so no row is -inf throughout
            cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
            return jnp.where(cols < keys, scores, -jnp.inf)

        if not causal:
            update(padded_keys if m > keys else None)
        else:
            qi = pl.program_id(1)
            first, last = key_block_range(jnp, qi, *span)
            kb = first + ki  # the k block in the buffers, where it is one this q block sees
            seen = kb <= last
            top = qi * block_q + (keys - rows)  # the last key the block's first row sees
            crossed = kb * block_k + block_k - 1 > top  # by the diagonal
            if window is not None:  # or by the band's lower edge, the last row's
                crossed |= kb * block_k < top + block_q - window

            def band(scores, start):
                row = qi * block_q + start + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
                if n > rows:  # a padded row sees what the last true row sees
                    row = jnp.minimum(row, rows - 1)
                own = row + (keys - rows)  # the row's own key: padded keys lie past it
                if block is not None:  # to the end of its block, which the keys hold whole
                    own |= block - 1
                cols = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
                visible = cols <= own
                if window is not None:
                    visible &= cols > own - window
                return jnp.where(visible, scores, MASKED)

            pl.when(seen & crossed)(lambda: update(band))
            pl.when(seen & ~crossed)(lambda: update(None))

        @pl.when(ki == num_k_blocks - 1)
        def _finalize():
            total = sum_ref[...].sum(axis=-1, keepdims=True)
            o_ref[0] = (acc_ref[...] / total).astype(o_ref.dtype)

    def q_map(bh, qi, ki):
        return bh // lanes, qi, bh % lanes

    def kv_map(bh, qi, ki):
        if causal:
            first, last = key_block_range(jnp, qi, *span)
            ki = jnp.minimum(first + ki, last)
        if group == 1:
            return bh // lanes, ki, bh % lanes
        # bh counts (batch, query head): folded, key heads lie in that order too
        return (bh // group, ki, 0) if fold else (bh // h, ki, bh % h // group)

    out = pl.pallas_call(
        kernel,
        grid=(b * h, n // block_q, num_k_blocks),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, dv), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, dv), q_map),
        out_shape=jax.ShapeDtypeStruct((*q.shape[:2], lanes * dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),  # acc
            pltpu.VMEM((block_q, ROUTE_MULTIPLE), jnp.float32),  # running max, lane-replicated
            pltpu.VMEM((block_q, ROUTE_MULTIPLE), jnp.float32),  # running sum, a partial a lane
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        # the kernel's name in a device trace; a causal call's is its own
        name="flash_attention_causal" if causal else "flash_attention",
    )(q, k, v)

    if fold:
        out = out.reshape(b, h, n, dv).transpose(0, 2, 1, 3)
    else:
        out = out.reshape(b, n, h, dv)
    return out[:, :rows, :, :v_width] if (n, dv) != (rows, v_width) else out


# --- a causal call narrower than the lane tile (PR 54) ----------------------
# Down here so that no line above moved: the kernel's source lines are part
# of what a compiled program is cached by, and a shift rebuilds every
# kernel-carrying program of every cell once.
#
# A causal call whose values are `NARROW_WIDTH` wide takes the kernel from
# `MIN_NARROW_KEYS` keys on, its heads padded to the lane tile and folded in
# front of the tokens (`flash_attention`'s `fold`): half of every MXU pass
# is then zeros, but `causal_attention_blocked` writes its heads' float32
# scores of a block of rows to HBM, 12 bytes a key and row and more, and at
# 65,536 keys one block of 256 rows of 32 heads is 2.1 GB. On a v5e, 8,192
# queries of 32 heads over 8 key heads of 64 (granite-4.0-h-micro's parts;
# PERF.md §6, PR 54), ms a call, kernel / XLA blocks: 5.26 / 17.44 at 8,192
# keys, 26.56 / 130.89 at 32,768, 55.16 at 65,536. Below 8,192 keys, and at
# any other width off the tile, no such call has been timed: they stay on XLA.
NARROW_WIDTH = 64
MIN_NARROW_KEYS = 8192


def narrow_keys(v_width: int) -> float:
    """The keys from which a causal call with values `v_width` wide, off
    the lane tile, goes to the kernel on a TPU: never, but at `NARROW_WIDTH`."""
    return MIN_NARROW_KEYS if v_width == NARROW_WIDTH else math.inf


# --- the short calls' own kernel (PR 60) ------------------------------------
# Down here for the same reason. A non-causal call at 64-wide heads whose
# keys fit one block goes to `ops/short_attention.py` where that kernel was
# timed on the chip and won (`short_attention.short_wins`); the module is
# imported at the call, since it imports this one.


def short_route(q: jax.Array, k: jax.Array) -> str | None:
    """ "short" where `short_attention.short_wins` names the operands'
    shape and dtype, else None: `attention_route` then asks `kernel_wins`."""
    from . import short_attention

    n, heads, width = q.shape[1:]
    return "short" if short_attention.short_wins(n, k.shape[1], heads, width, q.dtype) else None


def short_attend(q: jax.Array, k: jax.Array, v: jax.Array, interpret: bool = False) -> jax.Array:
    """`short_attention` on [B, N, H, 64] operands, its entry logged
    (`interpret` is the tests' and the rehearsal's, as above)."""
    from . import short_attention

    log = _ROUTE_LOG.get()
    if log is not None:
        log.append(short_attention.entry(q.shape[1], k.shape[1], q.shape[2], q.dtype))
    return short_attention.short_attention(q, k, v, interpret=interpret)


# --- what a causal call's route multiplies (PR 61) ---------------------------
# Down here for the same reason.


def causal_pairs_computed(route: str, n: int, m: int, d: int, dv: int, itemsize: int,
                          window: int | None = None) -> int:
    """The query-key pairs a head of a causal call of n queries over m
    keys (widths d and dv) multiplies on `route` ("flash" or "xla"), from
    the route's own blocks: the kernel's computed blocks at its plan's
    sizes, or `causal_attention_blocked`'s blocks of rows, each over the
    keys from its first row's first to its last row's last. What the
    mask lets a row see is the caller's to count."""
    if route == "flash":
        pad, pad_v = -d % ROUTE_MULTIPLE, -dv % ROUTE_MULTIPLE
        n_pad, _, block_q, block_k = flash_plan(
            n, m, max(d + pad, dv + pad_v), itemsize, causal=True, window=window)
        return causal_blocks(n_pad, block_q, block_k, n, m, window)[1] * block_q * block_k
    rows_q, pairs = min(CAUSAL_BLOCK_Q, n), 0
    for start in range(0, n, rows_q):
        stop = min(start + rows_q, n)
        first = 0 if window is None else max(start + m - n - window + 1, 0)
        pairs += (stop - start) * (stop + m - n - first)
    return pairs
