"""KDA's delta rule, as the models with such layers share it
(`solar_open2.py`, `ling_flash.py`): the recurrence

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T,   o_t = S_t^T q_t

with alpha_t = exp(g_t) a decay a channel, in two forms that must agree:
`kda_chunked` (a prefill: chunks of tokens in order carrying S, a
unit-triangular solve inside each chunk; on a TPU one Pallas kernel
that holds S in VMEM across the chunks, `ops/kda_delta.py`, elsewhere
`kda_chunked_scan`, a `lax.scan` over XLA operations and the kernel's
reference; `kda_delta.kda_delta_route` says which, a model's `report`
repeats it as `kda_form`) and `kda_step` (a decode step: the recurrence
itself); and what both models put in front of it, the causal depth-wise
convolution of the q, k and v projections (`conv_qkv`). Gates,
cumulative sums, the solve and S are float32; the large products take
their operands in the storage dtype and accumulate in float32. How g
and beta are made of the input is each model's own.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..ops import kda_delta
from .lm_common import rms_norm, short_conv

# Rows of a chunk whose pairwise decays are formed pair by pair
# (`decay_products`); between such blocks they go through one product.
KDA_SUBCHUNK = kda_delta.SUBCHUNK
L2_EPS = 1e-6


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def conv_qkv(projected, filters, tail, heads: int, d: int):
    """q, k (unit length; q times d^-1/2) and v, [T, H, d] in the
    projections' dtype, of `projected` [T, 3 H d] (q, k and v side by
    side), `filters` [kernel, 3 H d] and `tail` [kernel - 1, 3 H d], the
    convolution's inputs of the tokens before: a causal depth-wise
    convolution, SiLU, the norms, all float32. Also returns the inputs
    themselves, tail first, [kernel - 1 + T, 3 H d]: the rows after
    token t's are the tail token t leaves."""
    tokens = projected.shape[0]
    mixed, window = short_conv(projected, filters, tail)
    q, k, v = jnp.split(jax.nn.silu(mixed).reshape(tokens, 3 * heads, d), 3, axis=1)
    q, k, v = (a.astype(projected.dtype) for a in (_l2norm(q) * d ** -0.5, _l2norm(k), v))
    return q, k, v, window


def gated_output(o, gate, o_norm, w_o, eps: float):
    """y [T, hidden] of the rule's outputs o [T, H, d] float32: each
    head normed over its d channels (scale `o_norm` [d]), gated by
    `gate` [T, H, d], projected by `w_o`."""
    normed = rms_norm(o, o_norm, eps) * gate.astype(jnp.float32)
    return normed.astype(gate.dtype).reshape(o.shape[0], -1) @ w_o


def decay_products(x, k, decay, sub: int = KDA_SUBCHUNK):
    """M[..., i, j] = sum_c x[..., i, c] k[..., j, c] exp(G[..., i, c] -
    G[..., j, c]) for j <= i, 0 above the diagonal; k and `decay` (G:
    cumulative log-decays along the row axis, never increasing) are
    [..., C, c] float32, x the same or with further leading axes. Every
    ratio of decays is the exponential of a difference that is <= 0,
    never exp(-G_j) alone: within a block of `sub` rows the differences
    are formed pair by pair; a row block's products with the columns of
    earlier blocks go through the decay at the block's first row,
    exp(G_i - G_first) exp(G_first - G_j), both factors at most one."""
    size, width = k.shape[-2:]
    sub = min(sub, size)
    blocks = size // sub

    def blocked(a):
        return a.reshape(*a.shape[:-2], blocks, sub, width)

    xb, kb, gb = blocked(x), blocked(k), blocked(decay)
    # the blocks on the diagonal
    lower = jnp.tril(jnp.ones((sub, sub), bool))
    pairs = jnp.exp(jnp.where(
        lower[:, :, None], gb[..., :, None, :] - gb[..., None, :, :], -jnp.inf))
    on = jnp.sum(xb[..., :, None, :] * (kb[..., None, :, :] * pairs), axis=-1)
    if blocks == 1:
        return on.reshape(*x.shape[:-2], size, size)
    # the blocks below it
    first = gb[..., :1, :]                                          # [..., blocks, 1, c]
    rows = xb * jnp.exp(gb - first)
    columns = k[..., None, :, :] * jnp.exp(
        jnp.minimum(first - decay[..., None, :, :], 0.0))           # [..., blocks, C, c]
    below = jnp.einsum("...nic,...njc->...nij", rows, columns,
                       precision=jax.lax.Precision.HIGHEST)          # [..., blocks, sub, C]
    earlier = jnp.arange(size)[None, :] // sub < jnp.arange(blocks)[:, None]
    below = jnp.where(earlier[:, None, :], below, 0.0)
    below = below.reshape(*x.shape[:-2], blocks, sub, blocks, sub)
    here = jnp.eye(blocks, dtype=bool)[:, None, :, None]
    return jnp.where(here, on[..., :, :, None, :], below).reshape(*x.shape[:-2], size, size)


def unit_lower_solve(a, rhs, sub: int = KDA_SUBCHUNK):
    """X with (I + a) X = rhs, for a [..., C, C] strictly lower-
    triangular and rhs [..., C, n], float32: forward substitution by
    blocks of `sub` rows, a block's own (I + D)^-1 as the finite product
    (I - D)(I + D^2)(I + D^4)... that a strictly triangular D allows (D
    to the power `sub` is zero), so the whole is a few small products
    and no loop over rows."""
    size = a.shape[-1]
    sub = min(sub, size)
    product = partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    eye, solved = jnp.eye(sub, dtype=a.dtype), []
    for start in range(0, size, sub):
        rows = slice(start, start + sub)
        power = a[..., rows, rows]
        inverse = eye - power
        for _ in range(max(sub - 1, 1).bit_length() - 1):
            power = product(power, power)
            inverse = product(inverse, eye + power)
        left = rhs[..., rows, :]
        if start:  # less what the rows above have already settled
            left = left - product(a[..., rows, :start], jnp.concatenate(solved, axis=-2))
        solved.append(product(inverse, left))
    return jnp.concatenate(solved, axis=-2)


# Chunks whose terms `kda_chunked_scan` forms at once: the pairwise
# decays of one are 34 MB at the published sizes (64 heads x 4 blocks x
# 16 x 16 x 128 float32).
CHUNKS_AT_ONCE = 8


def kda_chunked(q, k, v, g, beta, state, chunk: int):
    """The delta rule over a whole sequence, a chunk at a time: q, k, v
    [T, H, d] in the storage dtype, g [T, H, d] and beta [T, H] float32
    (`kda_inputs`), `state` [H, d, d] float32 before the first token.
    With G the cumulative g inside a chunk and A_ij = beta_i sum_c k_ic
    k_jc exp(G_ic - G_jc) (j < i):

        (I + A) [W | U0] = diag(beta) [k exp(G) | v]     (unit lower-triangular)
        U = U0 - W S0                                    (what each token writes)
        o_i = (q_i exp(G_i)) S0 + sum_{j<=i} (sum_c q_ic k_jc exp(G_ic - G_jc)) u_j
        S_C = exp(G_C) S0 + sum_j (k_j exp(G_C - G_j)) u_j^T

    A last chunk that is short is filled with tokens that change nothing
    (g 0, beta 0). Returns (o [T, H, d] float32, the state after the last
    token). One algorithm in the form its input allows
    (`kda_delta.kda_delta_route`: the backend, d, the chunk): the
    kernel of `ops/kda_delta.py` or `kda_chunked_scan`, with one entry
    in `ops/attention.route_log` a traced call."""
    tokens, heads, d = q.shape
    form = kda_delta.kda_delta_route(heads, d, chunk, q.dtype)
    kda_delta.log_route(form, tokens, heads, d, chunk, q.dtype)
    if form == "kernel":
        return kda_delta.kda_delta(q, k, v, g, beta, state, chunk=chunk)
    return kda_chunked_scan(q, k, v, g, beta, state, chunk)


def kda_chunked_scan(q, k, v, g, beta, state, chunk: int):
    """`kda_chunked` in XLA operations, every backend's form and the
    kernel's reference. What does not read S (`terms`: float32) is
    formed first, `CHUNKS_AT_ONCE` chunks at a time; the scan then
    carries S through four products a chunk, their operands in the
    storage dtype and their sums float32."""
    tokens, heads, d = q.shape
    dtype = q.dtype
    count = -(-tokens // chunk)
    pad = count * chunk - tokens

    def chunks(a):  # [T, H, ...] -> [chunks, H, chunk, ...]
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return jnp.moveaxis(a.reshape(count, chunk, *a.shape[1:]), 1, 2)

    strictly = jnp.tril(jnp.ones((chunk, chunk), bool), -1)

    def terms(xs):
        q, k, v, g, beta = xs
        q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
        decay = jnp.cumsum(g, axis=1)                                # G, [H, C, d]
        kk, qk = decay_products(jnp.stack([k, q]), k, decay)
        into = jnp.exp(decay)                                        # from the chunk's start
        solved = unit_lower_solve(
            jnp.where(strictly, beta[..., None] * kk, 0.0),
            beta[..., None] * jnp.concatenate([k * into, v], axis=-1))
        out_of = jnp.exp(decay[:, -1:, :] - decay)                   # to the chunk's end
        return (solved[..., :d].astype(dtype), solved[..., d:], (q * into).astype(dtype),
                qk.astype(dtype), (k * out_of).astype(dtype), into[:, -1, :])

    def one_chunk(state, xs):
        w, u0, q_in, qk, k_out, end = xs
        held = state.astype(dtype)
        u = u0 - jnp.einsum("hik,hkv->hiv", w, held, preferred_element_type=jnp.float32)
        o = jnp.einsum("hik,hkv->hiv", q_in, held, preferred_element_type=jnp.float32) + (
            jnp.einsum("hij,hjv->hiv", qk, u.astype(dtype), preferred_element_type=jnp.float32))
        state = end[:, :, None] * state + jnp.einsum(
            "hjk,hjv->hkv", k_out, u.astype(dtype), preferred_element_type=jnp.float32)
        return state, o

    xs = jax.lax.map(terms, tuple(map(chunks, (q, k, v, g, beta))), batch_size=CHUNKS_AT_ONCE)
    state, o = jax.lax.scan(one_chunk, state, xs)                    # o [chunks, H, C, d]
    return jnp.moveaxis(o, 1, 2).reshape(count * chunk, heads, d)[:tokens], state


def kda_step(q, k, v, g, beta, state):
    """The recurrence itself, one token: q, k, v, g [H, d], beta [H],
    `state` [H, d, d], all float32. Returns (o [H, d], the state)."""
    read = partial(jnp.einsum, "hk,hkv->hv", precision=jax.lax.Precision.HIGHEST)
    state = jnp.exp(g)[:, :, None] * state
    u = beta[:, None] * (v - read(k, state))
    state = state + k[:, :, None] * u[:, None, :]
    return read(q, state), state
