"""Mamba-2's selective state-space layer (`nemotron_h.py`'s `M` blocks),
H heads of P channels over a state of N, B and C shared by G groups of
H / G heads (head h reads group h // (H / G)), x the normed input:

    [z | xBC] = W_in x,  dt = W_dt x               (inner | inner + 2 G N columns, inner = H P; H columns)
    xBC_t = silu(conv(xBC)_t + b_conv)             (causal, depth-wise, over all inner + 2 G N channels)
    [u | B | C] = xBC                              u_t [H, P],  B_t, C_t [G, N]
    D_t = softplus(dt_t + dt_bias)  in R^H         a_t = exp(D_t A),  A = -exp(A_log)  (one scalar a head)
    S_t = a_t S_{t-1} + D_t u_t B_t^T              (S [H, P, N] float32)
    y_t = S_t C_t + D (.) u_t                      (D, the skip, one scalar a head)
    o_t = rms_group(y_t (.) silu(z_t)) (.) w       (the gate first, then a norm over each of G groups of channels)
    out = W_out o_t

in two forms that must agree: `ssd_chunked` (a prefill: with L_t the
running sum of D A inside a chunk, a token's output is the chunk's own
part sum_{s<=t} exp(L_t - L_s) (C_t . B_s) D_s u_s plus the part of the
state that entered the chunk, exp(L_t) S_in C_t, and the chunk hands on
S_out = exp(L_Q) S_in + sum_s exp(L_Q - L_s) D_s u_s B_s^T) and
`ssm_step` (a decode step: the recurrence itself). Every ratio of decays
is the exponential of a difference that is <= 0, as `kda.decay_products`
has it. D, L, S and a chunk's scores are float32; the four large
products a chunk (C B^T, the scores with u, C with the entering state,
the weighted u with B) take their operands in the storage dtype and
accumulate in float32. Nothing of `kda.py` computes this: there is no
solve, the decay is one scalar a head and a step, B and C belong to
groups of heads, and the output is normed after the gate. What the two
share is the causal convolution in front (`lm_common.short_conv`).

Parameter layout where it departs from the published checkpoint's: the
input projection's `dt` columns are a matrix of their own (`w_dt`), so
that `w_in`'s columns are whole lane tiles (10,240 and not 10,304: the
device's layout of an array puts a tiled axis last, and a scan over
stacked layers would copy the stack to turn it back).

The layer's published initialisation gives a seeded state a trained
one's memory with no shift of a bias: `A` uniform in [1, 16] and a step
log-uniform in [`time_step_min`, `time_step_max`] (`init_steps`), so a
token's decay lies between e^-1.6 and e^-0.001, a state that holds from
one to a thousand tokens.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..ops import ssd_chunk
from .lm_common import short_conv


def shapes(hidden: int, heads: int, head_dim: int, groups: int, state: int, kernel: int) -> dict:
    """One layer's specs (`lm_common.init_from_shapes`): `a_log`,
    `dt_bias` and `d` are drawn like weights and overwritten by
    `init_steps`."""
    inner = heads * head_dim
    mixed = inner + 2 * groups * state
    return {
        "w_in": ((hidden, inner + mixed), hidden), "w_dt": ((hidden, heads), hidden),
        "conv": ((kernel, mixed), kernel),
        "conv_bias": ((mixed,), 3 * kernel),  # a deviation of 0.29: uniform in +-kernel^-1/2
        "a_log": ((heads,), 1), "dt_bias": ((heads,), 1), "d": ((heads,), 1),
        "norm": ((inner,), None),
        "w_out": ((inner, hidden), inner),
    }


def init_steps(key, shape: tuple, step_min: float, step_max: float, step_floor: float) -> dict:
    """The layer's published initialisation of what makes the decay,
    float32 whatever the weights' dtype, `shape` (..., H): `a_log` the
    logarithm of a uniform draw in [1, 16], `dt_bias` the inverse
    softplus of a step log-uniform in [`step_min`, `step_max`] and at
    least `step_floor`, `d` ones."""
    key_a, key_dt = jax.random.split(key)
    a = jax.random.uniform(key_a, shape, jnp.float32, 1.0, 16.0)
    step = jnp.exp(jax.random.uniform(
        key_dt, shape, jnp.float32, math.log(step_min), math.log(step_max)))
    step = jnp.maximum(step, step_floor)
    return {
        "a_log": jnp.log(a),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "d": jnp.ones(shape, jnp.float32),
    }


def mixer_inputs(p: dict, x, tail, heads: int, head_dim: int, groups: int, state: int):
    """What the recurrence takes of x [T, hidden] (normed), `tail`
    [kernel - 1, inner + 2 G N] the convolution's inputs of the tokens
    before: the gate z [T, inner], u [T, H, P], B and C [T, G, N], all
    in x's dtype, the step D [T, H] float32 (after its softplus), and
    the new tail. Convolution, bias and SiLU float32."""
    tokens, inner = x.shape[0], heads * head_dim
    with jax.named_scope("in_proj"):
        z, mixed = jnp.split(x @ p["w_in"], [inner], axis=-1)
        dt = jnp.dot(x, p["w_dt"], preferred_element_type=jnp.float32)
    with jax.named_scope("conv"):
        mixed, window = short_conv(mixed, p["conv"], tail)
        mixed = jax.nn.silu(mixed + p["conv_bias"].astype(jnp.float32)).astype(x.dtype)
        u, b, c = jnp.split(mixed, [inner, inner + groups * state], axis=-1)
    step = jax.nn.softplus(dt + p["dt_bias"])
    return (z, u.reshape(tokens, heads, head_dim), b.reshape(tokens, groups, state),
            c.reshape(tokens, groups, state), step, window[tokens:])


def ssd_chunked(u, b, c, step, a, state, chunk: int):
    """The recurrence over a whole sequence, a chunk at a time: u [T, H,
    P], b and c [T, G, N] in the storage dtype, `step` [T, H] float32,
    `a` [H] float32 (< 0), `state` [H, P, N] float32 before the first
    token. A last chunk that is short is filled with tokens that change
    nothing (a step of 0). Returns (y [T, H, P] float32, without the
    skip, and the state after the last token). One algorithm in the form
    its input allows (`ssd_chunk.ssd_route`: the backend, the dtype, P,
    N, the groups, the chunk): the kernel of `ops/ssd_chunk.py`, which
    never writes a chunk's Q x Q weights to HBM, or `ssd_chunked_xla`,
    with one entry in `ops/attention.route_log` a traced call."""
    tokens, heads, width = u.shape
    groups, n = b.shape[1:]
    form = ssd_chunk.ssd_route(heads, width, groups, n, chunk, u.dtype)
    ssd_chunk.log_route(form, tokens, heads, width, groups, n, chunk, u.dtype)
    if form == "kernel":
        return ssd_chunk.ssd_chunk(u, b, c, step, a, state, chunk=chunk)
    return ssd_chunked_xla(u, b, c, step, a, state, chunk)


def ssd_chunked_xla(u, b, c, step, a, state, chunk: int):
    """`ssd_chunked` in XLA operations, every backend's form and the
    kernel's reference. What does not read S is formed for every chunk
    at once; S is then carried through the chunks by a scan of one
    multiply-add a chunk, and what each token reads of the state that
    entered its chunk is one more product."""
    tokens, heads, width = u.shape
    groups, n = b.shape[1:]
    per, dtype = heads // groups, u.dtype
    count = -(-tokens // chunk)
    pad = count * chunk - tokens

    def chunks(arr):  # [T, ...] -> [chunks, chunk, ...]
        arr = jnp.pad(arr, ((0, pad),) + ((0, 0),) * (arr.ndim - 1))
        return arr.reshape(count, chunk, *arr.shape[1:])

    u, b, c, step = map(chunks, (u, b, c, step))
    step = jnp.moveaxis(step, 1, 2)                                   # [chunks, H, Q]
    decay = jnp.cumsum(step * a[None, :, None], axis=-1)             # L, never increasing
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))

    # the chunk's own part: scores C_t . B_s a group, decayed and stepped a head
    scores = jnp.einsum("cqgn,csgn->cgqs", c, b, preferred_element_type=jnp.float32)
    ratio = jnp.exp(jnp.where(lower, decay[..., :, None] - decay[..., None, :], -jnp.inf))
    weights = (ratio * step[..., None, :]).reshape(count, groups, per, chunk, chunk)
    weights = (weights * scores[:, :, None]).astype(dtype)           # [chunks, G, H / G, Q, Q]
    grouped = u.reshape(count, chunk, groups, per, width)
    y = jnp.einsum("cgjqs,csgjp->cqgjp", weights, grouped, preferred_element_type=jnp.float32)

    # what the chunk adds to the state, and how much of the entering state is left
    out_of = jnp.exp(decay[..., -1:] - decay) * step                  # to the chunk's end
    stepped = grouped.astype(jnp.float32) * jnp.moveaxis(
        out_of.reshape(count, groups, per, chunk), 3, 1)[..., None]
    added = jnp.einsum("cqgjp,cqgn->cgjpn", stepped.astype(dtype), b,
                       preferred_element_type=jnp.float32)
    left = jnp.exp(decay[..., -1]).reshape(count, groups, per)

    def carry(entering, xs):
        left, added = xs
        return left[..., None, None] * entering + added, entering

    state, entered = jax.lax.scan(
        carry, state.reshape(groups, per, width, n), (left, added))

    # what each token reads of the state that entered its chunk
    into = jnp.moveaxis(jnp.exp(decay).reshape(count, groups, per, chunk), 3, 1)
    y = y + into[..., None] * jnp.einsum(
        "cqgn,cgjpn->cqgjp", c, entered.astype(dtype), preferred_element_type=jnp.float32)
    return y.reshape(count * chunk, heads, width)[:tokens], state.reshape(heads, width, n)


def ssm_step(u, b, c, step, a, state):
    """The recurrence itself, one token: u [H, P], b and c [G, N], `step`
    and `a` [H], `state` [H, P, N], all float32. Returns (y [H, P],
    without the skip, and the state)."""
    per = u.shape[0] // b.shape[0]
    b, c = jnp.repeat(b, per, axis=0), jnp.repeat(c, per, axis=0)    # head h: group h // per
    state = jnp.exp(step * a)[:, None, None] * state + (
        (step[:, None] * u)[:, :, None] * b[:, None, :])
    return jnp.sum(state * c[:, None, :], axis=-1), state


def gated_norm(y, z, scale, groups: int, eps: float):
    """rms_group(y (.) silu(z)) (.) scale over y [T, inner] float32: the
    gate first, then each of `groups` runs of channels normed by its own
    mean square. Returns [T, inner] in z's dtype."""
    tokens, inner = y.shape
    gated = (y * jax.nn.silu(z.astype(jnp.float32))).reshape(tokens, groups, inner // groups)
    normed = gated * jax.lax.rsqrt(jnp.mean(gated * gated, axis=-1, keepdims=True) + eps)
    return (normed.reshape(tokens, inner) * scale.astype(jnp.float32)).astype(z.dtype)


def mixer(p: dict, x, tail, state, heads: int, head_dim: int, groups: int, n: int,
          chunk: int, eps: float):
    """A Mamba-2 mixer over x [T, hidden] (normed) from `tail` and
    `state`: `ssd_chunked` for a sequence, `ssm_step` for one token (a
    decode step). Returns (output [T, hidden], tail, state)."""
    tokens = x.shape[0]
    z, u, b, c, step, tail = mixer_inputs(p, x, tail, heads, head_dim, groups, n)
    a = -jnp.exp(p["a_log"])
    with jax.named_scope("ssd"):
        if tokens == 1:
            y, state = ssm_step(
                *(t[0].astype(jnp.float32) for t in (u, b, c)), step[0], a, state)
            y = y[None]
        else:
            y, state = ssd_chunked(u, b, c, step, a, state, chunk)
        y = y + p["d"][None, :, None] * u.astype(jnp.float32)
    with jax.named_scope("norm"):
        o = gated_norm(y.reshape(tokens, -1), z, p["norm"], groups, eps)
    with jax.named_scope("out_proj"):
        return o @ p["w_out"], tail, state
