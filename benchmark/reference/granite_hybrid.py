"""Plain reference of Granite 4.0-H's forward pass over a whole sequence
(ibm-granite/granite-4.0-h-micro, `model_type: granitemoehybrid` without
experts).

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernel, no cache, no parts
of a prompt, no chunked form of the state-space layer (the recurrence
itself, token by token), softmax attention under the full [T, T] causal
mask, no scan over layers, and no import from the code it is compared
with (`models/granite_hybrid.py`, `models/mamba2.py`, `ops/`). It is
written from the layer equations in that configuration's issue, which
are what the family's published modelling code computes
(`transformers.models.granitemoehybrid`: `tests/
test_granite_hybrid_published.py` holds this file against it where it is
installed), layer by layer, and reads the system's own parameters as a
sequence of the published layers, upcasting one weight at a time, so that
at the published sizes it fits on a chip.

    h_0 = embedding_multiplier . E[id]
    h = h + residual_multiplier . mixer_l(rms(h; w1_l))
    h = h + residual_multiplier . W_out_l (silu(a) (.) b),   [a | b] = W_in_l rms(h; w2_l)
    logits = E rms(h; w_f) / logits_scaling                  (the head is the embedding)

A layer that has a `mamba` entry is Mamba-2 (H heads of P, a state of N,
G groups; x the normed input):

    [z | xBC] = W_in x,  dt = W_dt x
    xBC_t = silu(conv(xBC)_t + b_conv)       (causal, depth-wise, zeros before the first token)
    [u | B | C] = xBC                       u_t [H, P],  B_t, C_t [G, N]; head h: group h // (H / G)
    D_t = softplus(dt_t + dt_bias),  a_t = exp(-D_t exp(A_log))      (one scalar a head)
    S_t = a_t S_{t-1} + D_t u_t B_t^T,       y_t = S_t C_t + D (.) u_t
    o_t = rms_group(y_t (.) silu(z_t)) (.) w      (the gate first; groups of inner / G channels)
    out = W_out o_t

one with an `attn` entry softmax attention: q = W_q x [heads, d], k, v
[key heads, d] (key head j serves query heads j x group .. (j + 1) x group
- 1), scores q . k x `attention_multiplier` under the causal mask,
softmax, W_o; no bias, no norm on q or k, no positional term.

Departures from the published description, none of which changes a
number: the input projection's `dt` columns are the tree's `w_dt` (the
published `in_proj` is `[z | xBC | dt]`), weights are `[in, out]`, the
convolution's filters `[kernel, channels]`; the SwiGLU and the attention
run `row_block` rows at a time and attention `head_chunk` query heads at
a time, only so that 65,664 tokens fit (a SwiGLU's float32 middle is 4.3
GB whole, 32 heads' scores 552 GB).

`round_to` rounds both operands of every matrix product to that dtype
before multiplying in float32 (the recurrence's too). It and the
switches of `Sizes` that name a wrong mechanism (a multiplier at another
value, `gate_before_norm`, `zero_state_at`) exist for one purpose: the
comparison's limits are set between what the system gives and what this
reference gives when computed one precision below the configuration's,
or with one of its mechanisms replaced by its nearest neighbour.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the parameter tree does not say about the architecture."""

    mamba_heads: int = 64
    ssm_state: int = 128
    groups: int = 1
    heads: int = 32
    kv_heads: int = 8
    eps: float = 1e-5
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 0.015625
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    # wrong mechanisms, for the comparison's controls
    gate_before_norm: bool = True        # False: the norm first, then the gate
    zero_state_at: int | None = None     # a position at which every Mamba layer forgets: its
    #                                      state and its convolution's inputs start from zero

    @classmethod
    def of(cls, cfg) -> "Sizes":
        """From any object that bears the published `config.json`'s names."""
        return cls(
            mamba_heads=cfg.mamba_n_heads, ssm_state=cfg.mamba_d_state,
            groups=cfg.mamba_n_groups, heads=cfg.num_attention_heads,
            kv_heads=cfg.num_key_value_heads, eps=cfg.rms_norm_eps,
            embedding_multiplier=cfg.embedding_multiplier,
            attention_multiplier=cfg.attention_multiplier,
            residual_multiplier=cfg.residual_multiplier, logits_scaling=cfg.logits_scaling,
        )


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _round(a, round_to):
    a = _f32(a)
    return a if round_to is None else a.astype(round_to).astype(jnp.float32)


def _mm(a, b, round_to):
    return jnp.matmul(_round(a, round_to), _round(b, round_to))


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(scale)


def _by_rows(fn, x, row_block):
    """fn over x [T, ...] `row_block` rows at a time (whole where None)."""
    if row_block is None or x.shape[0] <= row_block:
        return fn(x)
    return jnp.concatenate([fn(x[at:at + row_block]) for at in range(0, x.shape[0], row_block)])


def _swiglu(p, x, round_to):
    gate, up = jnp.split(_mm(x, p["w_gate_up"], round_to), 2, axis=-1)
    return _mm(jax.nn.silu(gate) * up, p["w_down"], round_to)


def _attention(sizes: Sizes, p, x, round_to, head_chunk, row_block):
    """Softmax attention with grouped queries over x [T, hidden], causal,
    no positional term: (output, the keys and the values [2, T, key
    heads, d])."""
    length = x.shape[0]
    heads, group = sizes.heads, sizes.heads // sizes.kv_heads
    q = _mm(x, p["w_q"], round_to).reshape(length, heads, -1)
    k = _mm(x, p["w_k"], round_to).reshape(length, sizes.kv_heads, -1)
    v = _mm(x, p["w_v"], round_to).reshape(length, sizes.kv_heads, -1)
    block = length if row_block is None else min(row_block, length)
    outs = []
    for first in range(0, heads, head_chunk):
        mine = np.arange(first, min(first + head_chunk, heads))
        kh = k[:, mine // group].transpose(1, 2, 0)                   # each head's key head
        vh = v[:, mine // group].transpose(1, 0, 2)
        rows_out = []
        for at in range(0, length, block):
            qh = q[at:at + block][:, mine].transpose(1, 0, 2)         # [chunk, rows, d]
            scores = _mm(qh, kh, round_to) * sizes.attention_multiplier
            rows = jnp.arange(at, min(at + block, length))
            seen = rows[:, None] >= jnp.arange(length)[None, :]       # the mask's rows
            probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
            rows_out.append(_mm(probs, vh, round_to).transpose(1, 0, 2))
        outs.append(jnp.concatenate(rows_out))
    merged = jnp.concatenate(outs, axis=1).reshape(length, -1)
    return _mm(merged, p["w_o"], round_to), jnp.stack([k, v])


def _mamba(sizes: Sizes, p, x, round_to, state_at):
    """A Mamba-2 mixer over x [T, hidden]: (output, (the state [H, P, N]
    after `state_at` tokens, or after all of them; the state after all of
    them; the convolution's last kernel - 1 inputs at `state_at`))."""
    length, heads, groups, n = x.shape[0], sizes.mamba_heads, sizes.groups, sizes.ssm_state
    inner = p["norm"].shape[0]
    width = inner // heads
    z, mixed = jnp.split(_mm(x, p["w_in"], round_to), [inner], axis=-1)
    dt = _mm(x, p["w_dt"], round_to)
    filters = _f32(p["conv"])                                         # [kernel, inner + 2 G N]
    kernel = filters.shape[0]
    state_at = length if state_at is None else min(state_at, length)
    forget = sizes.zero_state_at if sizes.zero_state_at is not None else length + kernel
    padded = jnp.concatenate([jnp.zeros((kernel - 1, filters.shape[1])), mixed])
    tail = padded[state_at:state_at + kernel - 1]                     # inputs of the tokens before
    # tap i of token t reads the input of token t - (kernel - 1 - i), which a
    # layer that forgot at `forget` does not have of a token before it
    token = np.arange(length)[:, None]
    mixed = jax.nn.silu(sum(
        jnp.where((token >= forget) & (token - (kernel - 1 - i) < forget), 0.0,
                  padded[i:i + length]) * filters[i]
        for i in range(kernel)) + _f32(p["conv_bias"]))
    u, b, c = jnp.split(mixed, [inner, inner + groups * n], axis=-1)
    u = u.reshape(length, heads, width)
    of_head = np.arange(heads) // (heads // groups)
    b, c = b.reshape(length, groups, n), c.reshape(length, groups, n)
    step = jax.nn.softplus(dt + _f32(p["dt_bias"]))                   # [T, H]
    a = -jnp.exp(_f32(p["a_log"]))

    def one(state, xs):
        u, b, c, step, keep = xs                      # [H, P], [G, N], [G, N], [H], a scalar
        b, c = b[of_head], c[of_head]                                 # [H, N]
        state = keep * jnp.exp(step * a)[:, None, None] * state + (
            (step[:, None] * u)[:, :, None] * b[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", _round(state, round_to), _round(c, round_to))

    keep = _f32(np.arange(length) != forget)
    state = jnp.zeros((heads, width, n), jnp.float32)
    xs = (u, b, c, step, keep)
    kept, first = jax.lax.scan(one, state, jax.tree_util.tree_map(lambda t: t[:state_at], xs))
    state, rest = jax.lax.scan(one, kept, jax.tree_util.tree_map(lambda t: t[state_at:], xs))
    y = jnp.concatenate([first, rest])
    y = (y + _f32(p["d"])[None, :, None] * u).reshape(length, groups, inner // groups)
    gate = jax.nn.silu(z).reshape(y.shape)

    def group_norm(t):
        return t * jax.lax.rsqrt(jnp.mean(t * t, axis=-1, keepdims=True) + sizes.eps)

    o = group_norm(y * gate) if sizes.gate_before_norm else group_norm(y) * gate
    o = o.reshape(length, inner) * _f32(p["norm"])
    return _mm(o, p["w_out"], round_to), (kept, state, tail)


def layer(sizes: Sizes, p, h, round_to=None, head_chunk=8, state_at=None, row_block=None):
    """One published layer `p` over h [T, hidden] float32: (h out, what
    its mixer keeps: a Mamba layer's (state after `state_at` tokens,
    state after all, tail at `state_at`), an attention layer's keys and
    values [2, T, key heads, d])."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(_f32, p)  # the layer's weights upcast once, not a use
        x = _rms_norm(h, p["norm1"], sizes.eps)
        if "mamba" in p:
            out, kept = _mamba(sizes, p["mamba"], x, round_to, state_at)
        else:
            out, kept = _attention(sizes, p["attn"], x, round_to, head_chunk, row_block)
        h = h + sizes.residual_multiplier * out
        out = _by_rows(
            lambda r: _swiglu(p["mlp"], _rms_norm(r, p["norm2"], sizes.eps), round_to),
            h, row_block)
        return h + sizes.residual_multiplier * out, kept


def forward(sizes: Sizes, params, ids, round_to=None, head_chunk=8, positions=None,
            state_at=None, row_block=None):
    """Logits [len(positions) or T, vocab] (float32) of the whole
    sequence `ids`; each Mamba layer's state [2, Mamba layers, H, P, N],
    after `state_at` tokens (after the last where None) and after the
    last, and its convolution's tail at `state_at` [Mamba layers, kernel
    - 1, channels]; each attention layer's keys and values [attention
    layers, 2, T, key heads, d]. `params["layers"]` is a sequence of the
    published layers; `positions` keeps the head to those rows."""
    with jax.default_matmul_precision("highest"):
        embedding = _f32(params["embed"])
        h = sizes.embedding_multiplier * embedding[jnp.asarray(ids)]
        states, tails, kv = [], [], []
        for p in params["layers"]:
            h, kept = layer(sizes, p, h, round_to, head_chunk, state_at, row_block)
            if "mamba" in p:
                states.append(jnp.stack(kept[:2]))
                tails.append(kept[2])
            else:
                kv.append(kept)
        h = _rms_norm(h, params["final_norm"], sizes.eps)
        if positions is not None:
            h = h[jnp.asarray(positions)]
        logits = _mm(h, embedding.T, round_to) / sizes.logits_scaling
        return logits, jnp.stack(states, axis=1), jnp.stack(tails), jnp.stack(kv)
