"""Plain reference of Nemotron-H's forward pass over a whole sequence
(NVIDIA-Nemotron-3-Nano-30B-A3B).

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no cache, no chunked form of
the state-space layer (the recurrence itself, a `lax.scan` over tokens),
full softmax attention under a [T, T] mask, no grouped product (a loop
over the held experts), no scan over stacked blocks, and no import from
the code it is compared with (`models/nemotron_h.py`, `models/mamba2.py`,
`models/moe.py`, `ops/`). It is written from the block equations in that
configuration's issue (the published `config.json` names the mechanisms
and gives every size; the modelling code is not in the sandbox), block
by block, and reads the system's own parameters as a sequence of the 52
published blocks, upcasting one weight at a time (one expert at a time),
so that at published widths it fits on a chip beside the system's
bfloat16 weights.

Block l of kind `pattern[l]` (`M`, `E` or `*`) is `h += part(rms(h))`,
one norm scale a block; a final RMS norm; an untied head.

`M`, Mamba-2 (H heads of P, a state of N, G groups; x the normed input):

    [z | xBC] = W_in x,  dt = W_dt x
    xBC_t = silu(conv(xBC)_t + b_conv)       (causal, depth-wise, 4 taps, zeros before the first token)
    [u | B | C] = xBC                        u_t [H, P],  B_t, C_t [G, N]; head h reads group h // (H / G)
    D_t = softplus(dt_t + dt_bias),  a_t = exp(-D_t exp(A_log))      (one scalar a head)
    S_t = a_t S_{t-1} + D_t u_t B_t^T,       y_t = S_t C_t + D (.) u_t
    o_t = rms_group(y_t (.) silu(z_t)) (.) w      (the gate first; groups of inner / G channels)
    out = W_out o_t

`*`: q = W_q x [heads, d], k, v [key heads, d] (key head j serves query
heads j x group .. (j + 1) x group - 1), scores q . k / sqrt(d) under
the causal mask, softmax, W_o; no bias, no gate, no norm on q or k and no
rotary embedding.

`E`: router scores sigmoid(W_r x); the k largest of score + bias, ties
to the lower index; weights the chosen scores over their sum times the
scaling factor; an expert is W_down relu(W_up x)^2; one shared expert of
the same form that every token takes. `held` lists the routed experts
the tree's stacks hold, row j of a stack being expert `held[j]`: all of
them, or one chip's share. Every token is routed over all
`n_routed_experts`; what the absent experts would have added is left
out, as in the system. Likewise the embedding and the head may be a
slice of the vocabulary. Weights are `[in, out]` but a routed expert's
W_up, which the tree stores `[out, in]`; the input projection's dt
columns are the tree's `w_dt`.

Attention is computed `head_chunk` query heads at a time, which changes
no number: 32 heads' float32 scores over 8,704 tokens are 9.7 GB.

`round_to` rounds both operands of every matrix product to that dtype
before multiplying in float32 (the recurrence's too). It and the three
switches of `Sizes` that name a wrong mechanism (`expert_square`,
`gate_before_norm`, `groups_strided`) exist for one purpose: the
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

    pattern: str = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    mamba_heads: int = 64
    ssm_state: int = 128
    groups: int = 8
    heads: int = 32
    kv_heads: int = 2
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    eps: float = 1e-5
    # wrong mechanisms, for the comparison's controls
    expert_square: bool = True      # False: a plain ReLU between an expert's matrices
    gate_before_norm: bool = True   # False: the group norm first, then the gate
    groups_strided: bool = False    # True: head h reads group h mod G

    @classmethod
    def of(cls, cfg) -> "Sizes":
        """From any object that bears the published `config.json`'s names."""
        return cls(
            pattern=cfg.hybrid_override_pattern, mamba_heads=cfg.mamba_num_heads,
            ssm_state=cfg.ssm_state_size, groups=cfg.n_groups, heads=cfg.num_attention_heads,
            kv_heads=cfg.num_key_value_heads, n_routed_experts=cfg.n_routed_experts,
            num_experts_per_tok=cfg.num_experts_per_tok, norm_topk_prob=cfg.norm_topk_prob,
            routed_scaling_factor=cfg.routed_scaling_factor, eps=cfg.layer_norm_epsilon,
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


def _mlp(sizes: Sizes, p, x, round_to):
    """down(relu(up x)^2); `p["w_up"]` is [in, out]."""
    middle = jax.nn.relu(_mm(x, p["w_up"], round_to))
    return _mm(middle * middle if sizes.expert_square else middle, p["w_down"], round_to)


def _attention(sizes: Sizes, p, x, round_to, head_chunk):
    """Softmax attention with grouped queries over x [T, hidden], causal,
    no positional term."""
    length = x.shape[0]
    heads, group = sizes.heads, sizes.heads // sizes.kv_heads
    q = _mm(x, p["w_q"], round_to).reshape(length, heads, -1)
    k = _mm(x, p["w_k"], round_to).reshape(length, sizes.kv_heads, -1)
    v = _mm(x, p["w_v"], round_to).reshape(length, sizes.kv_heads, -1)
    scale = q.shape[-1] ** -0.5
    causal = jnp.tril(jnp.ones((length, length), bool))
    outs = []
    for first in range(0, heads, head_chunk):
        mine = np.arange(first, min(first + head_chunk, heads))
        qh = q[:, mine].transpose(1, 0, 2)                            # [chunk, T, d]
        kh = k[:, mine // group].transpose(1, 0, 2)                   # each head's key head
        vh = v[:, mine // group].transpose(1, 0, 2)
        scores = _mm(qh, kh.transpose(0, 2, 1), round_to) * scale
        scores = jnp.where(causal[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        outs.append(_mm(probs, vh, round_to).transpose(1, 0, 2))
    return _mm(jnp.concatenate(outs, axis=1).reshape(length, -1), p["w_o"], round_to)


def _mamba(sizes: Sizes, p, x, round_to, state_at):
    """A Mamba-2 mixer over x [T, hidden]: (output, the state [H, P, N]
    after `state_at` tokens, or after all of them, and the state after
    all of them)."""
    length, heads, groups, n = x.shape[0], sizes.mamba_heads, sizes.groups, sizes.ssm_state
    inner = p["norm"].shape[0]
    width = inner // heads
    z, mixed = jnp.split(_mm(x, p["w_in"], round_to), [inner], axis=-1)
    dt = _mm(x, p["w_dt"], round_to)
    filters = _f32(p["conv"])                                         # [kernel, inner + 2 G N]
    kernel = filters.shape[0]
    padded = jnp.concatenate([jnp.zeros((kernel - 1, filters.shape[1])), mixed])
    mixed = jax.nn.silu(
        sum(padded[i:i + length] * filters[i] for i in range(kernel)) + _f32(p["conv_bias"]))
    u, b, c = jnp.split(mixed, [inner, inner + groups * n], axis=-1)
    u = u.reshape(length, heads, width)
    of_head = np.arange(heads) % groups if sizes.groups_strided else np.arange(heads) // (
        heads // groups)
    b = b.reshape(length, groups, n)[:, of_head]                      # [T, H, N]
    c = c.reshape(length, groups, n)[:, of_head]
    step = jax.nn.softplus(dt + _f32(p["dt_bias"]))                   # [T, H]
    a = -jnp.exp(_f32(p["a_log"]))

    def token(state, xs):
        u, b, c, step = xs                                            # [H, P], [H, N], [H, N], [H]
        state = jnp.exp(step * a)[:, None, None] * state + (
            (step[:, None] * u)[:, :, None] * b[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", _round(state, round_to), _round(c, round_to))

    state = jnp.zeros((heads, width, n), jnp.float32)
    xs = (u, b, c, step)
    if state_at is None or state_at >= length:
        state, y = jax.lax.scan(token, state, xs)
        kept = state
    else:
        kept, first = jax.lax.scan(token, state, jax.tree_util.tree_map(lambda a: a[:state_at], xs))
        state, rest = jax.lax.scan(token, kept, jax.tree_util.tree_map(lambda a: a[state_at:], xs))
        y = jnp.concatenate([first, rest])
    y = (y + _f32(p["d"])[None, :, None] * u).reshape(length, groups, inner // groups)
    gate = jax.nn.silu(z).reshape(y.shape)

    def group_norm(t):
        return t * jax.lax.rsqrt(jnp.mean(t * t, axis=-1, keepdims=True) + sizes.eps)

    o = group_norm(y * gate) if sizes.gate_before_norm else group_norm(y) * gate
    return _mm(o.reshape(length, inner) * _f32(p["norm"]), p["w_out"], round_to), (kept, state)


def route(sizes: Sizes, bias, logits):
    """Router logits [T, experts] in: (ids [T, k], weights [T, k]).
    Sigmoid scores; the k largest of score + bias; the chosen scores,
    without the bias, over their sum, times the scaling factor."""
    scores = jax.nn.sigmoid(logits)
    ids = jnp.argsort(-(scores + _f32(bias)), axis=-1, stable=True)[:, : sizes.num_experts_per_tok]
    weights = jnp.take_along_axis(scores, ids, axis=-1)
    if sizes.norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return ids, weights * sizes.routed_scaling_factor


def _moe(sizes: Sizes, p, x, held, round_to):
    """(output, chosen ids). The router's product is never rounded."""
    ids, weights = route(sizes, p["bias"], jnp.matmul(x, _f32(p["w_g"])))
    y = jnp.zeros_like(x)
    for row, expert in enumerate(held):
        weight = jnp.sum(jnp.where(ids == expert, weights, 0.0), axis=-1, keepdims=True)
        # a routed expert's up-projection is stored out by in, [width, hidden]
        one = {"w_up": p["experts"]["w_up"][row].T, "w_down": p["experts"]["w_down"][row]}
        y = y + weight * _mlp(sizes, one, x, round_to)
    return y + _mlp(sizes, p["shared"], x, round_to), ids


def block(sizes: Sizes, index, p, h, held, round_to=None, head_chunk=8, state_at=None):
    """Published block `index` over h [T, hidden] float32: (h out, the
    chosen ids of an `E` block or None, the states of an `M` block,
    after `state_at` tokens and after all, or None)."""
    with jax.default_matmul_precision("highest"):
        kind = sizes.pattern[index]
        x = _rms_norm(h, p["norm"], sizes.eps)
        ids = state = None
        if kind == "M":
            out, state = _mamba(sizes, p["mamba"], x, round_to, state_at)
        elif kind == "*":
            out = _attention(sizes, p["attn"], x, round_to, head_chunk)
        else:
            out, ids = _moe(sizes, p["moe"], x, held, round_to)
        return h + out, ids, state


def forward(sizes: Sizes, params, ids, held, round_to=None, head_chunk=8, positions=None,
            state_at=None):
    """Logits [len(positions) or T, vocab held] (float32) of the whole
    sequence `ids`, the experts chosen in each `E` block [E blocks, T,
    k], and each `M` block's state [2, M blocks, H, P, N]: after
    `state_at` tokens (after the last where None) and after the last.
    `params["blocks"]` is a sequence of the published blocks;
    `positions` keeps the head to those rows."""
    with jax.default_matmul_precision("highest"):
        h = _f32(params["embed"])[jnp.asarray(ids)]
        chosen, states = [], []
        for index in range(len(sizes.pattern)):
            h, ids_b, state = block(
                sizes, index, params["blocks"][index], h, held, round_to, head_chunk, state_at)
            if ids_b is not None:
                chosen.append(ids_b)
            if state is not None:
                states.append(state)
        h = _rms_norm(h, params["final_norm"], sizes.eps)
        if positions is not None:
            h = h[jnp.asarray(positions)]
        return _mm(h, params["head"], round_to), jnp.stack(chosen), (
            jnp.stack([jnp.stack(pair) for pair in states], axis=1))
