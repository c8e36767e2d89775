"""Plain reference of Solar-Open2's forward pass over a whole sequence.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no cache, no chunked form of
the linear attention (the delta rule is the recurrence itself, a
`lax.scan` over tokens), full softmax attention under a mask, no grouped
product (a loop over experts), and no import from the code it is
compared with (`models/solar_open2.py`, `models/moe.py`, `ops/`). It is
written from the layer equations in that configuration's issue (the
published `config.json` names the mechanisms; the modelling code is not
in the sandbox), layer by layer, and reads the system's own parameter
tree, upcasting one weight at a time (one expert at a time), so that at
published widths it fits on a chip beside the system's bfloat16 weights.

    h += mixer(rms(h));  h += moe(rms(h))

A layer is softmax attention when its index is a multiple of
`gqa_interval + 1` (grouped queries: key head j serves query heads
j x group .. (j + 1) x group - 1; no rotary embedding, no norm on q or k;
the output gated element-wise by sigmoid(W_gate x) before W_o), else KDA:

    q_t = l2norm(silu(conv(W_q x))_t) d^-1/2,  k_t = l2norm(silu(conv(W_k x))_t),
    v_t = silu(conv(W_v x))_t
    g_t = -exp(A_log) softplus(W_f2 W_f1 x + dt_bias),  beta_t = 2 sigmoid(W_beta x)
    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T,  o_t = S_t^T q_t
    y_t = W_o [rms_head(o_t) sigmoid(W_g2 W_g1 x)]

`held` lists the routed experts the tree's expert stacks hold, row j of a
stack being expert `held[j]`: all of them, or one chip's share. Every
token is routed over all `n_routed_experts`; the output of an expert
layer is the shared expert's plus the chosen experts' that are in `held`,
and what the others would have added is left out, as in the system.
Likewise the embedding and the head may be a slice of the vocabulary.

Choices the published config does not settle, each made as the system
makes it, so that the two are given the same problem: the gates' rank
(that of the tree's `w_f1`), the full-attention gate element-wise, the
router's scores sigmoids, l2norm's epsilon 1e-6 under the root, ties in
the router's top-k to the lower index; `w_qkv` holds the KDA q, k and v
projections side by side and `conv` their filters, `w_gate_up` a
SwiGLU's gate and up; weights are `[in, out]`; the tokenizer is outside
this file: ids are inputs.

Attention is computed `head_chunk` query heads at a time, which changes
no number: 64 heads' float32 scores over 8,448 tokens are 18 GB.

`round_to` rounds both operands of every matrix product to that dtype
before multiplying in float32 (the delta rule's products too). It exists
for one purpose: the comparison's limit is set between what the system
gives and what this reference gives when computed one precision below
the configuration's.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the parameter tree does not say about the architecture."""

    heads: int = 64
    kv_heads: int = 8
    gqa_interval: int = 3
    linear_heads: int = 64
    kda_allow_neg_eigval: bool = True
    decay_per_channel: bool = True
    n_routed_experts: int = 320
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5

    @classmethod
    def of(cls, cfg) -> "Sizes":
        """From any object that bears the published `config.json`'s names
        (the `linear_attn_config` block's prefixed with `linear_`)."""
        return cls(
            heads=cfg.num_attention_heads, kv_heads=cfg.num_key_value_heads,
            gqa_interval=cfg.gqa_interval, linear_heads=cfg.linear_num_heads,
            kda_allow_neg_eigval=cfg.kda_allow_neg_eigval,
            n_routed_experts=cfg.n_routed_experts,
            num_experts_per_tok=cfg.num_experts_per_tok, norm_topk_prob=cfg.norm_topk_prob,
            routed_scaling_factor=cfg.routed_scaling_factor, rms_norm_eps=cfg.rms_norm_eps,
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


def _mlp(p, x, round_to):
    """down(silu(gate x) * up x)."""
    width = p["w_gate_up"].shape[-1] // 2
    gate = _mm(x, p["w_gate_up"][..., :width], round_to)
    up = _mm(x, p["w_gate_up"][..., width:], round_to)
    return _mm(jax.nn.silu(gate) * up, p["w_down"], round_to)


def _attention(sizes: Sizes, p, x, round_to, head_chunk):
    """Gated softmax attention with grouped queries over x [T, hidden],
    causal, no positional term."""
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
    out = jnp.concatenate(outs, axis=1).reshape(length, -1)
    gate = jax.nn.sigmoid(_mm(x, p["w_gate"], round_to))
    return _mm(out * gate, p["w_o"], round_to)


def _l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _kda(sizes: Sizes, p, x, round_to, state_at):
    """A KDA mixer over x [T, hidden]: (output, the state [H, d, d] after
    `state_at` tokens, or after all of them)."""
    length, heads = x.shape[0], sizes.linear_heads
    d = p["o_norm"].shape[0]
    filters = _f32(p["conv"])                                         # [kernel, 3 H d]
    kernel = filters.shape[0]
    padded = jnp.concatenate(
        [jnp.zeros((kernel - 1, filters.shape[1])), _mm(x, p["w_qkv"], round_to)])
    mixed = sum(padded[i:i + length] * filters[i] for i in range(kernel))
    q, k, v = jnp.split(jax.nn.silu(mixed).reshape(length, 3 * heads, d), 3, axis=1)
    q, k = _l2norm(q) * d ** -0.5, _l2norm(k)
    rate = jax.nn.softplus(
        _mm(_mm(x, p["w_f1"], round_to), p["w_f2"], round_to) + _f32(p["dt_bias"]))
    g = -jnp.exp(_f32(p["a_log"]))[None, :, None] * rate.reshape(length, heads, d)
    if not sizes.decay_per_channel:  # a wrong mechanism: one decay a head
        g = jnp.broadcast_to(g.mean(axis=-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(_mm(x, p["w_beta"], round_to))              # [T, H]
    if sizes.kda_allow_neg_eigval:
        beta = 2.0 * beta

    def token(state, xs):
        q, k, v, g, beta = xs                                         # [H, d], beta [H]
        state = jnp.exp(g)[:, :, None] * state
        seen = jnp.einsum("hk,hkv->hv", _round(k, round_to), _round(state, round_to))
        state = state + beta[:, None, None] * k[:, :, None] * (v - seen)[:, None, :]
        return state, jnp.einsum("hk,hkv->hv", _round(q, round_to), _round(state, round_to))

    state = jnp.zeros((heads, d, d), jnp.float32)
    xs = (q, k, v, g, beta)
    if state_at is None or state_at >= length:
        state, o = jax.lax.scan(token, state, xs)
        kept = state
    else:
        kept, first = jax.lax.scan(token, state, jax.tree_util.tree_map(lambda a: a[:state_at], xs))
        _, rest = jax.lax.scan(token, kept, jax.tree_util.tree_map(lambda a: a[state_at:], xs))
        o = jnp.concatenate([first, rest])
    gate = jax.nn.sigmoid(_mm(_mm(x, p["w_g1"], round_to), p["w_g2"], round_to))
    normed = _rms_norm(o, p["o_norm"], sizes.rms_norm_eps).reshape(length, -1)
    return _mm(normed * gate, p["w_o"], round_to), kept


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
        one = {"w_gate_up": p["experts"]["w_gate_up"][row], "w_down": p["experts"]["w_down"][row]}
        y = y + weight * _mlp(one, x, round_to)
    return y + _mlp(p["shared"], x, round_to), ids


def layer(sizes: Sizes, index, block, h, held, round_to=None, head_chunk=8, state_at=None):
    """One decoder layer over h [T, hidden] float32: (h out, chosen ids,
    the KDA state or None)."""
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(h, block["mixer_norm"], sizes.rms_norm_eps)
        if index % (sizes.gqa_interval + 1) == 0:
            out, state = _attention(sizes, block["gqa"], x, round_to, head_chunk), None
        else:
            out, state = _kda(sizes, block["kda"], x, round_to, state_at)
        h = h + out
        out, ids = _moe(
            sizes, block["moe"], _rms_norm(h, block["ffn_norm"], sizes.rms_norm_eps), held,
            round_to)
        return h + out, ids, state


def forward(sizes: Sizes, params, ids, held, round_to=None, head_chunk=8, positions=None,
            state_at=None):
    """Logits [len(positions) or T, vocab held] (float32) of the whole
    sequence `ids`, every layer's input [layers + 1, T, hidden] (the last
    entry is the final layer's output), the experts chosen in each layer
    [layers, T, k], and each KDA layer's state [linear layers, H, d, d]
    after `state_at` tokens (after the last where None). `positions`
    keeps the head to those rows."""
    with jax.default_matmul_precision("highest"):
        h = _f32(params["embed"])[jnp.asarray(ids)]
        hidden, chosen, states = [h], [], []
        for index, block in enumerate(params["layers"]):
            h, ids_l, state = layer(sizes, index, block, h, held, round_to, head_chunk, state_at)
            hidden.append(h)
            chosen.append(ids_l)
            if state is not None:
                states.append(state)
        h = _rms_norm(h, params["final_norm"], sizes.rms_norm_eps)
        if positions is not None:
            h = h[jnp.asarray(positions)]
        return _mm(h, params["head"], round_to), jnp.stack(hidden), jnp.stack(chosen), (
            jnp.stack(states))
