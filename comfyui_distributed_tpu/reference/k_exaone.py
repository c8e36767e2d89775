"""Plain reference of K-EXAONE's forward passes over a whole sequence.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no cache, no ring, no blocks of
query rows (full `[T, T]` masks: causal, and causal with i - j < window),
no grouped product (a loop over experts), no loop of decode steps, and
nothing imported from the code it is compared with (`models/k_exaone.py`,
`models/moe.py`, `ops/`). It is written from the layer equations in that
configuration's issue (the published `config.json` gives every size and
the layer pattern; the modelling code is not in the sandbox), layer by
layer, and reads the system's own parameter tree, upcasting one weight at
a time, so that at published widths it fits on a chip beside the system's
bfloat16 weights.

    h += mixer(rms(h));  h += ffn(rms(h))

Mixer: q = W_q x, k = W_k x, v = W_v x; q and k normed over each head's
channels with a learned scale; a layer whose letter in the pattern is L
(a window layer) rotates q and k by their position (`rotate_half` over
all of a head's channels, theta `rope_theta`) and position i sees i -
window < j <= i; a G layer rotates nothing and sees every j <= i. Key
head j serves query heads j x group .. (j + 1) x group - 1. Layers below
`first_k_dense_replace` have a dense SwiGLU, the others the mixture:
sigmoid scores, the k largest of score + bias, the chosen scores over
their sum times the scaling factor, beside one shared expert.

`forward` gives the main model's logits and the residual stream h after
the last layer at every position; `mtp_forward` the MTP module's draft
logits at every position i that has a next token, from h_i and x_{i+1}:

    u_i = W_eh [rms_e(E[x_{i+1}]) ; rms_h(h_i)],   one G layer with the mixture,
    draft logits for x_{i+2} = Head(rms_mtp(layer(u)_i))

Speculation is the system's: the reference says what each distribution
must be, and `speculative_rule` what the emitted token's distribution is
under the rule, from the two probability vectors.

`held` lists the routed experts the tree's expert stacks hold, row j of a
stack being expert `held[j]`: all of them, or one chip's share; what the
others would have added is left out, as in the system. The embedding and
the head may be a slice of the vocabulary.

Attention is computed `head_chunk` query heads at a time, which changes
no number. `round_to` rounds both operands of every matrix product to
that dtype before multiplying in float32, for the one purpose of setting
the comparison's limit between the system's reading and this reference's
one precision below the configuration's. `Sizes.windowed` false is a
wrong mechanism for the same purpose: window layers that see every
position.
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
    window: int = 128
    pattern: str = "LLLG"
    rope_theta: float = 1e6
    first_k_dense_replace: int = 1
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-5
    windowed: bool = True

    @classmethod
    def of(cls, cfg) -> "Sizes":
        """From any object that bears the published `config.json`'s names."""
        return cls(
            heads=cfg.num_attention_heads, kv_heads=cfg.num_key_value_heads,
            window=cfg.sliding_window, pattern=cfg.sliding_window_pattern,
            rope_theta=cfg.rope_theta, first_k_dense_replace=cfg.first_k_dense_replace,
            num_experts=cfg.num_experts, num_experts_per_tok=cfg.num_experts_per_tok,
            norm_topk_prob=cfg.norm_topk_prob,
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


def _rotate(x, theta):
    """x [T, heads, d] by its row's position: the two halves of the last
    axis are a pair's members."""
    length, _, d = x.shape
    inverse = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(length, dtype=jnp.float32)[:, None] * inverse[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(sizes: Sizes, p, x, window: bool, round_to, head_chunk):
    """Softmax attention with grouped queries over x [T, hidden]."""
    length = x.shape[0]
    heads, group = sizes.heads, sizes.heads // sizes.kv_heads
    q = _mm(x, p["w_q"], round_to).reshape(length, heads, -1)
    k = _mm(x, p["w_k"], round_to).reshape(length, sizes.kv_heads, -1)
    v = _mm(x, p["w_v"], round_to).reshape(length, sizes.kv_heads, -1)
    q = _rms_norm(q, p["q_norm"], sizes.rms_norm_eps)
    k = _rms_norm(k, p["k_norm"], sizes.rms_norm_eps)
    seen = jnp.tril(jnp.ones((length, length), bool))
    if window:
        q, k = _rotate(q, sizes.rope_theta), _rotate(k, sizes.rope_theta)
        if sizes.windowed:
            i, j = jnp.arange(length)[:, None], jnp.arange(length)[None, :]
            seen = seen & (i - j < sizes.window)
    scale = q.shape[-1] ** -0.5
    outs = []
    for first in range(0, heads, head_chunk):
        mine = np.arange(first, min(first + head_chunk, heads))
        qh = q[:, mine].transpose(1, 0, 2)                            # [chunk, T, d]
        kh = k[:, mine // group].transpose(1, 0, 2)                   # each head's key head
        vh = v[:, mine // group].transpose(1, 0, 2)
        scores = _mm(qh, kh.transpose(0, 2, 1), round_to) * scale
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        outs.append(_mm(probs, vh, round_to).transpose(1, 0, 2))
    return _mm(jnp.concatenate(outs, axis=1).reshape(length, -1), p["w_o"], round_to)


def route(sizes: Sizes, bias, logits):
    """Router logits [T, experts] in: (ids [T, k], weights [T, k])."""
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


def layer(sizes: Sizes, block, h, window: bool, held, round_to=None, head_chunk=8):
    """One decoder layer over h [T, hidden] float32: (h out, chosen ids
    or None for a dense layer)."""
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(h, block["mixer_norm"], sizes.rms_norm_eps)
        h = h + _attention(sizes, block["attn"], x, window, round_to, head_chunk)
        x = _rms_norm(h, block["ffn_norm"], sizes.rms_norm_eps)
        if "mlp" in block:
            return h + _mlp(block["mlp"], x, round_to), None
        out, ids = _moe(sizes, block["moe"], x, held, round_to)
        return h + out, ids


def _logits(sizes, params, h, norm, positions, round_to):
    h = _rms_norm(h, norm, sizes.rms_norm_eps)
    if positions is not None:
        h = h[jnp.asarray(positions)]
    return _mm(h, params["head"], round_to)


def forward(sizes: Sizes, params, ids, held, round_to=None, head_chunk=8, positions=None):
    """The main model over the whole sequence `ids`: logits
    [len(positions) or T, vocab held] (float32), the residual stream
    after the last layer [T, hidden], and the experts chosen in each
    sparse layer [sparse layers, T, k]. `positions` keeps the head to
    those rows."""
    with jax.default_matmul_precision("highest"):
        h = _f32(params["embed"])[jnp.asarray(ids)]
        chosen = []
        for index, block in enumerate(params["layers"]):
            window = sizes.pattern[index % len(sizes.pattern)] == "L"
            h, ids_l = layer(sizes, block, h, window, held, round_to, head_chunk)
            if ids_l is not None:
                chosen.append(ids_l)
        logits = _logits(sizes, params, h, params["final_norm"], positions, round_to)
        return logits, h, jnp.stack(chosen)


def mtp_forward(sizes: Sizes, params, h, ids, held, round_to=None, head_chunk=8,
                positions=None):
    """The MTP module over the whole sequence: from the main model's
    residual streams h [T, hidden] (`forward`'s) and the ids [T], the
    draft logits at positions 0 .. T - 2 (row i, from h_i and x_{i+1},
    is the distribution of x_{i+2}), or at `positions` of them, and the
    experts chosen [T - 1, k]."""
    with jax.default_matmul_precision("highest"):
        p = params["mtp"]
        ids = jnp.asarray(ids)
        both = jnp.concatenate([
            _rms_norm(_f32(params["embed"])[ids[1:]], p["embed_norm"], sizes.rms_norm_eps),
            _rms_norm(h[:-1], p["hidden_norm"], sizes.rms_norm_eps),
        ], axis=-1)
        out, chosen = layer(
            sizes, p["layer"], _mm(both, p["w_eh"], round_to), False, held, round_to, head_chunk)
        return _logits(sizes, params, out, p["norm"], positions, round_to), chosen


def speculative_rule(p, q):
    """The distribution of the token a self-speculative step emits after
    the last one, from the main model's p and the draft's q [vocab]: a
    draft d ~ q is kept with probability min(1, p_d / q_d), else the
    token is drawn from max(p - q, 0) renormalised. Returns (accept
    [vocab], the residual distribution [vocab], the emitted token's
    distribution [vocab], which is p)."""
    accept = jnp.minimum(1.0, p / q)
    left = jnp.maximum(p - q, 0.0)
    left = left / jnp.sum(left)
    return accept, left, q * accept + jnp.sum(q * (1.0 - accept)) * left
