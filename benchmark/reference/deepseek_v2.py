"""Plain reference of DeepSeek-V2's forward pass over a whole sequence.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no cache, no absorbed form of
the attention, no kernel, no grouped product (a loop over experts), and
no import from the code it is compared with (`models/deepseek_v2.py`,
`ops/`). It follows the published implementation
(huggingface.co/deepseek-ai/DeepSeek-V2, `modeling_deepseek.py`:
`DeepseekV2Attention`, `MoEGate`, `DeepseekV2MoE`, `DeepseekV2MLP`,
`DeepseekV2YarnRotaryEmbedding`) layer by layer, and reads the system's
own parameter tree, upcasting one weight at a time (one expert at a
time), so that at published widths it fits on a chip beside the system's
bfloat16 weights.

`held` lists the routed experts the tree's expert stacks hold, row j of a
stack being expert `held[j]`: all of them, or one chip's share. Every
token is routed over all `n_routed_experts`; the output of an expert
layer is the shared experts' plus the chosen experts' that are in `held`,
and what the others would have added is left out, as in the system.
Likewise the embedding and the head may be a slice of the vocabulary.

Departures from the published code, each kept because the system under
test makes the same choice and the two must be given the same problem:

- rotary layout: the published checkpoint stores a rotary pair in
  adjacent columns and the code permutes them to the half-split order
  before `rotate_half`; here the weights are taken to be in the
  half-split order already (a fixed permutation of the columns of
  `q_b_proj` and `kv_a_proj_with_mqa`, which random weights cannot tell).
- `kv_b_proj` is stored as its two halves `w_uk` and `w_uv`, `gate_proj`
  and `up_proj` side by side as `w_gate_up`; weights are `[in, out]`.
- ties in the router's top-k go to the lower index (`torch.topk` does not
  say); they do not occur on random scores.
- the tokenizer is outside this file: ids are inputs.

Attention is computed `head_chunk` heads at a time, which changes no
number: 128 heads' float32 scores over 2,304 tokens are 2.7 GB.

`round_to` rounds both operands of every matrix product to that dtype
before multiplying in float32. It exists for one purpose: the
comparison's limit is set between what the system gives and what this
reference gives when computed one precision below the configuration's.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the parameter tree does not say about the architecture."""

    heads: int = 128
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    n_routed_experts: int = 160
    num_experts_per_tok: int = 6
    n_group: int = 8
    topk_group: int = 3
    routed_scaling_factor: float = 16.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707
    original_max_position_embeddings: int = 4096

    @classmethod
    def of(cls, cfg) -> "Sizes":
        """From any object that bears the published `config.json`'s names
        (the `rope_scaling` block's prefixed with `rope_`)."""
        return cls(
            heads=cfg.num_attention_heads, qk_nope_head_dim=cfg.qk_nope_head_dim,
            qk_rope_head_dim=cfg.qk_rope_head_dim, n_routed_experts=cfg.n_routed_experts,
            num_experts_per_tok=cfg.num_experts_per_tok, n_group=cfg.n_group,
            topk_group=cfg.topk_group, routed_scaling_factor=cfg.routed_scaling_factor,
            rms_norm_eps=cfg.rms_norm_eps, rope_theta=cfg.rope_theta,
            rope_factor=cfg.rope_factor, beta_fast=cfg.rope_beta_fast,
            beta_slow=cfg.rope_beta_slow, mscale=cfg.rope_mscale,
            mscale_all_dim=cfg.rope_mscale_all_dim,
            original_max_position_embeddings=cfg.rope_original_max_position_embeddings,
        )


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _mm(a, b, round_to):
    a, b = _f32(a), _f32(b)
    if round_to is not None:
        a = a.astype(round_to).astype(jnp.float32)
        b = b.astype(round_to).astype(jnp.float32)
    return jnp.matmul(a, b)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(scale)


def _mlp(p, x, round_to):
    """DeepseekV2MLP: down(silu(gate x) * up x)."""
    width = p["w_gate_up"].shape[-1] // 2
    gate = _mm(x, p["w_gate_up"][..., :width], round_to)
    up = _mm(x, p["w_gate_up"][..., width:], round_to)
    return _mm(jax.nn.silu(gate) * up, p["w_down"], round_to)


def _yarn_get_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def _rotary(sizes: Sizes, length: int):
    """DeepseekV2YarnRotaryEmbedding: cos and sin, [length, rope]."""
    dim, base = sizes.qk_rope_head_dim, sizes.rope_theta
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    freq_inter = 1.0 / (sizes.rope_factor * base ** (np.arange(0, dim, 2, dtype=np.float64) / dim))

    def find_correction_dim(num_rotations):
        return (
            dim * math.log(sizes.original_max_position_embeddings / (num_rotations * 2 * math.pi))
        ) / (2 * math.log(base))

    low = max(math.floor(find_correction_dim(sizes.beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(sizes.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    inv_freq = freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask
    freqs = np.outer(np.arange(length, dtype=np.float64), inv_freq)
    emb = np.concatenate([freqs, freqs], axis=-1)
    m = _yarn_get_mscale(sizes.rope_factor, sizes.mscale) / _yarn_get_mscale(
        sizes.rope_factor, sizes.mscale_all_dim
    )
    return _f32(np.cos(emb) * m), _f32(np.sin(emb) * m)


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _attention(sizes: Sizes, p, x, round_to, head_chunk):
    """DeepseekV2Attention over x [T, hidden], causal."""
    length = x.shape[0]
    heads, nope, rope = sizes.heads, sizes.qk_nope_head_dim, sizes.qk_rope_head_dim
    kv_lora = p["kv_norm"].shape[0]
    cos, sin = _rotary(sizes, length)

    c_q = _rms_norm(_mm(x, p["w_dq"], round_to), p["q_norm"], sizes.rms_norm_eps)
    q = _mm(c_q, p["w_uq"], round_to).reshape(length, heads, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    down = _mm(x, p["w_dkv"], round_to)
    c_kv = _rms_norm(down[:, :kv_lora], p["kv_norm"], sizes.rms_norm_eps)
    k_rope = down[:, kv_lora:]
    q_rope = q_rope * cos[:, None, :] + _rotate_half(q_rope) * sin[:, None, :]
    k_rope = k_rope * cos + _rotate_half(k_rope) * sin

    m = _yarn_get_mscale(sizes.rope_factor, sizes.mscale_all_dim)
    scale = (nope + rope) ** -0.5 * m * m
    causal = jnp.tril(jnp.ones((length, length), bool))
    outs = []
    for first in range(0, heads, head_chunk):
        chunk = slice(first, first + head_chunk)
        w_uk = _f32(p["w_uk"][:, chunk])                     # [kv_lora, chunk, nope]
        w_uv = _f32(p["w_uv"][:, chunk])
        width = w_uk.shape[1]
        k_nope = _mm(c_kv, w_uk.reshape(kv_lora, -1), round_to).reshape(length, width, nope)
        v = _mm(c_kv, w_uv.reshape(kv_lora, -1), round_to).reshape(length, width, -1)
        qh = jnp.concatenate([q_nope[:, chunk], q_rope[:, chunk]], axis=-1).transpose(1, 0, 2)
        kh = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, None, :], (length, width, rope))], axis=-1
        ).transpose(1, 0, 2)
        scores = _mm(qh, kh.transpose(0, 2, 1), round_to) * scale     # [chunk, T, T]
        scores = jnp.where(causal[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        outs.append(_mm(probs, v.transpose(1, 0, 2), round_to).transpose(1, 0, 2))
    out = jnp.concatenate(outs, axis=1).reshape(length, -1)
    return _mm(out, p["w_o"], round_to)


def route(sizes: Sizes, scores):
    """MoEGate with `group_limited_greedy`, softmax scores [T, experts]
    in: (ids [T, k], weights [T, k])."""
    tokens = scores.shape[0]
    group_scores = scores.reshape(tokens, sizes.n_group, -1).max(axis=-1)
    group_idx = jnp.argsort(-group_scores, axis=-1, stable=True)[:, : sizes.topk_group]
    group_mask = jnp.zeros((tokens, sizes.n_group)).at[
        jnp.arange(tokens)[:, None], group_idx
    ].set(1.0)
    per_group = sizes.n_routed_experts // sizes.n_group
    score_mask = jnp.repeat(group_mask, per_group, axis=1) > 0
    tmp_scores = jnp.where(score_mask, scores, 0.0)
    ids = jnp.argsort(-tmp_scores, axis=-1, stable=True)[:, : sizes.num_experts_per_tok]
    weights = jnp.take_along_axis(tmp_scores, ids, axis=-1)
    return ids, weights * sizes.routed_scaling_factor  # norm_topk_prob is false


def _moe(sizes: Sizes, p, x, held, round_to):
    """DeepseekV2MoE: (output, chosen ids). The router's product is never
    rounded: the published code computes it in float32 whatever the
    model's dtype."""
    logits = jnp.matmul(x, _f32(p["w_g"]))
    ids, weights = route(sizes, jax.nn.softmax(logits, axis=-1))
    y = jnp.zeros_like(x)
    for row, expert in enumerate(held):
        weight = jnp.sum(jnp.where(ids == expert, weights, 0.0), axis=-1, keepdims=True)
        one = {"w_gate_up": p["experts"]["w_gate_up"][row], "w_down": p["experts"]["w_down"][row]}
        y = y + weight * _mlp(one, x, round_to)
    return y + _mlp(p["shared"], x, round_to), ids


def layer(sizes: Sizes, block, h, held, round_to=None, head_chunk=16):
    """One decoder layer over h [T, hidden] float32: (h out, chosen ids
    or None)."""
    with jax.default_matmul_precision("highest"):
        h = h + _attention(
            sizes, block["attn"], _rms_norm(h, block["attn_norm"], sizes.rms_norm_eps),
            round_to, head_chunk,
        )
        x = _rms_norm(h, block["ffn_norm"], sizes.rms_norm_eps)
        if "moe" in block:
            out, ids = _moe(sizes, block["moe"], x, held, round_to)
        else:
            out, ids = _mlp(block["mlp"], x, round_to), None
        return h + out, ids


def forward(sizes: Sizes, params, ids, held, round_to=None, head_chunk=16, positions=None):
    """Logits [len(positions) or T, vocab held] (float32) of the whole
    sequence `ids`, every layer's input [layers + 1, T, hidden] (the last
    entry is the final layer's output) and the experts chosen in each
    expert layer [moe layers, T, k]. `positions` keeps the head to those
    rows: 2,304 x 25,600 float32 logits are 236 MB, and a comparison
    reads 257 of them."""
    with jax.default_matmul_precision("highest"):
        h = _f32(params["embed"])[jnp.asarray(ids)]
        hidden, chosen = [h], []
        for block in params["layers"]:
            h, ids_l = layer(sizes, block, h, held, round_to, head_chunk)
            hidden.append(h)
            if ids_l is not None:
                chosen.append(ids_l)
        h = _rms_norm(h, params["final_norm"], sizes.rms_norm_eps)
        if positions is not None:
            h = h[jnp.asarray(positions)]
        return _mm(h, params["head"], round_to), jnp.stack(hidden), jnp.stack(chosen)
