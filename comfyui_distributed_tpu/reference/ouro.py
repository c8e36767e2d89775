"""Plain reference of Ouro's forward pass over a whole sequence.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: Python loops over the passes
and over the layers, no cache, no scan, no kernel, and no import from the
code it is compared with (`models/ouro.py`, `ops/`). It is untied to the
system's stacked layout: `params["layers"]` is any sequence of per-layer
trees, and a weight-shared loop is nothing but the same sequence walked
again. Weights are upcast one layer at a time, so that at the published
widths it fits on a chip beside the system's bfloat16 weights.

The model (huggingface.co/ByteDance/Ouro-2.6B, `config.json` and
`modeling_ouro.py`), T = `total_ut_steps` passes over L layers:

    x = E[ids]
    for t in 1..T:
      for l in 1..L:
        a = rms(x; g1_l);  q, k, v = a Wq_l, a Wk_l, a Wv_l      # [heads, head_dim] each
        q, k = rope(q), rope(k)         # rotate-half over the whole head, theta 1e6, absolute position
        o = softmax_causal(q k^T / sqrt(head_dim)) v
        x = x + rms(o Wo_l; g2_l)
        m = rms(x; g3_l)
        x = x + rms((silu(m Wg_l) * (m Wu_l)) Wd_l; g4_l)
      x = h_t = rms(x; g_final)
      lambda_t = sigmoid(h_t . w_gate + b_gate)
    logits = h_T W_head
    p(t) = lambda_t prod_{j<t}(1 - lambda_j) for t < T;  p(T) = prod_{j<T}(1 - lambda_j)

What the published `config.json` does not say was read from the published
modeling code from memory, with no network, and is assumed here and in
the system alike: four norms a layer (one before and one after each
sub-layer, the "sandwich"); the final norm applied at the end of *every*
pass, its output feeding the next pass; an exit gate of one linear unit
with a bias on each pass's normed output; no bias on any projection and
no norm on queries or keys (the config names neither). At the published
`early_exit_threshold` of 1 no token leaves the loop early, so every
pass runs and the logits come from h_T; the gate's p(t) is returned and
decides nothing.

Departures in layout only, which random weights cannot tell from the
original: `w_qkv` is q, k and v's projections side by side, `w_gate_up`
the gate's and up's; weights are `[in, out]`. The tokenizer is outside
this file: ids are inputs.

Two variants exist for one purpose each, the comparison's limits having
to fail them:

- `round_to` rounds both operands of every matrix product to that dtype
  before multiplying in float32: the reference one precision below the
  configuration's;
- `shared_cache` makes every pass after the first attend to the *first*
  pass's keys and values of the same layer: what a cache with one slot a
  layer, instead of one a (pass, layer), would compute.

Attention is computed `head_chunk` heads at a time, which changes no
number: 16 heads' float32 scores over 2,112 tokens are 285 MB.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the parameter tree does not say about the architecture."""

    heads: int = 16
    head_dim: int = 128
    total_ut_steps: int = 4
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0

    @classmethod
    def of(cls, cfg) -> "Sizes":
        """From any object that bears the published `config.json`'s names."""
        return cls(
            heads=cfg.num_attention_heads, head_dim=cfg.head_dim,
            total_ut_steps=cfg.total_ut_steps, rms_norm_eps=cfg.rms_norm_eps,
            rope_theta=cfg.rope_theta,
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


def _rotary(sizes: Sizes, length: int):
    """cos and sin, [length, head_dim], each frequency twice over."""
    dim = sizes.head_dim
    inv_freq = 1.0 / sizes.rope_theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    freqs = np.outer(np.arange(length, dtype=np.float64), inv_freq)
    emb = np.concatenate([freqs, freqs], axis=-1)
    return _f32(np.cos(emb)), _f32(np.sin(emb))


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _attention(sizes: Sizes, block, a, round_to, head_chunk, kv):
    """Causal self-attention of a [T, hidden] (already normed): (the
    weighted sums through W_o, this layer's own rotated keys and values).
    `kv`, where given, is attended to in place of them."""
    length, heads, dim = a.shape[0], sizes.heads, sizes.head_dim
    width = heads * dim
    w_qkv = block["w_qkv"]
    q = _mm(a, w_qkv[:, :width], round_to).reshape(length, heads, dim)
    k = _mm(a, w_qkv[:, width:2 * width], round_to).reshape(length, heads, dim)
    v = _mm(a, w_qkv[:, 2 * width:], round_to).reshape(length, heads, dim)
    cos, sin = _rotary(sizes, length)
    q = q * cos[:, None, :] + _rotate_half(q) * sin[:, None, :]
    k = k * cos[:, None, :] + _rotate_half(k) * sin[:, None, :]
    own = (k, v)
    if kv is not None:
        k, v = kv
    causal = jnp.tril(jnp.ones((length, length), bool))
    outs = []
    for first in range(0, heads, head_chunk):
        chunk = slice(first, first + head_chunk)
        qh, kh, vh = (t[:, chunk].transpose(1, 0, 2) for t in (q, k, v))   # [chunk, T, dim]
        scores = _mm(qh, kh.transpose(0, 2, 1), round_to) * dim ** -0.5
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
        outs.append(_mm(probs, vh, round_to).transpose(1, 0, 2))
    out = jnp.concatenate(outs, axis=1).reshape(length, width)
    return _mm(out, block["w_o"], round_to), own


def _mlp(block, m, round_to):
    """down(silu(gate m) * up m)."""
    width = block["w_gate_up"].shape[-1] // 2
    gate = _mm(m, block["w_gate_up"][:, :width], round_to)
    up = _mm(m, block["w_gate_up"][:, width:], round_to)
    return _mm(jax.nn.silu(gate) * up, block["w_down"], round_to)


def layer(sizes: Sizes, block, x, round_to=None, head_chunk=4, kv=None):
    """One decoder layer over x [T, hidden] float32, a norm before and
    after each sub-layer: (x out, its own keys and values)."""
    eps = sizes.rms_norm_eps
    with jax.default_matmul_precision("highest"):
        out, own = _attention(
            sizes, block, _rms_norm(x, block["attn_norm"], eps), round_to, head_chunk, kv)
        x = x + _rms_norm(out, block["attn_out_norm"], eps)
        m = _mlp(block, _rms_norm(x, block["ffn_norm"], eps), round_to)
        return x + _rms_norm(m, block["ffn_out_norm"], eps), own


def exit_distribution(lambdas):
    """p(t) for t = 1..T from the gates' lambda_t (a list of arrays)."""
    survived, out = jnp.ones_like(lambdas[0]), []
    for lam in lambdas[:-1]:
        out.append(lam * survived)
        survived = survived * (1.0 - lam)
    return jnp.stack(out + [survived])


def forward(sizes: Sizes, params, ids, round_to=None, head_chunk=4, positions=None,
            shared_cache=False):
    """Of the whole sequence `ids`: the logits [len(positions) or N, vocab]
    (float32), every pass's h_t [T, positions, hidden] and the exit
    distribution p(t) [T, positions]. `positions` keeps the three to those
    rows (2,112 x 49,152 float32 logits are 415 MB, and a comparison reads
    65 of them)."""
    rows = slice(None) if positions is None else jnp.asarray(positions)
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"])[jnp.asarray(ids)]
        first_pass: list = []
        hidden, lambdas = [], []
        for step in range(sizes.total_ut_steps):
            for index, block in enumerate(params["layers"]):
                kv = first_pass[index] if shared_cache and step > 0 else None
                x, own = layer(sizes, block, x, round_to, head_chunk, kv)
                if shared_cache and step == 0:
                    first_pass.append(own)
            x = _rms_norm(x, params["final_norm"], sizes.rms_norm_eps)
            h = x[rows]
            hidden.append(h)
            # the gate is one unit wide and float32 in the system too:
            # never rounded
            score = jnp.matmul(h, _f32(params["gate"]["w"])) + _f32(params["gate"]["b"])
            lambdas.append(jax.nn.sigmoid(score))
        logits = _mm(hidden[-1], params["head"], round_to)
        return logits, jnp.stack(hidden), exit_distribution(lambdas)
