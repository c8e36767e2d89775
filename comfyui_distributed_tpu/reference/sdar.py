"""Plain reference of SDAR's forward pass over a whole sequence, and of
its generation by masked diffusion over blocks.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernel, no cache, no scan
over layers, no grouped product (a loop over all the experts with the
weights zero where an expert was not chosen), the full `[T, T]` mask, and
nothing imported from the code it is compared with (`models/sdar.py`,
`models/lm_common.py`, `models/moe.py`, `ops/`). It is written from the
layer equations in that configuration's issue (the published
`config.json` gives every size; the backbone is Qwen3-MoE's, whose
published modelling code `tests/test_sdar_published.py` holds `forward`
against), layer by layer, and reads the system's own parameter tree,
upcasting one weight at a time, so that at published widths it fits on a
chip beside the system's bfloat16 weights.

    h += attn(rms(h));  h += moe(rms(h));  logits = W_head rms(h)

Attention: q = W_q x, k = W_k x, v = W_v x, no bias; q and k normed over
each head's channels under a learned scale, THEN rotated by their
position (`rotate_half` over all of a head's channels, theta
`rope_theta`); scores at d^-1/2; position i sees position j iff
floor(j / B) <= floor(i / B), B the block length: every earlier block and
all of its own; float32 softmax; key head j serves query heads j x group
.. (j + 1) x group - 1. Expert layer: p = softmax(W_g x) over all the
experts, the k largest (ties to the lower index), the chosen p over their
sum, y = sum_e w_e W_down_e (silu(W_gate_e x) * W_up_e x); no shared
expert, no bias, no scaling factor. A logit at position i is of token i
itself: there is no shift.

`generate` is the family's published `generate.py`
(`block_diffusion_generate`, remasking `low_confidence_dynamic`): with L
prompt tokens the first P = floor(L / B) B are given and the L - P left
over open the first block unmasked; block by block, the block is its
known tokens and the mask's id elsewhere; for up to S passes, while a
position is masked: a `forward` over positions 0 .. the block's end gives
the block's logits, at each masked i x0_i ~ softmax(logits_i / T) with
c_i = softmax(logits_i / T)[x0_i] (T = 0: the largest, and its
probability at T = 1), and with n_s = floor(B / S) + (s < B mod S): the
masked positions with c_i > tau if they number n_s or more, else the n_s
of largest c (ties to the lower index), take their x0. Every pass is a
`forward` over the whole sequence so far, so a kept key is recomputed,
never read; the published procedure's closing pass, which only stores
keys and values, has nothing to do here.

Departures from the published description, each the system's too: what
is masked is kept as a boolean and not read off `ids == mask id`, so a
drawn id that equals the mask's is a token like any other and a block
closes after S passes at most; there is no top-k and no top-p (the
published defaults); batch 1.

`round_to` rounds both operands of every matrix product to that dtype
before multiplying in float32, for the one purpose of setting the
comparison's limit between the system's reading and this reference's one
precision below the configuration's. `Sizes.block_mask` false (a plain
causal mask), `norm_then_rotate` false (rotation before the norm),
`norm_topk_prob` false (the chosen weights not renormalised) and
`score_scale` (another scale than d^-1/2) are wrong mechanisms for the
same purpose.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the parameter tree does not say about the architecture, and
    the generation procedure's values."""

    heads: int = 32
    kv_heads: int = 4
    rope_theta: float = 1e6
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    block_length: int = 4
    denoising_steps: int = 4
    confidence_threshold: float = 0.85
    mask_token_id: int = 151669
    block_mask: bool = True
    norm_then_rotate: bool = True
    score_scale: float | None = None

    @classmethod
    def of(cls, cfg) -> "Sizes":
        """From any object that bears the published `config.json`'s names
        and the generation procedure's four."""
        return cls(
            heads=cfg.num_attention_heads, kv_heads=cfg.num_key_value_heads,
            rope_theta=cfg.rope_theta, num_experts_per_tok=cfg.num_experts_per_tok,
            norm_topk_prob=cfg.norm_topk_prob, rms_norm_eps=cfg.rms_norm_eps,
            block_length=cfg.block_length, denoising_steps=cfg.denoising_steps,
            confidence_threshold=cfg.confidence_threshold, mask_token_id=cfg.mask_token_id,
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


def _rotate(x, theta):
    """x [T, heads, d] by its row's position: the two halves of the last
    axis are a pair's members."""
    length, _, d = x.shape
    inverse = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(length, dtype=jnp.float32)[:, None] * inverse[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def seen_mask(sizes: Sizes, length: int):
    """[T, T] bool: row i sees column j."""
    i, j = jnp.arange(length)[:, None], jnp.arange(length)[None, :]
    if not sizes.block_mask:
        return j <= i
    return j // sizes.block_length <= i // sizes.block_length


def _attention(sizes: Sizes, p, x, round_to, head_chunk):
    """Softmax attention with grouped queries over x [T, hidden]; returns
    (output, keys [T, key heads, d], values)."""
    length = x.shape[0]
    heads, group = sizes.heads, sizes.heads // sizes.kv_heads
    q = _mm(x, p["w_q"], round_to).reshape(length, heads, -1)
    k = _mm(x, p["w_k"], round_to).reshape(length, sizes.kv_heads, -1)
    v = _mm(x, p["w_v"], round_to).reshape(length, sizes.kv_heads, -1)
    if sizes.norm_then_rotate:
        q = _rotate(_rms_norm(q, p["q_norm"], sizes.rms_norm_eps), sizes.rope_theta)
        k = _rotate(_rms_norm(k, p["k_norm"], sizes.rms_norm_eps), sizes.rope_theta)
    else:
        q = _rms_norm(_rotate(q, sizes.rope_theta), p["q_norm"], sizes.rms_norm_eps)
        k = _rms_norm(_rotate(k, sizes.rope_theta), p["k_norm"], sizes.rms_norm_eps)
    seen = seen_mask(sizes, length)
    scale = q.shape[-1] ** -0.5 if sizes.score_scale is None else sizes.score_scale
    outs = []
    for first in range(0, heads, head_chunk):
        mine = np.arange(first, min(first + head_chunk, heads))
        qh = q[:, mine].transpose(1, 0, 2)                            # [chunk, T, d]
        kh = k[:, mine // group].transpose(1, 0, 2)                   # each head's key head
        vh = v[:, mine // group].transpose(1, 0, 2)
        scores = _mm(qh, kh.transpose(0, 2, 1), round_to) * scale
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        outs.append(_mm(probs, vh, round_to).transpose(1, 0, 2))
    out = _mm(jnp.concatenate(outs, axis=1).reshape(length, -1), p["w_o"], round_to)
    return out, k, v


def route(sizes: Sizes, logits):
    """Router logits [T, experts] in: (ids [T, k], weights [T, k])."""
    probs = jax.nn.softmax(logits, axis=-1)
    ids = jnp.argsort(-probs, axis=-1, stable=True)[:, : sizes.num_experts_per_tok]
    weights = jnp.take_along_axis(probs, ids, axis=-1)
    if sizes.norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return ids, weights


def _moe(sizes: Sizes, p, x, round_to):
    """(output, chosen ids). The router's product is never rounded."""
    ids, weights = route(sizes, jnp.matmul(x, _f32(p["w_g"])))
    experts = p["experts"]
    width = experts["w_gate_up"].shape[-1] // 2
    y = jnp.zeros_like(x)
    for expert in range(experts["w_down"].shape[0]):
        weight = jnp.sum(jnp.where(ids == expert, weights, 0.0), axis=-1, keepdims=True)
        gate = _mm(x, experts["w_gate_up"][expert, :, :width], round_to)
        up = _mm(x, experts["w_gate_up"][expert, :, width:], round_to)
        y = y + weight * _mm(jax.nn.silu(gate) * up, experts["w_down"][expert], round_to)
    return y, ids


def layer(sizes: Sizes, block, h, round_to=None, head_chunk=8):
    """One decoder layer over h [T, hidden] float32: (h out, chosen ids
    [T, k], keys and values [2, key heads, T, d] as a cache holds them)."""
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(h, block["attn_norm"], sizes.rms_norm_eps)
        out, k, v = _attention(sizes, block["attn"], x, round_to, head_chunk)
        h = h + out
        out, ids = _moe(sizes, block["moe"], _rms_norm(h, block["moe_norm"], sizes.rms_norm_eps),
                        round_to)
        return h + out, ids, jnp.stack([k, v]).transpose(0, 2, 1, 3)


def head(sizes: Sizes, params, h, round_to=None):
    with jax.default_matmul_precision("highest"):
        return _mm(_rms_norm(h, params["final_norm"], sizes.rms_norm_eps), params["head"],
                   round_to)


def forward(sizes: Sizes, params, ids, round_to=None, head_chunk=8, positions=None):
    """The model over the whole sequence `ids` (a whole number of blocks,
    or the mask's last block is short): logits [len(positions) or T,
    vocab] float32 (row i of token i itself), the experts chosen [layers,
    T, k] and every layer's keys and values [layers, 2, key heads, T, d].
    `positions` keeps the head to those rows."""
    with jax.default_matmul_precision("highest"):
        h = _f32(params["embed"])[jnp.asarray(ids)]
        chosen, kv = [], []
        for block in params["layers"]:
            h, ids_l, entries = layer(sizes, block, h, round_to, head_chunk)
            chosen.append(ids_l)
            kv.append(entries)
        if positions is not None:
            h = h[jnp.asarray(positions)]
        return head(sizes, params, h, round_to), jnp.stack(chosen), jnp.stack(kv)


def confidence(logits, drawn, temperature: float):
    """softmax(logits / T)[drawn] a row (T = 0: at T = 1)."""
    scaled = logits / (temperature if temperature > 0 else 1.0)
    return jnp.take_along_axis(jax.nn.softmax(scaled, axis=-1), drawn[:, None], axis=-1)[:, 0]


def transferred(sizes: Sizes, conf, masked, s: int):
    """Which positions of a block take their drawn ids in pass `s`, from
    the drawn ids' confidences [B] and what is masked [B]: numpy in,
    numpy out."""
    block, passes = sizes.block_length, sizes.denoising_steps
    n = block // passes + (s < block % passes)
    conf = np.where(masked, np.asarray(conf, np.float32), -np.inf)
    above = conf > sizes.confidence_threshold
    if above.sum() >= n:
        return above
    order = np.argsort(-conf, kind="stable")[:n]  # ties to the lower index
    kept = np.zeros_like(masked)
    kept[order] = True
    return kept & masked


def generate(sizes: Sizes, params, prompt, steps: int, key, temperature: float = 1.0,
             round_to=None):
    """`steps` new ids after `prompt` by the procedure at the top: a
    Python loop over blocks and passes, every pass one `forward` over
    positions 0 .. the block's end. Returns (ids [steps], the passes each
    block took)."""
    block = sizes.block_length
    known = [int(t) for t in prompt]
    first = len(known) - len(known) % block
    ids, took = list(known[:first]), []
    for b in range(-(-(len(known) - first + steps) // block)):
        opening = known[first + b * block:first + (b + 1) * block] if b == 0 else []
        tokens = np.array(opening + [sizes.mask_token_id] * (block - len(opening)), np.int32)
        masked = np.arange(block) >= len(opening)
        s = 0
        while masked.any():
            logits, _, _ = forward(
                sizes, params, np.concatenate([np.asarray(ids, np.int32), tokens]),
                round_to=round_to, positions=np.arange(len(ids), len(ids) + block))
            if temperature > 0:
                keys = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, b), s), block)
                drawn = jax.vmap(jax.random.categorical)(keys, logits / temperature)
            else:
                drawn = jnp.argmax(logits, axis=-1)
            move = transferred(sizes, confidence(logits, drawn, temperature), masked, s)
            tokens = np.where(move, np.asarray(drawn, np.int32), tokens)
            masked = masked & ~move
            s += 1
        took.append(s)
        ids.extend(int(t) for t in tokens)
    return np.asarray(ids[len(known):len(known) + steps], np.int32), took
