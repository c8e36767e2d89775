"""Plain reference of dots3-note-prev's text path over a whole sequence.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: the whole sequence at once,
every key and value expanded from its latent, a `[T, T]` mask a layer
(the band of a sliding layer, or a full layer's selection by a full
stable sort of its `[T, T]` index scores), no cache, no ring, no parts,
no absorbed form, no kernel, no grouped product (a loop over the held
experts), no loop of decode steps, and nothing imported from the code it
is compared with (`models/dots3.py`, `models/dsa.py`, `models/mla.py`,
`models/moe.py`, `ops/`). It is written from the layer equations of the
configuration's issue (ISSUE 61, Tentpole section 1) and reads the
system's own parameter tree, upcasting one weight at a time. `[T, T]`
arrays are bools whole and float32 a block of `row_block` query rows
(and `head_chunk` heads) at a time, which changes no number, so that
33,024 positions at the published widths fit beside the weights.

    h += attn(rms(h));  h += ffn(rms(h));  logits = W_head rms(h)

Attention, x the normed input, the sizes those of the layer's kind (a
layer whose tree has an `indexer` is full, any other sliding):

    c_q = rms(W_dq x);  [q_nope | q_rope] = s_q W_uq c_q,  s_q = (hidden / r_q)^1/2
    [c | k_r] = W_dkv x;  c' = s_kv rms(c),  s_kv = (hidden / r)^1/2
    q_rope, k_r rotated in pairs (2i, 2i + 1) at the kind's theta; k_r not scaled
    k_nope_j = W_uk c'_j,  v_j = W_uv c'_j
    score_ij = (q_nope_i . k_nope_j + q_rope_i . rot(k_r)_j) / sqrt(nope + rope)
    o_i = sum_{j seen} softmax_{j seen}(score_ij) v_j
    out = W_o [sigmoid(W_gate x)_h o_h]_h

What i sees. Sliding: 0 <= i - j < window. Full, the layer's own index:

    qI_i = W_qI c_q,i  [heads, d] (c_q before s_q), the first `rope` of each head rotated in pairs
    kI_j = LayerNorm(W_kI x_j)  [d], the first `rope` rotated, the full kind's theta
    w_i  = W_w x_i heads^-1/2 d^-1/2
    I_ij = sum_h w_ih relu(qI_ih . kI_j)   for j <= i
    S_i  = the `index_topk` positions j <= i with the largest I_ij; ties to the lower j

Feed-forward: a dense SwiGLU where the tree has `mlp`, else sigmoid
scores, the k largest of score + bias (ties to the lower index), the
chosen scores over their sum times the scaling factor, beside one shared
expert.

Departures from the published description, each also in the
configuration's `assumed`: the vision tower, the audio encoder and the
MTP module are left out (the config gives none of their sizes); the
rescale's and the gate's forms are inferred from their switches' names
(LongCat-Flash's `mla_scale_*_lora`; arXiv:2505.06708's head-wise gate);
weights are seeded.

`held` lists the routed experts the tree's expert stacks hold, row j of a
stack being expert `held[j]`; what the others would have added is left
out, as in the system. `round_to` rounds both operands of every matrix
product to that dtype before multiplying in float32, for the one purpose
of setting the comparison's limit. `Sizes` has wrong mechanisms for the
same purpose: `window` another number or None (a sliding layer sees
every position before it), `gate` false, `rescale_q` / `rescale_kv`
false, `swa_rope_theta` the full layers', `relu` false, `index_topk`
another number, and `blind_part` (a sliding layer's query sees no
position before the first of its own part of that many positions).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the parameter tree does not say about the architecture."""

    rope_theta: float = 8e7
    swa_rope_theta: float = 5e4
    window: int | None = 513
    index_heads: int = 64
    index_topk: int = 2048
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    rescale_q: bool = True
    rescale_kv: bool = True
    gate: bool = True
    relu: bool = True
    blind_part: int = 0

    @classmethod
    def of(cls, cfg, **wrong) -> "Sizes":
        """From any object that bears the published `config.json`'s names."""
        given = dict(
            rope_theta=cfg.rope_theta, swa_rope_theta=cfg.swa_rope_theta,
            window=cfg.sliding_window_size, index_heads=cfg.index_n_heads,
            index_topk=cfg.index_topk, num_experts_per_tok=cfg.num_experts_per_tok,
            norm_topk_prob=cfg.norm_topk_prob,
            routed_scaling_factor=cfg.routed_scaling_factor, rms_norm_eps=cfg.rms_norm_eps,
            rescale_q=cfg.apply_mla_qkv_lora_rescale, rescale_kv=cfg.apply_mla_qkv_lora_rescale)
        return cls(**{**given, **wrong})


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _round(a, round_to):
    a = _f32(a)
    return a if round_to is None else a.astype(round_to).astype(jnp.float32)


def _mm(a, b, round_to):
    return jnp.matmul(_round(a, round_to), _round(b, round_to))


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(scale)


def _layer_norm(x, scale, bias, eps):
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(centred * centred, axis=-1, keepdims=True)
    return centred * jax.lax.rsqrt(var + eps) * _f32(scale) + _f32(bias)


def _mlp(p, x, round_to, row_block=1024):
    """down(silu(gate x) * up x), a block of rows at a time."""
    width = p["w_gate_up"].shape[-1] // 2
    w_gate, w_up = _f32(p["w_gate_up"][..., :width]), _f32(p["w_gate_up"][..., width:])
    w_down = _f32(p["w_down"])
    return jnp.concatenate([
        _mm(jax.nn.silu(_mm(rows, w_gate, round_to)) * _mm(rows, w_up, round_to), w_down, round_to)
        for rows in (x[i:i + row_block] for i in range(0, x.shape[0], row_block))])


def _rotate(x, theta):
    """x [T, ..., d] by its row's position: channels 2i and 2i + 1 are a
    pair's members."""
    length, d = x.shape[0], x.shape[-1]
    inverse = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(length, dtype=jnp.float32)[:, None] * inverse[None, :]
    angles = angles.reshape(length, *(1,) * (x.ndim - 2), d // 2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def band(sizes: Sizes, length: int):
    """[T, T]: which positions a sliding layer's queries see."""
    i, j = jnp.arange(length)[:, None], jnp.arange(length)[None, :]
    seen = j <= i
    if sizes.window is not None:
        seen = seen & (i - j < sizes.window)
    if sizes.blind_part:
        seen = seen & (j >= i - i % sizes.blind_part)
    return seen


def selection(sizes: Sizes, p, c_q, x, rope: int, round_to=None, row_block: int = 1024):
    """S as a mask [T, T] of a full layer's input x [T, hidden] (normed)
    and its query latent c_q: the index's scores for every pair, a full
    sort of each row (stable, so ties go to the lower position), the
    first `index_topk` of it that the query sees."""
    length, heads = x.shape[0], sizes.index_heads
    q = _mm(c_q, p["w_q"], round_to).reshape(length, heads, -1)
    k = _layer_norm(_mm(x, p["w_k"], round_to), p["k_scale"], p["k_bias"], sizes.rms_norm_eps)
    turn = lambda a: jnp.concatenate(  # noqa: E731
        [_rotate(a[..., :rope], sizes.rope_theta), a[..., rope:]], axis=-1)
    q, k = turn(q), turn(k)
    w = _mm(x, p["w_w"], round_to) * (heads * q.shape[-1]) ** -0.5
    masks = []
    row_block = max(row_block // heads, 1)  # every head's products of a block at once
    for first in range(0, length, row_block):
        last = min(first + row_block, length)
        products = jnp.einsum(
            "thd,sd->ths", _round(q[first:last], round_to), _round(k, round_to))
        if sizes.relu:
            products = jax.nn.relu(products)
        index = jnp.sum(products * w[first:last, :, None], axis=1)
        seen = jnp.arange(length)[None, :] <= jnp.arange(first, last)[:, None]
        order = jnp.argsort(-jnp.where(seen, index, -jnp.inf), axis=-1, stable=True)
        best = order[:, : sizes.index_topk]
        rows = jnp.arange(last - first)[:, None]
        masks.append(jnp.zeros((last - first, length), bool).at[rows, best].set(True) & seen)
    return jnp.concatenate(masks)


def latents(sizes: Sizes, p, x, theta, round_to=None):
    """[c' | rot(k_r)] [T, rank + rope] of a layer's normed input: what a
    cache or a ring of the system should hold at every position."""
    rank, hidden = p["kv_norm"].shape[0], x.shape[-1]
    down = _mm(x, p["w_dkv"], round_to)
    c = _rms_norm(down[:, :rank], p["kv_norm"], sizes.rms_norm_eps)
    if sizes.rescale_kv:
        c = c * (hidden / rank) ** 0.5
    return jnp.concatenate([c, _rotate(down[:, rank:], theta)], axis=-1)


def _attention(sizes: Sizes, p, x, c_q, rows_kv, seen, theta, round_to, head_chunk, row_block):
    """Latent attention over x [T, hidden] (normed), expanded: every key
    and value built from its latent (`rows_kv`, as `latents` gives
    them), each query over the positions `seen` [T, T] marks; every
    head's output under its gate."""
    length, hidden = x.shape
    rank, heads, nope = p["w_uk"].shape
    width, v_width = p["w_uq"].shape[1] // heads, p["w_uv"].shape[2]
    c, r = rows_kv[:, :rank], rows_kv[:, rank:]
    s_q = (hidden / c_q.shape[-1]) ** 0.5 if sizes.rescale_q else 1.0
    gates = jax.nn.sigmoid(_mm(x, p["w_gate"], round_to)) if sizes.gate else None
    out = 0.0
    for h0 in range(0, heads, head_chunk):
        mine = slice(h0, min(h0 + head_chunk, heads))
        q = s_q * _mm(c_q, p["w_uq"][:, mine.start * width:mine.stop * width], round_to)
        q = q.reshape(length, -1, width)
        qh = jnp.concatenate(
            [q[..., :nope], _rotate(q[..., nope:], theta)], axis=-1).transpose(1, 0, 2)
        w_uk, w_uv = _round(p["w_uk"][:, mine], round_to), _round(p["w_uv"][:, mine], round_to)
        k_nope = jnp.einsum("sc,chd->hsd", _round(c, round_to), w_uk)
        v = jnp.einsum("sc,chd->hsd", _round(c, round_to), w_uv)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(r[None], (*k_nope.shape[:2], r.shape[-1]))], axis=-1)
        rows = []
        for first in range(0, length, row_block):
            last = min(first + row_block, length)
            scores = _mm(qh[:, first:last], k.transpose(0, 2, 1), round_to) * width ** -0.5
            probs = jax.nn.softmax(jnp.where(seen[None, first:last], scores, -jnp.inf), axis=-1)
            rows.append(_mm(probs, v, round_to))
        heads_out = jnp.concatenate(rows, axis=1).transpose(1, 0, 2)      # [T, chunk, v]
        if gates is not None:
            heads_out = heads_out * gates[:, mine, None]
        out = out + _mm(
            heads_out.reshape(length, -1),
            p["w_o"][mine.start * v_width:mine.stop * v_width], round_to)
    return out


def route(sizes: Sizes, bias, logits):
    """Router logits [T, experts] in: (ids [T, k], weights [T, k])."""
    scores = jax.nn.sigmoid(logits)
    ids = jnp.argsort(-(scores + _f32(bias)), axis=-1, stable=True)[:, : sizes.num_experts_per_tok]
    weights = jnp.take_along_axis(scores, ids, axis=-1)
    if sizes.norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return ids, weights * sizes.routed_scaling_factor


def _moe(sizes: Sizes, p, x, held, round_to, row_block):
    """(output, chosen ids). The router's product is never rounded."""
    ids, weights = route(sizes, p["bias"], jnp.matmul(x, _f32(p["w_g"])))
    y = jnp.zeros_like(x)
    for row, expert in enumerate(held):
        weight = jnp.sum(jnp.where(ids == expert, weights, 0.0), axis=-1, keepdims=True)
        one = {"w_gate_up": p["experts"]["w_gate_up"][row], "w_down": p["experts"]["w_down"][row]}
        y = y + weight * _mlp(one, x, round_to, row_block)
    return y + _mlp(p["shared"], x, round_to, row_block), ids


def layer(sizes: Sizes, block, h, held, round_to=None, head_chunk=8, row_block=1024):
    """One decoder layer over h [T, hidden] float32. Returns (h out, the
    selection [T, T] of a full layer or None, chosen expert ids or None
    for a dense layer, the latents [T, rank + rope] the layer's cache or
    ring should hold)."""
    with jax.default_matmul_precision("highest"):
        p, eps = block["attn"], sizes.rms_norm_eps
        x = _rms_norm(h, block["attn_norm"], eps)
        c_q = _rms_norm(_mm(x, p["w_dq"], round_to), p["q_norm"], eps)
        if "indexer" in block:
            rope = p["w_dkv"].shape[1] - p["kv_norm"].shape[0]
            chosen = seen = selection(
                sizes, block["indexer"], c_q, x, rope, round_to, row_block)
            theta = sizes.rope_theta
        else:
            chosen, seen, theta = None, band(sizes, h.shape[0]), sizes.swa_rope_theta
        rows = latents(sizes, p, x, theta, round_to)
        h = h + _attention(sizes, p, x, c_q, rows, seen, theta, round_to, head_chunk, row_block)
        x = _rms_norm(h, block["ffn_norm"], eps)
        if "mlp" in block:
            return h + _mlp(block["mlp"], x, round_to, row_block), chosen, None, rows
        out, ids = _moe(sizes, block["moe"], x, held, round_to, row_block)
        return h + out, chosen, ids, rows


def forward(sizes: Sizes, params, ids, held, round_to=None, head_chunk=8, row_block=1024,
            positions=None, queries=None, keep_latents=None):
    """The model over the whole sequence `ids`: logits [len(positions)
    or T, vocab held] (float32), the experts chosen in each sparse layer
    [sparse layers, T, k], the selection of each full layer, a list of
    [len(queries) or T, T] masks, and each sliding layer's latents, a
    list of [len(keep_latents) or T, rank + rope]. `positions` keeps the
    head, `queries` the selections, `keep_latents` the latents, to those
    rows."""
    with jax.default_matmul_precision("highest"):
        h = _f32(params["embed"])[jnp.asarray(ids)]
        experts, selections, rings = [], [], []
        for block in params["layers"]:
            h, chosen, ids_l, rows = layer(
                sizes, block, h, held, round_to, head_chunk, row_block)
            if chosen is not None:
                selections.append(chosen if queries is None else chosen[jnp.asarray(queries)])
            else:
                rings.append(rows if keep_latents is None else rows[jnp.asarray(keep_latents)])
            if ids_l is not None:
                experts.append(ids_l)
        h = _rms_norm(h, params["final_norm"], sizes.rms_norm_eps)
        if positions is not None:
            h = h[jnp.asarray(positions)]
        return _mm(h, params["head"], round_to), jnp.stack(experts), selections, rings
