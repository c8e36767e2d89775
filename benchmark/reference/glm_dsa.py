"""Plain reference of GLM-5.2's forward passes over a whole sequence.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: the whole sequence at once, the
indexer's scores for every pair of positions, the selection by a full
sort with the tie rule, attention in the expanded form (every key and
value built from its latent) under a `[T, T]` mask of the selection, no
cache, no parts, no absorbed form, no grouped product (a loop over the
held experts), no loop of decode steps, and nothing imported from the
code it is compared with (`models/glm_dsa.py`, `models/dsa.py`,
`models/mla.py`, `models/moe.py`, `ops/`). It is written from the layer
equations in that configuration's issue (the published `config.json`
gives every size and both layer patterns; the indexer's form is the
published DSA's), layer by layer, and reads the system's own parameter
tree, upcasting one weight at a time. `[T, T]` arrays are bools whole and
float32 a block of `row_block` query rows (and `head_chunk` heads) at a
time, which changes no number, so that 32,896 positions at the published
widths fit on a chip beside the system's bfloat16 weights.

    h += attn(rms(h));  h += ffn(rms(h))

Attention, x the normed input:

    c_q = rms(W_dq x);  [q_nope | q_rope] = W_uq c_q, q_rope rotated (pairs (2i, 2i + 1))
    [c_kv | k_r] = W_dkv x;  c = rms(c_kv);  r = rot(k_r)
    k_nope_s = W_uk c_s,  v_s = W_uv c_s
    score_ts = (q_nope_t . k_nope_s + q_rope_t . r_s) / sqrt(nope + rope)
    o_t = sum_{s in S_t} softmax_{s in S_t}(score_ts) v_s;  out = W_o o_t

The indexer, in a layer whose tree has one (a `full` layer):

    qI_t = W_qI c_q,t  [heads, d], the first `rope` of each head rotated in pairs
    kI_s = LayerNorm(W_kI x_s)  [d], the first `rope` rotated
    w_t  = W_w x_t heads^-1/2 d^-1/2
    I_ts = sum_j w_tj relu(qI_tj . kI_s)   for s <= t
    S_t  = the `index_topk` positions s <= t with the largest I_ts; ties to the lower s

A layer without one (`shared`) attends by the S_t of the nearest layer
below that has. Feed-forward: a dense SwiGLU where the tree has `mlp`,
else sigmoid scores, the k largest of score + bias, the chosen scores
over their sum times the scaling factor, beside one shared expert.

`forward` gives the main model's logits, the residual stream after the
last layer, the experts chosen and each `full` layer's selection as a
`[T, T]` mask; `mtp_forward` the MTP module's draft logits at every
position i that has a next token, from h_i and x_{i+1}:

    u_i = W_eh [rms_e(E[x_{i+1}]) ; rms_h(h_i)],   one sparse layer with its own indexer,
    draft logits for x_{i+2} = Head(rms_mtp(layer(u)_i))

`held` lists the routed experts the tree's expert stacks hold, row j of a
stack being expert `held[j]`; what the others would have added is left
out, as in the system. `round_to` rounds both operands of every matrix
product to that dtype before multiplying in float32, for the one purpose
of setting the comparison's limit. `Sizes` has four wrong mechanisms for
the same purpose: `relu` false (the indexer's products summed as they
are), `index_halves` (the indexer's rotation over halves, not pairs),
`share_above` (a `shared` layer attends by the selection of the `full`
layer above it, as a first, right pass computed it) and `blind_part` (a
query sees no position before the first of its own part of that many
positions).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the parameter tree does not say about the architecture."""

    rope_theta: float = 8e6
    index_heads: int = 32
    index_topk: int = 2048
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-5
    relu: bool = True
    index_halves: bool = False
    share_above: bool = False
    blind_part: int = 0

    @classmethod
    def of(cls, cfg, **wrong) -> "Sizes":
        """From any object that bears the published `config.json`'s names."""
        return cls(
            rope_theta=cfg.rope_theta, index_heads=cfg.index_n_heads,
            index_topk=cfg.index_topk, num_experts_per_tok=cfg.num_experts_per_tok,
            norm_topk_prob=cfg.norm_topk_prob,
            routed_scaling_factor=cfg.routed_scaling_factor, rms_norm_eps=cfg.rms_norm_eps,
            **wrong,
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


def _rotate(x, theta, halves: bool = False):
    """x [T, ..., d] by its row's position: channels 2i and 2i + 1 are a
    pair's members (or, `halves`, channels i and i + d / 2)."""
    length, d = x.shape[0], x.shape[-1]
    inverse = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(length, dtype=jnp.float32)[:, None] * inverse[None, :]
    angles = angles.reshape(length, *(1,) * (x.ndim - 2), d // 2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if halves:
        x1, x2 = x[..., : d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def _visible(sizes: Sizes, length: int, first: int, last: int):
    """[last - first, T]: which positions the queries `first` .. `last` - 1 see."""
    i, j = jnp.arange(first, last)[:, None], jnp.arange(length)[None, :]
    seen = j <= i
    if sizes.blind_part:
        seen = seen & (j >= i - i % sizes.blind_part)
    return seen


def selection(sizes: Sizes, p, c_q, x, rope: int, round_to=None, row_block: int = 1024):
    """S as a mask [T, T] of the layer's input x [T, hidden] (normed) and
    its query latent c_q: the indexer's scores for every pair, a full
    sort of each row (stable, so ties go to the lower position), the
    first `index_topk` of it that the query sees."""
    length, heads = x.shape[0], sizes.index_heads
    q = _mm(c_q, p["w_q"], round_to).reshape(length, heads, -1)
    k = _layer_norm(_mm(x, p["w_k"], round_to), p["k_scale"], p["k_bias"], sizes.rms_norm_eps)
    turn = lambda a: jnp.concatenate(  # noqa: E731
        [_rotate(a[..., :rope], sizes.rope_theta, sizes.index_halves), a[..., rope:]], axis=-1)
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
        seen = _visible(sizes, length, first, last)
        order = jnp.argsort(-jnp.where(seen, index, -jnp.inf), axis=-1, stable=True)
        best = order[:, : sizes.index_topk]
        rows = jnp.arange(last - first)[:, None]
        masks.append(jnp.zeros((last - first, length), bool).at[rows, best].set(True) & seen)
    return jnp.concatenate(masks)


def _attention(sizes: Sizes, p, x, c_q, chosen, round_to, head_chunk, row_block):
    """Latent attention over x [T, hidden] (normed), expanded: every key
    and value built from its latent, each query over the positions
    `chosen` [T, T] marks."""
    length = x.shape[0]
    rank, heads, nope = p["w_uk"].shape
    width, v_width = p["w_uq"].shape[1] // heads, p["w_uv"].shape[2]
    down = _mm(x, p["w_dkv"], round_to)
    c = _rms_norm(down[:, :rank], p["kv_norm"], sizes.rms_norm_eps)
    r = _rotate(down[:, rank:], sizes.rope_theta)
    out = 0.0
    for h0 in range(0, heads, head_chunk):
        mine = slice(h0, min(h0 + head_chunk, heads))
        q = _mm(c_q, p["w_uq"][:, mine.start * width:mine.stop * width], round_to)
        q = q.reshape(length, -1, width)
        qh = jnp.concatenate(
            [q[..., :nope], _rotate(q[..., nope:], sizes.rope_theta)], axis=-1).transpose(1, 0, 2)
        k_nope = jnp.einsum("sc,chd->hsd", _round(c, round_to), _round(p["w_uk"][:, mine], round_to))
        v = jnp.einsum("sc,chd->hsd", _round(c, round_to), _round(p["w_uv"][:, mine], round_to))
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(r[None], (*k_nope.shape[:2], r.shape[-1]))], axis=-1)
        rows = []
        for first in range(0, length, row_block):
            last = min(first + row_block, length)
            scores = _mm(qh[:, first:last], k.transpose(0, 2, 1), round_to) * width ** -0.5
            probs = jax.nn.softmax(jnp.where(chosen[None, first:last], scores, -jnp.inf), axis=-1)
            rows.append(_mm(probs, v, round_to))
        heads_out = jnp.concatenate(rows, axis=1).transpose(1, 0, 2).reshape(length, -1)
        out = out + _mm(heads_out, p["w_o"][mine.start * v_width:mine.stop * v_width], round_to)
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


def layer(sizes: Sizes, block, h, chosen, held, round_to=None, head_chunk=8, row_block=1024):
    """One decoder layer over h [T, hidden] float32; `chosen` is the
    selection handed to it, which a layer with an indexer replaces by
    its own. Returns
    (h out, the selection it attended by, chosen expert ids or None for
    a dense layer)."""
    with jax.default_matmul_precision("highest"):
        p, eps = block["attn"], sizes.rms_norm_eps
        x = _rms_norm(h, block["attn_norm"], eps)
        c_q = _rms_norm(_mm(x, p["w_dq"], round_to), p["q_norm"], eps)
        if "indexer" in block:
            rope = p["w_dkv"].shape[1] - p["kv_norm"].shape[0]
            chosen = selection(sizes, block["indexer"], c_q, x, rope, round_to, row_block)
        h = h + _attention(sizes, p, x, c_q, chosen, round_to, head_chunk, row_block)
        x = _rms_norm(h, block["ffn_norm"], eps)
        if "mlp" in block:
            return h + _mlp(block["mlp"], x, round_to, row_block), chosen, None
        out, ids = _moe(sizes, block["moe"], x, held, round_to, row_block)
        return h + out, chosen, ids


def _logits(sizes, params, h, norm, positions, round_to):
    h = _rms_norm(h, norm, sizes.rms_norm_eps)
    if positions is not None:
        h = h[jnp.asarray(positions)]
    return _mm(h, params["head"], round_to)


def forward(sizes: Sizes, params, ids, held, round_to=None, head_chunk=8, row_block=1024,
            positions=None, queries=None):
    """The main model over the whole sequence `ids`: logits
    [len(positions) or T, vocab held] (float32), the residual stream
    after the last layer [T, hidden], the experts chosen in each sparse
    layer [sparse layers, T, k], and the selection of each layer that
    has an indexer, a list of [len(queries) or T, T] masks. `positions`
    keeps the head, `queries` the selections, to those rows."""
    with jax.default_matmul_precision("highest"):
        def walk(above=None):
            h = _f32(params["embed"])[jnp.asarray(ids)]
            experts, selections, chosen = [], [], None
            for index, block in enumerate(params["layers"]):
                full = "indexer" in block
                if above is not None and not full:
                    # the wrong mechanism: the next selection above, as the right pass made it
                    later = sum("indexer" in b for b in params["layers"][:index])
                    chosen = above[min(later, len(above) - 1)]
                h, chosen, ids_l = layer(
                    sizes, block, h, chosen, held, round_to, head_chunk, row_block)
                if full:
                    selections.append(chosen)
                if ids_l is not None:
                    experts.append(ids_l)
            return h, experts, selections

        h, experts, selections = walk()
        if sizes.share_above:
            h, experts, selections = walk(selections)
        if queries is not None:
            selections = [chosen[jnp.asarray(queries)] for chosen in selections]
        logits = _logits(sizes, params, h, params["final_norm"], positions, round_to)
        return logits, h, jnp.stack(experts), selections


def mtp_forward(sizes: Sizes, params, h, ids, held, round_to=None, head_chunk=8,
                row_block=1024, positions=None):
    """The MTP module over the whole sequence: from the main model's
    residual streams h [T, hidden] (`forward`'s) and the ids [T], the
    draft logits at positions 0 .. T - 2 (row i, from h_i and x_{i+1},
    is the distribution of x_{i+2}), or at `positions` of them, the
    experts chosen [T - 1, k] and the module's own selection [T - 1,
    T - 1], or its rows at `positions`."""
    with jax.default_matmul_precision("highest"):
        p = params["mtp"]
        ids = jnp.asarray(ids)
        both = jnp.concatenate([
            _rms_norm(_f32(params["embed"])[ids[1:]], p["embed_norm"], sizes.rms_norm_eps),
            _rms_norm(h[:-1], p["hidden_norm"], sizes.rms_norm_eps),
        ], axis=-1)
        out, chosen, experts = layer(
            sizes, p["layer"], _mm(both, p["w_eh"], round_to), None, held, round_to,
            head_chunk, row_block)
        if positions is not None:
            chosen = chosen[jnp.asarray(positions)]
        return _logits(sizes, params, out, p["norm"], positions, round_to), experts, chosen


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
