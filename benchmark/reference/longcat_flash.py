"""Plain reference of LongCat-Flash-Chat over a whole sequence.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: the whole sequence at once,
every key and value expanded from its latent, one `[T, T]` mask, no
cache, no parts, no blocks of the expert branch, no absorbed form, no
kernel, no grouped product (a loop over the held experts), no loop of
decode steps, and nothing imported from the code it is compared with
(`models/longcat_flash.py`, `models/mla.py`, `models/moe.py`, `ops/`).
It is written from the layer equations of the configuration's issue
(ISSUE 63, Tentpole section 1), which are the installed modelling file's
(`transformers` 4.57.6, models/longcat_flash/modeling_longcat_flash.py;
lines cited below), and reads the system's own parameter tree, upcasting
one weight at a time. `[T, T]` arrays are bools whole and float32 a block
of `row_block` query rows (and `head_chunk` heads) at a time, which
changes no number, so that 33,024 positions at the published widths fit
beside the weights.

A layer (lines 448-480): two sublayers and the expert layer on a shortcut,

    h = x + A_0(n_0(x));  u = n_0'(h);  m = M(u);  h = h + F_0(u)
    h = h + A_1(n_1(h));  y = h + F_1(n_1'(h)) + m;   logits = W_head rms(y_last layer)

Latent attention A_i (lines 340-368), x the normed input:

    c_q = rms(W_dq x; 1e-6);  [q_nope | q_rope] = s_q W_uq c_q,  s_q = (hidden / r_q)^1/2
    [c | k_r] = W_dkv x;  c' = s_kv rms(c; 1e-6),  s_kv = (hidden / r)^1/2
    q_rope, k_r rotated in pairs (2i, 2i + 1) at theta; k_r not scaled
    k_nope_j = W_uk c'_j,  v_j = W_uv c'_j
    score_ij = (q_nope_i . k_nope_j + q_rope_i . rot(k_r)_j) / sqrt(nope + rope)
    o_i = sum_{j seen} softmax_{j seen}(score_ij) v_j;  out = W_o [o_h]_h

The expert layer M (lines 118-176): s = softmax over the router's whole
width of float32(u) W_g; the k largest of s + bias, ties to the lower
index; weights `routed_scaling_factor` s at the chosen ids, not
renormalised; M(u) = sum_{j chosen, j < experts} w_j SwiGLU_j(u) +
(sum_{j chosen, j >= experts} w_j) u.

Departures from the installed modelling file: its attention and router
run in the checkpoint's dtype and this in float32; its rotation reorders
a head's channels to halves and rotates those (`apply_rotary_pos_emb_
interleave`), which is the rotation of pairs (2i, 2i + 1) written here
followed by a fixed permutation of the channels of q and k alike, so no
score differs; its `topk` is unsorted and ties are its backend's, here
to the lower index; its experts' sum is cast to the hidden dtype before
it is added, here everything is float32; weights are seeded.

`held` lists the routed experts the tree's expert stacks hold, row j of a
stack being expert `held[j]`; what the others would have added is left
out, as in the system; an identity is no chip's and always added.
`round_to` rounds both operands of every matrix product to that dtype
before multiplying in float32, for the one purpose of setting the
comparison's limit. `Sizes` has wrong mechanisms for the same purpose:
`rescale_q` / `rescale_kv` false, `renormalise` true, another
`routed_scaling_factor`, `identities` false (their pairs dropped),
`branch_from_x` (the branch reads the layer's first normed input n_0(x)
instead of u), `branch_after_first` (the branch is added after F_0, so
the second sublayer sees it), `rotate_halves` (channels i and i + d/2
pair up). `positions` and `seen` let a caller lay two continuations of
one prompt in one sequence: the rotary position of every row, and which
rows each row sees (default: 0 .. T - 1 and j <= i).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

MLA_NORM_EPS = 1e-6  # `LongcatFlashRMSNorm`'s default: the two norms inside an attention


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the parameter tree does not say about the architecture."""

    rope_theta: float = 1e7
    n_routed_experts: int = 512
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    rms_norm_eps: float = 1e-5
    rescale_q: bool = True
    rescale_kv: bool = True
    renormalise: bool = False
    identities: bool = True
    branch_from_x: bool = False
    branch_after_first: bool = False
    rotate_halves: bool = False

    @classmethod
    def of(cls, cfg, **wrong) -> "Sizes":
        """From any object that bears the published `config.json`'s names."""
        given = dict(
            rope_theta=cfg.rope_theta, n_routed_experts=cfg.n_routed_experts,
            moe_topk=cfg.moe_topk, routed_scaling_factor=cfg.routed_scaling_factor,
            rms_norm_eps=cfg.rms_norm_eps, rescale_q=cfg.mla_scale_q_lora,
            rescale_kv=cfg.mla_scale_kv_lora)
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


def _mlp(p, x, round_to, row_block=1024):
    """down(silu(gate x) * up x), a block of rows at a time."""
    width = p["w_gate_up"].shape[-1] // 2
    w_gate, w_up = _f32(p["w_gate_up"][..., :width]), _f32(p["w_gate_up"][..., width:])
    w_down = _f32(p["w_down"])
    return jnp.concatenate([
        _mm(jax.nn.silu(_mm(rows, w_gate, round_to)) * _mm(rows, w_up, round_to), w_down, round_to)
        for rows in (x[i:i + row_block] for i in range(0, x.shape[0], row_block))])


def _rotate(sizes: Sizes, x, positions):
    """x [T, ..., d] by its row's position: channels 2i and 2i + 1 are a
    pair's members (under `rotate_halves`, wrongly, i and i + d / 2)."""
    length, d = x.shape[0], x.shape[-1]
    inverse = sizes.rope_theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = _f32(positions)[:, None] * inverse[None, :]
    angles = angles.reshape(length, *(1,) * (x.ndim - 2), d // 2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if sizes.rotate_halves:
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def latents(sizes: Sizes, p, x, positions, round_to=None):
    """[c' | rot(k_r)] [T, rank + rope] of an attention's normed input:
    what its cache in the system should hold at every position."""
    rank, hidden = p["kv_norm"].shape[0], x.shape[-1]
    down = _mm(x, p["w_dkv"], round_to)
    c = _rms_norm(down[:, :rank], p["kv_norm"], MLA_NORM_EPS)
    if sizes.rescale_kv:
        c = c * (hidden / rank) ** 0.5
    return jnp.concatenate([c, _rotate(sizes, down[:, rank:], positions)], axis=-1)


def attention(sizes: Sizes, p, x, positions, seen, round_to=None, head_chunk=8, row_block=1024):
    """Latent attention over x [T, hidden] (normed), expanded: every key
    and value built from its latent, each query over the rows `seen` [T,
    T] marks. Returns (output [T, hidden], the latents)."""
    length, hidden = x.shape
    rank, heads, nope = p["w_uk"].shape
    width, v_width = p["w_uq"].shape[1] // heads, p["w_uv"].shape[2]
    c_q = _rms_norm(_mm(x, p["w_dq"], round_to), p["q_norm"], MLA_NORM_EPS)
    rows_kv = latents(sizes, p, x, positions, round_to)
    c, r = rows_kv[:, :rank], rows_kv[:, rank:]
    s_q = (hidden / c_q.shape[-1]) ** 0.5 if sizes.rescale_q else 1.0
    out = 0.0
    for h0 in range(0, heads, head_chunk):
        mine = slice(h0, min(h0 + head_chunk, heads))
        q = s_q * _mm(c_q, p["w_uq"][:, mine.start * width:mine.stop * width], round_to)
        q = q.reshape(length, -1, width)
        qh = jnp.concatenate(
            [q[..., :nope], _rotate(sizes, q[..., nope:], positions)], axis=-1).transpose(1, 0, 2)
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
        out = out + _mm(
            heads_out.reshape(length, -1),
            p["w_o"][mine.start * v_width:mine.stop * v_width], round_to)
    return out, rows_kv


def route(sizes: Sizes, bias, logits):
    """Router logits [T, width] in: (ids [T, k], weights [T, k])."""
    scores = jax.nn.softmax(logits, axis=-1)
    ids = jnp.argsort(-(scores + _f32(bias)), axis=-1, stable=True)[:, : sizes.moe_topk]
    weights = jnp.take_along_axis(scores, ids, axis=-1)
    if sizes.renormalise:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return ids, weights * sizes.routed_scaling_factor


def expert_branch(sizes: Sizes, p, u, held, round_to=None, row_block=1024):
    """M(u) in its two parts: (the held experts' weighted sum, the
    identities' weights' sum times u, the chosen ids). The router's
    product is never rounded."""
    ids, weights = route(sizes, p["bias"], jnp.matmul(u, _f32(p["w_g"])))
    routed = jnp.zeros_like(u)
    for row, expert in enumerate(held):
        weight = jnp.sum(jnp.where(ids == expert, weights, 0.0), axis=-1, keepdims=True)
        one = {"w_gate_up": p["experts"]["w_gate_up"][row], "w_down": p["experts"]["w_down"][row]}
        routed = routed + weight * _mlp(one, u, round_to, row_block)
    kept = jnp.sum(
        jnp.where(ids >= sizes.n_routed_experts, weights, 0.0), axis=-1, keepdims=True)
    return routed, (kept * u if sizes.identities else jnp.zeros_like(u)), ids


def layer(sizes: Sizes, block, h, held, positions, seen, round_to=None, head_chunk=8,
          row_block=1024):
    """One layer over h [T, hidden] float32. Returns (h out, the branch m
    that went into it, the chosen ids [T, k], the two attentions'
    latents [T, rank + rope] each)."""
    with jax.default_matmul_precision("highest"):
        first, second = block["sub"]
        eps, kept = sizes.rms_norm_eps, []
        x = _rms_norm(h, first["attn_norm"], eps)
        out, rows = attention(
            sizes, first["attn"], x, positions, seen, round_to, head_chunk, row_block)
        kept.append(rows)
        h = h + out
        u = _rms_norm(h, first["ffn_norm"], eps)
        routed, identity, ids = expert_branch(
            sizes, block["moe"], x if sizes.branch_from_x else u, held, round_to, row_block)
        m = routed + identity
        h = h + _mlp(first["mlp"], u, round_to, row_block)
        if sizes.branch_after_first:
            h = h + m
        out, rows = attention(
            sizes, second["attn"], _rms_norm(h, second["attn_norm"], eps), positions, seen,
            round_to, head_chunk, row_block)
        kept.append(rows)
        h = h + out
        h = h + _mlp(second["mlp"], _rms_norm(h, second["ffn_norm"], eps), round_to, row_block)
        if not sizes.branch_after_first:
            h = h + m
        return h, m, ids, kept


def forward(sizes: Sizes, params, ids, held, round_to=None, head_chunk=8, row_block=1024,
            keep=None, positions=None, seen=None):
    """The model over the whole sequence `ids`: logits [len(keep) or T,
    vocab held] (float32), the ids chosen in each layer [layers, T, k],
    and every attention's latents, a list of [len(keep) or T, rank +
    rope] in the order of the system's caches. `keep` keeps the head and
    the latents to those rows."""
    with jax.default_matmul_precision("highest"):
        length = len(ids)
        positions = jnp.arange(length) if positions is None else jnp.asarray(positions)
        if seen is None:
            seen = jnp.arange(length)[None, :] <= jnp.arange(length)[:, None]
        h = _f32(params["embed"])[jnp.asarray(ids)]
        chosen, caches = [], []
        for block in params["layers"]:
            h, _, ids_l, rows = layer(
                sizes, block, h, held, positions, seen, round_to, head_chunk, row_block)
            chosen.append(ids_l)
            caches += rows if keep is None else [r[jnp.asarray(keep)] for r in rows]
        h = _rms_norm(h, params["final_norm"], sizes.rms_norm_eps)
        if keep is not None:
            h = h[jnp.asarray(keep)]
        return _mm(h, params["head"], round_to), jnp.stack(chosen), caches
