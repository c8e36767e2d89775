"""Plain reference of Ling-3.0-flash's forward passes over a whole sequence.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no cache, no slots, no chunked
form of the linear attention (the delta rule is the recurrence itself, a
`lax.scan` over tokens), latent attention expanded under a full `[T, T]`
mask, no grouped product (a loop over the held experts), no loop of
decode steps, and nothing imported from the code it is compared with
(`models/ling_flash.py`, `models/kda.py`, `models/mla.py`,
`models/moe.py`, `ops/`). It is written from the layer equations in that
configuration's issue (the published `config.json` gives every size, the
layer pattern and the names of the mechanisms; the modelling code is not
in the sandbox), layer by layer, and reads the system's own parameter
tree, upcasting one weight at a time, so that at published widths it
fits on a chip beside the system's bfloat16 weights.

    h += mixer(rms(h));  h += ffn(rms(h))

The tree's layers are the published ones from `first_layer` on. Published
layer l is latent attention where (l + 1) mod `group` = 0, else KDA; its
feed-forward part is a dense SwiGLU where l < `first_k_dense_replace`,
else the mixture.

KDA, a head, L = `kda_lower_bound`:

    [q~, k~, v~]_t = silu(conv(W_qkv x))_t,  q_t = l2norm(q~_t) d^-1/2,  k_t = l2norm(k~_t)
    g_t = L sigmoid(exp(A_log) (W_f x_t + dt_bias)),  beta_t = sigmoid(W_beta x_t)
    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T,  o_t = S_t^T q_t
    y_t = W_o [rms_head(o_t) sigmoid(W_g x_t)]

Latent attention: q = W_q x, a head's [nope | rope], the rope part
rotated (`rotate_half`, theta `rope_theta`); [c | r] = W_dkv x, c normed,
r rotated, one r for every head; k_nope = W_uk c, v = W_uv c; scores
(q_nope . k_nope + q_rope . r) / sqrt(nope + rope) under a causal mask;
each head's output times sigmoid(w_a,h . x); W_o.

The mixture: scores sigmoid(W_r x); c = s + bias; the experts in
`n_group` runs of equal length, a group's score the sum of its two
largest c, the `topk_group` best groups stay (ties to the lower index);
the k largest c among their experts are chosen (ties to the lower
index); weights s_chosen / sum s_chosen x the scaling factor; beside one
shared expert. Under a layer's limit lambda > 0 a SwiGLU is
silu(min(gate, lambda)) clip(up, -lambda, lambda), the routed experts'
and the shared expert's each by its own list.

`forward` gives the main model's logits and the residual stream h after
the last layer at every position; `mtp_forward` the MTP module's draft
logits at every position i that has a next token, from h_i and x_{i+1}:

    u_i = W_eh [rms_e(E[x_{i+1}]) ; rms_h(h_i)],  one latent-attention layer with the
    mixture under the last main layer's limits,  draft logits = Head(rms_mtp(layer(u)_i))

Speculation is the system's: the reference says what each distribution
must be, and `speculative_rule` what the emitted token's distribution is
under the rule.

`held` lists the routed experts the tree's expert stacks hold, row j of a
stack being expert `held[j]`: all of them, or one chip's share; what the
others would have added is left out, as in the system. The embedding and
the head may be a slice of the vocabulary.

Attention is computed `head_chunk` query heads at a time, which changes
no number. `round_to` rounds both operands of every matrix product to
that dtype before multiplying in float32 (the delta rule's products
too), for the one purpose of setting the comparison's limit between the
system's reading and this reference's one precision below the
configuration's. `Sizes.bounded_decay` false (g = -exp(A_log)
softplus(.), the family's unbounded gate) and `Sizes.grouped` false (the
k largest c of all experts) are wrong mechanisms for the same purpose.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the parameter tree does not say about the architecture."""

    heads: int = 32
    first_layer: int = 0
    group: int = 6
    first_k_dense_replace: int = 2
    kv_lora_rank: int = 512
    nope: int = 128
    rope_theta: float = 6e6
    kda_lower_bound: float = -5.0
    num_experts: int = 512
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    expert_limits: tuple = (0,) * 35 + (4,) * 7
    shared_limits: tuple = (0,) * 34 + (5,) * 6 + (7,) * 2
    rms_norm_eps: float = 1e-6
    bounded_decay: bool = True
    grouped: bool = True

    @classmethod
    def of(cls, cfg) -> "Sizes":
        """From any object that bears the published `config.json`'s names
        (and `first_layer`, the published index of the tree's first)."""
        return cls(
            heads=cfg.num_attention_heads, first_layer=cfg.first_layer,
            group=cfg.layer_group_size, first_k_dense_replace=cfg.first_k_dense_replace,
            kv_lora_rank=cfg.kv_lora_rank, nope=cfg.qk_nope_head_dim,
            rope_theta=cfg.rope_theta, kda_lower_bound=cfg.kda_lower_bound,
            num_experts=cfg.num_experts, num_experts_per_tok=cfg.num_experts_per_tok,
            n_group=cfg.n_group, topk_group=cfg.topk_group, norm_topk_prob=cfg.norm_topk_prob,
            routed_scaling_factor=cfg.routed_scaling_factor,
            expert_limits=tuple(cfg.expert_swiglu_limit_list),
            shared_limits=tuple(cfg.share_expert_swiglu_limit_list),
            rms_norm_eps=cfg.rms_norm_eps,
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


def _mlp(p, x, round_to, limit=0.0):
    """down(silu(gate x) * up x), gate and up clamped under a limit."""
    width = p["w_gate_up"].shape[-1] // 2
    gate = _mm(x, p["w_gate_up"][..., :width], round_to)
    up = _mm(x, p["w_gate_up"][..., width:], round_to)
    if limit > 0:
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return _mm(jax.nn.silu(gate) * up, p["w_down"], round_to)


def _rotate(x, theta):
    """x [T, (heads,) d] by its row's position: the two halves of the
    last axis are a pair's members."""
    length, d = x.shape[0], x.shape[-1]
    inverse = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(length, dtype=jnp.float32)[:, None] * inverse[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if x.ndim == 3:
        cos, sin = cos[:, None, :], sin[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _latent_attention(sizes: Sizes, p, x, round_to, head_chunk):
    """Gated multi-head latent attention over x [T, hidden], keys and
    values built for every position."""
    length, heads, rank, nope = x.shape[0], sizes.heads, sizes.kv_lora_rank, sizes.nope
    q = _mm(x, p["w_q"], round_to).reshape(length, heads, -1)
    q_rope = _rotate(q[..., nope:], sizes.rope_theta)
    down = _mm(x, p["w_dkv"], round_to)
    c = _rms_norm(down[:, :rank], p["kv_norm"], sizes.rms_norm_eps)
    r = _rotate(down[:, rank:], sizes.rope_theta)                     # [T, rope]
    scale = q.shape[-1] ** -0.5
    causal = jnp.tril(jnp.ones((length, length), bool))
    outs = []
    for first in range(0, heads, head_chunk):
        mine = slice(first, min(first + head_chunk, heads))
        w_uk, w_uv = _f32(p["w_uk"])[:, mine], _f32(p["w_uv"])[:, mine]  # [rank, chunk, d]
        k_nope = _mm(c, w_uk.reshape(rank, -1), round_to).reshape(length, -1, nope)
        v = _mm(c, w_uv.reshape(rank, -1), round_to).reshape(length, k_nope.shape[1], -1)
        scores = _mm(q[:, mine, :nope].transpose(1, 0, 2), k_nope.transpose(1, 2, 0), round_to)
        scores = scores + _mm(q_rope[:, mine].transpose(1, 0, 2), r.T, round_to)
        probs = jax.nn.softmax(jnp.where(causal[None], scores * scale, -jnp.inf), axis=-1)
        outs.append(_mm(probs, v.transpose(1, 0, 2), round_to).transpose(1, 0, 2))
    out = jnp.concatenate(outs, axis=1)                               # [T, heads, v]
    gate = jax.nn.sigmoid(_mm(x, p["w_a"], round_to))                 # [T, heads]
    return _mm((out * gate[:, :, None]).reshape(length, -1), p["w_o"], round_to)


def _l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _kda(sizes: Sizes, p, x, round_to):
    """A KDA mixer over x [T, hidden], token by token."""
    length, heads = x.shape[0], sizes.heads
    d = p["o_norm"].shape[0]
    filters = _f32(p["conv"])                                         # [kernel, 3 H d]
    kernel = filters.shape[0]
    padded = jnp.concatenate(
        [jnp.zeros((kernel - 1, filters.shape[1])), _mm(x, p["w_qkv"], round_to)])
    mixed = sum(padded[i:i + length] * filters[i] for i in range(kernel))
    q, k, v = jnp.split(jax.nn.silu(mixed).reshape(length, 3 * heads, d), 3, axis=1)
    q, k = _l2norm(q) * d ** -0.5, _l2norm(k)
    rate = (_mm(x, p["w_f"], round_to) + _f32(p["dt_bias"])).reshape(length, heads, d)
    speed = jnp.exp(_f32(p["a_log"]))[None, :, None]
    if sizes.bounded_decay:
        g = sizes.kda_lower_bound * jax.nn.sigmoid(speed * rate)
    else:  # a wrong mechanism: the family's gate without the bound
        g = -speed * jax.nn.softplus(rate)
    beta = jax.nn.sigmoid(_mm(x, p["w_beta"], round_to))              # [T, H]

    def token(state, xs):
        q, k, v, g, beta = xs                                         # [H, d], beta [H]
        state = jnp.exp(g)[:, :, None] * state
        seen = jnp.einsum("hk,hkv->hv", _round(k, round_to), _round(state, round_to))
        state = state + beta[:, None, None] * k[:, :, None] * (v - seen)[:, None, :]
        return state, jnp.einsum("hk,hkv->hv", _round(q, round_to), _round(state, round_to))

    _, o = jax.lax.scan(token, jnp.zeros((heads, d, d), jnp.float32), (q, k, v, g, beta))
    gate = jax.nn.sigmoid(_mm(x, p["w_g"], round_to))
    normed = _rms_norm(o, p["o_norm"], sizes.rms_norm_eps).reshape(length, -1)
    return _mm(normed * gate, p["w_o"], round_to)


def route(sizes: Sizes, bias, logits):
    """Router logits [T, experts] in: (ids [T, k], weights [T, k])."""
    scores = jax.nn.sigmoid(logits)
    biased = scores + _f32(bias)
    if sizes.grouped and sizes.n_group > 1:
        tokens, experts = biased.shape
        groups = biased.reshape(tokens, sizes.n_group, -1)
        of_group = jnp.sum(jnp.sort(groups, axis=-1)[..., -2:], axis=-1)       # its two largest
        best = jnp.argsort(-of_group, axis=-1, stable=True)[:, : sizes.topk_group]
        stays = jnp.any(best[:, :, None] == jnp.arange(sizes.n_group)[None, None, :], axis=1)
        biased = jnp.where(jnp.repeat(stays, experts // sizes.n_group, axis=1), biased, -jnp.inf)
    ids = jnp.argsort(-biased, axis=-1, stable=True)[:, : sizes.num_experts_per_tok]
    weights = jnp.take_along_axis(scores, ids, axis=-1)
    if sizes.norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return ids, weights * sizes.routed_scaling_factor


def _moe(sizes: Sizes, p, x, held, round_to, limits):
    """(output, chosen ids). The router's product is never rounded."""
    ids, weights = route(sizes, p["bias"], jnp.matmul(x, _f32(p["w_g"])))
    y = jnp.zeros_like(x)
    for row, expert in enumerate(held):
        weight = jnp.sum(jnp.where(ids == expert, weights, 0.0), axis=-1, keepdims=True)
        one = {"w_gate_up": p["experts"]["w_gate_up"][row], "w_down": p["experts"]["w_down"][row]}
        y = y + weight * _mlp(one, x, round_to, limits[0])
    return y + _mlp(p["shared"], x, round_to, limits[1]), ids


def layer(sizes: Sizes, index: int, block, h, held, round_to=None, head_chunk=8):
    """One decoder layer over h [T, hidden] float32, `index` its
    published index (-1: the MTP module's, whose limits are the last
    main layer's): (h out, chosen ids or None for a dense layer)."""
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(h, block["mixer_norm"], sizes.rms_norm_eps)
        if "mla" in block:
            h = h + _latent_attention(sizes, block["mla"], x, round_to, head_chunk)
        else:
            h = h + _kda(sizes, block["kda"], x, round_to)
        x = _rms_norm(h, block["ffn_norm"], sizes.rms_norm_eps)
        if "mlp" in block:
            return h + _mlp(block["mlp"], x, round_to), None
        limits = (sizes.expert_limits[index], sizes.shared_limits[index])
        out, ids = _moe(sizes, block["moe"], x, held, round_to, limits)
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
        for offset, block in enumerate(params["layers"]):
            index = sizes.first_layer + offset
            mixer = "mla" if (index + 1) % sizes.group == 0 else "kda"
            if mixer not in block or ("mlp" in block) != (index < sizes.first_k_dense_replace):
                raise ValueError(f"published layer {index}: the tree holds another kind")
            h, ids_l = layer(sizes, index, block, h, held, round_to, head_chunk)
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
            sizes, -1, p["layer"], _mm(both, p["w_eh"], round_to), held, round_to, head_chunk)
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
