"""Ling-3.0-flash (`bailing_hybrid`): layers in groups of
`layer_group_size`, the last of a group multi-head latent attention (MLA)
with a gate a head, the others KDA, linear attention by a gated delta
rule under a bounded decay; the first `first_k_dense_replace` layers'
feed-forward part dense, the others routed experts chosen groups first
beside one shared expert, the SwiGLU of the last layers clamped; and one
multi-token-prediction (MTP) module that drafts for a self-speculative
decode.

    h += mixer(rms(h));  h += ffn(rms(h))           (pre-norm, assumed)

KDA, a head (H heads of d_k = d_v = d), x the normed input, L =
`kda_lower_bound`:

    [q~, k~, v~]_t = silu(conv4(W_qkv x))_t        (`kda.conv_qkv`)
    q_t = l2norm(q~_t) d^-1/2    k_t = l2norm(k~_t)    v_t = v~_t
    g_t = L sigmoid(exp(A_log) (W_f x_t + dt_bias))   in (L, 0)^d,  alpha_t = exp(g_t)
    beta_t = sigmoid(W_beta x_t)                   in (0, 1)
    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T,   o_t = S_t^T q_t
    y_t = W_o [rms_head(o_t) * sigmoid(W_g x_t)]

the rule itself in `models/kda.py`'s two forms (shared with
`solar_open2.py`); the gates, at full rank and bounded, are this model's.

MLA: q_t = W_q x_t, a head's [nope | rope] with the rope part rotated
(theta `rope_theta`, no scaling); the cache holds [rms(c_t) | r_t] of
W_dkv x_t, r_t rotated (`mla.latents`); scores over nope + rope at
(nope + rope)^-1/2, expanded in the prefill and absorbed in the decode
(`models/mla.py`, shared with `deepseek_v2.py`);
y_t = W_o [sigmoid(w_a,h . x_t) o_t,h]_h: one gate a head.

The MTP module, for position i with h_i the residual stream after the
last main layer and x_{i+1} the next token (DeepSeek-V3's form, as
`k_exaone.py` has it): u_i = W_eh [rms_e(E[x_{i+1}]) ; rms_h(h_i)], one
layer (MLA, the sparse feed-forward part under the last main layer's
limits, a latent cache of its own), a norm of its own, the main model's
embedding and head.

A request's state is a tree (`state_shapes`): `latents` [MLA layers + 1,
positions, rank + rope], which grows with the position, the MTP module's
the last slot; `state`, an array [2, H, d, d] float32 a KDA layer, and
`conv` [KDA layers, 2, kernel - 1, 3 H d], each KDA layer's matrix states
and convolution tail in **two slots**, of which `slot` says which stands;
and `h`, the residual stream of the last position the MTP module has not
seen. The prefill allocates it, the decode takes it by donation and hands
it back.

The decode is one program either way. `draft_tokens` 0: `steps`
one-token steps, each KDA layer's state written over where it was read.
`draft_tokens` 1: `lm_common.draft_loop`, whose step drafts one token with the MTP
module, runs the last emitted token and the draft through the main model
as two positions, keeps the draft with probability min(1, p / q) (else
draws from the renormalised max(p - q, 0)) and so emits one or two
tokens, until `steps` ids are written. A dropped draft has been
multiplied into a matrix state for good, so a KDA layer writes the state
and tail after the first position, S_1, into the slot that does not
stand, and those after the second, S_2, over what it read; whether the
draft is kept is known only after the last layer and the head, and then
`slot` stays (S_2 stands) or flips (S_1 stands): no state is copied. The
latents a dropped draft wrote (and the MTP's) the next step writes over
before anything reads them.

The expert layer is `moe.expert_layer` under `moe.sigmoid_route` with
groups; the chip holds `expert_range(ep_rank, ep_size)` of the experts
and the first of `vocab_shards` slices of the vocabulary. The layers
held are the published ones from `first_layer` on, and a layer's kind
and limits are read at its published index.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..ops.decode_attention import position_valid
from ..ops.kda_delta import kda_delta_route
from ..parallel.sharding import expert_range
from . import mla
from .kda import conv_qkv, gated_output, kda_chunked, kda_step
from .lm_common import (
    LanguageModel,
    apply_rope,
    count_params,
    decode_loop,
    draft_loop,
    drafting_report,
    drafts,
    head,
    init_from_shapes,
    mlp_shapes,
    mtp_input,
    nbytes,
    rms_norm,
    rope_tables,
    swiglu,
    zeros,
)
from .moe import decode_route, expert_layer, prefill_route, report_loads, sigmoid_route


@dataclasses.dataclass(frozen=True)
class LingFlashConfig:
    """The published `config.json`'s shape keys under their own names,
    the chunk the prefill scans by, and the chip's share of a deployment
    as `SolarOpen2Config` states it, with the published index of the
    first layer held (`first_layer`)."""

    hidden_size: int = 2560
    num_hidden_layers: int = 42
    first_layer: int = 0
    layer_group_size: int = 6
    num_attention_heads: int = 32
    head_dim: int = 128
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    kda_chunk: int = 64
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6e6
    intermediate_size: int = 6144
    first_k_dense_replace: int = 2
    moe_intermediate_size: int = 768
    moe_shared_expert_intermediate_size: int = 768
    num_experts: int = 512
    num_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    expert_swiglu_limit_list: tuple = (0,) * 35 + (4,) * 7
    share_expert_swiglu_limit_list: tuple = (0,) * 34 + (5,) * 6 + (7,) * 2
    num_nextn_predict_layers: int = 1
    vocab_size: int = 157184
    rms_norm_eps: float = 1e-6
    ep_size: int = 1
    ep_rank: int = 0
    vocab_shards: int = 1

    def __post_init__(self):
        if self.num_nextn_predict_layers != 1:
            raise ValueError("only the published form is written: one MTP module")
        last = self.first_layer + self.num_hidden_layers
        if min(len(self.expert_swiglu_limit_list), len(self.share_expert_swiglu_limit_list)) < last:
            raise ValueError(f"the lists of SwiGLU limits do not reach layer {last - 1}")

    @property
    def layers(self) -> range:
        """The published indices of the layers held."""
        return range(self.first_layer, self.first_layer + self.num_hidden_layers)

    @property
    def held_experts(self) -> range:
        return expert_range(self.num_experts, self.ep_rank, self.ep_size)

    @property
    def vocab_held(self) -> int:
        return self.vocab_size // self.vocab_shards

    def is_mla(self, layer: int) -> bool:
        """Latent attention (the last layer of a group), else KDA."""
        return (layer + 1) % self.layer_group_size == 0

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_k_dense_replace

    def limits(self, layer: int) -> tuple[float, float]:
        """(routed, shared): the SwiGLU clamps of a published layer's
        feed-forward part, 0 for none; -1 is the MTP module's, which
        takes the last main layer's."""
        return (float(self.expert_swiglu_limit_list[layer]),
                float(self.share_expert_swiglu_limit_list[layer]))

    @property
    def mla_layers(self) -> int:
        """Those of the main model; the MTP module's is one more."""
        return sum(self.is_mla(layer) for layer in self.layers)

    @property
    def kda_layers(self) -> int:
        return self.num_hidden_layers - self.mla_layers

    @property
    def sparse_layers(self) -> int:
        return sum(not self.is_dense(layer) for layer in self.layers)

    @property
    def linear_width(self) -> int:
        return self.num_attention_heads * self.head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def cache_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim


# --- parameters -----------------------------------------------------------


def param_shapes(cfg: LingFlashConfig) -> dict[str, Any]:
    """The tree's shapes with each weight's fan-in (None: a norm's
    scale, initialised to one). `a_log` and `dt_bias` are drawn like
    weights of fan-in 1 and shifted by `init_params`."""
    h, heads, lin = cfg.hidden_size, cfg.num_attention_heads, cfg.linear_width
    held = len(cfg.held_experts)
    mla_shapes = {
        "w_q": ((h, heads * cfg.qk_head_dim), h),
        "w_dkv": ((h, cfg.cache_width), h),
        "kv_norm": ((cfg.kv_lora_rank,), None),
        "w_uk": ((cfg.kv_lora_rank, heads, cfg.qk_nope_head_dim), cfg.kv_lora_rank),
        "w_uv": ((cfg.kv_lora_rank, heads, cfg.v_head_dim), cfg.kv_lora_rank),
        "w_a": ((h, heads), h),
        "w_o": ((heads * cfg.v_head_dim, h), heads * cfg.v_head_dim),
    }
    kda_shapes = {
        "w_qkv": ((h, 3 * lin), h),
        "conv": ((cfg.short_conv_kernel_size, 3 * lin), cfg.short_conv_kernel_size),
        "w_f": ((h, lin), h), "a_log": ((heads,), 1), "dt_bias": ((lin,), 1),
        "w_beta": ((h, heads), h), "w_g": ((h, lin), h),
        "o_norm": ((cfg.head_dim,), None),
        "w_o": ((lin, h), lin),
    }

    def layer(mixer: str, dense: bool) -> dict:
        width = cfg.moe_intermediate_size
        ffn = {"mlp": mlp_shapes(h, cfg.intermediate_size)} if dense else {"moe": {
            "w_g": ((h, cfg.num_experts), h),
            "bias": ((cfg.num_experts,), None),
            "experts": {
                "w_gate_up": ((held, h, 2 * width), h),
                "w_down": ((held, width, h), width),
            },
            "shared": mlp_shapes(
                h, cfg.moe_shared_expert_intermediate_size * cfg.num_shared_experts),
        }}
        return {
            "mixer_norm": ((h,), None),
            mixer: dict(mla_shapes if mixer == "mla" else kda_shapes),
            "ffn_norm": ((h,), None),
            **ffn,
        }

    return {
        "embed": ((cfg.vocab_held, h), 1),
        "layers": [layer("mla" if cfg.is_mla(i) else "kda", cfg.is_dense(i)) for i in cfg.layers],
        "final_norm": ((h,), None),
        "head": ((h, cfg.vocab_held), h),
        "mtp": {
            "embed_norm": ((h,), None), "hidden_norm": ((h,), None),
            "w_eh": ((2 * h, h), 2 * h),
            "layer": layer("mla", False),
            "norm": ((h,), None),
        },
    }


def param_count(cfg: LingFlashConfig) -> int:
    return count_params(param_shapes(cfg))


# What `init_params` subtracts from the drawn `dt_bias` (as
# `solar_open2.DT_BIAS_SHIFT` does, by 4 under its softplus): with A =
# exp(N(0, 1)) inside the sigmoid and W_f x + dt_bias drawn N(-6, 2), a
# channel's alpha = exp(-5 sigmoid(A z)) has its median at 0.985 and its
# quartiles at 0.77 and 0.99996: a memory of tens to hundreds of tokens
# with a tail either way (a bias drawn about 0 would give g about -2.5,
# alpha 0.08: a state that forgets within a token).
DT_BIAS_SHIFT = 6.0


def init_params(cfg: LingFlashConfig, key, dtype=jnp.float32) -> dict[str, Any]:
    """Seeded random weights in `dtype` (`lm_common.init_from_shapes`);
    the routers' selection bias zero, `dt_bias` shifted down, and those
    with `a_log` float32 whatever `dtype`, as `solar_open2.init_params`
    has them."""
    params = init_from_shapes(param_shapes(cfg), key, dtype)
    for block in (*params["layers"], params["mtp"]["layer"]):
        if "moe" in block:
            block["moe"]["bias"] = jnp.zeros_like(block["moe"]["bias"], jnp.float32)
        if "kda" in block:
            kda = block["kda"]
            kda["a_log"] = kda["a_log"].astype(jnp.float32)
            kda["dt_bias"] = kda["dt_bias"].astype(jnp.float32) - DT_BIAS_SHIFT
    return params


# --- a request's state ----------------------------------------------------


def state_shapes(cfg: LingFlashConfig, cache_len: int, dtype) -> dict[str, jax.ShapeDtypeStruct]:
    """The tree a request carries from its prefill through its decode."""
    heads, d, tail = cfg.num_attention_heads, cfg.head_dim, cfg.short_conv_kernel_size - 1
    return {
        "latents": jax.ShapeDtypeStruct((cfg.mla_layers + 1, cache_len, cfg.cache_width), dtype),
        # an array a layer: a step then moves a layer's two slots and no other's
        "state": tuple(
            jax.ShapeDtypeStruct((2, heads, d, d), jnp.float32) for _ in range(cfg.kda_layers)),
        "conv": jax.ShapeDtypeStruct((cfg.kda_layers, 2, tail, 3 * cfg.linear_width), dtype),
        "slot": jax.ShapeDtypeStruct((), jnp.int32),
        "h": jax.ShapeDtypeStruct((cfg.hidden_size,), dtype),
    }


def _slot(cfg, layer: int) -> int:
    """A published layer's place among the held layers of its kind."""
    return sum(cfg.is_mla(i) == cfg.is_mla(layer) for i in range(cfg.first_layer, layer))


def standing(slot, kept):
    """Which of a KDA layer's two slots stands after a step that ran two
    positions from `slot`: the one it read, now holding the state after
    the second position, where the draft was kept, else the other, which
    holds the state after the first."""
    return jnp.where(kept, slot, 1 - slot)


def standing_state(cache) -> tuple[jax.Array, jax.Array]:
    """(matrix states [KDA layers, H, d, d], tails [KDA layers, kernel -
    1, 3 H d]) of the slot that stands."""
    states = jnp.stack([held[cache["slot"]] for held in cache["state"]])
    return states, cache["conv"][:, cache["slot"]]


# --- the mixers -----------------------------------------------------------


def _rope(cfg, positions):
    return rope_tables(cfg.rope_theta, cfg.qk_rope_head_dim, positions)


def _queries(cfg, p, x, rope):
    """[T, heads, nope] and rotated [T, heads, rope] of x [T, hidden]:
    no query latent."""
    q = (x @ p["w_q"]).reshape(x.shape[0], cfg.num_attention_heads, cfg.qk_head_dim)
    return q[..., : cfg.qk_nope_head_dim], apply_rope(q[..., cfg.qk_nope_head_dim:], *rope)


def _gated_heads(p, x, out):
    """y [T, hidden] of the heads' outputs [T, heads, v]: each head
    times its gate sigmoid(w_a,h . x), then W_o."""
    gate = jax.nn.sigmoid(jnp.dot(x, p["w_a"], preferred_element_type=jnp.float32))
    gated = (out.astype(jnp.float32) * gate[:, :, None]).astype(out.dtype)
    return gated.reshape(x.shape[0], -1) @ p["w_o"]


def mla_whole(cfg, p, x, rope):
    """Over a whole sequence x [T, hidden] (the prefill's form). Returns
    (output [T, hidden], the latents to cache [T, rank + rope])."""
    latents = mla.latents(p, x, rope, cfg.rms_norm_eps)
    out = mla.expanded(
        *_queries(cfg, p, x, rope), latents, p["w_uk"], p["w_uv"], cfg.qk_head_dim ** -0.5)
    return _gated_heads(p, x, out), latents


def mla_cached(cfg, p, x, cache, index: int, positions):
    """A step's W new tokens x [W, hidden] at `positions` [W] (one after
    another): their latents written into slot `index` of the cache, then
    each query over what it may see of the slot, absorbed. Returns
    (output [W, hidden], cache)."""
    rope = _rope(cfg, positions)
    held = jax.lax.dynamic_update_slice(
        cache["latents"], mla.latents(p, x, rope, cfg.rms_norm_eps)[None],
        (index, positions[0], 0))
    out = mla.absorbed(
        *_queries(cfg, p, x, rope), held[index], position_valid(positions, held.shape[1]),
        p["w_uk"], p["w_uv"], cfg.qk_head_dim ** -0.5)
    return _gated_heads(p, x, out), {**cache, "latents": held}


def kda_inputs(cfg, p, x, tail):
    """What the delta rule takes of x [T, hidden] (normed), `tail`
    [kernel - 1, 3 H d] the convolution's inputs of the tokens before: q,
    k, v and the output's gate, [T, H, d] in x's dtype, the log-decay g
    [T, H, d] in (`kda_lower_bound`, 0) and beta [T, H] in (0, 1),
    float32, and the convolution's inputs, tail first."""
    tokens, heads, d = x.shape[0], cfg.num_attention_heads, cfg.head_dim
    with jax.named_scope("conv"):
        q, k, v, window = conv_qkv(x @ p["w_qkv"], p["conv"], tail, heads, d)
    with jax.named_scope("gates"):
        rate = jnp.dot(x, p["w_f"], preferred_element_type=jnp.float32) + p["dt_bias"]
        g = cfg.kda_lower_bound * jax.nn.sigmoid(
            jnp.exp(p["a_log"])[None, :, None] * rate.reshape(tokens, heads, d))
        beta = jax.nn.sigmoid(jnp.dot(x, p["w_beta"], preferred_element_type=jnp.float32))
        gate = jax.nn.sigmoid(jnp.dot(x, p["w_g"], preferred_element_type=jnp.float32))
        gate = gate.reshape(tokens, heads, d).astype(x.dtype)
    return q, k, v, g, beta, gate, window


def kda_whole(cfg, p, x, tail, state):
    """A KDA mixer over a whole sequence x [T, hidden] from `tail` and
    `state` (the prefill's form). Returns (output, tail, state)."""
    q, k, v, g, beta, gate, window = kda_inputs(cfg, p, x, tail)
    with jax.named_scope("delta"):
        o, state = kda_chunked(q, k, v, g, beta, state, cfg.kda_chunk)
    return (gated_output(o, gate, p["o_norm"], p["w_o"], cfg.rms_norm_eps),
            window[x.shape[0]:], state)


def kda_cached(cfg, p, x, cache, index: int):
    """A KDA mixer for a step's W new tokens x [W, hidden], one or two,
    from the slot of layer `index` that stands: the recurrence once a
    token, each output from its own state. The state and tail after the
    last token go over those read; with two tokens, those after the
    first into the other slot (`keep`), and `standing` says afterwards
    which of the two slots holds. Returns (output [W, hidden], cache)."""
    slot, tokens = cache["slot"], x.shape[0]
    q, k, v, g, beta, gate, window = kda_inputs(cfg, p, x, cache["conv"][index, slot])
    with jax.named_scope("delta"):
        q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
        state, outs, states = cache["state"][index][slot], [], []
        for j in range(tokens):
            o, state = kda_step(q[j], k[j], v[j], g[j], beta[j], state)
            outs.append(o)
            states.append(state)

    def write(cache, after: int, into):
        """The state and the tail that token `after` of the step left."""
        tail = window[after + 1:after + cache["conv"].shape[2] + 1]
        held = jax.lax.dynamic_update_slice(
            cache["state"][index], states[after][None], (into, 0, 0, 0))
        return {
            **cache,
            "state": (*cache["state"][:index], held, *cache["state"][index + 1:]),
            "conv": jax.lax.dynamic_update_slice(
                cache["conv"], tail[None, None], (index, into, 0, 0)),
        }

    cache = write(cache, tokens - 1, slot)
    if tokens == 2:
        with jax.named_scope("keep"):
            cache = write(cache, 0, 1 - slot)
    out = gated_output(jnp.stack(outs), gate, p["o_norm"], p["w_o"], cfg.rms_norm_eps)
    return out, cache


# --- a layer, in either form ----------------------------------------------


def _feed_forward(cfg, block, x, layer: int):
    """(output, chosen ids [T, k] or None, pairs per held expert or
    None); `layer` the published index whose limits hold (-1: the MTP
    module's)."""
    if "mlp" in block:
        with jax.named_scope("dense"):
            return swiglu(x, block["mlp"]), None, None
    p = block["moe"]
    route = partial(
        sigmoid_route, bias=p["bias"], k=cfg.num_experts_per_tok,
        scale=cfg.routed_scaling_factor, renormalise=cfg.norm_topk_prob,
        n_group=cfg.n_group, topk_group=cfg.topk_group)
    return expert_layer(p, x, cfg.held_experts, route, *cfg.limits(layer))


def _layer(cfg, block, h, layer: int, mixer):
    """One pre-norm residual layer; `mixer(kind, p, x)` (`kind` "mla" or
    "kda") returns (output, what it hands back). Returns (h, that,
    chosen ids, pairs per held expert)."""
    kind = "mla" if "mla" in block else "kda"
    with jax.named_scope(kind):
        out, kept = mixer(kind, block[kind], rms_norm(h, block["mixer_norm"], cfg.rms_norm_eps))
    h = h + out
    out, ids, sizes = _feed_forward(
        cfg, block, rms_norm(h, block["ffn_norm"], cfg.rms_norm_eps), layer)
    return h + out, kept, ids, sizes


# --- the two programs -----------------------------------------------------


class Prefill(NamedTuple):
    logits: jax.Array   # [vocab_held] float32, at the prompt's last position
    cache: dict         # `state_shapes`: the request's state after the prompt
    loads: jax.Array    # [sparse layers, held] pairs on each held expert
    chosen: jax.Array | None  # [sparse layers, T, k] experts chosen (the parity check reads it)


class Decode(NamedTuple):
    ids: jax.Array      # [steps]
    loads: jax.Array    # [sparse layers + 1, held], summed over the steps; the MTP's last
    counts: jax.Array   # [4] int32: steps taken, drafts made, drafts kept, held experts read
    cache: dict         # the state it was given, after the steps
    kept: dict | None   # under `collect`: see `decode`


@partial(jax.jit, static_argnames=("cfg", "cache_len", "collect"))
def prefill(cfg: LingFlashConfig, params, ids, *, cache_len: int, collect: bool = False):
    """The whole prompt `ids` [T] at once. Returns the logits at its last
    position, the request's state (allocated here, once: each MLA slot's
    first T positions written, each KDA layer's state and tail as the
    last token left them in slot 0, which stands, `h` the last position's
    residual stream), the pairs that fell on each held expert and the experts
    chosen, whatever `collect`: one program (`deepseek_v2.prefill`).

    Of the MTP module the prompt needs the latents only (nothing reads
    its output before the decode's first draft), so that is what runs:
    `mtp_input`, the norm, `mla.latents`. Position T - 1 has no next
    token yet and is written from token 0; the decode's first step
    writes it again before anything reads it."""
    tokens = ids.shape[0]
    rope = _rope(cfg, jnp.arange(tokens))
    h = params["embed"][ids]
    # each layer writes into the tree as allocated here: put together from the layers'
    # states at the end, the program holds 0.8 GB more (compiled for a described v5e)
    cache = zeros(state_shapes(cfg, cache_len, h.dtype))

    def mixer(index, kind, p, x):
        if kind == "mla":
            return mla_whole(cfg, p, x, rope)
        out, tail, state = kda_whole(cfg, p, x, cache["conv"][index, 0], cache["state"][index][0])
        return out, (state, tail)

    chosen, loads = [], []
    for layer, block in zip(cfg.layers, params["layers"]):
        index = _slot(cfg, layer)
        with jax.named_scope(f"layer_{layer}"):
            h, kept, ids_l, sizes = _layer(cfg, block, h, layer, partial(mixer, index))
        if cfg.is_mla(layer):
            cache["latents"] = jax.lax.dynamic_update_slice(
                cache["latents"], kept[None], (index, 0, 0))
        else:
            state, tail = kept
            cache["state"] = tuple(
                held.at[0].set(state) if at == index else held
                for at, held in enumerate(cache["state"]))
            cache["conv"] = cache["conv"].at[index, 0].set(tail)
        if ids_l is not None:
            chosen.append(ids_l)
            loads.append(sizes)
    with jax.named_scope("mtp"):
        block = params["mtp"]["layer"]
        u = mtp_input(cfg, params, h, jnp.concatenate([ids[1:], jnp.zeros((1,), ids.dtype)]))
        with jax.named_scope("mla"):
            latents = mla.latents(
                block["mla"], rms_norm(u, block["mixer_norm"], cfg.rms_norm_eps), rope,
                cfg.rms_norm_eps)
        cache["latents"] = jax.lax.dynamic_update_slice(
            cache["latents"], latents[None], (cfg.mla_layers, 0, 0))
    cache["h"] = h[-1]
    return Prefill(
        head(cfg, params, h[-1:])[0], cache, jnp.stack(loads),
        jnp.stack(chosen),  # served too: one program, whatever `collect`
    )


def main_step(cfg, params, cache, tokens, position):
    """W tokens [W], one or two, at `position`, `position` + 1 through
    every main layer over the request's state. Returns (logits [W,
    vocab_held], the residual streams [W, hidden], cache, ids [sparse
    layers, W, k], pairs per held expert [sparse layers, held]). After
    two tokens `cache["slot"]` has yet to be told which slot stands."""
    positions = position + jnp.arange(tokens.shape[0])
    h = params["embed"][tokens]

    def mixer(index, kind, p, x):
        if kind == "mla":
            return mla_cached(cfg, p, x, cache, index, positions)
        return kda_cached(cfg, p, x, cache, index)

    chosen, loads = [], []
    for layer, block in zip(cfg.layers, params["layers"]):
        with jax.named_scope(f"layer_{layer}"):
            h, cache, ids_l, sizes = _layer(
                cfg, block, h, layer, partial(mixer, _slot(cfg, layer)))
        if ids_l is not None:
            chosen.append(ids_l)
            loads.append(sizes)
    logits = head(cfg, params, h)
    return logits, h, cache, jnp.stack(chosen), jnp.stack(loads)


def mtp_step(cfg, params, cache, h, tokens, position):
    """The MTP module over W confirmed positions from `position`: their
    residual streams h [W, hidden] and the tokens that follow them [W].
    Returns (draft logits [W, vocab_held], cache, ids [W, k], pairs per
    held expert [held])."""
    positions = position + jnp.arange(tokens.shape[0])
    out, cache, ids, sizes = _layer(
        cfg, params["mtp"]["layer"], mtp_input(cfg, params, h, tokens), -1,
        lambda kind, p, x: mla_cached(cfg, p, x, cache, cfg.mla_layers, positions))
    return head(cfg, params, out, params["mtp"]["norm"]), cache, ids, sizes


def _decode_plain(cfg, params, cache, logits, start, key, temperature, steps, collect):
    """`steps` one-token steps, as the other models' decodes."""

    def step(cache, token, position):
        rows, _, cache, chosen, loads = main_step(cfg, params, cache, token[None], position)
        kept = {"logits": rows[0], "chosen": chosen[:, 0]} if collect else None
        return rows[0], cache, (loads, jnp.count_nonzero(loads)), kept

    cache, ids, (loads, read), kept = decode_loop(
        step, cache, logits, start, key, temperature, steps)
    loads = jnp.concatenate([loads, jnp.zeros_like(loads[:1])])  # the MTP module's row
    counts = jnp.stack([jnp.int32(steps), jnp.int32(0), jnp.int32(0), read.astype(jnp.int32)])
    return Decode(ids, loads, counts, cache, kept)


def _decode_drafting(cfg, params, cache, logits, start, key, temperature, steps, collect):
    """The self-speculative loop (`lm_common.draft_loop`) over this
    model's two steps. Once a draft's fate is known `cache["slot"]` is
    told which slot of each KDA layer stands (`standing`), so before a
    step the main model's state holds positions 0 .. n - 1 in it."""

    def drafted(cache, h, tokens, position):
        rows, cache, _, loads = mtp_step(cfg, params, cache, h, tokens, position)
        return rows, None, cache, (loads, jnp.count_nonzero(loads)), None

    def verified(cache, tokens, position):
        rows, h, cache, chosen, loads = main_step(cfg, params, cache, tokens, position)
        kept = {"chosen": chosen} if collect else None
        return rows, h, cache, (loads, jnp.count_nonzero(loads)), kept

    def settle(cache, accepted):
        with jax.named_scope("keep"):
            return {**cache, "slot": standing(cache["slot"], accepted)}

    cache, ids, counts, ((loads_mtp, read_mtp), (loads, read)), kept = draft_loop(
        drafted, verified, cache, logits, start, key, temperature, steps, settle)
    loads = jnp.concatenate([loads, loads_mtp[None]])  # the MTP module's row
    counts = jnp.concatenate([counts, (read + read_mtp).astype(jnp.int32)[None]])
    return Decode(ids, loads, counts, cache, kept)


@partial(jax.jit, static_argnames=("cfg", "steps", "collect", "draft_tokens"),
         donate_argnames=("cache",))
def decode(cfg: LingFlashConfig, params, cache, logits, start, key, temperature, *,
           steps: int, collect: bool = False, draft_tokens: int = 0):
    """`steps` ids in one program, from the prefill's `logits` at
    position `start - 1`; no early stop. With `draft_tokens` 0 that is
    `steps` one-token steps (draw id i from the logits, run it through
    the model at `start + i`); with 1 the self-speculative loop, which
    takes as many steps as its drafts' fates make it, a loop on the
    device with no trip to the host. The state tree is donated, carried through
    the loop and handed back. Returns the ids, the pairs on each held
    expert, `counts` and, under `collect`, what `k_exaone.decode` keeps:
    per step the main model's logits, the experts chosen and, when
    drafting, the logits each draft was drawn from, the step's position
    n and whether its draft was kept."""
    run = _decode_drafting if drafts(draft_tokens) else _decode_plain
    return run(cfg, params, dict(cache), logits, start, key, temperature, steps, collect)


class LingFlash(LanguageModel):
    """What a bundle's `lm` part is (the contract is in `lm_common`)."""

    _init = staticmethod(init_params)
    _prefill = staticmethod(prefill)
    _decode = staticmethod(decode)
    draft_tokens_max = 1

    @property
    def layer_passes(self) -> int:
        return self.cfg.num_hidden_layers

    def read_back(self, prefill: Prefill, decode: Decode) -> tuple:
        """The pairs on each held expert, of either program, and the
        decode's counts."""
        return prefill.loads, decode.loads, decode.counts

    def describe(self, cache_len: int) -> dict[str, int]:
        cfg, shapes = self.cfg, state_shapes(self.cfg, cache_len, self.dtype)
        return {
            "layers": cfg.num_hidden_layers,
            "linear_layers": cfg.kda_layers,
            "latent_layers": cfg.mla_layers + 1,
            "experts_held": len(cfg.held_experts),
            "experts_total": cfg.num_experts,
            "cache_bytes": nbytes(shapes["latents"]),
            "state_bytes": sum(map(nbytes, shapes["state"])) + nbytes(shapes["conv"]),
        }

    def report(self, prompt_tokens: int, new_tokens: int, cache_len: int,
               prefill_loads, decode_loads, counts) -> dict:
        """`describe`, the chunks a KDA layer's prefill walked and in
        which form (`kda_delta_route`), what the decode's steps came to,
        the layer bodies either program ran (the
        decode's over every position a step ran, a rejected draft's and
        the MTP module's among them; of the MTP module the prefill runs
        only the latents, no body) and, per phase, the routing as
        `moe.report_loads` has it, the decode's pairs counted over the
        positions its steps ran."""
        cfg = self.cfg
        width, drafting = drafting_report(
            counts, cfg.num_experts_per_tok, cfg.sparse_layers, cfg.num_hidden_layers)
        return {
            **self.describe(cache_len),
            "prefill_chunks": -(-prompt_tokens // cfg.kda_chunk),
            "kda_form": kda_delta_route(
                cfg.num_attention_heads, cfg.head_dim, cfg.kda_chunk, self.dtype),
            **report_loads(
                cfg.num_experts_per_tok, cfg.num_experts, prompt_tokens, new_tokens,
                prefill_loads, decode_loads,
                # a step's positions in a main layer; the module's one takes the same route
                decode_route(
                    width * cfg.num_experts_per_tok, cfg.hidden_size,
                    cfg.moe_intermediate_size, self.dtype),
                prefill_expert_route=prefill_route(
                    prompt_tokens, cfg.num_experts_per_tok, len(cfg.held_experts),
                    cfg.num_experts, cfg.hidden_size, cfg.moe_intermediate_size, self.dtype)),
            "prefill_layer_passes": prompt_tokens * cfg.num_hidden_layers,
            **drafting,
        }
