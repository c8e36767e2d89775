"""Solar-Open2: layers of two kinds in a period of `gqa_interval + 1`.
The first of a period is softmax attention with grouped queries, no
rotary embedding and an output gate; the others are KDA, linear
attention by a gated delta rule whose state is one float32 matrix a head
of fixed size, whatever the position. Every layer's feed-forward part is
a mixture of routed experts beside one shared expert.

    h += mixer(rms(h));  h += moe(rms(h))

KDA, a head (H heads of d_k = d_v = d), x the normed input:

    q_t = l2norm(silu(conv4(W_q x))_t) d^-1/2     k_t = l2norm(silu(conv4(W_k x))_t)
    v_t = silu(conv4(W_v x))_t                    (a causal depth-wise convolution of 4)
    g_t = -exp(A_log) softplus(W_f2 W_f1 x + dt_bias)   in R^d, alpha_t = exp(g_t)
    beta_t = 2 sigmoid(W_beta x)                  (2: `kda_allow_neg_eigval`)
    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T,   o_t = S_t^T q_t
    y_t = W_o [rms_head(o_t) * sigmoid(W_g2 W_g1 x)]

in the two forms of `models/kda.py` (shared with `ling_flash.py`), which
must agree: `kda_chunked` (the prefill, by chunks of `kda_chunk` tokens)
and `kda_step` (the decode: the recurrence itself). The gates above are
this model's own.

A request's state is therefore a tree of three kinds (`state_shapes`):
`kv` [full layers, 2, key heads, positions, d], which grows with the
position; `state` [linear layers, H, d, d] float32 and `conv` [linear
layers, 3, 3 H d], the convolutions' last three inputs, which do not.
The prefill allocates it, the decode takes it by donation, writes it in
place and hands it back.

The expert layer is `moe.expert_layer` (shared with `deepseek_v2.py`)
under this model's rule: sigmoid scores, the `num_experts_per_tok`
largest of score + bias, the chosen scores renormalised. The chip holds
`expert_range(ep_rank, ep_size)` of the experts and the first of
`vocab_shards` slices of the vocabulary, as `DeepSeekV2Config` has it.

Parameter layout where it departs from the published checkpoint's (a
fixed split of weight columns): a KDA layer's q, k and v projections lie
side by side as `w_qkv` and their convolutions' filters as `conv`;
`w_gate_up` is a SwiGLU's gate and up.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..ops.attention import dot_product_attention
from ..ops.decode_attention import attend_xla, position_valid
from ..ops.kda_delta import kda_delta_route
from ..parallel.sharding import expert_range
from .kda import KDA_SUBCHUNK, conv_qkv, gated_output, kda_chunked, kda_step
from .lm_common import (
    LanguageModel,
    count_params,
    decode_loop,
    head,
    init_from_shapes,
    mlp_shapes,
    nbytes,
    rms_norm,
    zeros,
)
from .moe import decode_route, expert_layer, prefill_route, report_loads, sigmoid_route


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config:
    """The published `config.json`'s shape keys under their own names
    (`linear_*` and `short_conv_kernel_size` are its `linear_attn_config`
    block), the chunk the prefill scans by, and the chip's share of a
    deployment as `DeepSeekV2Config` states it."""

    hidden_size: int = 4096
    num_hidden_layers: int = 48
    gqa_interval: int = 3
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    use_rope: bool = False
    use_gqa_gate: bool = True
    linear_num_heads: int = 64
    linear_head_dim: int = 128
    short_conv_kernel_size: int = 4
    kda_use_full_proj: bool = False
    kda_allow_neg_eigval: bool = True
    kda_chunk: int = 64
    moe_intermediate_size: int = 1280
    n_routed_experts: int = 320
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    vocab_size: int = 196608
    rms_norm_eps: float = 1e-5
    ep_size: int = 1
    ep_rank: int = 0
    vocab_shards: int = 1

    def __post_init__(self):
        if self.use_rope or not self.use_gqa_gate or self.kda_use_full_proj:
            raise ValueError(
                "only the published form is written: no rotary embedding on the full-"
                "attention layers (use_rope false), their output gated (use_gqa_gate "
                "true), KDA's gates low-rank (kda_use_full_proj false)"
            )
        if self.kda_chunk % min(self.kda_chunk, KDA_SUBCHUNK):
            raise ValueError(f"a chunk of {self.kda_chunk} is no multiple of {KDA_SUBCHUNK}")

    @property
    def held_experts(self) -> range:
        return expert_range(self.n_routed_experts, self.ep_rank, self.ep_size)

    @property
    def vocab_held(self) -> int:
        return self.vocab_size // self.vocab_shards

    def is_full(self, layer: int) -> bool:
        """Softmax attention (the first layer of a period), else KDA."""
        return layer % (self.gqa_interval + 1) == 0

    @property
    def full_layers(self) -> int:
        return sum(self.is_full(layer) for layer in range(self.num_hidden_layers))

    @property
    def linear_layers(self) -> int:
        return self.num_hidden_layers - self.full_layers

    @property
    def linear_width(self) -> int:
        return self.linear_num_heads * self.linear_head_dim


# --- parameters -----------------------------------------------------------


def param_shapes(cfg: SolarOpen2Config) -> dict[str, Any]:
    """The tree's shapes with each weight's fan-in (None: a norm's
    scale, initialised to one). `a_log` and `dt_bias` are drawn like
    weights of fan-in 1 and shifted by `init_params`."""
    h, width = cfg.hidden_size, cfg.moe_intermediate_size
    heads, kv = cfg.num_attention_heads * cfg.head_dim, cfg.num_key_value_heads * cfg.head_dim
    lin, rank = cfg.linear_width, cfg.linear_head_dim
    held = len(cfg.held_experts)

    layers = []
    for layer in range(cfg.num_hidden_layers):
        if cfg.is_full(layer):
            mixer = {
                "w_q": ((h, heads), h), "w_k": ((h, kv), h), "w_v": ((h, kv), h),
                "w_gate": ((h, heads), h), "w_o": ((heads, h), heads),
            }
        else:
            mixer = {
                "w_qkv": ((h, 3 * lin), h),
                "conv": ((cfg.short_conv_kernel_size, 3 * lin), cfg.short_conv_kernel_size),
                "w_f1": ((h, rank), h), "w_f2": ((rank, lin), rank),
                "a_log": ((cfg.linear_num_heads,), 1), "dt_bias": ((lin,), 1),
                "w_beta": ((h, cfg.linear_num_heads), h),
                "w_g1": ((h, rank), h), "w_g2": ((rank, lin), rank),
                "o_norm": ((cfg.linear_head_dim,), None),
                "w_o": ((lin, h), lin),
            }
        layers.append({
            "mixer_norm": ((h,), None),
            "gqa" if cfg.is_full(layer) else "kda": mixer,
            "ffn_norm": ((h,), None),
            "moe": {
                "w_g": ((h, cfg.n_routed_experts), h),
                "bias": ((cfg.n_routed_experts,), None),
                "experts": {
                    "w_gate_up": ((held, h, 2 * width), h),
                    "w_down": ((held, width, h), width),
                },
                "shared": mlp_shapes(h, width * cfg.n_shared_experts),
            },
        })
    return {
        "embed": ((cfg.vocab_held, h), 1),
        "layers": layers,
        "final_norm": ((h,), None),
        "head": ((h, cfg.vocab_held), h),
    }


def param_count(cfg: SolarOpen2Config) -> int:
    return count_params(param_shapes(cfg))


# What `init_params` subtracts from the drawn `dt_bias`: softplus of
# N(-4, 2) is 0.01-0.1, so with A = exp(N(0, 1)) a channel's alpha is
# mostly 0.9-0.999, a memory of tens to hundreds of tokens with a tail
# either way, as a trained gate's is (a bias drawn about 0 would give
# alpha 0.4: a state that holds two tokens).
DT_BIAS_SHIFT = 4.0


def init_params(cfg: SolarOpen2Config, key, dtype=jnp.float32) -> dict[str, Any]:
    """Seeded random weights in `dtype` (`lm_common.init_from_shapes`);
    the router's selection bias zero, `dt_bias` shifted down, `a_log`
    and `dt_bias` float32 whatever `dtype`: they are 8,256 numbers a
    layer, and the decay they make is raised to the chunk's length."""
    params = init_from_shapes(param_shapes(cfg), key, dtype)
    for block in params["layers"]:
        block["moe"]["bias"] = jnp.zeros_like(block["moe"]["bias"], jnp.float32)
        if "kda" in block:
            kda = block["kda"]
            kda["a_log"] = kda["a_log"].astype(jnp.float32)
            kda["dt_bias"] = kda["dt_bias"].astype(jnp.float32) - DT_BIAS_SHIFT
    return params


# --- a request's state ----------------------------------------------------


def state_shapes(cfg: SolarOpen2Config, cache_len: int, dtype) -> dict[str, jax.ShapeDtypeStruct]:
    """The tree a request carries from its prefill through its decode."""
    heads, d = cfg.linear_num_heads, cfg.linear_head_dim
    return {
        "kv": jax.ShapeDtypeStruct(
            (cfg.full_layers, 2, cfg.num_key_value_heads, cache_len, cfg.head_dim), dtype),
        "state": jax.ShapeDtypeStruct((cfg.linear_layers, heads, d, d), jnp.float32),
        "conv": jax.ShapeDtypeStruct(
            (cfg.linear_layers, cfg.short_conv_kernel_size - 1, 3 * cfg.linear_width), dtype),
    }


# --- the routing rule -----------------------------------------------------


def route(cfg: SolarOpen2Config, bias: jax.Array, logits: jax.Array):
    """`moe.sigmoid_route` at this model's sizes: (ids, weights)."""
    return sigmoid_route(
        logits, bias, cfg.num_experts_per_tok, cfg.routed_scaling_factor, cfg.norm_topk_prob)


def moe(cfg, p, x):
    """(output, chosen ids [T, k], pairs on each held expert [held])"""
    return expert_layer(p, x, cfg.held_experts, partial(route, cfg, p["bias"]))


# --- the full-attention layer ---------------------------------------------


def _gqa_projections(cfg, p, x):
    """q [T, heads, d], k and v [T, key heads, d], the output's gate
    [T, heads x d] (float32) of x [T, hidden]. No rotation, no norm."""
    tokens = x.shape[0]
    q = (x @ p["w_q"]).reshape(tokens, cfg.num_attention_heads, cfg.head_dim)
    k = (x @ p["w_k"]).reshape(tokens, cfg.num_key_value_heads, cfg.head_dim)
    v = (x @ p["w_v"]).reshape(tokens, cfg.num_key_value_heads, cfg.head_dim)
    gate = jax.nn.sigmoid(jnp.dot(x, p["w_gate"], preferred_element_type=jnp.float32))
    return q, k, v, gate


def _gated_out(p, out, gate):
    return (out.astype(jnp.float32) * gate).astype(out.dtype) @ p["w_o"]


def gqa_whole(cfg, p, x):
    """Over a whole sequence x [T, hidden] (the prefill's form). Returns
    (output [T, hidden], keys and values [2, key heads, T, d])."""
    q, k, v, gate = _gqa_projections(cfg, p, x)
    out = dot_product_attention(q[None], k[None], v[None], causal=True)[0]
    return _gated_out(p, out.reshape(x.shape[0], -1), gate), jnp.stack([k, v]).transpose(0, 2, 1, 3)


def gqa_cached(cfg, p, x, kv, index, position):
    """One new token x [1, hidden] at `position`: its key and value
    written into slot `index` of kv [full layers, 2, key heads,
    positions, d], attention over the slot's positions up to it
    (`decode_attention.attend_xla`, a key head serving its group of
    queries). Returns (output [1, hidden], kv)."""
    q, k, v, gate = _gqa_projections(cfg, p, x)
    kv = jax.lax.dynamic_update_slice(
        # one token: [2, 1, heads, d] and [2, heads, 1, d] are the same bytes
        kv, jnp.stack([k, v]).reshape(1, 2, cfg.num_key_value_heads, 1, cfg.head_dim),
        (index, 0, 0, position, 0))
    valid = position_valid(jnp.asarray(position).reshape(1), kv.shape[3])
    out = attend_xla(q, kv, (index,), valid)
    return _gated_out(p, out.reshape(1, -1), gate), kv


# --- KDA ------------------------------------------------------------------


def kda_inputs(cfg, p, x, tail):
    """What the delta rule takes of x [T, hidden] (normed), `tail`
    [kernel - 1, 3 H d] the convolutions' inputs of the tokens before:
    q, k (unit length; q times d^-1/2), v and the output's gate, [T, H,
    d] in x's dtype, the log-decay g [T, H, d] (< 0) and beta [T, H],
    float32, and the new tail. Convolution, SiLU and norms float32."""
    tokens, heads, d = x.shape[0], cfg.linear_num_heads, cfg.linear_head_dim
    with jax.named_scope("conv"):
        q, k, v, window = conv_qkv(x @ p["w_qkv"], p["conv"], tail, heads, d)
    with jax.named_scope("gates"):
        def low_rank(first, second):
            return jnp.dot(x @ p[first], p[second], preferred_element_type=jnp.float32)

        rate = jax.nn.softplus(low_rank("w_f1", "w_f2") + p["dt_bias"])
        g = -jnp.exp(p["a_log"])[None, :, None] * rate.reshape(tokens, heads, d)
        beta = jax.nn.sigmoid(jnp.dot(x, p["w_beta"], preferred_element_type=jnp.float32))
        if cfg.kda_allow_neg_eigval:
            beta = 2.0 * beta
        gate = jax.nn.sigmoid(low_rank("w_g1", "w_g2")).reshape(tokens, heads, d).astype(x.dtype)
    return q, k, v, g, beta, gate, window[tokens:]


def kda_whole(cfg, p, x, tail, state):
    """A KDA mixer over a whole sequence x [T, hidden] from `tail` and
    `state` (the prefill's form). Returns (output, tail, state)."""
    q, k, v, g, beta, gate, tail = kda_inputs(cfg, p, x, tail)
    with jax.named_scope("delta"):
        o, state = kda_chunked(q, k, v, g, beta, state, cfg.kda_chunk)
    return gated_output(o, gate, p["o_norm"], p["w_o"], cfg.rms_norm_eps), tail, state


def kda_cached(cfg, p, x, tail, state):
    """A KDA mixer for one new token x [1, hidden] (the decode's form)."""
    q, k, v, g, beta, gate, tail = kda_inputs(cfg, p, x, tail)
    with jax.named_scope("delta"):
        q, k, v = (a[0].astype(jnp.float32) for a in (q, k, v))
        o, state = kda_step(q, k, v, g[0], beta[0], state)
    return gated_output(o[None], gate, p["o_norm"], p["w_o"], cfg.rms_norm_eps), tail, state


# --- a layer, in either form ----------------------------------------------


def _slot(cfg, layer: int) -> int:
    """A layer's place among the layers of its kind."""
    return sum(cfg.is_full(i) == cfg.is_full(layer) for i in range(layer))


def _layer(cfg, layer: int, block, h, cache, gqa, kda):
    """One pre-norm residual layer. `gqa(p, x, kv, index)` returns
    (output, kv); `kda(p, x, tail, state)` (output, tail, state).
    Returns (h, cache, chosen ids, pairs per held expert)."""
    index = _slot(cfg, layer)
    with jax.named_scope(f"layer_{layer}"):
        x = rms_norm(h, block["mixer_norm"], cfg.rms_norm_eps)
        if cfg.is_full(layer):
            with jax.named_scope("gqa"):
                out, kv = gqa(block["gqa"], x, cache["kv"], index)
            cache = {**cache, "kv": kv}
        else:
            with jax.named_scope("kda"):
                out, tail, state = kda(
                    block["kda"], x, cache["conv"][index], cache["state"][index])
            cache = {
                **cache, "conv": cache["conv"].at[index].set(tail),
                "state": cache["state"].at[index].set(state),
            }
        h = h + out
        out, ids, sizes = moe(cfg, block["moe"], rms_norm(h, block["ffn_norm"], cfg.rms_norm_eps))
        return h + out, cache, ids, sizes


# --- the two programs -----------------------------------------------------


class Prefill(NamedTuple):
    logits: jax.Array   # [vocab_held] float32, at the prompt's last position
    cache: dict         # `state_shapes`: the request's state after the prompt
    loads: jax.Array    # [layers, held] pairs on each held expert
    chosen: jax.Array | None  # [layers, T, k] experts chosen (the parity check reads it)


class Decode(NamedTuple):
    ids: jax.Array      # [steps]
    loads: jax.Array    # [layers, held], summed over the steps
    cache: dict         # the state it was given, after the steps
    logits: jax.Array | None  # [steps, vocab_held] float32, after id i; under `collect`
    chosen: jax.Array | None  # [steps, layers, k]; under `collect`


@partial(jax.jit, static_argnames=("cfg", "cache_len", "collect"))
def prefill(cfg: SolarOpen2Config, params, ids, *, cache_len: int, collect: bool = False):
    """The whole prompt `ids` [T] at once. Returns the logits at its last
    position, the request's state (allocated here, once: the first T
    positions of `kv` written, `state` and `conv` as the last token left
    them), the pairs that fell on each held expert and the experts chosen,
    whatever `collect`: one program (`deepseek_v2.prefill`)."""
    tokens = ids.shape[0]
    h = params["embed"][ids]
    cache = zeros(state_shapes(cfg, cache_len, h.dtype))

    def gqa(p, x, kv, index):
        out, slot_kv = gqa_whole(cfg, p, x)
        return out, jax.lax.dynamic_update_slice(kv, slot_kv[None], (index, 0, 0, 0, 0))

    chosen, loads = [], []
    for layer, block in enumerate(params["layers"]):
        h, cache, ids_l, sizes = _layer(
            cfg, layer, block, h, cache, gqa, partial(kda_whole, cfg))
        chosen.append(ids_l)
        loads.append(sizes)
    return Prefill(
        head(cfg, params, h[-1:])[0], cache, jnp.stack(loads),
        jnp.stack(chosen),  # served too: one program, whatever `collect`
    )


def decode_step(cfg, params, cache, token, position):
    """One token through every layer over the request's state. Returns
    (logits [vocab_held], cache, ids [layers, k], pairs per held expert
    [layers, held])."""
    h = params["embed"][token][None]

    def gqa(p, x, kv, index):
        return gqa_cached(cfg, p, x, kv, index, position)

    chosen, loads = [], []
    for layer, block in enumerate(params["layers"]):
        h, cache, ids_l, sizes = _layer(
            cfg, layer, block, h, cache, gqa, partial(kda_cached, cfg))
        chosen.append(ids_l[0])
        loads.append(sizes)
    return head(cfg, params, h)[0], cache, jnp.stack(chosen), jnp.stack(loads)


@partial(jax.jit, static_argnames=("cfg", "steps", "collect"), donate_argnames=("cache",))
def decode(cfg: SolarOpen2Config, params, cache, logits, start, key, temperature, *,
           steps: int, collect: bool = False):
    """`steps` dependent decode steps in one program, from the prefill's
    `logits` at position `start - 1`: draw id i from the logits, run it
    through the model at position `start + i`. Always `steps` ids, no
    early stop. The state tree is donated, carried through the loop and
    handed back as `cache`. Returns the ids, the pairs on each held
    expert summed over the steps and, under `collect`, every step's
    logits (the logits after id i) and the experts chosen."""

    def step(cache, token, position):
        logits, cache, chosen, loads = decode_step(cfg, params, cache, token, position)
        return logits, cache, loads, (logits, chosen) if collect else None

    cache, ids, loads, kept = decode_loop(step, cache, logits, start, key, temperature, steps)
    return Decode(ids, loads, cache, *(kept or (None, None)))


class SolarOpen2(LanguageModel):
    """What a bundle's `lm` part is (the contract is in `lm_common`)."""

    _init = staticmethod(init_params)
    _prefill = staticmethod(prefill)
    _decode = staticmethod(decode)

    @property
    def layer_passes(self) -> int:
        return self.cfg.num_hidden_layers

    def read_back(self, prefill: Prefill, decode: Decode) -> tuple:
        """The pairs on each held expert, of either program."""
        return prefill.loads, decode.loads

    def describe(self, cache_len: int) -> dict[str, int]:
        cfg, shapes = self.cfg, state_shapes(self.cfg, cache_len, self.dtype)
        return {
            "layers": cfg.num_hidden_layers,
            "full_layers": cfg.full_layers,
            "linear_layers": cfg.linear_layers,
            "experts_held": len(cfg.held_experts),
            "experts_total": cfg.n_routed_experts,
            "cache_bytes": nbytes(shapes["kv"]),
            "state_bytes": nbytes(shapes["state"]) + nbytes(shapes["conv"]),
        }

    def report(self, prompt_tokens: int, new_tokens: int, cache_len: int,
               prefill_loads, decode_loads) -> dict:
        """`describe`, the chunks a linear layer's prefill walked and in
        which form (`kda_delta_route`), and per phase the token-expert
        pairs the router made and those on held experts."""
        cfg = self.cfg
        return {
            **self.describe(cache_len),
            "prefill_chunks": -(-prompt_tokens // cfg.kda_chunk),
            "kda_form": kda_delta_route(
                cfg.linear_num_heads, cfg.linear_head_dim, cfg.kda_chunk, self.dtype),
            **report_loads(
                cfg.num_experts_per_tok, cfg.n_routed_experts,
                prompt_tokens, new_tokens, prefill_loads, decode_loads,
                decode_route(
                    cfg.num_experts_per_tok, cfg.hidden_size, cfg.moe_intermediate_size,
                    self.dtype),
                prefill_expert_route=prefill_route(
                    prompt_tokens, cfg.num_experts_per_tok, len(cfg.held_experts),
                    cfg.n_routed_experts, cfg.hidden_size, cfg.moe_intermediate_size, self.dtype)),
        }
