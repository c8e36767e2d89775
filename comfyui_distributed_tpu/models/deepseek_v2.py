"""DeepSeek-V2: multi-head latent attention and a mixture of experts
with shared experts, as a language model that generates tokens.

Pure functions over a parameter tree (no flax module: the same weights
are read by two forms of one block, and the float32 reference in
`reference/deepseek_v2.py` takes the tree as it is):

- `prefill`: the whole prompt at once. MLA in its *expanded* form (keys
  and values built from the latent, causal attention through
  `ops.attention.causal_attention`), the latent cache written.
- `decode`: N dependent steps in one program (`lax.fori_loop`), MLA in
  its *absorbed* form (W_uk folded into the query, W_uv applied after
  the weighted sum, attention over the 576-wide latent cache itself),
  the next id sampled on the device from the seed.

The two forms themselves are `models/mla.py`'s (shared with
`ling_flash.py`); this model's own are its queries (a query latent with
a norm, YaRN's rotation) and its softmax scale.

The expert layer (`moe.expert_layer`, shared with `solar_open2.py`) is
told which experts it holds (`parallel.sharding.expert_range` of
`ep_rank` / `ep_size`), routes over all of them by this model's rule
(`route`) and computes its own experts' part of the result; what absent
experts would add is left out. The vocabulary may be a slice too
(`vocab_shards`): embedding, logits and sampling are over the slice.

Parameter layout, where it departs from the published checkpoint's (a
fixed permutation or split of weight columns, nothing a forward pass can
tell from the original): `w_uk` / `w_uv` are the two halves of
`kv_b_proj`; gate and up projections are stored side by side as
`w_gate_up`; rotary dimensions are half-split (`rotate_half`), where the
checkpoint stores interleaved pairs that the published code permutes
into this order before rotating.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.sharding import expert_range
from . import mla
from .lm_common import (
    LanguageModel,
    apply_rope,
    count_params,
    decode_loop,
    head,
    init_from_shapes,
    mlp_shapes,
    rms_norm,
    swiglu,
)
from .moe import decode_route, expert_layer, prefill_route, report_loads


@dataclasses.dataclass(frozen=True)
class DeepSeekV2Config:
    """The published `config.json`'s shape keys under their own names,
    and the chip's share of a deployment: `ep_size` chips share each
    layer's experts and this one is `ep_rank`; the vocabulary is cut
    `vocab_shards` ways and this chip holds the first slice."""

    hidden_size: int = 5120
    num_hidden_layers: int = 60
    first_k_dense_replace: int = 1
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 12288
    moe_intermediate_size: int = 1536
    n_routed_experts: int = 160
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    n_group: int = 8
    topk_group: int = 3
    routed_scaling_factor: float = 16.0
    vocab_size: int = 102400
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.707
    rope_mscale_all_dim: float = 0.707
    rope_original_max_position_embeddings: int = 4096
    ep_size: int = 1
    ep_rank: int = 0
    vocab_shards: int = 1

    @property
    def held_experts(self) -> range:
        return expert_range(self.n_routed_experts, self.ep_rank, self.ep_size)

    @property
    def vocab_held(self) -> int:
        return self.vocab_size // self.vocab_shards

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def cache_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        m = _yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return self.qk_head_dim ** -0.5 * m * m

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_k_dense_replace

    def layer_name(self, layer: int) -> str:
        return f"dense_{layer}" if self.is_dense(layer) else f"moe_{layer}"


# --- rotary position embedding with YaRN ---------------------------------


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: DeepSeekV2Config) -> np.ndarray:
    """The rotary frequencies: a blend of 1/theta^(2i/d) and the same
    divided by the factor, by a linear ramp between the correction
    dimensions of `beta_fast` and `beta_slow` rotations over the
    original context."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    exponent = np.arange(0, dim, 2, dtype=np.float64) / dim
    extrapolated = 1.0 / base ** exponent
    interpolated = extrapolated / cfg.rope_factor

    def correction_dim(rotations: float) -> float:
        return (
            dim * math.log(cfg.rope_original_max_position_embeddings
                           / (rotations * 2 * math.pi))
        ) / (2 * math.log(base))

    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    return (interpolated * ramp + extrapolated * (1 - ramp)).astype(np.float32)


def rope_tables(cfg: DeepSeekV2Config, positions: jax.Array):
    """cos and sin, [T, rope/2] float32; YaRN's factor on them is
    mscale(factor, mscale) / mscale(factor, mscale_all_dim). Its own:
    `lm_common.rope_tables` has neither YaRN's blend nor its factor."""
    angles = positions.astype(jnp.float32)[:, None] * jnp.asarray(yarn_inv_freq(cfg))
    m = _yarn_mscale(cfg.rope_factor, cfg.rope_mscale) / _yarn_mscale(
        cfg.rope_factor, cfg.rope_mscale_all_dim
    )
    return jnp.cos(angles) * m, jnp.sin(angles) * m


# --- parameters -----------------------------------------------------------


def param_shapes(cfg: DeepSeekV2Config) -> dict[str, Any]:
    """The tree's shapes with each weight's fan-in (None: a norm's
    scale, initialised to one)."""
    h, heads = cfg.hidden_size, cfg.num_attention_heads
    held = len(cfg.held_experts)

    layers = []
    for layer in range(cfg.num_hidden_layers):
        block: dict[str, Any] = {
            "attn_norm": ((h,), None),
            "attn": {
                "w_dq": ((h, cfg.q_lora_rank), h),
                "q_norm": ((cfg.q_lora_rank,), None),
                "w_uq": ((cfg.q_lora_rank, heads * cfg.qk_head_dim), cfg.q_lora_rank),
                "w_dkv": ((h, cfg.cache_width), h),
                "kv_norm": ((cfg.kv_lora_rank,), None),
                "w_uk": ((cfg.kv_lora_rank, heads, cfg.qk_nope_head_dim), cfg.kv_lora_rank),
                "w_uv": ((cfg.kv_lora_rank, heads, cfg.v_head_dim), cfg.kv_lora_rank),
                "w_o": ((heads * cfg.v_head_dim, h), heads * cfg.v_head_dim),
            },
            "ffn_norm": ((h,), None),
        }
        if cfg.is_dense(layer):
            block["mlp"] = mlp_shapes(h, cfg.intermediate_size)
        else:
            width = cfg.moe_intermediate_size
            block["moe"] = {
                "w_g": ((h, cfg.n_routed_experts), h),
                "experts": {
                    "w_gate_up": ((held, h, 2 * width), h),
                    "w_down": ((held, width, h), width),
                },
                "shared": mlp_shapes(h, width * cfg.n_shared_experts),
            }
        layers.append(block)
    return {
        "embed": ((cfg.vocab_held, h), 1),
        "layers": layers,
        "final_norm": ((h,), None),
        "head": ((h, cfg.vocab_held), h),
    }


def param_count(cfg: DeepSeekV2Config) -> int:
    return count_params(param_shapes(cfg))


def init_params(cfg: DeepSeekV2Config, key, dtype=jnp.float32) -> dict[str, Any]:
    """Seeded random weights in `dtype` (`lm_common.init_from_shapes`)."""
    return init_from_shapes(param_shapes(cfg), key, dtype)


# --- blocks ---------------------------------------------------------------


def _queries(cfg, p, x, cos, sin):
    """[T, heads, nope] and rotated [T, heads, rope] from x [T, hidden]."""
    c_q = rms_norm(x @ p["w_dq"], p["q_norm"], cfg.rms_norm_eps)
    q = (c_q @ p["w_uq"]).reshape(x.shape[0], cfg.num_attention_heads, cfg.qk_head_dim)
    q_nope, q_rope = q[..., : cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim:]
    return q_nope, apply_rope(q_rope, cos, sin)


def mla_expanded(cfg, p, x, rope):
    """MLA over a whole sequence, keys and values built from the latent
    (`mla.expanded`); `rope` is `rope_tables` of its positions. Returns
    the block's output [T, hidden] and the latents to cache."""
    q_nope, q_rope = _queries(cfg, p, x, *rope)
    latents = mla.latents(p, x, rope, cfg.rms_norm_eps)
    out = mla.expanded(q_nope, q_rope, latents, p["w_uk"], p["w_uv"], cfg.softmax_scale)
    return out.reshape(x.shape[0], -1) @ p["w_o"], latents


def mla_absorbed(cfg, p, x, rope, cache, valid):
    """MLA for new tokens x [T, hidden] over a latent cache [S, kv_lora +
    rope] that already holds their own latents (`mla.absorbed`: W_uk
    folded into the query, attention over the latent itself, W_uv after
    the weighted sum). `valid` [T, S] says which cached positions each
    token sees."""
    q_nope, q_rope = _queries(cfg, p, x, *rope)
    out = mla.absorbed(
        q_nope, q_rope, cache, valid, p["w_uk"], p["w_uv"], cfg.softmax_scale)
    return out.reshape(x.shape[0], -1) @ p["w_o"]


def route(cfg: DeepSeekV2Config, scores: jax.Array):
    """`group_limited_greedy` over router scores [T, experts] (float32,
    softmax already taken): a group's score is its best expert's, the
    best `topk_group` groups stay, and the `num_experts_per_tok` largest
    scores among their experts are chosen. Returns (ids, weights), the
    weights `routed_scaling_factor` times the scores, not renormalised.
    Ties go to the lower index, among groups and among experts."""
    tokens = scores.shape[0]
    per_group = cfg.n_routed_experts // cfg.n_group
    group_scores = scores.reshape(tokens, cfg.n_group, per_group).max(axis=-1)
    _, groups = jax.lax.top_k(group_scores, cfg.topk_group)
    keep = jnp.zeros((tokens, cfg.n_group), bool).at[
        jnp.arange(tokens)[:, None], groups
    ].set(True)
    masked = jnp.where(jnp.repeat(keep, per_group, axis=1), scores, 0.0)
    weights, ids = jax.lax.top_k(masked, cfg.num_experts_per_tok)
    return ids, weights * cfg.routed_scaling_factor


def moe(cfg, p, x):
    """The expert layer of this chip over x [T, hidden] (`moe.expert_layer`
    under this model's routing rule: softmax scores, `route`). Returns
    (output, chosen ids [T, k], pairs on each held expert [held])."""
    return expert_layer(
        p, x, cfg.held_experts, lambda logits: route(cfg, jax.nn.softmax(logits, axis=-1)))


def _feed_forward(cfg, layer, block, x):
    """(output, ids or None, pairs per held expert or None)"""
    if cfg.is_dense(layer):
        return swiglu(x, block["mlp"]), None, None
    return moe(cfg, block["moe"], x)


def block_expanded(cfg, layer: int, block: dict, h, rope):
    """One pre-norm residual block over a whole sequence (the prefill's
    form). Returns (h, latents, ids, pairs per held expert)."""
    with jax.named_scope(cfg.layer_name(layer)):
        with jax.named_scope("mla"):
            attn, latents = mla_expanded(
                cfg, block["attn"], rms_norm(h, block["attn_norm"], cfg.rms_norm_eps), rope
            )
        h = h + attn
        out, ids, sizes = _feed_forward(
            cfg, layer, block, rms_norm(h, block["ffn_norm"], cfg.rms_norm_eps)
        )
        return h + out, latents, ids, sizes


# --- the two programs -----------------------------------------------------


class Prefill(NamedTuple):
    logits: jax.Array   # [vocab_held] float32, at the prompt's last position
    cache: jax.Array    # [layers, cache_len, kv_lora + rope]
    loads: jax.Array    # [moe layers, held] pairs on each held expert
    chosen: jax.Array | None  # [moe layers, T, k] experts chosen (the parity check reads it)


class Decode(NamedTuple):
    ids: jax.Array      # [steps]
    loads: jax.Array    # [moe layers, held], summed over the steps
    logits: jax.Array | None  # [steps, vocab_held] float32, after id i; under `collect`
    chosen: jax.Array | None  # [steps, moe layers, k]; under `collect`


@partial(jax.jit, static_argnames=("cfg", "cache_len", "collect"))
def prefill(cfg: DeepSeekV2Config, params, ids, *, cache_len: int, collect: bool = False):
    """The whole prompt `ids` [T] at once. Returns the logits at its last
    position [vocab_held] (float32), the latent cache [layers, cache_len,
    kv_lora + rope] with the first T positions written, the pairs that
    fell on each held expert [moe layers, held] and the experts chosen
    [moe layers, T, k], whatever `collect` (the parity check's): its
    prefill is the served program itself (PERF.md §6, PR 64)."""
    tokens = ids.shape[0]
    rope = rope_tables(cfg, jnp.arange(tokens))
    h = params["embed"][ids]
    cache = jnp.zeros((cfg.num_hidden_layers, cache_len, cfg.cache_width), h.dtype)
    chosen, loads = [], []
    for layer, block in enumerate(params["layers"]):
        h, latents, ids_l, sizes = block_expanded(cfg, layer, block, h, rope)
        cache = cache.at[layer, :tokens].set(latents)
        if ids_l is not None:
            chosen.append(ids_l)
            loads.append(sizes)
    return Prefill(
        head(cfg, params, h[-1:])[0], cache, jnp.stack(loads),
        jnp.stack(chosen),  # served too: one program, whatever `collect`
    )


def decode_step(cfg, params, cache, token, position):
    """One token through every layer over the latent cache (absorbed
    MLA). Returns (logits [vocab_held], cache, ids [moe layers, k],
    pairs per held expert [moe layers, held])."""
    valid = (jnp.arange(cache.shape[1]) <= position)[None, :]
    rope = rope_tables(cfg, position[None])
    h = params["embed"][token][None]
    chosen, loads = [], []
    for layer, block in enumerate(params["layers"]):
        with jax.named_scope(cfg.layer_name(layer)):
            with jax.named_scope("mla"):
                x = rms_norm(h, block["attn_norm"], cfg.rms_norm_eps)
                latent = mla.latents(block["attn"], x, rope, cfg.rms_norm_eps)
                cache = jax.lax.dynamic_update_slice(cache, latent[None], (layer, position, 0))
                h = h + mla_absorbed(cfg, block["attn"], x, rope, cache[layer], valid)
            out, ids_l, sizes = _feed_forward(
                cfg, layer, block, rms_norm(h, block["ffn_norm"], cfg.rms_norm_eps)
            )
            h = h + out
        if ids_l is not None:
            chosen.append(ids_l[0])
            loads.append(sizes)
    return head(cfg, params, h)[0], cache, jnp.stack(chosen), jnp.stack(loads)


@partial(jax.jit, static_argnames=("cfg", "steps", "collect"))
def decode(cfg: DeepSeekV2Config, params, cache, logits, start, key, temperature, *,
           steps: int, collect: bool = False):
    """`steps` dependent decode steps in one program, from the prefill's
    `logits` at position `start - 1`: draw id i from the logits, run it
    through the model at position `start + i`. Always `steps` ids, no
    early stop. Returns the ids [steps], the pairs on each held expert
    summed over the steps [moe layers, held] and, under `collect`, every
    step's logits [steps, vocab_held] (the logits after id i) and the
    experts chosen [steps, moe layers, k]."""

    def step(cache, token, position):
        logits, cache, chosen, loads = decode_step(cfg, params, cache, token, position)
        return logits, cache, loads, (logits, chosen) if collect else None

    _, ids, loads, kept = decode_loop(step, cache, logits, start, key, temperature, steps)
    return Decode(ids, loads, *(kept or (None, None)))


class DeepSeekV2(LanguageModel):
    """What a bundle's `lm` part is (the contract is in `lm_common`): what
    a node reads back and reports of this model's two programs."""

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
        cfg = self.cfg
        cache = cfg.num_hidden_layers * cache_len * cfg.cache_width * self.dtype.itemsize
        return {
            "layers": cfg.num_hidden_layers,
            "experts_held": len(cfg.held_experts),
            "experts_total": cfg.n_routed_experts,
            "cache_bytes": cache,
            "state_bytes": 0,
        }

    def report(self, prompt_tokens: int, new_tokens: int, cache_len: int,
               prefill_loads, decode_loads) -> dict:
        """`describe` and, per phase, the token-expert pairs the router
        made, those that fell on held experts, and the fullest held
        expert's."""
        cfg = self.cfg
        return {
            **self.describe(cache_len),
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
