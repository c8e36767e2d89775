"""LongCat-Flash-Chat (`longcat_flash`): a layer of two latent attentions
and two dense feed-forwards with an expert layer on a shortcut. The
branch reads the first sublayer's normed input to its feed-forward and
is added after the second sublayer's feed-forward:

    h = x + A_0(n_0(x));  u = n_0'(h);  m = M(u);  h = h + F_0(u)
    h = h + A_1(n_1(h));  y = h + F_1(n_1'(h)) + m

Latent attention A_i (64 heads; `mla.py`'s two forms), x the normed input:

    c_q = rms(W_dq x; 1e-6);  [q_nope | q_rope] = s_q W_uq c_q,  s_q = (hidden / r_q)^1/2
    [c | k_r] = W_dkv x;  c' = s_kv rms(c; 1e-6),  s_kv = (hidden / r)^1/2
    q_rope, k_r rotated in pairs; the cache's row is [c' | rot(k_r)] (`mla.latents`)
    score_ij = (nope + rope)^-1/2 (q_nope_i . W_uk c'_j + q_rope_i . rot(k_r)_j),  j <= i

The expert layer M: a router `zero_expert_num` outputs wider than the
`n_routed_experts` experts; scores are the softmax over all of them, the
`moe_topk` largest of score + bias are chosen, the weights are
`routed_scaling_factor` times the chosen scores, not renormalised; an id
from `n_routed_experts` on is an identity (its weight times u, no
weights anywhere, `moe.expert_layer`'s `identities`); no shared expert.

A request's state is `latents`: one `[positions, rank + rope]` cache an
attention, two a layer (`state_shapes`), all of which grow.

The prefill is one program that reads the prompt in parts of
`prefill_part` positions (`lm_common.prefill_in_parts`): a part writes
its latents into the caches of full length and its queries attend, in
the expanded form, over the rows the parts before left and its own: one
`causal_attention` call a possible count of keys, of which the part runs
its own (`lax.switch`), the heads `attention_heads_a_call` at a time so
that only so many heads' rebuilt keys and values are alive at once. The
expert branch is a function of each token alone and runs over blocks of
`expert_block` tokens, so the ladder's top rung is a block's pairs and
not a part's.

The decode is `lm_common.decode_loop` over a one-token step:
`mla.absorbed` over each cache.

The chip holds the first `num_layers` layers, `expert_range(ep_rank,
ep_size)` of the experts and the first of `vocab_shards` slices of the
vocabulary; the identity experts belong to no chip's share.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.sharding import expert_range
from . import mla
from .lm_common import (
    LanguageModel,
    apply_rope_pairs,
    count_params,
    decode_loop,
    head,
    init_from_shapes,
    mlp_shapes,
    nbytes,
    parts_of,
    prefill_in_parts,
    rms_norm,
    rope_tables,
    swiglu,
    zeros,
)
from .moe import decode_route, expert_layer, prefill_route, report_loads, row_ladder, rung_index

# `LongcatFlashRMSNorm`'s default, which the two norms inside an attention
# keep (modeling_longcat_flash.py:312, 321); the layer norms take `rms_norm_eps`.
MLA_NORM_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class LongcatFlashConfig:
    """The published `config.json`'s shape keys under their own names,
    the chip's share of a deployment as `Dots3Config` states it, and
    what the prefill's temporaries are cut by: the positions a part
    takes, the tokens a block of the expert branch takes and the heads a
    causal call takes."""

    hidden_size: int = 6144
    num_layers: int = 28
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    rope_theta: float = 1e7
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    n_routed_experts: int = 512
    zero_expert_num: int = 256
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    vocab_size: int = 131072
    rms_norm_eps: float = 1e-5
    ep_size: int = 1
    ep_rank: int = 0
    vocab_shards: int = 1
    prefill_part: int = 8192
    expert_block: int = 1024
    attention_heads_a_call: int = 16

    def __post_init__(self):
        if self.num_attention_heads % self.attention_heads_a_call:
            raise ValueError(
                f"{self.num_attention_heads} heads in calls of {self.attention_heads_a_call}")

    @property
    def held_experts(self) -> range:
        return expert_range(self.n_routed_experts, self.ep_rank, self.ep_size)

    @property
    def router_width(self) -> int:
        return self.n_routed_experts + self.zero_expert_num

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
    def attention_sublayers(self) -> int:
        return 2 * self.num_layers

    @property
    def s_q(self) -> float:
        """What the queries are multiplied by."""
        return (self.hidden_size / self.q_lora_rank) ** 0.5 if self.mla_scale_q_lora else 1.0

    @property
    def s_kv(self) -> float:
        """And the normed latent."""
        return (self.hidden_size / self.kv_lora_rank) ** 0.5 if self.mla_scale_kv_lora else 1.0


# --- parameters -----------------------------------------------------------


def param_shapes(cfg: LongcatFlashConfig) -> dict[str, Any]:
    """The tree's shapes with each weight's fan-in (None: a norm's
    scale, initialised to one). A layer is its two sublayers (`sub`: a
    latent attention and a dense feed-forward each, with their norms)
    and the expert layer on the shortcut (`moe`)."""
    h, heads, rank = cfg.hidden_size, cfg.num_attention_heads, cfg.kv_lora_rank
    held, width = len(cfg.held_experts), cfg.expert_ffn_hidden_size

    def sublayer() -> dict:
        return {
            "attn_norm": ((h,), None),
            "attn": {
                "w_dq": ((h, cfg.q_lora_rank), h),
                "q_norm": ((cfg.q_lora_rank,), None),
                "w_uq": ((cfg.q_lora_rank, heads * cfg.qk_head_dim), cfg.q_lora_rank),
                "w_dkv": ((h, cfg.cache_width), h),
                "kv_norm": ((rank,), None),
                "w_uk": ((rank, heads, cfg.qk_nope_head_dim), rank),
                "w_uv": ((rank, heads, cfg.v_head_dim), rank),
                "w_o": ((heads * cfg.v_head_dim, h), heads * cfg.v_head_dim),
            },
            "ffn_norm": ((h,), None),
            "mlp": mlp_shapes(h, cfg.ffn_hidden_size),
        }

    def layer() -> dict:
        return {
            "sub": [sublayer(), sublayer()],
            "moe": {
                "w_g": ((h, cfg.router_width), h),
                "bias": ((cfg.router_width,), None),
                "experts": {
                    "w_gate_up": ((held, h, 2 * width), h),
                    "w_down": ((held, width, h), width),
                },
            },
        }

    return {
        "embed": ((cfg.vocab_held, h), 1),
        "layers": [layer() for _ in range(cfg.num_layers)],
        "final_norm": ((h,), None),
        "head": ((h, cfg.vocab_held), h),
    }


def param_count(cfg: LongcatFlashConfig) -> int:
    return count_params(param_shapes(cfg))


def init_params(cfg: LongcatFlashConfig, key, dtype=jnp.float32) -> dict[str, Any]:
    """Seeded random weights in `dtype` (`lm_common.init_from_shapes`);
    the routers' selection bias zero and float32, as the published code
    initialises `e_score_correction_bias`."""
    params = init_from_shapes(param_shapes(cfg), key, dtype)
    for block in params["layers"]:
        block["moe"]["bias"] = jnp.zeros_like(block["moe"]["bias"], jnp.float32)
    return params


def state_shapes(cfg: LongcatFlashConfig, cache_len: int, dtype) -> dict[str, Any]:
    """The tree a request carries from its prefill through its decode:
    a latent cache an attention, the layer's first then its second."""
    rows = jax.ShapeDtypeStruct((cache_len, cfg.cache_width), dtype)
    return {"latents": (rows,) * cfg.attention_sublayers}


# --- a layer ----------------------------------------------------------------


def _rows(cfg, p, x, cache, positions):
    """Of x [T, hidden] (normed) at `positions`, one after another: the
    heads' queries under the rescale ([T, heads, nope] and, rotated in
    pairs, [T, heads, rope]), the rows the cache holds of it, and
    `cache` with them written at their positions."""
    rope = rope_tables(cfg.rope_theta, cfg.qk_rope_head_dim, positions)
    c_q = rms_norm(x @ p["w_dq"], p["q_norm"], MLA_NORM_EPS)
    q = jnp.dot(c_q, p["w_uq"], preferred_element_type=jnp.float32) * cfg.s_q
    q = q.astype(x.dtype).reshape(x.shape[0], cfg.num_attention_heads, cfg.qk_head_dim)
    rows = mla.latents(p, x, rope, MLA_NORM_EPS, rotate=apply_rope_pairs, scale=cfg.s_kv)
    nope = cfg.qk_nope_head_dim
    cache = jax.lax.dynamic_update_slice(cache, rows, (positions[0], 0))
    return q[..., :nope], apply_rope_pairs(q[..., nope:], *rope), rows, cache


def attention_part(cfg, key_counts: tuple[int, ...], p, x, cache, positions):
    """A part of the prompt, x [P, hidden] (normed) at `positions`: its
    latents written into `cache` at their positions, its queries over the
    rows 0 .. `positions[0]` + P - 1, every key and value rebuilt from its
    latent. `key_counts` are the values `positions[0]` + P can take, in
    the order of `positions[0]` // P: a call of `mla.expanded` each (the
    heads `attention_heads_a_call` at a time), of which the part runs its
    own. Returns (output [P, hidden], cache)."""
    q_nope, q_rope, rows, cache = _rows(cfg, p, x, cache, positions)
    tokens, some = x.shape[0], cfg.attention_heads_a_call

    def over(count: int, q_nope, q_rope, rows, cache):
        before = cache[:count - tokens] if count > tokens else None

        def some_heads(operands):
            q_nope, q_rope, w_uk, w_uv = operands
            return mla.expanded(
                q_nope, q_rope, rows, w_uk, w_uv, cfg.qk_head_dim ** -0.5, before=before)

        operands = (q_nope, q_rope, p["w_uk"], p["w_uv"])
        if some == cfg.num_attention_heads:
            return some_heads(operands)
        # a loop and not a row of calls: the compiler would rebuild every group's keys
        # and values before the first call (compiled for a v5e: all of them alive at once)
        out = jax.lax.map(some_heads, tuple(
            jnp.moveaxis(a.reshape(a.shape[0], -1, some, a.shape[2]), 1, 0) for a in operands))
        return jnp.moveaxis(out, 0, 1).reshape(tokens, cfg.num_attention_heads, -1)

    calls = [partial(over, count) for count in key_counts]
    out = calls[0](q_nope, q_rope, rows, cache) if len(calls) == 1 else jax.lax.switch(
        positions[0] // tokens, calls, q_nope, q_rope, rows, cache)
    return out.reshape(tokens, -1) @ p["w_o"], cache


def attention_step(cfg, p, x, cache, positions):
    """A step's new token x [1, hidden] (normed) at `positions`: its
    latent written into `cache`, its query over the rows up to it in the
    absorbed form. Returns (output [1, hidden], cache)."""
    q_nope, q_rope, _, cache = _rows(cfg, p, x, cache, positions)
    valid = jnp.arange(cache.shape[0])[None, :] <= positions[:, None]
    out = mla.absorbed(
        q_nope, q_rope, cache, valid, p["w_uk"], p["w_uv"], cfg.qk_head_dim ** -0.5)
    return out.reshape(x.shape[0], -1) @ p["w_o"], cache


def route(cfg: LongcatFlashConfig, bias, logits):
    """Over float32 router logits [T, router width]: scores are their
    softmax over the whole width, the `moe_topk` largest of score + bias
    are chosen (ties to the lower index), the weights
    `routed_scaling_factor` times the chosen scores, without the bias
    and not renormalised. Returns (ids [T, k], weights [T, k])."""
    scores = jax.nn.softmax(logits, axis=-1)
    _, ids = jax.lax.top_k(scores + bias.astype(jnp.float32), cfg.moe_topk)
    return ids, jnp.take_along_axis(scores, ids, axis=-1) * cfg.routed_scaling_factor


def expert_blocks(cfg, tokens: int) -> list[int]:
    """The token counts of the blocks the expert branch cuts `tokens`
    into: whole blocks of `expert_block`, then what is left."""
    whole, left = divmod(tokens, cfg.expert_block)
    return [cfg.expert_block] * whole + [left] * bool(left)


def shortcut(cfg, p, u):
    """The expert branch M(u) over u [T, hidden], a block of
    `expert_block` tokens at a time (`expert_blocks`; the whole blocks
    one `lax.map` body). Returns (output [T, hidden], chosen ids [T, k],
    pairs on each held expert a block [blocks, held])."""
    def run(rows):
        return expert_layer(
            p, rows, cfg.held_experts, partial(route, cfg, p["bias"]),
            identities=cfg.n_routed_experts)

    tokens, size = u.shape[0], cfg.expert_block
    whole, left = divmod(tokens, size)
    if whole + bool(left) == 1:
        out, ids, sizes = run(u)
        return out, ids, sizes[None]
    outs = []
    if whole:
        done = jax.lax.map(run, u[:whole * size].reshape(whole, size, -1))
        outs.append((done[0].reshape(whole * size, -1), done[1].reshape(whole * size, -1), done[2]))
    if left:
        out, ids, sizes = run(u[whole * size:])
        outs.append((out, ids, sizes[None]))
    return tuple(jnp.concatenate(each) for each in zip(*outs))


def real_histogram(cfg, chosen):
    """[moe_topk + 1] int32 of chosen ids [..., k]: the (token, layer)
    pairs of which 0, 1, .., k chosen ids are experts with weights
    somewhere (the others identities)."""
    real = jnp.sum(chosen < cfg.n_routed_experts, axis=-1).reshape(-1)
    return jnp.zeros((cfg.moe_topk + 1,), jnp.int32).at[real].add(1)


def walk(cfg, params, caches: tuple, h, attend):
    """h [W, hidden] through every layer held. `attend(p, x, cache)` is
    an attention in the caller's form and returns (output, cache).
    Returns (h, caches, chosen ids [layers, W, k], pairs on each held
    expert a block [layers, blocks, held])."""
    caches, chosen, loads, eps = list(caches), [], [], cfg.rms_norm_eps
    for index, block in enumerate(params["layers"]):
        first, second = block["sub"]
        with jax.named_scope(f"layer_{index}"):
            with jax.named_scope("mla"):
                out, caches[2 * index] = attend(
                    first["attn"], rms_norm(h, first["attn_norm"], eps), caches[2 * index])
                h = h + out
            with jax.named_scope("mlp"):
                u = rms_norm(h, first["ffn_norm"], eps)
            with jax.named_scope("shortcut"):
                branch, ids, sizes = shortcut(cfg, block["moe"], u)
            with jax.named_scope("mlp"):
                h = h + swiglu(u, first["mlp"])
            with jax.named_scope("mla"):
                out, caches[2 * index + 1] = attend(
                    second["attn"], rms_norm(h, second["attn_norm"], eps), caches[2 * index + 1])
                h = h + out
            with jax.named_scope("mlp"):
                h = h + swiglu(rms_norm(h, second["ffn_norm"], eps), second["mlp"])
            with jax.named_scope("shortcut"):
                h = h + branch
        chosen.append(ids)
        loads.append(sizes)
    return h, tuple(caches), jnp.stack(chosen), jnp.stack(loads)


# --- the two programs -----------------------------------------------------


class Prefill(NamedTuple):
    logits: jax.Array   # [vocab_held] float32, at the prompt's last position
    cache: dict         # `state_shapes`: the request's state after the prompt
    loads: jax.Array    # [parts, layers, blocks, held] pairs on each held expert, a block
    real: jax.Array     # [parts, k + 1] (token, layer) pairs by their count of real experts
    chosen: jax.Array | None  # [layers, T, k] the ids chosen; under `collect`


class Decode(NamedTuple):
    ids: jax.Array      # [steps]
    loads: jax.Array    # [layers, held], summed over the steps
    read: jax.Array     # int32: held experts read, summed over the steps and layers
    real: jax.Array     # [k + 1], summed over the steps
    cache: dict         # the state it was given, after the steps
    kept: dict | None   # under `collect`: see `decode`


@partial(jax.jit, static_argnames=("cfg", "cache_len", "collect"))
def prefill(cfg: LongcatFlashConfig, params, ids, *, cache_len: int, collect: bool = False):
    """The prompt `ids` [T] in parts (`prefill_in_parts`): the whole parts
    one scanned body, what is left a body of its own, each over the
    caches as the parts before left them. Returns the logits at the last
    position, the request's state (the caches allocated here, once), a
    part's pairs on each held expert a block and its histogram of real
    experts a token and, under `collect` (the parity check's), the ids
    chosen [layers, T, k]."""
    state = zeros(state_shapes(cfg, cache_len, params["embed"].dtype))
    most = len(expert_blocks(cfg, min(cfg.prefill_part, ids.shape[0])))

    def part(state, cuts, start, ends):
        (tokens,) = cuts
        positions = start + jnp.arange(tokens.shape[0])
        h, caches, chosen, loads = walk(
            cfg, params, state["latents"], params["embed"][tokens],
            lambda p, x, cache: attention_part(cfg, ends, p, x, cache, positions))
        # a part's outputs have one shape: what is left over keeps its rows filled up to a part's
        loads = jnp.pad(loads, [(0, 0), (0, most - loads.shape[1]), (0, 0)])
        kept = jnp.pad(
            chosen, [(0, 0), (0, cfg.prefill_part - tokens.shape[0]), (0, 0)]) if collect else None
        return {"latents": caches}, (h[-1], loads, real_histogram(cfg, chosen), kept)

    state, (h, loads, real, kept) = prefill_in_parts(part, state, (ids,), cfg.prefill_part)
    if collect:  # [parts, layers, P, k] -> [layers, T, k]
        kept = jnp.moveaxis(kept, 0, 1).reshape(cfg.num_layers, -1, cfg.moe_topk)[:, :ids.shape[0]]
    return Prefill(head(cfg, params, h[-1:])[0], state, loads, real, kept)


def decode_step(cfg, params, cache, token, position):
    """One token at `position` through every layer over the request's
    caches. Returns (logits [vocab_held], cache, ids [layers, k], pairs
    per held expert [layers, held])."""
    positions = position + jnp.arange(1)
    h, caches, chosen, loads = walk(
        cfg, params, cache["latents"], params["embed"][token[None]],
        lambda p, x, rows: attention_step(cfg, p, x, rows, positions))
    return head(cfg, params, h)[0], {"latents": caches}, chosen[:, 0], loads[:, 0]


@partial(jax.jit, static_argnames=("cfg", "steps", "collect"), donate_argnames=("cache",))
def decode(cfg: LongcatFlashConfig, params, cache, logits, start, key, temperature, *,
           steps: int, collect: bool = False):
    """`steps` ids in one program, from the prefill's `logits` at
    position `start - 1`, one token a step (`decode_step`); no early
    stop. The state tree is donated, carried through the loop and handed
    back. Returns the ids, the pairs on each held expert, the held
    experts read, the histogram of real experts a token and, under
    `collect`, per step: the logits and the ids chosen."""

    def step(cache, token, position):
        row, cache, chosen, loads = decode_step(cfg, params, cache, token, position)
        kept = {"logits": row, "chosen": chosen} if collect else None
        tally = (loads, jnp.count_nonzero(loads).astype(jnp.int32), real_histogram(cfg, chosen))
        return row, cache, tally, kept

    cache, ids, (loads, read, real), kept = decode_loop(
        step, dict(cache), logits, start, key, temperature, steps)
    return Decode(ids, loads, read, real, cache, kept)


class LongcatFlash(LanguageModel):
    """What a bundle's `lm` part is (the contract is in `lm_common`)."""

    _init = staticmethod(init_params)
    _prefill = staticmethod(prefill)
    _decode = staticmethod(decode)

    @property
    def layer_passes(self) -> int:
        return self.cfg.num_layers

    def read_back(self, prefill: Prefill, decode: Decode) -> tuple:
        """The pairs on each held expert and the histogram of real
        experts a token, of either program, and the held experts the
        decode read."""
        return prefill.loads, prefill.real, decode.loads, decode.real, decode.read

    def describe(self, cache_len: int) -> dict[str, int]:
        cfg = self.cfg
        return {
            "layers": cfg.num_layers,
            "attention_sublayers": cfg.attention_sublayers,
            "prefill_part": cfg.prefill_part,
            "expert_block": cfg.expert_block,
            "experts_held": len(cfg.held_experts),
            "experts_total": cfg.n_routed_experts,
            "zero_experts": cfg.zero_expert_num,
            "cache_bytes": sum(
                nbytes(leaf) for leaf in state_shapes(cfg, cache_len, self.dtype)["latents"]),
            "state_bytes": 0,
        }

    def report(self, prompt_tokens: int, new_tokens: int, cache_len: int,
               prefill_loads, prefill_real, decode_loads, decode_real, read) -> dict:
        """`describe` and, per phase, the routing as `moe.report_loads`
        has it (the router's width for `experts`; the prefill's ladder
        read a block of a part) with the pairs that chose an identity,
        and over the whole request the real experts a token and layer:
        their mean and the least and the most any token drew."""
        cfg, k = self.cfg, self.cfg.moe_topk
        prefill_loads, counts = np.asarray(prefill_loads), np.arange(k + 1)
        real = (np.asarray(prefill_real, np.int64).sum(axis=0), np.asarray(decode_real, np.int64))
        both, zero = real[0] + real[1], tuple(int(np.sum((k - counts) * r)) for r in real)
        routing = report_loads(
            k, cfg.router_width, prompt_tokens, new_tokens, np.sum(prefill_loads, axis=(0, 2)),
            decode_loads,
            decode_route(k, cfg.hidden_size, cfg.expert_ffn_hidden_size, self.dtype), zero,
            prefill_expert_route=prefill_route(
                min(prompt_tokens, cfg.expert_block), k, len(cfg.held_experts), cfg.router_width,
                cfg.hidden_size, cfg.expert_ffn_hidden_size, self.dtype))
        whole, left = parts_of(prompt_tokens, cfg.prefill_part)
        rows = 0
        lengths = [cfg.prefill_part] * whole + [left] * bool(left)
        for length, by_layer in zip(lengths, prefill_loads):
            for at, tokens in enumerate(expert_blocks(cfg, length)):
                ladder = row_ladder(tokens * k, len(cfg.held_experts), cfg.router_width)
                rows += sum(ladder[rung_index(ladder, int(n))] for n in np.sum(
                    by_layer[:, at], axis=-1))
        routing["prefill_expert_rows"] = rows
        drew = np.flatnonzero(both)
        return {
            **self.describe(cache_len),
            **routing,
            "prefill_parts": len(lengths),
            "decode_experts_read": int(read),
            "real_experts_per_token_mean": float(np.sum(counts * both) / max(np.sum(both), 1)),
            "real_experts_per_token_min": int(drew[0]) if drew.size else 0,
            "real_experts_per_token_max": int(drew[-1]) if drew.size else 0,
        }
