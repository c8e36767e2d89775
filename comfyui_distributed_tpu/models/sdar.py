"""SDAR (`sdar_moe`): a Qwen3-MoE backbone that generates by masked
diffusion over blocks of `block_length` positions under a mask that is
causal over blocks and full inside one.

    h += attn(rms(h));  h += moe(rms(h))               (pre-norm)

Attention: q = W_q x (heads x d), k = W_k x, v = W_v x (key heads x d),
no bias; q and k each normed over a head's d channels under a learned
scale, then rotated (all d channels, `rotate_half`, theta `rope_theta`);
scores at d^-1/2; position i sees position j iff j's block is no later
than i's; key head j serves its group of query heads; y = W_o o. Expert
layer, every layer: softmax of the router's logits over all the experts
in float32, the `num_experts_per_tok` largest (ties to the lower index),
their probabilities over their sum (`norm_topk_prob`), each a SwiGLU of
`moe_intermediate_size` columns; no shared expert, no bias, no scaling
factor (`moe.expert_layer` over a tree without `shared`). The head is
untied; a logit at position i is of token i itself (no shift).

Generation is not in `config.json`; it is the family's published
`generate.py` (`block_diffusion_generate`), its values fields of the
configuration: blocks of `block_length`, `denoising_steps` passes a
block at most, remasking `low_confidence_dynamic` at
`confidence_threshold`, the mask's id `mask_token_id`
(`lm_common.denoise_loop` has the procedure, `transfer` the rule). The
prompt's whole blocks are run once (`prefill`) and their keys and values
kept; the tokens left over open the first block unmasked.

A request's state (`state_shapes`): `kv`, a leaf a layer `[2, key heads,
positions, d]`, positions rounded up to whole blocks, and `pending`
`[block]`, the prompt's left-over ids in its first entries (how many the
prompt's length says: `start` modulo the block).

Two things the served form does that the published procedure does not,
neither a change of the result. Every pass writes its block's keys and
values at the block's own cache entries and attends every entry below the
block's end, so a denoising pass's writes are written over by the next
pass's and the closing pass's are the ones that stand: one store path,
no second one for "keep" and "do not keep". And the closing pass stops at
the last layer's keys and values: it runs no head (nothing reads its
logits) and, of that layer, neither the queries nor the expert layer. One
departure, the reference's too: what
is masked is a boolean the loop carries and not `ids == mask_token_id`,
so a drawn id that happens to be the mask's is a token like any other
and a block always closes after `denoising_steps` passes at most.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..ops.attention import causal_attention
from ..ops.decode_attention import attend_xla, block_valid
from .lm_common import (
    LanguageModel,
    apply_rope,
    blocks_most,
    count_params,
    denoise_loop,
    head,
    init_from_shapes,
    nbytes,
    rms_norm,
    rope_tables,
    zeros,
)
from .moe import decode_route, expert_layer, prefill_route, report_loads

# Under `collect` the denoising passes' float32 logits are kept for about
# this many blocks, evenly spread (every eighth of the cell's 128: 160 MB
# where all 512 passes' would be 1.2 GB).
COLLECT_BLOCKS = 16


@dataclasses.dataclass(frozen=True)
class SdarConfig:
    """The published `config.json`'s shape keys under their own names,
    and the four values of the published generation script that the
    configuration states (`assumed` in the benchmark's file). Every
    expert and every id is held: a chip's share of a deployment is a run
    of whole layers."""

    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e6
    moe_intermediate_size: int = 768
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    vocab_size: int = 151936
    rms_norm_eps: float = 1e-6
    block_length: int = 4
    denoising_steps: int = 4
    confidence_threshold: float = 0.85
    mask_token_id: int = 151669

    def __post_init__(self):
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(f"mask id {self.mask_token_id} outside {self.vocab_size} ids")

    @property
    def held_experts(self) -> range:
        return range(self.num_experts)


# --- parameters -----------------------------------------------------------


def param_shapes(cfg: SdarConfig) -> dict[str, Any]:
    """The tree's shapes with each weight's fan-in (None: a norm's
    scale, initialised to one)."""
    h, d, width = cfg.hidden_size, cfg.head_dim, cfg.moe_intermediate_size
    heads, kv = cfg.num_attention_heads * d, cfg.num_key_value_heads * d
    layer = {
        "attn_norm": ((h,), None),
        "attn": {
            "w_q": ((h, heads), h), "w_k": ((h, kv), h), "w_v": ((h, kv), h),
            "q_norm": ((d,), None), "k_norm": ((d,), None),
            "w_o": ((heads, h), heads),
        },
        "moe_norm": ((h,), None),
        "moe": {
            "w_g": ((h, cfg.num_experts), h),
            "experts": {
                "w_gate_up": ((cfg.num_experts, h, 2 * width), h),
                "w_down": ((cfg.num_experts, width, h), width),
            },
        },
    }
    return {
        "embed": ((cfg.vocab_size, h), 1),
        "layers": [layer for _ in range(cfg.num_hidden_layers)],
        "final_norm": ((h,), None),
        "head": ((h, cfg.vocab_size), h),
    }


def param_count(cfg: SdarConfig) -> int:
    return count_params(param_shapes(cfg))


def init_params(cfg: SdarConfig, key, dtype=jnp.float32) -> dict[str, Any]:
    """Seeded random weights in `dtype` (`lm_common.init_from_shapes`)."""
    return init_from_shapes(param_shapes(cfg), key, dtype)


# --- a request's state ----------------------------------------------------


def positions_held(cfg: SdarConfig, cache_len: int) -> int:
    """Entries a layer's cache has for `cache_len` positions: whole blocks."""
    return -(-cache_len // cfg.block_length) * cfg.block_length


def state_shapes(cfg: SdarConfig, cache_len: int, dtype) -> dict:
    """The tree a request carries from its prefill through its decode."""
    kv = jax.ShapeDtypeStruct(
        (2, cfg.num_key_value_heads, positions_held(cfg, cache_len), cfg.head_dim), dtype)
    return {
        "kv": tuple(kv for _ in range(cfg.num_hidden_layers)),
        "pending": jax.ShapeDtypeStruct((cfg.block_length,), jnp.int32),
    }


# --- a layer, in either form ----------------------------------------------


def _keys_values(cfg, p, x, rope):
    """k and v [T, key heads, d] of x [T, hidden]: k normed a head, then
    rotated by `rope` (cos, sin)."""
    tokens, d = x.shape[0], cfg.head_dim
    k = rms_norm((x @ p["w_k"]).reshape(tokens, -1, d), p["k_norm"], cfg.rms_norm_eps)
    return apply_rope(k, *rope), (x @ p["w_v"]).reshape(tokens, -1, d)


def _entries(k, v):
    """Keys and values [T, key heads, d] as a cache holds them: [2, key
    heads, T, d]."""
    return jnp.stack([k, v]).transpose(0, 2, 1, 3)


def _queries(cfg, p, x, rope):
    """q [T, heads, d] of x [T, hidden]: normed a head, then rotated."""
    tokens, d = x.shape[0], cfg.head_dim
    q = rms_norm((x @ p["w_q"]).reshape(tokens, -1, d), p["q_norm"], cfg.rms_norm_eps)
    return apply_rope(q, *rope)


def attn_whole(cfg, p, x):
    """Over the prompt's whole blocks x [T, hidden] (the prefill's form),
    under the block mask. Returns (output, keys and values [2, key heads,
    T, d])."""
    rope = rope_tables(cfg.rope_theta, cfg.head_dim, jnp.arange(x.shape[0]))
    k, v = _keys_values(cfg, p, x, rope)
    out = causal_attention(
        _queries(cfg, p, x, rope)[None], k[None], v[None], block=cfg.block_length)[0]
    return out.reshape(x.shape[0], -1) @ p["w_o"], _entries(k, v)


def write_block(cfg, p, x, kv, position):
    """One block's keys and values, of x [block, hidden] at `position`
    .., written at the block's own entries of `kv` [2, key heads,
    positions, d]. Returns (the array written, the block's rope tables)."""
    rope = rope_tables(cfg.rope_theta, cfg.head_dim, position + jnp.arange(x.shape[0]))
    entries = _entries(*_keys_values(cfg, p, x, rope))
    return jax.lax.dynamic_update_slice(kv, entries, (0, 0, position, 0)), rope


def attn_block(cfg, p, x, kv, position):
    """One block x [block, hidden] at `position` .. : its keys and values
    written (`write_block`), then all of its queries over every entry
    below the block's end. Returns (output, the array written)."""
    kv, rope = write_block(cfg, p, x, kv, position)
    out = attend_xla(
        _queries(cfg, p, x, rope), kv[None], (0,), block_valid(position, kv.shape[2], x.shape[0]))
    return out.reshape(x.shape[0], -1) @ p["w_o"], kv


def softmax_route(cfg, logits):
    """The plain rule over float32 router logits [T, experts]: softmax
    over all of them, the k largest (ties to the lower index), their
    probabilities over their sum where `norm_topk_prob`."""
    weights, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        weights = weights / weights.sum(axis=-1, keepdims=True)
    return ids, weights


def _layer(cfg, block, h, attn):
    """One pre-norm residual layer; `attn(p, x)` returns (output, what it
    hands back: the keys and values, or the cache array it wrote).
    Returns (h, that, chosen ids, pairs per expert)."""
    with jax.named_scope("attn"):
        out, kept = attn(block["attn"], rms_norm(h, block["attn_norm"], cfg.rms_norm_eps))
    h = h + out
    with jax.named_scope("moe"):
        out, ids, sizes = expert_layer(
            block["moe"], rms_norm(h, block["moe_norm"], cfg.rms_norm_eps), cfg.held_experts,
            partial(softmax_route, cfg))
    return h + out, kept, ids, sizes


# --- the two programs -----------------------------------------------------


class Prefill(NamedTuple):
    logits: jax.Array   # [vocab] float32, at the last prefilled position (zeros: none)
    cache: dict         # `state_shapes`: the request's state after the prompt's whole blocks
    loads: jax.Array    # [layers, experts] pairs on each expert
    chosen: jax.Array | None  # [layers, P, k] experts chosen (the parity check reads it)


class Decode(NamedTuple):
    ids: jax.Array      # [steps]
    loads: jax.Array    # [layers, experts], summed over the passes
    counts: jax.Array   # [5] int32: `denoise_loop`'s four, then the experts the passes read
    cache: dict         # the state it was given, after the blocks
    kept: dict | None   # under `collect`: see `decode`


@partial(jax.jit, static_argnames=("cfg", "cache_len", "collect"))
def prefill(cfg: SdarConfig, params, ids, *, cache_len: int, collect: bool = False):
    """The prompt's whole blocks, `ids`' first P = T - T % block, at once
    under the block mask. Returns the logits at position P - 1 (of token
    P - 1 itself; the decode does not read them), the request's state
    (allocated here, once: each layer's first P entries written, the
    left-over ids pending), the pairs on each expert and the experts
    chosen, whatever `collect`: one program (`deepseek_v2.prefill`). A
    prompt shorter than one block runs no layer."""
    whole = ids.shape[0] - ids.shape[0] % cfg.block_length
    cache = zeros(state_shapes(cfg, cache_len, params["embed"].dtype))
    cache["pending"] = jnp.zeros_like(cache["pending"]).at[:ids.shape[0] - whole].set(ids[whole:])
    experts, k = cfg.num_experts, cfg.num_experts_per_tok
    if not whole:
        return Prefill(
            jnp.zeros((cfg.vocab_size,), jnp.float32), cache,
            jnp.zeros((cfg.num_hidden_layers, experts), jnp.int32),
            jnp.zeros((cfg.num_hidden_layers, 0, k), jnp.int32))  # whatever `collect`
    h = params["embed"][ids[:whole]]
    kv, chosen, loads = [], [], []
    for layer, (block, held) in enumerate(zip(params["layers"], cache["kv"])):
        with jax.named_scope(f"layer_{layer}"):
            h, entries, ids_l, sizes = _layer(cfg, block, h, partial(attn_whole, cfg))
        kv.append(jax.lax.dynamic_update_slice(held, entries, (0, 0, 0, 0)))
        chosen.append(ids_l)
        loads.append(sizes)
    cache["kv"] = tuple(kv)
    return Prefill(
        head(cfg, params, h[-1:])[0], cache, jnp.stack(loads),
        jnp.stack(chosen))  # served too: one program, whatever `collect`


def block_pass(cfg, params, cache, tokens, position, close: bool):
    """One block's tokens [block] at `position` .. through every layer
    over the request's state, each layer's entries of the block written
    first. A closing pass stops at the last layer's keys and values:
    nothing reads what follows them (that layer's queries, its expert
    layer, the head), so its rows of the two last results are zeros.
    Returns (logits [block, vocab] float32, or None for a closing pass;
    cache; ids [layers, block, k]; pairs per expert [layers, experts])."""
    h = params["embed"][tokens]
    kv, chosen, loads = [], [], []
    for layer, (block, held) in enumerate(zip(params["layers"], cache["kv"])):
        with jax.named_scope(f"layer_{layer}"):
            if close and layer == cfg.num_hidden_layers - 1:
                with jax.named_scope("attn"):
                    held, _ = write_block(
                        cfg, block["attn"], rms_norm(h, block["attn_norm"], cfg.rms_norm_eps),
                        held, position)
                ids_l, sizes = jnp.zeros_like(chosen[-1]), jnp.zeros_like(loads[-1])
            else:
                h, held, ids_l, sizes = _layer(
                    cfg, block, h, lambda p, x: attn_block(cfg, p, x, held, position))
        kv.append(held)
        chosen.append(ids_l)
        loads.append(sizes)
    cache = {**cache, "kv": tuple(kv)}
    return (None if close else head(cfg, params, h)), cache, jnp.stack(chosen), jnp.stack(loads)


def collect_stride(cfg: SdarConfig, steps: int) -> int:
    """Every how many blocks a collecting decode keeps its passes' logits."""
    return max(1, blocks_most(steps, cfg.block_length) // COLLECT_BLOCKS)


@partial(jax.jit, static_argnames=("cfg", "steps", "collect"), donate_argnames=("cache",))
def decode(cfg: SdarConfig, params, cache, logits, start, key, temperature, *,
           steps: int, collect: bool = False):
    """`steps` ids in one program by `lm_common.denoise_loop` over
    `block_pass`, from the block that holds position `start` (the
    prompt's length) on; no early stop, and no trip to the host: the
    passes a block takes are the loop's own affair. The prefill's
    `logits` are not read (a block's first pass sees the block itself).
    The state tree is donated, carried through the loops and handed
    back. Returns the ids, the pairs on each expert, `counts` and, under
    `collect`, what `denoise_loop` keeps of every pass (the block as the
    pass saw it, what was masked, drawn and kept, its position) and, of
    every `collect_stride`-th block's passes, the float32 `logits`
    [block, vocab] and the experts `chosen` [layers, block, k]."""
    del logits

    def step(cache, tokens, position, close):
        rows, cache, chosen, loads = block_pass(cfg, params, cache, tokens, position, close)
        kept = {"logits": rows, "chosen": chosen} if collect and not close else None
        return rows, cache, (loads, jnp.count_nonzero(loads)), kept

    cache = dict(cache)
    cache, ids, counts, (loads, read), kept = denoise_loop(
        step, cache, cache["pending"], start, key, temperature, steps, cfg.block_length,
        cfg.denoising_steps, cfg.confidence_threshold, cfg.mask_token_id,
        collect_stride(cfg, steps))
    return Decode(
        ids, loads, jnp.concatenate([counts, read.astype(jnp.int32)[None]]), cache, kept)


class Sdar(LanguageModel):
    """What a bundle's `lm` part is (the contract is in `lm_common`)."""

    _init = staticmethod(init_params)
    _prefill = staticmethod(prefill)
    _decode = staticmethod(decode)

    @property
    def layer_passes(self) -> int:
        return self.cfg.num_hidden_layers

    def read_back(self, prefill: Prefill, decode: Decode) -> tuple:
        """The pairs on each expert, of either program, and the decode's
        counts."""
        return prefill.loads, decode.loads, decode.counts

    def describe(self, cache_len: int) -> dict[str, int]:
        cfg, shapes = self.cfg, state_shapes(self.cfg, cache_len, self.dtype)
        return {
            "layers": cfg.num_hidden_layers,
            "block_length": cfg.block_length,
            "denoising_steps": cfg.denoising_steps,
            "experts_held": cfg.num_experts,
            "experts_total": cfg.num_experts,
            "cache_bytes": sum(nbytes(kv) for kv in shapes["kv"]),
            "state_bytes": 0,
        }

    def report(self, prompt_tokens: int, new_tokens: int, cache_len: int,
               prefill_loads, decode_loads, counts) -> dict:
        """`describe`, what the decode's passes came to (`decode_steps`
        counts every pass, the closing ones among them; the layer bodies
        and the token-expert pairs are counted over every position a pass
        ran through a whole layer: a closing pass stops at the last
        layer's keys and values) and, per phase, the routing as
        `moe.report_loads` has it. The prefill ran the prompt's whole
        blocks; the left-over tokens are the first block's."""
        cfg = self.cfg
        denoise, closing, by_threshold, by_floor, read = (int(n) for n in counts)
        whole = prompt_tokens - prompt_tokens % cfg.block_length
        # layer bodies the passes ran, a position each
        bodies = cfg.block_length * (
            denoise * cfg.num_hidden_layers + closing * (cfg.num_hidden_layers - 1))
        report = report_loads(
            cfg.num_experts_per_tok, cfg.num_experts, whole, new_tokens, prefill_loads,
            decode_loads, decode_route(
                cfg.block_length * cfg.num_experts_per_tok, cfg.hidden_size,
                cfg.moe_intermediate_size, self.dtype),
            prefill_expert_route=prefill_route(
                whole, cfg.num_experts_per_tok, len(cfg.held_experts), cfg.num_experts,
                cfg.hidden_size, cfg.moe_intermediate_size, self.dtype))
        pairs = bodies * cfg.num_experts_per_tok
        return {
            **self.describe(cache_len), **report,
            "decode_routed_pairs": pairs, "decode_expert_rows": pairs,
            "prefill_layer_passes": whole * cfg.num_hidden_layers,
            "decode_steps": denoise + closing,
            "denoise_passes": denoise, "closing_passes": closing,
            "transferred_by_threshold": by_threshold, "transferred_by_floor": by_floor,
            "decode_layer_passes": bodies,
            "decode_experts_read": read,
        }
