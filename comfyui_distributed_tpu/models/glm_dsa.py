"""GLM-5.2 (`glm_moe_dsa`): multi-head latent attention that reads only
the `index_topk` positions a learned indexer picks for each query
(`models/dsa.py`), the index computed in some layers (`full`) and shared
by the layers after them (`shared`); the first feed-forward parts dense,
the others a mixture of routed experts beside one shared expert; and one
multi-token-prediction (MTP) module that drafts for a self-speculative
decode.

    h += attn(rms(h));  h += ffn(rms(h))

Attention, x the normed input: a query latent c_q = rms(W_dq x), queries
[q_nope | q_rope] = W_uq c_q with q_rope rotated in pairs (2i, 2i + 1);
the cache's row [rms(c_kv) | rot(k_r)] of W_dkv x (`mla.latents`); in a
`full` layer the indexer's key of x goes into a second cache and its
queries (of c_q) and weights (of x) score every visible position, of
which the best `index_topk` are the query's selection S_t (`dsa.select`);
a `shared` layer takes S_t as the nearest `full` layer below computed it
in this pass, and holds no indexer. Scores (q_nope . W_uk c_s + q_rope .
r_s) / sqrt(qk_head_dim), softmax over s in S_t, values W_uv c_s, W_o.

A request's state is a tree (`state_shapes`): `latents`, one `[positions,
kv_lora_rank + rope]` array a layer and the MTP module's last; `index`,
one `[positions, index_head_dim]` array a `full` layer and the module's
last: two caches that grow, of different widths; and `h` [hidden], the
residual stream of the last position the MTP module has not seen yet.

The prefill is one program that reads the prompt in parts of
`prefill_part` positions: a part goes through every layer, its latents
and indexer keys written into the caches at its positions, its queries
over the caches as the parts before left them, so the indexer's scores,
the expert ladder and the activations are a part's and not the prompt's.
The whole parts are one scanned body over caches of full length; what
is left of the prompt is a body of its own (`lm_common.prefill_in_parts`).

The decode is K-EXAONE's: `draft_tokens` 0, `steps` one-token steps; 1,
`lm_common.draft_loop`, whose step drafts with the module (its own indexer over its
own cache), runs the last token and the draft through the main model as
two positions, each with its own S_t, and emits one or two tokens by
`lm_common.verify`. What a dropped draft wrote in either cache the next
step writes over before anything reads it.

The chip holds the layers from `first_layer` on, read at their published
index, `expert_range(ep_rank, ep_size)` of the experts and the first of
`vocab_shards` slices of the vocabulary.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.sharding import expert_range
from . import dsa, mla
from .lm_common import (
    LanguageModel,
    apply_rope_pairs,
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
    parts_of,
    prefill_in_parts,
    rms_norm,
    rope_tables,
    swiglu,
    zeros,
)
from .moe import decode_route, expert_layer, prefill_route, report_loads, sigmoid_route


@dataclasses.dataclass(frozen=True)
class GlmDsaConfig:
    """The published `config.json`'s shape keys under their own names
    (`rope_theta` is its `rope_parameters` block's; `indexer_types` is
    what `index_topk_freq` and `index_skip_topk_offset` give), the chip's
    share of a deployment as `KExaoneConfig` states it, the first layer
    held (`first_layer`) and the positions a part of the prefill takes."""

    hidden_size: int = 6144
    num_hidden_layers: int = 78
    first_layer: int = 0
    first_k_dense_replace: int = 3
    num_attention_heads: int = 64
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 8e6
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    index_topk_freq: int = 4
    index_skip_topk_offset: int = 3
    intermediate_size: int = 12288
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    num_nextn_predict_layers: int = 1
    vocab_size: int = 154880
    rms_norm_eps: float = 1e-5
    ep_size: int = 1
    ep_rank: int = 0
    vocab_shards: int = 1
    prefill_part: int = 8192

    def __post_init__(self):
        if self.num_nextn_predict_layers != 1:
            raise ValueError("only the published form is written: one MTP module")
        if not self.is_full(self.first_layer):
            raise ValueError(
                f"layer {self.first_layer} attends by the index of a layer that is not held")

    @property
    def layers(self) -> range:
        """The published indices of the layers held."""
        return range(self.first_layer, self.first_layer + self.num_hidden_layers)

    @property
    def held_experts(self) -> range:
        return expert_range(self.n_routed_experts, self.ep_rank, self.ep_size)

    @property
    def vocab_held(self) -> int:
        return self.vocab_size // self.vocab_shards

    def is_full(self, layer: int) -> bool:
        """Whether the layer computes an index (`indexer_types` "full":
        the first `index_skip_topk_offset` layers, then every
        `index_topk_freq`-th) or attends by the one below ("shared")."""
        after = layer - self.index_skip_topk_offset + 1
        return after <= 0 or after % self.index_topk_freq == 0

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_k_dense_replace

    @property
    def full_layers(self) -> int:
        """Those of the main model held; the MTP module's is one more."""
        return sum(self.is_full(layer) for layer in self.layers)

    @property
    def sparse_layers(self) -> int:
        return sum(not self.is_dense(layer) for layer in self.layers)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def cache_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim


# --- parameters -----------------------------------------------------------


def param_shapes(cfg: GlmDsaConfig) -> dict[str, Any]:
    """The tree's shapes with each weight's fan-in (None: a norm's
    scale, initialised to one)."""
    h, heads, rank = cfg.hidden_size, cfg.num_attention_heads, cfg.kv_lora_rank
    held = len(cfg.held_experts)

    def layer(dense: bool, full: bool) -> dict:
        width = cfg.moe_intermediate_size
        block: dict[str, Any] = {
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
        }
        if full:
            d = cfg.index_head_dim
            block["indexer"] = {
                "w_q": ((cfg.q_lora_rank, cfg.index_n_heads * d), cfg.q_lora_rank),
                "w_k": ((h, d), h),
                "k_scale": ((d,), None),
                "k_bias": ((d,), d),
                "w_w": ((h, cfg.index_n_heads), h),
            }
        if dense:
            block["mlp"] = mlp_shapes(h, cfg.intermediate_size)
        else:
            block["moe"] = {
                "w_g": ((h, cfg.n_routed_experts), h),
                "bias": ((cfg.n_routed_experts,), None),
                "experts": {
                    "w_gate_up": ((held, h, 2 * width), h),
                    "w_down": ((held, width, h), width),
                },
                "shared": mlp_shapes(h, width * cfg.n_shared_experts),
            }
        return block

    return {
        "embed": ((cfg.vocab_held, h), 1),
        "layers": [layer(cfg.is_dense(i), cfg.is_full(i)) for i in cfg.layers],
        "final_norm": ((h,), None),
        "head": ((h, cfg.vocab_held), h),
        "mtp": {
            "embed_norm": ((h,), None), "hidden_norm": ((h,), None),
            "w_eh": ((2 * h, h), 2 * h),
            # the module's layer is the 79th of the pattern, `full`: it reads its own index
            "layer": layer(False, True),
            "norm": ((h,), None),
        },
    }


def param_count(cfg: GlmDsaConfig) -> int:
    return count_params(param_shapes(cfg))


def init_params(cfg: GlmDsaConfig, key, dtype=jnp.float32) -> dict[str, Any]:
    """Seeded random weights in `dtype` (`lm_common.init_from_shapes`);
    the routers' selection bias zero and float32."""
    params = init_from_shapes(param_shapes(cfg), key, dtype)
    for block in (*params["layers"], params["mtp"]["layer"]):
        if "moe" in block:
            block["moe"]["bias"] = jnp.zeros_like(block["moe"]["bias"], jnp.float32)
    return params


# --- a request's state ----------------------------------------------------


def state_shapes(cfg: GlmDsaConfig, cache_len: int, dtype) -> dict[str, Any]:
    """The tree a request carries from its prefill through its decode: a
    leaf a layer in either cache, the MTP module's the last of each."""
    def rows(width):
        return jax.ShapeDtypeStruct((cache_len, width), dtype)

    return {
        "latents": (rows(cfg.cache_width),) * (cfg.num_hidden_layers + 1),
        "index": (rows(cfg.index_head_dim),) * (cfg.full_layers + 1),
        "h": jax.ShapeDtypeStruct((cfg.hidden_size,), dtype),
    }


def _index_slot(cfg, layer: int) -> int:
    """A `full` layer's place among the held layers that have an indexer."""
    return sum(cfg.is_full(i) for i in range(cfg.first_layer, layer))


def _put(leaves: tuple, slot: int, rows, position):
    """`rows` written into leaf `slot` from `position` on."""
    return tuple(
        jax.lax.dynamic_update_slice(leaf, rows, (position, 0)) if at == slot else leaf
        for at, leaf in enumerate(leaves))


# --- a layer ----------------------------------------------------------------


def _rope(cfg, positions):
    return rope_tables(cfg.rope_theta, cfg.qk_rope_head_dim, positions)


def _queries(cfg, p, x, rope):
    """The query latent [T, q_lora_rank] of x [T, hidden], and the heads'
    queries of it: [T, heads, nope] and, rotated in pairs, [T, heads, rope]."""
    c_q = rms_norm(x @ p["w_dq"], p["q_norm"], cfg.rms_norm_eps)
    q = (c_q @ p["w_uq"]).reshape(x.shape[0], cfg.num_attention_heads, cfg.qk_head_dim)
    q_nope, q_rope = q[..., : cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim:]
    return c_q, q_nope, apply_rope_pairs(q_rope, *rope)


def attention(cfg, block, x, cache, slot: int, index_slot: int, positions, selection):
    """x [W, hidden] (normed) at `positions` [W], one after another: the
    latents written into leaf `slot` of the latent cache; where the layer
    has an indexer its keys written into leaf `index_slot` of the
    indexer's and each query's selection made over it, else `selection`
    (a `dsa.Selection`) as it was handed in; then each query over its
    chosen rows of the latent cache. Returns (output
    [W, hidden], cache, the selection it attended by)."""
    p, eps = block["attn"], cfg.rms_norm_eps
    rope = _rope(cfg, positions)
    with jax.named_scope("mla"):
        c_q, q_nope, q_rope = _queries(cfg, p, x, rope)
        rows = mla.latents(p, x, rope, eps, rotate=apply_rope_pairs)
        cache = {**cache, "latents": _put(cache["latents"], slot, rows, positions[0])}
    if "indexer" in block:
        with jax.named_scope("indexer"):
            cache["index"] = _put(
                cache["index"], index_slot, dsa.keys(block["indexer"], x, rope, eps),
                positions[0])
            q_index, weights = dsa.queries(block["indexer"], c_q, x, rope, cfg.index_n_heads)
            selection = dsa.select(
                q_index, weights, cache["index"][index_slot], positions, cfg.index_topk)
    with jax.named_scope("mla"):
        out = dsa.attend(
            q_nope, q_rope, cache["latents"][slot], selection, p["w_uk"], p["w_uv"],
            cfg.qk_head_dim ** -0.5)
        return out.reshape(x.shape[0], -1) @ p["w_o"], cache, selection


def _feed_forward(cfg, block, x):
    """(output, chosen ids [T, k] or None, pairs per held expert or None)"""
    if "mlp" in block:
        with jax.named_scope("dense"):
            return swiglu(x, block["mlp"]), None, None
    p = block["moe"]
    route = partial(
        sigmoid_route, bias=p["bias"], k=cfg.num_experts_per_tok,
        scale=cfg.routed_scaling_factor, renormalise=cfg.norm_topk_prob)
    return expert_layer(p, x, cfg.held_experts, route)


def _layer(cfg, block, h, cache, slot, index_slot, positions, selection):
    """One pre-norm residual layer over the request's state. Returns (h,
    cache, selection, chosen ids, pairs per held expert)."""
    out, cache, selection = attention(
        cfg, block, rms_norm(h, block["attn_norm"], cfg.rms_norm_eps), cache, slot,
        index_slot, positions, selection)
    h = h + out
    out, ids, sizes = _feed_forward(
        cfg, block, rms_norm(h, block["ffn_norm"], cfg.rms_norm_eps))
    return h + out, cache, selection, ids, sizes


def _keys_seen(positions, selections):
    """[2, layers] int32: over a walk's positions, the keys each of its
    attention layers' queries could see (t + 1 each) and those they
    read (|S_t| each), a column a layer."""
    visible = jnp.sum(positions + 1).astype(jnp.int32)
    return jnp.stack([
        jnp.stack([visible] * len(selections)),
        jnp.stack([jnp.count_nonzero(s.counts).astype(jnp.int32) for s in selections]),
    ])


def _kept(cfg, cache, selections):
    """Selections as `collect` keeps them: (positions, which count) each."""
    most = min(cfg.index_topk, cache["latents"][0].shape[0])
    return tuple(dsa.as_positions(selection, most) for selection in selections)


def walk(cfg, params, cache, h, positions):
    """h [W, hidden] at `positions` through every main layer held, a
    `full` layer's selection handed to the `shared` layers after it.
    Returns (h, cache, chosen ids [sparse layers, W, k], pairs per held
    expert [sparse layers, held], the selections of the `full` layers
    (a tuple of (positions, which count)), keys seen [2, layers])."""
    chosen, loads, selections, every = [], [], [], []
    selection = None
    for slot, (layer, block) in enumerate(zip(cfg.layers, params["layers"])):
        with jax.named_scope(f"layer_{layer}"):
            h, cache, selection, ids_l, sizes = _layer(
                cfg, block, h, cache, slot, _index_slot(cfg, layer), positions, selection)
        every.append(selection)
        if "indexer" in block:
            selections.append(selection)
        if ids_l is not None:
            chosen.append(ids_l)
            loads.append(sizes)
    return (h, cache, jnp.stack(chosen), jnp.stack(loads), tuple(selections),
            _keys_seen(positions, every))


# --- the two programs -----------------------------------------------------


class Prefill(NamedTuple):
    logits: jax.Array   # [vocab_held] float32, at the prompt's last position
    cache: dict         # `state_shapes`: the request's state after the prompt
    loads: jax.Array    # [parts, sparse layers, held] pairs on each held expert, a part
    keys: jax.Array     # [parts, 2, layers] keys visible and keys read, a part and layer
    kept: dict | None   # under `collect`: see `prefill`


class Decode(NamedTuple):
    ids: jax.Array      # [steps]
    loads: jax.Array    # [sparse layers + 1, held], summed over the steps; the MTP's last
    counts: jax.Array   # [4] int32: steps taken, drafts made, drafts kept, held experts read
    keys: jax.Array     # [2, layers + 1] keys visible and keys read; the MTP's last
    cache: dict         # the state it was given, after the steps
    kept: dict | None   # under `collect`: see `decode`


def _part(cfg, params, cache, ids, after, start, collect: bool):
    """One part of the prompt, `ids` [P] from position `start`, `after`
    [P] the tokens that follow each, through every layer over the
    caches. Of the MTP module the prompt needs what the decode will read
    of it, its latents and indexer keys, so that is what runs:
    `mtp_input`, the norm, `mla.latents`, `dsa.keys`. Returns (cache,
    (last residual stream, loads, keys seen, what `collect` keeps))."""
    positions = start + jnp.arange(ids.shape[0])
    h, cache, chosen, loads, selections, keys = walk(
        cfg, params, cache, params["embed"][ids], positions)
    with jax.named_scope("mtp"):
        block, rope = params["mtp"]["layer"], _rope(cfg, positions)
        x = rms_norm(mtp_input(cfg, params, h, after), block["attn_norm"], cfg.rms_norm_eps)
        with jax.named_scope("mla"):
            rows = mla.latents(block["attn"], x, rope, cfg.rms_norm_eps, rotate=apply_rope_pairs)
            cache["latents"] = _put(cache["latents"], cfg.num_hidden_layers, rows, start)
        with jax.named_scope("indexer"):
            cache["index"] = _put(
                cache["index"], cfg.full_layers,
                dsa.keys(block["indexer"], x, rope, cfg.rms_norm_eps), start)
    kept = {"chosen": chosen, "selections": _kept(cfg, cache, selections)} if collect else None
    return cache, (h[-1], loads, keys, kept)


def _rows_in_order(parts):
    """[parts, ..., P, k] -> [..., parts x P, k]: the parts' rows one after another."""
    return jnp.moveaxis(parts, 0, -3).reshape(*parts.shape[1:-2], -1, parts.shape[-1])


@partial(jax.jit, static_argnames=("cfg", "cache_len", "collect"))
def prefill(cfg: GlmDsaConfig, params, ids, *, cache_len: int, collect: bool = False):
    """The prompt `ids` [T] in parts (`prefill_in_parts`): the whole parts
    one scanned body, what is left a body of its own, each over the
    caches as the parts before left them. Returns the logits at the last
    position, the request's state (allocated here, once), a part's pairs
    on each held expert and keys seen and, under `collect` (the parity
    check's), `kept`: `chosen` [sparse layers, T, k] the experts chosen
    and `selections`, a `full` layer's (positions [T, k], which count).

    Position T - 1 of the MTP module's caches has no next token yet and
    is written from token 0; the decode's first step writes it again
    before anything reads it."""
    after = jnp.concatenate([ids[1:], jnp.zeros((1,), ids.dtype)])
    cache = zeros(state_shapes(cfg, cache_len, params["embed"].dtype))

    def part(cache, cuts, start, ends):
        cache, (*out, kept) = _part(cfg, params, cache, *cuts, start, collect)
        # a part's outputs have one shape: what is left over keeps its rows filled up to a part's
        fill = [(0, 0), (0, cfg.prefill_part - cuts[0].shape[0]), (0, 0)]
        return cache, (*out, jax.tree_util.tree_map(lambda a: jnp.pad(a, fill[-a.ndim:]), kept))

    cache, (h, loads, keys, kept) = prefill_in_parts(part, cache, (ids, after), cfg.prefill_part)
    kept = jax.tree_util.tree_map(lambda a: _rows_in_order(a)[..., :ids.shape[0], :], kept)
    cache["h"] = h[-1]
    return Prefill(head(cfg, params, h[-1:])[0], cache, loads, keys, kept)


def main_step(cfg, params, cache, tokens, position):
    """W tokens [W] at `position`, `position` + 1, ... through every
    main layer over the request's state, each position with its own
    selection. Returns (logits [W, vocab_held], the residual streams [W,
    hidden], cache, ids [sparse layers, W, k], pairs per held expert,
    the `full` layers' selections (`dsa.Selection`s), keys seen [2,
    layers])."""
    positions = position + jnp.arange(tokens.shape[0])
    h, cache, chosen, loads, selections, keys = walk(
        cfg, params, cache, params["embed"][tokens], positions)
    return head(cfg, params, h), h, cache, chosen, loads, selections, keys


def mtp_step(cfg, params, cache, h, tokens, position):
    """The MTP module over W confirmed positions from `position`: their
    residual streams h [W, hidden] and the tokens that follow them [W];
    its own indexer over its own cache. Returns (draft logits [W,
    vocab_held], cache, pairs per held expert [held], its selection,
    keys seen [2, 1])."""
    positions = position + jnp.arange(tokens.shape[0])
    out, cache, selection, _, sizes = _layer(
        cfg, params["mtp"]["layer"], mtp_input(cfg, params, h, tokens), cache,
        cfg.num_hidden_layers, cfg.full_layers, positions, None)
    return (head(cfg, params, out, params["mtp"]["norm"]), cache, sizes, selection,
            _keys_seen(positions, [selection]))


def _decode_plain(cfg, params, cache, logits, start, key, temperature, steps, collect):
    """`steps` one-token steps, as the other models' decodes."""

    def step(cache, token, position):
        rows, _, cache, chosen, loads, selections, keys = main_step(
            cfg, params, cache, token[None], position)
        kept = {"logits": rows[0], "chosen": chosen[:, 0],
                "selections": jax.tree_util.tree_map(
                    lambda a: a[0], _kept(cfg, cache, selections)),
                } if collect else None
        return rows[0], cache, (loads, jnp.count_nonzero(loads), keys), kept

    cache, ids, (loads, read, keys), kept = decode_loop(
        step, cache, logits, start, key, temperature, steps)
    loads = jnp.concatenate([loads, jnp.zeros_like(loads[:1])])  # the MTP module's row
    keys = jnp.concatenate([keys, jnp.zeros_like(keys[:, :1])], axis=1)
    counts = jnp.stack([jnp.int32(steps), jnp.int32(0), jnp.int32(0), read.astype(jnp.int32)])
    return Decode(ids, loads, counts, keys, cache, kept)


def _decode_drafting(cfg, params, cache, logits, start, key, temperature, steps, collect):
    """The self-speculative loop (`lm_common.draft_loop`) over this
    model's two steps, which also say the keys they saw and, under
    `collect`, their selections (of the module's the loop keeps the row
    the draft was drawn from); nothing of the state waits on a draft's
    fate."""

    def drafted(cache, h, tokens, position):
        rows, cache, loads, selection, keys = mtp_step(cfg, params, cache, h, tokens, position)
        kept = {"draft_selection": _kept(cfg, cache, [selection])[0]} if collect else None
        return rows, None, cache, (loads, jnp.count_nonzero(loads), keys), kept

    def verified(cache, tokens, position):
        rows, h, cache, chosen, loads, selections, keys = main_step(
            cfg, params, cache, tokens, position)
        kept = {"chosen": chosen, "selections": _kept(cfg, cache, selections)} if collect else None
        return rows, h, cache, (loads, jnp.count_nonzero(loads), keys), kept

    cache, ids, counts, ((loads_mtp, read_mtp, keys_mtp), (loads, read, keys)), kept = draft_loop(
        drafted, verified, cache, logits, start, key, temperature, steps)
    loads = jnp.concatenate([loads, loads_mtp[None]])  # the MTP module's row
    keys = jnp.concatenate([keys, keys_mtp], axis=1)
    counts = jnp.concatenate([counts, (read + read_mtp).astype(jnp.int32)[None]])
    return Decode(ids, loads, counts, keys, cache, kept)


@partial(jax.jit, static_argnames=("cfg", "steps", "collect", "draft_tokens"),
         donate_argnames=("cache",))
def decode(cfg: GlmDsaConfig, params, cache, logits, start, key, temperature, *,
           steps: int, collect: bool = False, draft_tokens: int = 0):
    """`steps` ids in one program, from the prefill's `logits` at
    position `start - 1`; no early stop. With `draft_tokens` 0 that is
    `steps` one-token steps; with 1 the self-speculative loop, on
    the device with no trip to the host. The state tree is donated,
    carried through the loop and handed back. Returns the ids, the pairs
    on each held expert, `counts`, the keys seen and, under `collect`, per
    step: the main model's logits, the experts chosen, the `full`
    layers' selections and, when drafting, the logits each draft was
    drawn from with the module's selection for it, the step's position n
    and whether its draft was kept (the logits' row 0 is position n's,
    row 1 the draft's at n + 1)."""
    run = _decode_drafting if drafts(draft_tokens) else _decode_plain
    return run(cfg, params, dict(cache), logits, start, key, temperature, steps, collect)


class GlmDsa(LanguageModel):
    """What a bundle's `lm` part is (the contract is in `lm_common`)."""

    _init = staticmethod(init_params)
    _prefill = staticmethod(prefill)
    _decode = staticmethod(decode)
    draft_tokens_max = 1

    @property
    def layer_passes(self) -> int:
        return self.cfg.num_hidden_layers

    def read_back(self, prefill: Prefill, decode: Decode) -> tuple:
        """The pairs on each held expert and the keys seen, of either
        program, and the decode's counts."""
        return prefill.loads, prefill.keys, decode.loads, decode.keys, decode.counts

    def describe(self, cache_len: int) -> dict[str, int]:
        cfg, shapes = self.cfg, state_shapes(self.cfg, cache_len, self.dtype)
        index = sum(nbytes(leaf) for leaf in shapes["index"])
        return {
            "layers": cfg.num_hidden_layers,
            "index_topk": cfg.index_topk,
            "indexer_layers": cfg.full_layers,
            "index_shared_layers": cfg.num_hidden_layers - cfg.full_layers,
            "prefill_part": cfg.prefill_part,
            "experts_held": len(cfg.held_experts),
            "experts_total": cfg.n_routed_experts,
            "cache_bytes": sum(nbytes(leaf) for leaf in shapes["latents"]) + index,
            "indexer_cache_bytes": index,
            "state_bytes": 0,
        }

    def report(self, prompt_tokens: int, new_tokens: int, cache_len: int,
               prefill_loads, prefill_keys, decode_loads, decode_keys, counts) -> dict:
        """`describe`, what the decode's steps came to, the layer bodies
        either program ran (the decode's over every position a step ran,
        a rejected draft's and the MTP module's among them; of the module
        the prefill runs only what its caches hold, no body), the keys the
        attention layers' queries could see and those they read, summed
        over both programs as the device counted them, and, per phase,
        the routing as `moe.report_loads` has it, the prefill's ladder
        read a part."""
        cfg = self.cfg
        width, drafting = drafting_report(
            counts, cfg.num_experts_per_tok, cfg.sparse_layers, cfg.num_hidden_layers)
        whole, left = parts_of(prompt_tokens, cfg.prefill_part)
        lengths = [cfg.prefill_part] * whole + [left] * bool(left)
        by_part = [
            report_loads(
                cfg.num_experts_per_tok, cfg.n_routed_experts, length, new_tokens, loads,
                decode_loads,
                # a step's positions in a main layer; the module's one takes the same route
                decode_route(
                    width * cfg.num_experts_per_tok, cfg.hidden_size,
                    cfg.moe_intermediate_size, self.dtype),
                prefill_expert_route=prefill_route(
                    length, cfg.num_experts_per_tok, len(cfg.held_experts), cfg.n_routed_experts,
                    cfg.hidden_size, cfg.moe_intermediate_size, self.dtype))
            for length, loads in zip(lengths, np.asarray(prefill_loads))]
        routing = {**by_part[-1], "prefill_expert_route": by_part[0]["prefill_expert_route"]}
        for name in ("prefill_routed_pairs", "prefill_routed_pairs_held", "prefill_expert_rows"):
            routing[name] = sum(part[name] for part in by_part)
        routing["prefill_expert_load_max"] = int(np.max(np.sum(prefill_loads, axis=0)))
        visible, selected = (
            int(np.sum(np.asarray(prefill_keys)[:, i], dtype=np.int64)
                + np.sum(np.asarray(decode_keys)[i], dtype=np.int64))
            for i in range(2))
        return {
            **self.describe(cache_len),
            **routing,
            "prefill_parts": len(lengths),
            "keys_visible": visible, "keys_selected": selected,
            "prefill_sparse_attention_form": dsa.form(min(prompt_tokens, cfg.prefill_part)),
            "prefill_selection_form": dsa.selection_form(
                min(prompt_tokens, cfg.prefill_part), cache_len, cfg.index_topk),
            "decode_sparse_attention_form": dsa.form(width),
            "prefill_layer_passes": prompt_tokens * cfg.num_hidden_layers,
            **drafting,
        }
