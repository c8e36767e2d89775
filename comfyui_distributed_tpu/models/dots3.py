"""dots3-note-prev (`dots3_note`), the text path: latent attention of
two kinds in one model. A `full_attention` layer (0, then every fourth
from 1: 13 of 46) has 128 heads over a 512-wide latent and reads, for
each query, the `index_topk` positions an index of its own picks
(`models/dsa.py`; every full layer computes its selection, none shares
one). A `sliding_attention` layer (33 of 46) has 64 heads over a latent
of its own width (1,024), another nope width and rotation base, and sees
a window of `sliding_window_size` positions, the query's own among them.
Both kinds rescale the queries and the normed latent, and gate every
head's output by a sigmoid of the layer's input before W_o. The first
feed-forward part is dense, the others 256 sigmoid-routed experts beside
one shared expert. No draft module and no tower: the published config
gives neither's sizes.

    h += attn(rms(h));  h += ffn(rms(h))

Attention, x the normed input, with (heads, query rank r_q, latent rank
r, nope, rope, value, theta) the layer kind's (`Kind`):

    c_q = rms(W_dq x);  [q_nope | q_rope] = s_q W_uq c_q,  s_q = (hidden / r_q)^1/2
    [c | k_r] = W_dkv x;  c' = s_kv rms(c),  s_kv = (hidden / r)^1/2
    q_rope, k_r rotated in pairs; the cache's row is [c' | rot(k_r)] (`mla.latents`)
    score_ij = (nope + rope)^-1/2 (q_nope_i . W_uk c'_j + q_rope_i . rot(k_r)_j)
    out = W_o [sigmoid(W_gate x)_h o_h]_h

over the j that i sees: its selection S_i in a full layer (the index
reads c_q before s_q), `0 <= i - j < sliding_window_size` in a sliding one.

A request's state is a tree of three kinds (`state_shapes`): `latents`,
one `[positions, 576]` array a full layer, and `index`, one `[positions,
128]` array a full layer, two caches that grow; `ring`, one
`[ring_positions, 1088]` array a sliding layer, position p in row p
modulo `ring_positions`: what a decode step's window reads, whatever the
position.

The prefill is one program that reads the prompt in parts of
`prefill_part` positions (`lm_common.prefill_in_parts`). A full layer's
part is GLM-5.2's: its latents and index keys written into caches of
full length, its queries over them as the parts before left them. A
sliding layer's part hands on neither a cache nor a recurrent state but
a **tail**: the last `sliding_window_size` - 1 latents before the part,
which `mla.expanded` puts in front of the part's own under the band
(fewer queries than keys), and of `[tail | part]` the last that many go
on to the next part. How many of the tail's rows are positions at all
(none before the first part) is one of a few static counts a body
serves, picked by `lax.switch`. After the last part the tails become the
rings (`ring_of`).

The decode is `lm_common.decode_loop` over a one-token step: a full
layer as the prefill's with one query (the selection a mask,
`mla.absorbed` under it), a sliding layer its latent written into the
ring and `mla.absorbed` over the ring under `ring_valid`.

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

from ..ops import attention as attention_ops
from ..ops.decode_attention import ring_valid
from ..parallel.sharding import expert_range
from . import dsa, mla
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
from .moe import decode_route, expert_layer, prefill_route, report_loads, sigmoid_route

# A ring's length is a whole number of these (`k_exaone.RING_MULTIPLE`:
# the sublane tile of a 32-bit array).
RING_MULTIPLE = 8

FULL, SLIDING = "full_attention", "sliding_attention"
# `layer_types` as published: layer 0, then `full, sliding, sliding, sliding`.
PUBLISHED_LAYER_TYPES = (FULL,) + (FULL, SLIDING, SLIDING, SLIDING) * 11 + (FULL,)


class Kind(NamedTuple):
    """One layer kind's latent attention, from the published keys (a
    sliding layer's are the `swa_` ones)."""

    heads: int
    q_rank: int
    rank: int
    nope: int
    rope: int
    value: int
    theta: float
    s_q: float   # what the queries are multiplied by
    s_kv: float  # and the normed latent

    @property
    def width(self) -> int:
        """Of a head's query and key."""
        return self.nope + self.rope

    @property
    def cache_width(self) -> int:
        return self.rank + self.rope


@dataclasses.dataclass(frozen=True)
class Dots3Config:
    """The published `config.json`'s shape keys under their own names,
    the chip's share of a deployment as `GlmDsaConfig` states it, the
    first layer held and the positions a part of the prefill takes."""

    hidden_size: int = 5120
    num_hidden_layers: int = 46
    first_layer: int = 0
    layer_types: tuple[str, ...] = PUBLISHED_LAYER_TYPES
    first_k_dense_replace: int = 1
    apply_mla_qkv_lora_rescale: bool = True
    attention_gate_type: str = "headwise"
    num_attention_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 8e7
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    sliding_window_size: int = 513
    swa_attention_gate_type: str = "headwise"
    swa_num_attention_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 5e4
    intermediate_size: int = 13824
    moe_intermediate_size: int = 1536
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    vocab_size: int = 152064
    rms_norm_eps: float = 1e-5
    ep_size: int = 1
    ep_rank: int = 0
    vocab_shards: int = 1
    prefill_part: int = 8192

    def __post_init__(self):
        if {self.attention_gate_type, self.swa_attention_gate_type} != {"headwise"}:
            raise ValueError("only the published form is written: one sigmoid gate a head")
        if set(self.layer_types) - {FULL, SLIDING} or len(self.layer_types) < max(self.layers) + 1:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers of {FULL!r} / {SLIDING!r}; "
                f"layers {self.layers[0]}-{self.layers[-1]} are held")

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
        return self.layer_types[layer] == FULL

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_k_dense_replace

    @property
    def full_layers(self) -> int:
        return sum(self.is_full(layer) for layer in self.layers)

    @property
    def window_layers(self) -> int:
        return self.num_hidden_layers - self.full_layers

    @property
    def sparse_layers(self) -> int:
        return sum(not self.is_dense(layer) for layer in self.layers)

    def _kind(self, heads, q_rank, rank, nope, rope, value, theta) -> Kind:
        rescale = self.apply_mla_qkv_lora_rescale
        return Kind(
            heads, q_rank, rank, nope, rope, value, theta,
            (self.hidden_size / q_rank) ** 0.5 if rescale else 1.0,
            (self.hidden_size / rank) ** 0.5 if rescale else 1.0)

    @property
    def full(self) -> Kind:
        return self._kind(
            self.num_attention_heads, self.q_lora_rank, self.kv_lora_rank, self.qk_nope_head_dim,
            self.qk_rope_head_dim, self.v_head_dim, self.rope_theta)

    @property
    def sliding(self) -> Kind:
        return self._kind(
            self.swa_num_attention_heads, self.swa_q_lora_rank, self.swa_kv_lora_rank,
            self.swa_qk_nope_head_dim, self.swa_qk_rope_head_dim, self.swa_v_head_dim,
            self.swa_rope_theta)

    def kind(self, layer: int) -> Kind:
        return self.full if self.is_full(layer) else self.sliding

    @property
    def tail_positions(self) -> int:
        """The positions before a query that a sliding layer lets it see."""
        return self.sliding_window_size - 1

    @property
    def ring_positions(self) -> int:
        """Rows a sliding layer keeps: the window, rounded up to `RING_MULTIPLE`."""
        return -(-self.sliding_window_size // RING_MULTIPLE) * RING_MULTIPLE


# --- parameters -----------------------------------------------------------


def param_shapes(cfg: Dots3Config) -> dict[str, Any]:
    """The tree's shapes with each weight's fan-in (None: a norm's
    scale, initialised to one)."""
    h, held, width = cfg.hidden_size, len(cfg.held_experts), cfg.moe_intermediate_size

    def layer(index: int) -> dict:
        kind = cfg.kind(index)
        block: dict[str, Any] = {
            "attn_norm": ((h,), None),
            "attn": {
                "w_dq": ((h, kind.q_rank), h),
                "q_norm": ((kind.q_rank,), None),
                "w_uq": ((kind.q_rank, kind.heads * kind.width), kind.q_rank),
                "w_dkv": ((h, kind.cache_width), h),
                "kv_norm": ((kind.rank,), None),
                "w_uk": ((kind.rank, kind.heads, kind.nope), kind.rank),
                "w_uv": ((kind.rank, kind.heads, kind.value), kind.rank),
                "w_gate": ((h, kind.heads), h),
                "w_o": ((kind.heads * kind.value, h), kind.heads * kind.value),
            },
            "ffn_norm": ((h,), None),
        }
        if cfg.is_full(index):
            d = cfg.index_head_dim
            block["indexer"] = {
                "w_q": ((kind.q_rank, cfg.index_n_heads * d), kind.q_rank),
                "w_k": ((h, d), h),
                "k_scale": ((d,), None),
                "k_bias": ((d,), d),
                "w_w": ((h, cfg.index_n_heads), h),
            }
        if cfg.is_dense(index):
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
        "layers": [layer(i) for i in cfg.layers],
        "final_norm": ((h,), None),
        "head": ((h, cfg.vocab_held), h),
    }


def param_count(cfg: Dots3Config) -> int:
    return count_params(param_shapes(cfg))


def init_params(cfg: Dots3Config, key, dtype=jnp.float32) -> dict[str, Any]:
    """Seeded random weights in `dtype` (`lm_common.init_from_shapes`);
    the routers' selection bias zero and float32."""
    params = init_from_shapes(param_shapes(cfg), key, dtype)
    for block in params["layers"]:
        if "moe" in block:
            block["moe"]["bias"] = jnp.zeros_like(block["moe"]["bias"], jnp.float32)
    return params


# --- a request's state ----------------------------------------------------


def state_shapes(cfg: Dots3Config, cache_len: int, dtype) -> dict[str, Any]:
    """The tree a request carries from its prefill through its decode: a
    leaf a full layer in either cache that grows, a leaf a sliding layer
    among the rings."""
    def rows(count, width):
        return jax.ShapeDtypeStruct((count, width), dtype)

    return {
        "latents": (rows(cache_len, cfg.full.cache_width),) * cfg.full_layers,
        "index": (rows(cache_len, cfg.index_head_dim),) * cfg.full_layers,
        "ring": (rows(cfg.ring_positions, cfg.sliding.cache_width),) * cfg.window_layers,
    }


def _slot(cfg, layer: int) -> int:
    """A layer's place among the held layers of its own kind."""
    return sum(cfg.is_full(i) == cfg.is_full(layer) for i in range(cfg.first_layer, layer))


def _put(leaves: tuple, slot: int, rows, position):
    """`rows` written into leaf `slot` from `position` on."""
    return tuple(
        jax.lax.dynamic_update_slice(leaf, rows, (position, 0)) if at == slot else leaf
        for at, leaf in enumerate(leaves))


def ring_of(cfg: Dots3Config, tail, tokens: int):
    """What a sliding layer's ring holds after a prefill of `tokens`
    positions whose last `tail_positions` latents are `tail` (row r
    position `tokens` - `tail_positions` + r; zeros where that is below
    0): row s the newest position that is s modulo the ring's length
    where the tail has it, zero elsewhere. The rows it leaves at zero
    are of positions no later query sees."""
    size, first = cfg.ring_positions, tokens - cfg.tail_positions
    held = tokens - 1 - (tokens - 1 - np.arange(size)) % size
    there = held >= max(first, 0)
    return jnp.where(there[:, None], tail[np.where(there, held - first, 0)], 0)


# --- a layer ----------------------------------------------------------------


def _queries(cfg, kind: Kind, p, x, rope):
    """The query latent [T, r_q] of x [T, hidden] (before the rescale:
    what an index reads), and the heads' queries of it under the
    rescale: [T, heads, nope] and, rotated in pairs, [T, heads, rope]."""
    c_q = rms_norm(x @ p["w_dq"], p["q_norm"], cfg.rms_norm_eps)
    q = jnp.dot(c_q, p["w_uq"], preferred_element_type=jnp.float32) * kind.s_q
    q = q.astype(x.dtype).reshape(x.shape[0], kind.heads, kind.width)
    return c_q, q[..., :kind.nope], apply_rope_pairs(q[..., kind.nope:], *rope)


def _gated(p, x, out):
    """W_o over the heads' outputs [T, heads, v], each under its gate:
    one sigmoid a head of the layer's input x."""
    with jax.named_scope("gate"):
        gate = jax.nn.sigmoid(jnp.dot(x, p["w_gate"], preferred_element_type=jnp.float32))
        out = (out * gate[:, :, None]).astype(out.dtype)
    return out.reshape(x.shape[0], -1) @ p["w_o"]


def full_attention(cfg, block, x, cache, slot: int, positions):
    """x [W, hidden] (normed) at `positions` [W], one after another,
    through a full layer: its latents and index keys written into leaf
    `slot` of the two caches, each query's selection made over the index
    cache, then each query over its chosen rows of the latent cache.
    Returns (output [W, hidden], cache, the selection)."""
    p, eps, kind = block["attn"], cfg.rms_norm_eps, cfg.full
    rope = rope_tables(kind.theta, kind.rope, positions)
    with jax.named_scope("mla"):
        c_q, q_nope, q_rope = _queries(cfg, kind, p, x, rope)
        rows = mla.latents(p, x, rope, eps, rotate=apply_rope_pairs, scale=kind.s_kv)
        cache = {**cache, "latents": _put(cache["latents"], slot, rows, positions[0])}
    with jax.named_scope("indexer"):
        cache["index"] = _put(
            cache["index"], slot, dsa.keys(block["indexer"], x, rope, eps), positions[0])
        q_index, weights = dsa.queries(block["indexer"], c_q, x, rope, cfg.index_n_heads)
        selection = dsa.select(
            q_index, weights, cache["index"][slot], positions, cfg.index_topk)
    with jax.named_scope("mla"):
        out = dsa.attend(
            q_nope, q_rope, cache["latents"][slot], selection, p["w_uk"], p["w_uv"],
            kind.width ** -0.5)
        return _gated(p, x, out), cache, selection


def _window_rows(cfg, p, x, positions):
    """A sliding layer's queries of x at `positions` ([W, heads, nope],
    [W, heads, rope] rotated) and the rows its cache holds of them."""
    kind = cfg.sliding
    rope = rope_tables(kind.theta, kind.rope, positions)
    _, q_nope, q_rope = _queries(cfg, kind, p, x, rope)
    rows = mla.latents(p, x, rope, cfg.rms_norm_eps, rotate=apply_rope_pairs, scale=kind.s_kv)
    return q_nope, q_rope, rows


def tails_seen(cfg, part: int, ends: tuple[int, ...]) -> tuple[int, ...]:
    """How many of a tail's rows are positions, for the parts of `part`
    positions that end at `ends`: the distinct counts, ascending."""
    return tuple(sorted({min(end - part, cfg.tail_positions) for end in ends}))


def window_attention_part(cfg, block, x, tail, positions, seen: tuple[int, ...]):
    """A part x [P, hidden] (normed) at `positions` through a sliding
    layer: the band over [tail | the part's own latents], `tail`
    [`tail_positions`, width] the latents before the part, of which the
    last min(`positions[0]`, `tail_positions`) are positions at all:
    one of `seen` (`tails_seen`), a branch each. Returns (output [P,
    hidden], the tail the part leaves)."""
    p, kind, keep = block["attn"], cfg.sliding, cfg.tail_positions
    with jax.named_scope("window_latent"):
        q_nope, q_rope, rows = _window_rows(cfg, p, x, positions)

        def over(count, q_nope, q_rope, rows, tail):
            return mla.expanded(
                q_nope, q_rope, rows, p["w_uk"], p["w_uv"], kind.width ** -0.5,
                window=cfg.sliding_window_size,
                before=tail[tail.shape[0] - count:] if count else None)

        if len(seen) == 1:
            out = over(seen[0], q_nope, q_rope, rows, tail)
        else:
            have = jnp.minimum(positions[0], cfg.tail_positions)
            out = jax.lax.switch(
                sum(have > count for count in seen[:-1]), [partial(over, c) for c in seen],
                q_nope, q_rope, rows, tail)
        # a part no shorter than the tail leaves its own last rows and nothing of the old tail
        left = rows[-keep:] if rows.shape[0] >= keep else jnp.concatenate([tail, rows])[-keep:]
        return _gated(p, x, out), left


def window_attention_step(cfg, block, x, ring, positions):
    """A step's new tokens x [W, hidden] (normed) at `positions` through
    a sliding layer: their latents written into the ring, each at its
    position modulo the ring's length, then each query over the rows of
    the ring its window reaches (`ring_valid`). Returns (output, ring)."""
    p, kind = block["attn"], cfg.sliding
    with jax.named_scope("window_latent"):
        q_nope, q_rope, rows = _window_rows(cfg, p, x, positions)
        size = ring.shape[0]
        for j in range(x.shape[0]):  # two rows need not lie side by side
            ring = jax.lax.dynamic_update_slice(ring, rows[j:j + 1], (positions[j] % size, 0))
        out = mla.absorbed(
            q_nope, q_rope, ring, ring_valid(positions, size, cfg.sliding_window_size),
            p["w_uk"], p["w_uv"], kind.width ** -0.5)
        return _gated(p, x, out), ring


def _feed_forward(cfg, block, x):
    """(output, chosen ids [T, k] or None, pairs per held expert or None)"""
    if "mlp" in block:
        with jax.named_scope("mlp"):
            return swiglu(x, block["mlp"]), None, None
    p = block["moe"]
    route = partial(
        sigmoid_route, bias=p["bias"], k=cfg.num_experts_per_tok,
        scale=cfg.routed_scaling_factor, renormalise=cfg.norm_topk_prob)
    with jax.named_scope("moe"):
        return expert_layer(p, x, cfg.held_experts, route)


def _keys_seen(positions, selections):
    """[2, full layers] int32: over a walk's positions, the keys each
    full layer's queries could see (t + 1 each) and those they read
    (|S_t| each)."""
    visible = jnp.sum(positions + 1).astype(jnp.int32)
    return jnp.stack([
        jnp.stack([visible] * len(selections)),
        jnp.stack([jnp.count_nonzero(s.counts).astype(jnp.int32) for s in selections]),
    ])


def walk(cfg, params, state, h, positions, window_attention):
    """h [W, hidden] at `positions` through every layer held.
    `window_attention(block, x, slot)` is a sliding layer's attention in
    the caller's form and returns (output, what the caller keeps of it);
    a full layer's runs over `state`'s two caches. Returns (h, state,
    what the sliding layers left (a tuple), chosen ids [sparse layers,
    W, k], pairs per held expert [sparse layers, held], the full layers'
    selections, keys seen [2, full layers])."""
    chosen, loads, selections, left = [], [], [], []
    for layer, block in zip(cfg.layers, params["layers"]):
        with jax.named_scope(f"layer_{layer}"):
            x = rms_norm(h, block["attn_norm"], cfg.rms_norm_eps)
            if cfg.is_full(layer):
                out, state, selection = full_attention(
                    cfg, block, x, state, _slot(cfg, layer), positions)
                selections.append(selection)
            else:
                out, kept = window_attention(block, x, _slot(cfg, layer))
                left.append(kept)
            h = h + out
            out, ids_l, sizes = _feed_forward(
                cfg, block, rms_norm(h, block["ffn_norm"], cfg.rms_norm_eps))
            h = h + out
        if ids_l is not None:
            chosen.append(ids_l)
            loads.append(sizes)
    return (h, state, tuple(left), jnp.stack(chosen), jnp.stack(loads), tuple(selections),
            _keys_seen(positions, selections))


# --- the two programs -----------------------------------------------------


class Prefill(NamedTuple):
    logits: jax.Array   # [vocab_held] float32, at the prompt's last position
    cache: dict         # `state_shapes`: the request's state after the prompt
    loads: jax.Array    # [parts, sparse layers, held] pairs on each held expert, a part
    keys: jax.Array     # [parts, 2, full layers] keys visible and keys read, a part and layer
    kept: dict | None   # under `collect`: see `prefill`


class Decode(NamedTuple):
    ids: jax.Array      # [steps]
    loads: jax.Array    # [sparse layers, held], summed over the steps
    read: jax.Array     # int32: held experts read, summed over the steps and layers
    keys: jax.Array     # [2, full layers] keys visible and keys read
    cache: dict         # the state it was given, after the steps
    kept: dict | None   # under `collect`: see `decode`


def _kept(cfg, state, selections):
    """Selections as `collect` keeps them: (positions, which count) each."""
    most = min(cfg.index_topk, state["latents"][0].shape[0])
    return tuple(dsa.as_positions(selection, most) for selection in selections)


def _rows_in_order(parts):
    """[parts, ..., P, k] -> [..., parts x P, k]: the parts' rows one after another."""
    return jnp.moveaxis(parts, 0, -3).reshape(*parts.shape[1:-2], -1, parts.shape[-1])


@partial(jax.jit, static_argnames=("cfg", "cache_len", "collect"))
def prefill(cfg: Dots3Config, params, ids, *, cache_len: int, collect: bool = False):
    """The prompt `ids` [T] in parts (`prefill_in_parts`): the whole parts
    one scanned body, what is left a body of its own, each over the two
    caches and the sliding layers' tails as the parts before left them.
    Returns the logits at the last position, the request's state (the
    caches allocated here, once; the rings made of the last tails), a
    part's pairs on each held expert and keys seen and, under `collect`
    (the parity check's), `kept`: `chosen` [sparse layers, T, k] the
    experts chosen and `selections`, a full layer's (positions [T, k],
    which count)."""
    dtype = params["embed"].dtype
    shapes = state_shapes(cfg, cache_len, dtype)
    tail = jax.ShapeDtypeStruct((cfg.tail_positions, cfg.sliding.cache_width), dtype)
    state = zeros({"latents": shapes["latents"], "index": shapes["index"],
                   "tail": (tail,) * cfg.window_layers})

    def part(state, cuts, start, ends):
        (tokens,) = cuts
        positions = start + jnp.arange(tokens.shape[0])
        seen = tails_seen(cfg, tokens.shape[0], ends)
        h, state, tails, chosen, loads, selections, keys = walk(
            cfg, params, state, params["embed"][tokens], positions,
            lambda block, x, slot: window_attention_part(
                cfg, block, x, state["tail"][slot], positions, seen))
        state = {**state, "tail": tails}
        kept = {"chosen": chosen, "selections": _kept(cfg, state, selections)} if collect else None
        # a part's outputs have one shape: what is left over keeps its rows filled up to a part's
        fill = [(0, 0), (0, cfg.prefill_part - tokens.shape[0]), (0, 0)]
        kept = jax.tree_util.tree_map(lambda a: jnp.pad(a, fill[-a.ndim:]), kept)
        return state, (h[-1], loads, keys, kept)

    state, (h, loads, keys, kept) = prefill_in_parts(part, state, (ids,), cfg.prefill_part)
    kept = jax.tree_util.tree_map(lambda a: _rows_in_order(a)[..., :ids.shape[0], :], kept)
    cache = {
        "latents": state["latents"], "index": state["index"],
        "ring": tuple(ring_of(cfg, tail, ids.shape[0]) for tail in state["tail"]),
    }
    return Prefill(head(cfg, params, h[-1:])[0], cache, loads, keys, kept)


def decode_step(cfg, params, cache, token, position):
    """One token at `position` through every layer over the request's
    state: a full layer over its two caches, a sliding layer over its
    ring. Returns (logits [vocab_held], cache, ids [sparse layers, k],
    pairs per held expert [sparse layers, held], the full layers'
    selections (`dsa.Selection`s), keys seen [2, full layers])."""
    positions = position + jnp.arange(1)
    rings = cache["ring"]
    h, cache, rings, chosen, loads, selections, keys = walk(
        cfg, params, cache, params["embed"][token[None]], positions,
        lambda block, x, slot: window_attention_step(cfg, block, x, rings[slot], positions))
    return head(cfg, params, h)[0], {**cache, "ring": rings}, chosen[:, 0], loads, selections, keys


@partial(jax.jit, static_argnames=("cfg", "steps", "collect"), donate_argnames=("cache",))
def decode(cfg: Dots3Config, params, cache, logits, start, key, temperature, *,
           steps: int, collect: bool = False):
    """`steps` ids in one program, from the prefill's `logits` at
    position `start - 1`, one token a step (`decode_step`); no early
    stop. The state tree is donated, carried through the loop and handed
    back. Returns the ids, the pairs on each held expert, the held
    experts read, the keys seen and, under `collect`, per step: the
    logits, the experts chosen and the full layers' selections."""

    def step(cache, token, position):
        row, cache, chosen, loads, selections, keys = decode_step(
            cfg, params, cache, token, position)
        kept = {"logits": row, "chosen": chosen,
                "selections": jax.tree_util.tree_map(
                    lambda a: a[0], _kept(cfg, cache, selections)),
                } if collect else None
        return row, cache, (loads, jnp.count_nonzero(loads).astype(jnp.int32), keys), kept

    cache, ids, (loads, read, keys), kept = decode_loop(
        step, dict(cache), logits, start, key, temperature, steps)
    return Decode(ids, loads, read, keys, cache, kept)


def band_keys(cfg: Dots3Config, prompt_tokens: int, dtype) -> tuple[int, int, str]:
    """Over one sliding layer's prefill of `prompt_tokens` positions:
    (the query-key pairs the band lets rows see, min(i + 1, window)
    each; those the route taken multiplied, by its own blocks; the
    route), the parts as `prefill` cuts them."""
    kind, window = cfg.sliding, cfg.sliding_window_size
    whole, left = parts_of(prompt_tokens, cfg.prefill_part)
    ramp = min(prompt_tokens, window)
    seen = ramp * (ramp + 1) // 2 + (prompt_tokens - ramp) * window
    computed, route = 0, "xla"
    for start, rows in [(i * cfg.prefill_part, cfg.prefill_part) for i in range(whole)] + (
            [(prompt_tokens - left, left)] if left else []):
        keys = rows + min(start, cfg.tail_positions)
        q, k, v = (jax.ShapeDtypeStruct((1, n, kind.heads, d), dtype) for n, d in (
            (rows, kind.width), (keys, kind.width), (keys, kind.value)))
        route = attention_ops.causal_route(q, k, v, window)
        computed += attention_ops.causal_pairs_computed(
            route, rows, keys, kind.width, kind.value, jnp.dtype(dtype).itemsize, window)
    return seen, computed, route


class Dots3(LanguageModel):
    """What a bundle's `lm` part is (the contract is in `lm_common`)."""

    _init = staticmethod(init_params)
    _prefill = staticmethod(prefill)
    _decode = staticmethod(decode)

    @property
    def layer_passes(self) -> int:
        return self.cfg.num_hidden_layers

    def read_back(self, prefill: Prefill, decode: Decode) -> tuple:
        """The pairs on each held expert and the keys seen, of either
        program, and the held experts the decode read."""
        return prefill.loads, prefill.keys, decode.loads, decode.keys, decode.read

    def describe(self, cache_len: int) -> dict[str, int]:
        cfg, shapes = self.cfg, state_shapes(self.cfg, cache_len, self.dtype)
        index = sum(nbytes(leaf) for leaf in shapes["index"])
        return {
            "layers": cfg.num_hidden_layers,
            "full_layers": cfg.full_layers,
            "window_layers": cfg.window_layers,
            "window": cfg.sliding_window_size,
            "ring_positions": cfg.ring_positions,
            "index_topk": cfg.index_topk,
            "indexer_layers": cfg.full_layers,
            "prefill_part": cfg.prefill_part,
            "experts_held": len(cfg.held_experts),
            "experts_total": cfg.n_routed_experts,
            "cache_bytes": sum(nbytes(leaf) for leaf in shapes["latents"]) + index,
            "indexer_cache_bytes": index,
            "state_bytes": sum(nbytes(leaf) for leaf in shapes["ring"]),
        }

    def report(self, prompt_tokens: int, new_tokens: int, cache_len: int,
               prefill_loads, prefill_keys, decode_loads, decode_keys, read) -> dict:
        """`describe`, the keys the full layers' queries could see and
        those they read, summed over both programs as the device counted
        them, the sliding layers' band in the prefill (`band_keys`, from
        the shapes and the route alone), and, per phase, the routing as
        `moe.report_loads` has it, the prefill's ladder read a part."""
        cfg = self.cfg
        whole, left = parts_of(prompt_tokens, cfg.prefill_part)
        lengths = [cfg.prefill_part] * whole + [left] * bool(left)
        by_part = [
            report_loads(
                cfg.num_experts_per_tok, cfg.n_routed_experts, length, new_tokens, loads,
                decode_loads,
                decode_route(
                    cfg.num_experts_per_tok, cfg.hidden_size, cfg.moe_intermediate_size,
                    self.dtype),
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
        seen, computed, route = band_keys(cfg, prompt_tokens, self.dtype)
        return {
            **self.describe(cache_len),
            **routing,
            "prefill_parts": len(lengths),
            "keys_visible": visible, "keys_selected": selected,
            "prefill_band_keys_seen": seen * cfg.window_layers,
            "prefill_band_keys_computed": computed * cfg.window_layers,
            "prefill_band_route": route,
            "prefill_sparse_attention_form": dsa.form(min(prompt_tokens, cfg.prefill_part)),
            "prefill_selection_form": dsa.selection_form(
                min(prompt_tokens, cfg.prefill_part), cache_len, cfg.index_topk),
            "decode_sparse_attention_form": dsa.form(1),
            "decode_experts_read": int(read),
            "decode_steps": new_tokens,
            "prefill_layer_passes": prompt_tokens * cfg.num_hidden_layers,
            "decode_layer_passes": new_tokens * cfg.num_hidden_layers,
        }
