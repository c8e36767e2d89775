"""K-EXAONE (`exaone_moe`): attention layers of two kinds in a period of
four, `LLLG`: three that see a window of `sliding_window` positions, then
one that sees every position; layer 0's feed-forward part dense, the
others a mixture of routed experts beside one shared expert; and one
multi-token-prediction (MTP) module that drafts for a self-speculative
decode.

    h += mixer(rms(h));  h += ffn(rms(h))           (pre-norm, assumed)

Mixer, both kinds: q = W_q x (heads x d), k = W_k x, v = W_v x (key heads
x d), q and k each normed over a head's d channels with a learned scale,
causal softmax at d^-1/2, key head j serving its group of query heads,
y = W_o o. A window layer rotates q and k (all d channels, theta
`rope_theta`) after the norm and position i sees i - window < j <= i; a
full layer sees every j <= i and rotates nothing.

The MTP module, for position i with h_i the residual stream after the
last main layer and x_{i+1} the next token:

    u_i = W_eh [rms_e(E[x_{i+1}]) ; rms_h(h_i)]
    draft logits for x_{i+2} = Head(rms_mtp(layer(u)_i))

one full-attention layer with a sparse feed-forward part and a cache of
its own, the main model's embedding and head.

A request's state is a tree (`state_shapes`): `ring` [window layers, 2,
key heads, `ring_positions`, d], position p in entry p modulo the ring's
length, keys stored rotated: fixed size whatever the position; `kv`
[full layers + 1, 2, key heads, positions, d], which grows with the
position, the MTP module's the last slot; and `h` [hidden], the residual
stream of the last position the MTP module has not seen yet. The prefill
allocates it, the decode takes it by donation and hands it back.

The decode is one program either way. `draft_tokens` 0: `steps`
one-token steps. `draft_tokens` 1: `lm_common.draft_loop`, whose step drafts one
token with the MTP module, runs the last emitted token and the draft
through the main model as two positions, keeps the draft with
probability min(1, p / q) (else draws from the renormalised max(p - q,
0)) and so emits one or two tokens, until `steps` ids are written. What
a rejected draft wrote (ring, `kv`, the MTP's slot) the next step writes
over before anything reads it. A ring of `sliding_window` entries alone
would not do: a step's second write would land on the oldest key its
first query still sees, hence `ring_positions`.

The expert layer is `moe.expert_layer` under `moe.sigmoid_route`; the
chip holds `expert_range(ep_rank, ep_size)` of the experts and the first
of `vocab_shards` slices of the vocabulary, as `SolarOpen2Config` has it.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import causal_attention
from ..ops.decode_attention import attend_xla, position_valid, ring_valid
from ..parallel.sharding import expert_range
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

# A ring's length is a whole number of these (the sublane tile of a
# 32-bit layout; a 16-bit one pads to twice it by itself).
RING_MULTIPLE = 8


@dataclasses.dataclass(frozen=True)
class KExaoneConfig:
    """The published `config.json`'s shape keys under their own names
    (`rope_theta` is its `rope_parameters` block's), and the chip's
    share of a deployment as `SolarOpen2Config` states it. `ring` 0: the
    ring's length follows from the window and the drafts a step may
    make; another value is taken as it is (the parity check's control)."""

    hidden_size: int = 6144
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 128
    sliding_window_pattern: str = "LLLG"
    rope_theta: float = 1e6
    intermediate_size: int = 18432
    first_k_dense_replace: int = 1
    moe_intermediate_size: int = 2048
    num_experts: int = 128
    num_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    num_nextn_predict_layers: int = 1
    vocab_size: int = 153600
    rms_norm_eps: float = 1e-5
    ep_size: int = 1
    ep_rank: int = 0
    vocab_shards: int = 1
    ring: int = 0

    def __post_init__(self):
        if self.num_nextn_predict_layers != 1 or set(self.sliding_window_pattern) - set("LG"):
            raise ValueError(
                "only the published form is written: one MTP module, a layer pattern of "
                "L (window) and G (full)"
            )

    @property
    def held_experts(self) -> range:
        return expert_range(self.num_experts, self.ep_rank, self.ep_size)

    @property
    def vocab_held(self) -> int:
        return self.vocab_size // self.vocab_shards

    def is_window(self, layer: int) -> bool:
        return self.sliding_window_pattern[layer % len(self.sliding_window_pattern)] == "L"

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_k_dense_replace

    @property
    def window_layers(self) -> int:
        return sum(self.is_window(layer) for layer in range(self.num_hidden_layers))

    @property
    def full_layers(self) -> int:
        """Those of the main model; the MTP module's is one more."""
        return self.num_hidden_layers - self.window_layers

    @property
    def sparse_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def ring_positions(self) -> int:
        """Entries a window layer keeps: the window and the one drafted
        position a step writes beside it, rounded up to `RING_MULTIPLE`."""
        wanted = self.sliding_window + self.num_nextn_predict_layers
        return self.ring or -(-wanted // RING_MULTIPLE) * RING_MULTIPLE


# --- parameters -----------------------------------------------------------


def param_shapes(cfg: KExaoneConfig) -> dict[str, Any]:
    """The tree's shapes with each weight's fan-in (None: a norm's
    scale, initialised to one)."""
    h, d = cfg.hidden_size, cfg.head_dim
    heads, kv = cfg.num_attention_heads * d, cfg.num_key_value_heads * d
    held = len(cfg.held_experts)

    def layer(dense: bool) -> dict:
        width = cfg.moe_intermediate_size
        ffn = {"mlp": mlp_shapes(h, cfg.intermediate_size)} if dense else {"moe": {
            "w_g": ((h, cfg.num_experts), h),
            "bias": ((cfg.num_experts,), None),
            "experts": {
                "w_gate_up": ((held, h, 2 * width), h),
                "w_down": ((held, width, h), width),
            },
            "shared": mlp_shapes(h, width * cfg.num_shared_experts),
        }}
        return {
            "mixer_norm": ((h,), None),
            "attn": {
                "w_q": ((h, heads), h), "w_k": ((h, kv), h), "w_v": ((h, kv), h),
                "q_norm": ((d,), None), "k_norm": ((d,), None),
                "w_o": ((heads, h), heads),
            },
            "ffn_norm": ((h,), None),
            **ffn,
        }

    return {
        "embed": ((cfg.vocab_held, h), 1),
        "layers": [layer(cfg.is_dense(i)) for i in range(cfg.num_hidden_layers)],
        "final_norm": ((h,), None),
        "head": ((h, cfg.vocab_held), h),
        "mtp": {
            "embed_norm": ((h,), None), "hidden_norm": ((h,), None),
            "w_eh": ((2 * h, h), 2 * h),
            "layer": layer(False),
            "norm": ((h,), None),
        },
    }


def param_count(cfg: KExaoneConfig) -> int:
    return count_params(param_shapes(cfg))


def init_params(cfg: KExaoneConfig, key, dtype=jnp.float32) -> dict[str, Any]:
    """Seeded random weights in `dtype` (`lm_common.init_from_shapes`);
    the routers' selection bias zero and float32."""
    params = init_from_shapes(param_shapes(cfg), key, dtype)
    for block in (*params["layers"], params["mtp"]["layer"]):
        if "moe" in block:
            block["moe"]["bias"] = jnp.zeros_like(block["moe"]["bias"], jnp.float32)
    return params


# --- a request's state ----------------------------------------------------


def state_shapes(cfg: KExaoneConfig, cache_len: int, dtype) -> dict[str, jax.ShapeDtypeStruct]:
    """The tree a request carries from its prefill through its decode."""
    heads, d = cfg.num_key_value_heads, cfg.head_dim
    return {
        "ring": jax.ShapeDtypeStruct((cfg.window_layers, 2, heads, cfg.ring_positions, d), dtype),
        "kv": jax.ShapeDtypeStruct((cfg.full_layers + 1, 2, heads, cache_len, d), dtype),
        "h": jax.ShapeDtypeStruct((cfg.hidden_size,), dtype),
    }


def _slot(cfg, layer: int) -> int:
    """A layer's place among the layers of its kind."""
    return sum(cfg.is_window(i) == cfg.is_window(layer) for i in range(layer))


# --- the mixer ------------------------------------------------------------


def _projections(cfg, p, x, rope):
    """q [T, heads, d], k and v [T, key heads, d] of x [T, hidden]: q and
    k normed a head, then rotated where `rope` (cos, sin) is given."""
    tokens, d = x.shape[0], cfg.head_dim
    q = rms_norm((x @ p["w_q"]).reshape(tokens, -1, d), p["q_norm"], cfg.rms_norm_eps)
    k = rms_norm((x @ p["w_k"]).reshape(tokens, -1, d), p["k_norm"], cfg.rms_norm_eps)
    v = (x @ p["w_v"]).reshape(tokens, -1, d)
    if rope is not None:
        q, k = apply_rope(q, *rope), apply_rope(k, *rope)
    return q, k, v


def _entries(k, v):
    """Keys and values [T, key heads, d] as a cache holds them: [2, key
    heads, T, d]."""
    return jnp.stack([k, v]).transpose(0, 2, 1, 3)


def mixer_whole(cfg, p, x, window: bool):
    """Over a whole sequence x [T, hidden] (the prefill's form). Returns
    (output [T, hidden], keys and values [2, key heads, T, d])."""
    rope = rope_tables(cfg.rope_theta, cfg.head_dim, jnp.arange(x.shape[0])) if window else None
    q, k, v = _projections(cfg, p, x, rope)
    out = causal_attention(
        q[None], k[None], v[None], window=cfg.sliding_window if window else None)[0]
    return out.reshape(x.shape[0], -1) @ p["w_o"], _entries(k, v)


def mixer_cached(cfg, p, x, cache, name: str, index: int, positions):
    """A step's W new tokens x [W, hidden] at `positions` [W] (one after
    another): their keys and values written into slot `index` of
    `cache[name]`, a ring (an entry a position, modulo its length) or a
    cache that grows, then each query over what it may see of the slot.
    Returns (output [W, hidden], the array written)."""
    window = name == "ring"
    rope = rope_tables(cfg.rope_theta, cfg.head_dim, positions) if window else None
    q, k, v = _projections(cfg, p, x, rope)
    new, held = _entries(k, v)[None], cache[name]
    size = held.shape[3]
    if window:
        for j in range(x.shape[0]):  # two entries need not lie side by side
            held = jax.lax.dynamic_update_slice(
                held, new[:, :, :, j:j + 1], (index, 0, 0, positions[j] % size, 0))
        valid = ring_valid(positions, size, cfg.sliding_window)
    else:
        held = jax.lax.dynamic_update_slice(held, new, (index, 0, 0, positions[0], 0))
        valid = position_valid(positions, size)
    out = attend_xla(q, held, (index,), valid)
    return out.reshape(x.shape[0], -1) @ p["w_o"], held


def ring_of(cfg, entries, tokens: int):
    """What a window layer's ring holds after a prefill of `tokens`
    positions whose keys and values are `entries` [2, key heads, T, d]:
    entry s the newest position that is s modulo the ring's length, zero
    where there is none yet."""
    size = cfg.ring_positions
    held = tokens - 1 - (tokens - 1 - np.arange(size)) % size
    ring = entries[:, :, np.maximum(held, 0)]
    return jnp.where((held >= 0)[None, None, :, None], ring, 0)


# --- a layer, in either form ----------------------------------------------


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


def _layer(cfg, block, h, window: bool, mixer):
    """One pre-norm residual layer; `mixer(p, x)` returns (output, what
    it hands back: the keys and values, or the cache array it wrote).
    Returns (h, that, chosen ids, pairs per held expert)."""
    with jax.named_scope("swa" if window else "full"):
        out, kept = mixer(block["attn"], rms_norm(h, block["mixer_norm"], cfg.rms_norm_eps))
    h = h + out
    out, ids, sizes = _feed_forward(
        cfg, block, rms_norm(h, block["ffn_norm"], cfg.rms_norm_eps))
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
def prefill(cfg: KExaoneConfig, params, ids, *, cache_len: int, collect: bool = False):
    """The whole prompt `ids` [T] at once. Returns the logits at its last
    position, the request's state (allocated here, once: each ring with
    the prompt's last positions, each growing slot's first T positions
    written, `h` the last position's residual stream), the pairs that
    fell on each held expert and the experts chosen, whatever `collect`
    (the parity check's): one program (`deepseek_v2.prefill`).

    Of the MTP module the prompt needs the keys and values only (nothing
    reads its output before the decode's first draft), so that is what
    runs: `mtp_input`, the norm, W_k and W_v. Position T - 1 has no next
    token yet and is written from token 0; the decode's first step
    writes it again before anything reads it."""
    tokens = ids.shape[0]
    h = params["embed"][ids]
    cache = zeros(state_shapes(cfg, cache_len, h.dtype))

    def write(kv, index, entries):
        return jax.lax.dynamic_update_slice(kv, entries[None], (index, 0, 0, 0, 0))

    chosen, loads = [], []
    for layer, block in enumerate(params["layers"]):
        window, index = cfg.is_window(layer), _slot(cfg, layer)
        with jax.named_scope(f"layer_{layer}"):
            h, entries, ids_l, sizes = _layer(
                cfg, block, h, window, lambda p, x: mixer_whole(cfg, p, x, window))
        if window:
            cache["ring"] = cache["ring"].at[index].set(ring_of(cfg, entries, tokens))
        else:
            cache["kv"] = write(cache["kv"], index, entries)
        if ids_l is not None:
            chosen.append(ids_l)
            loads.append(sizes)
    with jax.named_scope("mtp"):
        block = params["mtp"]["layer"]
        u = mtp_input(cfg, params, h, jnp.concatenate([ids[1:], jnp.zeros((1,), ids.dtype)]))
        _, k, v = _projections(
            cfg, block["attn"], rms_norm(u, block["mixer_norm"], cfg.rms_norm_eps), None)
        cache["kv"] = write(cache["kv"], cfg.full_layers, _entries(k, v))
    cache["h"] = h[-1]
    return Prefill(
        head(cfg, params, h[-1:])[0], cache, jnp.stack(loads),
        jnp.stack(chosen),  # served too: one program, whatever `collect`
    )


def main_step(cfg, params, cache, tokens, position):
    """W tokens [W] at `position`, `position` + 1, ... through every
    main layer over the request's state. Returns (logits [W,
    vocab_held], the residual streams [W, hidden], cache, ids [sparse
    layers, W, k], pairs per held expert [sparse layers, held])."""
    positions = position + jnp.arange(tokens.shape[0])
    h = params["embed"][tokens]
    chosen, loads = [], []
    for layer, block in enumerate(params["layers"]):
        name = "ring" if cfg.is_window(layer) else "kv"
        with jax.named_scope(f"layer_{layer}"):
            h, cache[name], ids_l, sizes = _layer(
                cfg, block, h, name == "ring",
                lambda p, x: mixer_cached(cfg, p, x, cache, name, _slot(cfg, layer), positions))
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
    block = params["mtp"]["layer"]
    out, cache["kv"], ids, sizes = _layer(
        cfg, block, mtp_input(cfg, params, h, tokens), False,
        lambda p, x: mixer_cached(cfg, p, x, cache, "kv", cfg.full_layers, positions))
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
    model's two steps; nothing of the state waits on a draft's fate."""

    def drafted(cache, h, tokens, position):
        rows, cache, _, loads = mtp_step(cfg, params, cache, h, tokens, position)
        return rows, None, cache, (loads, jnp.count_nonzero(loads)), None

    def verified(cache, tokens, position):
        rows, h, cache, chosen, loads = main_step(cfg, params, cache, tokens, position)
        kept = {"chosen": chosen} if collect else None
        return rows, h, cache, (loads, jnp.count_nonzero(loads)), kept

    cache, ids, counts, ((loads_mtp, read_mtp), (loads, read)), kept = draft_loop(
        drafted, verified, cache, logits, start, key, temperature, steps)
    loads = jnp.concatenate([loads, loads_mtp[None]])  # the MTP module's row
    counts = jnp.concatenate([counts, (read + read_mtp).astype(jnp.int32)[None]])
    return Decode(ids, loads, counts, cache, kept)


@partial(jax.jit, static_argnames=("cfg", "steps", "collect", "draft_tokens"),
         donate_argnames=("cache",))
def decode(cfg: KExaoneConfig, params, cache, logits, start, key, temperature, *,
           steps: int, collect: bool = False, draft_tokens: int = 0):
    """`steps` ids in one program, from the prefill's `logits` at
    position `start - 1`; no early stop. With `draft_tokens` 0 that is
    `steps` one-token steps (draw id i from the logits, run it through
    the model at `start + i`); with 1 the self-speculative loop, which
    takes as many steps as its drafts' fates make it, a loop on the
    device with no trip to the host. The state tree is donated, carried through
    the loop and handed back. Returns the ids, the pairs on each held
    expert, `counts` and, under `collect`, per step: the main model's
    logits, the experts chosen and, when drafting, the logits each draft
    was drawn from, the step's position n and whether its draft was
    kept (the first token comes from the prefill's logits; the logits'
    row 0 is position n's, row 1 the draft's at n + 1)."""
    run = _decode_drafting if drafts(draft_tokens) else _decode_plain
    return run(cfg, params, dict(cache), logits, start, key, temperature, steps, collect)


class KExaone(LanguageModel):
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
            "window_layers": cfg.window_layers,
            "full_layers": cfg.full_layers + 1,
            "window": cfg.sliding_window,
            "ring_positions": cfg.ring_positions,
            "experts_held": len(cfg.held_experts),
            "experts_total": cfg.num_experts,
            "cache_bytes": nbytes(shapes["kv"]),
            "state_bytes": nbytes(shapes["ring"]),
        }

    def report(self, prompt_tokens: int, new_tokens: int, cache_len: int,
               prefill_loads, decode_loads, counts) -> dict:
        """`describe`, what the decode's steps came to, the layer bodies
        either program ran (the decode's over every position a step ran, a
        rejected draft's and the MTP module's among them; of the MTP
        module the prefill runs only the keys and values, no body) and,
        per phase, the routing as `moe.report_loads` has it, the decode's
        pairs counted over the positions its steps ran."""
        cfg = self.cfg
        width, drafting = drafting_report(
            counts, cfg.num_experts_per_tok, cfg.sparse_layers, cfg.num_hidden_layers)
        return {
            **self.describe(cache_len),
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
