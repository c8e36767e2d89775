"""Granite 4.0-H (ibm-granite/granite-4.0-h-micro, `model_type:
granitemoehybrid` with `num_local_experts` 0): 40 layers, each a mixer
**and** a dense SwiGLU under two norms, and four scalars that stand where
no other model here has one:

    h_0 = embedding_multiplier . E[id]                                 (12)
    h = h + residual_multiplier . mixer_l(rms(h; w1_l))                 (0.22)
    h = h + residual_multiplier . W_out_l (silu(a) (.) b),  [a | b] = W_in_l rms(h; w2_l)
    logits = E rms(h; w_f) / logits_scaling                             (tied head; 8)

`layer_types` says what layer l's mixer is. `mamba` (36 of 40) is
`models/mamba2.py` whole, letter for letter what Nemotron-H's `M` blocks
call, at one group: one B and one C that all 64 heads read, the gated
norm over all 4,096 channels. `attention` (layers 5, 15, 25, 35): q = W_q x
[32 heads, 64], k, v = W_k x, W_v x [8 key heads, 64] (key head j serves
query heads 4j .. 4j + 3), scores q . k x `attention_multiplier` (1/64,
not 64^-1/2) under the causal mask, softmax, W_o; no bias, no QK norm,
no window and **no rotation** (`position_embedding_type: "nope"`):
position comes from the Mamba layers around them. No experts, no MTP
module.

**The prefill reads the prompt in parts** of `prefill_part` positions
(`lm_common.prefill_in_parts`), as `glm_dsa.prefill` does: the whole parts
are one scanned body, what is left over a body of its own. A part goes through
all 40 layers. A Mamba layer enters it with the float32 matrix state and
the convolution tail the part before left (`mamba2.mixer` takes both),
so 65,536 tokens hold a part's chunk weights and SwiGLU middle at a time
and not a prompt's (4.3 GB and 2.1 GB a layer, whole). An attention layer
writes the part's keys and values at their positions and attends the
part's queries over every position so far, `causal_attention` with fewer
queries than keys (query i sees keys up to i + M - N). M is a shape, and
the part's number is traced under the scan: the scanned body holds one
call a possible M (`lax.switch` over the whole parts' counts), each with
its own entry in the route log, and each takes only the blocks its own
triangle has.

**The decode** walks the 40 layers one after another, weights a tree a
layer, every state and cache read and written where it lies in
`lm_common.decode_loop`'s carry (no stack handed back by a scan: PERF.md
section 6, PR 49): `mamba2.ssm_step` and `decode_attention.attend_xla`
for the two kinds, the tied head over the whole vocabulary.

A request's state (`state_shapes`): `kv`, one `[2, key heads, positions,
d]` array an attention layer, which grows; `ssm` and `conv`, one `[H, P,
N]` float32 state and one `[kernel - 1, inner + 2 G N]` tail a Mamba
layer, which do not.

The model is held whole: all 40 layers, all `vocab_size` ids, nothing a
share of anything.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..ops.attention import causal_attention
from ..ops.decode_attention import attend_xla, position_valid
from . import mamba2
from .lm_common import (
    LanguageModel,
    count_params,
    decode_loop,
    init_from_shapes,
    mlp_shapes,
    nbytes,
    parts_of,
    prefill_in_parts,
    rms_norm,
    swiglu,
    zeros,
)

PUBLISHED_LAYER_TYPES = tuple(
    "attention" if index % 10 == 5 else "mamba" for index in range(40))


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    """The published `config.json`'s shape keys under their own names
    (`num_hidden_layers` is `len(layer_types)`; `mamba_expand`,
    `rope_theta` and `max_position_embeddings` are read by nothing), the
    family code's hard-wired ranges of a Mamba layer's initial step, and
    `prefill_part`, the positions a part of the prompt has."""

    hidden_size: int = 2048
    layer_types: tuple = PUBLISHED_LAYER_TYPES
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    shared_intermediate_size: int = 8192
    vocab_size: int = 100352
    rms_norm_eps: float = 1e-5
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 0.015625
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    prefill_part: int = 8192

    def __post_init__(self):
        unknown = sorted(set(self.layer_types) - {"mamba", "attention"})
        if unknown:
            raise ValueError(f"a layer is mamba or attention, not {unknown}")

    @property
    def num_hidden_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def layers_of(self, kind: str) -> list[int]:
        """The published indices of the layers of one kind."""
        return [i for i, k in enumerate(self.layer_types) if k == kind]

    @property
    def mamba_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_channels(self) -> int:
        return self.mamba_inner + 2 * self.mamba_n_groups * self.mamba_d_state


# --- parameters -----------------------------------------------------------


def _layer_shapes(cfg: GraniteHybridConfig, kind: str) -> dict[str, Any]:
    h = cfg.hidden_size
    if kind == "mamba":
        mixer = mamba2.shapes(h, cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_n_groups,
                              cfg.mamba_d_state, cfg.mamba_d_conv)
    else:
        heads, kv = cfg.num_attention_heads * cfg.head_dim, cfg.num_key_value_heads * cfg.head_dim
        mixer = {"w_q": ((h, heads), h), "w_k": ((h, kv), h), "w_v": ((h, kv), h),
                 "w_o": ((heads, h), heads)}
    return {"norm1": ((h,), None), "mamba" if kind == "mamba" else "attn": mixer,
            "norm2": ((h,), None), "mlp": mlp_shapes(h, cfg.shared_intermediate_size)}


def param_shapes(cfg: GraniteHybridConfig) -> dict[str, Any]:
    """The tree's shapes with each weight's fan-in (None: a norm's scale,
    initialised to one); `layers` has an entry a published layer. The
    embedding is the head too, so it is drawn as a head is: over the
    hidden size."""
    return {
        "embed": ((cfg.vocab_size, cfg.hidden_size), cfg.hidden_size),
        "layers": tuple(_layer_shapes(cfg, kind) for kind in cfg.layer_types),
        "final_norm": ((cfg.hidden_size,), None),
    }


def param_count(cfg: GraniteHybridConfig) -> int:
    return count_params(param_shapes(cfg))


@partial(jax.jit, static_argnames=("cfg", "kind", "dtype"))
def _init_layer(key, *, cfg: GraniteHybridConfig, kind: str, dtype):
    """One layer's weights in one program: the 40 layers are two programs
    (what Nemotron-H's `_init_segment` is for: a weight a program is a
    compile a weight on a cold start)."""
    layer = init_from_shapes(_layer_shapes(cfg, kind), key, dtype)
    if kind == "mamba":
        layer["mamba"].update(mamba2.init_steps(
            jax.random.fold_in(key, 1), layer["mamba"]["a_log"].shape, cfg.time_step_min,
            cfg.time_step_max, cfg.time_step_floor))
    return layer


def init_params(cfg: GraniteHybridConfig, key, dtype=jnp.float32) -> dict[str, Any]:
    """Seeded random weights in `dtype` (`lm_common.init_from_shapes`:
    normal, fan_in^-1/2), a layer a key; a Mamba layer's `a_log`,
    `dt_bias` and `d` by the layer's published initialisation
    (`mamba2.init_steps`), float32 whatever `dtype`."""
    ends = {name: spec for name, spec in param_shapes(cfg).items() if name != "layers"}
    dtype = jnp.dtype(dtype)
    return {
        **init_from_shapes(ends, jax.random.fold_in(key, cfg.num_hidden_layers), dtype),
        "layers": tuple(
            _init_layer(jax.random.fold_in(key, index), cfg=cfg, kind=kind, dtype=dtype)
            for index, kind in enumerate(cfg.layer_types)),
    }


# --- a request's state ----------------------------------------------------


def state_shapes(cfg: GraniteHybridConfig, cache_len: int, dtype) -> dict[str, tuple]:
    """The tree a request carries from its prefill through its decode:
    `kv` an entry an attention layer, `ssm` and `conv` an entry a Mamba
    layer, in published order. A leaf a layer, never a stack of several:
    the decode's loop carries the tree, and a leaf is updated where it
    lies."""
    mamba_layers = len(cfg.layers_of("mamba"))
    matrix = (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state)
    kv = (2, cfg.num_key_value_heads, cache_len, cfg.head_dim)
    tail = (cfg.mamba_d_conv - 1, cfg.conv_channels)
    return {
        "kv": (jax.ShapeDtypeStruct(kv, dtype),) * len(cfg.layers_of("attention")),
        "ssm": (jax.ShapeDtypeStruct(matrix, jnp.float32),) * mamba_layers,
        "conv": (jax.ShapeDtypeStruct(tail, dtype),) * mamba_layers,
    }


# --- the layer ------------------------------------------------------------


def embed(cfg: GraniteHybridConfig, params, ids):
    """h_0 [T, hidden]: the ids' rows times `embedding_multiplier`."""
    rows = params["embed"][ids]
    return (rows.astype(jnp.float32) * cfg.embedding_multiplier).astype(rows.dtype)


def head(cfg: GraniteHybridConfig, params, h):
    """Float32 logits of h [T, hidden] over the whole vocabulary: the
    final norm, the embedding read as the output matrix where it lies
    (the head is tied: a product over its last axis, nothing is turned
    round), over `logits_scaling`."""
    with jax.named_scope("head"):
        h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
        logits = jax.lax.dot_general(
            h, params["embed"], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        return logits / cfg.logits_scaling


def _add(cfg: GraniteHybridConfig, h, out):
    """h + `residual_multiplier` . out, the sum float32 and stored as h is."""
    return (h.astype(jnp.float32) + cfg.residual_multiplier * out.astype(jnp.float32)
            ).astype(h.dtype)


def feed_forward(cfg: GraniteHybridConfig, layer: dict, h):
    """The layer's second sublayer, the dense SwiGLU under its own norm."""
    with jax.named_scope("mlp"):
        return _add(cfg, h, swiglu(rms_norm(h, layer["norm2"], cfg.rms_norm_eps), layer["mlp"]))


def mamba(cfg: GraniteHybridConfig, p, x, tail, state):
    """(output, tail, state): `mamba2.mixer` at this model's sizes."""
    return mamba2.mixer(
        p, x, tail, state, cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_n_groups,
        cfg.mamba_d_state, cfg.mamba_chunk_size, cfg.rms_norm_eps)


def mamba_layer(cfg: GraniteHybridConfig, layer: dict, h, tail, state):
    """A Mamba layer, both sublayers, over the layer's tail and state:
    (h, tail, state)."""
    with jax.named_scope("mamba"):
        out, tail, state = mamba(
            cfg, layer["mamba"], rms_norm(h, layer["norm1"], cfg.rms_norm_eps), tail, state)
    return feed_forward(cfg, layer, _add(cfg, h, out)), tail, state


def attention_layer(cfg: GraniteHybridConfig, layer: dict, h, kv, attn):
    """An attention layer, both sublayers; `attn(p, x, kv) -> (output,
    kv)` is the mixer's form (a part of a prompt, or one token).
    Returns (h, kv)."""
    with jax.named_scope("attn"):
        out, kv = attn(layer["attn"], rms_norm(h, layer["norm1"], cfg.rms_norm_eps), kv)
    return feed_forward(cfg, layer, _add(cfg, h, out)), kv


def _qkv(cfg, p, x):
    tokens = x.shape[0]
    q = (x @ p["w_q"]).reshape(tokens, cfg.num_attention_heads, cfg.head_dim)
    k = (x @ p["w_k"]).reshape(tokens, cfg.num_key_value_heads, cfg.head_dim)
    v = (x @ p["w_v"]).reshape(tokens, cfg.num_key_value_heads, cfg.head_dim)
    return q, k, v


def attn_part(cfg, start, key_counts: tuple, p, x, kv):
    """A part of the prompt, x [P, hidden] from position `start`: its keys
    and values written into `kv` at their positions, its queries over the
    positions 0 .. `start` + P - 1. `key_counts` are the values `start` +
    P can take, in the order of `start` // P: a call of
    `causal_attention` each, of which the part runs its own (one count:
    `start` is that call's and nothing is switched). Returns (output [P,
    hidden], kv)."""
    q, k, v = _qkv(cfg, p, x)
    kv = jax.lax.dynamic_update_slice(
        kv, jnp.stack([k, v]).transpose(0, 2, 1, 3), (0, 0, start, 0))

    def over(count: int):
        def attend(q, kv):
            keys, values = (kv[i, :, :count].transpose(1, 0, 2)[None] for i in range(2))
            return causal_attention(q[None], keys, values, scale=cfg.attention_multiplier)[0]

        return attend

    calls = [over(count) for count in key_counts]
    out = calls[0](q, kv) if len(calls) == 1 else jax.lax.switch(
        start // x.shape[0], calls, q, kv)
    return out.reshape(x.shape[0], -1) @ p["w_o"], kv


def attn_cached(cfg, position, p, x, kv):
    """One new token x [1, hidden] at `position`: its key and value
    written into `kv` [2, key heads, positions, d], attention over the
    positions up to it (`decode_attention.attend_xla`, a key head serving
    its 4 queries). Returns (output [1, hidden], kv)."""
    q, k, v = _qkv(cfg, p, x)
    kv = jax.lax.dynamic_update_slice(
        # one token: [2, 1, heads, d] and [2, heads, 1, d] are the same bytes
        kv, jnp.stack([k, v]).reshape(2, cfg.num_key_value_heads, 1, cfg.head_dim),
        (0, 0, position, 0))
    valid = position_valid(jnp.asarray(position).reshape(1), kv.shape[2])
    out = attend_xla(q, kv[None], (0,), valid, scale=cfg.attention_multiplier)
    return out.reshape(1, -1) @ p["w_o"], kv


def walk(cfg: GraniteHybridConfig, layers: tuple, h, cache: dict, attn):
    """h [T, hidden] through the 40 layers over the request's state, one
    after another, each matrix a whole array read where it lies and each
    state updated where it lies in the caller's carry; `attn` as
    `attention_layer` takes it. Returns (h, cache).

    Layer by layer in the prefill too. A run of Mamba layers (5, 9, 9, 9,
    4) under one `lax.scan`, as `nemotron_h.scanned_run` has its pairs,
    needs the run's weights as one stack: stacked inside the program
    that is a second copy of them beside the tree (1.37 GB for nine
    layers, and hoisted out of the parts' scan all 36: 5.5 GB), and a
    stack in the tree is what the decode must not read (PERF.md section
    6, PR 49)."""
    kv, ssm, conv = (list(cache[name]) for name in ("kv", "ssm", "conv"))
    kv_at = ssm_at = 0
    # jitted here and not at the module's level: a program traces and lowers the
    # Mamba layer once for its 36, and a new trace of the program (the parity
    # script's, under a patched `mamba2.mixer`) traces it anew
    one_mamba = jax.jit(partial(mamba_layer, cfg))
    for index, layer in enumerate(layers):
        with jax.named_scope(f"layer_{index}"):
            if "mamba" in layer:
                h, conv[ssm_at], ssm[ssm_at] = one_mamba(layer, h, conv[ssm_at], ssm[ssm_at])
                ssm_at += 1
            else:
                h, kv[kv_at] = attention_layer(cfg, layer, h, kv[kv_at], attn)
                kv_at += 1
    return h, {"kv": tuple(kv), "ssm": tuple(ssm), "conv": tuple(conv)}


# --- the two programs -----------------------------------------------------


class Prefill(NamedTuple):
    logits: jax.Array   # [vocab] float32, at the prompt's last position
    cache: dict         # `state_shapes`: the request's state after the prompt


class Decode(NamedTuple):
    ids: jax.Array      # [steps]
    cache: dict         # the state it was given, after the steps
    logits: jax.Array | None  # [steps, vocab] float32, after id i; under `collect`


def _part(cfg, params, cache, ids, start, key_counts: tuple):
    """One part of the prompt, `ids` [P] from position `start`, through
    every layer over the state the parts before left. Returns (cache,
    the residual stream [P, hidden])."""
    h, cache = walk(cfg, params["layers"], embed(cfg, params, ids), cache,
                    partial(attn_part, cfg, start, key_counts))
    return cache, h


@partial(jax.jit, static_argnames=("cfg", "cache_len", "collect"))
def prefill(cfg: GraniteHybridConfig, params, ids, *, cache_len: int, collect: bool = False):
    """The prompt `ids` [T] in parts (`prefill_in_parts`): the whole parts
    one scanned body, what is left a body of its own, each over the state
    as the parts before left it. Returns the logits at the last position and
    the request's state (allocated here, once). `collect` keeps nothing
    more: what the parity check compares of a prefill, the states and the
    keys and values, is the state."""
    del collect
    cache = zeros(state_shapes(cfg, cache_len, params["embed"].dtype))

    def part(cache, cuts, start, ends):
        cache, h = _part(cfg, params, cache, *cuts, start, ends)
        return cache, h[-1]

    cache, lasts = prefill_in_parts(part, cache, (ids,), cfg.prefill_part)
    return Prefill(head(cfg, params, lasts[-1:])[0], cache)


def decode_step(cfg, params, cache, token, position):
    """One token through every layer over the request's state. Returns
    (logits [vocab], cache)."""
    h, cache = walk(cfg, params["layers"], embed(cfg, params, token[None]), cache,
                    partial(attn_cached, cfg, position))
    return head(cfg, params, h)[0], cache


@partial(jax.jit, static_argnames=("cfg", "steps", "collect"), donate_argnames=("cache",))
def decode(cfg: GraniteHybridConfig, params, cache, logits, start, key, temperature, *,
           steps: int, collect: bool = False):
    """`steps` dependent decode steps in one program, from the prefill's
    `logits` at position `start - 1`: draw id i from the logits, run it
    through the model at position `start + i`. Always `steps` ids, no
    early stop. The state tree is donated, carried through the loop and
    handed back as `cache`. Returns the ids and, under `collect`, every
    step's logits (the logits after id i)."""

    def step(cache, token, position):
        logits, cache = decode_step(cfg, params, cache, token, position)
        return logits, cache, (), logits if collect else None

    cache, ids, _, kept = decode_loop(step, cache, logits, start, key, temperature, steps)
    return Decode(ids, cache, kept)


class GraniteHybrid(LanguageModel):
    """What a bundle's `lm` part is (the contract is in `lm_common`)."""

    _init = staticmethod(init_params)
    _prefill = staticmethod(prefill)
    _decode = staticmethod(decode)

    @property
    def layer_passes(self) -> int:
        return self.cfg.num_hidden_layers

    def read_back(self, prefill: Prefill, decode: Decode) -> tuple:
        """Nothing beside the ids: no router, no draft, no selection."""
        return ()

    def describe(self, cache_len: int) -> dict[str, int]:
        cfg, shapes = self.cfg, state_shapes(self.cfg, cache_len, self.dtype)
        return {
            "layers": cfg.num_hidden_layers,
            "mamba_layers": len(cfg.layers_of("mamba")),
            "attention_layers": len(cfg.layers_of("attention")),
            "prefill_part": cfg.prefill_part,
            "cache_bytes": sum(map(nbytes, shapes["kv"])),
            "state_bytes": sum(map(nbytes, shapes["ssm"] + shapes["conv"])),
            "tied_head_bytes": cfg.vocab_size * cfg.hidden_size * self.dtype.itemsize,
        }

    def report(self, prompt_tokens: int, new_tokens: int, cache_len: int) -> dict:
        """`describe`, the parts the prompt was read in and the chunks a
        Mamba layer's prefill walked over them (a part's last chunk may
        be short)."""
        cfg = self.cfg
        whole, left = parts_of(prompt_tokens, cfg.prefill_part)
        lengths = [cfg.prefill_part] * whole + [left] * bool(left)
        return {
            **self.describe(cache_len),
            "prefill_parts": len(lengths),
            "prefill_chunks": sum(-(-length // cfg.mamba_chunk_size) for length in lengths),
        }
