"""Ouro: a looped language model. One stack of `num_hidden_layers`
decoder layers is walked `total_ut_steps` times for every token, the
same weights each time; the final norm closes every pass and its output
feeds the next; a 1-wide gate on each pass's output gives the
probability of stopping there.

    x = E[ids]
    for t in 1..T:                          # the same layers each pass
      for l in 1..L:                        # cache slot (t, l)
        a = rms(x; g1_l);  q, k, v = a Wq_l, a Wk_l, a Wv_l
        q, k = rope(q), rope(k)             # rotate-half, the whole head
        o = softmax_causal(q k^T / sqrt(d)) v
        x = x + rms(o Wo_l; g2_l)           # a norm before AND after
        m = rms(x; g3_l)
        x = x + rms((silu(m Wg_l) * (m Wu_l)) Wd_l; g4_l)
      x = h_t = rms(x; g_final)
      lambda_t = sigmoid(h_t . w_gate + b_gate)
    logits = h_T W_head
    p(t) = lambda_t prod_{j<t}(1 - lambda_j), t < T;  p(T) = prod_{j<T}(1 - lambda_j)

Pure functions over a parameter tree, as `deepseek_v2.py` is, with two
differences that the loop forces:

- the layers' weights are *stacked* (`params["layers"]` is one tree whose
  leaves lead with `[num_hidden_layers, ...]`) and walked by `lax.scan`
  inside a second `lax.scan` over the passes that reuses the same stack,
  so the prefill and the decode hold the layer body once, not 192 times;
- a token's keys and values differ at every pass, so the cache has a
  slot for each (pass, layer): `[T, L, 2, heads, positions, head_dim]`
  (keys, then values, each head's positions together, which is the order
  the prefill's attention wants its keys in: any other and the TPU's
  compiler keeps that order inside the loop and copies all of the cache
  on the way out), carried through every loop and written in place.
  `decode` takes it by donation and hands it back; a decode step reads
  each slot once, out of the carried cache where it lies
  (`ops/decode_attention`).

The published rule leaves the loop at the first pass whose cumulative
p reaches `early_exit_threshold`; at the published threshold, 1, that is
the last pass, so every token runs all T and the logits come from h_T.
The gate is computed and reported (`exit`), never acted on; a threshold
below 1 is refused, because a token that leaves early has no keys and
values in the later passes' slots for the tokens after it to read.

Weights, the cache and every product's operands are in the storage dtype
(bfloat16 on the chip); the residual stream x and every norm are float32:
x grows to ten times the size of what a layer adds to it, and rounded to
bfloat16 after each of a token's 384 additions it loses a few per cent of
every addend (on the chip the logits' relative L2 against the float32
reference read 0.18 with x in bfloat16).

Parameter layout where it departs from the published checkpoint's (a
fixed split of weight columns): `w_qkv` is q, k and v's projections side
by side, `w_gate_up` the gate's and up's.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

from ..ops import decode_attention
from ..ops.attention import dot_product_attention
from .lm_common import (
    LanguageModel,
    apply_rope,
    count_params,
    decode_loop,
    init_from_shapes,
    rms_norm,
    rope_tables,
    swiglu,
)


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    """The published `config.json`'s shape keys under their own names."""

    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    intermediate_size: int = 5632
    total_ut_steps: int = 4
    early_exit_threshold: float = 1.0
    vocab_size: int = 49152
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 65536

    def __post_init__(self):
        if self.early_exit_threshold < 1:
            raise ValueError(
                f"early_exit_threshold {self.early_exit_threshold} < 1 would let a token "
                "leave the loop before its last pass; the passes it skips would leave "
                "their cache slots unwritten at its position, which every later token "
                "reads. Only the published threshold, 1 (never exit early), is implemented"
            )
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError(
                f"{self.num_key_value_heads} key/value heads for "
                f"{self.num_attention_heads} query heads: grouped queries are not "
                "written (the published model has as many of one as of the other)"
            )

    @property
    def layer_passes(self) -> int:
        return self.total_ut_steps * self.num_hidden_layers

    def cache_shape(self, cache_len: int) -> tuple[int, ...]:
        return (self.total_ut_steps, self.num_hidden_layers, 2,
                self.num_attention_heads, cache_len, self.head_dim)


# --- parameters -----------------------------------------------------------


def param_shapes(cfg: OuroConfig) -> dict[str, Any]:
    """The tree's shapes with each weight's fan-in (None: a norm's
    scale, initialised to one). The gate's bias is drawn like a weight of
    fan-in 1, so that leaving it out shows."""
    h, layers, width = cfg.hidden_size, cfg.num_hidden_layers, cfg.intermediate_size
    heads = cfg.num_attention_heads * cfg.head_dim
    return {
        "embed": ((cfg.vocab_size, h), 1),
        "layers": {
            "attn_norm": ((layers, h), None),
            "w_qkv": ((layers, h, 3 * heads), h),
            "w_o": ((layers, heads, h), heads),
            "attn_out_norm": ((layers, h), None),
            "ffn_norm": ((layers, h), None),
            "w_gate_up": ((layers, h, 2 * width), h),
            "w_down": ((layers, width, h), width),
            "ffn_out_norm": ((layers, h), None),
        },
        "final_norm": ((h,), None),
        "gate": {"w": ((h,), h), "b": ((), 1)},
        "head": ((h, cfg.vocab_size), h),
    }


def param_count(cfg: OuroConfig) -> int:
    return count_params(param_shapes(cfg))


def init_params(cfg: OuroConfig, key, dtype=jnp.float32) -> dict[str, Any]:
    """Seeded random weights in `dtype` (`lm_common.init_from_shapes`)."""
    return init_from_shapes(param_shapes(cfg), key, dtype)


class _LayerList:
    """The stacked layers as a sequence of per-layer trees, each sliced
    out when it is asked for: what `reference/ouro.py` walks, with no
    second copy of the stack alive."""

    def __init__(self, stacked: dict):
        self.stacked = stacked

    def __len__(self) -> int:
        return jax.tree_util.tree_leaves(self.stacked)[0].shape[0]

    def __getitem__(self, index: int) -> dict:
        if not 0 <= index < len(self):
            raise IndexError(index)
        return jax.tree_util.tree_map(lambda leaf: leaf[index], self.stacked)


def unstacked(params: dict) -> dict:
    """The tree with `layers` as a sequence of per-layer trees."""
    return {**params, "layers": _LayerList(params["layers"])}


# --- blocks ---------------------------------------------------------------


def _qkv(cfg, p, x, rope):
    """q, k, v [T, heads, head_dim] of x [T, hidden], q and k rotated."""
    shape = (x.shape[0], cfg.num_attention_heads, cfg.head_dim)
    a = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps).astype(p["w_qkv"].dtype)
    q, k, v = jnp.split(a @ p["w_qkv"], 3, axis=-1)
    return (apply_rope(q.reshape(shape), *rope), apply_rope(k.reshape(shape), *rope),
            v.reshape(shape))


def _after_attention(cfg, p, x, out):
    """The rest of a layer, from the attention's weighted sum [T, heads x
    head_dim]: its output projection, and the SwiGLU, each normed again
    before it joins the residual stream."""
    x = x + rms_norm(out @ p["w_o"], p["attn_out_norm"], cfg.rms_norm_eps)
    with jax.named_scope("mlp"):
        m = rms_norm(x, p["ffn_norm"], cfg.rms_norm_eps).astype(out.dtype)
        # one token's products on the vector: as a [1, 2 x width] matrix the first one's
        # result is re-tiled on a TPU (bfloat16 rows go two to a sublane, so one row is
        # half padding) before the split reads it: 0.095 us a layer, and silu x up
        # 0.14 us longer on the padded row (v5e, PERF.md section 5)
        m = swiglu(m[0], p)[None] if m.shape[0] == 1 else swiglu(m, p)
        return x + rms_norm(m, p["ffn_out_norm"], cfg.rms_norm_eps)


def layer_whole(cfg, p, x, rope):
    """One layer over a whole sequence x [T, hidden] (the prefill's
    form). Returns (x, its keys and values [2, heads, T, head_dim])."""
    with jax.named_scope("attn"):
        q, k, v = _qkv(cfg, p, x, rope)
        out = dot_product_attention(q[None], k[None], v[None], causal=True)[0]
    kv = jnp.stack([k, v]).transpose(0, 2, 1, 3)
    return _after_attention(cfg, p, x, out.reshape(x.shape[0], -1)), kv


def layer_cached(cfg, p, x, rope, cache, slot, position):
    """One layer for one new token x [1, hidden] at `position`: its key
    and value written into the cache's `slot` (pass, layer), attention
    over that slot's positions up to it, read out of the cache where
    they lie (`ops/decode_attention.attend`). Returns (x, cache)."""
    with jax.named_scope("attn"):
        q, k, v = _qkv(cfg, p, x, rope)
        cache = jax.lax.dynamic_update_slice(
            # one token: [2, 1, heads, d] and [2, heads, 1, d] are the same bytes
            cache, jnp.stack([k, v]).reshape(1, 1, 2, cfg.num_attention_heads, 1, cfg.head_dim),
            (*slot, 0, 0, position, 0))
        cache = with_layout_constraint(cache, Layout(major_to_minor=tuple(range(cache.ndim))))
        out = decode_attention.attend(q[0], cache, slot, position)
    return _after_attention(cfg, p, x, out.reshape(1, -1)), cache


def _close_pass(cfg, params, x):
    """What ends a pass over x [T, hidden]: the final norm, whose output
    h_t is the pass's result and the next pass's input, and the exit
    gate's lambda_t [T] (float32)."""
    h = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    with jax.named_scope("gate"):
        gate = params["gate"]
        score = jnp.dot(
            h.astype(jnp.float32), gate["w"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        ) + gate["b"].astype(jnp.float32)
        return h, jax.nn.sigmoid(score)


def exit_distribution(lam: jax.Array) -> jax.Array:
    """p(t) [T, ...] from every pass's lambda_t [T, ...]: the chance of
    stopping at pass t, having passed the gates before it; the last pass
    takes what is left."""
    before = jnp.concatenate(
        [jnp.ones_like(lam[:1]), jnp.cumprod(1.0 - lam[:-1], axis=0)], axis=0)
    return jnp.concatenate([(lam * before)[:-1], before[-1:]], axis=0)


def _loop(cfg, params, x, cache, one_layer):
    """`total_ut_steps` passes over the one stack of layers. `one_layer(
    p, x, cache, slot)` returns (x, cache). Returns (h_T, cache, every
    pass's lambda [T, tokens], every pass's h_t at the last token [T,
    hidden]). The layer's index is a counter in the carry: scanned
    beside the weights it is sliced out of an array, a device operation
    a layer, which one token's body runs 192 times."""
    def one_pass(carry, step):
        def body(carry, p):
            x, cache, index = carry
            with jax.named_scope("layer"):
                return (*one_layer(p, x, cache, (step, index)), index + 1), None

        (x, cache, _), _ = jax.lax.scan(body, (*carry, jnp.int32(0)), params["layers"])
        h, lam = _close_pass(cfg, params, x)
        return (h, cache), (lam, h[-1])

    with jax.named_scope("loop"):
        (h, cache), (lam, hidden) = jax.lax.scan(
            one_pass, (x, cache), jnp.arange(cfg.total_ut_steps))
    return h, cache, lam, hidden


def _head(params, h):
    # its own: `_close_pass` has normed h already, and h is float32 beside stored weights
    with jax.named_scope("head"):
        head = params["head"]
        return jnp.dot(h.astype(head.dtype), head, preferred_element_type=jnp.float32)


# --- the two programs -----------------------------------------------------


class Prefill(NamedTuple):
    logits: jax.Array   # [vocab] float32, at the prompt's last position
    cache: jax.Array    # [T, L, 2, heads, cache_len, head_dim]
    exit: jax.Array     # [T] float32, p(t) summed over the prompt's tokens
    hidden: jax.Array | None  # [T, hidden], h_t at the last position; under `collect`
    exits: jax.Array | None   # [T] float32, p(t) there; under `collect`


class Decode(NamedTuple):
    ids: jax.Array      # [steps]
    exit: jax.Array     # [T] float32, p(t) summed over the steps
    cache: jax.Array    # the cache it was given, the steps' positions written
    logits: jax.Array | None  # [steps, vocab] float32, after id i; under `collect`
    hidden: jax.Array | None  # [steps, T, hidden]; under `collect`
    exits: jax.Array | None   # [steps, T] float32; under `collect`


@partial(jax.jit, static_argnames=("cfg", "cache_len", "collect"))
def prefill(cfg: OuroConfig, params, ids, *, cache_len: int, collect: bool = False):
    """The whole prompt `ids` [N] at once. Returns the logits at its last
    position, the cache (allocated here, once, at `cache_len` positions,
    the first N of every slot written), the exit distribution summed over
    the prompt's tokens and, under `collect` (the parity check's), every
    pass's h_t and p(t) at the last position."""
    tokens = ids.shape[0]
    rope = rope_tables(cfg.rope_theta, cfg.head_dim, jnp.arange(tokens))
    x = params["embed"][ids].astype(jnp.float32)

    def one_layer(p, x, cache, slot):
        x, slot_kv = layer_whole(cfg, p, x, rope)
        cache = jax.lax.dynamic_update_slice(cache, slot_kv[None, None], (*slot, 0, 0, 0, 0))
        # as in `layer_cached`: the attention kernel takes k and v token-major, and
        # the compiler would else order the cache's axes so and copy it whole at the end
        return x, with_layout_constraint(cache, Layout(major_to_minor=tuple(range(cache.ndim))))

    cache = jnp.zeros(cfg.cache_shape(cache_len), params["embed"].dtype)
    h, cache, lam, hidden = _loop(cfg, params, x, cache, one_layer)
    exits = exit_distribution(lam)
    kept = (hidden, exits[:, -1]) if collect else (None, None)
    return Prefill(_head(params, h[-1]), cache, exits.sum(axis=1), *kept)


def decode_step(cfg, params, cache, token, position):
    """One token through every pass and layer over the cache. Returns
    (logits [vocab], cache, h_t [T, hidden], p(t) [T])."""
    rope = rope_tables(cfg.rope_theta, cfg.head_dim, position[None])

    def one_layer(p, x, cache, slot):
        return layer_cached(cfg, p, x, rope, cache, slot, position)

    x = params["embed"][token][None].astype(jnp.float32)
    h, cache, lam, hidden = _loop(cfg, params, x, cache, one_layer)
    return _head(params, h[0]), cache, hidden, exit_distribution(lam[:, 0])


@partial(jax.jit, static_argnames=("cfg", "steps", "collect"), donate_argnames=("cache",))
def decode(cfg: OuroConfig, params, cache, logits, start, key, temperature, *,
           steps: int, collect: bool = False):
    """`steps` dependent decode steps in one program, from the prefill's
    `logits` at position `start - 1`: draw id i from the logits, run it
    through the model at position `start + i`. Always `steps` ids, no
    early stop. The cache is donated and written in place; it comes back
    as `cache` (which is what lets the buffer be reused). Returns the ids,
    the exit distribution summed over the steps and, under `collect`,
    every step's logits (the logits after id i), h_t and p(t)."""

    def step(cache, token, position):
        logits, cache, hidden, exits = decode_step(cfg, params, cache, token, position)
        return logits, cache, exits, (logits, hidden, exits) if collect else None

    cache, ids, exit_sum, kept = decode_loop(step, cache, logits, start, key, temperature, steps)
    return Decode(ids, exit_sum, cache, *(kept or (None, None, None)))


class Ouro(LanguageModel):
    """What a bundle's `lm` part is (the contract is in `lm_common`)."""

    _init = staticmethod(init_params)
    _prefill = staticmethod(prefill)
    _decode = staticmethod(decode)

    @property
    def layer_passes(self) -> int:
        return self.cfg.layer_passes

    def read_back(self, prefill: Prefill, decode: Decode) -> tuple:
        """The exit distribution summed over either program's tokens."""
        return prefill.exit, decode.exit

    def describe(self, cache_len: int) -> dict[str, int]:
        cfg = self.cfg
        return {
            "ut_steps": cfg.total_ut_steps,
            "layers": cfg.num_hidden_layers,
            "cache_slots": cfg.layer_passes,
            "cache_bytes": int(np.prod(cfg.cache_shape(cache_len))) * self.dtype.itemsize,
            "state_bytes": 0,
        }

    def report(self, prompt_tokens: int, new_tokens: int, cache_len: int,
               prefill_exit, decode_exit) -> dict:
        """`describe`, the layer bodies walked in either phase, and where
        the request's tokens would have left the loop had the gate been
        acted on."""
        attrs = {
            **self.describe(cache_len),
            "prefill_layer_passes": prompt_tokens * self.layer_passes,
            "decode_layer_passes": new_tokens * self.layer_passes,
        }
        mass = np.asarray(prefill_exit, np.float64) + np.asarray(decode_exit, np.float64)
        for step, value in enumerate(mass, start=1):
            attrs[f"exit_mass_{step}"] = float(value)
        return attrs
