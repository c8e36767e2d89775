"""What the language models share (`deepseek_v2.py`, `ouro.py`,
`solar_open2.py`, `k_exaone.py`, `ling_flash.py`, `nemotron_h.py`, `glm_dsa.py`): the blocks and helpers
they are written from, the one initialisation rule, sampling on the
device, the rule by which a drafted token is kept or replaced, the
decode loop, and the stand-in tokenizer.

A bundle's `lm` part is an object with this contract (`LanguageModel`
below holds what every model's class has alike), which is all that
`graph/nodes_text.TextGenerate` knows of a model:

- `cfg`, `tokenizer`, `init(key, dtype)` (which also records `dtype`,
  the one the weights and the cache are stored in);
- `prefill(params, ids, cache_len, collect)` and `decode(params, cache,
  logits, start, key, steps, temperature, collect, draft_tokens)`: the
  two programs; what they return has `.cache` and `.logits` (the
  prefill's) and `.ids` (the decode's: `[steps]`, always). `.cache` is a
  request's whole state, one array or a tree of them, handed from one
  program to the other as it is; the node never looks inside;
- `draft_tokens_max`: tokens a decode step may draft and verify beside
  the one it emits anyway (0: no draft module, and `decode` refuses any
  other `draft_tokens`);
- `read_back(prefill, decode)`: the device arrays a request reads back
  beside the ids, in the one `device.wait`;
- `report(prompt_tokens, new_tokens, cache_len, *read)`: everything the
  model says on `node.TextGenerate`: what its own shapes and dtypes give
  (`layers`; `cache_bytes`, what grows with `cache_len`; `state_bytes`,
  what does not), and what `read_back`'s arrays do as the host got them.
  The first part is also callable alone, `describe(cache_len)`: the
  benchmark's reader tests hold their counts by hand against it;
- `layer_passes`, the layer bodies one token walks through, from which
  `counted` answers what the node's counters take where `report` does
  not say otherwise (`decode_steps`, `prefill_layer_passes`,
  `decode_layer_passes`: a model whose step runs more than one position).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import route_log


# --- blocks ---------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    normed = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (normed * scale.astype(jnp.float32)).astype(x.dtype)


def clamped_silu_product(gate: jax.Array, up: jax.Array, limit: float = 0.0) -> jax.Array:
    """silu(gate) * up, a SwiGLU's middle; under a `limit` > 0 the gate
    is held to at most it and up to within it either way first (0: no
    clamp)."""
    if limit:
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return jax.nn.silu(gate) * up


def swiglu(x: jax.Array, p: dict, limit: float = 0.0) -> jax.Array:
    gate, up = jnp.split(x @ p["w_gate_up"], 2, axis=-1)
    return clamped_silu_product(gate, up, limit) @ p["w_down"]


def relu2_mlp(x: jax.Array, p: dict) -> jax.Array:
    """An ungated feed-forward part, two matrices: relu(x W_up)^2 W_down."""
    return jnp.square(jax.nn.relu(x @ p["w_up"])) @ p["w_down"]


def short_conv(projected: jax.Array, filters: jax.Array, tail: jax.Array):
    """A causal depth-wise convolution over the token axis, float32:
    `projected` [T, channels], `filters` [kernel, channels], `tail`
    [kernel - 1, channels] the inputs of the tokens before. Returns
    (the sums [T, channels] float32, before any bias or activation, and
    the inputs themselves, tail first, [kernel - 1 + T, channels]: the
    rows after token t's are the tail token t leaves). What a KDA layer
    runs over its q, k and v projections (`kda.conv_qkv`) and a Mamba-2
    layer over x, B and C (`mamba2.mixer_inputs`)."""
    tokens, kernel = projected.shape[0], filters.shape[0]
    window = jnp.concatenate([tail, projected.astype(tail.dtype)], axis=0)
    filters = filters.astype(jnp.float32)
    mixed = sum(window[i:i + tokens].astype(jnp.float32) * filters[i] for i in range(kernel))
    return mixed, window


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate [..., T, (heads,) rope] by its position, the two halves of
    the last axis as the pair's members (`rotate_half`)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    if x.ndim == cos.ndim + 1:  # a heads axis between T and rope
        cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def apply_rope_pairs(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate [..., T, (heads,) rope] by its position, channels 2i and
    2i + 1 as the pair's members (the interleaved form, as a checkpoint
    stores it)."""
    x1, x2 = x[..., 0::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
    if x.ndim == cos.ndim + 1:  # a heads axis between T and rope
        cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.stack(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).reshape(x.shape).astype(x.dtype)


def rope_tables(theta: float, head_dim: int, positions: jax.Array):
    """cos and sin, [T, head_dim / 2] float32, of the positions' angles at
    the frequencies theta^(-2i / head_dim); no scaling."""
    inv_freq = 1.0 / theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    angles = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)
    return jnp.cos(angles), jnp.sin(angles)


def head(cfg, params, h, norm=None):
    """Float32 logits of h [T, hidden]: a norm (the final one unless
    another scale is given), then the output embedding."""
    with jax.named_scope("head"):
        h = rms_norm(h, params["final_norm"] if norm is None else norm, cfg.rms_norm_eps)
        return jnp.dot(h, params["head"], preferred_element_type=jnp.float32)


def sample(logits, key, temperature):
    """The next id from float32 logits: the largest at temperature 0,
    else a draw from softmax(logits / temperature). `temperature` is a
    traced scalar, so every value runs the one program."""
    drawn = jax.random.categorical(key, logits / jnp.where(temperature > 0, temperature, 1.0))
    return jnp.where(temperature > 0, drawn, jnp.argmax(logits)).astype(jnp.int32)


# --- drafting: the lossless rule of a self-speculative step ----------------


def mtp_input(cfg, params, h, tokens):
    """What a multi-token-prediction module's layer takes (DeepSeek-V3's
    form, which K-EXAONE's and Ling-3.0-flash's modules share): u [T,
    hidden] = W_eh [rms_e(E[x_{i+1}]) ; rms_h(h_i)] of the residual
    streams h [T, hidden] after the last main layer and the tokens that
    follow each [T]; `params["mtp"]` holds `embed_norm`, `hidden_norm`
    and `w_eh`."""
    p = params["mtp"]
    both = jnp.concatenate([
        rms_norm(params["embed"][tokens], p["embed_norm"], cfg.rms_norm_eps),
        rms_norm(h, p["hidden_norm"], cfg.rms_norm_eps),
    ], axis=-1)
    return both @ p["w_eh"]


def accept_probability(p, q, draft):
    """With which probability a draft drawn from q stands for a draw
    from p: min(1, p(draft) / q(draft))."""
    return jnp.minimum(1.0, p[draft] / q[draft])


def residual(p, q):
    """What a rejected draft is replaced from: max(p - q, 0) over its
    sum (p itself where the two are equal and nothing is left)."""
    left = jnp.maximum(p - q, 0.0)
    total = left.sum()
    return jnp.where(total > 0, left / jnp.where(total > 0, total, 1.0), p)


def verify(logits, draft_logits, draft, key, temperature):
    """The lossless rule over the main model's logits [2, vocab] at the
    last emitted token and at the draft, and the logits the draft was
    drawn from. Returns (kept, the token after the last emitted one, the
    token after that, which counts only where the draft was kept). At
    temperature 0 the draft is kept iff it is the main model's largest."""
    key_accept, key_again, key_next = jax.random.split(key, 3)
    safe = jnp.where(temperature > 0, temperature, 1.0)
    p = jax.nn.softmax(logits[0] / safe)
    q = jax.nn.softmax(draft_logits / safe)
    kept = jnp.where(
        temperature > 0,
        jax.random.uniform(key_accept) < accept_probability(p, q, draft),
        draft == jnp.argmax(logits[0]))
    again = jnp.where(
        temperature > 0,
        jax.random.categorical(key_again, jnp.log(residual(p, q))),
        jnp.argmax(logits[0])).astype(jnp.int32)
    return kept, jnp.where(kept, draft, again), sample(logits[1], key_next, temperature)


# --- a request's state and the decode loop --------------------------------


def nbytes(shape: jax.ShapeDtypeStruct) -> int:
    return math.prod(shape.shape) * jnp.dtype(shape.dtype).itemsize


def zeros(shapes):
    """A `ShapeDtypeStruct`, or a dict or tuple of them, as arrays of
    zeros, made in the order they are written in."""
    if isinstance(shapes, jax.ShapeDtypeStruct):
        return jnp.zeros(shapes.shape, shapes.dtype)
    if isinstance(shapes, dict):
        return {name: zeros(s) for name, s in shapes.items()}
    return tuple(zeros(s) for s in shapes)


def decode_loop(step, cache, logits, start, key, temperature, steps: int):
    """`steps` dependent one-token steps as one `fori_loop`, from the
    prefill's `logits` at position `start - 1`: draw id i from the logits
    with the key folded by i, run it through `step(cache, token, start +
    i)`, which returns (logits, cache, a tree the loop sums over the
    steps, a tree of which the loop keeps every step's or None). Always
    `steps` ids, no early stop. Returns (cache, ids [steps], the summed
    tree, the kept rows stacked or None)."""

    def body(i, carry):
        cache, logits, ids, tally, kept = carry
        token = sample(logits, jax.random.fold_in(key, i), temperature)
        logits, cache, tally_i, kept_i = step(cache, token, start + i)
        if kept is not None:
            kept = jax.tree_util.tree_map(lambda rows, row: rows.at[i].set(row), kept, kept_i)
        return (cache, logits, ids.at[i].set(token),
                jax.tree_util.tree_map(jnp.add, tally, tally_i), kept)

    # what a step adds and keeps, as shapes: the step's own word. Nothing is run, and
    # the routes this extra tracing logs are dropped: the loop's body logs the program's
    with route_log():
        _, _, tally, kept = jax.eval_shape(
            step, cache, jax.ShapeDtypeStruct((), jnp.int32), start)
    if kept is not None:
        kept = zeros(jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct((steps, *s.shape), s.dtype), kept))
    carry = (cache, logits, jnp.zeros((steps,), jnp.int32), zeros(tally), kept)
    cache, _, ids, tally, kept = jax.lax.fori_loop(0, steps, body, carry)
    return cache, ids, tally, kept


# --- parameters -----------------------------------------------------------
# A tree of specs: each leaf ((shape), fan_in), fan_in None for a norm's
# scale, initialised to one.


def mlp_shapes(hidden: int, width: int) -> dict:
    """One SwiGLU's specs: gate and up side by side, then down."""
    return {"w_gate_up": ((hidden, 2 * width), hidden), "w_down": ((width, hidden), width)}


def is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def count_params(shapes: dict[str, Any]) -> int:
    specs = jax.tree_util.tree_leaves(shapes, is_leaf=is_spec)
    return sum(math.prod(shape) for shape, _ in specs)


@partial(jax.jit, static_argnames=("shape", "std", "dtype"))
def _normal(key, shape, std, dtype):
    if len(shape) >= 3 and math.prod(shape) >= 2**28:
        # a stack of experts or of layers (or a run's stack of stacks),
        # one at a time: the float32 draw of a whole stack (2.5 GB at
        # the published widths) is never alive at once
        keys = jax.random.split(key, shape[0])
        return jax.lax.map(
            lambda k: (jax.random.normal(k, shape[1:], jnp.float32) * std).astype(dtype), keys
        )
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def init_from_shapes(shapes: dict[str, Any], key, dtype=jnp.float32) -> dict[str, Any]:
    """Seeded random weights built in `dtype`, weight by weight: normal
    with standard deviation fan_in^-1/2, so activations keep their scale
    through the depth (and a router's logits spread by about one)."""
    specs, treedef = jax.tree_util.tree_flatten(shapes, is_leaf=is_spec)
    dtype = jnp.dtype(dtype)
    leaves = []
    for index, (shape, fan_in) in enumerate(specs):
        if fan_in is None:
            leaves.append(jnp.ones(shape, dtype))
        else:
            leaves.append(
                _normal(jax.random.fold_in(key, index), shape, float(fan_in) ** -0.5, dtype)
            )
    return jax.tree_util.tree_unflatten(treedef, leaves)


# --- the stand-in tokenizer -----------------------------------------------


class ByteTokenizer:
    """A stand-in for the published tokenizers, which are not in the
    sandbox: deterministic and byte-level, into the first ids of the
    vocabulary (or of its slice). `encode`: id 0 (begin of sentence), then
    1 + b for each byte b of the text's UTF-8. `decode`: ids 1..256 give
    their byte where it is printable ASCII and nothing otherwise; id 0
    gives nothing; every other id n gives a space and then n - 257 written
    in base 26 with the letters a..z, least digit first. So any ids come
    back as lower-case words that CLIP's BPE can tokenise."""

    BOS = 0
    BYTES = 256

    def encode(self, text: str) -> list[int]:
        return [self.BOS] + [1 + b for b in text.encode("utf-8")]

    def decode(self, ids) -> str:
        pieces = []
        for n in map(int, ids):
            if n == self.BOS:
                continue
            if n <= self.BYTES:
                pieces.append(chr(n - 1) if 32 <= n - 1 < 127 else "")
                continue
            n -= self.BYTES + 1
            word = chr(97 + n % 26)
            while n >= 26:
                n //= 26
                word += chr(97 + n % 26)
            pieces.append(" " + word)
        return "".join(pieces).strip()


# --- what a model's class starts from --------------------------------------


class LanguageModel:
    """The part of the contract that is the same for every model: the
    configuration with the module's three functions bound to it
    (`init_params(cfg, key, dtype)`, the jitted `prefill(cfg, params, ids,
    *, cache_len, collect)` and `decode(cfg, params, cache, logits, start,
    key, temperature, *, steps, collect)`, which a subclass names as
    `_init`, `_prefill`, `_decode`), the tokenizer, the dtype the weights
    and the cache are stored in, and `counted`. A subclass adds
    `layer_passes`, `read_back` and `report` (over its `describe`), and
    where it has a draft module `draft_tokens_max` and a `_decode` that
    takes `draft_tokens`."""

    _init = _prefill = _decode = None
    draft_tokens_max = 0

    def __init__(self, cfg):
        self.cfg = cfg
        self.tokenizer = ByteTokenizer()
        self.dtype = jnp.dtype(jnp.float32)  # until `init` says otherwise

    def init(self, key, dtype=jnp.float32):
        self.dtype = jnp.dtype(dtype)
        return self._init(self.cfg, key, dtype)

    def counted(self, report: dict, prompt_tokens: int, new_tokens: int) -> dict[str, int]:
        """What the node's counters take of a request: the model's own
        `report` where it says them, else a step a token and each token
        through `layer_passes` layer bodies."""
        defaults = {
            "decode_steps": new_tokens,
            "prefill_layer_passes": prompt_tokens * self.layer_passes,
            "decode_layer_passes": new_tokens * self.layer_passes,
        }
        return {key: report.get(key, value) for key, value in defaults.items()}

    def prefill(self, params, ids, cache_len: int, collect: bool = False):
        return self._prefill(self.cfg, params, ids, cache_len=cache_len, collect=collect)

    def decode(self, params, cache, logits, start: int, key, steps: int, temperature: float,
               collect: bool = False, draft_tokens: int = 0):
        if not 0 <= draft_tokens <= self.draft_tokens_max:
            raise ValueError(
                f"draft_tokens {draft_tokens}: {type(self).__name__} "
                + (f"drafts at most {self.draft_tokens_max} a step" if self.draft_tokens_max
                   else "has no draft module; only 0 (one token a step) is served"))
        drafting = {"draft_tokens": draft_tokens} if self.draft_tokens_max else {}
        return self._decode(
            self.cfg, params, cache, logits, jnp.int32(start), key, jnp.float32(temperature),
            steps=steps, collect=collect, **drafting,
        )
