"""What the language models share (`deepseek_v2.py`, `ouro.py`,
`solar_open2.py`, `k_exaone.py`): the blocks they are written from, the one
initialisation rule, sampling on the device, and the stand-in tokenizer.

A bundle's `lm` part is an object with this contract (`LanguageModel`
below holds what every model's class has alike), which
`graph/nodes_text.TextGenerate` holds every model to:

- `cfg`, `tokenizer`, `init(key, dtype)` (which also records `dtype`,
  the one the weights and the cache are stored in);
- `prefill(params, ids, cache_len, collect)` and `decode(params, cache,
  logits, start, key, steps, temperature, collect, draft_tokens)`: the
  two programs; what they return has `.cache` and `.logits` (the
  prefill's) and `.ids` (the decode's: `[steps]`, always). `.cache` is a
  request's whole state, handed from one program to the other as it is:
  one array, or a tree of arrays of several kinds and dtypes (keys and
  values that grow with the position, a window layer's ring or a
  recurrent layer's state of fixed size); the node never looks inside;
- `draft_tokens_max`: tokens a decode step may draft and verify beside
  the one it emits anyway (0: the model has no draft module, and
  `decode` refuses any other `draft_tokens`). With drafting a step
  yields one token or more, so `steps` ids take fewer steps of the
  loop, which stays one program; the model's `read_back` then carries
  how many it took and how many drafts it made and kept;
- `layer_passes`: layer bodies one token walks through (where a step
  may run more than one position, `report` gives the counts run as
  `prefill_layer_passes` and `decode_layer_passes`);
- `read_back(prefill, decode)`: the device arrays a request reads back
  beside the ids, in the one `device.wait`;
- `describe(cache_len)` and `report(prompt_tokens, new_tokens, *read)`:
  the attributes `node.TextGenerate` carries. The first says from the
  model's own shapes and dtypes what a request's state takes:
  `cache_bytes`, what grows with `cache_len`, and `state_bytes`, what
  does not (0 for a model whose state is keys and values only); the
  second is made from `read_back`'s arrays as the host got them.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp


# --- blocks ---------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    normed = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (normed * scale.astype(jnp.float32)).astype(x.dtype)


def swiglu(x: jax.Array, p: dict) -> jax.Array:
    gate, up = jnp.split(x @ p["w_gate_up"], 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ p["w_down"]


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


def sample(logits, key, temperature):
    """The next id from float32 logits: the largest at temperature 0,
    else a draw from softmax(logits / temperature). `temperature` is a
    traced scalar, so every value runs the one program."""
    drawn = jax.random.categorical(key, logits / jnp.where(temperature > 0, temperature, 1.0))
    return jnp.where(temperature > 0, drawn, jnp.argmax(logits)).astype(jnp.int32)


# --- parameters -----------------------------------------------------------
# A tree of specs: each leaf ((shape), fan_in), fan_in None for a norm's
# scale, initialised to one.


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def count_params(shapes: dict[str, Any]) -> int:
    specs = jax.tree_util.tree_leaves(shapes, is_leaf=_is_spec)
    return sum(math.prod(shape) for shape, _ in specs)


@partial(jax.jit, static_argnames=("shape", "std", "dtype"))
def _normal(key, shape, std, dtype):
    if len(shape) == 3 and math.prod(shape) >= 2**28:
        # a stack of experts or of layers, one at a time: the float32
        # draw of a whole stack (2.5 GB at the published widths) is never
        # alive at once
        keys = jax.random.split(key, shape[0])
        return jax.lax.map(
            lambda k: (jax.random.normal(k, shape[1:], jnp.float32) * std).astype(dtype), keys
        )
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def init_from_shapes(shapes: dict[str, Any], key, dtype=jnp.float32) -> dict[str, Any]:
    """Seeded random weights built in `dtype`, weight by weight: normal
    with standard deviation fan_in^-1/2, so activations keep their scale
    through the depth (and a router's logits spread by about one)."""
    specs, treedef = jax.tree_util.tree_flatten(shapes, is_leaf=_is_spec)
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
    `_init`, `_prefill`, `_decode`), the tokenizer, and the dtype the
    weights and the cache are stored in. A subclass adds `layer_passes`,
    `read_back`, `describe` and `report`, and where it has a draft
    module `draft_tokens_max` and a `_decode` that takes `draft_tokens`."""

    _init = _prefill = _decode = None
    draft_tokens_max = 0

    def __init__(self, cfg):
        self.cfg = cfg
        self.tokenizer = ByteTokenizer()
        self.dtype = jnp.dtype(jnp.float32)  # until `init` says otherwise

    def init(self, key, dtype=jnp.float32):
        self.dtype = jnp.dtype(dtype)
        return self._init(self.cfg, key, dtype)

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
