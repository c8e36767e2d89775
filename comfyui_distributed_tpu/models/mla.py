"""Multi-head latent attention's two forms, as the models with such
layers share them (`deepseek_v2.py`, `ling_flash.py`, `glm_dsa.py`,
`dots3.py`, `longcat_flash.py`), over a head's
queries in two parts (`q_nope` [T, heads, nope], and `q_rope` [T, heads,
rope], rotated), the latents a cache holds ([S, rank + rope]: the normed
latent c and the one rotated rope key r of all heads, side by side) and
the two halves of the up-projection, `w_uk` [rank, heads, nope] and
`w_uv` [rank, heads, v]:

    k_nope = W_uk c,  v = W_uv c,  score_ij = scale (q_nope_i . k_nope_j + q_rope_i . r_j)

`latents` makes what the cache holds; `expanded` builds every key and value from the latents (a whole
sequence, causal: a prefill; under a `window` a band, and with the
latents `before` the sequence, a part of a prompt whose queries see a
window's tail or every position before it); `absorbed` folds W_uk into the query,
attends over the latents themselves and applies W_uv after the weighted
sum (a decode step's new positions over a cache, or over a ring: the
cache is then `ring_positions` rows, position p in row p modulo that,
and `valid` is `ops/decode_attention.ring_valid`'s; the ring's write
and what a prefill leaves in it are the model's, `dots3.py`);
`models/dsa.py` has
the absorbed form over the rows of the cache a learned index chose. All
return the heads' outputs [T, heads, v]; the value width v need not be
the nope width. How the queries are made of the input
(a query latent or none, which rotation, a rescale), the softmax's scale, and what
follows the heads' outputs (a gate, the projection) are each model's own.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops.attention import causal_attention
from .lm_common import apply_rope, rms_norm


def latents(p, x, rope, eps: float, rotate=apply_rope, scale: float = 1.0):
    """What the cache holds of x [T, hidden]: the normed latent (`w_dkv`'s
    first columns under `kv_norm`) and the rope key, rotated by `rope`
    (cos, sin of the positions; `rotate` says which channels pair up:
    `lm_common.apply_rope`'s halves, or `apply_rope_pairs`), side by
    side, [T, rank + rope]. A `scale` other than 1 multiplies the normed
    latent, in the norm's float32 before its one rounding, and not the
    rope key (a model that rescales its latent: this is the one place)."""
    rank = p["kv_norm"].shape[0]
    down = x @ p["w_dkv"]
    norm = p["kv_norm"] if scale == 1.0 else p["kv_norm"].astype(jnp.float32) * scale
    c_kv = rms_norm(down[:, :rank], norm, eps)
    return jnp.concatenate([c_kv, rotate(down[:, rank:], *rope)], axis=-1)


def expanded(q_nope, q_rope, latents, w_uk, w_uv, scale: float, window: int | None = None,
             before=None):
    """Causal attention of a whole sequence's queries over the keys and
    values built from its own `latents` [T, rank + rope] and, where
    given, from the latents `before` [B, rank + rope] of the B positions
    that precede it (T queries over B + T keys); under a `window` a
    query sees the last `window` positions up to its own, itself among
    them (`ops/attention.causal_attention`'s band)."""
    rank = w_uk.shape[0]
    if before is not None:
        latents = jnp.concatenate([before, latents])
    c_kv, k_rope = latents[:, :rank], latents[:, rank:]
    k_nope = jnp.einsum("tc,chd->thd", c_kv, w_uk)
    v = jnp.einsum("tc,chd->thd", c_kv, w_uv)
    k_rope = jnp.broadcast_to(k_rope[:, None, :], (*k_nope.shape[:2], k_rope.shape[-1]))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, k_rope], axis=-1)
    return causal_attention(q[None], k[None], v[None], scale=scale, window=window)[0]


def absorbed(q_nope, q_rope, cache, valid, w_uk, w_uv, scale: float):
    """New positions' queries over a latent cache [S, rank + rope] that
    already holds their own latents; `valid` [T, S] says which cached
    positions each sees. Scores and softmax float32, the probabilities
    rounded to the cache's dtype."""
    rank = w_uk.shape[0]
    q_lat = jnp.einsum("thd,chd->thc", q_nope, w_uk)
    q = jnp.concatenate([q_lat, q_rope], axis=-1)            # [T, heads, rank + rope]
    scores = scale * jnp.einsum("thc,sc->ths", q, cache, preferred_element_type=jnp.float32)
    scores = jnp.where(valid[:, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(cache.dtype)
    o_lat = jnp.einsum("ths,sc->thc", probs, cache[:, :rank])
    return jnp.einsum("thc,chd->thd", o_lat, w_uv)
