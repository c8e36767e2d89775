"""The expert layer of one chip, as the language models with a mixture
of experts share it (`deepseek_v2.py`, `solar_open2.py`).

The layer is told which routed experts it holds (`held`, a contiguous
run: `parallel.sharding.expert_range` of the chip's rank), routes every
token over all of them by the model's own rule, and computes its own
experts' part of the result as one grouped product
(`jax.lax.ragged_dot`) with no dropped token and no capacity factor;
what absent experts would add is left out. The shared expert sees every
token.

Parameters: `w_g` [hidden, experts] (the router), `experts` {`w_gate_up`
[held, hidden, 2 x width], `w_down` [held, width, hidden]}, `shared` (one
SwiGLU); whatever else the model's rule reads (a selection bias) stays
with the rule.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from .lm_common import swiglu


def expert_layer(p: dict, x: jax.Array, held: range, route: Callable):
    """x [T, hidden] through the layer. `route(logits)` takes the
    router's float32 logits [T, experts] and returns (ids [T, k],
    weights [T, k] float32). Returns (output, chosen ids [T, k], pairs
    on each held expert [held])."""
    with jax.named_scope("router"):
        logits = jnp.dot(
            x.astype(jnp.float32), p["w_g"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        ids, weights = route(logits)
    with jax.named_scope("experts"):
        tokens, k = ids.shape
        local = ids.reshape(-1) - held.start
        here = (local >= 0) & (local < len(held))
        # sort the token-expert pairs by held expert, the pairs of absent
        # experts last: each held expert's rows are then one segment
        slot = jnp.where(here, local, len(held))
        order = jnp.argsort(slot, stable=True)
        sizes = jnp.zeros((len(held),), jnp.int32).at[slot].add(1, mode="drop")
        rows = x[order // k]
        gate, up = jnp.split(
            jax.lax.ragged_dot(rows, p["experts"]["w_gate_up"], sizes), 2, axis=-1
        )
        out = jax.lax.ragged_dot(jax.nn.silu(gate) * up, p["experts"]["w_down"], sizes)
        # rows past the last segment are absent experts' pairs: weight 0
        out = jnp.where(here[order][:, None], out, 0).astype(jnp.float32)
        out = out * weights.reshape(-1)[order][:, None]
        routed = out[jnp.argsort(order)].reshape(tokens, k, -1).sum(axis=1)
    with jax.named_scope("shared"):
        shared = swiglu(x, p["shared"])
    return shared + routed.astype(x.dtype), ids, sizes


def report_loads(pairs_a_token: int, prompt_tokens: int, new_tokens: int,
                 prefill_loads, decode_loads) -> dict:
    """`node.TextGenerate`'s attributes of the routing, per phase: the
    token-expert pairs the router made, those that fell on held experts
    (`loads` [expert layers, held], as read back), and the fullest held
    expert's."""
    attrs = {}
    for phase, tokens, loads in (
        ("prefill", prompt_tokens, prefill_loads), ("decode", new_tokens, decode_loads)
    ):
        attrs[f"{phase}_routed_pairs"] = tokens * pairs_a_token
        attrs[f"{phase}_routed_pairs_held"] = int(np.sum(loads))
        attrs[f"{phase}_expert_load_max"] = int(np.max(loads))
    return attrs
