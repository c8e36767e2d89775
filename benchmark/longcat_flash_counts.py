"""Analytic counts for the LongCat-Flash-Chat cell: parameters (held and
published), and the operations and bytes of one prefill and of one decode
step, from the sizes in configs/longcat-flash-chat.json; the chip's peaks
keyed by `device_kind` are flux_counts' one table. Kept with the benchmark
so that every PR computes a roofline share in the same way.

A multiply-add counts as two operations. Operations are what the **model**
defines, whatever form the program computes them in. A layer is two
latent attentions, two dense feed-forwards and the router, which every
token passes, and the held experts for the pairs that fell on them; a
pair that chose an identity expert is a multiplication by a scalar and
counts as nothing. Attention is counted in the expanded form: a key and a
value of every head built once a position from its latent, then nope +
rope + value multiply-adds a head and visible pair (the triangle j <= i:
what the mask lets through). A program that multiplies whole blocks the
diagonal crosses, pads a 192-wide head to 256 lanes, or rebuilds the
keys and values of the positions before a part once more for every part,
does work beyond this count, so no share can pass 100 for it.

A decode step at batch 1 reads every weight it uses once: the eight
attentions' matrices, the eight dense feed-forwards, the routers; of the
routed experts the **distinct** held ones the step's token fell on (the
node's `decode_experts_read`: most steps none); the head; the
embedding's row. Of the state it reads each of the eight latent caches
whole at the length of mid-decode (1,152 B a position), as
`mla.absorbed` does, and writes a row in each.
"""

from __future__ import annotations

import json
import os

from flux_counts import BYTES, PEAKS, peaks  # noqa: F401  (the one table of peaks)

HERE = os.path.dirname(os.path.abspath(__file__))


def config() -> dict:
    with open(os.path.join(HERE, "configs", "longcat-flash-chat.json"), encoding="utf-8") as fh:
        return json.load(fh)


def attention_sublayers(cfg: dict) -> int:
    return 2 * cfg["num_layers"]


def head_width(cfg: dict) -> int:
    """Of a head's query and key."""
    return cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]


def cache_width(cfg: dict) -> int:
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def router_width(cfg: dict) -> int:
    """The router's outputs: the published experts and the identities,
    whatever the cut holds."""
    return cfg["published"]["n_routed_experts"] + cfg["zero_expert_num"]


def projection_params(cfg: dict) -> int:
    """What every position passes in one attention: W_dq, W_uq, W_dkv, W_o."""
    h, heads, r_q = cfg["hidden_size"], cfg["num_attention_heads"], cfg["q_lora_rank"]
    return (h * r_q + r_q * heads * head_width(cfg) + h * cache_width(cfg)
            + heads * cfg["v_head_dim"] * h)


def up_params(cfg: dict) -> int:
    """W_uk and W_uv: a key and a value of every head from a latent."""
    return cfg["kv_lora_rank"] * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["v_head_dim"])


def attention_params(cfg: dict) -> int:
    """The matrices and the two norms' scales."""
    return projection_params(cfg) + up_params(cfg) + cfg["q_lora_rank"] + cfg["kv_lora_rank"]


def dense_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["ffn_hidden_size"]


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["expert_ffn_hidden_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * router_width(cfg)


def layer_params(cfg: dict, experts: float) -> float:
    """A layer with `experts` routed experts: two attentions, two dense
    feed-forwards, four norms, the router and its selection bias."""
    return (2 * attention_params(cfg) + 2 * dense_params(cfg) + 4 * cfg["hidden_size"]
            + router_params(cfg) + router_width(cfg) + experts * expert_params(cfg))


def total_params(cfg: dict) -> int:
    """Everything the chip holds: `num_layers`, `n_routed_experts` and
    `vocab_size` in the file are the held counts."""
    h = cfg["hidden_size"]
    return int(cfg["num_layers"] * layer_params(cfg, cfg["n_routed_experts"])
               + 2 * cfg["vocab_size"] * h + h)


def published_params(cfg: dict) -> int:
    """The model uncut: every layer, expert and id."""
    h, p = cfg["hidden_size"], cfg["published"]
    return int(p["num_layers"] * layer_params(cfg, p["n_routed_experts"])
               + 2 * p["vocab_size"] * h + h)


def active_params(cfg: dict, real_experts: float) -> float:
    """A token's parameters in the model uncut, at `real_experts` experts
    with weights among its `moe_topk` (8 on average as published), one
    vocabulary matrix and the final norm."""
    h, p = cfg["hidden_size"], cfg["published"]
    return p["num_layers"] * layer_params(cfg, real_experts) + p["vocab_size"] * h + h


def row_bytes(cfg: dict) -> int:
    return cache_width(cfg) * BYTES[cfg["as_run"]["compute_dtype"]]


def cache_bytes(cfg: dict, tokens: int) -> int:
    """A latent row a position in each of the attentions' caches."""
    return attention_sublayers(cfg) * tokens * row_bytes(cfg)


def keys_visible(first: int, last: int) -> int:
    """Positions the queries at `first` .. `last` - 1 see: t + 1 each."""
    return (last * (last + 1) - first * (first + 1)) // 2


def decode_step_bytes(cfg: dict, experts_read: float, cache_tokens: int) -> float:
    """One step: see the module's docstring. `experts_read`: distinct
    held experts the step read, summed over its layers."""
    h = cfg["hidden_size"]
    weights = (
        cfg["num_layers"] * layer_params(cfg, 0) + experts_read * expert_params(cfg)
        + h + cfg["vocab_size"] * h          # the final norm, the head
        + h                                  # the embedding's row
    )
    state = cache_bytes(cfg, cache_tokens + 1) + attention_sublayers(cfg) * row_bytes(cfg)
    return weights * BYTES[cfg["as_run"]["weights_dtype"]] + state


def attention_flops(cfg: dict, pairs: int) -> float:
    """One attention over `pairs` query-key pairs, expanded: q k^T over
    nope + rope and p v over the value width, a head."""
    return 2.0 * cfg["num_attention_heads"] * (head_width(cfg) + cfg["v_head_dim"]) * pairs


def prefill_causal_calls(cfg: dict, tokens: int) -> list:
    """(rows, keys) of each part's causal call in one attention: a part's
    queries over every position up to its last."""
    part = cfg["as_run"]["prefill_part"]
    return [(min(part, tokens - start), min(start + part, tokens))
            for start in range(0, tokens, part)]


def causal_call_flops(cfg: dict, rows: int, keys: int) -> float:
    """One causal call of `rows` queries over `keys` keys, all heads: the
    pairs the mask lets through, the last `rows` of the triangle's rows."""
    return attention_flops(cfg, keys_visible(keys - rows, keys))


def causal_call_bytes(cfg: dict, rows: int, keys: int) -> float:
    """q, the output, the keys and the values of one call once."""
    heads, width, value = cfg["num_attention_heads"], head_width(cfg), cfg["v_head_dim"]
    moved = rows * heads * (width + value) + keys * heads * (width + value)
    return float(moved) * BYTES[cfg["as_run"]["compute_dtype"]]


def prefill_flops(cfg: dict, tokens: int, pairs_held: float) -> float:
    """One prefill: two operations a weight and token for what every
    token passes through (each attention's projections with a key and a
    value of every head built once a position, the dense feed-forwards,
    the routers), the held experts for the pairs that fell on them, each
    attention's visible triangle, and the head for one token."""
    per_token = cfg["num_layers"] * (
        2 * (projection_params(cfg) + up_params(cfg)) + 2 * dense_params(cfg)
        + router_params(cfg))
    return (
        2.0 * tokens * per_token
        + 2.0 * pairs_held * expert_params(cfg)
        + attention_sublayers(cfg) * attention_flops(cfg, keys_visible(0, tokens))
        + 2.0 * cfg["vocab_size"] * cfg["hidden_size"]
    )
