"""Analytic counts for the DeepSeek-V2 cell: parameters, and the
operations and bytes of one prefill and of one decode step, from the
sizes in configs/deepseek-v2.json; the chip's peaks keyed by
`device_kind` are flux_counts' one table. Kept with the benchmark so that
every PR computes a roofline share in the same way.

A multiply-add counts as two operations. Bytes are what the algorithm has
to move, not what an implementation moves: a decode step at batch 1 reads
every weight it uses once (of the routed experts only those a token's
pairs fell on), one row of the embedding and the latent cache so far. The
prefill's attention is an XLA form (`ops/attention.causal_attention_
blocked`), not a kernel of this repo, so there is no kernel call to count;
its operations are `causal_attention_flops`.
"""

from __future__ import annotations

import json
import os

from flux_counts import BYTES, peaks, roofline_seconds  # noqa: F401  (the one table of peaks)

HERE = os.path.dirname(os.path.abspath(__file__))


def config() -> dict:
    with open(os.path.join(HERE, "configs", "deepseek-v2.json"), encoding="utf-8") as fh:
        return json.load(fh)


def attention_params(cfg: dict) -> int:
    """MLA's five projections: W_dq, W_uq, W_dkv, W_ukv, W_o."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return (
        h * cfg["q_lora_rank"]
        + cfg["q_lora_rank"] * heads * qk
        + h * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
        + cfg["kv_lora_rank"] * heads * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
        + heads * cfg["v_head_dim"] * h
    )


def swiglu_params(cfg: dict, width: int) -> int:
    return 3 * cfg["hidden_size"] * width


def expert_params(cfg: dict) -> int:
    return swiglu_params(cfg, cfg["moe_intermediate_size"])


def shared_params(cfg: dict) -> int:
    return swiglu_params(cfg, cfg["moe_intermediate_size"] * cfg["n_shared_experts"])


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["published"]["n_routed_experts"]


def layers(cfg: dict) -> tuple[int, int]:
    """(dense layers, expert layers) as held."""
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return dense, cfg["num_hidden_layers"] - dense


def norm_params(cfg: dict) -> int:
    per_layer = 2 * cfg["hidden_size"] + cfg["q_lora_rank"] + cfg["kv_lora_rank"]
    return cfg["num_hidden_layers"] * per_layer + cfg["hidden_size"]


def total_params(cfg: dict) -> int:
    """Everything the chip holds: `n_routed_experts` and `vocab_size` in
    the file are the held counts."""
    dense, sparse = layers(cfg)
    return (
        cfg["num_hidden_layers"] * attention_params(cfg)
        + dense * swiglu_params(cfg, cfg["intermediate_size"])
        + sparse * (router_params(cfg) + shared_params(cfg)
                    + cfg["n_routed_experts"] * expert_params(cfg))
        + 2 * cfg["vocab_size"] * cfg["hidden_size"]
        + norm_params(cfg)
    )


def decode_step_params(cfg: dict, held_experts_a_layer: float) -> float:
    """Weights one token's step multiplies by: every layer's attention,
    the dense layers' SwiGLU, each expert layer's router, shared experts
    and the held experts its pairs fell on, and the head."""
    dense, sparse = layers(cfg)
    return (
        cfg["num_hidden_layers"] * attention_params(cfg)
        + dense * swiglu_params(cfg, cfg["intermediate_size"])
        + sparse * (router_params(cfg) + shared_params(cfg)
                    + held_experts_a_layer * expert_params(cfg))
        + cfg["vocab_size"] * cfg["hidden_size"]
    )


def cache_bytes(cfg: dict, tokens: int) -> int:
    width = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return cfg["num_hidden_layers"] * tokens * width * BYTES[cfg["as_run"]["compute_dtype"]]


def decode_step_bytes(cfg: dict, held_experts_a_layer: float, cache_tokens: int) -> float:
    """The step's weights once, the embedding's row, and the latent
    cache of the tokens so far."""
    itemsize = BYTES[cfg["as_run"]["weights_dtype"]]
    return (
        (decode_step_params(cfg, held_experts_a_layer) + cfg["hidden_size"]) * itemsize
        + cache_bytes(cfg, cache_tokens)
    )


def decode_step_flops(cfg: dict, held_experts_a_layer: float, cache_tokens: int) -> float:
    """Two operations a weight, and the absorbed attention over the
    cache: scores over kv_lora + rope and the weighted sum over kv_lora,
    for every head."""
    width = 2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    attention = 2.0 * cfg["num_attention_heads"] * cache_tokens * width * cfg["num_hidden_layers"]
    return 2.0 * decode_step_params(cfg, held_experts_a_layer) + attention


def causal_attention_flops(cfg: dict, tokens: int) -> float:
    """One layer's expanded causal attention over `tokens`: q k^T over
    nope + rope and p v over the value width, the lower triangle only."""
    width = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]
    return 2.0 * cfg["num_attention_heads"] * width * tokens * (tokens + 1) / 2.0


def prefill_flops(cfg: dict, tokens: int, pairs_held: float) -> float:
    """One prefill: two operations a weight and token for what every
    token passes through, the held experts for the pairs that fell on
    them, causal attention in every layer, and the head for one token."""
    dense, sparse = layers(cfg)
    per_token = (
        cfg["num_hidden_layers"] * attention_params(cfg)
        + dense * swiglu_params(cfg, cfg["intermediate_size"])
        + sparse * (router_params(cfg) + shared_params(cfg))
    )
    return (
        2.0 * tokens * per_token
        + 2.0 * pairs_held * expert_params(cfg)
        + cfg["num_hidden_layers"] * causal_attention_flops(cfg, tokens)
        + 2.0 * cfg["vocab_size"] * cfg["hidden_size"]
    )


def prefill_bytes(cfg: dict, tokens: int) -> float:
    """Every weight once (all held experts are touched by 2,048 tokens),
    the embedding's rows, and the cache written."""
    itemsize = BYTES[cfg["as_run"]["weights_dtype"]]
    rows = (tokens - cfg["vocab_size"]) * cfg["hidden_size"]  # rows in place of the table
    return (total_params(cfg) + rows) * itemsize + cache_bytes(cfg, tokens)
