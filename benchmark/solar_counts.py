"""Analytic counts for the Solar-Open2 cell: parameters, and the
operations and bytes of one prefill and of one decode step, from the
sizes in configs/solar-open2-250b.json; the chip's peaks keyed by
`device_kind` are flux_counts' one table. Kept with the benchmark so that
every PR computes a roofline share in the same way.

A multiply-add counts as two operations. Bytes are what the algorithm has
to move, not what an implementation moves: a decode step at batch 1 reads
every weight it uses once (of the routed experts only those a token's
pairs fell on), one row of the embedding, the one full-attention layer's
keys and values so far, and **reads and writes each KDA layer's matrix
state once** (it is the whole of a linear layer's memory: 4.19 MB a
layer, whatever the position). The prefill's softmax attention is an XLA
form (`ops/attention.causal_attention_blocked`) and its delta rule a
`lax.scan` over chunks, not kernels of this repo, so there is no kernel
call to count; their operations are `causal_attention_flops` and
`delta_rule_flops`.
"""

from __future__ import annotations

import json
import os

from flux_counts import BYTES, PEAKS, peaks  # noqa: F401  (the one table of peaks)

HERE = os.path.dirname(os.path.abspath(__file__))


def config() -> dict:
    with open(os.path.join(HERE, "configs", "solar-open2-250b.json"), encoding="utf-8") as fh:
        return json.load(fh)


def layers(cfg: dict) -> tuple[int, int]:
    """(full-attention layers, KDA layers) as held: a layer whose index is
    a multiple of `gqa_interval` + 1 is full attention."""
    period = cfg["gqa_interval"] + 1
    full = sum(layer % period == 0 for layer in range(cfg["num_hidden_layers"]))
    return full, cfg["num_hidden_layers"] - full


def linear_width(cfg: dict) -> int:
    linear = cfg["linear_attn_config"]
    return linear["num_heads"] * linear["head_dim"]


def gqa_params(cfg: dict) -> int:
    """W_q, W_gate and W_o over all the query heads, W_k and W_v over the
    key heads."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    return 3 * h * cfg["num_attention_heads"] * d + 2 * h * cfg["num_key_value_heads"] * d


def kda_matrix_params(cfg: dict) -> int:
    """W_q, W_k, W_v and W_o; the two low-rank gates (rank: the head's
    width); W_beta."""
    h, lin = cfg["hidden_size"], linear_width(cfg)
    rank = cfg["linear_attn_config"]["head_dim"]
    return 4 * h * lin + 2 * (h * rank + rank * lin) + h * cfg["linear_attn_config"]["num_heads"]


def kda_params(cfg: dict) -> int:
    """The matrices, the three convolutions' filters, A_log a head,
    dt_bias a channel, the output norm's scale."""
    linear = cfg["linear_attn_config"]
    small = (3 * linear["short_conv_kernel_size"] * linear_width(cfg)
             + linear["num_heads"] + linear_width(cfg) + linear["head_dim"])
    return kda_matrix_params(cfg) + small


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def always_params(cfg: dict) -> int:
    """What every token passes in a layer's feed-forward part: the router
    (its published width) and the shared experts."""
    return (cfg["hidden_size"] * cfg["published"]["n_routed_experts"]
            + cfg["n_shared_experts"] * expert_params(cfg))


def small_params(cfg: dict) -> int:
    """Two norms and the router's selection bias a layer, the final norm."""
    per_layer = 2 * cfg["hidden_size"] + cfg["published"]["n_routed_experts"]
    return cfg["num_hidden_layers"] * per_layer + cfg["hidden_size"]


def mixer_params(cfg: dict) -> int:
    full, linear = layers(cfg)
    return full * gqa_params(cfg) + linear * kda_params(cfg)


def total_params(cfg: dict) -> int:
    """Everything the chip holds: `n_routed_experts` and `vocab_size` in
    the file are the held counts."""
    return (
        mixer_params(cfg)
        + cfg["num_hidden_layers"] * (
            always_params(cfg) + cfg["n_routed_experts"] * expert_params(cfg))
        + 2 * cfg["vocab_size"] * cfg["hidden_size"]
        + small_params(cfg)
    )


def cache_bytes(cfg: dict, tokens: int) -> int:
    """A key and a value of every key head in every full-attention layer."""
    width = 2 * cfg["num_key_value_heads"] * cfg["head_dim"]
    return layers(cfg)[0] * tokens * width * BYTES[cfg["as_run"]["compute_dtype"]]


def state_bytes(cfg: dict) -> int:
    """What does not grow with the position: a matrix state a KDA head
    (`state_dtype`) and the convolutions' last inputs."""
    linear = cfg["linear_attn_config"]
    matrices = linear["num_heads"] * linear["head_dim"] ** 2 * BYTES[cfg["as_run"]["state_dtype"]]
    tails = (3 * (linear["short_conv_kernel_size"] - 1) * linear_width(cfg)
             * BYTES[cfg["as_run"]["compute_dtype"]])
    return layers(cfg)[1] * (matrices + tails)


def decode_step_params(cfg: dict, held_experts_a_layer: float) -> float:
    """Weights one token's step multiplies by: every layer's mixer, router
    and shared expert, the held experts its pairs fell on, and the head."""
    return (
        mixer_params(cfg)
        + cfg["num_hidden_layers"] * (
            always_params(cfg) + held_experts_a_layer * expert_params(cfg))
        + small_params(cfg)
        + cfg["vocab_size"] * cfg["hidden_size"]
    )


def decode_step_bytes(cfg: dict, held_experts_a_layer: float, cache_tokens: int) -> float:
    """The step's weights once, the embedding's row, the keys and values
    of the tokens so far, and the fixed-size state read and written."""
    itemsize = BYTES[cfg["as_run"]["weights_dtype"]]
    return (
        (decode_step_params(cfg, held_experts_a_layer) + cfg["hidden_size"]) * itemsize
        + cache_bytes(cfg, cache_tokens)
        + 2 * state_bytes(cfg)
    )


def causal_attention_flops(cfg: dict, tokens: int) -> float:
    """One full-attention layer over `tokens`: q k^T and p v for every
    query head, the lower triangle only."""
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    return 4.0 * width * tokens * (tokens + 1) / 2.0


def delta_rule_flops(cfg: dict, tokens: int) -> float:
    """One KDA layer's chunked delta rule over `tokens`, a chunk of C
    tokens and a head of width d: the two pairwise products k k^T and
    q k^T under the decay (their lower triangles: 2 C^2 d), the unit-
    triangular solve for 2 d right-hand sides (2 C^2 d), W S, (q exp G) S
    and the state's update (6 C d^2), P U (C^2 d)."""
    linear = cfg["linear_attn_config"]
    chunk, d = cfg["as_run"]["kda_chunk"], linear["head_dim"]
    chunks = -(-tokens // chunk)
    return float(chunks * linear["num_heads"] * (5 * chunk * chunk * d + 6 * chunk * d * d))


def prefill_flops(cfg: dict, tokens: int, pairs_held: float) -> float:
    """One prefill: two operations a weight and token for what every
    token passes through, the held experts for the pairs that fell on
    them, the causal attention, the delta rule, and the head for one
    token."""
    full, linear = layers(cfg)
    per_token = (
        full * gqa_params(cfg) + linear * kda_matrix_params(cfg)
        + cfg["num_hidden_layers"] * always_params(cfg)
    )
    return (
        2.0 * tokens * per_token
        + 2.0 * pairs_held * expert_params(cfg)
        + full * causal_attention_flops(cfg, tokens)
        + linear * delta_rule_flops(cfg, tokens)
        + 2.0 * cfg["vocab_size"] * cfg["hidden_size"]
    )


def prefill_bytes(cfg: dict, tokens: int) -> float:
    """Every weight once (all held experts are touched by 8,192 tokens),
    the embedding's rows, and the state written."""
    itemsize = BYTES[cfg["as_run"]["weights_dtype"]]
    rows = (tokens - cfg["vocab_size"]) * cfg["hidden_size"]  # rows in place of the table
    return (total_params(cfg) + rows) * itemsize + cache_bytes(cfg, tokens) + state_bytes(cfg)
