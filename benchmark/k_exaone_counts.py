"""Analytic counts for the K-EXAONE cell: parameters, and the operations
and bytes of one prefill and of one decode step, from the sizes in
configs/k-exaone-236b-a23b.json; the chip's peaks keyed by `device_kind`
are flux_counts' one table. Kept with the benchmark so that every PR
computes a roofline share in the same way.

A multiply-add counts as two operations. Bytes are what the algorithm has
to move, not what an implementation moves. A self-speculative step at
batch 1 runs two positions (the last emitted token and a draft) through
the main model and two through the MTP module, and reads every weight it
uses once whatever the positions: the mixers, the dense feed-forward
part, routers and shared experts; of the routed experts the **distinct**
held ones the step's positions fell on, a layer (two positions on one
expert read it once: the node's `decode_experts_read`); the head once a
use (the main model's two rows share one read, the MTP module's use is
another); `W_eh`; the embedding's rows; the two caches that grow (the
full layer's and the MTP module's) at the length of mid-decode, which is
exact for bytes that grow by the same amount every position; and each
window layer's ring read whole and two entries written. A step without
drafting runs one position and nothing of the MTP module.

The prefill's attention is an XLA form (`ops/attention.
causal_attention_blocked`), not a kernel of this repo, so there is no
kernel call to count. A window layer's operations are those of the
published band, position i over min(i + 1, window) keys, not of the
blocks that hold it (a block of 256 rows multiplies by 383 keys). Of the
MTP module a prefill computes only what the decode will read of it, the
prompt's keys and values: `W_eh`, `W_k`, `W_v`.
"""

from __future__ import annotations

import json
import os

from flux_counts import BYTES, PEAKS, peaks  # noqa: F401  (the one table of peaks)

HERE = os.path.dirname(os.path.abspath(__file__))


def config() -> dict:
    with open(os.path.join(HERE, "configs", "k-exaone-236b-a23b.json"), encoding="utf-8") as fh:
        return json.load(fh)


def layers(cfg: dict) -> tuple[int, int]:
    """(window layers, full layers) of the main model as held: a layer
    whose letter in `sliding_window_pattern` is L is a window layer."""
    pattern = cfg["sliding_window_pattern"]
    window = sum(pattern[i % len(pattern)] == "L" for i in range(cfg["num_hidden_layers"]))
    return window, cfg["num_hidden_layers"] - window


def sparse_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def kv_width(cfg: dict) -> int:
    return cfg["num_key_value_heads"] * cfg["head_dim"]


def mixer_matrix_params(cfg: dict) -> int:
    """W_q and W_o over the query heads, W_k and W_v over the key heads."""
    h = cfg["hidden_size"]
    return 2 * h * cfg["num_attention_heads"] * cfg["head_dim"] + 2 * h * kv_width(cfg)


def mixer_params(cfg: dict) -> int:
    """The matrices and the two norms' scales over a head's channels."""
    return mixer_matrix_params(cfg) + 2 * cfg["head_dim"]


def dense_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def always_params(cfg: dict) -> int:
    """What every token passes in a sparse layer's feed-forward part: the
    router (its published width) and the shared expert."""
    return (cfg["hidden_size"] * cfg["published"]["num_experts"]
            + cfg["num_shared_experts"] * expert_params(cfg))


def sparse_layer_params(cfg: dict, experts: float) -> float:
    """A sparse layer with `experts` routed experts: mixer, two norms,
    router, its selection bias, the shared expert."""
    return (mixer_params(cfg) + 2 * cfg["hidden_size"] + cfg["published"]["num_experts"]
            + always_params(cfg) + experts * expert_params(cfg))


def mtp_params(cfg: dict, experts: float) -> float:
    """W_eh, the three norms of its own, one sparse layer."""
    h = cfg["hidden_size"]
    return 2 * h * h + 3 * h + sparse_layer_params(cfg, experts)


def total_params(cfg: dict) -> int:
    """Everything the chip holds: `num_experts` and `vocab_size` in the
    file are the held counts."""
    h, held = cfg["hidden_size"], cfg["num_experts"]
    dense = cfg["first_k_dense_replace"] * (mixer_params(cfg) + 2 * h + dense_params(cfg))
    return int(
        dense + sparse_layers(cfg) * sparse_layer_params(cfg, held) + mtp_params(cfg, held)
        + 2 * cfg["vocab_size"] * h + h)


def cache_bytes(cfg: dict, tokens: int) -> int:
    """A key and a value of every key head, a position, in the main
    model's full layers and the MTP module's one."""
    slots = layers(cfg)[1] + cfg["num_nextn_predict_layers"]
    return slots * tokens * 2 * kv_width(cfg) * BYTES[cfg["as_run"]["compute_dtype"]]


def state_bytes(cfg: dict) -> int:
    """What does not grow with the position: a ring a window layer."""
    entry = 2 * kv_width(cfg) * BYTES[cfg["as_run"]["compute_dtype"]]
    return layers(cfg)[0] * cfg["as_run"]["ring_positions"] * entry


def decode_step_bytes(cfg: dict, experts_read: float, cache_tokens: int,
                      drafting: bool = True) -> float:
    """One step: see the module's docstring. `experts_read`: distinct
    held experts the step read, summed over its sparse layers (the MTP
    module's among them when drafting)."""
    h = cfg["hidden_size"]
    itemsize = BYTES[cfg["as_run"]["weights_dtype"]]
    entry = 2 * kv_width(cfg) * BYTES[cfg["as_run"]["compute_dtype"]]
    window, full = layers(cfg)
    positions = 2 if drafting else 1
    weights = (
        cfg["first_k_dense_replace"] * (mixer_params(cfg) + 2 * h + dense_params(cfg))
        + sparse_layers(cfg) * sparse_layer_params(cfg, 0)
        + experts_read * expert_params(cfg)
        + h + cfg["vocab_size"] * h          # the final norm, the head
        + positions * h                      # the embedding's rows
    )
    caches = full * cache_tokens * entry
    if drafting:
        weights += mtp_params(cfg, 0) + cfg["vocab_size"] * h + positions * h
        caches += cfg["num_nextn_predict_layers"] * cache_tokens * entry
    rings = state_bytes(cfg) + window * positions * entry
    return weights * itemsize + caches + rings


def causal_attention_flops(cfg: dict, tokens: int) -> float:
    """One full layer over `tokens`: q k^T and p v for every query head,
    the lower triangle only."""
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    return 4.0 * width * tokens * (tokens + 1) / 2.0


def band_attention_flops(cfg: dict, tokens: int) -> float:
    """One window layer over `tokens`: position i over min(i + 1,
    window) keys."""
    width, window = cfg["num_attention_heads"] * cfg["head_dim"], cfg["sliding_window"]
    short = min(tokens, window)
    pairs = short * (short + 1) / 2.0 + max(tokens - window, 0) * window
    return 4.0 * width * pairs


def prefill_flops(cfg: dict, tokens: int, pairs_held: float) -> float:
    """One prefill: two operations a weight and token for what every
    token passes through (the main layers; of the MTP module W_eh, W_k
    and W_v), the held experts for the pairs that fell on them, the full
    layer's causal attention, the window layers' bands, and the head for
    one token."""
    h = cfg["hidden_size"]
    window, full = layers(cfg)
    per_token = (
        cfg["num_hidden_layers"] * mixer_matrix_params(cfg)
        + cfg["first_k_dense_replace"] * dense_params(cfg)
        + sparse_layers(cfg) * always_params(cfg)
        + cfg["num_nextn_predict_layers"] * (2 * h * h + 2 * h * kv_width(cfg))
    )
    return (
        2.0 * tokens * per_token
        + 2.0 * pairs_held * expert_params(cfg)
        + full * causal_attention_flops(cfg, tokens)
        + window * band_attention_flops(cfg, tokens)
        + 2.0 * cfg["vocab_size"] * h
    )


def prefill_bytes(cfg: dict, tokens: int) -> float:
    """Every weight the prefill uses once (all held experts are touched
    by 8,192 tokens; of the MTP module W_eh, W_k, W_v), the embedding's
    rows, and the state written."""
    h = cfg["hidden_size"]
    itemsize = BYTES[cfg["as_run"]["weights_dtype"]]
    unused = mtp_params(cfg, cfg["num_experts"]) - (2 * h * h + 2 * h * kv_width(cfg))
    rows = (tokens - cfg["vocab_size"]) * h  # rows in place of the table
    return (total_params(cfg) - unused + rows) * itemsize + cache_bytes(cfg, tokens) + (
        state_bytes(cfg))
