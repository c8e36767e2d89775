"""Analytic counts for the Ling-3.0-flash cell: parameters, and the
operations and bytes of one prefill and of one decode step, from the
sizes in configs/ling-3.0-flash.json; the chip's peaks keyed by
`device_kind` are flux_counts' one table. Kept with the benchmark so that
every PR computes a roofline share in the same way.

A multiply-add counts as two operations. Bytes are what the algorithm has
to move, not what an implementation moves. A self-speculative step at
batch 1 runs two positions (the last emitted token and a draft) through
the main model and two through the MTP module, and reads every weight it
uses once whatever the positions: the mixers (KDA's at full rank, MLA's
with both halves of the up-projection), the dense feed-forward part,
routers and shared experts; of the routed experts the **distinct** held
ones the step's positions fell on, a layer (the node's
`decode_experts_read`); the head once a use (the main model's two rows
share one read, the MTP module's use is another); `W_eh`; the embedding's
rows; the latents of the main model's MLA layer and of the MTP module's
at the length of mid-decode, which is exact for bytes that grow by the
same amount every position; and **each KDA layer's matrix state read once
and written once a position**: the state after the first position and
the state after the second both have to exist until the draft's fate is
known, so the second write is what keeping or dropping a draft adds
(`keep_bytes`; a select between the two at the step's end would read both
and write one more). A step without drafting runs one position, nothing
of the MTP module, and writes each state once.

The prefill's latent attention is expanded (`ops/attention.
causal_attention`: 192-wide queries and keys, 128-wide values) and its
delta rule a `lax.scan` over chunks (`models/kda.kda_chunked`), counted
as `solar_counts` counts them. Of the MTP module a prefill computes only
what the decode will read of it, the prompt's latents: `W_eh`, `W_dkv`.
"""

from __future__ import annotations

import json
import os

from flux_counts import BYTES, PEAKS, peaks  # noqa: F401  (the one table of peaks)

HERE = os.path.dirname(os.path.abspath(__file__))


def config() -> dict:
    with open(os.path.join(HERE, "configs", "ling-3.0-flash.json"), encoding="utf-8") as fh:
        return json.load(fh)


def held_layers(cfg: dict) -> range:
    """The published indices of the layers held."""
    first = cfg["as_run"]["first_layer"]
    return range(first, first + cfg["num_hidden_layers"])


def layers(cfg: dict) -> tuple[int, int]:
    """(KDA layers, MLA layers) of the main model as held: published
    layer l is MLA where (l + 1) mod `layer_group_size` is 0."""
    latent = sum((layer + 1) % cfg["layer_group_size"] == 0 for layer in held_layers(cfg))
    return cfg["num_hidden_layers"] - latent, latent


def dense_layers(cfg: dict) -> int:
    return sum(layer < cfg["first_k_dense_replace"] for layer in held_layers(cfg))


def sparse_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - dense_layers(cfg)


def linear_width(cfg: dict) -> int:
    return cfg["num_attention_heads"] * cfg["head_dim"]


def cache_width(cfg: dict) -> int:
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def kda_matrix_params(cfg: dict) -> int:
    """W_qkv, the two gates at full rank (W_f, W_g), W_o, W_beta."""
    h, lin = cfg["hidden_size"], linear_width(cfg)
    return 6 * h * lin + h * cfg["num_attention_heads"]


def kda_params(cfg: dict) -> int:
    """The matrices, the convolution's filters, A_log a head, dt_bias a
    channel, the output norm's scale."""
    lin = linear_width(cfg)
    return (kda_matrix_params(cfg) + cfg["short_conv_kernel_size"] * 3 * lin
            + cfg["num_attention_heads"] + lin + cfg["head_dim"])


def mla_matrix_params(cfg: dict) -> int:
    """W_q (no query latent), W_dkv, W_uk and W_uv, the gate a head, W_o."""
    h, heads, rank = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    return (h * heads * cfg["qk_head_dim"] + h * cache_width(cfg)
            + rank * heads * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + h * heads + heads * cfg["v_head_dim"] * h)


def mla_params(cfg: dict) -> int:
    return mla_matrix_params(cfg) + cfg["kv_lora_rank"]  # the latent's norm


def dense_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def always_params(cfg: dict) -> int:
    """What every token passes in a sparse layer's feed-forward part: the
    router (its published width) and the shared expert."""
    shared = 3 * cfg["hidden_size"] * cfg["moe_shared_expert_intermediate_size"]
    return (cfg["hidden_size"] * cfg["published"]["num_experts"]
            + cfg["num_shared_experts"] * shared)


def sparse_part_params(cfg: dict, experts: float) -> float:
    """A sparse feed-forward part with `experts` routed experts: router,
    its selection bias, the shared expert."""
    return always_params(cfg) + cfg["published"]["num_experts"] + experts * expert_params(cfg)


def mtp_params(cfg: dict, experts: float) -> float:
    """W_eh, the three norms of its own, one MLA layer with a sparse part."""
    h = cfg["hidden_size"]
    return 2 * h * h + 3 * h + 2 * h + mla_params(cfg) + sparse_part_params(cfg, experts)


def main_params(cfg: dict, experts: float) -> float:
    """The held main layers with `experts` routed experts in each sparse
    one: mixers, two norms a layer, the feed-forward parts."""
    kda, latent = layers(cfg)
    return (kda * kda_params(cfg) + latent * mla_params(cfg)
            + cfg["num_hidden_layers"] * 2 * cfg["hidden_size"]
            + dense_layers(cfg) * dense_params(cfg)
            + sparse_layers(cfg) * sparse_part_params(cfg, experts))


def total_params(cfg: dict) -> int:
    """Everything the chip holds: `num_experts` and `vocab_size` in the
    file are the held counts."""
    h, held = cfg["hidden_size"], cfg["num_experts"]
    return int(main_params(cfg, held) + mtp_params(cfg, held) + 2 * cfg["vocab_size"] * h + h)


def cache_bytes(cfg: dict, tokens: int) -> int:
    """A latent and its rope key a position, in the main model's MLA
    layers and the MTP module's one."""
    slots = layers(cfg)[1] + cfg["num_nextn_predict_layers"]
    return slots * tokens * cache_width(cfg) * BYTES[cfg["as_run"]["compute_dtype"]]


def matrix_state_bytes(cfg: dict) -> int:
    """One KDA layer's matrix states, one slot: heads x d x d."""
    return (cfg["num_attention_heads"] * cfg["head_dim"] ** 2
            * BYTES[cfg["as_run"]["state_dtype"]])


def tail_bytes(cfg: dict) -> int:
    """One KDA layer's convolution tail, one slot."""
    return ((cfg["short_conv_kernel_size"] - 1) * 3 * linear_width(cfg)
            * BYTES[cfg["as_run"]["compute_dtype"]])


def state_bytes(cfg: dict) -> int:
    """What does not grow with the position: two slots a KDA layer of
    matrix states and of tails."""
    return layers(cfg)[0] * 2 * (matrix_state_bytes(cfg) + tail_bytes(cfg))


def keep_bytes(cfg: dict) -> int:
    """What keeping or dropping a draft adds to a step: the state and
    tail after the first position written beside those after the second."""
    return layers(cfg)[0] * (matrix_state_bytes(cfg) + tail_bytes(cfg))


def decode_step_bytes(cfg: dict, experts_read: float, cache_tokens: int,
                      drafting: bool = True) -> float:
    """One step: see the module's docstring. `experts_read`: distinct
    held experts the step read, summed over its sparse layers (the MTP
    module's among them when drafting)."""
    h = cfg["hidden_size"]
    itemsize = BYTES[cfg["as_run"]["weights_dtype"]]
    entry = cache_width(cfg) * BYTES[cfg["as_run"]["compute_dtype"]]
    kda, latent = layers(cfg)
    positions = 2 if drafting else 1
    weights = (
        main_params(cfg, 0) + experts_read * expert_params(cfg)
        + h + cfg["vocab_size"] * h          # the final norm, the head
        + positions * h                      # the embedding's rows
    )
    latents = latent * (cache_tokens + positions) * entry
    if drafting:
        weights += mtp_params(cfg, 0) + cfg["vocab_size"] * h + positions * h
        latents += cfg["num_nextn_predict_layers"] * (cache_tokens + positions) * entry
    # each layer's state and tail read once and written once; a second position's beside them
    states = kda * 2 * (matrix_state_bytes(cfg) + tail_bytes(cfg))
    if drafting:
        states += keep_bytes(cfg)
    return weights * itemsize + latents + states


def causal_attention_flops(cfg: dict, tokens: int) -> float:
    """One MLA layer over `tokens`, expanded: q k^T over nope + rope and
    p v over the value width for every head, the lower triangle only."""
    width = cfg["num_attention_heads"] * (cfg["qk_head_dim"] + cfg["v_head_dim"])
    return 2.0 * width * tokens * (tokens + 1) / 2.0


def delta_rule_flops(cfg: dict, tokens: int) -> float:
    """One KDA layer's chunked delta rule over `tokens`, as
    `solar_counts.delta_rule_flops` counts it: a chunk of C tokens and a
    head of width d take 5 C^2 d + 6 C d^2."""
    chunk, d = cfg["as_run"]["kda_chunk"], cfg["head_dim"]
    chunks = -(-tokens // chunk)
    return float(chunks * cfg["num_attention_heads"] * (5 * chunk * chunk * d + 6 * chunk * d * d))


def mtp_prefill_params(cfg: dict) -> int:
    """Of the MTP module a prefill multiplies by W_eh and W_dkv."""
    h = cfg["hidden_size"]
    return 2 * h * h + h * cache_width(cfg)


def prefill_flops(cfg: dict, tokens: int, pairs_held: float) -> float:
    """One prefill: two operations a weight and token for what every
    token passes through (the main layers; of the MTP module W_eh and
    W_dkv), the held experts for the pairs that fell on them, the MLA
    layer's causal attention, the KDA layers' delta rule, and the head
    for one token."""
    kda, latent = layers(cfg)
    per_token = (
        kda * kda_matrix_params(cfg) + latent * mla_matrix_params(cfg)
        + dense_layers(cfg) * dense_params(cfg) + sparse_layers(cfg) * always_params(cfg)
        + cfg["num_nextn_predict_layers"] * mtp_prefill_params(cfg)
    )
    return (
        2.0 * tokens * per_token
        + 2.0 * pairs_held * expert_params(cfg)
        + latent * causal_attention_flops(cfg, tokens)
        + kda * delta_rule_flops(cfg, tokens)
        + 2.0 * cfg["vocab_size"] * cfg["hidden_size"]
    )


def prefill_bytes(cfg: dict, tokens: int) -> float:
    """Every weight the prefill uses once (all held experts are touched
    by 8,192 tokens; of the MTP module W_eh, W_dkv), the embedding's
    rows, and the state written (one slot)."""
    itemsize = BYTES[cfg["as_run"]["weights_dtype"]]
    unused = mtp_params(cfg, cfg["num_experts"]) - mtp_prefill_params(cfg)
    rows = (tokens - cfg["vocab_size"]) * cfg["hidden_size"]  # rows in place of the table
    return ((total_params(cfg) - unused + rows) * itemsize + cache_bytes(cfg, tokens)
            + state_bytes(cfg) / 2)
