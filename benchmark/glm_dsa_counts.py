"""Analytic counts for the GLM-5.2 cell: parameters, and the operations
and bytes of one prefill and of one decode step, from the sizes in
configs/glm-5.2.json; the chip's peaks keyed by `device_kind` are
flux_counts' one table. Kept with the benchmark so that every PR computes
a roofline share in the same way.

A multiply-add counts as two operations. Operations and bytes are what
the **model** defines, whatever form the program computes them in: the
indexer scores every position a query sees (32 heads x 128, float32
accumulation), attention reads the **chosen** positions only, min(t + 1,
`index_topk`) of them for the query at t, and is counted in the expanded
form (a key and a value of every head built once a position from its
latent, then `qk_head_dim` + `v_head_dim` multiply-adds a head and pair).
A program that computes every visible key under a mask, or folds W_uk
into the query and works over the 576-wide latents (more operations a
pair), or reads the whole latent cache in a step, does work beyond this
count, so neither share can pass 100 for it.

A self-speculative step at batch 1 runs two positions through the main
model and two through the MTP module, and reads every weight it uses once
whatever the positions: the attention and indexer matrices, the dense
feed-forward part, routers and shared experts; of the routed experts the
**distinct** held ones the step's positions fell on (the node's
`decode_experts_read`); the head once a use (the main model's two rows
share one read, the module's use is another); `W_eh`; the embedding's
rows. Of the two caches it reads, a layer, each position's chosen rows of
the latents (1,152 B each) and, in a layer with an indexer, that cache
whole at the length of mid-decode (256 B a position); and writes a row a
position in each. A step without drafting runs one position and nothing
of the module.

Of the MTP module a prefill computes only what the decode will read of
it, the prompt's latents and indexer keys: `W_eh`, `W_dkv`, the
indexer's `W_k`.
"""

from __future__ import annotations

import json
import os

from flux_counts import BYTES, PEAKS, peaks  # noqa: F401  (the one table of peaks)

HERE = os.path.dirname(os.path.abspath(__file__))


def config() -> dict:
    with open(os.path.join(HERE, "configs", "glm-5.2.json"), encoding="utf-8") as fh:
        return json.load(fh)


def held_layers(cfg: dict) -> range:
    """The published indices of the layers held."""
    return range(cfg["first_layer"], cfg["first_layer"] + cfg["num_hidden_layers"])


def full_layers(cfg: dict) -> int:
    """Held layers of the main model that compute an index."""
    return sum(cfg["indexer_types"][i] == "full" for i in held_layers(cfg))


def dense_layers(cfg: dict) -> int:
    return sum(cfg["mlp_layer_types"][i] == "dense" for i in held_layers(cfg))


def sparse_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - dense_layers(cfg)


def cache_width(cfg: dict) -> int:
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def mla_projection_params(cfg: dict) -> int:
    """What every position passes: W_dq, W_uq, W_dkv, W_o."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return (h * cfg["q_lora_rank"] + cfg["q_lora_rank"] * heads * cfg["qk_head_dim"]
            + h * cache_width(cfg) + heads * cfg["v_head_dim"] * h)


def mla_up_params(cfg: dict) -> int:
    """W_uk and W_uv: a key and a value of every head from a latent."""
    return cfg["kv_lora_rank"] * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["v_head_dim"])


def mla_params(cfg: dict) -> int:
    """The matrices and the two norms' scales."""
    return mla_projection_params(cfg) + mla_up_params(cfg) + cfg["q_lora_rank"] + (
        cfg["kv_lora_rank"])


def indexer_matrix_params(cfg: dict) -> int:
    """W_qI, W_kI, W_w."""
    h, width = cfg["hidden_size"], cfg["index_n_heads"] * cfg["index_head_dim"]
    return cfg["q_lora_rank"] * width + h * cfg["index_head_dim"] + h * cfg["index_n_heads"]


def indexer_params(cfg: dict) -> int:
    """The matrices and the LayerNorm's scale and bias."""
    return indexer_matrix_params(cfg) + 2 * cfg["index_head_dim"]


def dense_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def always_params(cfg: dict) -> int:
    """What every token passes in a sparse layer's feed-forward part: the
    router (its published width) and the shared expert."""
    return (cfg["hidden_size"] * cfg["published"]["n_routed_experts"]
            + cfg["n_shared_experts"] * expert_params(cfg))


def layer_params(cfg: dict, dense: bool, full: bool, experts: float) -> float:
    """A layer with `experts` routed experts: attention, two norms, an
    indexer where it is `full`, and its feed-forward part (a sparse one's
    router, selection bias and shared expert)."""
    ffn = dense_params(cfg) if dense else (
        always_params(cfg) + cfg["published"]["n_routed_experts"] + experts * expert_params(cfg))
    return mla_params(cfg) + 2 * cfg["hidden_size"] + full * indexer_params(cfg) + ffn


def main_params(cfg: dict, experts: float) -> float:
    return sum(
        layer_params(cfg, cfg["mlp_layer_types"][i] == "dense",
                     cfg["indexer_types"][i] == "full", experts)
        for i in held_layers(cfg))


def mtp_params(cfg: dict, experts: float) -> float:
    """W_eh, the three norms of its own, one sparse layer with an indexer."""
    h = cfg["hidden_size"]
    return 2 * h * h + 3 * h + layer_params(cfg, False, True, experts)


def total_params(cfg: dict) -> int:
    """Everything the chip holds: `n_routed_experts` and `vocab_size` in
    the file are the held counts."""
    h, held = cfg["hidden_size"], cfg["n_routed_experts"]
    return int(main_params(cfg, held) + mtp_params(cfg, held) + 2 * cfg["vocab_size"] * h + h)


def latent_row_bytes(cfg: dict) -> int:
    return cache_width(cfg) * BYTES[cfg["as_run"]["compute_dtype"]]


def index_row_bytes(cfg: dict) -> int:
    return cfg["index_head_dim"] * BYTES[cfg["as_run"]["compute_dtype"]]


def indexer_cache_bytes(cfg: dict, tokens: int) -> int:
    return (full_layers(cfg) + cfg["num_nextn_predict_layers"]) * tokens * index_row_bytes(cfg)


def cache_bytes(cfg: dict, tokens: int) -> int:
    """Both caches: a latent row a position in every layer and the MTP
    module's, an indexer key a position in every `full` layer and the
    module's."""
    layers = cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]
    return layers * tokens * latent_row_bytes(cfg) + indexer_cache_bytes(cfg, tokens)


def keys_visible(first: int, last: int) -> int:
    """Positions the queries at `first` .. `last` - 1 see: t + 1 each."""
    return (last * (last + 1) - first * (first + 1)) // 2


def keys_chosen(first: int, last: int, topk: int) -> int:
    """Positions they read: min(t + 1, topk) each."""
    bend = min(max(topk, first), last)  # queries below `bend` see `topk` positions or fewer
    return keys_visible(first, bend) + (last - bend) * topk


def decode_step_bytes(cfg: dict, experts_read: float, cache_tokens: int,
                      drafting: bool = True) -> float:
    """One step: see the module's docstring. `experts_read`: distinct
    held experts the step read, summed over its sparse layers (the MTP
    module's among them when drafting)."""
    h = cfg["hidden_size"]
    itemsize = BYTES[cfg["as_run"]["weights_dtype"]]
    positions = 2 if drafting else 1
    chosen = min(cache_tokens, cfg["index_topk"])
    weights = (
        main_params(cfg, 0) + experts_read * expert_params(cfg)
        + h + cfg["vocab_size"] * h          # the final norm, the head
        + positions * h                      # the embedding's rows
    )
    latent_layers, index_layers = cfg["num_hidden_layers"], full_layers(cfg)
    if drafting:
        weights += mtp_params(cfg, 0) + cfg["vocab_size"] * h + positions * h
        latent_layers += cfg["num_nextn_predict_layers"]
        index_layers += cfg["num_nextn_predict_layers"]
    caches = (
        latent_layers * positions * (chosen + 1) * latent_row_bytes(cfg)
        + index_layers * (cache_tokens + positions) * index_row_bytes(cfg))
    return weights * itemsize + caches


def index_flops(cfg: dict, tokens: int) -> float:
    """One `full` layer's index over `tokens`: every head's product with
    every position the query sees."""
    return 2.0 * cfg["index_n_heads"] * cfg["index_head_dim"] * keys_visible(0, tokens)


def chosen_attention_flops(cfg: dict, tokens: int) -> float:
    """One layer's attention over `tokens`, the chosen positions only,
    expanded: q k^T over `qk_head_dim` and p v over `v_head_dim` a head."""
    pair = 2.0 * cfg["num_attention_heads"] * (cfg["qk_head_dim"] + cfg["v_head_dim"])
    return pair * keys_chosen(0, tokens, cfg["index_topk"])


def prefill_flops(cfg: dict, tokens: int, pairs_held: float) -> float:
    """One prefill: two operations a weight and token for what every
    token passes through (each layer's projections with a key and a
    value of every head built once a position, the indexers' matrices,
    the dense part, routers and shared experts; of the MTP module W_eh,
    W_dkv and the indexer's W_k), the held experts for the pairs that
    fell on them, each `full` layer's index, every layer's attention
    over the chosen positions, and the head for one token."""
    h = cfg["hidden_size"]
    per_token = (
        cfg["num_hidden_layers"] * (mla_projection_params(cfg) + mla_up_params(cfg))
        + full_layers(cfg) * indexer_matrix_params(cfg)
        + dense_layers(cfg) * dense_params(cfg)
        + sparse_layers(cfg) * always_params(cfg)
        + cfg["num_nextn_predict_layers"] * (
            2 * h * h + h * cache_width(cfg) + h * cfg["index_head_dim"])
    )
    return (
        2.0 * tokens * per_token
        + 2.0 * pairs_held * expert_params(cfg)
        + full_layers(cfg) * index_flops(cfg, tokens)
        + cfg["num_hidden_layers"] * chosen_attention_flops(cfg, tokens)
        + 2.0 * cfg["vocab_size"] * h
    )
