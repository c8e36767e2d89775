"""Analytic counts for the SDAR cell: parameters published and held, and
the bytes of a denoising pass and of a closing pass and the operations of
one prefill, from the sizes in configs/sdar-30b-a3b-chat.json; the chip's
peaks keyed by `device_kind` are flux_counts' one table. Kept with the
benchmark so that every PR computes a roofline share in the same way.

A multiply-add counts as two operations. Bytes are what the algorithm has
to move, not what an implementation moves. A pass runs one block's
`block_length` positions through every layer at batch 1 and reads every
weight it uses once whatever the positions: each layer's attention
matrices, its norms and its router; of the experts the **distinct** ones
the block's positions chose, a layer (two positions on one expert read it
once: the node's `decode_experts_read`, summed over layers and passes),
each its two matrices (gate and up side by side, and down); the
embedding's rows; the keys and values of every layer below the block's
end, read, and the block's own entries written; and, on a denoising pass
alone, the final norm and the head. A closing pass stops at the last
layer's keys and values: it runs no head and, of its last layer, only the
first norm, W_k, W_v and the keys' norm (nothing reads what follows), so
`decode_experts_read` counts none of that layer's experts for it. The
cache's length is that of mid-decode, which is exact for bytes that grow
by the same amount every block.

The prefill runs the prompt's whole blocks: the projections and the
router for every token, `num_experts_per_tok` experts a token (every
expert is held, so every pair counts), attention as the block mask gives
it, position i over `block` x floor(i / `block`) + `block` keys, and the
head at one position.
"""

from __future__ import annotations

import json
import os

from flux_counts import BYTES, PEAKS, peaks  # noqa: F401  (the one table of peaks)

HERE = os.path.dirname(os.path.abspath(__file__))


def config() -> dict:
    with open(os.path.join(HERE, "configs", "sdar-30b-a3b-chat.json"), encoding="utf-8") as fh:
        return json.load(fh)


def block_length(cfg: dict) -> int:
    return cfg["as_run"]["block_length"]


def kv_width(cfg: dict) -> int:
    return cfg["num_key_value_heads"] * cfg["head_dim"]


def attention_matrix_params(cfg: dict) -> int:
    """W_q and W_o over the query heads, W_k and W_v over the key heads."""
    h = cfg["hidden_size"]
    return 2 * h * cfg["num_attention_heads"] * cfg["head_dim"] + 2 * h * kv_width(cfg)


def expert_params(cfg: dict) -> int:
    """One expert's two matrices: gate and up side by side, and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_matrices_bytes(cfg: dict) -> int:
    return expert_params(cfg) * BYTES[cfg["as_run"]["weights_dtype"]]


def layer_params(cfg: dict, experts: float) -> float:
    """A layer with `experts` experts: the attention's matrices and its
    two norms over a head's channels, the layer's two norms, the router."""
    h = cfg["hidden_size"]
    return (attention_matrix_params(cfg) + 2 * cfg["head_dim"] + 2 * h
            + h * cfg["num_experts"] + experts * expert_params(cfg))


def total_params(cfg: dict, layers: int | None = None) -> int:
    """Embedding, `layers` whole layers (the file's `num_hidden_layers`:
    those held), the final norm and the untied head."""
    layers = cfg["num_hidden_layers"] if layers is None else layers
    h = cfg["hidden_size"]
    return int(layers * layer_params(cfg, cfg["num_experts"]) + 2 * cfg["vocab_size"] * h + h)


def cache_bytes(cfg: dict, tokens: int) -> int:
    """A key and a value of every key head, a position and layer."""
    return (cfg["num_hidden_layers"] * tokens * 2 * kv_width(cfg)
            * BYTES[cfg["as_run"]["compute_dtype"]])


def pass_bytes(cfg: dict, experts_read: float, cache_tokens: int, closing: bool) -> float:
    """One pass over one block: see the module's docstring.
    `experts_read`: distinct experts the pass read, summed over its
    layers; `cache_tokens`: entries below the block's end."""
    h, block = cfg["hidden_size"], block_length(cfg)
    itemsize = BYTES[cfg["as_run"]["weights_dtype"]]
    weights = (cfg["num_hidden_layers"] * layer_params(cfg, 0)
               + experts_read * expert_params(cfg) + block * h)
    read = cache_bytes(cfg, cache_tokens)
    if closing:
        # of the last layer: the first norm, W_k, W_v and the keys' norm; its cache is not read
        weights -= layer_params(cfg, 0) - (h + 2 * h * kv_width(cfg) + cfg["head_dim"])
        read -= read // cfg["num_hidden_layers"]
    else:
        weights += h + cfg["vocab_size"] * h
    return weights * itemsize + read + cache_bytes(cfg, block)


def decode_bytes(cfg: dict, denoise_passes: int, closing_passes: int, experts_read: float,
                 cache_tokens: int) -> float:
    """A request's passes, the experts read shared among them by the
    expert layers each runs (a closing pass one fewer)."""
    layers = cfg["num_hidden_layers"]
    bodies = denoise_passes * layers + closing_passes * (layers - 1)
    each = experts_read / float(bodies) if bodies else 0.0
    return (denoise_passes * pass_bytes(cfg, each * layers, cache_tokens, False)
            + closing_passes * pass_bytes(cfg, each * (layers - 1), cache_tokens, True))


def expected_experts_read(cfg: dict) -> float:
    """Distinct experts a pass reads a layer under an even router: the
    union of `block_length` positions' independent choices."""
    experts, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    return experts * (1.0 - (1.0 - k / float(experts)) ** block_length(cfg))


def block_attention_flops(cfg: dict, tokens: int) -> float:
    """One layer over `tokens` (whole blocks): q k^T and p v for every
    query head, position i over block x floor(i / block) + block keys."""
    width, block = cfg["num_attention_heads"] * cfg["head_dim"], block_length(cfg)
    blocks = tokens // block
    return 4.0 * width * block * block * blocks * (blocks + 1) / 2.0


def prefill_flops(cfg: dict, tokens: int, pairs: float) -> float:
    """One prefill of `tokens` positions (the prompt's whole blocks):
    two operations a weight and token for the attention's matrices and
    the router, the experts for the token-expert `pairs`, the block-mask
    attention, and the head for one token."""
    h = cfg["hidden_size"]
    per_token = cfg["num_hidden_layers"] * (attention_matrix_params(cfg) + h * cfg["num_experts"])
    return (2.0 * tokens * per_token + 2.0 * pairs * expert_params(cfg)
            + cfg["num_hidden_layers"] * block_attention_flops(cfg, tokens)
            + 2.0 * cfg["vocab_size"] * h)
