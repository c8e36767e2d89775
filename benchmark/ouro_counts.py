"""Analytic counts for the Ouro-2.6B cell: parameters, and the operations
and bytes of one prefill and of one decode step, from the sizes in
configs/ouro-2.6b.json; the chip's peaks keyed by `device_kind` are
flux_counts' one table. Kept with the benchmark so that the parity
script's clock, and the day `xplane.py` gives device seconds by program,
reckon a roofline share in the same way.

A multiply-add counts as two operations. Bytes are what the algorithm has
to move, not what an implementation moves. The loop shares weights, not
work: a token walks the stack `total_ut_steps` times, so a decode step at
batch 1 reads every layer's weights once *a pass* (nothing holds 4.9 GB
between passes), one row of the embedding, the head once, and every
(pass, layer) slot of the cache so far. The prefill's attention is an XLA
form (`ops/attention.causal_attention_blocked`), not a kernel of this
repo, so there is no kernel call to count; its operations are
`causal_attention_flops`.
"""

from __future__ import annotations

import json
import os

from flux_counts import BYTES, PEAKS, peaks  # noqa: F401  (the one table of peaks)

HERE = os.path.dirname(os.path.abspath(__file__))


def config() -> dict:
    with open(os.path.join(HERE, "configs", "ouro-2.6b.json"), encoding="utf-8") as fh:
        return json.load(fh)


def width(cfg: dict) -> int:
    return cfg["num_attention_heads"] * cfg["head_dim"]


def layer_matrix_params(cfg: dict) -> int:
    """One layer's matrices: q, k, v, o; gate, up, down."""
    h = cfg["hidden_size"]
    return 4 * h * width(cfg) + 3 * h * cfg["intermediate_size"]


def layer_params(cfg: dict) -> int:
    """The matrices and four norms."""
    return layer_matrix_params(cfg) + 4 * cfg["hidden_size"]


def total_params(cfg: dict) -> int:
    """One stack of layers, the embedding and the untied head, the final
    norm, and the exit gate with its bias."""
    h = cfg["hidden_size"]
    return (
        cfg["num_hidden_layers"] * layer_params(cfg)
        + 2 * cfg["vocab_size"] * h + h + (h + 1)
    )


def layer_passes(cfg: dict) -> int:
    return cfg["total_ut_steps"] * cfg["num_hidden_layers"]


def cache_bytes_per_token(cfg: dict) -> int:
    """A key and a value of every head in every (pass, layer) slot."""
    return layer_passes(cfg) * 2 * width(cfg) * BYTES[cfg["as_run"]["compute_dtype"]]


def cache_bytes(cfg: dict, tokens: int) -> int:
    return tokens * cache_bytes_per_token(cfg)


def decode_step_bytes(cfg: dict, cache_tokens: int) -> float:
    """Every layer's weights once a pass, the final norm and gate a pass,
    the embedding's row and the head, and the cache of the tokens so far."""
    itemsize = BYTES[cfg["as_run"]["weights_dtype"]]
    h = cfg["hidden_size"]
    weights = (
        layer_passes(cfg) * layer_params(cfg)
        + cfg["total_ut_steps"] * (2 * h + 1)
        + h + cfg["vocab_size"] * h
    )
    return weights * itemsize + cache_bytes(cfg, cache_tokens)


def decode_step_flops(cfg: dict, cache_tokens: int) -> float:
    """Two operations a weight a pass, and scores and the weighted sum
    over the cache in every slot."""
    matrices = layer_matrix_params(cfg)
    attention = 4.0 * width(cfg) * cache_tokens * layer_passes(cfg)
    return (
        2.0 * layer_passes(cfg) * matrices
        + 2.0 * cfg["vocab_size"] * cfg["hidden_size"] + attention
    )


def causal_attention_flops(cfg: dict, tokens: int) -> float:
    """One slot's causal attention over `tokens`: q k^T and p v, the
    lower triangle only."""
    return 4.0 * width(cfg) * tokens * (tokens + 1) / 2.0


def prefill_flops(cfg: dict, tokens: int) -> float:
    """One prefill: two operations a weight, token and pass, causal
    attention in every slot, and the head for one token."""
    matrices = layer_matrix_params(cfg)
    return (
        2.0 * tokens * layer_passes(cfg) * matrices
        + layer_passes(cfg) * causal_attention_flops(cfg, tokens)
        + 2.0 * cfg["vocab_size"] * cfg["hidden_size"]
    )


def prefill_bytes(cfg: dict, tokens: int) -> float:
    """Every layer's weights once a pass, the embedding's rows and the
    head, and the cache written."""
    itemsize = BYTES[cfg["as_run"]["weights_dtype"]]
    h = cfg["hidden_size"]
    weights = layer_passes(cfg) * layer_params(cfg) + tokens * h + cfg["vocab_size"] * h
    return weights * itemsize + cache_bytes(cfg, tokens)
