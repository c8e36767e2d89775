"""Analytic counts for the granite-4.0-h-micro cell: parameters, and the
operations and bytes of one prefill and of one decode step, from the
sizes in configs/granite-4.0-h-micro.json; the chip's peaks keyed by
`device_kind` are flux_counts' one table. Kept with the benchmark so that
every PR computes a roofline share in the same way.

A multiply-add counts as two operations. Bytes are what the algorithm has
to move, not what an implementation moves: a decode step at batch 1 reads
every layer's weights once, **the tied embedding once, as the head** (its
one row as the input is counted beside it), the four attention layers'
keys and values so far, and **reads and writes each of the 36 Mamba-2
layers' matrix states once** (2.10 MB a layer, whatever the position)
with its convolution tail. The prefill's operations are what the model
defines, whatever the parts it is read in: two a weight and token for the
projections and the SwiGLUs, the chunked scans' four products a chunk at
the chunk the program uses (`as_run.prefill_chunk`), causal attention as
the mask gives it (position i over i + 1 keys, at the heads' true width
of 64: what a kernel pads to its lane tile is not counted), and the head
at one position. The causal kernel's own operations and bytes a call are
`causal_call_flops` and `causal_call_bytes`.
"""

from __future__ import annotations

import json
import os

from flux_counts import BYTES, PEAKS, peaks  # noqa: F401  (the one table of peaks)

HERE = os.path.dirname(os.path.abspath(__file__))


def config() -> dict:
    with open(os.path.join(HERE, "configs", "granite-4.0-h-micro.json"), encoding="utf-8") as fh:
        return json.load(fh)


def layers(cfg: dict) -> tuple[int, int]:
    """(Mamba-2, attention) layers of `layer_types`."""
    kinds = cfg["layer_types"]
    return kinds.count("mamba"), kinds.count("attention")


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def mamba_inner(cfg: dict) -> int:
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"]


def conv_channels(cfg: dict) -> int:
    """x, B and C side by side."""
    return mamba_inner(cfg) + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]


def mamba_matrix_params(cfg: dict) -> int:
    """W_in (z, xBC and dt side by side) and W_out."""
    h, inner = cfg["hidden_size"], mamba_inner(cfg)
    return h * (inner + conv_channels(cfg) + cfg["mamba_n_heads"]) + inner * h


def mamba_params(cfg: dict) -> int:
    """The matrices, the convolution's filters and bias, A_log, dt_bias
    and D a head, the gated norm's scale."""
    small = ((cfg["mamba_d_conv"] + 1) * conv_channels(cfg) + 3 * cfg["mamba_n_heads"]
             + mamba_inner(cfg))
    return mamba_matrix_params(cfg) + small


def attention_params(cfg: dict) -> int:
    """W_q and W_o over the query heads, W_k and W_v over the key heads."""
    h, d = cfg["hidden_size"], head_dim(cfg)
    return 2 * h * cfg["num_attention_heads"] * d + 2 * h * cfg["num_key_value_heads"] * d


def mlp_params(cfg: dict) -> int:
    """A layer's SwiGLU: gate and up side by side, then down."""
    return 3 * cfg["hidden_size"] * cfg["shared_intermediate_size"]


def small_params(cfg: dict) -> int:
    """Two norms a layer and the final one."""
    return (2 * cfg["num_hidden_layers"] + 1) * cfg["hidden_size"]


def embedding_params(cfg: dict) -> int:
    """The embedding, which is the head too: counted once."""
    return cfg["vocab_size"] * cfg["hidden_size"]


def total_params(cfg: dict) -> int:
    mamba, attention = layers(cfg)
    return (mamba * mamba_params(cfg) + attention * attention_params(cfg)
            + cfg["num_hidden_layers"] * mlp_params(cfg) + embedding_params(cfg)
            + small_params(cfg))


def cache_bytes(cfg: dict, tokens: int) -> int:
    """A key and a value of every key head in every attention layer."""
    width = 2 * cfg["num_key_value_heads"] * head_dim(cfg)
    return layers(cfg)[1] * tokens * width * BYTES[cfg["as_run"]["compute_dtype"]]


def state_bytes(cfg: dict) -> int:
    """What does not grow with the position: a matrix state a Mamba-2
    head (`state_dtype`) and the convolution's last inputs."""
    matrices = (cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"]
                * BYTES[cfg["as_run"]["state_dtype"]])
    tails = (cfg["mamba_d_conv"] - 1) * conv_channels(cfg) * BYTES[cfg["as_run"]["compute_dtype"]]
    return layers(cfg)[0] * (matrices + tails)


def decode_step_bytes(cfg: dict, cache_tokens: int) -> float:
    """Every weight once (the tied embedding as the head), the
    embedding's row, the keys and values of the tokens so far, and the
    fixed-size state read and written."""
    itemsize = BYTES[cfg["as_run"]["weights_dtype"]]
    return ((total_params(cfg) + cfg["hidden_size"]) * itemsize
            + cache_bytes(cfg, cache_tokens) + 2 * state_bytes(cfg))


def causal_attention_flops(cfg: dict, tokens: int) -> float:
    """One attention layer over `tokens`: q k^T and p v for every query
    head (4 a key head) at the true width, the lower triangle only."""
    width = cfg["num_attention_heads"] * head_dim(cfg)
    return 4.0 * width * tokens * (tokens + 1) / 2.0


def ssd_flops(cfg: dict, tokens: int) -> float:
    """One Mamba-2 layer's chunked scan over `tokens`, a chunk of Q
    tokens, H heads of P over a state of N in G groups, four products a
    chunk: the scores C B^T a group and their product with u a head
    (their lower triangles: Q (Q + 1) (G N + H P)), what each token reads
    of the entering state and what the chunk adds to it (4 Q H P N)."""
    chunk, heads = cfg["as_run"]["prefill_chunk"], cfg["mamba_n_heads"]
    width, n, groups = cfg["mamba_d_head"], cfg["mamba_d_state"], cfg["mamba_n_groups"]
    chunks = -(-tokens // chunk)
    own = chunk * (chunk + 1) * (groups * n + heads * width)
    return float(chunks * (own + 4 * chunk * heads * width * n))


def prefill_flops(cfg: dict, tokens: int) -> float:
    """One prefill: two operations a weight and token for the
    projections and the SwiGLUs, four causal attentions, the chunked
    scans, and the head for one token."""
    mamba, attention = layers(cfg)
    per_token = (mamba * mamba_matrix_params(cfg) + attention * attention_params(cfg)
                 + cfg["num_hidden_layers"] * mlp_params(cfg))
    return (2.0 * tokens * per_token
            + attention * causal_attention_flops(cfg, tokens)
            + mamba * ssd_flops(cfg, tokens)
            + 2.0 * embedding_params(cfg))


def prefill_bytes(cfg: dict, tokens: int) -> float:
    """Every weight once a part of the prompt, the prompt's rows of the
    embedding, the state read and written a part, the cache written and
    each part's keys so far read."""
    itemsize = BYTES[cfg["as_run"]["weights_dtype"]]
    part = cfg["as_run"]["prefill_part"]
    parts = -(-tokens // part)
    read = sum(cache_bytes(cfg, min((i + 1) * part, tokens)) for i in range(parts))
    return (parts * (total_params(cfg) - embedding_params(cfg)) * itemsize
            + (embedding_params(cfg) + tokens * cfg["hidden_size"]) * itemsize
            + 2 * parts * state_bytes(cfg) + cache_bytes(cfg, tokens) + read)


# --- the causal kernel at this model's heads --------------------------------


def causal_call_flops(cfg: dict, rows: int, keys: int) -> float:
    """What one `flash_attention_causal` call has to compute of `rows`
    queries (the last `rows` positions) over `keys` keys: row i sees
    keys - rows + i + 1 of them, two products, every query head at its
    true width. The kernel multiplies heads padded to the lane tile and
    whole blocks on the diagonal: that is its cost, not the call's work."""
    width = cfg["num_attention_heads"] * head_dim(cfg)
    seen = rows * (keys - rows) + rows * (rows + 1) / 2.0
    return 4.0 * width * seen


def causal_call_bytes(cfg: dict, rows: int, keys: int) -> float:
    """The call's operands once at their true width: q and the output,
    and every key and value the queries see."""
    itemsize, d = BYTES[cfg["as_run"]["compute_dtype"]], head_dim(cfg)
    return itemsize * d * (
        2 * rows * cfg["num_attention_heads"] + 2 * keys * cfg["num_key_value_heads"])


def prefill_causal_calls(cfg: dict, tokens: int) -> list[tuple[int, int]]:
    """(rows, keys) of the causal calls one attention layer makes over a
    prompt read in parts of `as_run.prefill_part`."""
    part = cfg["as_run"]["prefill_part"]
    return [(min(part, tokens - start), min(start + part, tokens))
            for start in range(0, tokens, part)]
