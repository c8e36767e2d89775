"""Analytic counts for the Nemotron-3-Nano cell: parameters, and the
operations and bytes of one prefill and of one decode step, from the
sizes in configs/nemotron-3-nano-30b-a3b.json; the chip's peaks keyed by
`device_kind` are flux_counts' one table. Kept with the benchmark so that
every PR computes a roofline share in the same way.

A multiply-add counts as two operations. Bytes are what the algorithm has
to move, not what an implementation moves: a decode step at batch 1 reads
every weight it uses once (of the routed experts only the **distinct**
held ones its token's pairs fell on: the node's `decode_experts_read`),
one row of the embedding, the six attention blocks' keys and values so
far, and **reads and writes each Mamba-2 block's matrix state once** (it
is the whole of a state-space block's memory: 2.10 MB a block, whatever
the position) with its convolution tail. The prefill's chunked scan is
XLA operations, not a kernel of this repo, so there is no kernel call to
count; its operations are `ssd_flops` (four products a chunk). The
decode's grouped products are `ops/expert_matvec`'s kernel calls, whose
bytes are `expert_matrices_bytes` a distinct expert read.
"""

from __future__ import annotations

import json
import os

from flux_counts import BYTES, PEAKS, peaks  # noqa: F401  (the one table of peaks)

HERE = os.path.dirname(os.path.abspath(__file__))


def config() -> dict:
    with open(os.path.join(HERE, "configs", "nemotron-3-nano-30b-a3b.json"),
              encoding="utf-8") as fh:
        return json.load(fh)


def blocks(cfg: dict) -> tuple[int, int, int]:
    """(Mamba-2, sparse, attention) blocks of the published string."""
    pattern = cfg["hybrid_override_pattern"]
    return pattern.count("M"), pattern.count("E"), pattern.count("*")


def mamba_inner(cfg: dict) -> int:
    return cfg["mamba_num_heads"] * cfg["mamba_head_dim"]


def conv_channels(cfg: dict) -> int:
    """x, B and C side by side."""
    return mamba_inner(cfg) + 2 * cfg["n_groups"] * cfg["ssm_state_size"]


def mamba_matrix_params(cfg: dict) -> int:
    """W_in (z, xBC and dt side by side) and W_out."""
    h, inner = cfg["hidden_size"], mamba_inner(cfg)
    return h * (inner + conv_channels(cfg) + cfg["mamba_num_heads"]) + inner * h


def mamba_params(cfg: dict) -> int:
    """The matrices, the convolution's filters and bias, A_log, dt_bias
    and D a head, the gated norm's scale."""
    small = ((cfg["conv_kernel"] + 1) * conv_channels(cfg) + 3 * cfg["mamba_num_heads"]
             + mamba_inner(cfg))
    return mamba_matrix_params(cfg) + small


def attention_params(cfg: dict) -> int:
    """W_q and W_o over the query heads, W_k and W_v over the key heads."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    return 2 * h * cfg["num_attention_heads"] * d + 2 * h * cfg["num_key_value_heads"] * d


def expert_params(cfg: dict) -> int:
    """Two matrices, no gate."""
    return 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_matrices_bytes(cfg: dict) -> int:
    """What `ops/expert_matvec`'s two calls fetch for one chosen expert."""
    return expert_params(cfg) * BYTES[cfg["as_run"]["weights_dtype"]]


def always_params(cfg: dict) -> int:
    """What every token passes in a sparse block: the router (its
    published width) and the shared expert."""
    shared = 2 * cfg["hidden_size"] * cfg["moe_shared_expert_intermediate_size"]
    return (cfg["hidden_size"] * cfg["published"]["n_routed_experts"]
            + cfg["n_shared_experts"] * shared)


def small_params(cfg: dict) -> int:
    """One norm a block, the router's selection bias a sparse block, the
    final norm."""
    _, sparse, _ = blocks(cfg)
    return ((cfg["num_hidden_layers"] + 1) * cfg["hidden_size"]
            + sparse * cfg["published"]["n_routed_experts"])


def total_params(cfg: dict) -> int:
    """Everything the chip holds: `n_routed_experts` and `vocab_size` in
    the file are the held counts."""
    mamba, sparse, attention = blocks(cfg)
    return (
        mamba * mamba_params(cfg) + attention * attention_params(cfg)
        + sparse * (always_params(cfg) + cfg["n_routed_experts"] * expert_params(cfg))
        + 2 * cfg["vocab_size"] * cfg["hidden_size"]
        + small_params(cfg)
    )


def cache_bytes(cfg: dict, tokens: int) -> int:
    """A key and a value of every key head in every attention block."""
    width = 2 * cfg["num_key_value_heads"] * cfg["head_dim"]
    return blocks(cfg)[2] * tokens * width * BYTES[cfg["as_run"]["compute_dtype"]]


def state_bytes(cfg: dict) -> int:
    """What does not grow with the position: a matrix state a Mamba-2
    head (`state_dtype`) and the convolution's last inputs."""
    matrices = (cfg["mamba_num_heads"] * cfg["mamba_head_dim"] * cfg["ssm_state_size"]
                * BYTES[cfg["as_run"]["state_dtype"]])
    tails = (cfg["conv_kernel"] - 1) * conv_channels(cfg) * BYTES[cfg["as_run"]["compute_dtype"]]
    return blocks(cfg)[0] * (matrices + tails)


def decode_step_params(cfg: dict, experts_read: float) -> float:
    """Weights one token's step multiplies by: every Mamba-2 and
    attention block, every router and shared expert, the distinct held
    experts its pairs fell on (`experts_read`, over all sparse blocks),
    and the head."""
    mamba, sparse, attention = blocks(cfg)
    return (
        mamba * mamba_params(cfg) + attention * attention_params(cfg)
        + sparse * always_params(cfg) + experts_read * expert_params(cfg)
        + small_params(cfg)
        + cfg["vocab_size"] * cfg["hidden_size"]
    )


def decode_step_bytes(cfg: dict, experts_read: float, cache_tokens: int) -> float:
    """The step's weights once, the embedding's row, the keys and values
    of the tokens so far, and the fixed-size state read and written."""
    itemsize = BYTES[cfg["as_run"]["weights_dtype"]]
    return (
        (decode_step_params(cfg, experts_read) + cfg["hidden_size"]) * itemsize
        + cache_bytes(cfg, cache_tokens)
        + 2 * state_bytes(cfg)
    )


def causal_attention_flops(cfg: dict, tokens: int) -> float:
    """One attention block over `tokens`: q k^T and p v for every query
    head (16 a key head), the lower triangle only."""
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    return 4.0 * width * tokens * (tokens + 1) / 2.0


def ssd_flops(cfg: dict, tokens: int) -> float:
    """One Mamba-2 block's chunked scan over `tokens`, a chunk of Q
    tokens, H heads of P over a state of N in G groups, four products a
    chunk: the scores C B^T a group and their product with u a head
    (their lower triangles: Q (Q + 1) (G N + H P)), what each token reads
    of the entering state and what the chunk adds to it (4 Q H P N)."""
    chunk, heads = cfg["chunk_size"], cfg["mamba_num_heads"]
    width, n, groups = cfg["mamba_head_dim"], cfg["ssm_state_size"], cfg["n_groups"]
    chunks = -(-tokens // chunk)
    own = chunk * (chunk + 1) * (groups * n + heads * width)
    return float(chunks * (own + 4 * chunk * heads * width * n))


def prefill_flops(cfg: dict, tokens: int, pairs_held: float) -> float:
    """One prefill: two operations a weight and token for what every
    token passes through (the projections, routers and shared experts),
    the held experts for the pairs that fell on them, six causal
    attentions, the chunked scans, and the head for one token."""
    mamba, sparse, attention = blocks(cfg)
    per_token = (
        mamba * mamba_matrix_params(cfg) + attention * attention_params(cfg)
        + sparse * always_params(cfg)
    )
    return (
        2.0 * tokens * per_token
        + 2.0 * pairs_held * expert_params(cfg)
        + attention * causal_attention_flops(cfg, tokens)
        + mamba * ssd_flops(cfg, tokens)
        + 2.0 * cfg["vocab_size"] * cfg["hidden_size"]
    )


def prefill_bytes(cfg: dict, tokens: int) -> float:
    """Every weight once (all held experts are touched by 8,192 tokens),
    the embedding's rows, and the state written."""
    itemsize = BYTES[cfg["as_run"]["weights_dtype"]]
    rows = (tokens - cfg["vocab_size"]) * cfg["hidden_size"]  # rows in place of the table
    return (total_params(cfg) + rows) * itemsize + cache_bytes(cfg, tokens) + state_bytes(cfg)
