"""Analytic counts for the dots3-note-prev cell: parameters (held and
published), and the operations and bytes of one prefill and of one decode
step, from the sizes in configs/dots3-note-prev.json; the chip's peaks
keyed by `device_kind` are flux_counts' one table. Kept with the benchmark
so that every PR computes a roofline share in the same way.

A multiply-add counts as two operations. Operations are what the **model**
defines, whatever form the program computes them in. A full layer's index
scores every position a query sees (64 heads x 128); its attention reads
the **chosen** positions only, min(t + 1, `index_topk`) of them for the
query at t. A sliding layer's attention reads min(t + 1,
`sliding_window_size`) positions. Both are counted in the expanded form
(a key and a value of every head built once a position from its latent,
then nope + rope + value multiply-adds a head and pair). A program that
multiplies whole blocks the band only crosses, or folds W_uk into the
query and works over the 576-wide latents, does work beyond this count,
so no share can pass 100 for it.

A decode step at batch 1 reads every weight it uses once: both kinds'
attention matrices and gates, the two indexes' matrices, the dense part,
routers and shared experts; of the routed experts the **distinct** held
ones the step's token fell on (the node's `decode_experts_read`); the
head; the embedding's row. Of the state it reads, as the step's form
does (the selection a mask, `mla.absorbed` under it): each full layer's
latent cache whole at the length of mid-decode (1,152 B a position) and
its index keys (256 B a position), each sliding layer's ring whole
(`ring_positions` rows of 2,176 B); and writes a row in each.
"""

from __future__ import annotations

import json
import os

from flux_counts import BYTES, PEAKS, peaks  # noqa: F401  (the one table of peaks)

HERE = os.path.dirname(os.path.abspath(__file__))
FULL = "full_attention"
RING_MULTIPLE = 8


def config() -> dict:
    with open(os.path.join(HERE, "configs", "dots3-note-prev.json"), encoding="utf-8") as fh:
        return json.load(fh)


def held_layers(cfg: dict) -> range:
    """The published indices of the layers held."""
    return range(cfg["first_layer"], cfg["first_layer"] + cfg["num_hidden_layers"])


def full_layers(cfg: dict) -> int:
    return sum(cfg["layer_types"][i] == FULL for i in held_layers(cfg))


def window_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - full_layers(cfg)


def dense_layers(cfg: dict) -> int:
    return sum(i < cfg["first_k_dense_replace"] for i in held_layers(cfg))


def kind(cfg: dict, full: bool) -> dict:
    """One layer kind's sizes (a sliding layer's are the `swa_` keys)."""
    at = "" if full else "swa_"
    return {
        "heads": cfg[at + "num_attention_heads"], "q_rank": cfg[at + "q_lora_rank"],
        "rank": cfg[at + "kv_lora_rank"], "nope": cfg[at + "qk_nope_head_dim"],
        "rope": cfg[at + "qk_rope_head_dim"], "value": cfg[at + "v_head_dim"],
    }


def cache_width(cfg: dict, full: bool) -> int:
    k = kind(cfg, full)
    return k["rank"] + k["rope"]


def ring_positions(cfg: dict) -> int:
    return -(-cfg["sliding_window_size"] // RING_MULTIPLE) * RING_MULTIPLE


def projection_params(cfg: dict, full: bool) -> int:
    """What every position passes: W_dq, W_uq, W_dkv, the gate, W_o."""
    h, k = cfg["hidden_size"], kind(cfg, full)
    return (h * k["q_rank"] + k["q_rank"] * k["heads"] * (k["nope"] + k["rope"])
            + h * (k["rank"] + k["rope"]) + h * k["heads"] + k["heads"] * k["value"] * h)


def up_params(cfg: dict, full: bool) -> int:
    """W_uk and W_uv: a key and a value of every head from a latent."""
    k = kind(cfg, full)
    return k["rank"] * k["heads"] * (k["nope"] + k["value"])


def attention_params(cfg: dict, full: bool) -> int:
    """The matrices and the two norms' scales."""
    k = kind(cfg, full)
    return projection_params(cfg, full) + up_params(cfg, full) + k["q_rank"] + k["rank"]


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
    index where it is full, and its feed-forward part (a sparse one's
    router, selection bias and shared expert)."""
    ffn = dense_params(cfg) if dense else (
        always_params(cfg) + cfg["published"]["n_routed_experts"] + experts * expert_params(cfg))
    return attention_params(cfg, full) + 2 * cfg["hidden_size"] + full * indexer_params(cfg) + ffn


def layers_params(cfg: dict, layers, experts: float) -> float:
    return sum(
        layer_params(cfg, i < cfg["first_k_dense_replace"], cfg["layer_types"][i] == FULL, experts)
        for i in layers)


def total_params(cfg: dict) -> int:
    """Everything the chip holds: `n_routed_experts` and `vocab_size` in
    the file are the held counts."""
    h = cfg["hidden_size"]
    return int(layers_params(cfg, held_layers(cfg), cfg["n_routed_experts"])
               + 2 * cfg["vocab_size"] * h + h)


def published_params(cfg: dict) -> int:
    """The text path uncut: every layer, expert and id."""
    h, p = cfg["hidden_size"], cfg["published"]
    return int(layers_params(cfg, range(p["num_hidden_layers"]), p["n_routed_experts"])
               + 2 * p["vocab_size"] * h + h)


def row_bytes(cfg: dict, width: int) -> int:
    return width * BYTES[cfg["as_run"]["compute_dtype"]]


def cache_bytes(cfg: dict, tokens: int) -> int:
    """The two caches that grow: a latent row and an index key a position
    in every full layer."""
    return full_layers(cfg) * tokens * row_bytes(
        cfg, cache_width(cfg, True) + cfg["index_head_dim"])


def state_bytes(cfg: dict) -> int:
    """The rings: `ring_positions` rows in every sliding layer."""
    return window_layers(cfg) * ring_positions(cfg) * row_bytes(cfg, cache_width(cfg, False))


def keys_visible(first: int, last: int) -> int:
    """Positions the queries at `first` .. `last` - 1 see: t + 1 each."""
    return (last * (last + 1) - first * (first + 1)) // 2


def keys_within(first: int, last: int, most: int) -> int:
    """Positions they read under a cap: min(t + 1, most) each (a
    selection of `most`, or a window of `most`)."""
    bend = min(max(most, first), last)  # queries below `bend` see `most` positions or fewer
    return keys_visible(first, bend) + (last - bend) * most


def decode_step_bytes(cfg: dict, experts_read: float, cache_tokens: int) -> float:
    """One step: see the module's docstring. `experts_read`: distinct
    held experts the step read, summed over its sparse layers."""
    h = cfg["hidden_size"]
    weights = (
        layers_params(cfg, held_layers(cfg), 0) + experts_read * expert_params(cfg)
        + h + cfg["vocab_size"] * h          # the final norm, the head
        + h                                  # the embedding's row
    )
    state = (
        cache_bytes(cfg, cache_tokens + 1) + state_bytes(cfg)
        + window_layers(cfg) * row_bytes(cfg, cache_width(cfg, False)))
    return weights * BYTES[cfg["as_run"]["weights_dtype"]] + state


def index_flops(cfg: dict, tokens: int) -> float:
    """One full layer's index over `tokens`: every head's product with
    every position the query sees."""
    return 2.0 * cfg["index_n_heads"] * cfg["index_head_dim"] * keys_visible(0, tokens)


def attention_flops(cfg: dict, full: bool, pairs: int) -> float:
    """One layer's attention over `pairs` query-key pairs, expanded: q
    k^T over nope + rope and p v over the value width, a head."""
    k = kind(cfg, full)
    return 2.0 * k["heads"] * (k["nope"] + k["rope"] + k["value"]) * pairs


def band_flops(cfg: dict, tokens: int) -> float:
    """One sliding layer's attention over `tokens`: what the band lets
    through, min(t + 1, window) keys a query."""
    return attention_flops(cfg, False, keys_within(0, tokens, cfg["sliding_window_size"]))


def band_bytes(cfg: dict, rows: int, keys: int) -> float:
    """One band call of `rows` queries over `keys` keys: q, the output,
    the keys and the values once."""
    k = kind(cfg, False)
    q, out = rows * k["heads"] * (k["nope"] + k["rope"]), rows * k["heads"] * k["value"]
    kv = keys * k["heads"] * (k["nope"] + k["rope"] + k["value"])
    return float(q + out + kv) * BYTES[cfg["as_run"]["compute_dtype"]]


def prefill_flops(cfg: dict, tokens: int, pairs_held: float) -> float:
    """One prefill: two operations a weight and token for what every
    token passes through (each layer's projections and gate with a key
    and a value of every head built once a position, the indexes'
    matrices, the dense part, routers and shared experts), the held
    experts for the pairs that fell on them, each full layer's index
    over the visible positions and attention over the chosen ones, each
    sliding layer's band, and the head for one token."""
    full, window = full_layers(cfg), window_layers(cfg)
    per_token = (
        full * (projection_params(cfg, True) + up_params(cfg, True) + indexer_matrix_params(cfg))
        + window * (projection_params(cfg, False) + up_params(cfg, False))
        + dense_layers(cfg) * dense_params(cfg)
        + (cfg["num_hidden_layers"] - dense_layers(cfg)) * always_params(cfg)
    )
    return (
        2.0 * tokens * per_token
        + 2.0 * pairs_held * expert_params(cfg)
        + full * index_flops(cfg, tokens)
        + full * attention_flops(cfg, True, keys_within(0, tokens, cfg["index_topk"]))
        + window * band_flops(cfg, tokens)
        + 2.0 * cfg["vocab_size"] * cfg["hidden_size"]
    )
