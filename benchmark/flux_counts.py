"""Analytic counts for the FLUX.1-dev cell: the operations and bytes of
one call of the flash-attention kernel and of one evaluation of the
denoiser, from the sizes in configs/flux.1-dev.json, and the chip's peaks
keyed by `device_kind`. Kept with the benchmark so that every PR computes
a roofline share in the same way.

A multiply-add counts as two operations. Bytes are what the algorithm has
to move, not what an implementation moves: the kernel reads q, k and v
and writes the output once each, in the configuration's compute dtype.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16, 819 GB/s of
# HBM bandwidth per chip. `device_kind` is what jax.devices()[0] reports.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
}
BYTES = {"bfloat16": 2, "float32": 4}


def config() -> dict:
    with open(os.path.join(HERE, "configs", "flux.1-dev.json"), encoding="utf-8") as fh:
        return json.load(fh)


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it to flux_counts.PEAKS")
    return PEAKS[device_kind]


def tokens(cfg: dict, px: int = 1024, text_tokens: int = 512) -> int:
    """The joint sequence: the text tokens and one token per 2 x 2 patch
    of the 8-times-smaller latent (16 px of the image a side)."""
    return text_tokens + (px // 16) ** 2


def attention_flops(cfg: dict, n: int) -> float:
    """One kernel call over all heads: q k^T and p v, 2 n^2 d each, twice
    for the multiply-add."""
    return 4.0 * n * n * cfg["attention_head_dim"] * cfg["num_attention_heads"]


def attention_bytes(cfg: dict, n: int) -> float:
    """q, k, v read and the output written, once each."""
    width = cfg["attention_head_dim"] * cfg["num_attention_heads"]
    return 4.0 * n * width * BYTES[cfg["as_run"]["compute_dtype"]]


def attention_calls_per_evaluation(cfg: dict) -> int:
    """One joint attention in every double- and single-stream block."""
    return cfg["num_layers"] + cfg["num_single_layers"]


def evaluation_flops(cfg: dict, n: int, text_tokens: int = 512) -> float:
    """One evaluation of the denoiser at batch 1. Per token and block the
    linear layers are 24 h^2 in both kinds of block: qkv 3 h^2, the
    attention's projection h^2 and the MLP 8 h^2, each twice for the
    multiply-add (a single block fuses them into h -> 7 h and 5 h -> h).
    The adaLN modulations act on one vector a sample, the embedders and
    the final layer on narrow inputs."""
    h = cfg["attention_head_dim"] * cfg["num_attention_heads"]
    blocks = attention_calls_per_evaluation(cfg)
    linear = 24.0 * h * h * n * blocks
    attention = attention_flops(cfg, n) * blocks
    modulation = 2.0 * h * h * (12 * cfg["num_layers"] + 3 * cfg["num_single_layers"] + 2)
    image_tokens = n - text_tokens
    ends = 2.0 * h * (
        2 * cfg["in_channels"] * image_tokens          # img_in and the final linear
        + cfg["joint_attention_dim"] * text_tokens     # txt_in
        + (2 * 256 + cfg["pooled_projection_dim"] + 3 * h)  # time, guidance, vector embedders
    )
    return linear + attention + modulation + ends


def roofline_seconds(flops: float, nbytes: float, device_kind: str) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s."""
    peak = peaks(device_kind)
    return max(flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"])
