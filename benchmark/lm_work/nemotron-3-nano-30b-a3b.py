"""What one request makes Nemotron-3-Nano's two programs do, from
`nemotron3_nano_counts` and the request's `node.TextGenerate` attributes:
the bytes its decode has to move (a step at batch 1 streams the weights of
all 52 blocks' parts that every token passes; of the routed experts the
distinct held ones the steps read, from `decode_experts_read`; the six
attention blocks' keys and values at the length of mid-decode, which is
exact for bytes that grow by the same amount every step; the 23 Mamba-2
states and tails read and written) and the operations of its prefill
(the projections, routers and shared experts, the pairs that fell on held
experts, six causal attentions at 16 queries a key head, and the chunked
scans' four products a chunk)."""

import nemotron3_nano_counts


def work(cfg: dict, attrs: dict) -> dict:
    tokens, new = attrs["prompt_tokens"], attrs["new_tokens"]
    step = nemotron3_nano_counts.decode_step_bytes(
        cfg, attrs["decode_experts_read"] / float(new), tokens + new // 2)
    return {
        "decode": new * step,
        "prefill": nemotron3_nano_counts.prefill_flops(
            cfg, tokens, attrs["prefill_routed_pairs_held"]),
    }
