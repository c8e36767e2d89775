"""What one request makes granite-4.0-h-micro's two programs do, from
`granite_hybrid_counts` and the request's `node.TextGenerate` attributes:
the bytes its decode has to move (a step at batch 1 streams every layer's
weights and the tied embedding once, as the head; the four attention
layers' keys and values at the length of mid-decode, which is exact for
bytes that grow by the same amount every step; the 36 Mamba-2 states and
tails read and written) and the operations of its prefill as the model
defines them, whatever parts it is read in (the projections and the 40
SwiGLUs, four causal attentions at the heads' true width of 64 with
position i over i + 1 keys, the chunked scans' four products a chunk, the
head at one position)."""

import granite_hybrid_counts


def work(cfg: dict, attrs: dict) -> dict:
    tokens, new = attrs["prompt_tokens"], attrs["new_tokens"]
    return {
        "decode": new * granite_hybrid_counts.decode_step_bytes(cfg, tokens + new // 2),
        "prefill": granite_hybrid_counts.prefill_flops(cfg, tokens),
    }
