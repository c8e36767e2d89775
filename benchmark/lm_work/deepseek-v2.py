"""What one request makes DeepSeek-V2's two programs do, from
`deepseek_counts` and the request's `node.TextGenerate` attributes: the
bytes its decode has to read (a step at batch 1 streams its weights; of
the routed experts, those the step's pairs fell on among the held ones, a
step and expert layer) and the operations of its prefill (with the pairs
that fell on held experts). The cache length is the one at mid-decode,
which is exact for bytes that grow by the same amount every step."""

import deepseek_counts


def work(cfg: dict, attrs: dict) -> dict:
    tokens, new = attrs["prompt_tokens"], attrs["new_tokens"]
    held_a_layer = attrs["decode_routed_pairs_held"] / float(
        new * deepseek_counts.layers(cfg)[1])
    return {
        "decode": new * deepseek_counts.decode_step_bytes(cfg, held_a_layer, tokens + new // 2),
        "prefill": deepseek_counts.prefill_flops(cfg, tokens, attrs["prefill_routed_pairs_held"]),
    }
