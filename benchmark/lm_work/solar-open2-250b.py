"""What one request makes Solar-Open2's two programs do, from
`solar_counts` and the request's `node.TextGenerate` attributes: the
bytes its decode has to move (a step at batch 1 streams its weights; of
the routed experts, those the step's pairs fell on among the held ones, a
step and layer; the full-attention layer's keys and values at the length
of mid-decode, which is exact for bytes that grow by the same amount
every step; the KDA states read and written) and the operations of its
prefill (with the pairs that fell on held experts, the causal attention
and the chunked delta rule)."""

import solar_counts


def work(cfg: dict, attrs: dict) -> dict:
    tokens, new = attrs["prompt_tokens"], attrs["new_tokens"]
    held_a_layer = attrs["decode_routed_pairs_held"] / float(new * cfg["num_hidden_layers"])
    return {
        "decode": new * solar_counts.decode_step_bytes(cfg, held_a_layer, tokens + new // 2),
        "prefill": solar_counts.prefill_flops(cfg, tokens, attrs["prefill_routed_pairs_held"]),
    }
