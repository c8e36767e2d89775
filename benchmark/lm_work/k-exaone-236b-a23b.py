"""What one request makes K-EXAONE's two programs do, from
`k_exaone_counts` and the request's `node.TextGenerate` attributes: the
bytes its decode has to move, **`decode_steps` x a step's** (a
self-speculative step runs two positions and emits one or two tokens, so
the steps, not the tokens, are what streams the weights; of the routed
experts the distinct held ones a step read, from `decode_experts_read`;
the two growing caches at the length of mid-decode; the rings read and
written), and the operations of its prefill (with the pairs that fell on
held experts, the full layer's causal attention, the window layers'
bands as the published window gives them, and the MTP module's keys and
values)."""

import k_exaone_counts


def work(cfg: dict, attrs: dict) -> dict:
    tokens, new, steps = attrs["prompt_tokens"], attrs["new_tokens"], attrs["decode_steps"]
    step = k_exaone_counts.decode_step_bytes(
        cfg, attrs["decode_experts_read"] / float(steps), tokens + new // 2,
        drafting=bool(attrs.get("draft_tokens")))
    return {
        "decode": steps * step,
        "prefill": k_exaone_counts.prefill_flops(cfg, tokens, attrs["prefill_routed_pairs_held"]),
    }
