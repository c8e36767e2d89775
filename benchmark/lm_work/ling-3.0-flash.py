"""What one request makes Ling-3.0-flash's two programs do, from
`ling_flash_counts` and the request's `node.TextGenerate` attributes: the
bytes its decode has to move, **`decode_steps` x a step's** (a
self-speculative step runs two positions and emits one or two tokens, so
the steps, not the tokens, are what streams the weights; of the routed
experts the distinct held ones a step read, from `decode_experts_read`;
the two latent caches at the length of mid-decode; each KDA layer's
matrix state read once and written once a position, the second position's
write being what keeping or dropping a draft adds), and the operations of
its prefill (with the pairs that fell on held experts, the MLA layer's
causal attention, the KDA layers' chunked delta rule, and the MTP
module's latents)."""

import ling_flash_counts


def work(cfg: dict, attrs: dict) -> dict:
    tokens, new, steps = attrs["prompt_tokens"], attrs["new_tokens"], attrs["decode_steps"]
    step = ling_flash_counts.decode_step_bytes(
        cfg, attrs["decode_experts_read"] / float(steps), tokens + new // 2,
        drafting=bool(attrs.get("draft_tokens")))
    return {
        "decode": steps * step,
        "prefill": ling_flash_counts.prefill_flops(cfg, tokens, attrs["prefill_routed_pairs_held"]),
    }
