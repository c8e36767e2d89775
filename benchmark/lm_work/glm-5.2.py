"""What one request makes GLM-5.2's two programs do, from
`glm_dsa_counts` and the request's `node.TextGenerate` attributes: the
bytes its decode has to move, **`decode_steps` x a step's** (a
self-speculative step runs two positions and emits one or two tokens, so
the steps, not the tokens, are what streams the weights; of the routed
experts the distinct held ones a step read, from `decode_experts_read`;
of the latent caches each position's chosen rows, of the indexer's
caches every row, at the length of mid-decode), and the operations of
its prefill as the model defines them (with the pairs that fell on held
experts, each `full` layer's index over the visible positions, every
layer's attention over the chosen positions only, and the MTP module's
latents and indexer keys): the same whatever `sparse_attention_form` the
program took."""

import glm_dsa_counts


def work(cfg: dict, attrs: dict) -> dict:
    tokens, new, steps = attrs["prompt_tokens"], attrs["new_tokens"], attrs["decode_steps"]
    step = glm_dsa_counts.decode_step_bytes(
        cfg, attrs["decode_experts_read"] / float(steps), tokens + new // 2,
        drafting=bool(attrs.get("draft_tokens")))
    return {
        "decode": steps * step,
        "prefill": glm_dsa_counts.prefill_flops(cfg, tokens, attrs["prefill_routed_pairs_held"]),
    }
