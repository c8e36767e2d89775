"""What one request makes LongCat-Flash-Chat's two programs do, from
`longcat_flash_counts` and the request's `node.TextGenerate` attributes:
the bytes its decode has to move, `decode_steps` x a step's (one token a
step; of the routed experts the distinct held ones a step read, from
`decode_experts_read`, most steps none; the eight latent caches whole at
the length of mid-decode), and the operations of its prefill as the
model defines them (with the pairs that fell on held experts; a pair on
an identity expert is no operation): the same whatever the parts, the
blocks or the route the causal calls took."""

import longcat_flash_counts


def work(cfg: dict, attrs: dict) -> dict:
    tokens, new, steps = attrs["prompt_tokens"], attrs["new_tokens"], attrs["decode_steps"]
    step = longcat_flash_counts.decode_step_bytes(
        cfg, attrs["decode_experts_read"] / float(steps), tokens + new // 2)
    return {
        "decode": steps * step,
        "prefill": longcat_flash_counts.prefill_flops(
            cfg, tokens, attrs["prefill_routed_pairs_held"]),
    }
