"""What one request makes dots3-note-prev's two programs do, from
`dots3_counts` and the request's `node.TextGenerate` attributes: the
bytes its decode has to move, `decode_steps` x a step's (one token a
step; of the routed experts the distinct held ones a step read, from
`decode_experts_read`; the two full layers' latent caches and index keys
whole at the length of mid-decode, as the step's masked form reads them;
the three rings whole), and the operations of its prefill as the model
defines them (with the pairs that fell on held experts, each full layer's
index over the visible positions and its attention over the chosen ones,
each sliding layer's band): the same whatever route the band took."""

import dots3_counts


def work(cfg: dict, attrs: dict) -> dict:
    tokens, new, steps = attrs["prompt_tokens"], attrs["new_tokens"], attrs["decode_steps"]
    step = dots3_counts.decode_step_bytes(
        cfg, attrs["decode_experts_read"] / float(steps), tokens + new // 2)
    return {
        "decode": steps * step,
        "prefill": dots3_counts.prefill_flops(cfg, tokens, attrs["prefill_routed_pairs_held"]),
    }
