"""What one request makes SDAR's two programs do, from `sdar_counts` and
the request's `node.TextGenerate` attributes: the bytes its decode has to
move, **the sum over its passes** (a pass runs one block's four positions
and fills in between one and four of them, so the passes, not the tokens,
are what streams the weights: `denoise_passes` with the head,
`closing_passes` without; of the experts the distinct ones a pass read,
from `decode_experts_read`; the cache at the length of mid-decode), and
the operations of its prefill over the prompt's whole blocks (every
token-expert pair on a held expert, attention as the block mask gives
it)."""

import sdar_counts


def work(cfg: dict, attrs: dict) -> dict:
    tokens, new = attrs["prompt_tokens"], attrs["new_tokens"]
    whole = tokens - tokens % sdar_counts.block_length(cfg)
    return {
        "decode": sdar_counts.decode_bytes(
            cfg, attrs["denoise_passes"], attrs["closing_passes"], attrs["decode_experts_read"],
            tokens + new // 2),
        "prefill": sdar_counts.prefill_flops(cfg, whole, attrs["prefill_routed_pairs_held"]),
    }
