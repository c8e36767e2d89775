"""What one request makes Ouro-2.6B's two programs do, from `ouro_counts`
and the request's `node.TextGenerate` attributes: the bytes its decode
has to read (the layers' weights once a pass, the cache so far; its
length the one at mid-decode, which is exact for bytes that grow by the
same amount every step) and the operations of its prefill."""

import ouro_counts


def work(cfg: dict, attrs: dict) -> dict:
    tokens, new = attrs["prompt_tokens"], attrs["new_tokens"]
    return {
        "decode": new * ouro_counts.decode_step_bytes(cfg, tokens + new // 2),
        "prefill": ouro_counts.prefill_flops(cfg, tokens),
    }
