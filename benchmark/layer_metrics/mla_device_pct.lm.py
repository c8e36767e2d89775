"""Of the device time the language model's two programs (`jit_prefill`,
`jit_decode`) took in the traced slice, the share, in per cent, of the
operations that the program put under its `mla` scope (a latent-attention
mixer: the queries, the latents written, the attention expanded in the
prefill and absorbed in the decode, what follows the heads' outputs;
DeepSeek-V2's every layer, Ling-3.0-flash's sixth and its MTP module's).
Self time, read by `scoped_self_time.py`.

Left out where there is no trace, no such program in it, or where no
operation of those programs names a scope."""

import scoped_self_time

PROGRAMS = ("jit_prefill", "jit_decode")
SCOPE = "mla"


def read(material):
    return scoped_self_time.share_pct(material, PROGRAMS, SCOPE)
