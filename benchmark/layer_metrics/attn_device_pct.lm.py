"""Of the device time the language model's two programs (`jit_prefill`,
`jit_decode`) took in the traced slice, the share, in per cent, of the
operations that the program put under its `attn` scope (a softmax
attention mixer whole: the q, k and v projections, the keys and values
written into the cache, the causal attention of a prompt's part or the
one query of a decode step over the cache, the output projection;
Nemotron-3-Nano's six `*` blocks, granite-4.0-h-micro's layers 5, 15, 25
and 35). With `ssm_device_pct.lm` and `mlp_device_pct.lm` it splits a
hybrid model's device time among its parts. Self time, read by
`scoped_self_time.py`.

Left out where there is no trace, no such program in it, or where no
operation of those programs names a scope."""

import scoped_self_time

PROGRAMS = ("jit_prefill", "jit_decode")
SCOPE = "attn"


def read(material):
    return scoped_self_time.share_pct(material, PROGRAMS, SCOPE)
