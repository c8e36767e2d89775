"""Of the device time the language model's two programs (`jit_prefill`,
`jit_decode`) took in the traced slice, the share, in per cent, of the
operations that the program put under its `experts` scope: what
`models/moe.expert_layer` does with the routed experts once the router
has chosen (the sort of the token-expert pairs by expert, the row gather,
the two grouped products, in the prefill `ragged_dot` over a rung of the
row ladder and in a decode step or pass the `expert_matvec` kernel, the
weights, and the way back to `[T, hidden]`); the router and a shared
expert have scopes of their own beside it and are not counted. Self time,
read by `scoped_self_time.py`.

Left out where there is no trace, no such program in it, or where no
operation of those programs names a scope."""

import scoped_self_time

PROGRAMS = ("jit_prefill", "jit_decode")
SCOPE = "experts"


def read(material):
    return scoped_self_time.share_pct(material, PROGRAMS, SCOPE)
