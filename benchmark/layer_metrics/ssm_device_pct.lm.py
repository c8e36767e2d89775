"""Of the device time the language model's two programs (`jit_prefill`,
`jit_decode`) took in the traced slice, the share, in per cent, of the
operations that the program put under its `mamba` scope (a selective
state-space mixer whole: the input projection, the causal convolution,
the chunked scan in the prefill and the recurrence in the decode, the
gated group norm, the output projection; Nemotron-3-Nano's 23 `M`
blocks). Self time, read by `scoped_self_time.py`.

Left out where there is no trace, no such program in it, or where no
operation of those programs names a scope."""

import scoped_self_time

PROGRAMS = ("jit_prefill", "jit_decode")
SCOPE = "mamba"


def read(material):
    return scoped_self_time.share_pct(material, PROGRAMS, SCOPE)
