"""Of the device time the language model's two programs (`jit_prefill`,
`jit_decode`) took in the traced slice, the share, in per cent, of the
operations that the program put under its `shortcut` scope:
LongCat-Flash-Chat's expert branch, which leaves a layer after the first
sublayer's norm and returns after the second sublayer's feed-forward
(the router over 768 outputs, the sort of a block's pairs, the held
experts' grouped products, the identities' weighted input, the sum back
into the stream). `experts_device_pct.lm` reads the `experts` scope
inside it; this one the whole branch, which is what a deployment hides
behind the dense path. Self time, read by `scoped_self_time.py`.

Left out where there is no trace, no such program in it, or where no
operation of those programs names the scope (a program without it, the
parent's)."""

import scoped_self_time

PROGRAMS = ("jit_prefill", "jit_decode")
SCOPE = "shortcut"


def read(material):
    return scoped_self_time.share_pct(material, PROGRAMS, SCOPE)
