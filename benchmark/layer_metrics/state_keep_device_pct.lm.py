"""Of the device time the language model's decode program (`jit_decode`)
took in the traced slice, the share, in per cent, of the operations that
the program put under its `keep` scope: what a self-speculative step over
recurrent layers pays to be able to keep or drop its draft (Ling-3.0-
flash: each KDA layer's matrix states and convolution tail after the
first position written into the slot that does not stand, those after the
second over what was read, and the flip of which slot stands). Self time,
read by `scoped_self_time.py`. To set beside `mtp_accept_pct.lm`, which
says how often the second state is the one that stands.

Left out where there is no trace, no `jit_decode` in it, or where no
operation of the program names a scope."""

import scoped_self_time

PROGRAMS = ("jit_decode",)
SCOPE = "keep"


def read(material):
    return scoped_self_time.share_pct(material, PROGRAMS, SCOPE)
