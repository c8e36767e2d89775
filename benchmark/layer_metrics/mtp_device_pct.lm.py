"""Of the device time the language model's decode program (`jit_decode`)
took in the traced slice, the share, in per cent, of the operations that
the program put under its `mtp` scope (K-EXAONE's multi-token-prediction
module: `W_eh`, its layer over two positions, its use of the head, the
draw of the draft). Self time, read by `scoped_self_time.py` (the table
of scopes in the trace's bytes, the operations inside a program, the
stack of self times). What drafting costs, to set against what
`mtp_accept_pct.lm` says it buys.

Left out where there is no trace, no `jit_decode` in it, or where no
operation of the program names a scope."""

import scoped_self_time

PROGRAMS = ("jit_decode",)
SCOPE = "mtp"


def read(material):
    return scoped_self_time.share_pct(material, PROGRAMS, SCOPE)
