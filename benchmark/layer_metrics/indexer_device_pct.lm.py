"""Of the device time the language model's two programs (`jit_prefill`,
`jit_decode`) took in the traced slice, the share, in per cent, of the
operations that the program put under its `indexer` scope (learned
sparse attention's index, `models/dsa.py`: the indexer's key written, its
queries and weights, `scores` of every visible position and `select`,
the exact choice of the `index_topk` best; GLM-5.2's `full` layers and
its MTP module's). The scope lies beside `mla`, not inside it, so
`mla_device_pct.lm` reads the attention over the chosen rows and this
metric what chose them. Self time, read by `scoped_self_time.py`.

Left out where there is no trace, no such program in it, or where no
operation of those programs names the scope (a program without it, the
parent's)."""

import scoped_self_time

PROGRAMS = ("jit_prefill", "jit_decode")
SCOPE = "indexer"


def read(material):
    return scoped_self_time.share_pct(material, PROGRAMS, SCOPE)
