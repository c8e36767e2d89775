"""Of the device time the language model's two programs (`jit_prefill`,
`jit_decode`) took in the traced slice, the share, in per cent, of the
operations that the program put under its `window_latent` scope: a
sliding layer's latent attention (dots3-note-prev's three layers in four:
the queries under their rescale, the latents written, in the prefill the
band over the tail the part before handed on and the part's own latents,
expanded, and the tail handed on; in a decode step the ring's write and
the absorbed form over the ring; the gate a head and W_o). The scope's
name is not `mla`, so `mla_device_pct.lm` (the full layers, whose index
and kernels lie beside and inside it) does not count it: beside that
metric this one says what three layers in four cost against one in four.
Self time, read by `scoped_self_time.py`.

Left out where there is no trace, no such program in it, or where no
operation of those programs names the scope (a program without it, the
parent's)."""

import scoped_self_time

PROGRAMS = ("jit_prefill", "jit_decode")
SCOPE = "window_latent"


def read(material):
    return scoped_self_time.share_pct(material, PROGRAMS, SCOPE)
