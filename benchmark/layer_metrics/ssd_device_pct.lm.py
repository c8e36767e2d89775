"""Of the device time the language model's two programs (`jit_prefill`,
`jit_decode`) took in the traced slice, the share, in per cent, of the
operations that the program put under its `ssd` scope: a Mamba-2 mixer's
recurrence alone (`models/mamba2.mixer`: the chunked scan in the prefill,
which on a TPU is the Pallas kernel `ssd_chunk` and the few fusions that
form its steps' running sums and turn its state round, the one-token
recurrence in the decode, and the skip), without the projections, the
convolution and the gated norm that `ssm_device_pct.lm` counts with it.
A share of time, not of a peak: times `prefill_device_ms.lm` over the
layers and parts a prefill walks it is the scan's milliseconds a layer
and part. Self time, read by `scoped_self_time.py`.

Left out where there is no trace, no such program in it, or where no
operation of those programs names the scope (a program without it)."""

import scoped_self_time

PROGRAMS = ("jit_prefill", "jit_decode")
SCOPE = "ssd"


def read(material):
    return scoped_self_time.share_pct(material, PROGRAMS, SCOPE)
