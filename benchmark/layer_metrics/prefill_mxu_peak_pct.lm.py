"""The prefill's share of the MXU's peak, in per cent:

    prefill_flops / device seconds of jit_prefill / peak FLOP/s

The operations are the median over the window's requests
(lm_work/<configuration>.py of the model the workflow loads:
`deepseek_counts` with the node's `prefill_routed_pairs_held`, or
`ouro_counts`); the seconds the median of `jit_prefill` on the device's
"XLA Modules" line of the traced slice; the peak that of the chip the
configuration names. At 2,048 tokens a prefill is right of the ridge.
Left out where the trace has no such program or the model has no lm_work
file."""

import device_modules

MODULE = "jit_prefill"


def read(material):
    return device_modules.lm_share_pct(material, "prefill", MODULE)
