"""The decode's share of the HBM roofline, in per cent:

    new_tokens x decode_step_bytes / device seconds of jit_decode / peak bytes/s

The bytes are the median over the window's requests of what a request's
steps have to move (lm_work/<configuration>.py of the model the workflow
loads: `deepseek_counts` with the held experts a step reads, from the
node's `decode_routed_pairs_held`, or `ouro_counts`), at the cache length
of mid-decode; the seconds the median of `jit_decode` on the device's
"XLA Modules" line of the traced slice; the peak that of the chip the
configuration names. A step at batch 1 is far left of the ridge, so the
bound is the HBM's. A reading above 100 is a bug in the count. Left out
where the trace has no such program or the model has no lm_work file."""

import device_modules

MODULE = "jit_decode"


def read(material):
    return device_modules.lm_share_pct(material, "decode", MODULE)
