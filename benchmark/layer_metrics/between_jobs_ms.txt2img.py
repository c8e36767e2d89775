"""Median, over the traced slice, of the time on the device's "XLA
Modules" line from the end of a job's last program (`jit_vae_apply`, the
decode of its image) to the start of the next job's first (`jit_prefill`
where a language model rewrites the prompt, else `jit__img2img_jit`), in
ms: how long the device had nothing of either job but the one-operation
programs between (a cast, the seed's key), while the executor thread read
the image back, handed the save off, came back to the queue and walked the
next graph to its first launch. Left out where the trace has no such
pair."""

import device_modules

LAST = "jit_vae_apply"
FIRST = ("jit_prefill", "jit__img2img_jit")


def read(material):
    return device_modules.gap_after_ms(material, LAST, FIRST)
