"""Median, over the traced slice, of the device seconds of the language
model's prefill: the events of `jit_prefill` on the device's "XLA
Modules" line. Nothing of the dispatch or of the ids' transfer, which a
host clock around the call holds (the parity scripts' does: ~3 ms). Left
out where the trace has no such program."""

import device_modules

MODULE = "jit_prefill"


def read(material):
    return device_modules.median_ms(material, MODULE)
