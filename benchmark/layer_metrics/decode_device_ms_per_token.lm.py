"""What one decode step takes on the device, in ms: the median device
seconds of `jit_decode` (one program a request: every step of it) on the
device's "XLA Modules" line of the traced slice, over the median
`new_tokens` of the window's `node.TextGenerate` spans. Nothing of the
prefill is in it (`decode_ms_per_token.lm` has the prefill's share). Left
out where the trace has no such program or the node says no tokens."""

import statistics

import deepseek_reduce
import device_modules
import spans

MODULE = "jit_decode"


def read(material):
    device_ms = device_modules.median_ms(material, MODULE)
    tokens = spans.per_request(
        material, lambda request: deepseek_reduce.attrs_of(request).get("new_tokens") or None)
    if device_ms is None or not tokens:
        return None
    return device_ms / statistics.median(tokens)
