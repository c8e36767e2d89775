"""Median, over the window's requests, of the `device.wait` under
node.TextGenerate divided by `new_tokens`: what a generated token costs
the request. The prefill runs inside the same wait (both programs are
dispatched before the one read-back), so this is the decode step plus the
prefill's 1/new_tokens share, never less than a step takes."""

import deepseek_reduce
import spans


def read(material):
    def one(request):
        wait = deepseek_reduce.wait_seconds(request)
        tokens = deepseek_reduce.attrs_of(request).get("new_tokens")
        if wait is None or not tokens:
            return None
        return wait / float(tokens)

    return spans.median_ms(material, one)
