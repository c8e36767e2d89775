"""Median node.TextGenerate span of the window's requests: tokenise,
dispatch the prefill and the decode, wait for the ids, detokenise."""

import deepseek_reduce
import spans


def read(material):
    return spans.median_ms(material, lambda request: spans.seconds(request, deepseek_reduce.NODE))
