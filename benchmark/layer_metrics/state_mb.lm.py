"""Median, over the window's requests, of node.TextGenerate's
`state_bytes`, in 1e6 bytes: the part of a request's state that does not
grow with the position (a linear-attention layer's matrix state a head,
its convolutions' last inputs), which the prefill leaves and every decode
step reads and writes whole. `cache_gb.lm` is the part that grows. Left
out where the node says nothing of it (a program from before PR 38)."""

import statistics

import deepseek_reduce
import spans


def read(material):
    values = spans.per_request(
        material, lambda request: deepseek_reduce.attrs_of(request).get("state_bytes"))
    return statistics.median(values) / 1e6 if values else None
