"""Median, over the window's requests, of node.TextGenerate's
`cache_bytes`, in 1e9 bytes: the key/value (or latent) cache one request
holds on the device at its full length, which the prefill allocates and
the decode streams every step. A looped model's has a slot for every
(pass, layer)."""

import statistics

import deepseek_reduce
import spans


def read(material):
    values = spans.per_request(
        material, lambda request: deepseek_reduce.attrs_of(request).get("cache_bytes"))
    return statistics.median(values) / 1e9 if values else None
