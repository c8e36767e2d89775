"""Median, over the window's requests, of node.TextGenerate over
execute_prompt, in per cent: the language model's share of a job's time
in the executor."""

import statistics

import deepseek_reduce
import spans


def read(material):
    def one(request):
        node = spans.seconds(request, deepseek_reduce.NODE)
        root = spans.seconds(request, "execute_prompt")
        if node is None or not root:
            return None
        return 100.0 * node / root

    values = spans.per_request(material, one)
    return statistics.median(values) if values else None
