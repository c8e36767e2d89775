"""Median node.VAEDecode span of the window's requests: host time
spent issuing the decode, operation by operation."""

import spans


def read(material):
    return spans.median_ms(
        material, lambda request: spans.seconds(request, "node.VAEDecode")
    )
