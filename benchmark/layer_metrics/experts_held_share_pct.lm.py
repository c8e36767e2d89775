"""Of the token-expert pairs the router made in the window's requests
(prefill and decode, every expert layer), the share that fell on experts
held here, in per cent: 25 where routing is even over the four chips that
share a layer. From node.TextGenerate's `*_routed_pairs` and
`*_routed_pairs_held`, which come back with the ids in one read-back."""

import deepseek_reduce


def read(material):
    pairs = held = 0
    for request in material["spans"].values():
        attrs = deepseek_reduce.attrs_of(request)
        for phase in ("prefill", "decode"):
            if attrs.get(f"{phase}_routed_pairs"):
                pairs += attrs[f"{phase}_routed_pairs"]
                held += attrs.get(f"{phase}_routed_pairs_held", 0)
    return 100.0 * held / pairs if pairs else None
