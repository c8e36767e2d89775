"""Of the token-expert pairs the router made in the window's requests
(prefill and decode, every layer), the share that chose an identity
expert, in per cent: pairs that cost no expert's weights and no row of a
grouped product, on any chip. From node.TextGenerate's `*_routed_pairs`
and `*_zero_pairs`, which come back with the ids in one read-back.
LongCat-Flash-Chat's router has 256 identities among its 768 outputs:
33.3 where the routing is even, as under seeded weights and a zero
selection bias; a trained router's bias moves it with the load. Left out
where no request reports the counts (a model without identity experts,
the parent's)."""

import deepseek_reduce


def read(material):
    pairs = zero = 0
    for request in material["spans"].values():
        attrs = deepseek_reduce.attrs_of(request)
        for phase in ("prefill", "decode"):
            if attrs.get(f"{phase}_zero_pairs") is not None and attrs.get(f"{phase}_routed_pairs"):
                pairs += attrs[f"{phase}_routed_pairs"]
                zero += attrs[f"{phase}_zero_pairs"]
    return 100.0 * zero / pairs if pairs else None
