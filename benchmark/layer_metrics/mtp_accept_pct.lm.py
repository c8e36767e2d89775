"""Of the tokens the language model's draft module (K-EXAONE's
multi-token-prediction module) proposed in the window's requests, the
share the main model's verification kept, in per cent: node.TextGenerate's
`mtp_accepted` over `mtp_drafted`, summed over the requests, which come
back with the ids in one read-back. A kept draft is a second token from
one step; `mtp_device_pct.lm` says what the drafting costs. Under seeded
random weights the share is what two near-flat distributions overlap by,
not a trained model's. Left out where no request drafted (a program
without the module, `draft_tokens` 0)."""

import deepseek_reduce


def read(material):
    drafted = accepted = 0
    for request in material["spans"].values():
        attrs = deepseek_reduce.attrs_of(request)
        drafted += attrs.get("mtp_drafted") or 0
        accepted += attrs.get("mtp_accepted") or 0
    return 100.0 * accepted / drafted if drafted else None
