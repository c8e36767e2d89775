"""Of the keys the attention layers' queries could see in the window's
requests, the share they read, in per cent: node.TextGenerate's
`keys_selected` over `keys_visible`, summed over the requests. Both are
integers the model's two programs count on the device, over every
position a request ran (prefill and decode, a rejected draft's among
them) and every attention layer: t + 1, and the size of the query's
selection, min(t + 1, `index_topk`). It says how sparse the traffic made
the attention: a change that moves it changed the model or the traffic,
not the program. Left out where no request reports the counts (a model
whose attention reads every key its mask allows)."""

import deepseek_reduce


def read(material):
    visible = selected = 0
    for request in material["spans"].values():
        attrs = deepseek_reduce.attrs_of(request)
        visible += attrs.get("keys_visible") or 0
        selected += attrs.get("keys_selected") or 0
    return 100.0 * selected / visible if visible else None
