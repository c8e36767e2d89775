"""Layer bodies a token of the window's requests walked through, prompt
and new tokens alike: node.TextGenerate's `prefill_layer_passes` +
`decode_layer_passes` over `prompt_tokens` + `new_tokens`, summed over the
requests. A model that walks its layers once says only how many it has
(`layers`), and that is its count. The same quantity leaves the program as
`cdt_lm_layer_passes_total` over `cdt_lm_tokens_total`, which
`client.parse_metrics` does not keep (PERF.md section 7)."""

import deepseek_reduce


def read(material):
    passes = tokens = 0
    for request in material["spans"].values():
        attrs = deepseek_reduce.attrs_of(request)
        mine = (attrs.get("prompt_tokens") or 0) + (attrs.get("new_tokens") or 0)
        if not mine:
            continue
        if "prefill_layer_passes" in attrs:
            walked = attrs["prefill_layer_passes"] + attrs.get("decode_layer_passes", 0)
        elif attrs.get("layers"):
            walked = mine * attrs["layers"]
        else:
            continue
        passes, tokens = passes + walked, tokens + mine
    return passes / tokens if tokens else None
