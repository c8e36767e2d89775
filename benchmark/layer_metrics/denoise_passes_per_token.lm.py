"""Passes of the decode's program a new token of the window's requests
cost: node.TextGenerate's `decode_steps` over `new_tokens`, summed over
the requests. A model that generates by masked diffusion over blocks
(SDAR) runs a block of `block_length` positions a pass and fills in
between one and all of them, then one closing pass a block that keeps the
block's keys and values: at blocks of 4 with 4 denoising passes at most,
1.25 where every pass fills in the rule's floor of one position (seeded
random weights: no confidence reaches the threshold), 0.5 where every
block closes after one. `decode_steps` counts both kinds of pass
(`denoise_passes` + `closing_passes`). The same quantity leaves the
program as `cdt_lm_decode_steps_total` over `cdt_lm_tokens_total`
{phase="decode"}.

Left out where no request of the window says `denoise_passes`: a model
that emits its tokens in order."""

import deepseek_reduce


def read(material):
    passes = tokens = 0
    for request in material["spans"].values():
        attrs = deepseek_reduce.attrs_of(request)
        if attrs.get("denoise_passes") is None or not attrs.get("new_tokens"):
            continue
        passes += attrs["decode_steps"]
        tokens += attrs["new_tokens"]
    return passes / tokens if tokens else None
