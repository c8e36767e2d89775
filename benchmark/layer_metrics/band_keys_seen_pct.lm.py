"""Of the query-key pairs the sliding layers' band route multiplied in
the window's prefills, the share the mask lets a row see, in per cent:
node.TextGenerate's `prefill_band_keys_seen` over
`prefill_band_keys_computed`, summed over the requests. Both are integers
the model's `report` counts from the prompt's length, the parts and the
route the band took (`prefill_band_route`): min(t + 1, window) keys a
query, against what the route's own blocks cover (XLA's blocks of 256
rows take the keys from the first row's first to the last row's last, 255
+ window a row; the Pallas kernel whole blocks of keys, every one the
band crosses). It says how much of the band's products the mask throws
away: a route or a block size that moves it changed the work, not the
model. At or under 100. Left out where no request reports the counts (a
model without a band over latents, the parent's)."""

import deepseek_reduce


def read(material):
    seen = computed = 0
    for request in material["spans"].values():
        attrs = deepseek_reduce.attrs_of(request)
        seen += attrs.get("prefill_band_keys_seen") or 0
        computed += attrs.get("prefill_band_keys_computed") or 0
    return 100.0 * seen / computed if computed else None
