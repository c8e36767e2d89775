"""The causal attention kernel's share of its roofline in
dots3-note-prev's prefill, where the sliding layers' band runs in it, in
per cent:

    least s a prefill  = the sliding layers x the larger of the band's
                         operations over the peak FLOP/s and its calls' bytes
                         over the peak bytes/s
    kernel s a prefill = device seconds of the events named
                         `flash_attention_causal` that begin inside a run of
                         `jit_prefill` lying whole in the traced slice, over the
                         number of such runs
    share              = 100 x least s / kernel s

with the band's operations from dots3_counts (`band_flops`: two products
for every head at its true widths, 256 and 128, over the keys each row
sees, min(t + 1, 513): what the mask lets through, not what the blocks
multiply) and its bytes (`band_bytes`: q, the output, the keys and the
values of a part's call once) at the node's `prompt_tokens`, and the
peaks of the chip the configuration names. The kernel multiplies whole
blocks of 512 keys, two a block of rows, of which the band crosses half:
that is its cost, not the call's work, so the share is of what the model
asks and stays near or under 50. A reading above 100 is a bug in the
count. Left out where the trace has no such kernel inside such a program
(the band on XLA's blocks) or the workflow loads another model.
`flash_attention_causal_roofline_pct.lm` is granite-4.0-h-micro's by its
own text; what the two share is imported."""

import statistics

import deepseek_reduce
import device_modules
import dots3_counts
import kernel_events
import spans
import xplane

KERNEL = "flash_attention_causal"
MODULE = "jit_prefill"


def least_seconds(cfg: dict, tokens: int) -> float:
    """What the chip's peaks allow the prefill's band calls."""
    peak = dots3_counts.peaks(cfg["as_run"]["chip"])
    part, tail = cfg["as_run"]["prefill_part"], cfg["sliding_window_size"] - 1
    calls = [(min(part, tokens - start), min(start, tail)) for start in range(0, tokens, part)]
    moved = sum(dots3_counts.band_bytes(cfg, rows, rows + before) for rows, before in calls)
    return dots3_counts.window_layers(cfg) * max(
        dots3_counts.band_flops(cfg, tokens) / peak["flops_per_s"], moved / peak["bytes_per_s"])


def read(material):
    cfg = dots3_counts.config()
    found = device_modules.lm_work(material)  # the configuration of the model the workflow loads
    runs = [(start, end) for name, start, end in device_modules.modules(material)[1:-1]
            if name == MODULE]
    prompts = spans.per_request(
        material, lambda request: deepseek_reduce.attrs_of(request).get("prompt_tokens"))
    if (found is None or found[1]["registry_name"] != cfg["registry_name"] or not runs
            or not prompts):
        return None
    path = xplane.find_trace(device_modules.profile_dir())
    seconds = kernel_events.seconds(path, runs, KERNEL) / len(runs)
    if not seconds:
        return None
    return 100.0 * least_seconds(cfg, int(statistics.median(prompts))) / seconds
