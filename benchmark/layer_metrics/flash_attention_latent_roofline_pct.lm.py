"""The causal attention kernel's share of its roofline in
LongCat-Flash-Chat's prefill, where every part's queries run over keys
and values rebuilt from the latents of every position so far, in per
cent:

    least s a prefill  = sum over the prefill's causal calls (eight
                         attentions x the prompt's parts: `rows` queries over
                         the `keys` positions so far) of the larger of the call's
                         operations over the peak FLOP/s and its bytes over the
                         peak bytes/s
    kernel s a prefill = device seconds of the events named
                         `flash_attention_causal` that begin inside a run of
                         `jit_prefill` lying whole in the traced slice, over the
                         number of such runs
    share              = 100 x least s / kernel s

with a call's operations and bytes from longcat_flash_counts
(`causal_call_flops`: two products for every head at its true widths, 192
and 128, over the keys each row sees; `causal_call_bytes`: q, the output
and the visible keys and values once) at the node's `prompt_tokens`, and
the peaks of the chip the configuration names. At 8,192 queries over
8,192 to 32,768 keys a call is far right of the ridge, so the bound is
the MXU's. The kernel multiplies q and k padded from 192 to 256 lanes and
whole blocks on the diagonal, and the program runs a call's heads in
groups: all of that is its cost, not the call's work, so the share is of
what the model asks. A reading above 100 is a bug in the count. Left out
where the trace has no such kernel inside such a program (the XLA route)
or the workflow loads another model. granite-4.0-h-micro's and
dots3-note-prev's readers are theirs by their own text."""

import statistics

import deepseek_reduce
import device_modules
import kernel_events
import longcat_flash_counts
import spans
import xplane

KERNEL = "flash_attention_causal"
MODULE = "jit_prefill"


def least_seconds(cfg: dict, tokens: int) -> float:
    """What the chip's peaks allow the prefill's causal calls."""
    peak = longcat_flash_counts.peaks(cfg["as_run"]["chip"])
    return longcat_flash_counts.attention_sublayers(cfg) * sum(
        max(longcat_flash_counts.causal_call_flops(cfg, rows, keys) / peak["flops_per_s"],
            longcat_flash_counts.causal_call_bytes(cfg, rows, keys) / peak["bytes_per_s"])
        for rows, keys in longcat_flash_counts.prefill_causal_calls(cfg, tokens))


def read(material):
    cfg = longcat_flash_counts.config()
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
