"""The causal attention kernel's share of its roofline in
granite-4.0-h-micro's prefill, in per cent:

    least s a prefill  = sum over the prefill's causal calls (four attention
                         layers x the prompt's parts: `rows` queries over the
                         `keys` positions so far) of the larger of the call's
                         operations over the peak FLOP/s and its bytes over the
                         peak bytes/s
    kernel s a prefill = device seconds of the events named
                         `flash_attention_causal` that begin inside a run of
                         `jit_prefill` lying whole in the traced slice, over the
                         number of such runs
    share              = 100 x least s / kernel s

with a call's operations and bytes from granite_hybrid_counts
(`causal_call_flops`: two products for every query head at its true width
of 64 over the keys each row sees; `causal_call_bytes`: q, the output and
the visible keys and values once) at the node's `prompt_tokens`, and the
peaks of the chip the configuration names. At 8,192 queries over 8,192 to
65,536 keys a call is far right of the ridge, so the bound is the MXU's.
The kernel multiplies heads padded from 64 to the lane tile (half of every
pass is zeros) and whole blocks on the diagonal: both are its cost, not
the call's work, so the share is of what the model asks, and cannot reach
100 at this width. A reading above 100 is a bug in the count. Left out
where the trace has no such kernel inside such a program (the XLA route)
or the workflow loads another model."""

import statistics

import deepseek_reduce
import device_modules
import granite_hybrid_counts
import kernel_events
import spans
import xplane

KERNEL = "flash_attention_causal"
MODULE = "jit_prefill"


def least_seconds(cfg: dict, tokens: int) -> float:
    """What the chip's peaks allow the prefill's causal calls."""
    peak = granite_hybrid_counts.peaks(cfg["as_run"]["chip"])
    layers = granite_hybrid_counts.layers(cfg)[1]
    return layers * sum(
        max(granite_hybrid_counts.causal_call_flops(cfg, rows, keys) / peak["flops_per_s"],
            granite_hybrid_counts.causal_call_bytes(cfg, rows, keys) / peak["bytes_per_s"])
        for rows, keys in granite_hybrid_counts.prefill_causal_calls(cfg, tokens))


def read(material):
    cfg = granite_hybrid_counts.config()
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
