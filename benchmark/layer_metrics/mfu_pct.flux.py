"""The denoiser's share of the MXU's peak over a whole sampler program, in
per cent:

    evals x evaluation_flops / device seconds of jit__img2img_jit / peak FLOP/s

Model operations (flux_counts, at the request's 4,608 tokens; `evals` the
median of the window's `node.KSampler` spans) over every device second of
the 20 steps, attention, norms and the sampler's own arithmetic alike, and
nothing of the text encoders or the autoencoder; the seconds the median
of `jit__img2img_jit` on the device's "XLA Modules" line of the traced
slice. Left out where the trace has no such program or the node sets no
`evals`."""

import statistics

import device_modules
import flux_counts
import flux_reduce
import spans

MODULE = "jit__img2img_jit"


def read(material):
    device_s = device_modules.median_s(material, MODULE)
    evals = spans.per_request(material, flux_reduce.evals_of)
    if not device_s or not evals:
        return None
    cfg = flux_counts.config()
    flops = flux_counts.evaluation_flops(cfg, flux_counts.tokens(cfg))
    peak = flux_counts.peaks(cfg["as_run"]["chip"])["flops_per_s"]
    return 100.0 * statistics.median(evals) * flops / device_s / peak
