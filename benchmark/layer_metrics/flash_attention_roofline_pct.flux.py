"""The flash-attention kernel's achieved FLOP/s against the roofline at
its arithmetic intensity, in per cent:

    calls a job    = `evals` (node.KSampler span) x blocks (configuration)
    kernel s a job = (kernel self time / slice) x job period of the records
    achieved       = calls a job x FLOPs a call / kernel s a job
    roofline       = FLOPs a call / max(FLOPs / peak FLOP/s, bytes / peak bytes/s)
    share          = 100 x achieved / roofline

with FLOPs and bytes of one call from flux_counts (4 n^2 d a head; q, k, v
and the output moved once) and the peaks of the chip the configuration
names. At 4,608 tokens the intensity is ~2,300 FLOP/byte, far right of the
ridge (240), so the bound is the MXU's peak. A reading above 100 is a bug
in the count. The job period spans the whole window while the kernel's
share is the slice's, so where a capture's `stop` slows later jobs the
share reads low, never high. Left out where the trace shows no such
kernel or the program sets no `evals`."""

import statistics

import flux_counts
import flux_reduce


def read(material):
    kernel_s = flux_reduce.kernel_seconds(material)
    period_s = flux_reduce.job_period_seconds(material)
    evals = [e for e in map(flux_reduce.evals_of, material["spans"].values()) if e]
    if kernel_s is None or period_s is None or not evals:
        return None
    cfg = flux_counts.config()
    n = flux_counts.tokens(cfg)
    calls = statistics.median(evals) * flux_counts.attention_calls_per_evaluation(cfg)
    kernel_s_per_job = kernel_s / material["trace"]["window_s"] * period_s
    least_s = flux_counts.roofline_seconds(
        flux_counts.attention_flops(cfg, n), flux_counts.attention_bytes(cfg, n),
        cfg["as_run"]["chip"])
    return 100.0 * calls * least_s / kernel_s_per_job
