"""The decode's expert kernel against the HBM's peak, in per cent:

    bytes a decode   = `decode_experts_read` (node.TextGenerate: the distinct held
                       experts the steps' kernel calls read) x one expert's two matrices
    kernel s a decode = device seconds of the events named `expert_matvec` that begin
                       inside a run of `jit_decode` lying whole in the traced slice,
                       over the number of such runs
    share            = 100 x bytes a decode / kernel s a decode / peak bytes/s

with one expert's bytes from nemotron3_nano_counts (`ops/expert_matvec`
names its pallas_call `expert_matvec`, on either walk) and the peak that
of the chip the configuration names. A decode step multiplies a handful
of rows, far left of the ridge, so the bound is the HBM's. The seconds
hold every call, those that found no held expert and fetched nothing
among them (45 % at an even routing), so the share is of the calls as
the step makes them, not of a full pipe. A reading above 100 is a bug in
the count. Left out where the trace has no such kernel inside such a
program, the node says nothing of the experts read, or the workflow
loads another model."""

import statistics

import deepseek_reduce
import device_modules
import nemotron3_nano_counts
import spans
import xplane

KERNEL = "expert_matvec"
MODULE = "jit_decode"


def kernel_seconds(path: str, runs: list) -> float:
    """Device seconds of the `KERNEL` events on the first device plane's
    operations line that begin inside one of `runs` [(start_ns, end_ns)]."""
    from jax.profiler import ProfileData

    total = 0
    for plane in ProfileData.from_file(path).planes:
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if (line.name or xplane.OPS_LINE) != xplane.OPS_LINE:
                continue
            for event in line.events:
                start = int(event.start_ns)
                if any(lo <= start < hi for lo, hi in runs) and xplane.kind(event.name) == KERNEL:
                    total += int(event.duration_ns)
        break
    return total / 1e9


def read(material):
    cfg = nemotron3_nano_counts.config()
    found = device_modules.lm_work(material)  # the configuration of the model the workflow loads
    runs = [(start, end) for name, start, end in device_modules.modules(material)[1:-1]
            if name == MODULE]
    read_a_request = spans.per_request(
        material, lambda request: deepseek_reduce.attrs_of(request).get("decode_experts_read"))
    if (found is None or found[1]["registry_name"] != cfg["registry_name"] or not runs
            or not read_a_request):
        return None
    path = xplane.find_trace(device_modules.profile_dir())
    seconds = kernel_seconds(path, runs) / len(runs)
    if not seconds:
        return None
    fetched = statistics.median(read_a_request) * nemotron3_nano_counts.expert_matrices_bytes(cfg)
    peak = nemotron3_nano_counts.peaks(cfg["as_run"]["chip"])["bytes_per_s"]
    return 100.0 * fetched / seconds / peak
