"""Of the device time the language model's two programs (`jit_prefill`,
`jit_decode`) took in the traced slice, the share, in per cent, of the
events named `dsa_select`: the Pallas kernel of `ops/dsa_select.py`, in
which a prefill part's queries pick the `index_topk` best of their
indexer's scores without a sort (the k-th largest by bisection, the mask
turned into positions by a compress network, a block's scores in VMEM
throughout: `models/dsa.select`'s gathered form on a TPU; its
`pallas_call` bears that name). A share of time, not of a peak or a
roofline: the kernel runs no MXU work and moves no bytes a model's count
names. Times `prefill_device_ms.lm` over the calls a prefill makes (parts
x indexer layers x blocks of 128 queries) it is the kernel's milliseconds
a call. The kernel runs inside the model's `indexer` scope, so
`indexer_device_pct.lm` counts it too.

Left out where there is no trace, no such program in it, or no such
kernel inside one: a program that picks by `lax.top_k` has a sort there."""

import device_modules
import xplane

KERNEL = "dsa_select"
PROGRAMS = ("jit_prefill", "jit_decode")


def kernel_ns(path: str, runs: list) -> int:
    """Device nanoseconds of the `KERNEL` events on the first device
    plane's operations line that begin inside one of `runs` [(start_ns,
    end_ns)]."""
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
                if xplane.kind(event.name) == KERNEL and any(lo <= start < hi for lo, hi in runs):
                    total += int(event.duration_ns)
        break
    return total


def read(material):
    runs = [(start, end) for name, start, end in device_modules.modules(material)
            if name in PROGRAMS]
    if not runs:
        return None
    path = xplane.find_trace(device_modules.profile_dir())
    kernel = kernel_ns(path, runs)
    if not kernel:
        return None
    return 100.0 * kernel / sum(end - start for start, end in runs)
