"""Of the device time the language model's two programs (`jit_prefill`,
`jit_decode`) took in the traced slice, the share, in per cent, of the
events named `dsa_attend`: the Pallas kernel of `ops/dsa_attend.py`, in
which a prefill part's queries attend over the rows their selection chose
with the layer's latent cache resident in VMEM (`models/dsa.attend`'s
gathered form on a TPU; its `pallas_call` bears that name). A share of
time, not of a peak: times `prefill_device_ms.lm` over the calls a prefill
makes (parts x layers x blocks of query rows) it is the kernel's
milliseconds a call. The kernel runs inside the model's `mla` scope, so
`mla_device_pct.lm` counts it too.

Left out where there is no trace, no such program in it, or no such
kernel inside one: a program whose gathered form is XLA's own has none."""

import device_modules
import xplane

KERNEL = "dsa_attend"
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
