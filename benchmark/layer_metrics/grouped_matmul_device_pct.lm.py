"""Of the device time the language model's prefill (`jit_prefill`) took in
the traced slice, the share, in per cent, of the events named
`grouped_matmul`: the Pallas kernel of `ops/grouped_matmul.py`, in which
an expert layer's lowest rung runs its two grouped products on a
TPU (row tiles walked group by group, an expert's columns read once a
sweep; `models/moe.expert_layer` through `grouped_rows`; its
`pallas_call` bears that name). A share of time, not of a peak or a
roofline: the kernel's operations depend on the loads a request draws
(the rung, and the blocks of 128 rows each group touches), and a count
that is wrong by a rung would read over 100 %; `prefill_mxu_peak_pct.lm`
counts the model's own operations over the same runs. Times
`prefill_device_ms.lm` it is the kernel's milliseconds a prefill; with
the trace's `ragged-dot-none` beside it, what the prefill's grouped
products cost in all. The kernel runs inside the model's `experts` scope,
so `experts_device_pct.lm` counts it too. Only the prefill's runs are the
base: a decode step's pairs are a tile or less and run in
`expert_matvec`.

Left out where there is no trace, no such program in it, or no such
kernel inside one: a program whose rungs all keep `jax.lax.ragged_dot`
(the parent's; LongCat-Flash's blocks) has `ragged-dot` there."""

import device_modules
import kernel_events
import xplane

KERNEL = "grouped_matmul"
PROGRAM = "jit_prefill"


def read(material):
    runs = [(start, end) for name, start, end in device_modules.modules(material)
            if name == PROGRAM]
    if not runs:
        return None
    kernel_s = kernel_events.seconds(xplane.find_trace(device_modules.profile_dir()), runs, KERNEL)
    if not kernel_s:
        return None
    return 100.0 * kernel_s * 1e9 / sum(end - start for start, end in runs)
