"""The share of the traced slice the device spent between programs, in per
cent: 100 x (1 - seconds inside a program / (last end - first start))
over the device's "XLA Modules" line. `device_idle_pct.*` is the same
slice's share outside any *operation*, over the capture's whole window:
this one leaves out the gaps inside a program and the slice's two edges,
so what is left is the host's (`between_jobs_ms.*` is its largest part).
Left out where the trace has no device line."""

import device_modules


def read(material):
    return device_modules.idle_pct(material)
