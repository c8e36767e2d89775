"""The flash-attention kernel's self time as a share of the device's
busy time in the traced slice (`breakdown.device_ops` sums self time by
kind of operation; the kernel's events are named `flash_attention`).
Left out where the trace shows no such kernel."""

import flux_reduce


def read(material):
    kernel_s = flux_reduce.kernel_seconds(material)
    if kernel_s is None:
        return None
    return 100.0 * kernel_s / material["trace"]["busy_s"]
