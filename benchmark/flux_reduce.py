"""What the FLUX cell's readers share."""

from __future__ import annotations

KERNEL = "flash_attention"  # ops/attention.py names its pallas_call so


def kernel_seconds(material: dict):
    """The kernel's self time in the traced slice, or None where there
    is no trace, no busy time, or no such kernel among the slice's
    largest kinds of operation."""
    trace = material.get("trace")
    if not trace or not trace.get("busy_s") or not trace.get("window_s"):
        return None
    for name, seconds in trace["breakdown"]["device_ops"]:
        if name == KERNEL:
            return float(seconds)
    return None


def job_period_seconds(material: dict):
    """Seconds from one finished job to the next over the window's
    finished requests: in a closed loop that keeps a job waiting, what
    the server takes a job."""
    ends = sorted(r["end"] for r in material["records"] if r.get("ok"))
    if len(ends) < 2:
        return None
    return (ends[-1] - ends[0]) / (len(ends) - 1)


def evals_of(request: list):
    """The model evaluations one request ran, as its `node.KSampler`
    span says (`evals`); None where the program sets none."""
    for span in request:
        if span["name"] == "node.KSampler":
            return (span.get("attrs") or {}).get("evals") or None
    return None
