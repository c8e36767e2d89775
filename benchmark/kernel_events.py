"""Device seconds of one Pallas kernel's events inside runs of a program,
for any per-layer metric that asks: the events of the first device
plane's "XLA Ops" line whose kind (`xplane.kind`: the `pallas_call`'s
`name`) is the kernel's and that begin inside one of the runs.

This is `layer_metrics/expert_matvec_hbm_pct.lm.py`'s loop (PR 48) and
`dsa_attend_device_pct.lm.py`'s (PR 53) with the kernel as an argument;
those files keep their own copies until a `benchmark` PR may edit them,
and a later kernel metric imports this one."""

import xplane


def seconds(path: str, runs: list, kernel: str) -> float:
    """Seconds of the `kernel` events in the trace at `path` that begin
    inside one of `runs` [(start_ns, end_ns)]."""
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
                if xplane.kind(event.name) == kernel and any(lo <= start < hi for lo, hi in runs):
                    total += int(event.duration_ns)
        break
    return total / 1e9
