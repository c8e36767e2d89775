"""From a profiler trace (.xplane.pb) to busy time, top operations and
idle gaps. Read with jax.profiler.ProfileData, which starts no backend;
the caller runs this only after the process that held the chip has
ended.

A device plane is one named "/device:TPU:<n>" (any accelerator:
"/device:<KIND>:<n>"). XLA writes one line of operations ("XLA Ops")
and one of whole programs ("XLA Modules") on it; busy time is the union
of the operation intervals, and a gap is labelled by the programs that
ran before and after it. The window is the span of every event of
every plane, host threads included: the host tracer runs from the
capture's start to its stop, the device lines only while programs run.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

import stats

DEVICE_PLANE = re.compile(r"^/device:(?!CPU)[A-Za-z_]+:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
TOP = 10


def find_trace(profile_dir: str) -> str | None:
    found = sorted(glob.glob(
        os.path.join(profile_dir, "**", "*.xplane.pb"), recursive=True
    ))
    return found[-1] if found else None


def load(path: str, any_plane: bool = False) -> dict:
    """{"window": (first start, last end) over every event of every
    plane, "devices": {plane: {line: [(name, start_ns, end_ns), ...]}},
    "lines": one description per plane and line}. `any_plane` takes
    host planes for devices too: a rehearsal on the CPU has no other."""
    from jax.profiler import ProfileData

    lo, hi, devices, described = None, None, {}, []
    for plane in ProfileData.from_file(path).planes:
        keep = any_plane or bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            events, count, first = [], 0, ""
            for event in line.events:
                start = int(event.start_ns)
                end = start + int(event.duration_ns)
                lo = start if lo is None or start < lo else lo
                hi = end if hi is None or end > hi else hi
                if keep:  # reading a name is most of an event's cost
                    events.append((event.name, start, end))
                elif not count:
                    first = event.name
                count += 1
            first = (events[0][0] if events else first)[:60]
            described.append(f"{plane.name} | {line.name} | {count} events | first: {first}")
            if keep:
                devices.setdefault(plane.name, {})[line.name or OPS_LINE] = events
    return {"window": (lo, hi), "devices": devices, "lines": described}


def kind(name: str) -> str:
    """`%fusion.123 = bf16[...] fusion(...)` -> `fusion`;
    `jit_silu(6576635703218322248)` -> `jit_silu`"""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\(\d+\)$|\.\d+", "", head) or head


def self_times(ops) -> dict:
    """{kind: ns} where nested operations are taken out of their parents."""
    out: dict = {}
    stack: list = []  # [name, end, self_ns]

    def close(entry) -> None:
        out[entry[0]] = out.get(entry[0], 0) + max(0, entry[2])

    for name, start, end in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= start:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(end, stack[-1][1]) - start
        stack.append([kind(name), end, end - start])
    while stack:
        close(stack.pop())
    return out


def clip(events, window):
    lo, hi = window
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events if e > lo and s < hi]


def reduce(loaded: dict, slice_s: float | None = None) -> dict | None:
    """busy_s (mean over device planes), window_s, the kinds of
    operation that took most device time, and the idle gaps that took
    most, summed by the programs on either side. `slice_s` is how long
    the capture was held open. None if no operation ran on a device."""
    lo, hi = loaded["window"]
    if slice_s is not None:
        hi = min(hi, lo + int(slice_s * 1e9))
    window = (lo, hi)
    devices = {
        name: {line: clip(events, window) for line, events in lines.items()}
        for name, lines in loaded["devices"].items()
    }
    devices = {name: lines for name, lines in devices.items() if lines.get(OPS_LINE)}
    if not devices:
        return None
    busy_ns, op_ns, gap_ns = [], {}, {}
    for lines in devices.values():
        ops = lines[OPS_LINE]
        busy_ns.append(stats.covered(stats.union((s, e) for _, s, e in ops)))
        for name, ns in self_times(ops).items():
            op_ns[name] = op_ns.get(name, 0) + ns
        # gaps between whole programs say what the host was late with;
        # gaps inside a program are the program's own
        modules = sorted(lines.get(MODULES_LINE) or [], key=lambda m: m[1])
        if not modules:
            gap_ns["between operations"] = (hi - lo) - busy_ns[-1]
            continue
        starts = [m[1] for m in modules]
        program_union = stats.union((s, e) for _, s, e in modules)
        for start, end in stats.gaps(program_union, window):
            i = bisect.bisect_left(starts, end)
            before = kind(modules[i - 1][0]) if i > 0 else "start of slice"
            after = kind(modules[i][0]) if i < len(modules) else "end of slice"
            label = f"after {before} | before {after}"
            gap_ns[label] = gap_ns.get(label, 0) + (end - start)
        inside = stats.covered(program_union) - busy_ns[-1]
        if inside > 0:
            key = "inside programs, between operations"
            gap_ns[key] = gap_ns.get(key, 0) + inside
    n = len(devices)

    def top(table: dict) -> list:
        ranked = sorted(table.items(), key=lambda kv: -kv[1])[:TOP]
        return [[name[:120], ns / n / 1e9] for name, ns in ranked]

    return {
        "busy_s": sum(busy_ns) / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "devices": n,
        "programs": sum(len(lines.get(MODULES_LINE) or []) for lines in devices.values()) / n,
        "breakdown": {"device_ops": top(op_ns), "idle_gaps": top(gap_ns)},
    }
