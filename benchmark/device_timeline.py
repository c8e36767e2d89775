#!/usr/bin/env python3
"""By hand: the device's timeline as the program's own `device.run` spans
give it, for a whole window and without a profiler.

    python3 benchmark/device_timeline.py --workload <cell> --seed <n> [--seconds 51]
    python3 benchmark/device_timeline.py --lateness <a kept profile directory>

The first sets the cell up as run.py does, offers its traffic for
`--seconds`, fetches every request's spans and prints, for the gaps
between consecutive `device.run` busy intervals (`begin` to `end`, every
job's on the tracer's one clock), the innermost span of the executor
thread that covers each instant of a gap, summed by span name: the idle
time put down to what the host was doing. Beside it the device seconds by
program and the median `after_ready_s` of the `device.wait` spans by node.
Writes them, and the spans, to <out>/device_timeline.json.

The second reads the trace a `run.py --trace 1 --keep-trace` run left:
while a capture is open the watcher thread holds a `device.watch` span as
long as it waits for a program's output, the capture mirrors it as an
annotation on that thread's line, and the program itself is an event of
the device's "XLA Modules" line on the same clock. It prints how long
after the program's end the annotation ended (median and worst): the
lateness of every `device.run`'s `end`, which is why the benchmark's
metrics read the device's line (`device_modules.py`) and not the spans;
and the device's own milliseconds by program, to lay beside the spans'
`busy_s`. Not part of a measured run.
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import device_modules  # noqa: E402
import device_spans  # noqa: E402
import xplane  # noqa: E402

# spans of other threads: the saver's, the watcher's, the HTTP route's,
# and `prompt_queue.wait`, which begins on the route's
NOT_THE_EXECUTOR = ("png.encode", "file.write", "device.watch", device_spans.RUN,
                    "sched.wait", "queue_orchestration", "prompt_queue.wait")
WATCH_EVENT = "device.watch"


def busy_intervals(requests: dict) -> list:
    """Sorted (begin, end, program) of every `device.run` of `requests`
    ({trace id: [span, ...]})."""
    return sorted(
        (float(s["attrs"]["begin"]), float(s["end"]), s["attrs"].get("program"))
        for request in requests.values() for s in device_spans.runs(request)
    )


def gap_table(requests: dict) -> dict:
    """{span name: seconds} over the gaps between consecutive busy
    intervals: each instant of a gap goes to the executor-thread span
    that began last among those covering it (the innermost: one thread's
    spans nest), or to "no span"."""
    busy = busy_intervals(requests)
    host = [
        (float(s["start"]), float(s["end"]), s["name"])
        for request in requests.values() for s in request
        if s.get("end") is not None and s["name"] not in NOT_THE_EXECUTOR
    ]
    table: dict = {}
    reached = busy[0][1] if busy else 0.0
    for begin, end, _ in busy[1:]:
        if begin > reached:
            covering = [h for h in host if h[0] < begin and h[1] > reached]
            cuts = sorted({reached, begin, *(
                t for h in covering for t in h[:2] if reached < t < begin)})
            for lo, hi in zip(cuts, cuts[1:]):
                inside = [h for h in covering if h[0] <= lo and h[1] >= hi]
                name = max(inside, key=lambda h: (h[0], -h[1]))[2] if inside else "no span"
                table[name] = table.get(name, 0.0) + (hi - lo)
        reached = max(reached, end)
    return dict(sorted(table.items(), key=lambda kv: -kv[1]))


def by_program(requests: dict) -> dict:
    """{program: device seconds} over `requests`."""
    out: dict = {}
    for begin, end, program in busy_intervals(requests):
        out[program] = out.get(program, 0.0) + (end - begin)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def after_ready_ms(requests: dict) -> dict:
    """{node: median ms} of `after_ready_s` on the `device.wait` spans,
    by the node that waited: what a read-back cost once the device was
    done."""
    found: dict = {}
    for request in requests.values():
        names = {s["span_id"]: s["name"] for s in request}
        for s in request:
            if s["name"] == "device.wait" and "after_ready_s" in (s.get("attrs") or {}):
                found.setdefault(names.get(s["parent_id"], "?"), []).append(
                    1e3 * float(s["attrs"]["after_ready_s"]))
    return {node: statistics.median(ms) for node, ms in sorted(found.items())}


RUNTIME_DONE = "Execute=>Done"  # the TPU runtime's own host-side event at a program's end


def watch_and_modules(path: str) -> tuple[list, list, list]:
    """From an .xplane.pb: the (end_ns, program) of every mirrored
    `device.watch` annotation (a host line bears the process's name, not
    the thread's, so they are found by the event's name); the (kind,
    start_ns, end_ns) of every event of the first device's "XLA Modules"
    line, sorted by end; and the sorted start_ns of the runtime's
    completion events on the host plane, which share the annotations'
    clock exactly where the device's line is aligned to it by the
    profiler."""
    from jax.profiler import ProfileData

    watched, done = [], []
    for plane in ProfileData.from_file(path).planes:
        if xplane.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for event in line.events:
                if event.name == WATCH_EVENT:
                    program = dict(event.stats).get("program", "")
                    watched.append((int(event.start_ns) + int(event.duration_ns), str(program)))
                elif RUNTIME_DONE in event.name:
                    done.append(int(event.start_ns))
    modules = sorted(device_modules.read_modules(path), key=lambda m: m[2])
    return watched, modules, sorted(done)


def after_last_ms(moments: list, earlier: list) -> list:
    """For each of `moments` (ns), how long after the last of the sorted
    `earlier` (ns) that had passed by then, in ms; those with none before
    them are left out."""
    out = []
    for at in moments:
        i = bisect.bisect_right(earlier, at)
        if i:
            out.append((at - earlier[i - 1]) / 1e6)
    return out


def lateness_ms(watched: list, module_ends: list) -> list:
    """For each annotation, how long before its end the last program
    ended that had ended by then, in ms: the runtime's own notice, the
    watcher's wake-up, the stamping of the span and the annotation's
    exit, and whatever the profiler's alignment of the two clocks is off
    by."""
    return after_last_ms([end for end, _ in watched], module_ends)


def _summary(values: list) -> dict:
    ordered = sorted(values)
    return {"n": len(ordered), "median_ms": statistics.median(ordered),
            "p90_ms": ordered[int(0.9 * (len(ordered) - 1))], "worst_ms": ordered[-1]}


def report_lateness(profile_dir: str) -> int:
    path = xplane.find_trace(profile_dir)
    if path is None:
        print(f"device_timeline: no .xplane.pb under {profile_dir}", file=sys.stderr)
        return 1
    watched, modules, done = watch_and_modules(path)
    module_ends = [end for _, _, end in modules]
    result = {"annotations": len(watched), "programs": len(modules), "runtime_done": len(done)}
    for name, values in (
        ("watch_after_program", lateness_ms(watched, module_ends)),
        # the same on one clock: the annotation against the runtime's own event
        ("watch_after_runtime_done", after_last_ms([end for end, _ in watched], done)),
        # and what lies between the two lines
        ("runtime_done_after_program", after_last_ms(done, module_ends)),
    ):
        if values:
            result[name] = _summary(values)
    # the device's own milliseconds by program, to lay beside the spans' busy_s
    by_kind: dict = {}
    for name, start, end in modules:
        by_kind.setdefault(name, []).append((end - start) / 1e6)
    result["module_ms"] = {
        name: {"calls": len(ms), "median": statistics.median(ms)}
        for name, ms in sorted(by_kind.items())}
    print(json.dumps(result))
    return 0


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--lateness", metavar="PROFILE_DIR", default=None)
    parser.add_argument("--workload")
    parser.add_argument("--seed", type=int)
    parser.add_argument("--seconds", type=float, default=51.0)
    parser.add_argument("--out", default=None)
    parser.add_argument("--rehearsal", action="store_true")
    args = parser.parse_args()
    if args.lateness:
        return report_lateness(args.lateness)
    if not args.workload or args.seed is None:
        parser.error("--workload and --seed, or --lateness")
    import run as harness

    args.trace = 0
    args.out = args.out or os.path.join(
        harness.ROOT, "chiprun_out", "device_timeline", args.workload)
    try:
        run = harness.Run(harness.Cell(args.workload, args.rehearsal), args)
        try:
            run.set_up()
            run.window()
            requests = run.spans()
        finally:
            run.server.stop(grace_s=60)
    except harness.Failure as exc:
        print(f"device_timeline: FAILED: {exc}", file=sys.stderr)
        return 1
    material = {"spans": requests}
    result = {
        "requests": len(requests),
        "device_idle_in_pct": device_spans.idle_pct(material),
        "between_jobs_ms": device_spans.between_jobs_ms(material),
        "device_s_by_program": by_program(requests),
        "after_ready_ms": after_ready_ms(requests),
        "gap_s_by_span": gap_table(requests),
    }
    print(json.dumps(result, indent=1), flush=True)
    with open(os.path.join(run.out, "device_timeline.json"), "w", encoding="utf-8") as fh:
        json.dump({**result, "spans": requests}, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
