"""A job's seconds by where it stood, stamped on `execute_prompt`.

Over J = [the start of the trace's `sched.wait`, else of its
`prompt_queue.wait`, the end of `execute_prompt`] each instant goes to
the first of these that applies:

- `device_s`: one of the job's own programs was on the chip (the union
  of its `device.run` [`begin`, `end`]);
- `starved_s`: the chip had nothing and this job's next launch was what
  it lacked (the `idle_before_s` before each of its launches, as far as
  it lies in J: before the job was enqueued the chip had no work, and
  that is nobody's);
- `tail_s`: after the end of the job's last program: the read-back once
  ready, the PNG encode, the file write, the hand-off;
- `waiting_s`: the rest: queued, walked or launched while the chip did
  earlier jobs' work.

The four sum to J's length. A job that launched nothing is all
`tail_s`; a failed program's launch says nothing and is left out.
`starved_in` names the innermost span of the executor thread that covers
most of `starved_s`: what the host was doing while the chip sat idle
(`node.TextGenerate`, `executor.between_jobs`, `program.build` ...).

`job_record` is a function of the trace's spans as `Tracer.spans` and
`/distributed/trace/<id>` give them; `stamp_job` is what the server
calls once a job, on whichever thread ends it. Neither waits for the
device or for the watcher thread.
"""

from __future__ import annotations

from typing import Any, Optional

from .runtime import _covered
from .tracing import Span, Tracer

PARTS = ("waiting", "device", "starved", "tail")
CAUSES = ("no_job", "between_jobs", "within_job")
# spans that are open while the executor thread is elsewhere: the
# watcher's, the saver's, the route's, and a wait for the chip itself
_OFF_THREAD = frozenset({
    "device.run", "device.watch", "device.wait", "png.encode", "file.write",
    "sched.wait", "prompt_queue.wait", "queue_orchestration",
})


def _starved_in(
    spans: list[dict[str, Any]], starved: list[tuple[float, float]], end: float
) -> Optional[str]:
    """Each starved instant goes to the innermost span the executor
    thread had open (of those open, the one that started last: one
    thread's spans nest); the name that gathers most seconds."""
    seconds: dict[str, float] = {}
    on_thread = [
        (s["start"], end if s["end"] is None else s["end"], s["name"])
        for s in spans if s["name"] not in _OFF_THREAD
    ]
    for a, b in starved:
        held = [h for h in on_thread if h[0] < b and h[1] > a]
        cuts = sorted({a, b, *(t for h in held for t in h[:2] if a < t < b)})
        for lo, hi in zip(cuts, cuts[1:]):
            inner = max(
                (h for h in held if h[0] <= lo and h[1] >= hi),
                key=lambda h: (h[0], -h[1]), default=None,
            )
            if inner is not None:
                seconds[inner[2]] = seconds.get(inner[2], 0.0) + hi - lo
    return max(seconds, key=seconds.get) if seconds else None


def job_record(
    spans: list[dict[str, Any]], end: float
) -> tuple[dict[str, Any], dict[str, float]]:
    """(the attributes of one job's `execute_prompt`, the chip's idle
    seconds before the job's launches by cause) from the spans of its
    trace and the span's end. A `device.run` whose `end` is None still
    runs at `end`."""
    def starts(name: str) -> list[float]:
        return [s["start"] for s in spans if s["name"] == name]

    begun = (starts("sched.wait") or starts("prompt_queue.wait")
             or starts("execute_prompt") or [end])
    t0 = min(min(begun), end)
    runs = sorted(
        (s for s in spans if s["name"] == "device.run" and s["status"] != "error"),
        key=lambda s: s["start"],
    )
    device, starved = [], []
    idle = dict.fromkeys(CAUSES, 0.0)
    last_end = t0
    for index, run in enumerate(runs):
        attrs = run["attrs"]
        launched = min(run["start"], end)
        begin = min(attrs.get("begin", max(launched, last_end)), end)
        finished = end if run["end"] is None else min(run["end"], end)
        last_end = max(last_end, finished)
        device.append((max(begin, t0), finished))
        idle_from = launched - attrs.get("idle_before_s", 0.0)
        if idle_from < launched:
            idle["no_job"] += max(0.0, min(t0, launched) - idle_from)
            if launched > t0:
                lacked = (max(idle_from, t0), launched)
                starved.append(lacked)
                idle["within_job" if index else "between_jobs"] += lacked[1] - lacked[0]
    device_s = _covered(device)
    starved_s = _covered(device + starved) - device_s
    tail_s = end - last_end
    record = {
        "waiting_s": max(0.0, (last_end - t0) - device_s - starved_s),
        "device_s": device_s,
        "starved_s": starved_s,
        "tail_s": tail_s,
    }
    if starved_s > 0.0:
        name = _starved_in(spans, starved, end)
        if name is not None:
            record["starved_in"] = name
    return record, idle


def stamp_job(tracer: Tracer, span: Span, end: float) -> None:
    """Stamp the record of the job whose `execute_prompt` is `span`,
    about to end at `end`, and count it. A launch the watcher has not
    stamped by now stands as on the chip to the job's end, whether its
    output is there or not: nothing here waits or asks."""
    from .instruments import device_idle_seconds_total, job_seconds_total

    record, idle = job_record(tracer.spans(span.trace_id), end)
    span.attrs.update(record)
    seconds, idle_seconds = job_seconds_total(), device_idle_seconds_total()
    for part in PARTS:
        seconds.inc(record[f"{part}_s"], part=part)
    for cause in CAUSES:
        idle_seconds.inc(idle[cause], cause=cause)
