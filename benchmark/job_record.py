"""What the readers of the job's record share.

When a job's last work ends, the program stamps its `execute_prompt`
span with the job's seconds by where it stood, from its arrival
(`sched.wait`'s start) to the span's end: `waiting_s` (queued, walked
or launched while the chip did earlier jobs' work), `device_s` (its own
programs on the chip: the union of its `device.run` [`begin`, `end`]),
`starved_s` (the chip had nothing until this job's next launch: the
`idle_before_s` of its launches as far as they lie inside the job) and
`tail_s` (after its last program's end: the read-back once ready, the
PNG encode, the file write). The four sum to the job. They are on the
tracer's one clock and need no profiler, so a reader has every request
of the window, not a slice. A program without the record (the parent of
the PR that brought it) makes every function here return None, and the
reader leaves its metric out.
"""

from __future__ import annotations

import spans

PARTS = ("waiting_s", "device_s", "starved_s", "tail_s")


def record(request: list):
    """The four parts of one request, off its finished `execute_prompt`;
    None where no such span bears them all."""
    for span in request:
        attrs = span.get("attrs") or {}
        if (span["name"] == "execute_prompt" and span.get("end") is not None
                and all(part in attrs for part in PARTS)):
            return {part: float(attrs[part]) for part in PARTS}
    return None


def part_of(part: str):
    """`one(request)` for `spans.median_ms` and `spans.percentile_ms`."""
    def one(request: list):
        found = record(request)
        return None if found is None else found[part]

    return one


def starved_pct(material: dict):
    """100 x starved seconds / (device + starved seconds), summed over
    the window's requests: of the time the chip either ran a job's
    program or sat waiting for that job's next launch, the share it
    sat. None without a record, or where no job launched anything."""
    found = spans.per_request(material, record)
    if not found:
        return None
    starved = sum(r["starved_s"] for r in found)
    whole = starved + sum(r["device_s"] for r in found)
    return 100.0 * starved / whole if whole > 0 else None
