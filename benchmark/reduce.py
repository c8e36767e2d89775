"""What the per-layer readers share: from a run's material to numbers.

`material` is what run.py collected: "records" (one per request of the
window, as the client saw it), "first" (the request as committed, with
its /history timings), "prompt" (the graph), "after_setup" and
"after_window" (scrapes of /distributed/metrics), "spans" ({trace id:
[span, ...]} from /distributed/trace/<id>) and "trace" (xplane.reduce).
A reader that finds nothing to read returns None.
"""

from __future__ import annotations

import statistics

import stats


def span_seconds(material: dict, name: str) -> dict:
    """{trace id: seconds of the first span of that name}"""
    out = {}
    for trace_id, spans in material["spans"].items():
        for span in spans:
            if span["name"] == name and span.get("duration") is not None:
                out[trace_id] = float(span["duration"])
                break
    return out


def execute_ms(material: dict):
    seconds = list(span_seconds(material, "execute_prompt").values())
    return 1e3 * statistics.median(seconds) if seconds else None


def queue_wait_ms(material: dict, p: float):
    """Per request, what the client waited beyond the executor's own
    span: admission, the scheduler, the prompt queue, HTTP and polling."""
    executing = span_seconds(material, "execute_prompt")
    waits = [
        1e3 * (r["latency_s"] - executing[r["trace_id"]])
        for r in material["records"] if r["ok"] and r.get("trace_id") in executing
    ]
    return stats.percentile(waits, p) if waits else None


def device_idle_pct(material: dict):
    trace = material["trace"]
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
