"""What the readers of the program's own span tree share.

`material["spans"]` is {trace id: [span, ...]} for the window's
requests, each span as /distributed/trace/<id> serves it (`name`,
`span_id`, `parent_id`, `duration`, `attrs`). The served path's tree is
`sched.wait`, `queue_orchestration`, `prompt_queue.wait`,
`execute_prompt`, under it one `node.<class_type>` per node that ran,
and `device.wait`, `png.encode`, `file.write` where the executor thread
blocks or saves. A program without these spans makes every function
here return None, and the reader leaves its metric out.
"""

from __future__ import annotations

import statistics

import stats


def seconds(spans: list, *names: str):
    """Summed duration of the closed spans of one request that bear any
    of `names`; None when it has none."""
    found = [
        float(s["duration"]) for s in spans
        if s["name"] in names and s.get("duration") is not None
    ]
    return sum(found) if found else None


def host_seconds(spans: list):
    """One request's `execute_prompt` less the `device.wait` spans below
    it: the time the executor thread was not parked waiting for the
    device. None without either span."""
    root = next(
        (s for s in spans
         if s["name"] == "execute_prompt" and s.get("duration") is not None),
        None,
    )
    if root is None:
        return None
    parents = {s["span_id"]: s.get("parent_id") for s in spans}

    def below_root(span_id) -> bool:
        for _ in spans:  # a tree is no deeper than it has spans
            span_id = parents.get(span_id)
            if span_id is None:
                return False
            if span_id == root["span_id"]:
                return True
        return False

    waits = [
        float(s["duration"]) for s in spans
        if s["name"] == "device.wait" and s.get("duration") is not None
        and below_root(s["span_id"])
    ]
    return float(root["duration"]) - sum(waits) if waits else None


def per_request(material: dict, one) -> list:
    """`one(spans)` for each of the window's requests that has it."""
    values = (one(spans) for spans in material["spans"].values())
    return [v for v in values if v is not None]


def median_ms(material: dict, one):
    values = per_request(material, one)
    return 1e3 * statistics.median(values) if values else None


def percentile_ms(material: dict, one, p: float):
    values = per_request(material, one)
    return 1e3 * stats.percentile(values, p) if values else None
