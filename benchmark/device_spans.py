"""What the readers of the `device.run` spans share.

The program opens one `device.run` span per program a node launched
(`Tracer.device_span`: `program` = sampler, vae_decode, text_encode,
prefill, decode, upscale_single) and its watcher thread ends it when the
program's output is ready. The span's `end` is when the device finished
it; its attributes `begin` (when the device could start it: the later of
the launch and the previous launch's end), `queued_s` and `busy_s` =
`end` - `begin`. Every span of every request is on the tracer's one
clock, so intervals of consecutive jobs compare as they stand, for every
request of a window and with no profiler: what `device_timeline.py` prints
by hand. An `end` is when the host could know, 1-2 ms after the device
finished (the runtime's notice; PERF.md §6, PR 36), so the benchmark's
metrics read the device's own line instead (`device_modules.py`), all but
the one whose program is longer than a traced slice holds whole
(`tile_device_ms.usdu`). A program without these spans (the parent of the
PR that brought them) makes every function here return None.
"""

from __future__ import annotations

import statistics

import spans

RUN = "device.run"


def runs(request: list, program: str | None = None) -> list:
    """The request's `device.run` spans that the watcher ended with a
    `busy_s`, of one program or of all."""
    return [
        s for s in request
        if s["name"] == RUN and s.get("end") is not None and s.get("status") == "ok"
        and "busy_s" in (s.get("attrs") or {})
        and (program is None or s["attrs"].get("program") == program)
    ]


def busy_seconds(request: list, program: str):
    """Device seconds of one program in one request; None without it."""
    found = runs(request, program)
    return sum(float(s["attrs"]["busy_s"]) for s in found) if found else None


def busy_ms(material: dict, program: str):
    """Median over the window's requests of a program's device seconds."""
    return spans.median_ms(material, lambda request: busy_seconds(request, program))


def jobs(material: dict) -> list:
    """(first begin, last end, summed busy_s) of each request that
    launched anything, in the order the device ran them."""
    out = []
    for request in material["spans"].values():
        found = runs(request)
        if found:
            out.append((
                min(float(s["attrs"]["begin"]) for s in found),
                max(float(s["end"]) for s in found),
                sum(float(s["attrs"]["busy_s"]) for s in found),
            ))
    return sorted(out)


def idle_pct(material: dict):
    """100 x (1 - device seconds / (last end - first begin)) over the
    window's launches: what the device did not spend on a launched
    program, between jobs and between a job's programs alike."""
    found = jobs(material)
    if not found:
        return None
    span_s = max(end for _, end, _ in found) - found[0][0]
    return 100.0 * (1.0 - sum(busy for _, _, busy in found) / span_s) if span_s > 0 else None


def between_jobs_ms(material: dict):
    """Median over consecutive jobs of the next one's first begin less
    this one's last end: how long the device had nothing of either."""
    found = jobs(material)
    gaps = [after[0] - before[1] for before, after in zip(found, found[1:])]
    return 1e3 * statistics.median(gaps) if gaps else None
