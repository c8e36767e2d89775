"""Set-up as the program itself saw it: what the seven set-up readers share.

When a capture starts, the program writes every span its tracer holds
into the capture's own directory, `spans_before.jsonl` (one span a line,
as /distributed/trace/<id> serves them, on the tracer's one clock):

- the trace `startup`: `process.start`, from the operating system's
  creation of the server's process to the bound socket, with its children
  `startup.chips`, `.compile_cache`, `.backend`, `.imports`, `.mesh`,
  `.server`, and a `program.build` span for each program built outside a
  request;
- one trace a request so far. Those that are not among the window's
  `material["records"]` are set-up's: the request as committed and the
  warm ones. Under their `node.<class_type>` spans lie `program.build`
  spans, one a program JAX took to the device: `program`, `outcome`
  (`built`, `fetched`, `traced`) and `trace_s`, `lower_s`, `build_s`,
  `fetch_s`, each the union of that phase's intervals, so a jit inside a
  jit is counted once.

`split()` gives seven numbers that add up to the stretch from
`process.start`'s start to the end of the last set-up request's
`execute_prompt`. run.py hands its readers no path: the file is found
under `device_modules.profile_dir()`, where the run keeps its profile
until the readers have run. Without the file (an untraced run, a program
that writes none) every reader returns None and its metric is left out.
"""

from __future__ import annotations

import glob
import json
import os

import device_modules

FILE = "spans_before.jsonl"
STARTUP = "startup"
ROOT = "process.start"
PROGRAM = "program.build"
PHASES = ("trace", "lower", "fetch", "build")
_LOADED: dict = {}  # path -> {trace id: [span, ...]}


def traces(material: dict):
    """{trace id: [span, ...]} of the run's spans_before.jsonl; None
    where there is none."""
    folder = device_modules.profile_dir()
    found = sorted(glob.glob(os.path.join(folder, "**", FILE), recursive=True)) if folder else []
    if not found:
        return None
    path = found[-1]
    if path not in _LOADED:
        by_trace: dict = {}
        with open(path, encoding="utf-8") as fh:
            for line in fh:
                if line.strip():
                    span = json.loads(line)
                    by_trace.setdefault(span["trace_id"], []).append(span)
        _LOADED[path] = by_trace
    return _LOADED[path]


def setup_requests(material: dict, by_trace: dict) -> list:
    """The traces that are neither the start's nor a request's of the
    window: what set-up sent."""
    window = {r.get("trace_id") for r in material["records"]}
    return [spans for trace_id, spans in by_trace.items()
            if trace_id != STARTUP and trace_id not in window]


def closed(spans: list, name: str | None = None) -> list:
    return [s for s in spans if s.get("end") is not None and (name is None or s["name"] == name)]


def split(material: dict):
    """The seven set-up metrics by name, or None without the file or
    without a `process.start` that has ended."""
    by_trace = traces(material)
    if by_trace is None:
        return None
    root = next(iter(closed(by_trace.get(STARTUP, []), ROOT)), None)
    if root is None:
        return None
    requests = setup_requests(material, by_trace)
    programs = [s for spans in [by_trace[STARTUP], *requests] for s in closed(spans, PROGRAM)]
    out = {
        f"program_{phase}_s": sum(float(s["attrs"].get(f"{phase}_s", 0.0)) for s in programs)
        for phase in PHASES
    }
    out["server_start_s"] = float(root["end"]) - float(root["start"])
    # a loader's node less the programs built under it, at whatever
    # depth (a node's own spans may lie between): weights drawn or read
    # and placed. `LoadImage` is an input, not a loader.
    loaders_s = 0.0
    for spans in requests:
        parents = {s["span_id"]: s.get("parent_id") for s in spans}
        nodes = {s["span_id"] for s in closed(spans)
                 if s["name"].startswith("node.") and "Loader" in s["name"]}

        def under_a_loader(span_id) -> bool:
            for _ in spans:  # a tree is no deeper than it has spans
                span_id = parents.get(span_id)
                if span_id is None or span_id in nodes:
                    return span_id is not None
            return False

        loaders_s += sum(float(s["duration"]) for s in closed(spans) if s["span_id"] in nodes)
        loaders_s -= sum(float(s["duration"]) for s in closed(spans, PROGRAM)
                         if under_a_loader(s["span_id"]))
    out["loaders_s"] = loaders_s
    ends = [float(s["end"]) for spans in requests for s in closed(spans, "execute_prompt")]
    whole = (max(ends) if ends else float(root["end"])) - float(root["start"])
    out["setup_other_s"] = whole - sum(out.values())
    return out


def read(material: dict, name: str):
    found = split(material)
    return None if found is None else found[name]
