"""The device's own seconds by program, from the trace a `--trace 1` run
keeps until its per-layer readers have run.

The first device plane of the .xplane.pb has a line "XLA Modules" with
one event a launched program (`jit_prefill(6576…)` -> `jit_prefill`), on
the device's clock: when the device began it and when it finished it.
A reader names the program it reads by that name, in its own file. The
program's own `device.run` spans (`device_spans.py`) say the same from
the host, but a host learns that a program has ended 1-2 ms after it has
(the runtime's notice; PERF.md §6, PR 36), so no share of a peak and no
gap of a few milliseconds stands on them.

run.py hands its readers no path: the profile lies under the directory
its `--out` names, or chiprun_out/benchmark/<--workload>, as its help
says. Without a trace there, or without a device plane in it, every
function here returns None and the reader leaves its metric out.
"""

from __future__ import annotations

import glob
import importlib.util
import json
import os
import statistics
import sys

import deepseek_reduce
import flux_counts
import spans
import stats
import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
_LOADED: dict = {}  # trace path -> the events of its "XLA Modules" line


def profile_dir(argv: list | None = None):
    """Where the run.py that was started with `argv` keeps its profile."""
    argv = sys.argv[1:] if argv is None else argv
    given = {
        flag: argv[i + 1] for i, flag in enumerate(argv[:-1]) if flag in ("--out", "--workload")
    }
    if "--out" in given:
        out = os.path.abspath(given["--out"])
    elif "--workload" in given:
        out = os.path.join(os.path.dirname(HERE), "chiprun_out", "benchmark", given["--workload"])
    else:
        return None
    return os.path.join(out, "profile")


def read_modules(path: str) -> list:
    """[(kind, start_ns, end_ns), ...] of the first device plane's "XLA
    Modules" line, by start; empty where the file has none."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name == xplane.MODULES_LINE:
                return sorted(
                    ((xplane.kind(e.name), int(e.start_ns), int(e.start_ns) + int(e.duration_ns))
                     for e in line.events), key=lambda m: m[1])
    return []


def modules(material: dict) -> list:
    """The programs the device ran in the traced slice, in order; empty
    for an untraced run or where no trace is found."""
    if not material.get("trace"):
        return []
    folder = profile_dir()
    path = xplane.find_trace(folder) if folder else None
    if path is None:
        return []
    if path not in _LOADED:
        _LOADED[path] = read_modules(path)
    return _LOADED[path]


def seconds(material: dict, kind: str) -> list:
    """Device seconds of each run of program `kind` that lies whole in
    the slice: the line's first and last events may be cut by its edges."""
    return [(end - start) / 1e9 for name, start, end in modules(material)[1:-1] if name == kind]


def median_s(material: dict, kind: str):
    found = seconds(material, kind)
    return statistics.median(found) if found else None


def median_ms(material: dict, kind: str):
    found = median_s(material, kind)
    return None if found is None else 1e3 * found


def idle_pct(material: dict):
    """100 x (1 - seconds inside a program / (last end - first start))
    over the slice's programs: what the device spent between programs."""
    found = modules(material)
    if len(found) < 2:
        return None
    busy = stats.covered(stats.union((start, end) for _, start, end in found))
    return 100.0 * (1.0 - busy / (max(end for _, _, end in found) - found[0][1]))


def gap_after_ms(material: dict, last: str, first: tuple):
    """Median over the slice, in ms, of the time from the end of a run
    of program `last` (the one that ends a job) to the start of the next
    run of one of `first` (those that begin one): how long the device
    had nothing of either job but the one-operation programs between."""
    found, gaps, ended = modules(material), [], None
    for name, start, end in found:
        if ended is not None and name in first:
            gaps.append((start - ended) / 1e6)
            ended = None
        if name == last:
            ended = end
    return statistics.median(gaps) if gaps else None


# --- the language models' work --------------------------------------------

PEAK = {"decode": "bytes_per_s", "prefill": "flops_per_s"}


def lm_work(material: dict):
    """(work, configuration) of the language model the cell's workflow
    loads: its `ckpt_name` against each configs/<stem>.json's
    `registry_name`, and lm_work/<stem>.py's `work(cfg, attrs)` ->
    {"decode": bytes, "prefill": FLOP} of one request whose
    `node.TextGenerate` span has `attrs`. None where the model has no
    such file."""
    loaded = {
        node["inputs"].get("ckpt_name") for node in material["prompt"].values()
        if node["class_type"] == "CheckpointLoaderSimple"
    }
    for path in sorted(glob.glob(os.path.join(HERE, "configs", "*.json"))):
        with open(path, encoding="utf-8") as fh:
            cfg = json.load(fh)
        stem = os.path.splitext(os.path.basename(path))[0]
        source = os.path.join(HERE, "lm_work", stem + ".py")
        if cfg.get("registry_name") in loaded and os.path.exists(source):
            spec = importlib.util.spec_from_file_location("lm_work_of_a_model", source)
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            return module.work, cfg
    return None


def lm_share_pct(material: dict, program: str, kind: str):
    """A language-model program's share of the chip's peak, in per cent:
    the median over the window's requests of what `program` (decode or
    prefill; `PEAK` says in which unit) has to do, over the median device
    seconds of module `kind`, against that peak of the chip the
    configuration names."""
    found, device_s = lm_work(material), median_s(material, kind)
    if found is None or not device_s:
        return None
    work, cfg = found

    def one(request):
        attrs = deepseek_reduce.attrs_of(request)
        return work(cfg, attrs)[program] if attrs.get("new_tokens") else None

    values = spans.per_request(material, one)
    if not values:
        return None
    peak = flux_counts.peaks(cfg["as_run"]["chip"])[PEAK[program]]
    return 100.0 * statistics.median(values) / device_s / peak
