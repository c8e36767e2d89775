#!/usr/bin/env python3
"""Per-stage latency breakdown from a trace JSONL export.

Input: one span per line, as written by `Tracer.write_jsonl`
(telemetry/tracing.py) — by the chaos harness (`run_chaos_usdu(...,
trace_jsonl=...)`), or by a live server with CDT_TRACE_EXPORT_DIR set.

Output: a per-span-name latency table (count / total / mean / p50 /
p95 / p99 / max) and, for spans carrying a `tile_idx` attribute, the
reconstructed per-tile lifecycle (which stages each tile went through,
in span-clock order, and which tiles are missing stages).

`--compare OLD.jsonl` turns the report into a regression gate: the
per-stage p95 of the new trace is checked against the old one and the
process exits 3 when any shared stage regressed by more than
`--regress-pct` percent (default 25) — the CI hook for "did this
PR make a stage slower".

Stdlib only; importable (tests call `build_report` / `tile_lifecycle`
/ `compare_reports` directly) and runnable:

    python scripts/perf_report.py trace.jsonl [--trace TRACE_ID] [--json]
    python scripts/perf_report.py new.jsonl --compare old.jsonl [--regress-pct 25]
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any

# A tile's lifecycle is complete when SOMEONE sampled it and the
# master blended it. That is the invariant of every completion path:
# master-computed (pull→sample→blend), worker-computed (worker
# pull→sample[→encode/submit], master decode→blend), requeue recovery
# (the successful attempt closes it), and the deadline fallback (the
# master samples un-pulled tiles directly). Per-tile submit spans are
# optional — the production worker flushes submits in batches without
# a tile_idx, while the chaos harness records them per tile. The ONE
# legitimate path with neither sample nor blend is a cache settle: a
# `tile.cache.hit` span on the master means the content-addressed
# cache served the tile and nobody computed it this run.
REQUIRED_ANY_ROLE = "sample"
REQUIRED_MASTER = "blend"
REQUIRED_CACHED = "cache.hit"

# Cache serving reconstruction: the master opens one `tile.cache.probe`
# span per job (attrs: `hits`) and one `tile.cache.hit` span per tile
# it settles from the cache; `tile.dispatch` spans carry the `real`
# tiles that DID burn device slots. hits / (hits + dispatched real) is
# the offline cache hit rate for the trace.
CACHE_HIT_STAGE = "cache.hit"
CACHE_PROBE_STAGE = "cache.probe"

# Scheduler queue-wait reconstruction: the admission gate opens a
# `sched.wait` span when a request is admitted (api/job_routes.py);
# the wait ends at the execution's FIRST tile pull (master- or
# worker-side). Requests that never reach a tile job (pure fan-out)
# fall back to the grant wait itself (the span's own duration).
SCHED_WAIT_SPAN = "sched.wait"
PULL_SPAN_NAMES = ("tile.pull", "rpc.request_image")

# Pipeline-overlap reconstruction: the elastic tier's staged executor
# (graph/tile_pipeline.py) dispatches the next batch's `sample` while
# the previous batch's readback/encode/submit ride the I/O stage. The
# overlap fraction — how much of the sample-stage wall ran concurrently
# with I/O-stage work — is reconstructed from the span timeline of the
# existing cdt_tile_stage_seconds spans (no new instrumentation).
SAMPLE_STAGE = "sample"
IO_STAGES = ("readback", "encode", "submit")

# Host-tax reconstruction (telemetry/profiling.py offline counterpart):
# `tile.dispatch` spans carry a `device` attr — True when a COMPILED
# program ran (device time), False/absent for the eager-stub tier
# (host time: Python ran the math). Host-bucket stages are the
# gather/encode/ship work between dispatches. The ratio
# host_ns / (host_ns + device_ns) is the host tax; a zero-device trace
# (eager chaos run) honestly reads 1.0, never NaN.
HOST_TAX_STAGES = ("readback", "encode", "decode", "submit")
_NS = 1_000_000_000


def _to_ns(seconds: Any) -> int:
    """PR-15 conservation idiom: one float->int rounding at ingest,
    integer arithmetic after — sums are exact, never float-drifty."""
    return int(round(float(seconds) * _NS))


def load_spans(path: str) -> list[dict[str, Any]]:
    spans = []
    with open(path, "r", encoding="utf-8") as fh:
        for line_no, line in enumerate(fh, 1):
            line = line.strip()
            if not line:
                continue
            try:
                spans.append(json.loads(line))
            except json.JSONDecodeError as exc:
                raise SystemExit(f"{path}:{line_no}: bad JSON line: {exc}")
    return spans


def _percentile(sorted_values: list[float], fraction: float) -> float:
    if not sorted_values:
        return 0.0
    idx = min(len(sorted_values) - 1, int(round(fraction * (len(sorted_values) - 1))))
    return sorted_values[idx]


def queue_wait_stats(spans: list[dict[str, Any]]) -> dict[str, Any] | None:
    """Admission→first-pull wait per trace, aggregated.

    For every trace carrying a `sched.wait` span, the queue wait is
    the gap between that span's start (admission) and the start of the
    trace's first tile pull; when the trace recorded no pulls, the
    grant wait (the sched.wait duration) stands in. None when no
    scheduler spans exist (pre-scheduler traces stay comparable)."""
    admits: dict[Any, dict[str, Any]] = {}
    first_pull: dict[Any, float] = {}
    for span in spans:
        trace_id = span.get("trace_id")
        start = span.get("start")
        if start is None:
            continue
        if span.get("name") == SCHED_WAIT_SPAN:
            current = admits.get(trace_id)
            if current is None or start < current["start"]:
                admits[trace_id] = {
                    "start": float(start),
                    "duration": span.get("duration"),
                }
        elif span.get("name") in PULL_SPAN_NAMES:
            prev = first_pull.get(trace_id)
            if prev is None or start < prev:
                first_pull[trace_id] = float(start)
    if not admits:
        return None
    waits: list[float] = []
    for trace_id, admit in admits.items():
        pull = first_pull.get(trace_id)
        if pull is not None and pull >= admit["start"]:
            waits.append(pull - admit["start"])
        elif admit["duration"] is not None:
            waits.append(float(admit["duration"]))
    if not waits:
        return None
    waits.sort()
    return {
        "count": len(waits),
        "mean": sum(waits) / len(waits),
        "p50": _percentile(waits, 0.50),
        "p95": _percentile(waits, 0.95),
        "max": waits[-1],
    }


def _merge_intervals(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[tuple[float, float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], end))
        else:
            merged.append((start, end))
    return merged


def pipeline_overlap_stats(spans: list[dict[str, Any]]) -> dict[str, Any] | None:
    """Fraction of sample-stage wall overlapped by the SAME pipeline's
    I/O-stage work (readback/encode/submit), from span start/duration
    timelines.

    Spans are grouped per (role, worker_id) before intersecting:
    participant A's submit riding concurrently with participant B's
    sample is fleet parallelism, not pipelining — counting it would let
    a fully serial per-worker loop read as overlapped just because the
    fleet is busy. 0.0 = fully serial (the pre-pipeline loop shape:
    every encode and submit sat squarely between device dispatches);
    values toward 1.0 mean each pipeline's I/O stages ride concurrently
    with its own sampling. None when no pipeline has both finished
    sample and I/O spans (nothing to overlap)."""
    sample_by: dict[tuple, list[tuple[float, float]]] = {}
    io_by: dict[tuple, list[tuple[float, float]]] = {}
    for span in spans:
        attrs = span.get("attrs") or {}
        stage = attrs.get("stage")
        start = span.get("start")
        duration = span.get("duration")
        if stage is None or start is None or duration is None:
            continue
        key = (attrs.get("role", "?"), attrs.get("worker_id") or "")
        interval = (float(start), float(start) + float(duration))
        if stage == SAMPLE_STAGE:
            sample_by.setdefault(key, []).append(interval)
        elif stage in IO_STAGES:
            io_by.setdefault(key, []).append(interval)
    sample_wall = 0.0
    overlapped = 0.0
    measured = False
    for key, sample_iv in sample_by.items():
        io_iv = io_by.get(key)
        if not io_iv:
            continue
        measured = True
        io_union = _merge_intervals(io_iv)
        sample_wall += sum(end - start for start, end in sample_iv)
        for s_start, s_end in sample_iv:
            for i_start, i_end in io_union:
                if i_start >= s_end:
                    break
                lo, hi = max(s_start, i_start), min(s_end, i_end)
                if hi > lo:
                    overlapped += hi - lo
    if not measured:
        return None
    return {
        "sample_wall": sample_wall,
        "overlapped": overlapped,
        "fraction": (overlapped / sample_wall) if sample_wall > 0 else 0.0,
    }


def batch_fill_stats(spans: list[dict[str, Any]]) -> dict[str, Any] | None:
    """Cross-job continuous-batching fill ratio from the executor's
    per-dispatch spans (graph/batch_executor.py emits one
    ``tile.dispatch`` span per device dispatch with ``real`` tiles vs
    padded ``bucket`` slots). 1.0 = every device slot ran a real tile;
    lower means slots burned on wraparound padding — the utilization
    the cross-job tier exists to recover. None when no dispatch spans
    are present (the scan tier emits none)."""
    real = 0
    slots = 0
    dispatches = 0
    cross_job_dispatches = 0
    for span in spans:
        attrs = span.get("attrs") or {}
        if attrs.get("stage") != "dispatch":
            continue
        try:
            r = int(attrs.get("real", 0))
            b = int(attrs.get("bucket", 0))
        except (TypeError, ValueError):
            continue
        if b <= 0:
            continue
        dispatches += 1
        real += r
        slots += b
        if int(attrs.get("jobs", 1) or 1) > 1:
            cross_job_dispatches += 1
    if dispatches == 0:
        return None
    return {
        "dispatches": dispatches,
        "cross_job_dispatches": cross_job_dispatches,
        "real_tiles": real,
        "slots": slots,
        "fill": (real / slots) if slots > 0 else 0.0,
    }


def adapter_stats(spans: list[dict[str, Any]]) -> dict[str, Any] | None:
    """Adapter-plane dispatch share from the executor's per-dispatch
    spans (graph/batch_executor.py stamps ``adapter=True`` on batches
    running the segmented per-slot LoRA patch). Reports how many
    dispatches/slots wore adapters and the fill ratio INSIDE those
    batches — personalized batches under-filling while base batches
    stay full is the adapter-thrashing signature (runbook §4p). None
    when the trace has no adapter dispatches (an adapter-less run
    stays comparable — absence is not a 0% share)."""
    dispatches = 0
    adapter_dispatches = 0
    adapter_real = 0
    adapter_slots = 0
    for span in spans:
        attrs = span.get("attrs") or {}
        if attrs.get("stage") != "dispatch":
            continue
        try:
            r = int(attrs.get("real", 0))
            b = int(attrs.get("bucket", 0))
        except (TypeError, ValueError):
            continue
        if b <= 0:
            continue
        dispatches += 1
        if attrs.get("adapter"):
            adapter_dispatches += 1
            adapter_real += r
            adapter_slots += b
    if adapter_dispatches == 0:
        return None
    return {
        "dispatches": dispatches,
        "adapter_dispatches": adapter_dispatches,
        "adapter_real_tiles": adapter_real,
        "adapter_slots": adapter_slots,
        "dispatch_share": adapter_dispatches / dispatches,
        "adapter_fill": (
            (adapter_real / adapter_slots) if adapter_slots > 0 else 0.0
        ),
    }


def cache_stats(spans: list[dict[str, Any]]) -> dict[str, Any] | None:
    """Tile-cache serving rate from the master's probe/hit spans vs
    the dispatch spans: what fraction of this trace's tiles were
    settled straight from the content-addressed cache instead of
    burning a device slot. None when the trace recorded no probe (a
    cache-off run stays comparable — absence is not a 0% hit rate)."""
    probes = 0
    hits = 0
    dispatched = 0
    for span in spans:
        attrs = span.get("attrs") or {}
        stage = attrs.get("stage")
        if stage == CACHE_HIT_STAGE:
            hits += 1
        elif stage == CACHE_PROBE_STAGE:
            probes += 1
        elif stage == "dispatch":
            try:
                dispatched += int(attrs.get("real", 0) or 0)
            except (TypeError, ValueError):
                continue
    if probes == 0 and hits == 0:
        return None
    served = hits + dispatched
    return {
        "probes": probes,
        "hits": hits,
        "dispatched_tiles": dispatched,
        "hit_rate": (hits / served) if served > 0 else 0.0,
    }


def host_tax_stats(spans: list[dict[str, Any]]) -> dict[str, Any] | None:
    """Device/host time split from the span stream alone.

    Device ns: dispatch spans whose `device` attr is truthy (a
    compiled program ran — graph/tile_pipeline.py and
    graph/batch_executor.py stamp the attr from the same
    ``hasattr(step, "lower")`` gate the jit decision uses). Eager
    dispatches (chaos stubs) are host work — Python executed the
    math — so they join the host side; that is what makes a
    zero-device run read host_tax = 1.0 instead of NaN. None when the
    trace has neither dispatches nor host-bucket stages (nothing to
    attribute)."""
    device_ns = 0
    eager_ns = 0
    host_ns = 0
    dispatches = 0
    device_dispatches = 0
    for span in spans:
        attrs = span.get("attrs") or {}
        stage = attrs.get("stage")
        duration = span.get("duration")
        if stage is None or duration is None:
            continue
        try:
            ns = _to_ns(duration)
        except (TypeError, ValueError):
            continue
        if stage == "dispatch":
            dispatches += 1
            if attrs.get("device"):
                device_dispatches += 1
                device_ns += ns
            else:
                eager_ns += ns
        elif stage in HOST_TAX_STAGES:
            host_ns += ns
    if dispatches == 0 and host_ns == 0:
        return None
    total_host = host_ns + eager_ns
    if device_ns <= 0:
        tax = 1.0
    else:
        tax = total_host / (total_host + device_ns)
    return {
        "dispatches": dispatches,
        "device_dispatches": device_dispatches,
        "device_ns": device_ns,
        "eager_ns": eager_ns,
        "host_ns": host_ns,
        "host_tax": tax,
    }


def host_tax_regressions(
    old_ht: dict[str, Any] | None,
    new_ht: dict[str, Any] | None,
    regress_pct: float,
) -> list[dict[str, Any]]:
    """The host-tax gate: the device-resident PRs must show the ratio
    FALLING, so growth beyond `regress_pct` percent relative fails
    --compare — host work crept back between device dispatches. Old
    tax below 1% gates on absolute growth of more than one percentage
    point (the usage_waste_share near-zero-base rule)."""
    if not old_ht or not new_ht:
        return []
    old_tax = old_ht["host_tax"]
    new_tax = new_ht["host_tax"]
    if old_tax < 0.01:
        if new_tax - old_tax <= 0.01:
            return []
        delta_pct = (new_tax - old_tax) * 100.0  # absolute points
    else:
        delta_pct = (new_tax / old_tax - 1.0) * 100.0
        if delta_pct <= regress_pct:
            return []
    return [
        {
            "stage": "host_tax",
            # shares, not seconds — old_p95/new_p95 keep the comparison
            # machinery uniform (the usage_waste_share convention)
            "old_p95": old_tax,
            "new_p95": new_tax,
            "old_share": old_tax,
            "new_share": new_tax,
            "delta_pct": delta_pct,
        }
    ]


def waterfall_report(spans: list[dict[str, Any]]) -> dict[str, Any]:
    """Per-tile lifecycle waterfall with EXACT integer-ns conservation.

    Each tile's spans (batched spans credit every tile in their
    ``batch`` attr) become an ordered sequence of stage segments on the
    span clock. Overlap is clipped with a cursor — a segment only
    credits the part past the furthest point already attributed — and
    gaps become explicit ``wait`` segments, so

        sum(stage_ns) + wait_ns == wall_ns   (exactly, per tile)

    where wall_ns is the tile's measured first-span-start to
    last-span-end. That telescoping identity is the acceptance check
    (`conserved` per tile, `all_conserved` for the run) — the PR-13
    analyzer's conservation rule at per-tile granularity."""
    segments: dict[int, list[tuple[int, int, str]]] = {}
    for span in spans:
        attrs = span.get("attrs") or {}
        tile_idx = attrs.get("tile_idx")
        stage = attrs.get("stage")
        start = span.get("start")
        duration = span.get("duration")
        if tile_idx is None or stage is None or start is None:
            continue
        if duration is None:
            continue
        try:
            start_ns = _to_ns(start)
            end_ns = start_ns + _to_ns(duration)
        except (TypeError, ValueError):
            continue
        for idx in attrs.get("batch") or [tile_idx]:
            segments.setdefault(int(idx), []).append(
                (start_ns, end_ns, str(stage))
            )
    tiles: dict[int, dict[str, Any]] = {}
    all_conserved = True
    for tile_idx in sorted(segments):
        segs = sorted(segments[tile_idx])
        first = segs[0][0]
        last = max(end for _start, end, _stage in segs)
        wall_ns = last - first
        stages: dict[str, int] = {}
        timeline: list[dict[str, Any]] = []
        wait_ns = 0
        cursor = first
        for start_ns, end_ns, stage in segs:
            if start_ns > cursor:
                gap = start_ns - cursor
                wait_ns += gap
                timeline.append(
                    {"stage": "wait", "start_ns": cursor, "ns": gap}
                )
                cursor = start_ns
            seg_start = max(cursor, start_ns)
            if end_ns > seg_start:
                credited = end_ns - seg_start
                stages[stage] = stages.get(stage, 0) + credited
                timeline.append(
                    {"stage": stage, "start_ns": seg_start, "ns": credited}
                )
                cursor = end_ns
        attributed = sum(stages.values()) + wait_ns
        conserved = attributed == wall_ns
        all_conserved = all_conserved and conserved
        tiles[tile_idx] = {
            "wall_ns": wall_ns,
            "wait_ns": wait_ns,
            "stages": stages,
            "timeline": timeline,
            "conserved": conserved,
        }
    return {"tiles": tiles, "all_conserved": all_conserved}


def render_waterfall(waterfall: dict[str, Any]) -> str:
    tiles = waterfall["tiles"]
    lines = [
        f"waterfall ({len(tiles)} tile(s), conservation "
        f"{'OK' if waterfall['all_conserved'] else 'VIOLATED'}):"
    ]
    for tile_idx, tile in tiles.items():
        flow = " -> ".join(
            f"{seg['stage']}({seg['ns'] / _NS:.4f}s)"
            for seg in tile["timeline"]
        )
        verdict = "" if tile["conserved"] else "  [NOT CONSERVED]"
        lines.append(
            f"  tile {tile_idx:>3}: wall {tile['wall_ns'] / _NS:.4f}s = "
            f"{flow}{verdict}"
        )
    return "\n".join(lines)


def usage_stats(spans: list[dict[str, Any]]) -> dict[str, Any] | None:
    """Chip-second attribution from the per-dispatch spans both
    execution tiers emit (``tile.dispatch`` with ``real``/``bucket``
    slot counts plus ``slot_jobs``/``slot_tenants`` breakdowns —
    graph/batch_executor.py and graph/tile_pipeline.py): each span's
    wall splits evenly across its bucket slots exactly like the live
    usage meter, so per-tenant/per-job shares and the waste share
    (padding + recompute slots) are reconstructable offline from a
    trace alone. ``recompute`` slots stay inside their job's slot count
    (the job caused the re-run) but count toward waste. None when no
    dispatch spans are present."""
    per_job: dict[str, float] = {}
    per_tenant: dict[str, float] = {}
    total = 0.0
    waste = 0.0
    dispatches = 0
    for span in spans:
        attrs = span.get("attrs") or {}
        if attrs.get("stage") != "dispatch":
            continue
        duration = span.get("duration")
        if duration is None:
            continue
        try:
            bucket = int(attrs.get("bucket", 0))
            real = int(attrs.get("real", 0) or 0)
            recompute = int(attrs.get("recompute", 0) or 0)
        except (TypeError, ValueError):
            continue
        if bucket <= 0:
            continue
        dispatches += 1
        share = float(duration) / bucket
        total += float(duration)
        waste += share * (max(0, bucket - real) + max(0, recompute))
        for job, n in (attrs.get("slot_jobs") or {}).items():
            try:
                per_job[str(job)] = per_job.get(str(job), 0.0) + share * int(n)
            except (TypeError, ValueError):
                continue
        for tenant, n in (attrs.get("slot_tenants") or {}).items():
            try:
                per_tenant[str(tenant)] = (
                    per_tenant.get(str(tenant), 0.0) + share * int(n)
                )
            except (TypeError, ValueError):
                continue
    if dispatches == 0 or total <= 0:
        return None
    return {
        "dispatches": dispatches,
        "total_s": total,
        "waste_s": waste,
        "waste_share": waste / total,
        "tenants": {
            t: {"chip_s": s, "share": s / total}
            for t, s in sorted(per_tenant.items())
        },
        "jobs": {
            j: {"chip_s": s, "share": s / total}
            for j, s in sorted(per_job.items())
        },
    }


def usage_regressions(
    old_usage: dict[str, Any] | None,
    new_usage: dict[str, Any] | None,
    regress_pct: float,
) -> list[dict[str, Any]]:
    """The --usage gate: waste share (padding + recompute fraction of
    dispatch chip time) growing by more than `regress_pct` percent
    relative fails --compare — device slots went back to burning
    wraparound padding or redundant recompute. Old waste below 1% is
    gated on absolute growth of more than one percentage point instead
    (relative growth on a near-zero base is noise — 0.99% -> 1.01%
    must pass, 0% -> 3% must fail)."""
    if not old_usage or not new_usage:
        return []
    old_share = old_usage["waste_share"]
    new_share = new_usage["waste_share"]
    if old_share < 0.01:
        if new_share - old_share <= 0.01:
            return []
        delta_pct = (new_share - old_share) * 100.0  # absolute points
    else:
        delta_pct = (new_share / old_share - 1.0) * 100.0
        if delta_pct <= regress_pct:
            return []
    return [
        {
            "stage": "usage_waste_share",
            # shares, not seconds — old_p95/new_p95 keep the comparison
            # machinery uniform (the critical_path convention)
            "old_p95": old_share,
            "new_p95": new_share,
            "old_share": old_share,
            "new_share": new_share,
            "delta_pct": delta_pct,
        }
    ]


def render_usage(usage: dict[str, Any]) -> str:
    lines = [
        "usage (chip-second attribution across "
        f"{usage['dispatches']} dispatch(es)): "
        f"{usage['total_s']:.4f}s total, waste share "
        f"{usage['waste_share'] * 100:.1f}%"
    ]
    for tenant, stats in usage["tenants"].items():
        lines.append(
            f"  tenant {tenant:24} {stats['chip_s']:>10.4f}s "
            f"({stats['share'] * 100:5.1f}%)"
        )
    for job, stats in usage["jobs"].items():
        lines.append(
            f"  job    {job:24} {stats['chip_s']:>10.4f}s "
            f"({stats['share'] * 100:5.1f}%)"
        )
    return "\n".join(lines)


def build_report(spans: list[dict[str, Any]]) -> dict[str, Any]:
    """Aggregate span durations per name → latency stats."""
    by_name: dict[str, list[float]] = {}
    unfinished = 0
    for span in spans:
        duration = span.get("duration")
        if duration is None:
            unfinished += 1
            continue
        by_name.setdefault(span["name"], []).append(float(duration))
    stages = {}
    for name, durations in sorted(by_name.items()):
        durations.sort()
        stages[name] = {
            "count": len(durations),
            "total": sum(durations),
            "mean": sum(durations) / len(durations),
            "p50": _percentile(durations, 0.50),
            "p95": _percentile(durations, 0.95),
            "p99": _percentile(durations, 0.99),
            "max": durations[-1],
        }
    return {
        "span_count": len(spans),
        "unfinished_spans": unfinished,
        "stages": stages,
        "queue_wait": queue_wait_stats(spans),
        "pipeline_overlap": pipeline_overlap_stats(spans),
        "batch_fill": batch_fill_stats(spans),
        "adapter": adapter_stats(spans),
        "cache": cache_stats(spans),
        "host_tax": host_tax_stats(spans),
    }


def tile_lifecycle(spans: list[dict[str, Any]]) -> dict[int, list[dict[str, Any]]]:
    """Group tile-stage spans by tile index, ordered by span start."""
    tiles: dict[int, list[dict[str, Any]]] = {}
    for span in spans:
        attrs = span.get("attrs") or {}
        tile_idx = attrs.get("tile_idx")
        stage = attrs.get("stage")
        if tile_idx is None or stage is None:
            continue
        # batched stages (pipelined grants) record one span covering
        # several tiles via the `batch` attr — credit each of them, or
        # the lifecycle of tiles 2..k in a batch would read incomplete
        for idx in attrs.get("batch") or [tile_idx]:
            tiles.setdefault(int(idx), []).append(
                {
                    "stage": stage,
                    "role": attrs.get("role", "?"),
                    "worker_id": attrs.get("worker_id"),
                    "start": span.get("start"),
                    "duration": span.get("duration"),
                    "status": span.get("status"),
                }
            )
    for stages in tiles.values():
        stages.sort(key=lambda s: (s["start"] is None, s["start"]))
    return dict(sorted(tiles.items()))


def incomplete_tiles(tiles: dict[int, list[dict[str, Any]]]) -> dict[int, str]:
    """Tiles whose recorded stages never completed: no participant
    sampled them, or the master never blended them (requeued tiles
    legitimately show extra abandoned attempts — one successful
    attempt closes the lifecycle)."""
    problems: dict[int, str] = {}
    for tile_idx, stages in tiles.items():
        seen: dict[str, set[str]] = {}
        for stage in stages:
            seen.setdefault(stage["role"], set()).add(stage["stage"])
        sampled = any(REQUIRED_ANY_ROLE in st for st in seen.values())
        blended = REQUIRED_MASTER in seen.get("master", set())
        cached = REQUIRED_CACHED in seen.get("master", set())
        if not (cached or (sampled and blended)):
            problems[tile_idx] = (
                "stages seen: "
                + "; ".join(
                    f"{role}={sorted(st)}" for role, st in sorted(seen.items())
                )
            )
    return problems


def compare_reports(
    old_report: dict[str, Any],
    new_report: dict[str, Any],
    regress_pct: float,
) -> list[dict[str, Any]]:
    """Per-stage p95 regressions of `new_report` vs `old_report`:
    stages present in BOTH whose new p95 exceeds the old by more than
    `regress_pct` percent. Stages that only exist on one side are
    skipped (new instrumentation is not a regression)."""
    regressions = []
    for name, new_stats in new_report["stages"].items():
        old_stats = old_report["stages"].get(name)
        if old_stats is None or old_stats["p95"] <= 0:
            continue
        delta_pct = (new_stats["p95"] / old_stats["p95"] - 1.0) * 100.0
        if delta_pct > regress_pct:
            regressions.append(
                {
                    "stage": name,
                    "old_p95": old_stats["p95"],
                    "new_p95": new_stats["p95"],
                    "delta_pct": delta_pct,
                }
            )
    # queue wait (admission→first pull) rides the same gate as a
    # pseudo-stage: a scheduler change that silently doubles time-to-
    # first-tile is exactly the regression this report exists to catch.
    old_wait = old_report.get("queue_wait")
    new_wait = new_report.get("queue_wait")
    if old_wait and new_wait and old_wait["p95"] > 0:
        delta_pct = (new_wait["p95"] / old_wait["p95"] - 1.0) * 100.0
        if delta_pct > regress_pct:
            regressions.append(
                {
                    "stage": "queue_wait",
                    "old_p95": old_wait["p95"],
                    "new_p95": new_wait["p95"],
                    "delta_pct": delta_pct,
                }
            )
    # pipeline overlap gates INVERTED: a DROP in the sample/IO overlap
    # fraction means the elastic pipeline lost concurrency (I/O time
    # moved back between device dispatches). delta_pct is the relative
    # drop so the same threshold applies.
    old_ov = old_report.get("pipeline_overlap")
    new_ov = new_report.get("pipeline_overlap")
    if old_ov and new_ov and old_ov["fraction"] > 0:
        drop_pct = (1.0 - new_ov["fraction"] / old_ov["fraction"]) * 100.0
        if drop_pct > regress_pct:
            regressions.append(
                {
                    "stage": "pipeline_overlap",
                    "old_p95": old_ov["fraction"],
                    "new_p95": new_ov["fraction"],
                    "delta_pct": drop_pct,
                }
            )
    # batch fill gates inverted too: a DROP in the cross-job fill
    # ratio means device slots went back to running wraparound padding
    # instead of other jobs' real tiles.
    old_bf = old_report.get("batch_fill")
    new_bf = new_report.get("batch_fill")
    if old_bf and new_bf and old_bf["fill"] > 0:
        drop_pct = (1.0 - new_bf["fill"] / old_bf["fill"]) * 100.0
        if drop_pct > regress_pct:
            regressions.append(
                {
                    "stage": "batch_fill",
                    "old_p95": old_bf["fill"],
                    "new_p95": new_bf["fill"],
                    "delta_pct": drop_pct,
                }
            )
    # adapter fill gates inverted like batch fill, but scoped to the
    # personalized batches: a DROP means adapter-wearing tiles stopped
    # sharing programs/batches (a signature or rank-bucket change that
    # splinters the segmented tier shows up exactly here).
    old_ad = old_report.get("adapter")
    new_ad = new_report.get("adapter")
    if old_ad and new_ad and old_ad["adapter_fill"] > 0:
        drop_pct = (
            1.0 - new_ad["adapter_fill"] / old_ad["adapter_fill"]
        ) * 100.0
        if drop_pct > regress_pct:
            regressions.append(
                {
                    "stage": "adapter_fill",
                    "old_p95": old_ad["adapter_fill"],
                    "new_p95": new_ad["adapter_fill"],
                    "delta_pct": drop_pct,
                }
            )
    # cache hit rate gates inverted too: a DROP means tiles the old
    # trace settled near-free from the content-addressed cache went
    # back to burning device slots (a key-schema change that silently
    # misses everything is exactly this regression).
    old_cache = old_report.get("cache")
    new_cache = new_report.get("cache")
    if old_cache and new_cache and old_cache["hit_rate"] > 0:
        drop_pct = (1.0 - new_cache["hit_rate"] / old_cache["hit_rate"]) * 100.0
        if drop_pct > regress_pct:
            regressions.append(
                {
                    "stage": "cache_hit_rate",
                    "old_p95": old_cache["hit_rate"],
                    "new_p95": new_cache["hit_rate"],
                    "delta_pct": drop_pct,
                }
            )
    # host tax gates on GROWTH: the device-resident PRs must show the
    # host share of every (host + device) nanosecond falling.
    regressions.extend(
        host_tax_regressions(
            old_report.get("host_tax"), new_report.get("host_tax"),
            regress_pct,
        )
    )
    return regressions


def render_comparison(
    regressions: list[dict[str, Any]], regress_pct: float
) -> str:
    if not regressions:
        return f"p95 comparison: no stage regressed more than {regress_pct:g}%"
    lines = [f"p95 REGRESSIONS (> {regress_pct:g}%):"]
    for item in regressions:
        if item["stage"] == "pipeline_overlap":
            lines.append(
                f"  {item['stage']:28} overlap {item['old_p95']:.3f} -> "
                f"{item['new_p95']:.3f} (-{item['delta_pct']:.1f}%)"
            )
            continue
        if item["stage"] == "batch_fill":
            lines.append(
                f"  {item['stage']:28} fill {item['old_p95']:.3f} -> "
                f"{item['new_p95']:.3f} (-{item['delta_pct']:.1f}%)"
            )
            continue
        if item["stage"] == "adapter_fill":
            lines.append(
                f"  {item['stage']:28} fill {item['old_p95']:.3f} -> "
                f"{item['new_p95']:.3f} (-{item['delta_pct']:.1f}%)"
            )
            continue
        if item["stage"] == "cache_hit_rate":
            lines.append(
                f"  {item['stage']:28} hit rate {item['old_p95']:.3f} -> "
                f"{item['new_p95']:.3f} (-{item['delta_pct']:.1f}%)"
            )
            continue
        if item["stage"] == "host_tax":
            # host SHARE of (host + device) time, unitless
            lines.append(
                f"  {item['stage']:28} tax {item['old_p95']:.3f} -> "
                f"{item['new_p95']:.3f} (+{item['delta_pct']:.1f}%)"
            )
            continue
        if item["stage"] == "usage_waste_share":
            # waste SHARES (unitless fractions of dispatch chip time)
            lines.append(
                f"  {item['stage']:28} share {item['old_p95']:.3f} -> "
                f"{item['new_p95']:.3f} (+{item['delta_pct']:.1f}%)"
            )
            continue
        if item["stage"].startswith("critical_path:"):
            # wall-time SHARES (unitless fractions), not p95 seconds
            lines.append(
                f"  {item['stage']:28} share {item['old_p95']:.3f} -> "
                f"{item['new_p95']:.3f} (+{item['delta_pct']:.1f}%)"
            )
            continue
        lines.append(
            f"  {item['stage']:28} {item['old_p95']:.4f}s -> "
            f"{item['new_p95']:.4f}s (+{item['delta_pct']:.1f}%)"
        )
    return "\n".join(lines)


def critical_path_report(spans: list[dict[str, Any]]) -> dict[str, Any]:
    """Wall-time stage attribution per job, reusing the offline
    incident analyzer (scripts/incident_report.py) — the same code
    path that reads a debug bundle with the process dead."""
    import incident_report

    return incident_report.critical_path(spans)


def critical_path_regressions(
    old_cp: dict[str, Any] | None,
    new_cp: dict[str, Any] | None,
    regress_pct: float,
) -> list[dict[str, Any]]:
    """Aggregate stage-share regressions: a stage whose share of total
    wall time grew by more than `regress_pct` percent (relative) —
    e.g. grant RTT creeping from 10% to 15% of wall — flagged under
    the same gate as the p95 stages. Stages below a 5% old share are
    skipped (noise on tiny denominators is not a regression)."""
    old_agg = (old_cp or {}).get("aggregate")
    new_agg = (new_cp or {}).get("aggregate")
    if not old_agg or not new_agg:
        return []
    regressions = []
    for name, new_stage in new_agg["stages"].items():
        old_stage = old_agg["stages"].get(name)
        if not old_stage or old_stage["share"] < 0.05:
            continue
        delta_pct = (new_stage["share"] / old_stage["share"] - 1.0) * 100.0
        if delta_pct > regress_pct:
            regressions.append(
                {
                    "stage": f"critical_path:{name}",
                    # shares, not seconds — old_p95/new_p95 keep the
                    # comparison machinery uniform, old_share/new_share
                    # carry the honest unit for JSON consumers, and
                    # render_comparison has a dedicated share branch
                    "old_p95": old_stage["share"],
                    "new_p95": new_stage["share"],
                    "old_share": old_stage["share"],
                    "new_share": new_stage["share"],
                    "delta_pct": delta_pct,
                }
            )
    return regressions


def render_critical_path(cp: dict[str, Any]) -> str:
    lines = ["critical path (dominant-stage share per job):"]
    for trace_id, job in cp["jobs"].items():
        lines.append(
            f"  {trace_id[:40]:40} wall {job['wall_s']:.4f}s  "
            f"dominant {job['dominant']} "
            f"({job['dominant_share'] * 100:.1f}%)"
        )
    aggregate = cp.get("aggregate")
    if aggregate:
        lines.append(
            f"  aggregate: dominant {aggregate['dominant']} "
            f"({aggregate['dominant_share'] * 100:.1f}% of "
            f"{aggregate['wall_s']:.4f}s)"
        )
    return "\n".join(lines)


def parse_slo_budgets(specs: list[str]) -> dict[str, float]:
    """``stage=seconds`` pairs (stage = a span name, or `queue_wait`)."""
    budgets: dict[str, float] = {}
    for spec in specs:
        stage, sep, value = spec.partition("=")
        if not sep:
            raise ValueError(f"--slo expects stage=seconds, got {spec!r}")
        budgets[stage.strip()] = float(value)
    return budgets


def slo_violations(
    report: dict[str, Any], budgets: dict[str, float]
) -> list[dict[str, Any]]:
    """Offline counterpart of the live burn-rate engine
    (docs/observability.md §SLO): check each budgeted stage's p95 in
    this trace against its target. A stage the trace never recorded is
    reported as `missing` (a lifecycle that skipped the instrumented
    path entirely should not pass silently)."""
    out: list[dict[str, Any]] = []
    for stage, budget in sorted(budgets.items()):
        stats = (
            report.get("queue_wait")
            if stage == "queue_wait"
            else report["stages"].get(stage)
        )
        if not stats:
            out.append({"stage": stage, "budget": budget, "missing": True})
        elif stats["p95"] > budget:
            out.append(
                {
                    "stage": stage,
                    "budget": budget,
                    "p95": stats["p95"],
                    "missing": False,
                }
            )
    return out


def render_slo(violations: list[dict[str, Any]]) -> str:
    if not violations:
        return "SLO check: every budgeted stage p95 within target"
    lines = ["SLO VIOLATIONS:"]
    for item in violations:
        if item["missing"]:
            lines.append(
                f"  {item['stage']:28} no samples in trace "
                f"(budget {item['budget']:g}s)"
            )
        else:
            lines.append(
                f"  {item['stage']:28} p95 {item['p95']:.4f}s > "
                f"budget {item['budget']:g}s"
            )
    return "\n".join(lines)


def render_text(report: dict[str, Any], tiles, problems) -> str:
    lines = []
    lines.append(
        f"spans: {report['span_count']} "
        f"(unfinished: {report['unfinished_spans']})"
    )
    lines.append("")
    header = (
        f"{'span':28} {'count':>6} {'total_s':>10} {'mean_s':>10} "
        f"{'p50_s':>10} {'p95_s':>10} {'p99_s':>10} {'max_s':>10}"
    )
    lines.append(header)
    lines.append("-" * len(header))
    for name, stats in report["stages"].items():
        lines.append(
            f"{name:28} {stats['count']:>6} {stats['total']:>10.4f} "
            f"{stats['mean']:>10.4f} {stats['p50']:>10.4f} "
            f"{stats['p95']:>10.4f} {stats['p99']:>10.4f} "
            f"{stats['max']:>10.4f}"
        )
    wait = report.get("queue_wait")
    if wait:
        lines.append("")
        lines.append(
            "queue wait (admission -> first pull): "
            f"count={wait['count']} mean={wait['mean']:.4f}s "
            f"p50={wait['p50']:.4f}s p95={wait['p95']:.4f}s "
            f"max={wait['max']:.4f}s"
        )
    overlap = report.get("pipeline_overlap")
    if overlap:
        lines.append("")
        lines.append(
            "pipeline overlap (sample wall concurrent with encode/"
            f"submit): {overlap['overlapped']:.4f}s of "
            f"{overlap['sample_wall']:.4f}s "
            f"(fraction {overlap['fraction']:.3f})"
        )
    fill = report.get("batch_fill")
    if fill:
        lines.append("")
        lines.append(
            "batch fill (real tiles / device slots across "
            f"{fill['dispatches']} dispatch(es), "
            f"{fill['cross_job_dispatches']} cross-job): "
            f"{fill['real_tiles']}/{fill['slots']} "
            f"(fill {fill['fill']:.3f})"
        )
    adapter = report.get("adapter")
    if adapter:
        lines.append("")
        lines.append(
            "adapter plane "
            f"({adapter['adapter_dispatches']}/{adapter['dispatches']} "
            f"dispatch(es) personalized, share "
            f"{adapter['dispatch_share']:.3f}): "
            f"{adapter['adapter_real_tiles']}/{adapter['adapter_slots']} "
            f"slots real (fill {adapter['adapter_fill']:.3f})"
        )
    cache = report.get("cache")
    if cache:
        lines.append("")
        lines.append(
            f"tile cache ({cache['probes']} probe(s)): "
            f"{cache['hits']} settled from cache vs "
            f"{cache['dispatched_tiles']} dispatched "
            f"(hit rate {cache['hit_rate']:.3f})"
        )
    host_tax = report.get("host_tax")
    if host_tax:
        lines.append("")
        lines.append(
            f"host tax ({host_tax['dispatches']} dispatch(es), "
            f"{host_tax['device_dispatches']} on device): "
            f"device {host_tax['device_ns'] / _NS:.4f}s, host "
            f"{(host_tax['host_ns'] + host_tax['eager_ns']) / _NS:.4f}s "
            f"(tax {host_tax['host_tax']:.3f})"
        )
    if tiles:
        lines.append("")
        lines.append(f"tile lifecycles: {len(tiles)} tile(s)")
        for tile_idx, stages in tiles.items():
            flow = " -> ".join(
                f"{s['stage']}[{s['role']}"
                + (f":{s['worker_id']}" if s.get("worker_id") else "")
                + "]"
                for s in stages
            )
            lines.append(f"  tile {tile_idx:>3}: {flow}")
        if problems:
            lines.append("")
            lines.append(f"INCOMPLETE tiles ({len(problems)}):")
            for tile_idx, detail in problems.items():
                lines.append(f"  tile {tile_idx}: {detail}")
        else:
            lines.append("  all tile lifecycles complete")
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("path", help="trace JSONL file (one span per line)")
    parser.add_argument(
        "--trace", default=None, help="only spans of this trace id"
    )
    parser.add_argument(
        "--json", action="store_true", help="machine-readable output"
    )
    parser.add_argument(
        "--compare",
        default=None,
        metavar="OLD.jsonl",
        help="baseline trace JSONL; exit 3 on per-stage p95 regression",
    )
    parser.add_argument(
        "--regress-pct",
        type=float,
        default=25.0,
        help="p95 regression threshold in percent for --compare (default 25)",
    )
    parser.add_argument(
        "--critical-path",
        action="store_true",
        help="attribute each job's wall time across queue-wait/grant-"
        "RTT/sample/encode-submit/blend (scripts/incident_report.py "
        "analyzer) and name the dominant stage; with --compare, "
        "aggregate stage-share regressions join the exit-3 gate",
    )
    parser.add_argument(
        "--usage",
        action="store_true",
        help="chip-second attribution from tile.dispatch spans: "
        "per-tenant chip-second shares, per-job shares, and the waste "
        "share (padding + recompute slots); with --compare, waste-share "
        "growth beyond --regress-pct joins the exit-3 gate",
    )
    parser.add_argument(
        "--waterfall",
        action="store_true",
        help="per-tile lifecycle waterfall: ordered stage segments + "
        "explicit waits on the span clock, with EXACT integer-ns "
        "conservation (stage sums + waits == tile wall); exit 5 when "
        "any tile's attribution fails to conserve",
    )
    parser.add_argument(
        "--slo",
        action="append",
        default=[],
        metavar="STAGE=SECONDS",
        help="p95 budget per stage (repeatable; stage may be `queue_wait`); "
        "exit 4 on violation — the offline counterpart of the live "
        "burn-rate SLO engine. A --compare regression takes exit-code "
        "precedence (3); both verdicts are always printed/serialized",
    )
    args = parser.parse_args(argv)
    try:
        slo_budgets = parse_slo_budgets(args.slo)
    except ValueError as exc:
        print(str(exc), file=sys.stderr)
        return 1

    try:
        spans = load_spans(args.path)
    except OSError as exc:
        print(f"cannot read {args.path}: {exc}", file=sys.stderr)
        return 1
    if args.trace:
        spans = [s for s in spans if s.get("trace_id") == args.trace]
    if not spans:
        print("no spans found", file=sys.stderr)
        return 1
    report = build_report(spans)
    tiles = tile_lifecycle(spans)
    problems = incomplete_tiles(tiles)

    critical = critical_path_report(spans) if args.critical_path else None
    usage = usage_stats(spans) if args.usage else None
    waterfall = waterfall_report(spans) if args.waterfall else None

    regressions = None
    if args.compare:
        try:
            old_spans = load_spans(args.compare)
        except OSError as exc:
            print(f"cannot read {args.compare}: {exc}", file=sys.stderr)
            return 1
        regressions = compare_reports(
            build_report(old_spans), report, args.regress_pct
        )
        if critical is not None:
            regressions.extend(
                critical_path_regressions(
                    critical_path_report(old_spans), critical,
                    args.regress_pct,
                )
            )
        if args.usage:
            regressions.extend(
                usage_regressions(
                    usage_stats(old_spans), usage, args.regress_pct
                )
            )

    violations = slo_violations(report, slo_budgets) if slo_budgets else None

    if args.json:
        payload = {
            "report": report,
            "tiles": {str(k): v for k, v in tiles.items()},
            "incomplete": {str(k): v for k, v in problems.items()},
        }
        if critical is not None:
            payload["critical_path"] = critical
        if usage is not None:
            payload["usage"] = usage
        if waterfall is not None:
            payload["waterfall"] = {
                "all_conserved": waterfall["all_conserved"],
                "tiles": {
                    str(k): v for k, v in waterfall["tiles"].items()
                },
            }
        if regressions is not None:
            payload["regressions"] = regressions
        if violations is not None:
            payload["slo_violations"] = violations
        print(json.dumps(payload, indent=2, sort_keys=True))
    else:
        print(render_text(report, tiles, problems))
        if critical is not None:
            print()
            print(render_critical_path(critical))
        if usage is not None:
            print()
            print(render_usage(usage))
        if waterfall is not None:
            print()
            print(render_waterfall(waterfall))
        if regressions is not None:
            print()
            print(render_comparison(regressions, args.regress_pct))
        if violations is not None:
            print()
            print(render_slo(violations))
    if regressions:
        return 3
    if violations:
        return 4
    if waterfall is not None and not waterfall["all_conserved"]:
        return 5
    return 2 if problems else 0


if __name__ == "__main__":
    raise SystemExit(main())
