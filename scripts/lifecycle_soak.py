#!/usr/bin/env python3
"""Lifecycle soak: concurrent-job cancels, a poison tile, and an
overload burst — the CI job for the request-lifecycle armor (ISSUE 10).

Phases (CI job `lifecycle-soak` runs this and uploads the JSON report
as an artifact):

1. **cancel cycles** — `--cycles` cancel-mid-job chaos runs
   (resilience/chaos.run_chaos_cancel) with the write-ahead journal
   attached and a live standby replica teed in. Every cycle must (a)
   settle the master with a terminal JobCancelled, (b) balance the
   refund accounting — zero leaked in-flight assignments the instant
   the cancel returns, (c) round-trip the journal (terminal drained
   state at cancel time, replica parity, idempotent replay), and (d)
   report the cancel→refund latency (`cancel_latency_ms`, the
   reclaim-speed number).

2. **poison tile** — one injected payload that crashes three
   consecutive workers (each crash opening that worker's breaker at
   the harshest failure_threshold=1 setting). The tile must be
   quarantined after CDT_TILE_MAX_ATTEMPTS, the job must complete
   DEGRADED with every unaffected tile bit-identical to a clean run,
   and the pardon must leave no worker quarantined for the poison.

3. **overload burst** — a synthetic flood drives queue-wait p95 far
   over threshold on a fake clock: the brownout controller must shed
   the low-priority lanes (429s recorded in cdt_shed_total) while the
   premium lane keeps admitting with zero-wait grants.

4. **bystander invariance** — an undisturbed chaos run before and
   after the whole soak must produce bit-identical canvases: the
   armor may change WHO finishes and WHEN jobs die, never WHAT
   surviving jobs render.

    python scripts/lifecycle_soak.py [--out lifecycle_soak.json]
        [--cycles 4]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

SEED = 11


def run_cancel_cycles(cycles: int) -> dict:
    from comfyui_distributed_tpu.resilience.chaos import run_chaos_cancel

    results = []
    for cycle in range(cycles):
        started = time.perf_counter()
        entry: dict = {"cycle": cycle}
        try:
            with tempfile.TemporaryDirectory(
                prefix="cdt-lifecycle-soak-"
            ) as journal_dir:
                r = run_chaos_cancel(
                    seed=SEED,
                    journal_dir=journal_dir,
                    job_id=f"soak-cancel-{cycle}",
                    cancel_after=1 + (cycle % 3),
                )
            refunded = (
                r.accounting.get("pending_refunded", 0)
                + r.accounting.get("in_flight_refunded", 0)
            )
            entry.update(
                {
                    "raised": r.raised,
                    "refunded": refunded,
                    "completed_before_cancel": r.completed_before_cancel,
                    "leaked_in_flight": r.stats_after.get("in_flight", -1),
                    "leaked_pending": r.stats_after.get("queue_depth", -1),
                    "terminal_state": bool(
                        r.state_after_cancel.get("cancelled")
                        and r.state_after_cancel.get("pending") == []
                        and r.state_after_cancel.get("assigned") == {}
                    ),
                    "replica_saw_cancel": r.replica_saw_cancel,
                    "idempotent_replay": r.idempotent_replay,
                    "cancel_latency_ms": round(r.cancel_latency_ms, 3),
                    "seconds": round(time.perf_counter() - started, 2),
                }
            )
            entry["ok"] = (
                r.raised == "JobCancelled"
                and refunded > 0
                and entry["leaked_in_flight"] == 0
                and entry["leaked_pending"] == 0
                and entry["terminal_state"]
                and r.replica_saw_cancel
                and r.idempotent_replay
            )
        except Exception as exc:  # noqa: BLE001 - reported per cycle
            entry.update({"ok": False, "error": f"{type(exc).__name__}: {exc}"})
        results.append(entry)
    latencies = [
        e["cancel_latency_ms"] for e in results if "cancel_latency_ms" in e
    ]
    return {
        "cycles": results,
        "ok": all(e["ok"] for e in results),
        "cancel_latency_ms_mean": (
            round(sum(latencies) / len(latencies), 3) if latencies else None
        ),
    }


def run_poison_phase() -> dict:
    import numpy as np

    from comfyui_distributed_tpu.resilience.chaos import (
        run_chaos_poison,
        run_chaos_usdu,
    )

    entry: dict = {}
    try:
        with tempfile.TemporaryDirectory(
            prefix="cdt-lifecycle-poison-"
        ) as journal_dir:
            r = run_chaos_poison(seed=SEED, journal_dir=journal_dir)
        baseline = run_chaos_usdu(
            seed=SEED, image_hw=(96, 96), tile=48, padding=16,
            job_id="soak-poison-baseline",
        )
        y, x, th, tw = r.poison_rect
        mask = np.ones(r.output.shape, bool)
        mask[:, y : y + th, x : x + tw, :] = False
        unaffected_identical = bool(
            np.array_equal(r.output[mask], baseline.output[mask])
        )
        entry.update(
            {
                "crashed_workers": r.crashed_workers,
                "attempts_on_poison": r.attempts.get(r.poison_tile),
                "quarantined": r.quarantined,
                "pardons": r.pardons,
                "workers_healthy_after": all(
                    s["state"] == "healthy" for s in r.health_after.values()
                ),
                "unaffected_tiles_bit_identical": unaffected_identical,
            }
        )
        entry["ok"] = (
            len(r.crashed_workers) == 3
            and r.poison_tile in r.quarantined
            and entry["workers_healthy_after"]
            and unaffected_identical
        )
    except Exception as exc:  # noqa: BLE001 - reported
        entry.update({"ok": False, "error": f"{type(exc).__name__}: {exc}"})
    return entry


def run_overload_burst() -> dict:
    from comfyui_distributed_tpu.scheduler import (
        BrownoutController,
        SchedulerControl,
        SchedulerOverloaded,
    )
    from comfyui_distributed_tpu.scheduler.queue import AdmissionQueue

    clock_now = [0.0]
    clock = lambda: clock_now[0]  # noqa: E731
    queue = AdmissionQueue(
        lanes=[("interactive", 64), ("batch", 64), ("background", 64)],
        max_active=2,
        clock=clock,
    )
    brownout = BrownoutController(
        queue.lane_order, wait_p95_threshold=1.0,
        journal_p95_threshold=0.25, cooldown=0.5, clock=clock,
    )
    control = SchedulerControl(queue=queue, brownout=brownout, clock=clock)

    class Payload:
        def __init__(self, lane):
            self.lane = lane
            self.tenant = "soak"
            self.trace_id = None
            self.deadline_s = None
            self.extra = {}

    # the burst: flood queue waits far past threshold, then step time
    # (the overload keeps feeding samples each step — premium grants
    # never stop — so the starvation decay stays out of the picture)
    for _ in range(64):
        brownout.note_queue_wait(30.0)
    shed = {"background": 0, "batch": 0}
    admitted_premium = 0
    premium_waits = []
    for step in range(8):
        clock_now[0] = (step + 1) * 1.0
        for _ in range(4):
            brownout.note_queue_wait(30.0)
        for lane in ("background", "batch"):
            try:
                control.submit_payload(Payload(lane))
            except SchedulerOverloaded:
                shed[lane] += 1
        ticket = control.submit_payload(Payload("interactive"))
        admitted_premium += 1
        if ticket.queue_wait_seconds is not None:
            premium_waits.append(ticket.queue_wait_seconds)
        queue.release(ticket) if ticket.state == "granted" else None
    entry = {
        "shed": shed,
        "shed_counts": dict(brownout.shed_counts),
        "level": brownout.level,
        "admitted_premium": admitted_premium,
        "premium_wait_max": max(premium_waits) if premium_waits else None,
    }
    entry["ok"] = (
        shed["background"] > 0
        and brownout.level >= 1
        and admitted_premium == 8
        and (not premium_waits or max(premium_waits) <= 1.0)
    )
    return entry


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", default="lifecycle_soak.json")
    parser.add_argument("--cycles", type=int, default=4)
    args = parser.parse_args()

    import numpy as np

    from comfyui_distributed_tpu.resilience.chaos import run_chaos_usdu

    started = time.perf_counter()
    bystander_before = run_chaos_usdu(seed=7, job_id="soak-bystander-before")

    report = {
        "cancel": run_cancel_cycles(args.cycles),
        "poison": run_poison_phase(),
        "overload": run_overload_burst(),
    }

    bystander_after = run_chaos_usdu(seed=7, job_id="soak-bystander-after")
    report["bystander_bit_identical"] = bool(
        np.array_equal(bystander_before.output, bystander_after.output)
    )
    report["seconds"] = round(time.perf_counter() - started, 1)
    report["ok"] = (
        report["cancel"]["ok"]
        and report["poison"]["ok"]
        and report["overload"]["ok"]
        and report["bystander_bit_identical"]
    )

    with open(args.out, "w") as fh:
        json.dump(report, fh, indent=2, sort_keys=True)
    print(json.dumps(report, indent=2, sort_keys=True))
    if not report["ok"]:
        print("lifecycle soak FAILED", file=sys.stderr)
        return 1
    print(
        f"lifecycle soak OK: {args.cycles} cancel cycle(s), poison "
        f"quarantine, overload burst in {report['seconds']}s"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
