#!/usr/bin/env python3
"""Failover soak: N kill-promote-kill-back cycles + the push-vs-poll
grant dispatch smoke.

Two phases (CI job `failover-soak` runs this and uploads the JSON
report as an artifact):

1. **failover cycles** — `--cycles` in-process kill-the-active-master
   scenarios (resilience/chaos.run_chaos_failover), rotating through
   distinct kill points (after a pull, after a partial submit, inside
   the snapshot cadence) and alternating push-mode grants on and off.
   All cycles share ONE journal directory, so each promoted master is
   the active the NEXT cycle kills — the lease epoch must climb
   strictly across the whole ladder (the kill-promote-kill-back
   property). Every cycle must (a) actually fire its crash, (b)
   promote the standby without a process restart, (c) produce a canvas
   bit-identical to the uninterrupted baseline, and (d) prove fencing:
   the zombie's journal append raises, the promoted store rejects
   stale-epoch RPCs, and neither journals a single record.

2. **grant A/B smoke** — the push-vs-poll grant dispatch measurement
   (`measure_grant_ab`) over the real HTTP surface (wave-released
   grants): push mode must land a lower mean grant RTT and fewer idle
   poll requests than pull mode.

    python scripts/failover_soak.py [--out failover_soak.json]
        [--cycles 6] [--skip-grant-ab]
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

# Rotating kill points. The master always performs at least two pulls
# (its empty_pulls<2 drain loop) and the store-side fault fires at the
# RPC boundary regardless of queue state, so every plan is guaranteed
# to fire on every run. snapshot_every=1 on the third plan lands the
# crash inside the snapshot cadence (a snapshot precedes every append).
KILL_POINTS = [
    ("after_pull", "crash@store:pull:master#2", 4),
    ("after_partial_submit",
     "latency(1.0)@store:pull:w1#1;latency(1.0)@store:pull:w2#1;"
     "crash@store:submit:master#1", 4),
    ("during_snapshot", "crash@store:pull:master#3", 1),
]


def run_failover_cycles(cycles: int) -> dict:
    import numpy as np

    from comfyui_distributed_tpu.resilience.chaos import (
        run_chaos_failover,
        run_chaos_usdu,
    )

    baseline = run_chaos_usdu(seed=SEED).output
    results = []
    last_epoch = 0
    with tempfile.TemporaryDirectory(prefix="cdt-failover-soak-") as journal_dir:
        for cycle in range(cycles):
            name, plan, snapshot_every = KILL_POINTS[cycle % len(KILL_POINTS)]
            push = cycle % 2 == 1
            started = time.perf_counter()
            entry = {
                "cycle": cycle,
                "kill_point": name,
                "push_grants": push,
            }
            try:
                result = run_chaos_failover(
                    seed=SEED,
                    crash_plan=plan,
                    journal_dir=journal_dir,
                    snapshot_every=snapshot_every,
                    push_grants=push,
                    job_id=f"soak-failover-{cycle}",
                )
                identical = bool(np.array_equal(baseline, result.output))
                epoch_climbed = result.epochs[1] > max(
                    result.epochs[0], last_epoch
                )
                entry.update(
                    {
                        "crash_fired": "crash" in result.fired_kinds(),
                        "epochs": list(result.epochs),
                        "epoch_climbed": epoch_climbed,
                        "bit_identical": identical,
                        "zombie_fenced": result.zombie_fenced,
                        "stale_pull_rejected": result.stale_pull_rejected,
                        "stale_submit_rejected": result.stale_submit_rejected,
                        "zombie_journaled_records":
                            result.zombie_journaled_records,
                        "tasks_requeued": result.report["tasks_requeued"],
                        "tasks_restored": result.report["tasks_restored"],
                        "repointed_workers": result.repointed_workers,
                        "seconds": round(time.perf_counter() - started, 2),
                    }
                )
                entry["ok"] = (
                    entry["crash_fired"]
                    and epoch_climbed
                    and identical
                    and result.zombie_fenced
                    and result.stale_pull_rejected
                    and result.stale_submit_rejected
                    and result.zombie_journaled_records == 0
                )
                last_epoch = result.epochs[1]
            except Exception as exc:  # noqa: BLE001 - reported per cycle
                entry.update({"ok": False, "error": f"{type(exc).__name__}: {exc}"})
            results.append(entry)
            status = "ok" if entry["ok"] else "FAIL"
            print(
                f"cycle {cycle} [{name}, push={push}]: {status} "
                f"(epochs {entry.get('epochs')})"
            )
    return {
        "ok": all(r["ok"] for r in results),
        "cycles": cycles,
        "final_epoch": last_epoch,
        "results": results,
    }


def measure_grant_ab(
    waves: int = 6,
    wave_tiles: int = 2,
    gap_s: float = 0.6,
    poll_s: float = 0.1,
) -> dict:
    """Push-vs-poll grant dispatch A/B over the REAL HTTP surface
    (needs no accelerator). One mode = one DistributedServer
    on a loopback port with a tile job whose grants are released in
    timed waves (the requeue/speculation shape that refills a pending
    queue mid-job):

    - **pull** — the classic protocol: the client re-polls
      request_image, each empty answer held QUEUE_POLL_INTERVAL
      server-side then paced poll_s client-side, so a wave landing
      between polls waits out the quantization;
    - **push** — the client parks on the /distributed/events WebSocket
      and pulls the instant a grant_available frame lands (push carries
      availability, never assignment — the pull RPC still transfers the
      grant, so placement sizing and fencing are identical).

    Grant RTT = release instant → client holds the tile. Idle polls =
    request_image answers that carried no work. Host-clock readings
    over loopback on whatever platform runs the soak: a protocol
    comparison, not a device metric."""
    import asyncio
    import math
    import socket
    import statistics

    import aiohttp

    from comfyui_distributed_tpu.api.server import DistributedServer

    total = waves * wave_tiles
    job_id = "grant-ab"

    async def run_mode(push: bool) -> dict:
        with socket.socket() as sock:
            sock.bind(("127.0.0.1", 0))
            port = sock.getsockname()[1]
        server = DistributedServer(port=port, is_worker=False)
        await server.start()
        stats = {"rtts": [], "idle_polls": 0, "requests": 0}
        try:
            store = server.job_store
            # the A/B flips the push publisher directly (start() wires
            # it from CDT_PUSH_GRANTS; both arms must run in-process)
            store.grant_notifier = (
                server.scheduler.placement.notify_grants if push else None
            )
            await store.init_tile_job(job_id, list(range(total)))
            claimed = []
            for _ in range(total):
                tid = await store.pull_task(job_id, "holder", timeout=0.05)
                if tid is not None:
                    claimed.append(tid)
            release_at: dict[int, float] = {}

            async def producer():
                for wave in range(waves):
                    await asyncio.sleep(gap_s)
                    batch = claimed[wave * wave_tiles : (wave + 1) * wave_tiles]
                    now = time.perf_counter()
                    for tid in batch:
                        release_at[tid] = now
                    await store.release_tasks(job_id, "holder", batch)

            url = f"http://127.0.0.1:{port}/distributed/request_image"

            async def pull_once(session) -> int | None:
                async with session.post(
                    url, json={"job_id": job_id, "worker_id": "ab-worker"}
                ) as resp:
                    out = await resp.json()
                stats["requests"] += 1
                tid = out.get("tile_idx")
                if tid is None:
                    stats["idle_polls"] += 1
                    return None
                stats["rtts"].append(time.perf_counter() - release_at[int(tid)])
                return int(tid)

            async def pull_client(session):
                got = 0
                while got < total:
                    tid = await pull_once(session)
                    if tid is None:
                        await asyncio.sleep(poll_s)
                    else:
                        got += 1

            async def push_client(session):
                got = 0
                ws_url = (
                    f"http://127.0.0.1:{port}/distributed/events"
                    "?types=grant_available"
                )
                async with session.ws_connect(ws_url) as ws:
                    while got < total:
                        msg = await asyncio.wait_for(ws.receive(), timeout=15)
                        if msg.type != aiohttp.WSMsgType.TEXT:
                            break
                        if json.loads(msg.data).get("type") != "grant_available":
                            continue  # hello frame
                        # drain everything the push announced, then
                        # park on the socket again (ONE empty pull ends
                        # the drain — that is push mode's whole idle
                        # request budget)
                        while got < total:
                            tid = await pull_once(session)
                            if tid is None:
                                break
                            got += 1

            producer_task = asyncio.create_task(producer())
            async with aiohttp.ClientSession() as session:
                await asyncio.wait_for(
                    (push_client if push else pull_client)(session),
                    timeout=waves * gap_s + 30,
                )
            await producer_task
            await store.cleanup_tile_job(job_id)
        finally:
            await server.stop()
        rtts = stats["rtts"]
        return {
            "grant_rtt_ms_mean": round(1e3 * statistics.fmean(rtts), 2),
            "grant_rtt_ms_p95": round(
                1e3 * sorted(rtts)[max(0, math.ceil(len(rtts) * 0.95) - 1)], 2
            ),
            "grants": len(rtts),
            "idle_polls": stats["idle_polls"],
            "requests": stats["requests"],
        }

    async def run_both() -> dict:
        pull = await run_mode(push=False)
        push = await run_mode(push=True)
        return {
            "pull": pull,
            "push": push,
            "rtt_speedup": round(
                pull["grant_rtt_ms_mean"] / max(push["grant_rtt_ms_mean"], 1e-6),
                2,
            ),
            "idle_poll_ratio": round(
                pull["idle_polls"] / max(push["idle_polls"], 1), 2
            ),
            "waves": waves,
            "wave_tiles": wave_tiles,
            "gap_s": gap_s,
            "poll_s": poll_s,
        }

    previous_watchdog = os.environ.get("CDT_WATCHDOG")
    os.environ["CDT_WATCHDOG"] = "0"  # no speculation over the held grants
    try:
        return asyncio.run(run_both())
    finally:
        if previous_watchdog is None:
            os.environ.pop("CDT_WATCHDOG", None)
        else:
            os.environ["CDT_WATCHDOG"] = previous_watchdog


def run_grant_ab() -> dict:
    try:
        ab = measure_grant_ab()
    except Exception as exc:  # noqa: BLE001 - reported as a failed phase
        return {"ok": False, "error": f"{type(exc).__name__}: {exc}"}
    ok = (
        ab["push"]["grant_rtt_ms_mean"] < ab["pull"]["grant_rtt_ms_mean"]
        and ab["push"]["idle_polls"] <= ab["pull"]["idle_polls"]
    )
    return {"ok": ok, **ab}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--out", default="failover_soak.json")
    parser.add_argument("--cycles", type=int, default=6)
    parser.add_argument(
        "--skip-grant-ab", action="store_true",
        help="failover cycles only (fast smoke)",
    )
    args = parser.parse_args(argv)

    cycles = run_failover_cycles(args.cycles)
    grant_ab = (
        {"ok": True, "skipped": True}
        if args.skip_grant_ab
        else run_grant_ab()
    )
    report = {
        "ok": cycles["ok"] and grant_ab["ok"],
        "failover_cycles": cycles,
        "grant_ab": grant_ab,
    }
    with open(args.out, "w", encoding="utf-8") as fh:
        json.dump(report, fh, indent=2, sort_keys=True)
    passed = sum(1 for r in cycles["results"] if r.get("ok"))
    print(
        f"failover cycles: {passed}/{cycles['cycles']} promoted "
        f"bit-identical with fencing (final epoch "
        f"{cycles['final_epoch']}) -> {'OK' if cycles['ok'] else 'FAIL'}"
    )
    if not args.skip_grant_ab:
        if grant_ab["ok"]:
            print(
                f"grant A/B: push {grant_ab['push']['grant_rtt_ms_mean']}ms "
                f"vs pull {grant_ab['pull']['grant_rtt_ms_mean']}ms mean RTT "
                f"({grant_ab['rtt_speedup']}x), idle polls "
                f"{grant_ab['push']['idle_polls']} vs "
                f"{grant_ab['pull']['idle_polls']} -> OK"
            )
        else:
            print(f"grant A/B FAILED: {grant_ab}")
    print(f"report written to {args.out}")
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
