#!/usr/bin/env python3
"""By hand: what the program's own span tree says about one cell's
requests, which a run of run.py does not keep.

    python3 benchmark/span_tree.py --workload <cell> --seed <n> [--burst 3]

Sets the cell up as run.py does, then prints (1) the first request's
tree, node by node, with what JAX traced, lowered, built or fetched in
each: the split of set-up; (2) for `--burst` requests sent at once, the
client's latency beside the spans that cover it, and what no span
covers. Writes both to <out>/span_tree.json. Not part of a measured run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as harness  # noqa: E402

PROGRAM_WORK = ("compiles", "compile_s", "cache_hits", "cache_misses",
                "trace_s", "lower_s", "cache_fetch_s")
COVERING = ("sched.wait", "queue_orchestration", "prompt_queue.wait", "execute_prompt")


def tree_of(run, record) -> list:
    return run.conn().ok("GET", f"/distributed/trace/{record['trace_id']}")["tree"]


def lines(nodes, depth=0):
    for node in nodes:
        attrs = {k: (round(v, 3) if isinstance(v, float) else v)
                 for k, v in node["attrs"].items() if k in PROGRAM_WORK + ("bytes", "depth")}
        name = "  " * depth + node["name"]
        yield f"{name:<40} {node['duration']:9.3f} s  {attrs or ''}"
        yield from lines(node["children"], depth + 1)


def flat(nodes):
    for node in nodes:
        yield node
        yield from flat(node["children"])


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--burst", type=int, default=3)
    parser.add_argument("--out", default=None)
    parser.add_argument("--rehearsal", action="store_true")
    args = parser.parse_args()
    args.seconds, args.trace = 0.0, 0
    args.out = args.out or os.path.join(
        harness.ROOT, "chiprun_out", "span_tree", args.workload)
    run = harness.Run(harness.Cell(args.workload, args.rehearsal), args)
    try:
        run.set_up()
        first = tree_of(run, run.first)
        print("\n".join(lines(first)), flush=True)
        records: list = [None] * args.burst

        def send(i: int) -> None:
            records[i] = run.send(10 + i)

        threads = [threading.Thread(target=send, args=(i,)) for i in range(args.burst)]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join()
        burst = []
        for record in records:
            tree = tree_of(run, record)
            covered = {name: sum(s["duration"] for s in flat(tree) if s["name"] == name)
                       for name in COVERING}
            burst.append({"latency_s": record["latency_s"], **covered,
                          "uncovered_s": record["latency_s"] - sum(covered.values()),
                          "tree": tree})
            print({k: round(v, 4) for k, v in burst[-1].items() if k != "tree"}, flush=True)
        with open(os.path.join(run.out, "span_tree.json"), "w", encoding="utf-8") as fh:
            json.dump({"setup_s": run.setup_s, "first": first, "burst": burst}, fh, indent=1)
    finally:
        run.server.stop(grace_s=60)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except harness.Failure as exc:
        print(f"span_tree: FAILED: {exc}", file=sys.stderr)
        sys.exit(1)
