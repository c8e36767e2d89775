#!/usr/bin/env python3
"""The benchmark's one command: one run of one cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Starts `python -m comfyui_distributed_tpu` as a child, sets it up (one
request as committed, then warm requests), offers the cell's traffic
for `--seconds`, checks every image, stops the child, and prints the
result as one JSON object on the last line. Everything a cell is made
of is data found by name from BENCHMARK.json: configs/<config>.json,
traffic/<traffic>.json, workloads/<cell>.json, layer_metrics/<metric>.py.
See README.md beside this file.

This process starts no JAX backend: the chip belongs to the child.
"""

from __future__ import annotations

import argparse
import copy
import hashlib
import importlib.util
import json
import os
import re
import shutil
import socket
import sys
import threading
import time

_STARTED = time.monotonic()  # set-up counts from the start of the process

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import client  # noqa: E402
import loadgen  # noqa: E402
import stats  # noqa: E402
from client import Failure  # noqa: E402

POLL_S = 0.01  # /history is polled this often; a latency is off by at most this
GRACE_S = 150.0  # after the window, a request not done by then has failed
PERCENTILE = re.compile(r"^job_s\.p(\d+)$")


def say(message: str) -> None:
    print(f"[benchmark +{time.monotonic() - _STARTED:6.1f}s] {message}", flush=True)


def load_json(*parts: str) -> dict:
    path = os.path.join(*parts)
    try:
        with open(path, encoding="utf-8") as fh:
            return json.load(fh)
    except OSError as exc:
        raise Failure(f"cannot read {path}: {exc}") from exc


def free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


class Cell:
    """One entry of BENCHMARK.json's workloads with the files it names."""

    def __init__(self, name: str, rehearsal: bool):
        manifest = load_json(ROOT, "BENCHMARK.json")
        entry = next((w for w in manifest["workloads"] if w["name"] == name), None)
        if entry is None:
            known = ", ".join(w["name"] for w in manifest["workloads"])
            raise Failure(f"no workload {name!r} in BENCHMARK.json (has: {known})")
        config_entry = next(
            (c for c in manifest["configs"] if c["name"] == entry["config"]), None
        )
        if config_entry is None:
            raise Failure(f"BENCHMARK.json has no configuration {entry['config']!r}")
        self.name, self.chips = name, int(entry["chips"])
        self.config = load_json(ROOT, config_entry["file"])
        self.mix = load_json(HERE, "traffic", entry["traffic"] + ".json")
        self.work = load_json(HERE, "workloads", name + ".json")
        self.rehearsal = rehearsal
        if rehearsal:
            self.work.update(self.work.get("rehearsal", {}).get("work", {}))

        def mine(metric: dict) -> bool:
            return "workloads" not in metric or name in metric["workloads"]

        self.end_to_end = [m for m in manifest["end_to_end"] if mine(m)]
        self.per_layer = [m for m in manifest["per_layer"] if mine(m)]
        self.prompt = load_json(ROOT, self.work["workflow"])
        loaders = [
            node["inputs"]["ckpt_name"] for node in self.prompt.values()
            if node["class_type"] == "CheckpointLoaderSimple"
        ]
        if loaders != [self.config["registry_name"]]:
            raise Failure(
                f"{self.work['workflow']} loads {loaders}, the cell's configuration "
                f"is {self.config['registry_name']!r}"
            )
        if rehearsal:
            for edit in self.work.get("rehearsal", {}).get("set", []):
                for node in self.prompt.values():
                    if node["class_type"] == edit["class_type"]:
                        node["inputs"][edit["input"]] = edit["value"]

    def input_names(self) -> list[str]:
        """File names of the input images; the first is the one the
        committed workflow asks for."""
        spec = self.work.get("input_images")
        if not spec:
            return []
        committed = next(
            node["inputs"]["image"] for node in self.prompt.values()
            if node["class_type"] == "LoadImage"
        )
        stem, ext = os.path.splitext(committed)
        return [committed] + [f"{stem}_{i}{ext}" for i in range(1, int(spec["count"]))]

    def request(self, index: int | None, seed: int | None) -> dict:
        """The committed graph (index None), or the same with only the
        seed, and the input image where there is one, changed."""
        prompt = copy.deepcopy(self.prompt)
        if index is None:
            return prompt
        names = self.input_names()
        for node in prompt.values():
            if node["class_type"] in self.work["seed_nodes"]:
                node["inputs"]["seed"] = seed
            if node["class_type"] == "LoadImage" and names:
                node["inputs"]["image"] = names[index % len(names)]
        return prompt

    def committed_seed(self) -> int:
        return next(
            int(node["inputs"]["seed"]) for node in self.prompt.values()
            if node["class_type"] in self.work["seed_nodes"]
        )


class Run:
    def __init__(self, cell: Cell, args):
        self.cell, self.args = cell, args
        self.out = os.path.abspath(
            args.out or os.path.join(ROOT, "chiprun_out", "benchmark", cell.name)
        )
        shutil.rmtree(self.out, ignore_errors=True)
        os.makedirs(self.out)
        self.port = free_port()
        self.local = threading.local()
        # request seeds: distinct, drawn from --seed, never the committed one
        import numpy as np

        self.base = int(np.random.default_rng(args.seed).integers(1, 2 ** 31 - 1))
        env, extra = {}, []
        if cell.rehearsal:
            env = {"JAX_PLATFORMS": "cpu",
                   "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}
            extra = ["--platform", "cpu"]
        if args.trace:
            env["CDT_PROFILE_DIR"] = os.path.join(self.out, "profile")
        self.server = client.Server(ROOT, self.out, self.port, extra_args=extra, extra_env=env)
        self.log: dict = {"workload": cell.name, "seed": args.seed,
                          "seconds": args.seconds, "trace": args.trace,
                          "rehearsal": cell.rehearsal}

    # --- one request -------------------------------------------------------

    def conn(self) -> client.Conn:
        if not hasattr(self.local, "conn"):
            self.local.conn = client.Conn(self.port)
        return self.local.conn

    def seed_of(self, index: int) -> int:
        seed = (self.base + index * 7919) % (2 ** 31 - 1)
        return seed + 1 if seed == self.cell.committed_seed() else seed

    def send(self, index: int | None, due: float | None = None,
             give_up_at: float | None = None) -> dict:
        """Post one request and wait until /history says it is done and
        its images are on disk. Never raises for what the server does
        to the request: a refusal, an error or a timeout is a failed
        record."""
        seed = None if index is None else self.seed_of(index)
        prompt = self.cell.request(index, seed)
        conn = self.conn()
        sent = time.monotonic()
        record = {"index": index, "seed": seed, "due": due if due is not None else sent,
                  "sent": sent, "ok": False, "error": None, "images": []}
        try:
            status, answer = conn.call(
                "POST", "/distributed/queue",
                {"prompt": prompt, "client_id": "benchmark", "workers": []},
            )
            if status != 200 or not isinstance(answer, dict) or not answer.get("prompt_id"):
                record["error"] = f"queue answered HTTP {status}: {str(answer)[:300]}"
            else:
                record["prompt_id"] = answer["prompt_id"]
                record["trace_id"] = answer.get("trace_id")
                record["granted"] = time.monotonic()
                path = f"/history/{answer['prompt_id']}"
                while True:
                    history = conn.ok("GET", path)
                    if history.get("done"):
                        break
                    if give_up_at is not None and time.monotonic() > give_up_at:
                        record["error"] = "not done when the run ended"
                        break
                    if not self.server.alive():
                        raise self.server_died()
                    time.sleep(POLL_S)
                if history.get("done"):
                    record["timings"] = history.get("timings")
                    record["error"] = history.get("error")
                    record["images"] = [
                        name for entry in (history.get("outputs") or {}).values()
                        for name in entry.get("images", [])
                    ]
                    on_disk = all(os.path.exists(self.image_path(n)) for n in record["images"])
                    if not record["error"] and not on_disk:
                        record["error"] = "history says done but an image is not on disk"
                    record["ok"] = not record["error"]
        except OSError as exc:
            if not self.server.alive():
                raise self.server_died() from exc
            record["error"] = f"{type(exc).__name__}: {exc}"
        record["end"] = time.monotonic()
        record["latency_s"] = record["end"] - record["due"]
        return record

    def server_died(self) -> Failure:
        return Failure("server died mid-request; end of its log:\n"
                       + client.tail(self.server.log_path))

    def image_path(self, name: str) -> str:
        return os.path.join(self.out, "data", "output", name)

    def take_images(self, record: dict) -> list[bytes]:
        """Read and remove a record's PNGs; note their digests and any
        fault on the record."""
        blobs, want = [], self.cell.work["output"]
        for name in record["images"]:
            with open(self.image_path(name), "rb") as fh:
                blobs.append(fh.read())
            os.remove(self.image_path(name))
        record["sha256"] = [hashlib.sha256(b).hexdigest() for b in blobs]
        faults = [client.image_fault(b, want["px"], want["block"]) for b in blobs]
        if len(blobs) != want["images"]:
            faults.append(f"{len(blobs)} image(s), expected {want['images']}")
        record["faults"] = [f for f in faults if f]
        return blobs

    # --- phases ------------------------------------------------------------

    def set_up(self) -> None:
        cell = self.cell
        spec = cell.work.get("input_images")
        for i, name in enumerate(cell.input_names()):
            client.write_input_image(
                os.path.join(self.out, "data", "input", name), int(spec["px"]),
                self.args.seed * 16 + i,
            )
        info = self.server.start()
        self.device = client.device_of(info)
        say(f"server up: {self.device}")
        want = "cpu" if cell.rehearsal else "tpu"
        if self.device["platform"] != want or self.device["count"] != cell.chips:
            raise Failure(
                f"the cell needs {cell.chips} {want} device(s); the server reports "
                f"{self.device}"
            )
        before = client.scrape(self.conn())
        first = self.send(None)
        self.must_be_good(first, "the request as committed")
        self.first = first
        after_first = client.scrape(self.conn())
        say(f"first request {first['latency_s']:.2f}s, programs "
            f"{after_first['compiles'] - before['compiles']:.0f}, timings {first['timings']}")
        # warm requests with new seeds until one builds no program; two at most
        self.warm: list[dict] = []
        scraped = after_first
        for index in (0, 1):
            record = self.send(index)
            self.must_be_good(record, f"warm request {index}")
            self.warm.append(record)
            now = client.scrape(self.conn())
            built = now["compiles"] - scraped["compiles"]
            scraped = now
            say(f"warm request {index}: {record['latency_s']:.2f}s, built {built:.0f} program(s)")
            if built == 0:
                break
        else:
            raise Failure("the second warm request still built a program")
        self.after_setup = scraped
        self.setup_s = time.monotonic() - _STARTED
        self.log["setup"] = {
            "setup_s": self.setup_s, "first": first, "warm": self.warm,
            "programs": {k: scraped[k] for k in
                         ("compiles", "compile_s", "cache_hits", "cache_misses")},
        }
        say(f"set-up done in {self.setup_s:.1f}s; programs {self.log['setup']['programs']}")

    def must_be_good(self, record: dict, what: str) -> None:
        if not record["ok"]:
            raise Failure(f"{what} failed: {record['error']}; end of the server's log:\n"
                          + client.tail(self.server.log_path))
        blobs = self.take_images(record)
        if record["faults"]:
            raise Failure(f"{what}: {record['faults']}")
        record["first_image"] = blobs[0]

    def window(self) -> None:
        args, cell = self.args, self.cell
        give_up_at = time.monotonic() + args.seconds + GRACE_S

        def send(index: int, due: float) -> dict:
            # indices 0 and 1 are the warm requests'
            return self.send(index + 2, due, give_up_at)

        tracer = threading.Thread(target=self.capture) if args.trace else None
        if tracer:
            tracer.start()
        result = loadgen.run(cell.mix, args.seconds, send)
        if tracer:
            tracer.join()
        self.window_start = result["start"]
        self.records = result["records"]
        self.after_window = client.scrape(self.conn())
        self.log["window"] = {
            "worst_lateness_s": result["worst_lateness_s"],
            "programs_built": self.after_window["compiles"] - self.after_setup["compiles"],
        }
        say(f"window: {len(self.records)} request(s), generator at most "
            f"{1e3 * result['worst_lateness_s']:.1f} ms late")

    def capture(self) -> None:
        """One profiler capture over a steady slice of the window. The
        stop is asked for and not waited on: the profiler takes several
        times the slice to convert what its Python tracer recorded, and
        the .xplane.pb is on disk long before. The route's own timer is
        set later than the slice so that this stop ends the capture."""
        spec = self.cell.work["trace"]
        time.sleep(float(spec["start_s"]))
        conn = client.Conn(self.port, timeout=600)
        status, answer = conn.call(
            "POST", "/distributed/profile/start",
            {"duration_s": float(spec["slice_s"]) + 10.0, "tag": "benchmark"},
        )
        opened = time.monotonic()
        self.log["capture"] = {"start": answer}
        if status != 200:
            return
        time.sleep(float(spec["slice_s"]))
        self.log["capture"]["held_s"] = time.monotonic() - opened

        def stop() -> None:
            try:
                self.log["capture"]["stop"] = conn.call("POST", "/distributed/profile/stop", {})[1]
            except OSError:
                pass  # the server was stopped under it

        threading.Thread(target=stop, daemon=True).start()
        import xplane

        size, deadline = -1, time.monotonic() + 200
        while time.monotonic() < deadline:
            path = xplane.find_trace(os.path.join(self.out, "profile"))
            now = os.path.getsize(path) if path else -1
            if path and now == size:
                break
            size = now
            time.sleep(1.0)
        say(f"capture: {self.log['capture']}, trace of {size} bytes")

    def check(self) -> bool:
        """What `correct` stands on."""
        cell, verdict = self.cell, {}
        digests = []
        for record in self.records:
            if record["ok"]:
                self.take_images(record)
                digests += record["sha256"]
        verdict["images_sound"] = not any(r.get("faults") for r in self.records)
        digests += [d for w in self.warm for d in w["sha256"]] + self.first["sha256"]
        verdict["seeds_distinct"] = len(set(digests)) == len(digests)
        verdict["nothing_compiled_in_window"] = self.log["window"]["programs_built"] == 0
        # the last warm request again, after everything else: same bytes
        # if the program is deterministic and nothing leaked between jobs
        again = self.send(len(self.warm) - 1)
        if again["ok"]:
            blobs = self.take_images(again)
            # a node answered from the executor's cache reports 0.0 seconds
            recomputed = any(
                seconds > 0 and cell.prompt[node]["class_type"] in cell.work["compute_nodes"]
                for node, seconds in (again["timings"] or {}).items()
            )
            verdict["repeat_recomputed"] = recomputed
            verdict["repeat_same_bytes"] = blobs[:1] == [self.warm[-1]["first_image"]]
        else:
            verdict["repeat_same_bytes"] = False
            verdict["repeat_error"] = again["error"]
        self.log["correct"] = verdict
        say(f"correctness: {verdict}")
        return all(v for k, v in verdict.items() if isinstance(v, bool))

    def spans(self) -> dict:
        """{trace_id: [span, ...]} for the window's requests."""
        out = {}

        def flatten(nodes, into):
            for node in nodes:
                children = node.pop("children", [])
                into.append(node)
                flatten(children, into)

        for record in self.records:
            trace_id = record.get("trace_id")
            if not trace_id:
                continue
            status, answer = self.conn().call("GET", f"/distributed/trace/{trace_id}")
            if status == 200:
                flatten(answer.get("tree", []), out.setdefault(trace_id, []))
        return out

    # --- numbers -----------------------------------------------------------

    def end_to_end(self) -> dict:
        done = [r for r in self.records if r["ok"]]
        if not done:
            raise Failure("no request of the window finished")
        worst = max(r["latency_s"] for r in self.records)
        latencies = [r["latency_s"] if r["ok"] else worst for r in self.records]
        rate = self.cell.work["rate"]
        elapsed = max(r["end"] for r in done) - self.window_start
        values = {"setup_s": self.setup_s,
                  rate["metric"]: len(done) * rate["units_per_job"] / elapsed}
        out = {}
        for metric in self.cell.end_to_end:
            name = metric["name"]
            match = PERCENTILE.match(name)
            if match:
                values[name] = stats.percentile(latencies, float(match.group(1)))
            if name not in values:
                raise Failure(f"no rule computes the end-to-end metric {name!r}")
            out[name] = {"value": values[name], "unit": metric["unit"]}
        return out

    def per_layer(self, material: dict) -> dict:
        out = {}
        for metric in self.cell.per_layer:
            path = os.path.join(HERE, "layer_metrics", metric["name"] + ".py")
            spec = importlib.util.spec_from_file_location("layer_metric", path)
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            value = module.read(material)
            if value is not None:
                out[metric["name"]] = {"value": value, "unit": metric["unit"]}
        return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--out", default=None,
                        help="output directory, emptied first "
                             "(default chiprun_out/benchmark/<workload>)")
    parser.add_argument("--rehearsal", action="store_true",
                        help="by hand only: the cell's tiny preset on --platform cpu, "
                             "to debug this command without a chip; its numbers mean nothing")
    parser.add_argument("--keep-trace", action="store_true",
                        help="leave the profiler's files under the output directory")
    args = parser.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, client.PACKAGE)):
        print(f"benchmark: {client.PACKAGE}/ is not in {ROOT}; there is nothing to measure",
              file=sys.stderr)
        return 2
    try:
        cell = Cell(args.workload, args.rehearsal)
        run = Run(cell, args)
        if args.rehearsal:
            say("REHEARSAL on the CPU at a toy size: this checks the command, not the chip")
        try:
            run.set_up()
            run.window()
            correct = run.check()
            material = {
                "records": run.records, "first": run.first,
                "prompt": cell.prompt, "after_setup": run.after_setup,
                "after_window": run.after_window, "trace": None, "spans": {},
            }
            if args.trace:
                material["spans"] = run.spans()
        finally:
            # a profiler still converting its trace would sit out a SIGTERM
            run.server.stop(grace_s=3 if args.trace else 60)
        say("server stopped")
        device = dict(run.device)
        peaks = run.after_window["peak_bytes_in_use"]
        device["memory_peak_bytes"] = max(peaks.values()) if peaks else 0
        result = {"correct": correct, "attempted": len(run.records),
                  "failed": sum(not r["ok"] for r in run.records)}
        if args.trace:
            import xplane

            profile_dir = os.path.join(run.out, "profile")
            path = xplane.find_trace(profile_dir)
            if path is None:
                raise Failure(f"no trace under {profile_dir}: {run.log.get('capture')}")
            loaded = xplane.load(path, any_plane=args.rehearsal)
            run.log["trace_lines"] = loaded["lines"]
            reduced = xplane.reduce(loaded, run.log["capture"].get("held_s"))
            say(f"trace reduced: {len(loaded['lines'])} line(s)")
            if reduced is None:
                raise Failure("the trace shows no operation on a device")
            material["trace"] = reduced
            device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
            result["metrics"] = run.per_layer(material)
            result["breakdown"] = reduced["breakdown"]
            if not args.keep_trace:
                shutil.rmtree(profile_dir, ignore_errors=True)
        else:
            result["metrics"] = run.end_to_end()
        result["device"] = device
        for record in [run.first, *run.warm]:
            record.pop("first_image", None)
        run.log.update(records=run.records, result=result)
        with open(os.path.join(run.out, "run.json"), "w", encoding="utf-8") as fh:
            json.dump(run.log, fh, indent=1, default=str)
        if args.rehearsal:
            say("REHEARSAL: the line below is not a measurement")
        print(json.dumps(result), flush=True)
        return 0
    except Failure as exc:
        print(f"benchmark: FAILED: {exc}", file=sys.stderr, flush=True)
        return 1


if __name__ == "__main__":
    sys.exit(main())
