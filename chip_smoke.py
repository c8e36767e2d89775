#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points an operator uses:

    serve      `python -m comfyui_distributed_tpu --port P` as its own
               process; the two BASELINE workflows queued over HTTP as
               committed — workflows/distributed-upscale.json (SDXL at
               registry width, 1024 -> 2048, 16 tiles, 20 steps) and
               workflows/distributed-txt2img.json (SD1.5, 512^2, 20
               steps) — POST /distributed/queue, poll /history/<id>,
               read the saved PNG. One cold request of each as
               committed, then warm ones with only the seed changed (a
               re-queued identical graph is answered from the node
               cache and would run nothing on the device). Weights are
               seeded random, the input image comes from a seed.
    restart    the server is stopped (SIGTERM, wait, port dead) and
               started again; both committed requests are repeated and
               must hit the persistent compile cache and give the same
               bytes as before the restart.
    attention  one child that holds the chip runs the attention
               dispatcher, compiled, at the shapes the served workflows
               produce, on both routes (the Pallas kernel and XLA)
               and on the kernel in the layout it had until PR 35
               (heads folded into the batch by transpositions), each
               between the [B, N, H*D] arrays a model holds and against
               a float32 reference, and prints which route the shape
               rule gives each shape and all three times; where
               `ops/short_attention.py` has a form for the shape (SDXL's
               64-wide heads over keys that fit one block) that kernel
               too, at every count of lane tiles a grid step, and every
               route between a block's linears (`in_block_ms`); then the
               four causal calls of the language models' prefills
               (`CAUSAL_SHAPES`) on the kernel under its mask and on the
               XLA form, and the kernel under each pair of block caps
               of the sweep, and the same for dots3-note-prev's band of
               513 over a part's 8,192 queries and the 512 latents
               before them (`BAND_TAIL_SHAPE`: more keys than queries,
               what `MIN_BAND_WINDOW` rests on) and for
               LongCat-Flash-Chat's last part over every key so far
               (`LATENT_PART_SHAPE`: 8,192 queries, 32,768 keys, no
               window); then the single-query
               kernel of a language model's decode
               (`ops/decode_attention`) against the einsum form over
               every slot of Ouro's 3.3 GB cache, a call a slot inside
               one jitted loop; then a drafting step's delta rule over
               Ling-3.0-flash's six KDA states, two positions a step,
               with two slots a layer and a flipped bit against one
               slot and a select at the step's end; then a prefill's
               chunked delta rule over one KDA layer at Ling's and at
               Solar-Open2's heads (`KDA_DELTA_SHAPES`): the kernel
               (`ops/kda_delta`) against the XLA form, us a (head,
               chunk) of each and the kernel at other heads a step; a
               Mamba-2 layer's chunked scan at Nemotron-3-Nano's and at
               granite-4.0-h-micro's sizes (`SSD_SHAPES`): the kernel
               (`ops/ssd_chunk`) and the XLA form, each against the
               recurrence token by token, ms a layer of each and the
               kernel at other heads a step, and a decode step's update
               of the blocks' states; learned sparse
               attention at GLM-5.2's sizes (`DSA_SHAPE`): a part's
               indexer scores, its selection by `lax.top_k` and by
               bisection, attention over the chosen rows gathered and
               under the selection's mask, and a step's two positions
               in both forms.
    experts    one child that holds the chip runs a decode step's two
               grouped products (gate-up, SiLU, down) over the held
               experts' stacked weights at the four models' decode
               shapes, DeepSeek's at 8 rows beside its 6, and the fifth
               model's experts without a gate at 1,856 columns (up
               stored out by in, relu squared, down): the
               kernel (`ops/expert_matvec`) against `jax.lax.ragged_dot`,
               a step's routing drawn anew inside one jitted loop, both
               against a float32 reference; it prints us a step, GB/s
               of the chosen experts' weights, and which lowering the
               compiler gave `ragged_dot` at that row count. Then a
               row a model and rung for the nine models' prefills
               (`PREFILL_EXPERT_SHAPES`, the ladder's lowest two rungs,
               of which the layer gives the kernel the lowest):
               the row gather, the two products and the weighted way
               back on `ragged_dot` and on the kernel of
               `ops/grouped_matmul`, ms a call of each, their ratio and
               the largest difference between them: what
               `grouped_matmul.route`'s rule rests on.
    init       only when named (`--legs init`; it builds every component
               cold): one child that holds the chip builds SD1.5's, SDXL's
               and FLUX's components as a start does, each with its one
               compiled program (`models/pipeline.init_program`), the
               compile cache off, and prints a row a component: the
               program's `temp_size_in_bytes` beside the stored bytes
               (a row fails above a quarter), its cold compile seconds,
               the seconds it runs, and, where a parent commit is
               unpacked at scratch/parent, the seconds that commit's
               `init_params` takes cold and the share of weights whose
               bits differ from its.
    multichip  only where the server reports two or more chips: the
               serve leg has then already run on every chip through
               the in-process mesh; this leg checks that, and runs the
               process-per-chip tier (master pinned to chip 0, a
               managed worker on chip 1, USDU through the elastic tile
               queue).

This process never initialises a JAX backend: a chip belongs to one
process, so it talks HTTP to a server child and stops that child before
the next process needs the chip. Every child writes under one output
directory (chiprun_out/chip_smoke by default) — config, data, logs, the
native build — so nothing it finds in the checkout is reused.

A leg that fails is a failure: the remaining independent legs still run
(a chip call is expensive and should report everything it can), then
the exit code is 1 and no result line is printed. The last line of a
passing run is one JSON object, {"ok": true, "device": {...}}, with the
device as JAX reported it to the server.

`--rehearsal [N]` is for debugging this command in a sandbox with no
chip, and for the integration test: tiny-unet at 64 px on `--platform
cpu` (N virtual devices, default 1; the Pallas kernel interpreted). It
is chosen by the caller, says so in its output, and is never entered
because no chip was found. Without it, anything but platform "tpu"
fails.
"""

from __future__ import annotations

import argparse
import copy
import functools
import importlib.util
import io
import json
import os
import shutil
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
PACKAGE = "comfyui_distributed_tpu"
LEGS = ("serve", "restart", "attention", "experts", "multichip", "init")
DEFAULT_LEGS = LEGS[:-1]  # `init` builds every component cold: only when named

if not os.path.isdir(os.path.join(HERE, PACKAGE)):
    # a check of the program, not a stand-in for it: without the
    # program nothing below, the benchmark's client included, is loaded
    raise SystemExit(
        f"chip_smoke: {PACKAGE}/ is not next to this script; it checks "
        "the program and cannot run without it"
    )


def _load_client():
    """benchmark/client.py by path, under a name of its own: what it
    and this script do the same way (`Failure`, `port_is_dead`, `tail`,
    the metrics-text parser, the seeded input image) lives there once.
    `Server`, `request` and the legs stay here: the restart and
    multichip legs need what the benchmark's do not have."""
    spec = importlib.util.spec_from_file_location(
        "cdt_benchmark_client", os.path.join(HERE, "benchmark", "client.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_client = _load_client()
Failure = _client.Failure
port_is_dead = _client.port_is_dead
tail = _client.tail

# tests/ops/test_upscale.py pins mesh == single-device USDU at
# atol=2e-2 on the unit range: 5.1 of a PNG's 255 levels, plus one for
# the two independent roundings to uint8.
CANVAS_TOLERANCE_LEVELS = 6

# Attention tolerance. Operands are bfloat16 and the output is rounded
# to bfloat16 (2^-8 relative steps); the XLA route also rounds the
# softmax weights to bfloat16 before the PV product. Against a float32
# reference at `highest` matmul precision on the SAME bfloat16 operands
# that bounds the error near 1e-2 of the output scale. Queries are
# scaled so logits have a std of ~2 — a peaked softmax with O(1)
# outputs, as in a trained model — so that a wrong scale, a missed key
# block or stale scratch (errors of 0.1-1) cannot hide under it.
ATTENTION_TOLERANCE = 2e-2

# SDXL as the registry builds it (UNet + VAE + CLIP-L + CLIP-G) is
# 3,468,837,867 parameters, stored on a TPU in bfloat16. A device that
# took part in the USDU job peaks above this; one that only holds the
# replicated weights does not.
SDXL_WEIGHT_BYTES = 2 * 3_468_837_867


_STARTED = time.monotonic()


def say(message: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _STARTED:6.1f}s] {message}", flush=True)


# --- HTTP ------------------------------------------------------------------


def http(method: str, url: str, body=None, timeout: float = 60.0):
    data = None if body is None else json.dumps(body).encode()
    request = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(request, timeout=timeout) as response:
            raw = response.read()
    except urllib.error.HTTPError as exc:
        detail = exc.read().decode(errors="replace")[:2000]
        raise Failure(f"{method} {url} -> HTTP {exc.code}: {detail}") from exc
    text = raw.decode()
    try:
        return json.loads(text)
    except json.JSONDecodeError:
        return text


# --- server child ----------------------------------------------------------


class Server:
    """One `python -m comfyui_distributed_tpu` child and its HTTP face."""

    def __init__(self, run: "Run", name: str, port: int, devices=None):
        self.run = run
        self.name = name
        self.port = port
        self.base = f"http://127.0.0.1:{port}"
        self.log_path = os.path.join(run.out, f"{name}.log")
        self.devices = devices  # rehearsal only: virtual CPU devices
        self.device: dict = {}  # as the server reports it, once up
        self.proc: subprocess.Popen | None = None

    def start(self, local_devices=None) -> dict:
        if not port_is_dead(self.port):
            raise Failure(f"port {self.port} is already taken")
        cmd = [sys.executable, "-m", PACKAGE, "--port", str(self.port)]
        cmd += self.run.platform_args
        env = self.run.child_env(self.devices)
        say(f"{self.name}: starting {' '.join(cmd[1:])} (log {self.log_path})")
        started = time.monotonic()
        with open(self.log_path, "ab") as log:
            self.proc = subprocess.Popen(
                cmd, cwd=HERE, env=env, stdout=log, stderr=subprocess.STDOUT,
                start_new_session=True,
            )
        self.run.children.append(self.proc)
        info = wait_for_server(self.base, self.proc, self.log_path, self.name)
        say(f"{self.name}: up in {time.monotonic() - started:.1f}s")
        self.device = check_system_info(
            self.run, self.name, info, local_devices
        )
        return info

    def stop(self) -> None:
        """SIGTERM, wait, and require the port dead: the next process
        that needs the chip must find it free."""
        proc = self.proc
        if proc is None:
            return
        proc.send_signal(signal.SIGTERM)
        try:
            code = proc.wait(timeout=90)
        except subprocess.TimeoutExpired:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()
            raise Failure(f"{self.name}: still running 90s after SIGTERM")
        self.proc = None
        if not port_is_dead(self.port):
            raise Failure(f"{self.name}: exited but port {self.port} answers")
        say(f"{self.name}: stopped (exit {code}), port {self.port} dead")

    def get(self, path: str, **kw):
        return http("GET", self.base + path, **kw)

    def post(self, path: str, body, **kw):
        return http("POST", self.base + path, body, **kw)


def wait_for_server(base: str, proc, log_path: str, name: str) -> dict:
    """Poll /distributed/system_info until it answers. The server only
    listens once its backend is up, so an answer is a started server;
    a child that exits first is a failed start, with its log's end."""
    deadline = time.monotonic() + 600
    while time.monotonic() < deadline:
        if proc is not None and proc.poll() is not None:
            raise Failure(
                f"{name}: exited with code {proc.returncode} during "
                f"start-up; end of {log_path}:\n{tail(log_path, 40)}"
            )
        try:
            return http("GET", base + "/distributed/system_info", timeout=10)
        except (urllib.error.URLError, OSError, Failure):
            time.sleep(0.5)
    raise Failure(f"{name}: no answer on {base} after 600s")


def wait_idle(base: str, name: str) -> None:
    """Until the server's prompt queue is empty (GET /prompt, the
    probe the master itself uses)."""
    deadline = time.monotonic() + 900
    while time.monotonic() < deadline:
        info = http("GET", base + "/prompt")
        if info["exec_info"]["queue_remaining"] == 0:
            return
        time.sleep(0.5)
    raise Failure(f"{name}: prompt queue still busy after 900s")


def read_metrics(base: str) -> dict:
    """The runtime gauges of /distributed/metrics (telemetry/runtime.py),
    plus the tiles each role has processed (the multichip leg reads
    who did the work)."""
    text = http("GET", base + "/distributed/metrics")
    out = _client.parse_metrics(text)
    out["tiles"] = {}
    for line in text.splitlines():
        if line.startswith("cdt_tiles_processed_total{"):
            labels, _, value = line.rpartition(" ")
            role = labels.partition('role="')[2].partition('"')[0]
            out["tiles"][role or "?"] = int(float(value))
    return out


def describe_metrics(before: dict, after: dict) -> dict:
    return {
        "compiles": int(after["compiles"] - before["compiles"]),
        "compile_s": round(after["compile_s"] - before["compile_s"], 2),
        "cache_hits": int(after["cache_hits"] - before["cache_hits"]),
        "cache_misses": int(after["cache_misses"] - before["cache_misses"]),
        "peak_bytes_in_use": after["peak_bytes_in_use"],
        "bytes_in_use": after["bytes_in_use"],
    }


# --- the run ---------------------------------------------------------------


class Run:
    def __init__(self, args):
        self.rehearsal: int = args.rehearsal or 0
        self.out = os.path.abspath(args.out)
        self.port = args.port
        self.children: list[subprocess.Popen] = []
        self.failures: list[str] = []
        self.device: dict | None = None
        self.results: dict[str, dict] = {}
        self.platform_args = ["--platform", "cpu"] if self.rehearsal else []
        self.expect_platform = "cpu" if self.rehearsal else "tpu"

    # every child of the smoke resolves state under the output
    # directory; the compile cache alone stays where the program puts
    # it (JAX_COMPILATION_CACHE_DIR, else the fixed in-checkout path)
    def child_env(self, devices: int | None = None) -> dict:
        env = dict(os.environ)
        env.update(
            CDT_CONFIG_PATH=os.path.join(self.out, "tpu_config.json"),
            CDT_DATA_DIR=os.path.join(self.out, "data"),
            CDT_LOG_DIR=os.path.join(self.out, "logs"),
            CDT_NATIVE_BUILD_DIR=os.path.join(self.out, "native_build"),
            PYTHONPATH=HERE + os.pathsep + env.get("PYTHONPATH", ""),
            PYTHONUNBUFFERED="1",
        )
        if self.rehearsal:
            n = self.rehearsal if devices is None else devices
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
            # a CPU mesh is opt-in (parallel/mesh.worker_mesh)
            env.pop("CDT_MESH_SHAPE", None)
            if n > 1:
                env["CDT_MESH_SHAPE"] = f"{n},1"
        return env

    def workflow(self, name: str) -> dict:
        with open(os.path.join(HERE, "workflows", name), encoding="utf-8") as fh:
            prompt = json.load(fh)
        if self.rehearsal:
            # the one place the committed graphs are edited, and only
            # on the caller's explicit request: same nodes, toy sizes
            for node in prompt.values():
                inputs = node["inputs"]
                if node["class_type"] == "CheckpointLoaderSimple":
                    inputs["ckpt_name"] = "tiny-unet"
                if node["class_type"] == "EmptyLatentImage":
                    inputs["width"] = inputs["height"] = 64
                if node["class_type"] in (
                    "KSampler", "UltimateSDUpscaleDistributed"
                ):
                    inputs["steps"] = 2
                if node["class_type"] == "UltimateSDUpscaleDistributed":
                    inputs["tile_width"] = inputs["tile_height"] = 64
                    inputs["tile_padding"] = 16
        return prompt

    @property
    def input_px(self) -> int:
        return 64 if self.rehearsal else 1024

    @property
    def tile_px(self) -> int:
        return 64 if self.rehearsal else 512

    @property
    def txt2img_px(self) -> int:
        return 64 if self.rehearsal else 512

    def workloads(self) -> tuple:
        """(tag, workflow file, output px, block px for the flatness
        check, images expected): the seed node fans txt2img out to one
        image per mesh participant."""
        return (
            ("usdu", "distributed-upscale.json", 2 * self.input_px,
             self.tile_px, 1),
            ("txt2img", "distributed-txt2img.json", self.txt2img_px,
             self.txt2img_px, self.device["count"]),
        )

    def attempt(self, leg: str, fn) -> bool:
        say(f"=== leg {leg} ===")
        started = time.monotonic()
        try:
            fn()
        except Failure as exc:
            self.failures.append(f"{leg}: {exc}")
            say(f"leg {leg} FAILED after {time.monotonic() - started:.1f}s: {exc}")
            return False
        say(f"leg {leg} passed in {time.monotonic() - started:.1f}s")
        return True

    def stop_children(self) -> None:
        for proc in self.children:
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                proc.wait()


def write_input_image(run: Run) -> None:
    """LoadImage's "input.png", from a seed (the benchmark's generator)."""
    path = os.path.join(run.out, "data", "input", "input.png")
    _client.write_input_image(path, run.input_px, 20260926)
    say(f"input image {run.input_px}x{run.input_px} from seed 20260926 -> {path}")


def check_system_info(run: "Run", name: str, info: dict, local_devices=None) -> dict:
    """Print what the server says it runs on; fail unless it is the
    platform this run is for."""
    topology = info.get("topology") or {}
    if "error" in topology:
        raise Failure(f"{name}: system_info topology error: {topology['error']}")
    if "error" in (topology.get("mesh") or {}):
        raise Failure(f"{name}: mesh error: {topology['mesh']['error']}")
    device = {
        "platform": topology.get("platform"),
        "kind": topology.get("device_kind"),
        "count": topology.get("device_count"),
    }
    say(
        f"{name}: platform={device['platform']} device_kind={device['kind']} "
        f"devices={device['count']} local={topology.get('local_device_count')} "
        f"visible_chips={topology.get('visible_chips')} "
        f"mesh={topology.get('mesh')} versions={topology.get('versions')} "
        f"data_plane={info.get('data_plane')}"
    )
    if device["platform"] != run.expect_platform:
        raise Failure(
            f"{name}: serves on platform {device['platform']!r}, "
            f"this run needs {run.expect_platform!r}"
        )
    if local_devices is not None and topology.get("local_device_count") != local_devices:
        raise Failure(
            f"{name}: {topology.get('local_device_count')} local device(s), "
            f"expected {local_devices}"
        )
    return device


def load_png(path: str):
    import numpy as np
    from PIL import Image

    with open(path, "rb") as fh:
        raw = fh.read()
    return raw, np.asarray(Image.open(io.BytesIO(raw)).convert("RGB"))


def check_image(label: str, pixels, size: int, block: int) -> None:
    """Right shape, finite, and no constant block: a NaN tile leaves
    the encoder as one flat colour, so flatness is how it shows."""
    import numpy as np

    if pixels.shape != (size, size, 3):
        raise Failure(f"{label}: shape {pixels.shape}, expected {(size, size, 3)}")
    as_float = pixels.astype(np.float32)
    if not np.isfinite(as_float).all():
        raise Failure(f"{label}: non-finite pixels")
    for y in range(0, size, block):
        for x in range(0, size, block):
            if as_float[y:y + block, x:x + block].std() == 0.0:
                raise Failure(f"{label}: constant {block}px block at ({y},{x})")


def request(
    run: Run, server: Server, label: str, prompt: dict, *, images: int,
    size: int, block: int, warm: bool, workers=(),
) -> dict:
    """Queue one prompt as a client would and hold it to the contract."""
    before = read_metrics(server.base)
    started = time.monotonic()
    queued = server.post(
        "/distributed/queue",
        {"prompt": prompt, "client_id": "chip_smoke", "workers": list(workers)},
        timeout=300,
    )
    prompt_id = queued.get("prompt_id")
    if not prompt_id:
        raise Failure(f"{label}: queue answered {queued}")
    if sorted(queued.get("workers", [])) != sorted(workers):
        raise Failure(
            f"{label}: asked for workers {list(workers)}, "
            f"dispatched to {queued.get('workers')}"
        )
    deadline = started + 1500
    while True:
        history = server.get(f"/history/{prompt_id}")
        if history.get("done"):
            break
        if server.proc is not None and server.proc.poll() is not None:
            raise Failure(
                f"{label}: server died mid-request; end of log:\n"
                f"{tail(server.log_path, 40)}"
            )
        if time.monotonic() > deadline:
            raise Failure(f"{label}: not done after 1500s")
        time.sleep(0.25)
    wall = time.monotonic() - started
    if history.get("error"):
        raise Failure(
            f"{label}: ended in error: {history['error']}; end of log:\n"
            f"{tail(server.log_path, 25)}"
        )
    names = [
        name
        for entry in (history.get("outputs") or {}).values()
        for name in entry.get("images", [])
    ]
    if len(names) != images:
        raise Failure(f"{label}: saved {names}, expected {images} image(s)")
    outputs = []
    for name in names:
        path = os.path.join(run.out, "data", "output", name)
        raw, pixels = load_png(path)
        check_image(f"{label} {name}", pixels, size, block)
        outputs.append({"name": name, "bytes": raw, "pixels": pixels})
        # held in memory from here; what the chip tool brings back is
        # capped, and a 2048^2 PNG of this input is ~10 MB
        os.remove(path)
    delta = describe_metrics(before, read_metrics(server.base))
    say(
        f"{label}: ok in {wall:.2f}s wall on {server.device['platform']}/"
        f"{server.device['kind']} x{server.device['count']} | "
        f"compiles={delta['compiles']} compile_s={delta['compile_s']} "
        f"cache_hits={delta['cache_hits']} cache_misses={delta['cache_misses']} "
        f"peak_bytes_in_use={delta['peak_bytes_in_use']} "
        f"bytes_in_use={delta['bytes_in_use']} | "
        f"node timings {history.get('timings')}"
    )
    if warm and delta["compiles"]:
        raise Failure(
            f"{label}: a warm request built {delta['compiles']} program(s) "
            f"({delta['compile_s']}s)"
        )
    return {"wall_s": wall, "outputs": outputs, "metrics": delta}


def with_seed(prompt: dict, seed: int) -> dict:
    """The committed graph with only its seed changed."""
    prompt = copy.deepcopy(prompt)
    for node in prompt.values():
        if node["class_type"] in ("UltimateSDUpscaleDistributed", "DistributedSeed"):
            node["inputs"]["seed"] = seed
    return prompt


def committed_seed(prompt: dict) -> int:
    for node in prompt.values():
        if node["class_type"] in ("UltimateSDUpscaleDistributed", "DistributedSeed"):
            return int(node["inputs"]["seed"])
    raise Failure("workflow has no seed node")


def require_distinct(label: str, blobs: list[bytes]) -> None:
    if len(set(blobs)) != len(blobs):
        raise Failure(f"{label}: outputs that must differ are identical")


# --- legs ------------------------------------------------------------------


def leg_serve(run: Run) -> None:
    server = Server(run, "server1", run.port)
    problems: list[str] = []
    try:
        server.start()
        run.device = server.device
        results = run.results.setdefault("serve", {})
        for tag, name, size, block, images in run.workloads():
            prompt = run.workflow(name)
            seed = committed_seed(prompt)
            runs = results.setdefault(tag, [])
            try:
                runs.append(request(
                    run, server, f"{tag} cold (seed {seed}, as committed)",
                    prompt, images=images, size=size, block=block, warm=False,
                ))
                for i in (1, 2):
                    runs.append(request(
                        run, server, f"{tag} warm {i} (seed {seed + i})",
                        with_seed(prompt, seed + i), images=images, size=size,
                        block=block, warm=True,
                    ))
                require_distinct(
                    f"{tag} across seeds",
                    [o["bytes"] for r in runs for o in r["outputs"]],
                )
            except Failure as exc:
                # the other workflow is independent of this one
                problems.append(str(exc))
                say(f"{tag} FAILED: {exc}")
                if not runs:
                    del results[tag]
    finally:
        server.stop()
    if problems:
        raise Failure("; ".join(problems))


def leg_restart(run: Run) -> None:
    first = run.results.get("serve") or {}
    if not ("usdu" in first and "txt2img" in first):
        raise Failure("needs the serve leg's cold outputs")
    server = Server(run, "server2", run.port)
    try:
        server.start()
        for tag, name, size, block, images in run.workloads():
            prompt = run.workflow(name)
            again = request(
                run, server,
                f"{tag} after restart (seed {committed_seed(prompt)}, as committed)",
                prompt, images=images, size=size, block=block, warm=False,
            )
            metrics = again["metrics"]
            if metrics["cache_hits"] == 0 or metrics["cache_misses"]:
                raise Failure(
                    f"{tag} after restart: persistent compile cache "
                    f"hits={metrics['cache_hits']} misses={metrics['cache_misses']}"
                    " (a restarted server must load every program it needs)"
                )
            before = [o["bytes"] for o in first[tag][0]["outputs"]]
            if [o["bytes"] for o in again["outputs"]] != before:
                raise Failure(
                    f"{tag} after restart: same seed, different bytes"
                )
            say(f"{tag} after restart: bytes identical to the first server's")
    finally:
        server.stop()


def leg_child(run: Run, name: str, limit_s: int = 900) -> None:
    """The `attention`, `experts` and `init` legs: one child that holds the chip
    (`--<name>-child`; the servers are down by now), its JSON rows
    printed, the first one the device; a row with a `shape` that is not
    `ok`, or none at all, fails the leg."""
    cmd = [sys.executable, os.path.abspath(__file__), f"--{name}-child"]
    if run.rehearsal:
        cmd += ["--rehearsal", "1"]
    log_path = os.path.join(run.out, f"{name}.log")
    with open(log_path, "wb") as log:
        proc = subprocess.Popen(
            cmd, cwd=HERE, env=run.child_env(devices=1),
            stdout=subprocess.PIPE, stderr=log, start_new_session=True,
        )
        run.children.append(proc)
        try:
            stdout, _ = proc.communicate(timeout=limit_s)
        except subprocess.TimeoutExpired:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()
            raise Failure(f"{name} child still running after {limit_s}s")
    rows = [
        json.loads(line) for line in stdout.decode().splitlines()
        if line.startswith("{")
    ]
    for row in rows:
        say(f"{name}: {json.dumps(row)}")
        if "devices" in row and run.device is None:
            run.device = {
                "platform": row["platform"], "kind": row["device_kind"],
                "count": row["devices"],
            }
    if proc.returncode != 0:
        raise Failure(
            f"{name} child exited {proc.returncode}; end of log:\n{tail(log_path, 40)}"
        )
    bad = [r for r in rows if "shape" in r and not r["ok"]]
    if bad or not any("shape" in r for r in rows):
        raise Failure(f"{name}: {len(bad)} shape(s) out of tolerance")


def leg_multichip(run: Run) -> None:
    """(a) what the serve leg did on every chip through the in-process
    mesh, and (b) the process-per-chip tier. A check that fails is kept
    and the leg goes on — a four-chip call should say everything it can
    — then the leg fails with all of them."""
    import numpy as np

    chips = run.device["count"]
    serve = run.results.get("serve") or {}
    problems: list[str] = []

    def check(name: str, fn) -> None:
        try:
            fn()
        except Failure as exc:
            problems.append(f"{name}: {exc}")
            say(f"{name} FAILED: {exc}")

    def every_device_worked():
        if "usdu" not in serve:
            raise Failure("the serve leg gave no USDU result")
        peak = serve["usdu"][0]["metrics"]["peak_bytes_in_use"]
        say(f"(a) USDU on the mesh: peak bytes per device {peak}")
        if run.rehearsal:  # the CPU backend reports no memory stats
            return
        if len(peak) != chips or min(peak.values()) <= SDXL_WEIGHT_BYTES:
            raise Failure(
                f"expected every one of {chips} devices to peak above the "
                f"{SDXL_WEIGHT_BYTES} bytes of weights"
            )

    def one_image_per_chip():
        if "txt2img" not in serve:
            raise Failure("the serve leg gave no txt2img result")
        images = [o["bytes"] for o in serve["txt2img"][0]["outputs"]]
        if len(images) != chips:
            raise Failure(f"{len(images)} image(s) on {chips} chips")
        require_distinct("txt2img participants", images)
        say(f"(a) txt2img: {chips} distinct images, one per chip")

    check("(a) every device worked", every_device_worked)
    check("(a) one image per chip", one_image_per_chip)

    # (b) process per chip: the operator's config edit, then the API
    with open(os.path.join(run.out, "tpu_config.json"), "w", encoding="utf-8") as fh:
        json.dump({"master": {"tpu_chips": [0]}}, fh)
    worker_port = run.port + 1
    worker_base = f"http://127.0.0.1:{worker_port}"
    usdu = run.workflow("distributed-upscale.json")
    size, block = 2 * run.input_px, run.tile_px
    # in rehearsal "one chip" is one virtual CPU device
    master = Server(run, "master_chip0", run.port, devices=1)

    def launch_worker() -> None:
        launched = master.post("/distributed/launch_worker", {"worker_id": "w1"})
        say(f"(b) launched worker w1: {launched}")
        log_path = launched.get("log", "")
        try:
            info = wait_for_server(worker_base, None, log_path, "worker_chip1")
        except Failure as exc:
            raise Failure(f"{exc}; end of {log_path}:\n{tail(log_path, 40)}")
        check_system_info(run, "worker_chip1", info, local_devices=1)

    def stop_worker() -> None:
        stopped = master.post("/distributed/stop_worker", {"worker_id": "w1"})
        if not stopped.get("stopped") or not port_is_dead(worker_port):
            raise Failure(
                f"stop_worker answered {stopped}; port dead: "
                f"{port_is_dead(worker_port)}"
            )
        say("(b) worker stopped, its port is dead")

    def canvas_matches_one_chip():
        solo = request(
            run, master, "(b) usdu on chip 0 alone (as committed)", usdu,
            images=1, size=size, block=block, warm=False,
        )
        if "usdu" not in serve:
            raise Failure("no mesh canvas to compare with")
        diff = np.abs(
            solo["outputs"][0]["pixels"].astype(np.int16)
            - serve["usdu"][0]["outputs"][0]["pixels"].astype(np.int16)
        )
        say(
            f"(a) {chips}-chip mesh canvas vs one-chip canvas: max |diff| "
            f"{int(diff.max())} of 255 levels, mean {float(diff.mean()):.4f}, "
            f"{float((diff > CANVAS_TOLERANCE_LEVELS).mean()):.6f} of pixels "
            f"over {CANVAS_TOLERANCE_LEVELS}"
        )
        if diff.max() > CANVAS_TOLERANCE_LEVELS:
            raise Failure(
                f"mesh canvas differs from the one-chip canvas by "
                f"{int(diff.max())} levels (> {CANVAS_TOLERANCE_LEVELS})"
            )

    def both_processes_contribute():
        # the first elastic job finds the worker still loading its
        # model (the master, already warm, may finish every tile
        # alone); the split is judged on the second, once the worker
        # has drained its queue and holds model and programs
        for label, seed in (("cold", 7), ("warm", 8)):
            before_m = read_metrics(master.base)["tiles"].get("master", 0)
            before_w = read_metrics(worker_base)["tiles"].get("worker", 0)
            request(
                run, master,
                f"(b) elastic usdu {label} (seed {seed}, workers [w1])",
                with_seed(usdu, seed), images=1, size=size, block=block,
                warm=label == "warm", workers=("w1",),
            )
            tiles_m = read_metrics(master.base)["tiles"].get("master", 0) - before_m
            tiles_w = read_metrics(worker_base)["tiles"].get("worker", 0) - before_w
            say(f"(b) {label}: tiles master={tiles_m} worker={tiles_w}")
            wait_idle(worker_base, "worker_chip1")
            totals = read_metrics(worker_base)
            totals.pop("tiles")
            say(f"(b) {label}: worker process so far {totals}")
        if not (tiles_m > 0 and tiles_w > 0):
            raise Failure(
                f"warm elastic job: master {tiles_m} tile(s), worker "
                f"{tiles_w} — both must contribute"
            )

    try:
        master.start(local_devices=1)
        check("(a) mesh canvas equals one-chip canvas", canvas_matches_one_chip)
        master.post("/distributed/config/worker", {
            "id": "w1", "name": "w1", "type": "local", "host": "127.0.0.1",
            "port": worker_port, "tpu_chips": [1], "enabled": True,
            "extra_args": " ".join(run.platform_args),
        })
        launch_worker()
        check("(b) both processes contribute", both_processes_contribute)
        stop_worker()
        # the chip is free again only if a new process can take it
        launch_worker()
        say("(b) chip 1 was free again: a second worker took it")
        stop_worker()
    finally:
        master.stop()
    if problems:
        raise Failure("; ".join(problems))


# --- the attention child (the only code here that imports jax) --------------

# (label, [B, N, H, D] of q, key tokens M). Self-attention has M = N.
# SD1.5 at 512^2 is a 64x64 latent with CFG batch 2: 8 heads of
# 40/80/160 at 4,096/1,024/256 tokens, 64 tokens in the middle block,
# 77 text keys in cross-attention; its VAE middle block is one
# 512-wide head over 4,096 tokens. A 512 px USDU tile with 32 px of
# padding is 576 px, a 72x72 latent: SDXL attends at 1,296 tokens
# (10 heads of 64) and 324 tokens (20 heads of 64) with tile batch 8
# under CFG (batch 16), over themselves and over 77 text keys, and its
# VAE middle block sees 5,184 tokens.
# FLUX.1-dev at 1024^2 attends jointly over 512 text + 4,096 image
# tokens with 24 heads of 128 (no CFG batch), and its VAE middle block
# sees the 128x128 latent: 16,384 tokens.
SERVED_SHAPES = (
    ("sd15 self 64x64", (2, 4096, 8, 40), 4096),
    ("sd15 self 32x32", (2, 1024, 8, 80), 1024),
    ("sd15 self 16x16", (2, 256, 8, 160), 256),
    ("sd15 self 8x8 mid", (2, 64, 8, 160), 64),
    ("sd15 cross 64x64", (2, 4096, 8, 40), 77),
    ("sd15 vae mid 64x64", (1, 4096, 1, 512), 4096),
    ("sdxl tile self 36x36", (16, 1296, 10, 64), 1296),
    ("sdxl tile self 18x18", (16, 324, 20, 64), 324),
    ("sdxl tile cross 36x36", (16, 1296, 10, 64), 77),
    ("sdxl tile cross 18x18", (16, 324, 20, 64), 77),
    ("sdxl tile vae mid 72x72", (8, 5184, 1, 512), 5184),
    ("flux joint 4608", (1, 4608, 24, 128), 4608),
    ("flux vae mid 128x128", (1, 16384, 1, 512), 16384),
)
# the same routes at sizes the Pallas interpreter finishes in seconds
REHEARSAL_SHAPES = (
    ("toy self aligned", (1, 256, 2, 40), 256),
    ("toy self ragged, few keys", (1, 81, 2, 64), 81),
    ("toy self ragged", (1, 600, 1, 64), 600),
    ("toy cross", (1, 256, 2, 40), 77),
)


# (label, [B, N, H, Dq] of q, key heads, value width, window): the causal
# calls of the four language models' prefills (PR 43). Solar-Open2's one
# softmax layer and K-EXAONE's full layer are the same call, 8,192 tokens
# at 64 query over 8 key heads; K-EXAONE's window layers the same under a
# band of 128; Ouro 2,048 tokens at 16 over 16; DeepSeek-V2's MLA 2,048
# tokens at 128 heads, q and k 192 wide beside a v of 128 and a scale of
# its own; Ling-3.0-flash's one MLA layer the same widths at 32 heads over
# 8,192 tokens (PR 45); Nemotron-3-Nano's six attention blocks 8,192 tokens
# at 32 query heads over 2 key heads, 16 queries a key head (PR 48).
CAUSAL_SHAPES = (
    ("solar / k-exaone full 8192", (1, 8192, 64, 128), 8, 128, None),
    ("k-exaone window 8192", (1, 8192, 64, 128), 8, 128, 128),
    ("ouro 2048", (1, 2048, 16, 128), 16, 128, None),
    ("deepseek-v2 mla 2048", (1, 2048, 128, 192), 128, 128, None),
    ("ling-flash mla 8192", (1, 8192, 32, 192), 32, 128, None),
    ("nemotron3-nano 32:2 8192", (1, 8192, 32, 128), 2, 128, None),
    ("granite-4.0-h 32:8 of 64 8192", (1, 8192, 32, 64), 8, 64, None),
)
# dots3-note-prev's sliding layers (PR 61): a part's 8,192 queries over the
# 512 latents before the part and its own, 64 heads of 256 (192 + 64) beside
# values of 128, under a band of 513: fewer queries than keys (the last
# entry: the keys). What `ops/attention.MIN_BAND_WINDOW` rests on.
BAND_TAIL_SHAPE = ("dots3 window 8192 + 512", (1, 8192, 64, 256), 64, 128, 513, 8704)
REHEARSAL_BAND_TAIL_SHAPE = ("toy window with a tail", (1, 1280, 2, 256), 2, 128, 130, 1409)
# LongCat-Flash-Chat's attentions (PR 63): the last part's 8,192 queries
# over the keys and values rebuilt from every latent so far, 32,768, the 16
# heads one call takes, 192 (128 + 64) wide beside values of 128, no window:
# a plain causal call with four times as many keys as queries.
LATENT_PART_SHAPE = ("longcat latent part 8192 of 32768", (1, 8192, 16, 192), 16, 128, None, 32768)
REHEARSAL_LATENT_PART_SHAPE = ("toy latent part", (1, 256, 2, 192), 2, 128, None, 1024)
REHEARSAL_CAUSAL_SHAPES = (
    ("toy causal grouped", (1, 1280, 4, 128), 2, 128, None),
    ("toy causal window", (1, 1280, 2, 128), 2, 128, 100),
    ("toy causal value width", (1, 200, 2, 192), 2, 128, None),
)
# (block_q, block_k) caps the causal kernel is timed under beside the
# plan's own: what `ops/attention.CAUSAL_CAPS` and `BAND_CAPS` rest on
CAUSAL_SWEEP = ((256, 512), (512, 256), (512, 512), (512, 1024), (1024, 512), (1024, 1024))
BAND_SWEEP = ((256, 128), (256, 256), (512, 128), (512, 256), (512, 512), (1024, 256))


# A language model's decode attends with one query a head over a cache
# slot (`ops/decode_attention`): Ouro-2.6B's carried cache at the
# benchmark cell's 2,048 + 64 positions, [passes, layers, keys|values,
# heads, positions, head_dim], 3.3 GB. Timed as one call a slot inside
# one jitted loop, which is how the decode runs it.
DECODE_CACHE = ("ouro decode slot", (4, 48, 2, 16, 2112, 128))
REHEARSAL_DECODE_CACHE = ("toy decode slot", (2, 3, 2, 2, 64, 128))


def transposed(attend):
    """`attend` ([B, N, H, D] attention) in the layout the kernel had
    until PR 35, kept as the comparison (and as the tests' reference):
    heads folded into the batch by a transposition before the call and
    unfolded after it. At one head the kernel's view of its operands is
    the identity, so this is that program: grid, blocks, arithmetic."""
    def call(q, k, v):
        b, n, h, d = q.shape

        def fold(x):
            return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], 1, d)

        return attend(fold(q), fold(k), fold(v)).reshape(b, h, n, d).transpose(0, 2, 1, 3)
    return call


def child_device(rehearsal: bool) -> bool:
    """A child's start: the CPU only for a rehearsal, the compile cache
    where the server keeps it, and the device as the first row. False
    (after saying why) on any other device than the one asked for."""
    import jax

    if rehearsal:
        jax.config.update("jax_platforms", "cpu")
    from comfyui_distributed_tpu.workers.startup import configure_compile_cache

    configure_compile_cache()
    device = jax.devices()[0]
    header = {
        "platform": device.platform, "device_kind": device.device_kind,
        "devices": len(jax.devices()), "rehearsal": rehearsal,
        "tolerance": ATTENTION_TOLERANCE,
    }
    print(json.dumps(header), flush=True)
    if device.platform != ("cpu" if rehearsal else "tpu"):
        print(f"this child needs a TPU, found {device.platform}", file=sys.stderr)
        return False
    return True


def timed(fn, *operands):
    """(result, seconds of the first call, ms a call of ten dispatched
    back to back: the device's time a call)."""
    import jax

    started = time.perf_counter()
    out = jax.block_until_ready(fn(*operands))
    first_s = time.perf_counter() - started
    started = time.perf_counter()
    for _ in range(10):
        last = fn(*operands)
    jax.block_until_ready(last)
    return out, first_s, 1e3 * (time.perf_counter() - started) / 10


def in_block_ms(attends: dict, q_shape, m: int) -> dict:
    """ms a call of each route as a UNet block holds it: q out of one
    linear over the tokens, k and v out of two over the tokens or the
    text, the result into a fourth and added to the residual. Standing
    alone a call's operands are a program's parameters, which lie on
    the device as it likes them (a `[16, 324, 1280]` array batch-minor,
    since 324 is no multiple of 8), and a kernel pays copies a block
    never makes: the routes' *differences* here are what a block sees."""
    import jax
    import jax.numpy as jnp

    b, n, h, d = q_shape
    width = h * d
    context = width if m == n else 2048  # SDXL's text width

    @jax.jit
    def operands(key):
        kx, kc, *kw = jax.random.split(key, 6)
        weights = [
            (jax.random.normal(kw[i], (rows, width)) / rows ** 0.5).astype(jnp.bfloat16)
            for i, rows in enumerate((width, context, context, width))]
        x = jax.random.normal(kx, (b, n, width)).astype(jnp.bfloat16)
        text = jax.random.normal(kc, (b, m, context)).astype(jnp.bfloat16)
        return x, (x if m == n else text), weights

    x, ctx, weights = operands(jax.random.key(n + m))
    out = {}
    for name, attend in attends.items():
        def block(x, ctx, weights, attend=attend):
            wq, wk, wv, wo = weights
            q, k, v = (
                (y @ w).reshape(b, y.shape[1], h, d) for y, w in ((x, wq), (ctx, wk), (ctx, wv)))
            return x + attend(q, k, v).reshape(b, n, width) @ wo

        out[name] = round(timed(jax.jit(block), x, ctx, weights)[2], 3)
    return out


def served_row(rehearsal: bool, label, q_shape, m) -> bool:
    """A served shape's non-causal call on every route whatever the rule
    says, each between the [B, N, H*D] arrays a model's linears give and
    take, against `jax.nn.dot_product_attention` in float32."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from comfyui_distributed_tpu.ops import attention, short_attention

    @jax.jit
    def errors(out, q, k, v):
        with jax.default_matmul_precision("highest"):
            ref = jax.nn.dot_product_attention(
                q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32)
            )
        return (
            jnp.max(jnp.abs(out.astype(jnp.float32) - ref)), jnp.max(jnp.abs(ref))
        )

    b, n, h, d = q_shape

    @jax.jit
    def operands(key, q_shape=q_shape, kv_shape=(b, m, h, d)):
        kq, kk, kv = jax.random.split(key, 3)
        return (
            (2.0 * jax.random.normal(kq, q_shape)).astype(jnp.bfloat16),
            jax.random.normal(kk, kv_shape).astype(jnp.bfloat16),
            jax.random.normal(kv, kv_shape).astype(jnp.bfloat16),
        )

    q, k, v = operands(jax.random.key(n * 131 + m * 7 + d))
    # the route the rule gives the shape on a TPU (the CPU never
    # routes to the kernel by itself), then every route whatever the
    # rule says: the rule rests on their times
    route = "flash" if attention.kernel_wins(n, m) else "xla"
    if short_attention.short_wins(n, m, h, d, q.dtype):
        route = "short"
    row = {
        "shape": label, "q": list(q_shape), "keys": m, "dtype": "bfloat16",
        "route": route, "ok": True,
    }
    if not rehearsal and attention.attention_route(q, k) != route:
        row["ok"] = False

    def as_served(attend, b=b, n=n, m=m, h=h, d=d):
        """`attend` between the [B, N, H*D] a model's linears give
        and take: what a layout costs shows only from there."""
        def call(q, k, v):
            out = attend(
                q.reshape(b, n, h, d), k.reshape(b, m, h, d), v.reshape(b, m, h, d)
            )
            return out.reshape(b, n, h * d)
        return jax.jit(call)

    flat = lambda x: x.reshape(*x.shape[:2], h * d)
    attends = {
        name: functools.partial(
            attention.dot_product_attention, force_flash=name == "flash",
            interpret=name == "flash" and rehearsal)
        for name in ("flash", "xla")}
    # `ops/short_attention.py`'s kernel wherever it has a form for the shape
    short = d == short_attention.WIDTH and short_attention.plan(n, m, h, 2) is not None
    if short:
        attends["short"] = functools.partial(attention.short_attend, interpret=rehearsal)
    for name, attend in dict(attends, transposed=transposed(attends["flash"])).items():
        fn = as_served(attend)
        with attention.route_log() as routes:
            out, first_s, ms = timed(fn, flat(q), flat(k), flat(v))
        err, ref_max = (float(x) for x in errors(out.reshape(q.shape), q, k, v))
        scale = max(1.0, ref_max)
        row["ok"] &= bool(np.isfinite(err)) and err <= ATTENTION_TOLERANCE * scale
        row[name] = {
            "entry": routes[0], "max_abs_err": round(err, 5),
            "first_call_s": round(first_s, 2), "ms": round(ms, 3),
        }
    row["ref_max_abs"] = round(scale, 3)
    if short:
        # the kernel at every count of lane tiles a grid step (its plan takes the most
        # that fit VMEM), then each route where a model has it: between a block's linears
        lane_tiles = h * d // attention.ROUTE_MULTIPLE
        block_q, m_pad, _ = short_attention.plan(n, m, h, 2)
        row["short"]["sweep_ms"] = {
            f"h{2 * tiles}": round(timed(as_served(functools.partial(
                short_attention.short_attention, interpret=rehearsal, tiles=tiles,
            )), flat(q), flat(k), flat(v))[2], 3)
            for tiles in range(1, lane_tiles + 1) if lane_tiles % tiles == 0
            and short_attention.vmem_bytes(block_q, m_pad, tiles, 2) <= attention.VMEM_BUDGET}
        row["in_block_ms"] = in_block_ms(attends, q_shape, m)
    print(json.dumps(row), flush=True)
    return row["ok"]


def attention_child(rehearsal: bool) -> int:
    if not child_device(rehearsal):
        return 1
    failed = 0
    for shape in REHEARSAL_SHAPES if rehearsal else SERVED_SHAPES:
        failed += not served_row(rehearsal, *shape)
    for shape in REHEARSAL_CAUSAL_SHAPES if rehearsal else CAUSAL_SHAPES:
        failed += not causal_row(rehearsal, *shape)
    failed += not causal_row(
        rehearsal, *(REHEARSAL_BAND_TAIL_SHAPE if rehearsal else BAND_TAIL_SHAPE))
    failed += not causal_row(
        rehearsal, *(REHEARSAL_LATENT_PART_SHAPE if rehearsal else LATENT_PART_SHAPE))
    failed += not decode_slot_row(rehearsal)
    failed += not kda_keep_row(rehearsal)
    for shape in REHEARSAL_KDA_DELTA_SHAPES if rehearsal else KDA_DELTA_SHAPES:
        failed += not kda_delta_row(rehearsal, *shape)
    for shape in REHEARSAL_SSD_SHAPES if rehearsal else SSD_SHAPES:
        failed += not ssd_row(rehearsal, *shape)
    failed += not dsa_row(rehearsal, *(REHEARSAL_DSA_SHAPE if rehearsal else DSA_SHAPE))
    return 1 if failed else 0


def causal_row(rehearsal: bool, label, q_shape, kv_heads, v_width, window, keys=None) -> bool:
    """A prefill's causal call on both routes, between the [B, N, H*D]
    arrays a model's projections give and take, against the XLA form in
    float32; then the kernel under each pair of block caps of the sweep
    (ms only: the plan's caps rest on these). `keys` where the call has
    more keys than queries (a part of a prompt over a tail before it)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from comfyui_distributed_tpu.ops import attention

    b, n, h, d = q_shape
    m = keys or n
    scale = 0.1147 if d != v_width else None  # DeepSeek-V2's is its own (YaRN)
    shapes = (q_shape, (b, m, kv_heads, d), (b, m, kv_heads, v_width))

    @jax.jit
    def operands(key):
        keys = jax.random.split(key, 3)
        return tuple(
            (gain * jax.random.normal(k, (s[0], s[1], s[2] * s[3]))).astype(jnp.bfloat16)
            for k, s, gain in zip(keys, shapes, (2.0, 1.0, 1.0)))

    def as_served(attend):
        def call(*flat):
            out = attend(*(x.reshape(s) for x, s in zip(flat, shapes)))
            return out.reshape(b, n, h * v_width)
        return jax.jit(call)

    flat = operands(jax.random.key(n * 131 + h * 7 + d))
    route = "flash" if attention.causal_kernel_wins(m, v_width, window) else "xla"
    row = {
        "shape": label, "q": list(q_shape), "keys": m, "key_heads": kv_heads,
        "value_width": v_width,
        "window": window, "dtype": "bfloat16", "route": route, "ok": True,
    }
    if not rehearsal and attention.causal_route(
            *(jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in shapes), window) != route:
        row["ok"] = False

    @as_served
    def in_float32(q, k, v):
        with jax.default_matmul_precision("highest"):
            return attention.causal_attention_blocked(
                q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
                scale=scale, window=window)

    ref = np.asarray(in_float32(*flat))
    ref_max = max(1.0, float(np.abs(ref).max()))
    for name in ("flash", "xla"):
        flash = name == "flash"
        fn = as_served(functools.partial(
            attention.causal_attention, scale=scale, window=window, force_flash=flash,
            interpret=flash and rehearsal))
        with attention.route_log() as routes:
            out, first_s, ms = timed(fn, *flat)
        err = float(np.abs(np.asarray(out, np.float32) - ref).max())
        row["ok"] &= bool(np.isfinite(err)) and err <= ATTENTION_TOLERANCE * ref_max
        row[name] = {
            "entry": routes[0], "max_abs_err": round(err, 5),
            "first_call_s": round(first_s, 2), "ms": round(ms, 3),
        }
    row["ref_max_abs"] = round(ref_max, 3)
    caps_name = "CAUSAL_CAPS" if window is None else "BAND_CAPS"
    chosen, sweep = getattr(attention, caps_name), {}
    for caps in () if rehearsal else (CAUSAL_SWEEP if window is None else BAND_SWEEP):
        # the plan reads the caps while the call is traced: a new trace a pair
        setattr(attention, caps_name, caps)
        try:
            _, block_q, block_k = attention.flash_plan(
                n, m, max(d + -d % 128, v_width), 2, causal=True, window=window)[1:]
            fn = as_served(functools.partial(
                attention.flash_attention.__wrapped__, scale=scale, causal=True, window=window))
            sweep[f"bq{block_q} bk{block_k}"] = round(timed(fn, *flat)[2], 3)
        finally:
            setattr(attention, caps_name, chosen)
    if sweep:
        row["sweep_ms"] = sweep
    print(json.dumps(row), flush=True)
    return row["ok"]


def decode_slot_row(rehearsal: bool) -> bool:
    """The single-query kernel against the einsum form over every slot
    of a real cache, a call a slot inside one jitted loop (a call's cost
    inside a program, not a dispatch), both against a float32 reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from comfyui_distributed_tpu.ops import decode_attention as da

    label, shape = REHEARSAL_DECODE_CACHE if rehearsal else DECODE_CACHE
    passes, layers, _, heads, positions, d = shape
    slots = passes * layers

    def over_slots(attend):
        def loop(q, cache, position):
            def body(_, i):
                return None, attend(q, cache, (i // layers, i % layers), position)
            return jax.lax.scan(body, None, jnp.arange(slots))[1]
        return jax.jit(loop)

    def xla(q, cache, slot, position):
        valid = da.position_valid(position[None], positions)
        return da.decode_attention_xla(q[None], cache, slot, valid)[0]

    def in_float32(q, cache, slot, position):
        with jax.default_matmul_precision("highest"):
            return xla(q.astype(jnp.float32), cache[slot].astype(jnp.float32)[None, None],
                       (0, 0), position)

    q = (2.0 * jax.random.normal(jax.random.key(1), (heads, d))).astype(jnp.bfloat16)
    cache = jax.jit(lambda key: jax.random.normal(key, shape, jnp.bfloat16))(jax.random.key(2))
    position = jnp.int32(positions - 12)
    route = da.decode_attention_route(heads, positions, d, cache.dtype)
    row = {
        "shape": label, "cache": list(shape), "dtype": "bfloat16", "route": route,
        "heads_a_step": da.decode_plan(heads, positions, d, cache.dtype.itemsize),
        "position": int(position), "ok": rehearsal or route == "decode-kernel",
    }
    ref = np.asarray(over_slots(in_float32)(q, cache, position))
    scale = max(1.0, float(np.abs(ref).max()))
    for name, attend in (
        ("kernel", functools.partial(da.decode_attention, interpret=rehearsal)),
        ("xla", xla),
    ):
        out, first_s, ms = timed(over_slots(attend), q, cache, position)
        err = float(np.abs(np.asarray(out, np.float32) - ref).max())
        row["ok"] &= bool(np.isfinite(err)) and err <= ATTENTION_TOLERANCE * scale
        row[name] = {
            "max_abs_err": round(err, 5), "first_call_s": round(first_s, 2),
            "ms_all_slots": round(ms, 3), "us_a_call": round(1e3 * ms / slots, 2),
        }
    row["ref_max_abs"] = round(scale, 3)
    print(json.dumps(row), flush=True)
    return row["ok"]


# A self-speculative step over recurrent layers (`models/ling_flash.py`):
# (label, KDA layers, heads, head width), Ling-3.0-flash's held group.
KDA_STATES = ("ling-flash two positions over six KDA states", 6, 32, 128)
REHEARSAL_KDA_STATES = ("toy two positions over two KDA states", 2, 2, 16)


def kda_keep_row(rehearsal: bool) -> bool:
    """A drafting step's delta rule over every KDA layer's float32 matrix
    states, two positions a step, the draft kept or dropped by a drawn
    bit, in the two forms a model could take, many steps inside one
    jitted loop: `slots` (what `ling_flash.kda_cached` does: two slots a
    layer in an array of the layer's own, the state after the first
    position into the one that does not stand, that after the second
    over the one read, `standing` flips a bit) and `select` (one slot a layer; every layer's
    pair of states kept to the step's end, then one `where` between
    them). They have to agree to the digit; the row prints us a step of
    each, which is what PERF.md §6 (PR 45) sets the choice on."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from comfyui_distributed_tpu.models.kda import kda_step
    from comfyui_distributed_tpu.models.ling_flash import standing

    label, layers, heads, d = REHEARSAL_KDA_STATES if rehearsal else KDA_STATES
    steps = 8 if rehearsal else 256

    def two_positions(state, q, k, v, g, beta):
        out_1, state_1 = kda_step(q[0], k[0], v[0], g[0], beta[0], state)
        out_2, state_2 = kda_step(q[1], k[1], v[1], g[1], beta[1], state_1)
        return out_1 + out_2, state_1, state_2

    @jax.jit
    def slots(states, xs):
        def body(carry, x):
            pairs, slot = carry
            *operands, kept = x
            outs, written = [], []
            for layer in range(layers):
                out, first, second = two_positions(
                    pairs[layer][slot], *(a[layer] for a in operands))
                pair = jax.lax.dynamic_update_slice(pairs[layer], second[None], (slot, 0, 0, 0))
                written.append(
                    jax.lax.dynamic_update_slice(pair, first[None], (1 - slot, 0, 0, 0)))
                outs.append(out)
            return (tuple(written), standing(slot, kept)), jnp.stack(outs)
        pairs = tuple(jnp.stack([state, jnp.zeros_like(state)]) for state in states)
        (pairs, slot), outs = jax.lax.scan(body, (pairs, jnp.int32(0)), xs)
        return jnp.stack([pair[slot] for pair in pairs]), outs

    @jax.jit
    def select(states, xs):
        def body(states, x):
            *operands, kept = x
            outs, firsts, seconds = zip(*(
                two_positions(states[layer], *(a[layer] for a in operands))
                for layer in range(layers)))
            return jnp.where(kept, jnp.stack(seconds), jnp.stack(firsts)), jnp.stack(outs)
        return jax.lax.scan(body, states, xs)

    keys = jax.random.split(jax.random.key(layers * 1000 + heads), 7)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    shape = (steps, layers, 2, heads, d)
    xs = (
        unit(jax.random.normal(keys[0], shape)) * d ** -0.5, unit(jax.random.normal(keys[1], shape)),
        jax.random.normal(keys[2], shape),
        -5.0 * jax.nn.sigmoid(jax.random.normal(keys[3], shape) - 6.0),
        jax.nn.sigmoid(jax.random.normal(keys[4], shape[:-1])),
        jax.random.bernoulli(keys[5], 0.5, (steps,)),
    )
    states = 0.1 * jax.random.normal(keys[6], (layers, heads, d, d))
    state_bytes = layers * heads * d * d * 4
    row = {"shape": label, "layers": layers, "heads": heads, "d": d, "dtype": "float32",
           "steps": steps, "state_mb": round(state_bytes / 1e6, 2), "ok": True}
    results = {}
    for name, fn in (("slots", slots), ("select", select)):
        (final, outs), first_s, ms = timed(fn, states, xs)
        results[name] = (np.asarray(final), np.asarray(outs))
        row[name] = {"first_call_s": round(first_s, 2), "us_a_step": round(1e3 * ms / steps, 2)}
    for mine, theirs in zip(results["slots"], results["select"]):
        err = float(np.abs(mine - theirs).max())
        row["ok"] &= bool(np.isfinite(err)) and err <= 1e-4 * max(1.0, float(np.abs(theirs).max()))
    row["max_abs_diff"] = round(float(np.abs(results["slots"][0] - results["select"][0]).max()), 7)
    print(json.dumps(row), flush=True)
    return row["ok"]


# A prefill's chunked delta rule over one KDA layer (`models/kda.
# kda_chunked`): (label, tokens, heads, head width, chunk), the cells'
# prompt at Ling-3.0-flash's and at Solar-Open2's held heads.
KDA_DELTA_SHAPES = (
    ("ling-flash a KDA layer's prefill", 8192, 32, 128, 64),
    ("solar-open2 a KDA layer's prefill", 8192, 64, 128, 64),
)
REHEARSAL_KDA_DELTA_SHAPES = (("toy a KDA layer's prefill", 72, 3, 128, 32),)
# Heads a grid step, timed beside the plan's: `ops/kda_delta.MAX_HEADS`
# rests on these.
KDA_DELTA_SWEEP = (1, 2, 4, 8)
KDA_DELTA_TOLERANCE = 2e-3


def kda_delta_row(rehearsal: bool, label, tokens, heads, d, chunk) -> bool:
    """The delta rule over a whole prompt in its two forms, alone: the
    kernel (`ops/kda_delta.kda_delta`) and the XLA form
    (`models/kda.kda_chunked_scan`), each between the [T, H*d] arrays a
    model's projections give and take, bfloat16 as stored. The row
    prints us a (head, chunk) of each, the largest difference of their
    outputs and of their final states relative to the largest entry,
    and the kernel at other heads a grid step (`sweep_us`)."""
    import jax
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models.kda import kda_chunked_scan
    from comfyui_distributed_tpu.ops import kda_delta

    @jax.jit
    def operands(key):
        keys = jax.random.split(key, 6)
        unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
        shape = (tokens, heads, d)
        flat = lambda x, dtype: x.reshape(tokens, heads * d).astype(dtype)
        return (
            flat(unit(jax.random.normal(keys[0], shape)) * d ** -0.5, jnp.bfloat16),
            flat(unit(jax.random.normal(keys[1], shape)), jnp.bfloat16),
            flat(jax.random.normal(keys[2], shape), jnp.bfloat16),
            flat(-5.0 * jax.nn.sigmoid(jax.random.normal(keys[3], shape) - 3.0), jnp.float32),
            2.0 * jax.nn.sigmoid(jax.random.normal(keys[4], (tokens, heads))),
            0.1 * jax.random.normal(keys[5], (heads, d, d)),
        )

    def as_served(form):
        def call(q, k, v, g, beta, state):
            o, state = form(*(a.reshape(tokens, heads, d) for a in (q, k, v, g)), beta, state)
            return o.reshape(tokens, heads * d), state
        return jax.jit(call)

    def kernel(group=None):
        return as_served(functools.partial(
            kda_delta.kda_delta, chunk=chunk, group=group, interpret=rehearsal))

    xs = operands(jax.random.key(tokens + heads * 7 + d))
    pairs = heads * -(-tokens // chunk)
    plan = kda_delta.delta_plan(heads, d, chunk, 2)
    row = {"shape": label, "tokens": tokens, "heads": heads, "d": d, "chunk": chunk,
           "dtype": "bfloat16", "heads_a_step": plan, "ok": True}
    results = {}
    for name, fn in (("kernel", kernel()), ("scan", as_served(
            functools.partial(kda_chunked_scan, chunk=chunk)))):
        results[name], first_s, ms = timed(fn, *xs)
        row[name] = {"first_call_s": round(first_s, 2), "ms": round(ms, 3),
                     "us_a_head_chunk": round(1e3 * ms / pairs, 3)}
    for what, mine, theirs in zip(("o", "state"), results["kernel"], results["scan"]):
        diff = float(jnp.max(jnp.abs(mine - theirs)) / jnp.max(jnp.abs(theirs)))
        row[f"max_rel_diff_{what}"] = round(diff, 7)
        row["ok"] &= diff <= KDA_DELTA_TOLERANCE  # a NaN fails it too
    row["sweep_us"] = {
        str(group): round(1e3 * timed(kernel(group), *xs)[2] / pairs, 3)
        for group in KDA_DELTA_SWEEP if group != plan and group <= heads}
    print(json.dumps(row), flush=True)
    return row["ok"]


# A Mamba-2 layer's recurrence in its two forms (`models/mamba2`), as
# Nemotron-3-Nano's cell runs them (the chunked scan over the 8,192-token
# prompt: 64 heads of 64 over a state of 128, B and C in 8 groups, chunks
# of 128; the decode's step over 23 blocks' states inside one jitted
# loop) and as granite-4.0-h-micro's does (a part of 8,192 of its
# document: one group, chunks of 256, 36 layers): (label, tokens, heads,
# width, groups, state, chunk, blocks).
SSD_SHAPES = (
    ("nemotron3-nano mamba-2", 8192, 64, 64, 8, 128, 128, 23),
    ("granite-4.0-h-micro mamba-2", 8192, 64, 64, 1, 128, 256, 36),
)
# the second on the lane tile, so that the rehearsal runs the kernel too (interpreted)
REHEARSAL_SSD_SHAPES = (
    ("toy mamba-2", 75, 4, 8, 2, 16, 32, 3),
    ("toy mamba-2 on the lane tile", 200, 4, 64, 2, 128, 128, 2),
)
# Heads a grid step, timed beside the plan's: `ops/ssd_chunk.MAX_HEADS`
# rests on these.
SSD_SWEEP = (2, 4, 8, 16)
SSD_TOLERANCE = 2e-2  # bfloat16 operands against the float32 recurrence, of the largest entry


def ssd_row(rehearsal: bool, label, tokens, heads, width, groups, n, chunk, blocks) -> bool:
    """The chunked scan over a whole prompt (bfloat16 operands as
    stored, float32 state) in its two forms, alone: the XLA form
    (`mamba2.ssd_chunked_xla`) and, where the shape has a plan, the
    kernel (`ops/ssd_chunk.ssd_chunk`), each against the recurrence
    token by token in float32: ms a layer's prefill and the largest
    difference of outputs and final states relative to the largest
    entry, the kernel at other heads a grid step (`sweep_ms`), and which
    of the two `mamba2.ssd_chunked` takes here (`route`); then a decode
    step's update of `blocks` states (`ssm_step`, a block an iteration
    of one jitted loop): us a block and GB/s of the states read and
    written."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from comfyui_distributed_tpu.models import mamba2
    from comfyui_distributed_tpu.ops import ssd_chunk

    @jax.jit
    def operands(key):
        keys = jax.random.split(key, 6)
        u = jax.random.normal(keys[0], (tokens, heads, width))
        b = jax.random.normal(keys[1], (tokens, groups, n)) * n ** -0.5
        c = jax.random.normal(keys[2], (tokens, groups, n))
        steps = mamba2.init_steps(keys[3], (heads,), 0.001, 0.1, 1e-4)
        step = jax.nn.softplus(jax.random.normal(keys[4], (tokens, heads)) + steps["dt_bias"])
        return u, b, c, step, -jnp.exp(steps["a_log"]), jnp.zeros((heads, width, n))

    u, b, c, step, a, state = operands(jax.random.key(tokens + heads))
    low = tuple(t.astype(jnp.bfloat16) for t in (u, b, c))

    def kernel(block=None):
        return functools.partial(
            ssd_chunk.ssd_chunk, chunk=chunk, block=block, interpret=rehearsal)

    @jax.jit
    def recurrence(u, b, c, step, a, state):
        def token(state, xs):
            y, state = mamba2.ssm_step(*xs, a, state)
            return state, y
        state, y = jax.lax.scan(token, state, (u, b, c, step))
        return y, state

    y_ref, after_ref = recurrence(*(t.astype(jnp.float32) for t in low), step, a, state)
    plan = ssd_chunk.chunk_plan(heads, width, groups, n, chunk, 2)
    row = {"shape": label, "tokens": tokens, "heads": heads, "width": width, "groups": groups,
           "state": n, "chunk": chunk, "dtype": "bfloat16", "heads_a_step": plan,
           "route": ssd_chunk.ssd_route(heads, width, groups, n, chunk, jnp.bfloat16),
           "max_rel_diff_y": 0.0, "max_rel_diff_state": 0.0, "ok": True}
    forms = [("xla", jax.jit(functools.partial(mamba2.ssd_chunked_xla, chunk=chunk)))]
    if plan:
        forms.append(("kernel", kernel()))
    for name, fn in forms:
        (y, after), first_s, ms = timed(fn, *low, step, a, state)
        row[name] = {"first_call_s": round(first_s, 2), "ms": round(ms, 3)}
        for what, mine, theirs in (("y", y, y_ref), ("state", after, after_ref)):
            diff = float(jnp.max(jnp.abs(mine - theirs)) / jnp.max(jnp.abs(theirs)))
            row[name][f"max_rel_diff_{what}"] = round(diff, 6)
            row[f"max_rel_diff_{what}"] = max(row[f"max_rel_diff_{what}"], round(diff, 6))
            row["ok"] &= diff <= SSD_TOLERANCE  # a NaN fails it too
    if plan:
        row["sweep_ms"] = {
            str(block): round(timed(kernel(block), *low, step, a, state)[2], 3)
            for block in SSD_SWEEP
            if block != plan and ssd_chunk.tiles_a_group(block, heads // groups, width)}

    @jax.jit
    def step_over_blocks(u, b, c, step, a, states):
        def block(_, state):
            y, state = mamba2.ssm_step(u, b, c, step, a, state)
            return None, (y, state)
        return jax.lax.scan(block, None, states)[1]

    states = jnp.broadcast_to(after_ref, (blocks, *after_ref.shape)) + 0.0
    one = (u[0], b[0], c[0], step[0], a)
    (ys, stepped), first_s, ms = timed(step_over_blocks, *one, states)
    want_y, want_state = mamba2.ssm_step(*one, after_ref)
    row["ok"] &= bool(np.allclose(np.asarray(stepped[-1]), np.asarray(want_state), atol=1e-5))
    row["ok"] &= bool(np.allclose(np.asarray(ys[0]), np.asarray(want_y), atol=1e-4))
    moved = 2 * blocks * heads * width * n * 4
    row["step"] = {"blocks": blocks, "first_call_s": round(first_s, 2),
                   "us_a_block": round(1e3 * ms / blocks, 2),
                   "gb_per_s": round(moved / (1e-3 * ms) / 1e9, 1)}
    print(json.dumps(row), flush=True)
    return row["ok"]


# Learned sparse attention as GLM-5.2's cell runs it: the last part of the
# 32,768-token prompt over caches of the request's full length, and a
# drafting step's two positions: (label, a part's queries, cache rows,
# positions chosen, heads, nope, rope, value width, latent rank, indexer
# heads, indexer width).
DSA_SHAPE = ("glm-5.2 dsa", 8192, 32896, 2048, 64, 192, 64, 256, 512, 32, 128)
REHEARSAL_DSA_SHAPE = ("toy dsa", 40, 72, 8, 4, 12, 8, 16, 24, 2, 16)
DSA_TOLERANCE = 2e-2  # one form's bfloat16 outputs against the other's, of the largest entry


def dsa_row(rehearsal: bool, label, queries, rows, top, heads, nope, rope, v, rank,
            index_heads, index_width) -> bool:
    """`models/dsa.py` at one part's shapes, bfloat16 as stored: ms of
    the indexer's scores, of the selection in each form (`lax.top_k`;
    the bisection's mask; the `dsa_select` kernel, interpreted in a
    rehearsal, and the first and the last again at the ladder's shorter
    rungs), of XLA's gather of the chosen rows with nothing
    behind it, and of attention in each form (`mla.absorbed` under the
    selection's mask a block of `dsa.BLOCK_ROWS` query rows at a time;
    the chosen rows gathered by XLA; gathered inside the `dsa_attend`
    kernel, interpreted in a rehearsal), the gathered forms from
    `lax.top_k`'s positions and from the kernel's ascending ones; that
    all selections are one set and the gathered forms the masked form's
    result (`DSA_TOLERANCE`);
    then a step's two positions, scores and selection and attention
    together, in either form."""
    import jax
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models import dsa, mla

    dtype = jnp.float32 if rehearsal else jnp.bfloat16

    @jax.jit
    def operands(key):
        keys = jax.random.split(key, 8)
        normal = lambda k, *shape: jax.random.normal(k, shape).astype(dtype)  # noqa: E731
        return (normal(keys[0], queries, index_heads, index_width),
                jax.random.normal(keys[1], (queries, index_heads)),
                normal(keys[2], rows, index_width), normal(keys[3], queries, heads, nope),
                normal(keys[4], queries, heads, rope), normal(keys[5], rows, rank + rope),
                (jax.random.normal(keys[6], (rank, heads, nope)) * rank ** -0.5).astype(dtype),
                (jax.random.normal(keys[7], (rank, heads, v)) * rank ** -0.5).astype(dtype))

    q_index, weights, cached, q_nope, q_rope, cache, w_uk, w_uv = operands(jax.random.key(rows))
    positions = jnp.arange(rows - queries, rows)
    scale = (nope + rope) ** -0.5
    def by_blocks(fn):
        return jax.jit(lambda *arrays: dsa.by_rows(fn, dsa.BLOCK_ROWS, *arrays))

    scores = by_blocks(lambda q, w, at: dsa.scores(q, w, cached, at))
    index, first_s, ms = timed(scores, q_index, weights, positions)
    row = {"shape": label, "queries": queries, "rows": rows, "top": top, "heads": heads,
           "dtype": jnp.dtype(dtype).name, "block_rows": dsa.BLOCK_ROWS,
           "scores": {"first_call_s": round(first_s, 2), "ms": round(ms, 3)}, "ok": True}
    selections = {}
    chooses = {"top_k": dsa.top, "bisection": dsa.above_threshold,
               "kernel": functools.partial(dsa.top_compacted, interpret=rehearsal)}
    for name, choose in chooses.items():
        selections[name], first_s, ms = timed(by_blocks(lambda i: choose(i, top)), index)
        row[f"select_{name}"] = {"first_call_s": round(first_s, 2), "ms": round(ms, 3)}
    masks = [dsa.as_mask(s, rows) for s in selections.values()]
    row["selections_equal"] = all(bool(jnp.array_equal(masks[0], m)) for m in masks[1:])
    row["ok"] &= row["selections_equal"]
    del masks
    # the ladder's shorter rungs, every key of them visible: the sort against the kernel
    for length in dsa.length_ladder(rows, top)[:-1]:
        cut, picked = index[:, :length], {}
        for name in ("top_k", "kernel"):
            picked[name], _, ms = timed(by_blocks(lambda i: chooses[name](i, top)), cut)
            row[f"select_{name}"][f"ms_at_{length}"] = round(ms, 3)
        row["ok"] &= bool(jnp.array_equal(*(dsa.as_mask(s, length) for s in picked.values())))
    del cut, picked

    gathered = selections["top_k"]
    _, first_s, ms = timed(
        jax.jit(lambda c: dsa.by_rows(lambda c: cache[c].sum(axis=1), dsa.ATTEND_ROWS, c)),
        gathered.chosen)
    row["gather_alone"] = {"first_call_s": round(first_s, 2), "ms": round(ms, 3)}
    on_all = lambda form, **how: jax.jit(lambda a, b, c, d: form(  # noqa: E731
        a, b, cache, dsa.Selection(c, d), w_uk, w_uv, scale, **how))
    forms = {
        "masked": by_blocks(lambda a, b, c, d: mla.absorbed(
            a, b, cache, dsa.as_mask(dsa.Selection(c, d), rows), w_uk, w_uv, scale)),
        "gathered": on_all(dsa.attend_gathered),
        "kernel": on_all(dsa.attend_kernel, interpret=rehearsal),
    }
    outs = {}
    for name, attend in forms.items():
        outs[name], first_s, ms = timed(attend, q_nope, q_rope, *gathered)
        row[f"attend_{name}"] = {"first_call_s": round(first_s, 2), "ms": round(ms, 3)}
    want = outs["masked"].astype(jnp.float32)
    for name in ("gathered", "kernel"):
        # from `lax.top_k`'s positions, then from the selection kernel's ascending ones
        ascending, _, ms = timed(forms[name], q_nope, q_rope, *selections["kernel"])
        row[f"attend_{name}"]["ms_ascending"] = round(ms, 3)
        for tag, out in (("max_rel_diff", outs[name]), ("max_rel_diff_ascending", ascending)):
            diff = jnp.max(jnp.abs(out.astype(jnp.float32) - want)) / jnp.max(jnp.abs(want))
            row[f"attend_{name}"][tag] = round(float(diff), 6)
            row["ok"] &= float(diff) <= DSA_TOLERANCE  # a NaN fails it too

    # a drafting step's two positions: what `glm_dsa.attention` runs of this module, and
    # the other form at the same two
    def step(choose):
        def run(q_index, weights, q_nope, q_rope, positions):
            selection = choose(dsa.scores(q_index, weights, cached, positions), top)
            return dsa.attend(q_nope, q_rope, cache, selection, w_uk, w_uv, scale)
        return jax.jit(run)

    last = (q_index[-2:], weights[-2:], q_nope[-2:], q_rope[-2:], positions[-2:])
    assert dsa.form(2) == "masked"
    steps = {}
    for form, choose in (("masked", dsa.above_threshold), ("gathered", dsa.top)):
        steps[form], first_s, ms = timed(step(choose), *last)
        row[f"step_{form}"] = {"first_call_s": round(first_s, 2), "us": round(1e3 * ms, 1)}
    diff = jnp.max(jnp.abs(steps["masked"].astype(jnp.float32) - steps["gathered"].astype(jnp.float32)))
    row["ok"] &= float(diff) <= DSA_TOLERANCE * float(jnp.max(jnp.abs(steps["masked"].astype(jnp.float32))))
    print(json.dumps(row), flush=True)
    return row["ok"]


# --- the experts child -------------------------------------------------------

# (label, token-expert pairs a step, experts a token, held experts,
# experts in all, hidden, width): a decode step of each model with a
# mixture of experts as its benchmark cell runs it. DeepSeek-V2 a
# quarter of 160 experts, 6 a token; Solar-Open2 an eighth of 320, 8 a
# token; K-EXAONE an eighth of 128, 8 a token, two positions a drafting
# step and one in its MTP module; Ling-3.0-flash an eighth of 512, 8 a
# token, the narrowest (2,560 columns, 768-wide experts), two positions a
# drafting step and one a plain step; SDAR every one of 128, 8 a token,
# the four positions of a block a pass: 32 rows over the union of four
# choices, some 29 experts (PR 58). DeepSeek's shape a second time at 8
# rows: at a row count off the sublane tile the compiler gives
# `ragged_dot` another lowering (PERF.md §6, PR 42).
EXPERT_SHAPES = (
    ("deepseek-v2 step", 6, 6, 40, 160, 5120, 1536),
    ("deepseek-v2 step at 8 rows", 8, 8, 40, 160, 5120, 1536),
    ("solar-open2 step", 8, 8, 40, 320, 4096, 1280),
    ("k-exaone two positions", 16, 8, 16, 128, 6144, 2048),
    ("k-exaone mtp position", 8, 8, 16, 128, 6144, 2048),
    ("ling-flash two positions", 16, 8, 64, 512, 2560, 768),
    ("ling-flash step", 8, 8, 64, 512, 2560, 768),
    ("sdar pass of four positions", 32, 8, 128, 128, 2048, 768),
)
REHEARSAL_EXPERT_SHAPES = (
    ("toy step off the sublane tile", 6, 3, 4, 16, 128, 64),
    ("toy two positions", 16, 8, 4, 16, 256, 128),
)
# The same tuple for experts without a gate, relu(x W_up)^2 W_down, whose
# up-projection is stored out by in (`models/moe.gated`): Nemotron-3-Nano
# a sixteenth of 128, 6 a token, 1,856 columns, off the lane tile: the
# kernel's `[2688] -> [1856]` walk by rows of the stored array and its
# `[1856] -> [2688]` walk by columns (PR 48).
RELU2_EXPERT_SHAPES = (
    ("nemotron3-nano step", 6, 6, 8, 128, 2688, 1856),
)
REHEARSAL_RELU2_EXPERT_SHAPES = (
    ("toy step without a gate", 6, 3, 4, 16, 128, 48),
)
EXPERT_STEPS = 64


def step_sizes(seed: int, steps: int, rows: int, k: int, held: int, experts: int):
    """[steps, held] rows on each held expert, a step's `rows / k` tokens
    each choosing `k` distinct experts of `experts` evenly: held are the
    ids below `held`."""
    import numpy as np

    rng = np.random.default_rng(seed)
    sizes = np.zeros((steps, held), np.int32)
    for step in range(steps):
        for _ in range(rows // k):
            chosen = rng.choice(experts, size=k, replace=False)
            np.add.at(sizes[step], chosen[chosen < held], 1)
    return sizes


def experts_child(rehearsal: bool) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    if not child_device(rehearsal):
        return 1
    from comfyui_distributed_tpu.models.moe import decode_route
    from comfyui_distributed_tpu.ops.expert_matvec import expert_matvec, grouped_xla

    def steps_of(grouped, gated, dtype=None):
        """Every step's two products, a step an iteration of one loop: a
        SwiGLU's, or relu squared between an up-projection stored out by
        in and a down-projection."""
        def loop(x, w_first, w_down, sizes):
            def body(_, step):
                rows, sizes_i = step
                if gated:
                    gate, up = jnp.split(grouped(rows, w_first, sizes_i), 2, axis=-1)
                    middle = jax.nn.silu(gate) * up
                else:
                    middle = jnp.square(jax.nn.relu(grouped(rows, w_first, sizes_i, out_major=True)))
                return None, grouped(middle, w_down, sizes_i)
            if dtype is not None:
                x, w_first, w_down = (a.astype(dtype) for a in (x, w_first, w_down))
            return jax.lax.scan(body, None, (x, sizes))[1]
        return jax.jit(loop)

    def in_float32(rows, weights, sizes, out_major=False):
        # on whole sublane tiles of rows: at 6 rows the compiler's float32
        # lowering read 3.4 off both bfloat16 forms, which agree with each
        # other to the digit and with the model's reference (PERF.md §6, PR 42)
        # weights stored out by in are turned round first: in float32 the compiler's
        # grouped product over the stored array read 5.6 off both bfloat16 forms, which
        # agree with each other to the digit (my chip run, PR 48)
        padded = jnp.pad(rows, ((0, -rows.shape[0] % 8), (0, 0)))
        if out_major:
            weights = weights.swapaxes(1, 2)
        return jax.lax.ragged_dot(
            padded, weights, sizes, precision=jax.lax.Precision.HIGHEST)[:rows.shape[0]]

    failed = 0
    steps = 4 if rehearsal else EXPERT_STEPS
    shapes = [(True, *shape) for shape in (
        REHEARSAL_EXPERT_SHAPES if rehearsal else EXPERT_SHAPES)] + [(False, *shape) for shape in (
            REHEARSAL_RELU2_EXPERT_SHAPES if rehearsal else RELU2_EXPERT_SHAPES)]
    for gated, label, rows, k, held, experts, hidden, width in shapes:
        keys = jax.random.split(jax.random.key(hidden + rows), 3)
        x = jax.random.normal(keys[0], (steps, rows, hidden), jnp.bfloat16)
        first = (held, hidden, 2 * width) if gated else (held, width, hidden)
        w_gate_up = jax.jit(lambda key: hidden ** -0.5 * jax.random.normal(
            key, first, jnp.bfloat16))(keys[1])
        w_down = jax.jit(lambda key: width ** -0.5 * jax.random.normal(
            key, (held, width, hidden), jnp.bfloat16))(keys[2])
        sizes = step_sizes(rows * 1000 + held, steps, rows, k, held, experts)
        # what a step has to read: the chosen held experts' matrices
        matrices = 3 if gated else 2
        step_bytes = np.count_nonzero(sizes, axis=1).mean() * matrices * hidden * width * 2
        route = decode_route(rows, hidden, width, jnp.bfloat16, gated)
        row = {
            "shape": label, "rows": rows, "held": held, "hidden": hidden, "width": width,
            "dtype": "bfloat16", "gated": gated, "route": route,
            "chosen_a_step": round(float(np.count_nonzero(sizes, axis=1).mean()), 3),
            "held_pairs_a_step": round(float(sizes.sum(axis=1).mean()), 3),
            "mb_a_step": round(step_bytes / 1e6, 2),
            "ok": rehearsal or route == "kernel",
        }
        operands = (x, w_gate_up, w_down, jnp.asarray(sizes))
        ref = np.asarray(steps_of(in_float32, gated, jnp.float32)(*operands))
        # a row past the step's held pairs is nobody's: the kernel leaves
        # it zero, `ragged_dot` what it likes
        mine = (np.arange(rows)[None, :] < sizes.sum(axis=1)[:, None])[:, :, None]
        scale = max(1.0, float(np.abs(np.where(mine, ref, 0.0)).max()))
        for name, grouped in (
            ("kernel", functools.partial(expert_matvec, interpret=rehearsal)),
            ("xla", grouped_xla),
        ):
            fn = steps_of(grouped, gated)
            if name == "xla" and not rehearsal:
                # off the sublane tile the compiler has no grouped kernel
                # of its own and multiplies under a mask a group
                text = fn.lower(*operands).compile().as_text()
                row["xla_lowering"] = (
                    "masked convolution" if " convolution(" in text else "custom call")
            out, first_s, ms = timed(fn, *operands)
            # picked out, not multiplied by a mask: what `ragged_dot` likes there may be
            # no number (Ling-3.0-flash's shapes on the chip, PR 45)
            err = float(np.abs(np.where(mine, np.asarray(out, np.float32) - ref, 0.0)).max())
            row["ok"] &= bool(np.isfinite(err)) and err <= ATTENTION_TOLERANCE * scale
            row[name] = {
                "max_abs_err": round(err, 5), "first_call_s": round(first_s, 2),
                "us_a_step": round(1e3 * ms / steps, 2),
                "gb_per_s": round(step_bytes / (1e-3 * ms / steps) / 1e9, 1),
            }
        row["ref_max_abs"] = round(scale, 3)
        failed += not row["ok"]
        print(json.dumps(row), flush=True)
    failed += prefill_expert_rows(rehearsal)
    return 1 if failed else 0


# (label, tokens a call of `moe.expert_layer`, experts a token, held
# experts, the router's width, hidden, width, whether the expert has a
# gate, whether its weights are a layer of a stack): a prefill (or a
# prefill's part or block) of each model with a mixture of experts as its
# benchmark cell runs it (PR 64). DeepSeek-V2 and SDAR prefill 2,048
# tokens, Solar, K-EXAONE, Ling and Nemotron 8,192, GLM-5.2 and dots3
# parts of 8,192, LongCat-Flash blocks of 1,024 under a router of 768
# outputs; Nemotron's experts have no gate, an up-projection stored out by
# in, and stand in a stack a scanned run.
PREFILL_EXPERT_SHAPES = (
    ("deepseek-v2 prefill", 2048, 6, 40, 160, 5120, 1536, True, False),
    ("solar-open2 prefill", 8192, 8, 40, 320, 4096, 1280, True, False),
    ("k-exaone prefill", 8192, 8, 16, 128, 6144, 2048, True, False),
    ("ling-flash prefill", 8192, 8, 64, 512, 2560, 768, True, False),
    ("nemotron3-nano prefill", 8192, 6, 8, 128, 2688, 1856, False, True),
    ("glm-5.2 part", 8192, 8, 16, 256, 6144, 2048, True, False),
    ("sdar prefill", 2048, 8, 128, 128, 2048, 768, True, False),
    ("dots3-note-prev part", 8192, 8, 32, 256, 5120, 1536, True, False),
    ("longcat-flash block", 1024, 12, 8, 768, 6144, 2048, True, False),
)
REHEARSAL_PREFILL_EXPERT_SHAPES = (
    ("toy prefill", 512, 3, 4, 16, 128, 64, True, False),
    ("toy prefill without a gate, stacked", 512, 3, 4, 16, 128, 48, False, True),
    ("toy block whose lowest rung is a tile", 256, 3, 4, 16, 128, 64, True, False),
)
PREFILL_EXPERT_STEPS = 8
# what a rehearsal cuts the kernel's tiles to, so that a toy rung has several
REHEARSAL_GMM_CAPS = (128, 32, 2**19)


def prefill_routings(seed: int, steps: int, tokens: int, k: int, held: int, experts: int,
                     most: int):
    """`steps` routings of `tokens` tokens, each choosing `k` distinct
    experts of `experts` evenly, whose pairs on the held experts (the ids
    below `held`) are at most `most`: ids [steps, tokens, k]."""
    import numpy as np

    rng = np.random.default_rng(seed)
    kept = []
    while len(kept) < steps:
        ids = np.argsort(rng.random((tokens, experts)), axis=1)[:, :k]
        if np.count_nonzero(ids < held) <= most:
            kept.append(ids)
    return np.stack(kept)


def prefill_expert_rows(rehearsal: bool) -> int:
    """A row a model and rung (the ladder's lowest two; the layer gives
    the kernel the lowest): the rung's row gather, two grouped products and weighted way
    back as `moe.expert_layer` runs them, a routing an iteration of one
    jitted loop, on `jax.lax.ragged_dot` and on `ops/grouped_matmul`:
    ms a call of each, their ratio, and the largest difference between
    their results over the largest result. `route` is what the layer's
    lowest rung takes on this backend (`moe.prefill_route`). Returns
    the rows that failed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from comfyui_distributed_tpu.models import moe
    from comfyui_distributed_tpu.ops import grouped_matmul as gmm
    from comfyui_distributed_tpu.ops.expert_matvec import grouped_xla

    def calls_of(grouped, k, gated, stacked, tokens):
        def loop(x, w_first, w_down, top, sizes, scale):
            index = jnp.int32(1) if stacked else None

            def body(_, step):
                top_i, sizes_i, scale_i = step
                token = top_i // k
                rows = x[token]
                if gated:
                    gate, up = jnp.split(grouped(rows, w_first, sizes_i, index), 2, axis=-1)
                    middle = jax.nn.silu(gate) * up
                else:
                    middle = jnp.square(jax.nn.relu(
                        grouped(rows, w_first, sizes_i, index, out_major=True)))
                out = grouped(middle, w_down, sizes_i, index)
                # a row past the held pairs has scale 0, and is picked out, not multiplied
                out = jnp.where(scale_i[:, None] > 0, out, 0).astype(jnp.float32) * scale_i[:, None]
                edges = list(range(moe.SCATTER_LANES, out.shape[1], moe.SCATTER_LANES))
                return None, jnp.concatenate([
                    jnp.zeros((tokens, part.shape[1]), jnp.float32).at[token].add(part)
                    for part in jnp.split(out, edges, axis=1)], axis=1)
            return jax.lax.scan(body, None, (top, sizes, scale))[1]
        return jax.jit(loop)

    failed = 0
    steps = 2 if rehearsal else PREFILL_EXPERT_STEPS
    if rehearsal:  # this child runs the leg and nothing else: small tiles for the interpreter
        gmm.TILE_ROWS, gmm.BLOCK_ROWS, gmm.VMEM_BLOCK_BUDGET = REHEARSAL_GMM_CAPS
    kernel = functools.partial(gmm.grouped_matmul, interpret=rehearsal)
    for label, tokens, k, held, experts, hidden, width, gated, stacked in (
            REHEARSAL_PREFILL_EXPERT_SHAPES if rehearsal else PREFILL_EXPERT_SHAPES):
        keys = jax.random.split(jax.random.key(hidden + tokens), 3)
        x = jax.random.normal(keys[0], (tokens, hidden), jnp.bfloat16)
        lead = (2,) if stacked else ()
        first = (*lead, held, hidden, 2 * width) if gated else (*lead, held, width, hidden)
        w_first = jax.jit(lambda key: hidden ** -0.5 * jax.random.normal(
            key, first, jnp.bfloat16))(keys[1])
        w_down = jax.jit(lambda key: width ** -0.5 * jax.random.normal(
            key, (*lead, held, width, hidden), jnp.bfloat16))(keys[2])
        ladder = moe.row_ladder(tokens * k, held, experts)
        for rung in ladder[:2]:
            ids = prefill_routings(tokens + rung, steps, tokens, k, held, experts, rung)
            slot = np.where(ids < held, ids, held).reshape(steps, -1)
            order = np.argsort(slot, axis=1, kind="stable")
            top = order[:, :rung]
            sizes = np.stack([np.bincount(s, minlength=held + 1)[:held] for s in slot])
            scale = np.take_along_axis(slot, top, axis=1) < held
            scale = scale * np.random.default_rng(rung).uniform(0.05, 0.2, scale.shape)
            row = {
                "shape": f"{label}, rung {rung}", "tokens": tokens, "rows": rung, "held": held,
                "hidden": hidden, "width": width, "gated": gated, "stacked": stacked,
                "held_pairs_a_call": round(float(sizes.sum(axis=1).mean()), 1),
                "route": moe.prefill_route(
                    tokens, k, held, experts, hidden, width, jnp.bfloat16, with_gate=gated),
            }
            operands = (x, w_first, w_down, jnp.asarray(top, jnp.int32),
                        jnp.asarray(sizes, jnp.int32), jnp.asarray(scale, jnp.float32))
            outs = {}
            for name, grouped in (("xla", grouped_xla), ("kernel", kernel)):
                outs[name], first_s, ms = timed(calls_of(grouped, k, gated, stacked, tokens), *operands)
                row[name] = {"first_call_s": round(first_s, 2), "ms_a_call": round(ms / steps, 4)}
                if rung <= gmm.MAX_ROWS:  # a tile or fewer rows (LongCat-Flash's lowest): no plan
                    row.update(kernel=None, ok=True)
                    break
            if row.get("ok"):
                print(json.dumps(row), flush=True)
                continue
            row["xla_over_kernel"] = round(row["xla"]["ms_a_call"] / row["kernel"]["ms_a_call"], 3)
            want = np.asarray(outs["xla"], np.float32)
            diff = float(np.abs(np.asarray(outs["kernel"], np.float32) - want).max())
            row["max_rel_diff"] = round(diff / max(float(np.abs(want).max()), 1e-30), 6)
            row["ok"] = bool(np.isfinite(diff)) and row["max_rel_diff"] <= ATTENTION_TOLERANCE
            failed += not row["ok"]
            print(json.dumps(row), flush=True)
    return failed


# --- entry -----------------------------------------------------------------


INIT_BUNDLES = ("sd15", "sdxl", "flux-dev-5x10")
REHEARSAL_INIT_BUNDLES = ("tiny-unet",)


def parent_init_params():
    """`init_params` of the commit unpacked at scratch/parent, its
    `models/pipeline.py` loaded beside this checkout's (whose other
    modules it imports); None where no commit is unpacked there."""
    path = os.path.join(HERE, "scratch", "parent", PACKAGE, "models", "pipeline.py")
    if not os.path.exists(path):
        return None
    name = f"{PACKAGE}.models._parent_pipeline"
    spec = importlib.util.spec_from_file_location(name, path)
    module = sys.modules[name] = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.init_params


def init_child(rehearsal: bool) -> int:
    import jax
    import numpy as np

    if rehearsal:
        # the CPU keeps float32 by itself; the leg is about the stored form
        os.environ["CDT_PARAMS_DTYPE"] = "bfloat16"
    if not child_device(rehearsal):
        return 1
    jax.config.update("jax_enable_compilation_cache", False)  # every build here is a cold one
    from comfyui_distributed_tpu.models import pipeline as pl

    parent_init, load_init = parent_init_params(), pl.init_params
    leaves, failed = jax.tree_util.tree_leaves, 0

    def bits(x):
        x = np.asarray(x)
        return x.view(f"u{x.dtype.itemsize}")

    def measured(bundle):
        def init_params(module, key, *args, settle=True, **kwargs):
            nonlocal failed
            row = {"shape": f"{bundle} {type(module).__name__}"}
            theirs = None
            if parent_init is not None:
                # first, and moved to the host: the two trees never share the chip
                started = time.perf_counter()
                tree = jax.block_until_ready(parent_init(module, key, *args, **kwargs))
                row["parent_s"] = round(time.perf_counter() - started, 2)
                theirs = [bits(x) for x in leaves(tree)]
                del tree
            started = time.perf_counter()
            program = pl.init_program(module, pl.params_storage_dtype(), key, *args, **kwargs)
            compiled = program.lower(key).compile()
            row["compile_s"] = round(time.perf_counter() - started, 2)
            started = time.perf_counter()
            mine = jax.block_until_ready(compiled(key))
            row["run_s"] = round(time.perf_counter() - started, 2)
            built = leaves(mine)
            stored = sum(x.nbytes for x in built)
            temp = compiled.memory_analysis().temp_size_in_bytes
            row.update(
                weights=len(built), values=sum(x.size for x in built),
                stored_bytes=stored, temp_bytes=temp, temp_share=round(temp / stored, 4),
            )
            # the CPU does not fuse threefry's passes into the rounding: the bound is a TPU's
            row["ok"] = rehearsal or temp <= stored / 4
            if theirs is not None:
                row["ok"] &= [x.shape for x in theirs] == [x.shape for x in built]
                if row["ok"]:
                    row["differ"] = sum(
                        int(np.count_nonzero(bits(a) != b)) for a, b in zip(built, theirs)
                    )
                    row["differ_share"] = row["differ"] / row["values"]
            failed += not row["ok"]
            print(json.dumps(row), flush=True)
            return mine
        return init_params

    for bundle in REHEARSAL_INIT_BUNDLES if rehearsal else INIT_BUNDLES:
        pl.init_params = measured(bundle)
        try:
            pl.load_pipeline(bundle)
        finally:
            pl.init_params = load_init
    return 1 if failed else 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--rehearsal", type=int, nargs="?", const=1, default=None, metavar="N",
        help="debug run without a chip: tiny-unet, 64 px, --platform cpu on "
             "N virtual devices (default 1), Pallas interpreted",
    )
    parser.add_argument(
        "--legs", default=",".join(DEFAULT_LEGS),
        help=f"comma list of legs to run, of {','.join(LEGS)} (default: "
             f"{','.join(DEFAULT_LEGS)}; multichip runs only where there are "
             "two or more chips)",
    )
    parser.add_argument(
        "--out", default=os.path.join(HERE, "chiprun_out", "chip_smoke"),
        help="output directory; emptied first",
    )
    parser.add_argument("--port", type=int, default=18188)
    parser.add_argument("--attention-child", action="store_true", help=argparse.SUPPRESS)
    parser.add_argument("--experts-child", action="store_true", help=argparse.SUPPRESS)
    parser.add_argument("--init-child", action="store_true", help=argparse.SUPPRESS)
    args = parser.parse_args(argv)

    if args.attention_child:
        return attention_child(bool(args.rehearsal))
    if args.experts_child:
        return experts_child(bool(args.rehearsal))
    if args.init_child:
        return init_child(bool(args.rehearsal))

    legs = [leg.strip() for leg in args.legs.split(",") if leg.strip()]
    unknown = sorted(set(legs) - set(LEGS))
    if unknown:
        parser.error(f"unknown leg(s) {unknown}")
    run = Run(args)
    shutil.rmtree(run.out, ignore_errors=True)
    os.makedirs(run.out)
    started = time.monotonic()
    if run.rehearsal:
        say(
            f"REHEARSAL on {run.rehearsal} virtual CPU device(s): tiny-unet, "
            "64 px, Pallas interpreted — this checks the command, not the chip"
        )
    say(
        f"output under {run.out}; JAX_COMPILATION_CACHE_DIR="
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR') or '<unset: in-checkout default>'}"
    )
    try:
        write_input_image(run)
        if "serve" in legs:
            run.attempt("serve", lambda: leg_serve(run))
        if "restart" in legs:
            run.attempt("restart", lambda: leg_restart(run))
        if "attention" in legs:
            run.attempt("attention", lambda: leg_child(run, "attention"))
        if "experts" in legs:
            run.attempt("experts", lambda: leg_child(run, "experts"))
        if "multichip" in legs and run.device and run.device["count"] >= 2:
            run.attempt("multichip", lambda: leg_multichip(run))
        if "init" in legs:
            run.attempt("init", lambda: leg_child(run, "init", limit_s=3000))
    finally:
        run.stop_children()
    say(f"finished in {time.monotonic() - started:.1f}s")
    if run.device is None:
        run.failures.append("no server reported a device")
    if run.failures:
        # each was printed in full where it happened; the last lines
        # carry one line apiece
        for failure in run.failures:
            say(f"FAILED {failure.splitlines()[0]}")
        return 1
    result = {"ok": True, "device": run.device}
    if run.rehearsal:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
