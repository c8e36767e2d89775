"""The served path's span tree: queue wait, nodes, device waits and the
PNG save in the request's one trace; the profiler mirror; the set-up
split (one `program.build` span a program, the counter, the file beside
a capture). All on a tracer whose clock ticks once per reading, so every
duration is a pure function of the span sequence."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from comfyui_distributed_tpu.api.server import DistributedServer
from comfyui_distributed_tpu.graph.executor import ExecutionContext, GraphExecutor
from comfyui_distributed_tpu.graph.registry import NODE_REGISTRY
from comfyui_distributed_tpu.resilience.chaos import FakeClock
from comfyui_distributed_tpu.telemetry import Tracer, get_tracer, set_tracer
from comfyui_distributed_tpu.telemetry import profiling, runtime, tracing
from comfyui_distributed_tpu.telemetry.metrics import get_metrics_registry

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
FETCH_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SpanTestImage:
    """A cacheable source node: a host image, so nothing is compiled."""

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"value": ("FLOAT", {"default": 0.5})}}

    RETURN_TYPES = ("IMAGE",)
    FUNCTION = "make"

    def make(self, value):
        # what a node that builds a program makes JAX report
        report_program("make", at=100.0, trace=0.25, lower=0.125, fetch=0.5, compile_=0.75)
        return (np.full((1, 8, 8, 3), float(value), np.float32),)


def report_program(name, at, trace=0.0, lower=0.0, fetch=None, compile_=None, inner=()):
    """The events of one program's way to the device as JAX reports
    them on the calling thread: the trace of `name` from `at` (with
    `inner` jits' traces inside it, as (name, offset, seconds)), the
    lowering and the backend compile of `jit(name)` after it, the
    compile holding a cache retrieval of `fetch` seconds. `at` is on
    the listener's clock, whatever JAX's own reads."""
    from jax import monitoring

    at -= runtime._clock_offset
    for inner_name, offset, seconds in inner:
        monitoring.record_event_time_span(
            TRACE_EVENT, at + offset, at + offset + seconds, fun_name=inner_name)
    monitoring.record_event_time_span(TRACE_EVENT, at, at + trace, fun_name=name)
    at += trace
    if lower:
        monitoring.record_event_time_span(LOWER_EVENT, at, at + lower, fun_name=f"jit({name})")
        at += lower
    if compile_ is not None:
        if fetch is not None:
            monitoring.record_event("/jax/compilation_cache/cache_hits")
            monitoring.record_event_duration_secs(FETCH_EVENT, fetch)
        monitoring.record_event_duration_secs(COMPILE_EVENT, compile_, fun_name=f"jit({name})")
        monitoring.record_event_time_span(
            COMPILE_EVENT, at, at + compile_, fun_name=f"jit({name})")


def graph(value=0.5):
    return {
        "1": {"class_type": "SpanTestImage", "inputs": {"value": value}},
        "2": {"class_type": "SaveImage",
              "inputs": {"images": ["1", 0], "filename_prefix": "spans"}},
    }


@pytest.fixture()
def tracer():
    ticking = Tracer(clock=FakeClock(step=1.0))
    set_tracer(ticking)
    return ticking


@pytest.fixture()
def server(tmp_config_path, tmp_path, monkeypatch, tracer):
    monkeypatch.setitem(NODE_REGISTRY, "SpanTestImage", SpanTestImage)
    monkeypatch.setenv("CDT_OUTPUT_DIR", str(tmp_path / "out"))
    runtime.install_jax_monitoring()
    return DistributedServer(port=0, is_worker=True)


def run_queued(server):
    """The executor thread's loop, in this thread, until the queue is empty."""
    server._prompt_queue.put(None)
    server._executor_loop()


def by_name(tracer, trace_id):
    out = {}
    for span in tracer.spans(trace_id):
        out.setdefault(span["name"], []).append(span)
    return out


def test_a_request_is_one_tree_from_queue_wait_to_nodes(server, tracer):
    job = server.queue_prompt(graph(), "p1")
    run_queued(server)
    assert job.error is None
    (root,) = tracer.tree("p1")
    assert root["name"] == "prompt_queue.wait" and root["attrs"] == {"depth": 0}
    (execute,) = root["children"]
    assert execute["name"] == "execute_prompt"
    assert execute["attrs"]["nodes_run"] == 2 and execute["attrs"]["nodes_cached"] == 0
    assert [(c["name"], c["attrs"]["node_id"]) for c in execute["children"]] == [
        ("node.SpanTestImage", "1"), ("node.SaveImage", "2")]
    assert root["end"] < execute["start"]
    assert all(s["end"] is not None for s in tracer.spans("p1"))


def test_a_cached_node_opens_no_span_and_still_reports_zero(server, tracer):
    server.queue_prompt(graph(), "p1")
    again = server.queue_prompt(graph(), "p2")
    run_queued(server)
    spans = by_name(tracer, "p2")
    assert "node.SpanTestImage" not in spans and len(spans["node.SaveImage"]) == 1
    assert again.timings["1"] == 0.0 and set(again.timings) == {"1", "2"}
    assert spans["execute_prompt"][0]["attrs"]["nodes_run"] == 1
    assert spans["execute_prompt"][0]["attrs"]["nodes_cached"] == 1


def test_save_image_yields_device_wait_encode_and_write_with_bytes(server, tracer):
    server.queue_prompt(graph(), "p1")
    run_queued(server)
    spans = by_name(tracer, "p1")
    save = spans["node.SaveImage"][0]
    parts = [spans[name][0] for name in ("device.wait", "png.encode", "file.write")]
    assert all(p["parent_id"] == save["span_id"] for p in parts)
    assert [p["start"] for p in parts] == sorted(p["start"] for p in parts)
    wait, encode, write = parts
    assert wait["attrs"]["bytes"] == 8 * 8 * 3 * 4
    assert encode["attrs"]["bytes"] == write["attrs"]["bytes"] > 0


def test_the_second_queued_prompt_waits_out_the_firsts_graph_walk(server, tracer):
    server.queue_prompt(graph(0.25), "p1")
    server.queue_prompt(graph(0.75), "p2")
    run_queued(server)
    first, second = by_name(tracer, "p1"), by_name(tracer, "p2")
    waited = second["prompt_queue.wait"][0]
    assert waited["attrs"]["depth"] == 1
    # the executor takes p2 when p1's walk has handed its save off, not
    # when p1's file is written: that belongs to p1's execute_prompt alone
    walked = first["node.SaveImage"][0]
    assert waited["end"] >= walked["end"]
    assert waited["duration"] >= walked["end"] - first["execute_prompt"][0]["start"]
    assert first["execute_prompt"][0]["end"] >= first["file.write"][0]["end"]


def test_the_save_runs_on_the_saver_thread_below_execute_prompt(server, tracer):
    """What the benchmark's readers need of the tree: `device.wait`,
    `png.encode` and `file.write` descend from `execute_prompt`, which
    lasts until the file is written, and both readers return numbers."""
    import os
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
    sys.path.insert(0, bench)
    try:
        import spans as readers
    finally:
        sys.path.remove(bench)
    server.queue_prompt(graph(), "p1")
    run_queued(server)
    flat = tracer.spans("p1")
    (execute,) = [s for s in flat if s["name"] == "execute_prompt"]
    parents = {s["span_id"]: s["parent_id"] for s in flat}

    def descends(span):
        at = span["parent_id"]
        while at is not None and at != execute["span_id"]:
            at = parents.get(at)
        return at == execute["span_id"]

    parts = {s["name"]: s for s in flat if s["name"] in ("device.wait", "png.encode", "file.write")}
    assert set(parts) == {"device.wait", "png.encode", "file.write"}
    assert all(descends(s) for s in parts.values())
    assert execute["end"] >= parts["file.write"]["end"] and execute["status"] == "ok"
    host = readers.host_seconds(flat)
    assert host == execute["duration"] - parts["device.wait"]["duration"] > 0
    assert readers.seconds(flat, "png.encode", "file.write") == (
        parts["png.encode"]["duration"] + parts["file.write"]["duration"])


def test_node_spans_carry_the_program_work_done_in_them(server, tracer):
    server.queue_prompt(graph(), "p1")
    run_queued(server)
    spans = by_name(tracer, "p1")
    source = spans["node.SpanTestImage"][0]
    assert source["attrs"] == {
        "node_id": "1", "compiles": 1, "compile_s": 0.75, "cache_hits": 1,
        "trace_s": 0.25, "lower_s": 0.125, "cache_fetch_s": 0.5}
    assert spans["node.SaveImage"][0]["attrs"] == {"node_id": "2"}
    # the node's numbers are its one program.build child's
    (built,) = spans["program.build"]
    assert built["parent_id"] == source["span_id"]
    assert built["attrs"] == {"program": "jit(make)", "outcome": "fetched", "trace_s": 0.25,
                              "lower_s": 0.125, "build_s": 0.25, "fetch_s": 0.5}
    assert (built["start"], built["end"]) == (100.0, 101.125)


def collect(tracer, images, workers=(), context=None):
    """The master's collector on `images`; its result and its waits."""
    from comfyui_distributed_tpu.graph.nodes_distributed import DistributedCollector

    with tracer.span("node.DistributedCollector", trace_id="t"):
        result, _ = DistributedCollector().run(
            images, enabled_worker_ids=list(workers), context=context)
    return result, by_name(tracer, "t").get("device.wait", [])


def test_the_collector_hands_on_an_array_that_lies_whole_on_one_device(tracer):
    """No worker to gather from, nothing to gather: no read-back, no
    copy; the image leaves the device once, where it is saved."""
    import jax.numpy as jnp

    images = jnp.zeros((2, 4, 4, 3), jnp.float32)
    for context in (None, ExecutionContext(server=object())):
        result, waits = collect(tracer, images, context=context)
        assert result is images and waits == []
    # workers named and no server to drain them from: nobody to gather from
    result, waits = collect(tracer, images, workers=["w1"])
    assert result is images and waits == []


def sharded_images():
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    return jax.device_put(
        np.ones((2, 4, 4, 3), np.float32), NamedSharding(mesh, PartitionSpec("data")))


@pytest.mark.parametrize("make", [
    lambda: np.ones((2, 4, 4, 3), np.float32), sharded_images,
], ids=["host data", "a sharded array"])
def test_the_collector_read_back_is_a_device_wait(tracer, make):
    import jax

    images = make()
    result, (wait,) = collect(tracer, images)
    assert wait["attrs"]["bytes"] == images.nbytes
    # gathered on the host, then one array on one device, as before
    assert isinstance(result, jax.Array) and len(result.sharding.device_set) == 1
    np.testing.assert_array_equal(np.asarray(result), np.asarray(images))


def test_the_collector_with_enabled_workers_reads_its_own_batch_back(tracer, monkeypatch):
    """The elastic tier concatenates on the host: a one-device array is
    read back there, as before, and the workers' images follow it."""
    import jax.numpy as jnp

    from comfyui_distributed_tpu.graph.nodes_distributed import DistributedCollector

    theirs = np.full((4, 4, 3), 0.5, np.float32)
    monkeypatch.setattr(
        DistributedCollector, "_drain_worker_results",
        lambda self, server, job_id, workers, context: [
            {"worker_id": "w1", "batch_idx": 0, "tensor": theirs}])
    images = jnp.zeros((2, 4, 4, 3), jnp.float32)
    result, (wait,) = collect(
        tracer, images, workers=["w1"], context=ExecutionContext(server=object()))
    assert wait["attrs"]["bytes"] == images.nbytes
    assert result is not images and result.shape == (3, 4, 4, 3)
    np.testing.assert_array_equal(np.asarray(result[2]), theirs)


def test_a_served_image_crosses_to_the_host_once_below_save_image(
    server, tracer, monkeypatch
):
    """Source -> collector -> save, as the txt2img graphs end: one
    `device.wait` in the whole request, the saver thread's."""
    import jax.numpy as jnp

    on_device = jnp.full((1, 8, 8, 3), 0.5, jnp.float32)

    class DeviceImage(SpanTestImage):
        def make(self, value):
            return (on_device,)

    monkeypatch.setitem(NODE_REGISTRY, "DeviceImage", DeviceImage)
    job = server.queue_prompt({
        "1": {"class_type": "DeviceImage", "inputs": {"value": 0.5}},
        "2": {"class_type": "DistributedCollector", "inputs": {"images": ["1", 0]}},
        "3": {"class_type": "SaveImage",
              "inputs": {"images": ["2", 0], "filename_prefix": "once"}},
    }, "p1")
    run_queued(server)
    assert job.error is None
    spans = by_name(tracer, "p1")
    (wait,) = spans["device.wait"]
    assert wait["parent_id"] == spans["node.SaveImage"][0]["span_id"]
    assert wait["attrs"]["bytes"] == on_device.nbytes
    # the walk handed the very array on: SaveImage's own output is it
    assert job.outputs["3"][0]["images"] is on_device
    assert spans["execute_prompt"][0]["attrs"]["ahead"] == 0


def test_last_timings_and_counts_without_a_server(tmp_path, monkeypatch, tracer):
    monkeypatch.setitem(NODE_REGISTRY, "SpanTestImage", SpanTestImage)
    monkeypatch.setenv("CDT_OUTPUT_DIR", str(tmp_path))
    context = ExecutionContext()
    first, second = GraphExecutor(context), GraphExecutor(context)
    first.execute(graph())
    second.execute(graph())
    assert (first.nodes_run, first.nodes_cached) == (2, 0)
    assert (second.nodes_run, second.nodes_cached) == (1, 1)
    assert second.last_timings["1"] == 0.0


# --- what the device did: device.run, after_ready_s, executor.between_jobs -----


def txt2img_graph(seed=42):
    return {
        "1": {"class_type": "CheckpointLoaderSimple", "inputs": {"ckpt_name": "tiny-unet"}},
        "2": {"class_type": "CLIPTextEncode", "inputs": {"text": "a cat", "clip": ["1", 1]}},
        "3": {"class_type": "CLIPTextEncode", "inputs": {"text": "", "clip": ["1", 1]}},
        "4": {"class_type": "EmptyLatentImage",
              "inputs": {"width": 32, "height": 32, "batch_size": 1}},
        "6": {"class_type": "KSampler",
              "inputs": {"model": ["1", 0], "seed": seed, "steps": 2, "cfg": 3.0,
                         "sampler_name": "euler", "scheduler": "karras",
                         "positive": ["2", 0], "negative": ["3", 0],
                         "latent_image": ["4", 0], "denoise": 1.0}},
        "7": {"class_type": "VAEDecode", "inputs": {"samples": ["6", 0], "vae": ["1", 2]}},
        "8": {"class_type": "SaveImage",
              "inputs": {"images": ["7", 0], "filename_prefix": "device"}},
    }


def generate_graph():
    return {
        "1": {"class_type": "CheckpointLoaderSimple",
              "inputs": {"ckpt_name": "tiny-deepseek-v2"}},
        "2": {"class_type": "TextGenerate",
              "inputs": {"clip": ["1", 1], "text": "a short prompt", "seed": 7,
                         "max_new_tokens": 4, "temperature": 1.0}},
    }


@pytest.fixture()
def wall_tracer():
    """On the wall clock: the watcher thread reads it too, and a clock
    that ticks once a reading would order the two threads' readings."""
    real = Tracer()
    set_tracer(real)
    yield real
    real.stop_device_watch(timeout=30)


def walk(tracer, prompt, tmp_path, monkeypatch, trace_id):
    monkeypatch.setenv("CDT_OUTPUT_DIR", str(tmp_path))
    with tracer.span("execute_prompt", trace_id=trace_id):
        GraphExecutor(ExecutionContext()).execute(prompt)
    tracer.stop_device_watch(timeout=30)  # every launch seen to its end
    return by_name(tracer, trace_id)


def runs_of(spans):
    return {s["attrs"]["program"]: s for s in spans["device.run"]}


def test_a_txt2img_request_has_a_device_run_under_each_node_that_launched(
    wall_tracer, tmp_path, monkeypatch
):
    spans = walk(wall_tracer, txt2img_graph(), tmp_path, monkeypatch, "t")
    runs = runs_of(spans)
    assert set(runs) == {"text_encode", "sampler", "vae_decode"}
    assert len(spans["device.run"]) == 4  # the positive and the negative prompt
    assert runs["sampler"]["parent_id"] == spans["node.KSampler"][0]["span_id"]
    assert runs["vae_decode"]["parent_id"] == spans["node.VAEDecode"][0]["span_id"]
    assert runs["text_encode"]["parent_id"] in {
        s["span_id"] for s in spans["node.CLIPTextEncode"]}
    ordered = sorted(spans["device.run"], key=lambda s: s["start"])
    for before, after in zip(ordered, ordered[1:]):
        assert after["attrs"]["begin"] == max(after["start"], before["end"])
    for run in ordered:
        assert run["status"] == "ok" and run["end"] >= run["attrs"]["begin"] >= run["start"]
        assert run["attrs"]["queued_s"] + run["attrs"]["busy_s"] == pytest.approx(
            run["end"] - run["start"])
    (wait,) = spans["device.wait"]
    assert wait["parent_id"] == spans["node.SaveImage"][0]["span_id"]
    # both ended when the read-back did, give or take the watcher's own wake-up
    late = 0.25
    assert runs["sampler"]["end"] <= runs["vae_decode"]["end"] <= wait["end"] + late
    assert 0.0 <= wait["attrs"]["after_ready_s"] <= wait["duration"] + late
    assert "device.watch" not in spans  # the watcher's own span is a capture's


def test_a_generate_request_has_the_prefill_then_the_decode_back_to_back(
    wall_tracer, tmp_path, monkeypatch
):
    spans = walk(wall_tracer, generate_graph(), tmp_path, monkeypatch, "t")
    node = spans["node.TextGenerate"][0]
    below = sorted((s for names in spans.values() for s in names
                    if s["parent_id"] == node["span_id"]), key=lambda s: s["start"])
    assert [s["name"] for s in below] == [
        "lm.prefill", "device.run", "lm.decode", "device.run", "device.wait", "lm.detokenize"]
    prefill, decode = below[1], below[3]
    assert (prefill["attrs"]["program"], decode["attrs"]["program"]) == ("prefill", "decode")
    # the decode was launched before the prefill had ended: it begins where that ends
    assert decode["attrs"]["begin"] == max(decode["start"], prefill["end"])
    assert prefill["status"] == decode["status"] == "ok"
    assert "after_ready_s" in below[4]["attrs"]


class Ready:
    """Stands for a launched program's output that is there already."""

    def block_until_ready(self):
        return self

    def is_ready(self):
        return True


class LaunchingImage(SpanTestImage):
    """A source node that launches a program, as far as the tracer can
    tell: a `device.run` under its span, ended by the watcher thread."""

    def make(self, value):
        time.sleep(0.02)  # the host's work before the launch: the chip starves meanwhile
        get_tracer().device_span("sampler", Ready())
        return (np.full((1, 8, 8, 3), float(value), np.float32),)


def test_a_served_prompt_ends_with_its_four_parts_and_both_counters_move(
    tmp_config_path, wall_tracer, tmp_path, monkeypatch
):
    """Two prompts through the server's own loop, each launching one
    program: every finished `execute_prompt` bears the record, the four
    sum to the span from arrival to its end, and the counters hold the
    sums."""
    from comfyui_distributed_tpu.telemetry.instruments import (
        device_idle_seconds_total, job_seconds_total)
    from comfyui_distributed_tpu.telemetry.job_record import CAUSES, PARTS

    monkeypatch.setitem(NODE_REGISTRY, "LaunchingImage", LaunchingImage)
    monkeypatch.setenv("CDT_OUTPUT_DIR", str(tmp_path / "out"))
    server = DistributedServer(port=0, is_worker=True)
    prompts = [graph(value) for value in (0.25, 0.75)]
    for prompt in prompts:
        prompt["1"]["class_type"] = "LaunchingImage"
    jobs = [server.queue_prompt(prompt, f"p{i}") for i, prompt in enumerate(prompts)]
    run_queued(server)
    wall_tracer.stop_device_watch(timeout=30)
    assert all(job.done.is_set() and job.error is None for job in jobs)
    records = []
    for job in jobs:
        spans = by_name(wall_tracer, job.trace_id)
        (execute,) = spans["execute_prompt"]
        attrs = execute["attrs"]
        parts = [attrs[f"{part}_s"] for part in PARTS]
        assert all(seconds >= 0.0 for seconds in parts)
        arrived = spans["prompt_queue.wait"][0]["start"]
        assert sum(parts) == pytest.approx(execute["end"] - arrived, abs=1e-6)
        # its program ran, and the save came after it
        (run,) = spans["device.run"]
        assert attrs["device_s"] > 0.0 and attrs["tail_s"] > 0.0
        assert attrs["tail_s"] == pytest.approx(execute["end"] - run["end"], abs=1e-6)
        records.append(attrs)
    for part in PARTS:
        assert job_seconds_total().value(part=part) == pytest.approx(
            sum(r[f"{part}_s"] for r in records))
    idle = {cause: device_idle_seconds_total().value(cause=cause) for cause in CAUSES}
    assert idle["between_jobs"] + idle["within_job"] == pytest.approx(
        sum(r["starved_s"] for r in records))
    # the second prompt's launch came after the first's had ended: the chip
    # starved while the host walked to it, inside the node that launched
    assert records[1]["starved_s"] >= 0.02 and records[1]["starved_in"] == "node.LaunchingImage"
    assert idle["between_jobs"] > 0.0 and idle["no_job"] == 0.0  # both were queued at the start
    text = get_metrics_registry().render()
    assert 'cdt_job_seconds_total{part="device"}' in text
    assert 'cdt_device_idle_seconds_total{cause="between_jobs"}' in text


def test_two_requests_back_to_back_have_one_between_jobs_span_between_them(server, tracer):
    server.queue_prompt(graph(0.25), "p1")
    server.queue_prompt(graph(0.75), "p2")
    run_queued(server)
    first, second = by_name(tracer, "p1"), by_name(tracer, "p2")
    assert "executor.between_jobs" not in first
    (between,) = second["executor.between_jobs"]
    # the queue held p2 when the thread came back for it
    assert between["attrs"] == {"idle": 0}
    assert between["parent_id"] == second["prompt_queue.wait"][0]["span_id"]
    assert between["start"] > first["node.SaveImage"][0]["end"]
    assert between["end"] <= second["prompt_queue.wait"][0]["end"]
    assert between["end"] < second["execute_prompt"][0]["start"]


def test_between_jobs_says_when_the_thread_found_the_queue_empty(server, tracer):
    import threading

    loop = threading.Thread(target=server._executor_loop)
    loop.start()
    try:
        first = server.queue_prompt(graph(0.25), "p1")
        assert first.done.wait(30)
        time.sleep(0.05)  # the thread is back at the queue, which is empty
        second = server.queue_prompt(graph(0.75), "p2")
        assert second.done.wait(30)
    finally:
        server._prompt_queue.put(None)
        loop.join(30)
    assert not loop.is_alive()
    (between,) = by_name(tracer, "p2")["executor.between_jobs"]
    assert between["attrs"] == {"idle": 1}


# --- the profiler mirror ----------------------------------------------------


class FakeJaxProfiler:
    def __init__(self, monkeypatch):
        import jax

        self.started, self.annotations, self.exited = [], [], 0
        monkeypatch.setattr(jax.profiler, "start_trace", self.start_trace)
        monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
        monkeypatch.setattr(jax.profiler, "TraceAnnotation", self.annotation)

    def start_trace(self, path, profiler_options=None):
        self.started.append(profiler_options)

    def annotation(self, name, **attrs):
        fake = self

        class Annotation:
            def __enter__(self):
                fake.annotations.append((name, attrs))

            def __exit__(self, *exc):
                fake.exited += 1

        return Annotation()


@pytest.fixture()
def fake_jax_profiler(monkeypatch):
    yield FakeJaxProfiler(monkeypatch)
    tracing.set_span_annotator(None)


def test_capture_start_turns_the_python_tracer_off(tmp_path, fake_jax_profiler, tracer):
    capture = profiling.ProfilerCapture(str(tmp_path))
    answer = capture.start(5, "t")
    (options,) = fake_jax_profiler.started
    assert options.python_tracer_level == 0
    assert options.host_tracer_level == type(options)().host_tracer_level
    assert answer["started"] and answer["tracer_clock_s"] == 1.0
    assert abs(answer["unix_ns"] / 1e9 - time.time()) < 60
    capture.stop()


def test_spans_are_mirrored_once_each_while_a_capture_is_open(
    tmp_path, fake_jax_profiler, tracer
):
    capture = profiling.ProfilerCapture(str(tmp_path))
    with tracer.span("before", trace_id="t"):
        pass
    capture.start(5, "t")
    with tracer.span("execute_prompt", trace_id="t", prompt_id="p", skipped=[1]):
        with tracer.span("node.SaveImage", node_id="2"):
            pass
        tracer.end_span(tracer.start_span("prompt_queue.wait", trace_id="t"))
    assert fake_jax_profiler.annotations == [
        ("execute_prompt", {"prompt_id": "p"}), ("node.SaveImage", {"node_id": "2"})]
    assert fake_jax_profiler.exited == 2
    capture.stop()
    with tracer.span("after", trace_id="t"):
        pass
    assert len(fake_jax_profiler.annotations) == 2
    assert tracing._span_annotator is None


def test_a_failed_capture_start_installs_no_mirror(tmp_path, monkeypatch, tracer):
    import jax

    def refuse(path, profiler_options=None):
        raise RuntimeError("profiler busy")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    assert profiling.ProfilerCapture(str(tmp_path)).start(5, "t")["started"] is False
    assert tracing._span_annotator is None


def test_a_mirror_that_raises_does_not_break_the_span(tracer):
    def broken(span):
        raise RuntimeError("no profiler")

    tracing.set_span_annotator(broken)
    try:
        with tracer.span("execute_prompt", trace_id="t") as span:
            pass
    finally:
        tracing.set_span_annotator(None)
    assert span.end is not None and span.status == "ok"


# --- set-up, split ------------------------------------------------------------


def seconds_by_phase():
    """cdt_program_seconds_total as the scrape shows it."""
    out = {}
    for line in get_metrics_registry().render().splitlines():
        if line.startswith("cdt_program_seconds_total{"):
            out[line.split('"')[1]] = float(line.rsplit(" ", 1)[1])
    return out


def test_the_new_tallies_fill_from_monitoring_events_and_reach_the_scrape(tracer):
    import jax

    runtime.install_jax_monitoring()
    before, counted = runtime.program_work(), seconds_by_phase()
    with tracer.span("node.X", trace_id="t"):
        report_program("f", at=10.0, trace=1.5, lower=0.75, fetch=0.25, compile_=2.0)
    jax.monitoring.record_event_duration_secs("/jax/some/other_duration", 9.0)
    jax.monitoring.record_event_time_span("/jax/some/other_span", 1.0, 10.0)
    after = runtime.program_work()
    moved = {k: after[k] - before[k] for k in after if after[k] != before[k]}
    assert moved == {"trace_s": 1.5, "lower_s": 0.75, "cache_fetch_s": 0.25,
                     "compile_time_s": 2.0, "compiles": 1, "cache_hits": 1}
    now = seconds_by_phase()
    for phase, seconds in (("trace", 1.5), ("lower", 0.75), ("fetch", 0.25), ("build", 1.75)):
        assert now[phase] - counted.get(phase, 0.0) == seconds, phase
    text = get_metrics_registry().render()
    for gone in ("cdt_jax_trace_time_seconds", "cdt_jax_lower_time_seconds",
                 "cdt_jax_cache_retrieval_seconds"):
        assert gone not in text


def test_reset_zeroes_every_tally_with_its_type():
    runtime.reset_runtime_tallies()
    zero = runtime.program_work()
    assert all(v == 0 for v in zero.values())
    assert isinstance(zero["compiles"], int) and isinstance(zero["trace_s"], float)
    assert set(runtime.runtime_snapshot()) >= set(runtime.tallies())
    assert set(zero) == set(runtime.tallies()) | {"trace_s", "lower_s", "cache_fetch_s"}


def test_a_jit_inside_a_jit_is_one_program_build_span_counted_once(tracer):
    """The case the duration tallies counted twice: `inner` is traced
    inside `outer`'s trace, twice. One span, closed by the backend
    compile of `jit(outer)`, whose phases are unions: they add up to no
    more than the span covers."""
    runtime.install_jax_monitoring()
    with tracer.span("node.KSampler", trace_id="t") as node:
        before = runtime.program_work()
        report_program("outer", at=50.0, trace=4.0, lower=1.0, compile_=2.0,
                       inner=[("sin", 0.5, 0.25), ("inner", 0.25, 1.5), ("inner", 2.0, 1.0)])
        after = runtime.program_work()
    (built,) = by_name(tracer, "t")["program.build"]
    attrs = built["attrs"]
    assert attrs == {"program": "jit(outer)", "outcome": "built", "trace_s": 4.0,
                     "lower_s": 1.0, "build_s": 2.0, "fetch_s": 0.0}
    assert (built["start"], built["end"], built["parent_id"]) == (50.0, 57.0, node.span_id)
    assert attrs["trace_s"] + attrs["lower_s"] + attrs["build_s"] + attrs["fetch_s"] <= (
        built["duration"])
    assert after["trace_s"] - before["trace_s"] == 4.0  # not 4.0 + 0.25 + 1.5 + 1.0


def test_what_is_only_traced_is_a_span_of_its_own_closed_with_the_node(tracer):
    """An `eval_shape` ends in no compile: its outermost trace is closed
    when the node asks what it cost (or by the next compile), before the
    program that follows it, each with its own inner traces."""
    runtime.install_jax_monitoring()
    with tracer.span("node.Loader", trace_id="t"):
        before = runtime.program_work()
        report_program("shapes", at=10.0, trace=2.0, inner=[("init", 0.5, 1.0)])
        report_program("cast", at=13.0, trace=0.5, lower=0.25, compile_=0.25)
        report_program("late", at=20.0, trace=1.0)
        assert len(by_name(tracer, "t")["program.build"]) == 2  # `late` still pending
        after = runtime.program_work()
    spans = by_name(tracer, "t")["program.build"]
    assert [(s["attrs"]["program"], s["attrs"]["outcome"], s["start"], s["end"])
            for s in spans] == [("shapes", "traced", 10.0, 12.0), ("jit(cast)", "built", 13.0, 14.0),
                                ("late", "traced", 20.0, 21.0)]
    assert after["trace_s"] - before["trace_s"] == 3.5
    assert sum(s["attrs"]["trace_s"] for s in spans) == 3.5


def test_a_program_built_outside_a_request_goes_under_the_startup_root(tracer):
    runtime.install_jax_monitoring()
    report_program("nowhere", at=1.0, trace=1.0, compile_=1.0)  # no trace: no span
    assert tracer.trace_ids() == []
    root = tracer.start_span("process.start", trace_id=tracing.STARTUP_TRACE)
    report_program("warm", at=5.0, trace=1.0, lower=1.0, compile_=1.0)
    (built,) = by_name(tracer, tracing.STARTUP_TRACE)["program.build"]
    assert (built["parent_id"], built["attrs"]["program"]) == (root.span_id, "jit(warm)")


def test_a_real_nested_jit_adds_up_and_the_node_sums_its_children(wall_tracer):
    """The same on JAX's own events, whatever this machine's speed."""
    import jax
    import jax.numpy as jnp

    runtime.install_jax_monitoring()

    @jax.jit
    def inner(x):
        return jnp.sin(x) * 2

    @jax.jit
    def outer(x):
        return inner(x) + inner(x + 1)

    x = jnp.ones(7)
    with wall_tracer.span("node.X", trace_id="t") as node:
        before = runtime.program_work()
        outer(x)
        jax.eval_shape(outer, jnp.ones(9))
        node.attrs.update(
            {k: v - before[k] for k, v in runtime.program_work().items() if v != before[k]})
    spans = [s for s in by_name(wall_tracer, "t")["program.build"]]
    assert [s["attrs"]["program"] for s in spans if "outer" in s["attrs"]["program"]] == [
        "jit(outer)", "outer"]
    for span in spans:
        attrs = span["attrs"]
        assert span["parent_id"] == node.span_id
        assert node.start <= span["start"] <= span["end"] <= node.end
        assert attrs["trace_s"] + attrs["lower_s"] + attrs["build_s"] + attrs["fetch_s"] <= (
            span["duration"] + 1e-9)
    assert node.attrs["trace_s"] == pytest.approx(sum(s["attrs"]["trace_s"] for s in spans))
    assert node.attrs["lower_s"] == pytest.approx(sum(s["attrs"]["lower_s"] for s in spans))


_TWICE = """
import json, sys
import jax, jax.numpy as jnp
from comfyui_distributed_tpu.telemetry import get_tracer
from comfyui_distributed_tpu.workers.startup import configure_compile_cache
configure_compile_cache()
tracer = get_tracer()
with tracer.span("node.X", trace_id="t"):
    jax.jit(lambda x: jnp.tanh(x) @ x.T)(jnp.ones((16, 16))).block_until_ready()
    from comfyui_distributed_tpu.telemetry import runtime
    runtime.close_programs()
print(json.dumps([s["attrs"] for s in tracer.spans("t") if s["name"] == "program.build"
                  and "lambda" in s["attrs"]["program"]]))
"""


def test_outcome_is_fetched_on_the_second_process_of_a_cache_directory(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"),
               PYTHONPATH=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    outcomes = []
    for _ in range(2):
        done = subprocess.run([sys.executable, "-c", _TWICE], env=env, capture_output=True,
                              text=True, timeout=120, cwd=str(tmp_path))
        assert done.returncode == 0, done.stderr[-2000:]
        (attrs,) = json.loads(done.stdout.strip().splitlines()[-1])
        outcomes.append(attrs)
    assert [a["outcome"] for a in outcomes] == ["built", "fetched"]
    assert outcomes[0]["fetch_s"] == 0.0 and outcomes[0]["build_s"] > 0.0
    assert outcomes[1]["fetch_s"] > 0.0


def test_capture_start_writes_the_spans_it_holds_beside_the_capture(
    tmp_path, fake_jax_profiler, tracer
):
    """One bundle: the device's trace, the two clocks, and what the host
    did up to the capture's start, the `startup` trace among it."""
    root = tracer.start_span("process.start", trace_id=tracing.STARTUP_TRACE)
    tracer.end_span(root)
    with tracer.span("execute_prompt", trace_id="exec_1"):
        with tracer.span("node.KSampler"):
            pass
    running = tracer.start_span("execute_prompt", trace_id="exec_2")  # still open
    capture = profiling.ProfilerCapture(str(tmp_path))
    answer = capture.start(5, "bench")
    assert answer["started"] and answer["spans"] == 4 and answer["write_s"] == 1.0
    path = os.path.join(answer["path"], profiling.SPANS_BEFORE)
    assert os.path.dirname(path) == str(tmp_path / "trace-0001-bench")
    with open(path, encoding="utf-8") as fh:
        lines = [json.loads(line) for line in fh]
    assert [(l["trace_id"], l["name"]) for l in lines] == [
        (tracing.STARTUP_TRACE, "process.start"), ("exec_1", "execute_prompt"),
        ("exec_1", "node.KSampler"), ("exec_2", "execute_prompt")]
    assert lines[3]["end"] is None and lines[0] == tracer.spans(tracing.STARTUP_TRACE)[0]
    tracer.end_span(running)
    with tracer.span("after", trace_id="exec_3"):
        pass
    capture.stop()
    with open(path, encoding="utf-8") as fh:
        assert len(fh.readlines()) == 4  # written once, at the start
