"""The served path's span tree: queue wait, nodes, device waits and the
PNG save in the request's one trace; the profiler mirror; the set-up
tallies. All on a tracer whose clock ticks once per reading, so every
duration is a pure function of the span sequence."""

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
        import jax

        # what a node that builds a program makes JAX report
        jax.monitoring.record_event_duration_secs(TRACE_EVENT, 0.25)
        jax.monitoring.record_event_duration_secs(FETCH_EVENT, 0.5)
        return (np.full((1, 8, 8, 3), float(value), np.float32),)


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
    source = spans["node.SpanTestImage"][0]["attrs"]
    assert source == {"node_id": "1", "trace_s": 0.25, "cache_fetch_s": 0.5}
    assert spans["node.SaveImage"][0]["attrs"] == {"node_id": "2"}


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


def test_the_new_tallies_fill_from_monitoring_events_and_reach_the_scrape():
    import jax

    runtime.install_jax_monitoring()
    before = runtime.tallies()
    jax.monitoring.record_event_duration_secs(TRACE_EVENT, 1.5)
    jax.monitoring.record_event_duration_secs(LOWER_EVENT, 0.75)
    jax.monitoring.record_event_duration_secs(FETCH_EVENT, 0.25)
    jax.monitoring.record_event_duration_secs(COMPILE_EVENT, 2.0)
    jax.monitoring.record_event_duration_secs("/jax/some/other_duration", 9.0)
    after = runtime.tallies()
    moved = {k: after[k] - before[k] for k in after if after[k] != before[k]}
    assert moved == {"trace_time_s": 1.5, "lower_time_s": 0.75,
                     "cache_retrieval_s": 0.25, "compile_time_s": 2.0, "compiles": 1}
    runtime.collect_runtime_gauges()
    text = get_metrics_registry().render()
    for name, key in (("cdt_jax_trace_time_seconds", "trace_time_s"),
                      ("cdt_jax_lower_time_seconds", "lower_time_s"),
                      ("cdt_jax_cache_retrieval_seconds", "cache_retrieval_s")):
        assert f"{name} {after[key]}" in text or f"{name} {after[key]:g}" in text


def test_reset_zeroes_every_tally_with_its_type():
    runtime.reset_runtime_tallies()
    zero = runtime.tallies()
    assert all(v == 0 for v in zero.values())
    assert isinstance(zero["compiles"], int) and isinstance(zero["trace_time_s"], float)
    assert set(runtime.runtime_snapshot()) >= set(zero)
