"""The saver thread: a served prompt's read-back, PNG encode and file
write run while the executor thread walks the next prompt, which is
"ahead" while the earlier read-back lasts. `done` means on disk, names
are reserved at hand-off, a saver-side failure is the job's error, at
most one save waits beside the one running, and nothing that waits for
the server (the loop's sentinel, `stop`, `drain_worker`) leaves a save
unwritten. Read-backs and encodes are held on events, never on sleeps."""

import asyncio
import os
import threading
import time

import numpy as np
import pytest

from comfyui_distributed_tpu.api.server import DistributedServer, SaveThread
from comfyui_distributed_tpu.graph import io_dirs
from comfyui_distributed_tpu.graph.executor import ExecutionContext, GraphExecutor
from comfyui_distributed_tpu.graph.registry import NODE_REGISTRY
from comfyui_distributed_tpu.resilience.chaos import FakeClock
from comfyui_distributed_tpu.telemetry import Tracer, set_tracer
from comfyui_distributed_tpu.telemetry.instruments import job_seconds_total, walks_total
from comfyui_distributed_tpu.telemetry.job_record import PARTS
from comfyui_distributed_tpu.telemetry.metrics import get_metrics_registry
from comfyui_distributed_tpu.utils import image as img_utils
from comfyui_distributed_tpu.workers.startup import drain_worker

encode_png = img_utils.encode_png


class HostImage:
    """A host image of one value, so nothing is compiled."""

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"value": ("FLOAT", {"default": 0.5})}}

    RETURN_TYPES = ("IMAGE",)
    FUNCTION = "make"

    def make(self, value):
        return (np.full((1, 8, 8, 3), float(value), np.float32),)


class Gate:
    """While `hold` is clear whoever passes parks, having said so on
    `entered`; `fail_next` makes one pass raise `error`."""

    error = OSError("No space left on device")

    def __init__(self):
        self.hold = threading.Event()
        self.hold.set()
        self.entered = threading.Event()
        self.fail_next = False
        self.threads = []

    def pass_through(self):
        self.threads.append(threading.current_thread().name)
        self.entered.set()
        assert self.hold.wait(10), "the test never released the gate"
        if self.fail_next:
            self.fail_next = False
            raise self.error


class ReadBack(Gate):
    error = RuntimeError("the program failed on the device")


class OnDevice:
    """Stands in for an image on the device: its bytes reach the host
    when `__array__` returns, which passes `gate` first, as a read-back
    waits for the programs before it."""

    def __init__(self, value, gate):
        self.value, self.gate = value, gate

    def __len__(self):
        return 1

    def __array__(self, dtype=None, copy=None):
        self.gate.pass_through()
        return np.full((1, 8, 8, 3), self.value, dtype or np.float32)


def graph(value, prefix="saved", source="HostImage"):
    return {
        "1": {"class_type": source, "inputs": {"value": value}},
        "2": {"class_type": "SaveImage",
              "inputs": {"images": ["1", 0], "filename_prefix": prefix}},
    }


def expected_png(value):
    return encode_png(np.full((8, 8, 3), float(value), np.float32), compress_level=4)


def wait_until(condition, what, seconds=10.0):
    deadline = time.monotonic() + seconds
    while not condition():
        assert time.monotonic() < deadline, f"timed out waiting until {what}"
        time.sleep(0.001)


@pytest.fixture()
def tracer():
    ticking = Tracer(clock=FakeClock(step=1.0))
    set_tracer(ticking)
    return ticking


@pytest.fixture()
def out_dir(tmp_path, monkeypatch):
    monkeypatch.setitem(NODE_REGISTRY, "HostImage", HostImage)
    monkeypatch.setenv("CDT_OUTPUT_DIR", str(tmp_path / "out"))
    return tmp_path / "out"


class Encoder(Gate):
    """`encode_png` behind a gate."""

    def __call__(self, image, compress_level=0):
        self.pass_through()
        return encode_png(image, compress_level)


@pytest.fixture()
def encoder(monkeypatch):
    gate = Encoder()
    monkeypatch.setattr(img_utils, "encode_png", gate)
    return gate


@pytest.fixture()
def read_back(monkeypatch):
    """The gate of every image a `HeldImage` node makes."""
    gate = ReadBack()

    class HeldImage(HostImage):
        def make(self, value):
            return (OnDevice(float(value), gate),)

    monkeypatch.setitem(NODE_REGISTRY, "HeldImage", HeldImage)
    yield gate
    gate.hold.set()


@pytest.fixture()
def server(tmp_config_path, out_dir, tracer, encoder):
    """A server whose executor loop runs on a thread, as `start` runs it."""
    srv = DistributedServer(port=0, is_worker=True)
    srv._executor_thread = threading.Thread(target=srv._executor_loop, daemon=True)
    srv._executor_thread.start()
    yield srv
    encoder.hold.set()
    if srv._executor_thread.is_alive():
        srv._prompt_queue.put(None)
        srv._executor_thread.join(10)


def spans_named(tracer, trace_id, name):
    return [s for s in tracer.spans(trace_id) if s["name"] == name]


def walked(tracer, trace_id):
    """The prompt's graph walk has handed its save off."""
    done = spans_named(tracer, trace_id, "node.SaveImage")
    return bool(done) and done[0]["end"] is not None


@pytest.fixture()
def two_in_flight(server, tracer, encoder, out_dir):
    """p1's encode held on the saver thread, p2 walked behind it."""
    encoder.hold.clear()
    first = server.queue_prompt(graph(0.25), "p1")
    second = server.queue_prompt(graph(0.75), "p2")
    assert encoder.entered.wait(10)
    wait_until(lambda: walked(tracer, "p2"), "p2's walk has ended")
    return first, second


def release(encoder, *jobs):
    encoder.hold.set()
    for job in jobs:
        assert job.done.wait(10)


def test_the_next_prompts_nodes_run_while_the_save_is_held(
    two_in_flight, tracer, encoder, out_dir
):
    first, second = two_in_flight
    assert spans_named(tracer, "p1", "png.encode")[0]["end"] is None
    assert spans_named(tracer, "p2", "node.HostImage")[0]["end"] is not None
    assert encoder.threads == ["cdt-saver"]
    release(encoder, first, second)
    encode = spans_named(tracer, "p1", "png.encode")[0]
    assert all(
        node["start"] < encode["end"]
        for name in ("node.HostImage", "node.SaveImage")
        for node in spans_named(tracer, "p2", name)
    )
    assert encoder.threads == ["cdt-saver", "cdt-saver"]


def test_done_is_not_set_until_the_file_exists(two_in_flight, tracer, encoder, out_dir):
    first, second = two_in_flight
    assert not first.done.is_set() and not second.done.is_set()
    assert spans_named(tracer, "p1", "execute_prompt")[0]["end"] is None
    assert not os.path.exists(out_dir / "saved_00000.png")
    # the walk's results are there already; only `done` waits for the disk
    assert first.outputs is not None and set(first.timings) == {"1", "2"}
    release(encoder, first, second)
    assert os.path.exists(out_dir / "saved_00000.png")
    execute = spans_named(tracer, "p1", "execute_prompt")[0]
    assert execute["end"] >= spans_named(tracer, "p1", "file.write")[0]["end"]


def test_two_in_flight_prompts_of_one_prefix_get_distinct_names(
    two_in_flight, encoder, out_dir
):
    first, second = two_in_flight
    names = [job.outputs["2"][0]["ui"]["images"] for job in (first, second)]
    assert names == [["saved_00000.png"], ["saved_00001.png"]]
    release(encoder, first, second)
    assert (out_dir / "saved_00000.png").read_bytes() == expected_png(0.25)
    assert (out_dir / "saved_00001.png").read_bytes() == expected_png(0.75)
    assert sorted(os.listdir(out_dir)) == ["saved_00000.png", "saved_00001.png"]


def test_queue_remaining_counts_a_prompt_whose_save_is_pending(
    two_in_flight, server, encoder
):
    first, second = two_in_flight
    assert server._prompt_queue.qsize() == 0
    assert server.queue_remaining == 2 and server.saves_pending == 2
    assert server._executing.is_set()
    release(encoder, first, second)
    wait_until(lambda: server.queue_remaining == 0, "both prompts are done")
    assert server.saves_pending == 0 and not server._executing.is_set()


def test_the_overlap_counter_and_attribute_read_one_and_zero(
    two_in_flight, tracer, encoder
):
    first, second = two_in_flight
    release(encoder, first, second)
    # p2 was taken while p1's save was held; nothing was taken during p2's
    assert spans_named(tracer, "p1", "png.encode")[0]["attrs"]["overlapped"] == 1
    assert spans_named(tracer, "p2", "png.encode")[0]["attrs"]["overlapped"] == 0
    # the save's place in a job is the record's `tail_s`: each job launched
    # nothing, so all of it is the tail, the save included
    records = [spans_named(tracer, p, "execute_prompt")[0] for p in ("p1", "p2")]
    for record in records:
        attrs = record["attrs"]
        assert attrs["tail_s"] > 0 and "starved_in" not in attrs
        assert (attrs["waiting_s"], attrs["device_s"], attrs["starved_s"]) == (0.0, 0.0, 0.0)
    for part in PARTS:
        assert job_seconds_total().value(part=part) == sum(
            r["attrs"][f"{part}_s"] for r in records)
    text = get_metrics_registry().render()
    assert f'cdt_job_seconds_total{{part="tail"}} {job_seconds_total().value(part="tail"):g}' in text


@pytest.fixture()
def two_reading(server, tracer, read_back, out_dir):
    """p1's read-back held on the saver thread, p2 walked behind it."""
    read_back.hold.clear()
    first = server.queue_prompt(graph(0.25, source="HeldImage"), "p1")
    second = server.queue_prompt(graph(0.75, source="HeldImage"), "p2")
    assert read_back.entered.wait(10)
    wait_until(lambda: walked(tracer, "p2"), "p2's walk has ended")
    return first, second


def test_the_next_prompt_is_walked_while_the_read_back_is_held(
    two_reading, server, tracer, read_back, out_dir
):
    first, second = two_reading
    (wait,) = spans_named(tracer, "p1", "device.wait")
    assert wait["end"] is None and read_back.threads == ["cdt-saver"]
    assert wait["parent_id"] == spans_named(tracer, "p1", "node.SaveImage")[0]["span_id"]
    # the executor thread waited for nothing: p2 is walked, its read-back queued
    assert spans_named(tracer, "p2", "node.HeldImage")[0]["end"] is not None
    assert not spans_named(tracer, "p2", "device.wait")
    assert server._reading == 2 and not first.done.is_set()
    release(read_back, first, second)
    (wait,) = spans_named(tracer, "p1", "device.wait")
    nodes = [s for s in tracer.spans("p2") if s["name"].startswith("node.")]
    assert min(node["start"] for node in nodes) < wait["end"]
    assert read_back.threads == ["cdt-saver", "cdt-saver"] and server._reading == 0
    assert (out_dir / "saved_00000.png").read_bytes() == expected_png(0.25)
    assert (out_dir / "saved_00001.png").read_bytes() == expected_png(0.75)


def test_ahead_says_a_walk_began_before_an_earlier_read_back_ended(
    two_reading, server, tracer, read_back
):
    first, second = two_reading
    release(read_back, first, second)
    # nothing was on the device when p1 was taken; p1 was when p2 was
    assert spans_named(tracer, "p1", "execute_prompt")[0]["attrs"]["ahead"] == 0
    assert spans_named(tracer, "p2", "execute_prompt")[0]["attrs"]["ahead"] == 1
    # and a prompt that arrives once both have landed is ahead of nothing
    third = server.queue_prompt(graph(0.5, source="HeldImage"), "p3")
    assert third.done.wait(10)
    assert spans_named(tracer, "p3", "execute_prompt")[0]["attrs"]["ahead"] == 0
    assert walks_total().value(ahead="1") == 1 and walks_total().value(ahead="0") == 2
    text = get_metrics_registry().render()
    assert 'cdt_walks_total{ahead="1"} 1' in text
    assert 'cdt_walks_total{ahead="0"} 2' in text


def test_a_walk_behind_a_held_encode_is_overlapped_and_not_ahead(server, tracer, encoder):
    """What `ahead` counts is the read-back, not the save."""
    encoder.hold.clear()
    first = server.queue_prompt(graph(0.25), "p1")
    assert encoder.entered.wait(10)  # p1's image has landed; its encode is held
    second = server.queue_prompt(graph(0.75), "p2")
    wait_until(lambda: walked(tracer, "p2"), "p2's walk has ended")
    release(encoder, first, second)
    assert spans_named(tracer, "p2", "execute_prompt")[0]["attrs"]["ahead"] == 0
    assert spans_named(tracer, "p1", "png.encode")[0]["attrs"]["overlapped"] == 1
    assert walks_total().value(ahead="0") == 2 and walks_total().value(ahead="1") == 0


def test_the_served_paths_png_is_the_inline_paths_for_the_same_array(
    server, out_dir, monkeypatch
):
    import jax.numpy as jnp

    ramp = np.linspace(0.0, 1.0, 2 * 8 * 8 * 3, dtype=np.float32).reshape(2, 8, 8, 3)
    on_device = jnp.asarray(ramp)

    class DeviceRamp(HostImage):
        def make(self, value):
            return (on_device,)

    monkeypatch.setitem(NODE_REGISTRY, "DeviceRamp", DeviceRamp)
    job = server.queue_prompt(graph(0.0, prefix="served", source="DeviceRamp"), "p1")
    assert job.done.wait(30) and job.error is None
    GraphExecutor(ExecutionContext()).execute(graph(0.0, prefix="inline", source="DeviceRamp"))
    for i in (0, 1):
        served = (out_dir / f"served_{i:05d}.png").read_bytes()
        assert served == (out_dir / f"inline_{i:05d}.png").read_bytes()
        assert served == encode_png(ramp[i], compress_level=4)
    assert job.outputs["2"][0]["images"] is on_device  # the walk made no copy of it


def test_the_pending_gauge_is_in_the_scrape(two_in_flight, server, encoder):
    from comfyui_distributed_tpu.telemetry import bind_server_collectors

    unbind = bind_server_collectors(server)
    try:
        # the server's own field, and the queue depth that counts a pending save
        assert server.saves_pending == 2
        assert 'cdt_prompt_queue_depth{server="worker:0"} 2' in get_metrics_registry().render()
        release(encoder, *two_in_flight)
        wait_until(lambda: server.saves_pending == 0, "the saves have ended")
        text = get_metrics_registry().render()
        assert 'cdt_prompt_queue_depth{server="worker:0"} 0' in text
    finally:
        unbind()


@pytest.fixture(params=["encode", "read_back"])
def held(request, encoder, read_back):
    """What a test holds on the saver thread: the gate, the node whose
    image passes it, and the span that is open meanwhile."""
    if request.param == "encode":
        return encoder, "HostImage", "png.encode"
    return read_back, "HeldImage", "device.wait"


def test_a_third_hand_off_blocks_until_the_first_file_is_written(
    held, server, tracer, out_dir
):
    gate, source, _ = held
    gate.hold.clear()
    jobs = [server.queue_prompt(graph(0.1 * i, source=source), f"p{i}") for i in (1, 2, 3, 4)]
    assert gate.entered.wait(10)
    wait_until(lambda: spans_named(tracer, "p3", "node.SaveImage"),
               "p3 has reached its hand-off")
    time.sleep(0.005)
    # one save runs (p1), one waits (p2): the executor thread is parked
    # inside p3's SaveImage and has not taken p4 (the gauge counts p3's
    # save from the moment its hand-off began)
    assert spans_named(tracer, "p3", "node.SaveImage")[0]["end"] is None
    assert spans_named(tracer, "p4", "prompt_queue.wait")[0]["end"] is None
    assert server.queue_remaining == 4 and server.saves_pending == 3
    assert os.listdir(out_dir) == []
    release(gate, *jobs)
    ends = [spans_named(tracer, p, "file.write")[0]["end"] for p in ("p1", "p2", "p3", "p4")]
    assert ends == sorted(ends)
    # p3's hand-off returned only once p1's file was there
    assert spans_named(tracer, "p3", "node.SaveImage")[0]["end"] > ends[0]


def test_a_failed_save_is_the_jobs_error_and_the_next_job_runs(
    held, server, tracer, out_dir
):
    gate, source, span = held
    gate.fail_next = True
    failed = server.queue_prompt(graph(0.25, source=source), "p1")
    fine = server.queue_prompt(graph(0.75, source=source), "p2")
    assert failed.done.wait(10) and fine.done.wait(10)
    assert failed.error == f"{type(gate.error).__name__}: {gate.error}"
    execute = spans_named(tracer, "p1", "execute_prompt")[0]
    assert execute["status"] == "error" and execute["attrs"]["error"] == failed.error
    assert spans_named(tracer, "p1", span)[0]["status"] == "error"
    assert gate.threads == ["cdt-saver", "cdt-saver"]  # it failed there, not in the walk
    assert fine.error is None
    # the failed job's reserved name is not reused
    assert sorted(os.listdir(out_dir)) == ["saved_00001.png"]
    assert (out_dir / "saved_00001.png").read_bytes() == expected_png(0.75)
    # a read-back that raised has ended: nothing is ahead of it for ever
    assert server._reading == 0 and server.saves_pending == 0


def test_a_failed_walk_still_ends_execute_prompt_with_the_error(server, tracer):
    broken = {"1": {"class_type": "LoadImage", "inputs": {"image": "/nonexistent.png"}},
              "2": {"class_type": "SaveImage", "inputs": {"images": ["1", 0]}}}
    job = server.queue_prompt(broken, "p1")
    assert job.done.wait(10)
    assert job.error.startswith("FileNotFoundError")
    execute = spans_named(tracer, "p1", "execute_prompt")[0]
    assert execute["status"] == "error" and execute["end"] is not None
    assert server.queue_remaining == 0


def test_the_loop_returning_on_its_sentinel_leaves_no_save_unwritten(
    server, tracer, encoder, out_dir
):
    encoder.hold.clear()
    jobs = [server.queue_prompt(graph(0.1 * i), f"p{i}") for i in (1, 2)]
    server._prompt_queue.put(None)
    assert encoder.entered.wait(10)
    wait_until(lambda: walked(tracer, "p2"), "p2's walk has ended")
    assert server._executor_thread.is_alive()  # parked joining the saver
    encoder.hold.set()
    server._executor_thread.join(10)
    assert not server._executor_thread.is_alive()
    assert all(job.done.is_set() for job in jobs)
    assert sorted(os.listdir(out_dir)) == ["saved_00000.png", "saved_00001.png"]
    assert server._saver._thread is None


def test_the_loop_run_in_this_thread_saves_before_it_returns(
    tmp_config_path, out_dir, tracer
):
    server = DistributedServer(port=0, is_worker=True)
    jobs = [server.queue_prompt(graph(0.1 * i), f"p{i}") for i in (1, 2, 3)]
    server._prompt_queue.put(None)
    server._executor_loop()
    assert all(job.done.is_set() and job.error is None for job in jobs)
    assert len(os.listdir(out_dir)) == 3
    # and the loop can be entered again: the saver starts anew
    again = server.queue_prompt(graph(0.9), "p4")
    server._prompt_queue.put(None)
    server._executor_loop()
    assert again.done.is_set() and len(os.listdir(out_dir)) == 4


def release_soon(encoder):
    timer = threading.Timer(0.005, encoder.hold.set)
    timer.start()
    return timer


def test_stop_waits_for_a_pending_save(two_in_flight, server, encoder, out_dir):
    first, second = two_in_flight
    timer = release_soon(encoder)
    asyncio.run(server.stop())
    timer.join()
    assert first.done.is_set() and second.done.is_set()
    assert not server._executor_thread.is_alive()
    assert sorted(os.listdir(out_dir)) == ["saved_00000.png", "saved_00001.png"]


def test_drain_worker_waits_for_a_pending_save(two_in_flight, server, encoder, out_dir):
    first, second = two_in_flight
    timer = release_soon(encoder)
    assert asyncio.run(drain_worker(server, grace_seconds=10.0)) is True
    timer.join()
    assert first.done.is_set() and second.done.is_set()
    assert first.error is None and second.error is None
    assert sorted(os.listdir(out_dir)) == ["saved_00000.png", "saved_00001.png"]


def test_without_a_server_the_node_saves_inline(out_dir, tracer, encoder):
    context = ExecutionContext()
    assert context.defer is None
    GraphExecutor(context).execute(graph(0.25))
    assert (out_dir / "saved_00000.png").read_bytes() == expected_png(0.25)
    assert encoder.threads == [threading.current_thread().name]
    (encode,) = [s for ids in tracer.trace_ids() for s in spans_named(tracer, ids, "png.encode")]
    assert encode["attrs"]["overlapped"] == 0
    # no server, no job: nothing is stamped or counted
    assert all(job_seconds_total().value(part=part) == 0 for part in PARTS)


def test_a_context_with_a_server_but_no_defer_saves_inline(out_dir, tracer, encoder):
    """What selects the saver is the hand-off the served loop puts in
    the context, not the presence of a server object."""
    context = ExecutionContext(server=object())
    GraphExecutor(context).execute(graph(0.25))
    assert os.path.exists(out_dir / "saved_00000.png")
    assert encoder.threads == [threading.current_thread().name]


# --- the saver thread alone ---------------------------------------------------


def test_save_thread_runs_in_order_and_join_stops_it():
    saver, ran = SaveThread(), []
    for i in range(5):
        saver.submit(lambda i=i: ran.append((i, threading.current_thread().name)))
    saver.join()
    assert ran == [(i, "cdt-saver") for i in range(5)]
    assert saver._thread is None
    saver.join()  # nothing to join: a no-op
    saver.submit(lambda: ran.append("again"))
    saver.join()
    assert ran[-1] == "again"


def test_save_thread_holds_one_running_and_one_waiting():
    saver, gate, started = SaveThread(), threading.Event(), threading.Event()
    saver.submit(lambda: (started.set(), gate.wait(10)))
    assert started.wait(10)
    saver.submit(lambda: None)  # waits beside the running one
    third = threading.Thread(target=saver.submit, args=(lambda: None,))
    third.start()
    third.join(0.005)
    assert third.is_alive()  # blocked at the hand-off
    gate.set()
    third.join(10)
    assert not third.is_alive()
    saver.join()


# --- names ---------------------------------------------------------------------


def test_reserve_counter_starts_after_the_files_on_disk(tmp_path):
    (tmp_path / "a_00006.png").write_bytes(b"")
    assert io_dirs.reserve_counter(str(tmp_path), "a", "png") == 7


def test_reserve_counter_never_hands_a_counter_out_twice(tmp_path):
    first = io_dirs.reserve_counter(str(tmp_path), "a", "png", 3)
    second = io_dirs.reserve_counter(str(tmp_path), "a", "png")
    assert (first, second) == (0, 3)
    assert os.listdir(tmp_path) == []  # reserved, not written


def test_reserve_counter_follows_a_file_written_past_its_reservation(tmp_path):
    assert io_dirs.reserve_counter(str(tmp_path), "a", "png") == 0
    (tmp_path / "a_00041.png").write_bytes(b"")
    assert io_dirs.reserve_counter(str(tmp_path), "a", "png") == 42


@pytest.mark.parametrize("other", [("b", "png"), ("a", "webp")])
def test_reservations_are_per_directory_prefix_and_extension(tmp_path, other):
    assert io_dirs.reserve_counter(str(tmp_path), "a", "png", 5) == 0
    assert io_dirs.reserve_counter(str(tmp_path), *other) == 0
    assert io_dirs.reserve_counter(str(tmp_path / ".." / tmp_path.name), "a", "png") == 5


def test_next_counter_still_only_scans(tmp_path):
    """The animated savers' allocation: unchanged by a reservation."""
    io_dirs.reserve_counter(str(tmp_path), "a", "webp", 4)
    assert io_dirs.next_counter(str(tmp_path), "a", "webp") == 0
    (tmp_path / "a_00002.webp").write_bytes(b"")
    assert io_dirs.next_counter(str(tmp_path), "a", "webp") == 3


# --- the tracer's part -----------------------------------------------------------


def test_activate_with_a_span_id_parents_another_threads_spans(tracer):
    parent = tracer.start_span("execute_prompt", trace_id="t")
    found = {}

    def other_thread():
        token = tracer.activate("t", parent.span_id)
        with tracer.span("png.encode") as child:
            found["parent"] = child.parent_id
            found["current"] = tracer.current_span_id()
        found["after"] = tracer.current_span_id()
        tracer.deactivate(token)

    thread = threading.Thread(target=other_thread)
    thread.start()
    thread.join()
    assert found["parent"] == parent.span_id == found["after"]
    assert found["current"] != parent.span_id
    assert tracer.current_span_id() is None  # this thread never joined


def test_activate_without_a_span_id_still_falls_back_to_the_root(tracer):
    root = tracer.start_span("prompt_queue.wait", trace_id="t")
    tracer.start_span("execute_prompt", trace_id="t")
    token = tracer.activate("t")
    with tracer.span("late") as late:
        pass
    tracer.deactivate(token)
    assert late.parent_id == root.span_id
