"""`Tracer.device_span`: a `device.run` span per launched program, ended
by the one watcher thread when the program's output is ready. On the CPU
with a clock the test sets and outputs whose readiness an Event holds, so
every number is a function of the order things were released in."""

import threading
import time

import pytest

from comfyui_distributed_tpu.telemetry import Tracer, tracing
from comfyui_distributed_tpu.telemetry.instruments import device_busy_seconds_total
from comfyui_distributed_tpu.telemetry.tracing import WATCH_THREAD


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class Output:
    """Stands for a program's output array."""

    def __init__(self, fail=None):
        self.done, self.waited_for, self.fail = threading.Event(), threading.Event(), fail

    def block_until_ready(self):
        self.waited_for.set()
        assert self.done.wait(10), "never released"
        if self.fail is not None:
            raise self.fail
        return self

    def is_ready(self):
        if self.fail is not None:
            raise self.fail
        return self.done.is_set()


def until(condition, what="the watcher"):
    deadline = time.monotonic() + 10
    while not condition():
        assert time.monotonic() < deadline, f"{what} did not get there"
        time.sleep(0.001)


@pytest.fixture()
def clock():
    return Clock()


@pytest.fixture()
def tracer(clock):
    made = Tracer(clock=clock)
    yield made
    for launch in list(made._recent):  # a test that failed half way
        if launch.ready is not None:
            launch.ready.done.set()
    made.stop_device_watch(timeout=10)


def launch(tracer, clock, at, program="sampler", output=None, **attrs):
    clock.t = at
    output = output or Output()
    return tracer.device_span(program, output, **attrs), output


def finish(clock, at, span, output):
    clock.t = at
    output.done.set()
    until(lambda: span.end is not None)


def test_a_launch_opens_device_run_under_the_active_span_and_returns_at_once(tracer, clock):
    with tracer.span("node.KSampler", trace_id="t") as node:
        span, output = launch(tracer, clock, 1.0, steps=20)
        assert span.end is None and not output.done.is_set()  # nothing was waited for
    assert (span.name, span.parent_id, span.trace_id) == ("device.run", node.span_id, "t")
    assert span.start == 1.0 and span.attrs == {"program": "sampler", "steps": 20}
    finish(clock, 4.0, span, output)
    assert span.end == 4.0 and span.status == "ok"
    assert span.attrs["begin"] == 1.0 and span.attrs["busy_s"] == 3.0
    assert span.attrs["queued_s"] == 0.0


def test_outputs_are_waited_for_in_launch_order(tracer, clock):
    with tracer.span("execute_prompt", trace_id="t"):
        launched = [launch(tracer, clock, float(i), f"p{i}") for i in range(3)]
    (a, out_a), (b, out_b), (c, out_c) = launched
    until(out_a.waited_for.is_set)
    out_c.done.set()
    out_b.done.set()
    time.sleep(0.02)
    # the watcher sits on the first launch; later ones, though ready, wait their turn
    assert (a.end, b.end, c.end) == (None, None, None) and not out_b.waited_for.is_set()
    finish(clock, 9.0, a, out_a)
    until(lambda: c.end is not None)
    assert (a.end, b.end, c.end) == (9.0, 9.0, 9.0)
    assert b.attrs["begin"] == a.end and c.attrs["begin"] == b.end


@pytest.mark.parametrize("second_start, begin, queued_s, busy_s", [
    (2.0, 5.0, 3.0, 3.0),   # launched while the first still ran: it starts when that ends
    (6.5, 6.5, 0.0, 1.5),   # launched on an idle device: it starts at once
])
def test_begin_is_the_later_of_the_launch_and_the_previous_end(
    tracer, clock, second_start, begin, queued_s, busy_s
):
    with tracer.span("node.TextGenerate", trace_id="t"):
        first, out_first = launch(tracer, clock, 1.0, "prefill")
        if second_start < 5.0:
            second, out_second = launch(tracer, clock, second_start, "decode")
            finish(clock, 5.0, first, out_first)
        else:
            finish(clock, 5.0, first, out_first)
            second, out_second = launch(tracer, clock, second_start, "decode")
    finish(clock, 8.0, second, out_second)
    assert second.attrs["begin"] == begin == max(second.start, first.end)
    assert (second.attrs["queued_s"], second.attrs["busy_s"]) == (queued_s, busy_s)
    assert second.attrs["queued_s"] + second.attrs["busy_s"] == second.end - second.start


@pytest.mark.parametrize("fault", [
    RuntimeError("Array has been deleted with shape=float32[1,64,64,4]."),
    ValueError("the program failed on the device"),
])
def test_a_deleted_or_failed_output_ends_its_span_in_error_and_the_next_is_served(
    tracer, clock, fault
):
    before = device_busy_seconds_total().value(program="lost")
    with tracer.span("execute_prompt", trace_id="t"):
        good, out_good = launch(tracer, clock, 1.0)
        finish(clock, 2.0, good, out_good)
        bad, out_bad = launch(tracer, clock, 3.0, "lost", Output(fail=fault))
        finish(clock, 4.0, bad, out_bad)
        after, out_after = launch(tracer, clock, 3.5)
        finish(clock, 6.0, after, out_after)
    assert bad.status == "error" and bad.end == 4.0
    assert bad.attrs["error"] == f"{type(fault).__name__}: {fault}"
    assert "busy_s" not in bad.attrs
    assert device_busy_seconds_total().value(program="lost") == before
    # the lost output says nothing of when the device was free
    assert after.status == "ok" and after.attrs["begin"] == 3.5 and after.attrs["busy_s"] == 2.5
    assert tracer._watch_thread.is_alive()


@pytest.mark.parametrize("second_start, queued_s, idle_before_s", [
    (2.0, 3.0, 0.0),   # launched behind a program still running: it stood, the device did not
    (5.0, 0.0, 0.0),   # launched the moment the device came free
    (6.5, 0.0, 1.5),   # launched on a device that had had nothing for 1.5 s
])
def test_idle_before_is_the_complement_of_queued_and_one_of_the_two_is_zero(
    tracer, clock, second_start, queued_s, idle_before_s
):
    with tracer.span("execute_prompt", trace_id="earlier"):
        first, out_first = launch(tracer, clock, 1.0, "vae_decode")
    with tracer.span("execute_prompt", trace_id="t"):  # the previous launch is any trace's
        if second_start < 5.0:
            second, out_second = launch(tracer, clock, second_start)
            finish(clock, 5.0, first, out_first)
        else:
            finish(clock, 5.0, first, out_first)
            second, out_second = launch(tracer, clock, second_start)
    finish(clock, 8.0, second, out_second)
    assert first.attrs["idle_before_s"] == 0.0  # nothing before it says when the device was free
    assert (second.attrs["queued_s"], second.attrs["idle_before_s"]) == (queued_s, idle_before_s)
    assert second.attrs["idle_before_s"] == second.attrs["begin"] - first.end
    # from the previous end to this one the device was idle, then busy
    assert second.attrs["idle_before_s"] + second.attrs["busy_s"] == second.end - first.end


def test_a_failed_program_bears_no_idle_before_and_the_next_counts_from_the_last_good_end(
    tracer, clock
):
    with tracer.span("execute_prompt", trace_id="t"):
        good, out_good = launch(tracer, clock, 1.0)
        finish(clock, 2.0, good, out_good)
        bad, out_bad = launch(tracer, clock, 3.0, "lost", Output(fail=ValueError("failed")))
        finish(clock, 4.0, bad, out_bad)
        after, out_after = launch(tracer, clock, 4.5)
        finish(clock, 6.0, after, out_after)
    assert bad.status == "error" and not {"begin", "queued_s", "idle_before_s"} & set(bad.attrs)
    assert after.attrs["idle_before_s"] == 2.5 and after.attrs["queued_s"] == 0.0


def test_outside_a_trace_nothing_is_queued_and_no_thread_starts(tracer, clock):
    output = Output()
    assert tracer.device_span("sampler", output) is None
    assert tracer._watch_thread is None and not tracer._recent
    assert tracer._launches.empty() and tracer.trace_ids() == []
    assert not output.waited_for.is_set()


def test_stop_sees_every_launch_to_its_end_and_joins(tracer, clock):
    with tracer.span("execute_prompt", trace_id="t"):
        launched = [launch(tracer, clock, 1.0, f"p{i}") for i in range(4)]
    thread = tracer._watch_thread
    assert thread.name == WATCH_THREAD and thread.daemon and thread.is_alive()
    clock.t = 2.0
    for _, output in launched:
        output.done.set()
    tracer.stop_device_watch(timeout=10)
    assert not thread.is_alive() and tracer._watch_thread is None
    assert all(span.end == 2.0 and launch_.ready is None
               for (span, _), launch_ in zip(launched, tracer._recent))
    # the next launch starts another
    with tracer.span("execute_prompt", trace_id="u"):
        span, output = launch(tracer, clock, 3.0)
    assert tracer._watch_thread is not thread and tracer._watch_thread.is_alive()
    finish(clock, 4.0, span, output)
    assert span.attrs["begin"] == 3.0  # a new thread knows no previous end


def test_busy_seconds_are_counted_by_program(tracer, clock):
    counter = device_busy_seconds_total()
    before = {p: counter.value(program=p) for p in ("prefill", "decode")}
    with tracer.span("node.TextGenerate", trace_id="t"):
        prefill, out_prefill = launch(tracer, clock, 1.0, "prefill")
        decode, out_decode = launch(tracer, clock, 1.5, "decode")
        finish(clock, 2.0, prefill, out_prefill)
        finish(clock, 6.0, decode, out_decode)
    tracer.stop_device_watch(timeout=10)
    assert counter.value(program="prefill") - before["prefill"] == 1.0
    assert counter.value(program="decode") - before["decode"] == 4.0


def test_the_watcher_holds_a_mirrored_device_watch_span_while_it_waits(tracer, clock):
    mirrored = []

    class Annotation:
        def __init__(self, span):
            self.span = span

        def __enter__(self):
            mirrored.append((self.span.name, dict(self.span.attrs),
                             threading.current_thread().name))

        def __exit__(self, *exc):
            mirrored.append(("exit", self.span.name))

    tracing.set_span_annotator(Annotation)
    try:
        with tracer.span("node.VAEDecode", trace_id="t"):
            span, output = launch(tracer, clock, 1.0, "vae_decode")
        until(output.waited_for.is_set)
        watch = [s for s in tracer.spans("t") if s["name"] == "device.watch"]
        assert len(watch) == 1 and watch[0]["end"] is None  # open while it waits
        finish(clock, 3.0, span, output)
        tracer.stop_device_watch(timeout=10)
    finally:
        tracing.set_span_annotator(None)
    (watch,) = [s for s in tracer.spans("t") if s["name"] == "device.watch"]
    assert watch["parent_id"] == span.span_id and watch["attrs"] == {"program": "vae_decode"}
    assert (watch["start"], watch["end"]) == (1.0, 3.0)
    on_watcher = [m for m in mirrored if m[0] == "device.watch"]
    assert on_watcher == [("device.watch", {"program": "vae_decode"}, WATCH_THREAD)]
    assert ("exit", "device.watch") in mirrored


def test_with_no_capture_open_the_watcher_records_no_span_of_its_own(tracer, clock):
    with tracer.span("node.VAEDecode", trace_id="t"):
        span, output = launch(tracer, clock, 1.0, "vae_decode")
    finish(clock, 3.0, span, output)
    tracer.stop_device_watch(timeout=10)
    assert [s["name"] for s in tracer.spans("t")] == ["node.VAEDecode", "device.run"]
    assert span.attrs["busy_s"] == 2.0


def test_a_device_wait_says_how_long_after_the_device_it_ended(tracer, clock):
    with tracer.span("node.SaveImage", trace_id="t"):
        span, output = launch(tracer, clock, 1.0, "vae_decode")
        with tracer.device_wait(bytes=12) as wait:
            finish(clock, 5.0, span, output)
            clock.t = 7.0
    assert wait.name == "device.wait" and wait.attrs == {"bytes": 12, "after_ready_s": 2.0}
    assert wait.end == 7.0


def test_a_device_wait_beside_a_late_watcher_reads_zero_not_the_launch_before(tracer, clock):
    with tracer.span("node.TextGenerate", trace_id="t"):
        held, out_held = launch(tracer, clock, 1.0, "prefill")
        span, output = launch(tracer, clock, 2.0, "decode")
        until(out_held.waited_for.is_set)
        output.done.set()  # ready; the watcher is still on the launch before
        with tracer.device_wait() as wait:
            clock.t = 9.0
        assert span.end is None and wait.attrs == {"after_ready_s": 0.0}
        out_held.done.set()


def test_a_device_wait_skips_a_launch_that_still_runs(tracer, clock):
    with tracer.span("execute_prompt", trace_id="t"):
        first, out_first = launch(tracer, clock, 1.0, "sampler")
        finish(clock, 4.0, first, out_first)
        running, out_running = launch(tracer, clock, 4.5, "vae_decode")
        with tracer.device_wait() as wait:
            clock.t = 6.0
        assert wait.attrs == {"after_ready_s": 2.0}
        out_running.done.set()


def test_a_device_wait_with_no_launch_in_its_trace_gains_nothing(tracer, clock):
    with tracer.span("execute_prompt", trace_id="other"):
        span, output = launch(tracer, clock, 1.0)
        finish(clock, 2.0, span, output)
    with tracer.span("execute_prompt", trace_id="t"):
        with tracer.device_wait() as wait:
            pass
    assert wait.attrs == {}


def test_a_start_span_can_be_given_an_earlier_reading_of_the_clock(tracer, clock):
    clock.t = 3.0
    came_back = tracer.now()
    clock.t = 5.0
    span = tracer.start_span("executor.between_jobs", trace_id="t", start=came_back)
    tracer.end_span(span)
    assert (span.start, span.end, span.duration) == (3.0, 5.0, 2.0)


def test_launches_from_many_threads_all_end_and_none_is_lost():
    """More launching threads than cores, each in a trace of its own,
    with a short switch interval: every span ends once, with its numbers."""
    import sys

    tracer, per_thread, threads = Tracer(), 50, 16
    spans, lock = [], threading.Lock()

    def work(index):
        made = []
        with tracer.span("execute_prompt", trace_id=f"t{index}"):
            for _ in range(per_thread):
                output = Output()
                output.done.set()
                made.append(tracer.device_span("sampler", output))
        with lock:
            spans.extend(made)

    before = device_busy_seconds_total().value(program="sampler")
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        pool = [threading.Thread(target=work, args=(i,)) for i in range(threads)]
        for thread in pool:
            thread.start()
        for thread in pool:
            thread.join(30)
        assert not any(thread.is_alive() for thread in pool)
        tracer.stop_device_watch(timeout=30)
    finally:
        sys.setswitchinterval(interval)
    assert len(spans) == per_thread * threads and len({s.span_id for s in spans}) == len(spans)
    assert all(s.end is not None and s.status == "ok" for s in spans)
    assert all(s.attrs["queued_s"] >= 0 and s.attrs["busy_s"] >= 0 for s in spans)
    assert all(abs(s.attrs["queued_s"] + s.attrs["busy_s"] - (s.end - s.start)) < 1e-9
               for s in spans)
    total = sum(s.attrs["busy_s"] for s in spans)
    assert device_busy_seconds_total().value(program="sampler") - before == pytest.approx(total)
    assert tracer._watch_thread is None
