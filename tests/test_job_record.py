"""The job's record (telemetry/job_record.py): a job's seconds from its
arrival to the end of `execute_prompt`, each instant in one of four
parts, and the chip's idle seconds before the job's launches by cause.
On hand-made spans, every time a number the case sets; then stamped by a
tracer whose watcher a test holds, and by the server at a job's end."""

import pytest
from test_device_spans import Output, clock, finish, launch, tracer, until  # noqa: F401 - fixtures

from comfyui_distributed_tpu.telemetry.instruments import (
    device_idle_seconds_total,
    job_seconds_total,
)
from comfyui_distributed_tpu.telemetry.job_record import CAUSES, PARTS, job_record, stamp_job

_ids = iter(range(10 ** 6))


def span(name, start, end, parent=None, status="ok", **attrs):
    return {"name": name, "span_id": f"s{next(_ids)}", "parent_id": parent,
            "start": start, "end": end, "status": status, "attrs": attrs}


def run(program, launched, begin, end, parent=None, idle=0.0):
    """A `device.run` as the watcher stamps it."""
    return span("device.run", launched, end, parent, program=program, begin=begin,
                queued_s=begin - launched, idle_before_s=idle, busy_s=end - begin)


def walk(start, *below, end=None):
    """`execute_prompt` from `start`, still open, and the spans below it."""
    root = span("execute_prompt", start, end)
    for one in below:
        one["parent_id"] = one["parent_id"] or root["span_id"]
    return [root, *below]


def closed_client():
    """One of two closed clients: the other's job holds the chip until 4."""
    return [span("sched.wait", 0.0, 0.2), span("prompt_queue.wait", 0.2, 1.0),
            *walk(1.0, run("sampler", 2.0, 4.0, 8.0), run("vae_decode", 3.0, 8.0, 9.0))]


def fifth_of_a_burst():
    """Admitted after 6 s, walked behind the fourth, whose programs end at 9."""
    return [span("sched.wait", 0.0, 6.0), span("prompt_queue.wait", 6.0, 7.0),
            *walk(7.0, run("sampler", 8.0, 9.0, 11.0))]


def after_an_empty_queue():
    """The chip has had nothing since 0; the job arrives at 5. Its first
    launch ends 6.5 s of idleness, 5 of them before there was a job."""
    sampler = span("node.KSampler", 5.6, 7.0)
    decode = span("node.VAEDecode", 8.6, 9.2)
    return [span("prompt_queue.wait", 5.0, 5.5),
            span("executor.between_jobs", 1.0, 5.5, idle=1),
            *walk(5.5, sampler, run("sampler", 6.5, 6.5, 8.5, sampler["span_id"], idle=6.5),
                  decode, run("vae_decode", 9.0, 9.0, 10.0, decode["span_id"], idle=0.5))]


def cached_nodes():
    save = span("node.SaveImage", 1.5, 1.6)
    return [span("prompt_queue.wait", 0.0, 1.0),
            *walk(1.0, save, span("png.encode", 2.0, 2.5, save["span_id"]))]


def failed_program():
    """The second launch failed: the watcher ended it with no `begin`."""
    return [span("prompt_queue.wait", 0.0, 1.0),
            *walk(1.0, run("sampler", 1.5, 2.0, 4.0),
                  span("device.run", 3.0, 5.0, status="error", program="vae_decode",
                       error="ValueError: the program failed on the device"))]


def last_launch_not_stamped():
    """The watcher has not reached the second launch: no end, no `begin`."""
    return [span("prompt_queue.wait", 0.0, 1.0),
            *walk(1.0, run("sampler", 1.5, 2.0, 4.0),
                  span("device.run", 3.0, None, program="vae_decode"))]


CASES = {
    # name: (spans, end, waiting, device, starved, tail, starved_in, idle by cause)
    "two closed clients": (closed_client, 10.0, 4.0, 5.0, 0.0, 1.0, None, (0.0, 0.0, 0.0)),
    "a burst of five": (fifth_of_a_burst, 12.0, 9.0, 2.0, 0.0, 1.0, None, (0.0, 0.0, 0.0)),
    "a launch after an empty queue": (
        after_an_empty_queue, 10.5, 0.0, 3.0, 2.0, 0.5, "node.KSampler", (5.0, 1.5, 0.5)),
    "a job of cached nodes": (cached_nodes, 3.0, 0.0, 0.0, 0.0, 3.0, None, (0.0, 0.0, 0.0)),
    "a failed program": (failed_program, 6.0, 2.0, 2.0, 0.0, 2.0, None, (0.0, 0.0, 0.0)),
    "a last launch not yet stamped": (
        last_launch_not_stamped, 7.0, 2.0, 5.0, 0.0, 0.0, None, (0.0, 0.0, 0.0)),
}


@pytest.mark.parametrize("case", list(CASES))
def test_a_jobs_seconds_go_to_four_parts_that_sum_to_the_job(case):
    make, end, waiting, device, starved, tail, starved_in, idle = CASES[case]
    spans = make()
    record, by_cause = job_record(spans, end)
    parts = [record[f"{part}_s"] for part in PARTS]
    assert parts == pytest.approx([waiting, device, starved, tail])
    arrived = min(s["start"] for s in spans if s["name"] in ("sched.wait", "prompt_queue.wait"))
    assert sum(parts) == pytest.approx(end - arrived, abs=1e-9)
    assert record.get("starved_in") == starved_in
    assert [by_cause[cause] for cause in CAUSES] == pytest.approx(list(idle))
    # what is starved is the idle time inside the job, whichever launch ended it
    assert record["starved_s"] == pytest.approx(by_cause["between_jobs"] + by_cause["within_job"])


def test_starved_in_is_the_innermost_span_the_executor_thread_had_open():
    """The chip sat idle from 4 to 5: the thread was in the node until 4.7
    (0.2 of it in `lm.detokenize`, 0.3 parked in a `device.wait`, which
    is not the host's doing), then between two nodes."""
    node = span("node.TextGenerate", 1.0, 4.7)
    encode = span("node.CLIPTextEncode", 4.8, 5.5)
    spans = [span("prompt_queue.wait", 0.0, 1.0),
             *walk(1.0, node, run("decode", 1.5, 1.5, 4.0, node["span_id"]),
                   span("device.wait", 3.0, 4.3, node["span_id"]),
                   span("lm.detokenize", 4.3, 4.5, node["span_id"]),
                   encode, run("text_encode", 5.0, 5.0, 5.2, encode["span_id"], idle=1.0))]
    record, _ = job_record(spans, 6.0)
    assert record["starved_s"] == pytest.approx(1.0)
    assert record["starved_in"] == "node.TextGenerate"  # 0.5 s, to lm.detokenize's 0.2
    spans[-2]["start"] = 4.1  # the next node opened early: most of the gap is its own
    assert job_record(spans, 6.0)[0]["starved_in"] == "node.CLIPTextEncode"


# --- stamped by the tracer ------------------------------------------------------


def test_the_stamp_does_not_wait_for_a_watcher_that_is_a_launch_behind(tracer, clock):
    """The last launch's output is there and the watcher still sits on
    the one before: both stand as on the chip to the job's end, which is
    where `device.wait` would take the ready one as ended, and nothing
    blocks."""
    tracer.end_span(tracer.start_span("prompt_queue.wait", trace_id="t"))
    root = tracer.start_span("execute_prompt", trace_id="t")
    token = tracer.activate("t", root.span_id)
    first, out_first = launch(tracer, clock, 1.0)
    last, out_last = launch(tracer, clock, 2.0, "vae_decode")
    tracer.deactivate(token)
    until(out_first.waited_for.is_set)
    out_last.done.set()  # ready; the watcher is on the launch before
    clock.t = 5.0
    stamp_job(tracer, root, end=tracer.now())
    tracer.end_span(root, end=5.0)
    assert first.end is None and last.end is None
    assert [root.attrs[f"{part}_s"] for part in PARTS] == [1.0, 4.0, 0.0, 0.0]
    assert root.end == 5.0 and root.duration == 5.0
    assert [job_seconds_total().value(part=part) for part in PARTS] == [1.0, 4.0, 0.0, 0.0]
    out_first.done.set()


def test_the_stamp_counts_the_idle_seconds_before_the_jobs_launches_by_cause(tracer, clock):
    with tracer.span("execute_prompt", trace_id="earlier"):
        earlier = launch(tracer, clock, 1.0)
    finish(clock, 2.0, *earlier)
    clock.t = 4.0
    tracer.end_span(tracer.start_span("prompt_queue.wait", trace_id="t"))
    root = tracer.start_span("execute_prompt", trace_id="t")
    token = tracer.activate("t", root.span_id)
    first = launch(tracer, clock, 5.0)
    finish(clock, 6.0, *first)
    second = launch(tracer, clock, 6.5, "vae_decode")
    finish(clock, 7.0, *second)
    tracer.deactivate(token)
    clock.t = 8.0
    stamp_job(tracer, root, end=8.0)
    assert first[0].attrs["idle_before_s"] == 3.0 and second[0].attrs["idle_before_s"] == 0.5
    assert [device_idle_seconds_total().value(cause=cause) for cause in CAUSES] == [2.0, 1.0, 0.5]
    assert [root.attrs[f"{part}_s"] for part in PARTS] == [0.0, 1.5, 1.5, 1.0]
    assert root.attrs["starved_in"] == "execute_prompt"
