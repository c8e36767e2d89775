"""The seven readers of the job's record on a hand-made `material`: the
medians and the 90th percentile of `waiting_s` and `tail_s` in ms, the
starved share summed over the window's requests and not averaged a job;
without the attributes (the parent of the PR that brought the record)
every reader returns None; and the manifest lists the seven where the
metrics they stand beside are listed, whatever is appended after them.

    python -m pytest benchmark/tests -q
"""

import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import job_record  # noqa: E402

SEVEN = ("job_waiting_ms.txt2img", "job_waiting_ms.usdu", "job_waiting_ms.p90",
         "job_tail_ms.txt2img", "job_tail_ms.usdu",
         "device_starved_pct.txt2img", "device_starved_pct.usdu")


def reader(name: str):
    """The metric's read(), loaded as run.py loads it."""
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("layer_metric", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def request(waiting, device, starved, tail, start=0.0, **more):
    """One request as /distributed/trace/<id> serves it, flattened."""
    end = start + waiting + device + starved + tail
    attrs = {"prompt_id": "p", "waiting_s": waiting, "device_s": device,
             "starved_s": starved, "tail_s": tail, **more}
    return [
        {"name": "sched.wait", "span_id": "a", "parent_id": None, "start": start,
         "end": start + 0.001, "duration": 0.001, "attrs": {}, "status": "ok"},
        {"name": "execute_prompt", "span_id": "b", "parent_id": "a", "start": start + 0.002,
         "end": end, "duration": end - start - 0.002, "attrs": attrs, "status": "ok"},
        {"name": "device.run", "span_id": "c", "parent_id": "b", "start": start + 0.01,
         "end": end - tail, "duration": 0.1, "status": "ok",
         "attrs": {"program": "sampler", "begin": start + waiting, "busy_s": device,
                   "queued_s": 0.0, "idle_before_s": starved}},
    ]


def material(*requests):
    return {"spans": {f"t{i}": spans for i, spans in enumerate(requests)}, "records": []}


FIVE = material(
    request(0.400, 0.390, 0.000, 0.020),
    request(0.380, 0.395, 0.001, 0.022),
    request(0.390, 0.400, 0.000, 0.018),
    request(0.900, 0.395, 0.000, 0.030),   # the late one
    request(0.010, 0.420, 0.099, 0.021, starved_in="node.TextGenerate"),
)


@pytest.mark.parametrize("name, value", [
    ("job_waiting_ms.txt2img", 390.0),
    ("job_waiting_ms.usdu", 390.0),
    ("job_waiting_ms.p90", 900.0),      # nearest rank: the fifth of five
    ("job_tail_ms.txt2img", 21.0),
    ("job_tail_ms.usdu", 21.0),
    ("device_starved_pct.txt2img", 100.0 * 0.1 / 2.1),   # 0.1 s of 2.0 + 0.1, not a mean of shares
    ("device_starved_pct.usdu", 100.0 * 0.1 / 2.1),
])
def test_job_record_the_seven_by_hand(name, value):
    assert reader(name)(FIVE) == pytest.approx(value)


@pytest.mark.parametrize("name", SEVEN)
def test_job_record_without_the_attributes_the_metric_is_left_out(name):
    """The parent's `execute_prompt` bears `prompt_id`, `ahead` and the
    node counts only; an empty window has no span at all."""
    bare = request(0.4, 0.39, 0.0, 0.02)
    for part in job_record.PARTS:
        del bare[1]["attrs"][part]
    assert reader(name)(material(bare)) is None
    assert reader(name)(material()) is None
    assert reader(name)({"spans": {}, "records": [], "trace": None}) is None


def test_job_record_a_request_still_open_or_half_stamped_is_passed_over():
    open_ = request(0.4, 0.39, 0.0, 0.02)
    open_[1]["end"] = None
    half = request(0.4, 0.39, 0.0, 0.02)
    del half[1]["attrs"]["tail_s"]
    whole = request(0.2, 0.3, 0.1, 0.05)
    found = material(open_, half, whole)
    assert job_record.record(open_) is None and job_record.record(half) is None
    assert job_record.record(whole) == {
        "waiting_s": 0.2, "device_s": 0.3, "starved_s": 0.1, "tail_s": 0.05}
    assert reader("job_waiting_ms.txt2img")(found) == pytest.approx(200.0)
    assert reader("device_starved_pct.txt2img")(found) == pytest.approx(25.0)


def test_job_record_jobs_that_launched_nothing_have_no_starved_share():
    cached = material(request(0.0, 0.0, 0.0, 0.5), request(0.0, 0.0, 0.0, 0.4))
    assert reader("device_starved_pct.txt2img")(cached) is None
    assert reader("job_tail_ms.txt2img")(cached) == pytest.approx(450.0)


def test_job_record_the_manifest_lists_the_seven_beside_the_metrics_they_stand_by():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        manifest = json.load(fh)
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    names = list(per_layer)
    first = names.index("job_waiting_ms.txt2img")
    assert tuple(names[first:first + 7]) == SEVEN  # appended together, in this order
    assert first > names.index("grouped_matmul_device_pct.lm")  # after PR 64's
    # the thirteen cells `host_ms.txt2img` listed when the seven came
    txt2img, usdu = per_layer["host_ms.txt2img"]["workloads"][:13], ["sdxl_usdu_2k.closed2"]
    burst = per_layer["queue_wait_in_ms.p90"]["workloads"][:1]
    want = {
        "job_waiting_ms.txt2img": ("ms", "admission and queue", "job_s.p50", txt2img),
        "job_waiting_ms.usdu": ("ms", "admission and queue", "job_s.p50", usdu),
        "job_waiting_ms.p90": ("ms", "admission and queue", "job_s.p90", burst),
        "job_tail_ms.txt2img": ("ms", "graph executor and nodes", "job_s.p50", txt2img),
        "job_tail_ms.usdu": ("ms", "graph executor and tile tier", "job_s.p50", usdu),
        "device_starved_pct.txt2img": ("%", "device", "images_per_s", txt2img),
        "device_starved_pct.usdu": ("%", "device", "tiles_per_s", usdu),
    }
    for name, (unit, layer, moves, cells) in want.items():
        entry = per_layer[name]
        assert {k: v for k, v in entry.items() if k != "workloads"} == {
            "name": name, "unit": unit, "better": "lower", "source": "program_span",
            "layer": layer, "moves": moves}, name
        # the cells it began with, in their order; later cells may follow
        assert entry["workloads"][:len(cells)] == cells, name
        assert os.path.exists(os.path.join(HERE, "layer_metrics", name + ".py"))
    # every cell that reports a throughput has its starved share, and no cell two
    starved = (per_layer["device_starved_pct.txt2img"]["workloads"]
               + per_layer["device_starved_pct.usdu"]["workloads"])
    assert len(starved) == len(set(starved))
