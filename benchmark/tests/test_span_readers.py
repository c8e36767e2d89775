"""The readers of the program's span tree on hand-built material: the
value where the spans are there, None where the program has none.

    python -m pytest benchmark/tests -q
"""

import importlib.util
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)


def reader(name: str):
    """The metric's read(), loaded as run.py loads it."""
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("layer_metric", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def span(name, span_id, parent_id, duration):
    return {"name": name, "span_id": span_id, "parent_id": parent_id,
            "start": 0.0, "end": duration, "duration": duration, "attrs": {}}


def request(queue_s, execute_s, wait_s, decode_s=0.2, encode_s=0.3, write_s=0.05):
    """One served request's spans as /distributed/trace/<id> flattens them."""
    return [
        span("sched.wait", "s", None, 0.001),
        span("queue_orchestration", "o", "s", 0.002),
        span("prompt_queue.wait", "q", "o", queue_s),
        span("execute_prompt", "e", "s", execute_s),
        span("node.VAEDecode", "d", "e", decode_s),
        span("node.SaveImage", "n", "e", wait_s + encode_s + write_s),
        span("device.wait", "w", "n", wait_s),
        span("png.encode", "p", "n", encode_s),
        span("file.write", "f", "n", write_s),
        span("device.wait", "stray", None, 100.0),  # not below execute_prompt
        span("device.wait", "open", "n", None),     # never closed
    ]


NEW = {"spans": {f"t{i}": request(queue_s=float(i), execute_s=2.0 + i, wait_s=1.0)
                 for i in range(1, 11)}}
OLD = {"spans": {"t1": [span("sched.wait", "s", None, 0.001),
                        span("execute_prompt", "e", "s", 2.0)]}}


@pytest.mark.parametrize("name, expected", [
    ("queue_wait_in_ms.p90", 9001.0),         # nearest rank of 1..10 s, + sched.wait
    ("host_ms.usdu", 6500.0),                 # median execute 7.5 s less 1 s waiting
    ("host_ms.txt2img", 6500.0),
    ("save_ms.usdu", 350.0),
    ("decode_dispatch_ms.txt2img", 200.0),
])
def test_reader_gives_the_value_from_the_spans(name, expected):
    assert reader(name)(NEW) == pytest.approx(expected)


@pytest.mark.parametrize("name", [
    "queue_wait_in_ms.p90", "host_ms.usdu", "host_ms.txt2img", "save_ms.usdu",
    "decode_dispatch_ms.txt2img",
])
@pytest.mark.parametrize("material", [OLD, {"spans": {}}], ids=["parent", "untraced"])
def test_reader_gives_none_where_the_program_has_no_such_span(name, material):
    assert reader(name)(material) is None
