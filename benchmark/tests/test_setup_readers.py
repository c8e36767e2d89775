"""The seven set-up readers on a hand-made `spans_before.jsonl`: they add
up to the stretch from `process.start`'s start to the last set-up
request's end; a request of the window is not set-up's; a loader's node
counts less the programs built under it; without the file every reader
returns None; and the manifest lists the seven as PR 50 appended them,
every cell reporting them (no `workloads` list, as `model_load_s` and
`compile_s` have none).

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

import setup_spans  # noqa: E402

SEVEN = ("server_start_s", "loaders_s", "program_trace_s", "program_lower_s",
         "program_fetch_s", "program_build_s", "setup_other_s")


def reader(name: str):
    """The metric's read(), loaded as run.py loads it."""
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("layer_metric", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


_IDS = iter(range(10 ** 6))


def span(trace, name, start, end, parent=None, **attrs):
    return {"trace_id": trace, "span_id": f"s{next(_IDS)}", "parent_id": parent and parent["span_id"],
            "name": name, "start": start, "end": end,
            "duration": None if end is None else end - start, "attrs": attrs, "events": [],
            "status": "ok"}


def program(trace, parent, start, trace_s=0.0, lower_s=0.0, build_s=0.0, fetch_s=0.0, gap=0.0):
    """A `program.build` span; `gap` is what of it no phase covers."""
    outcome = "fetched" if fetch_s else "built" if build_s else "traced"
    return span(trace, "program.build", start, start + trace_s + lower_s + build_s + fetch_s + gap,
                parent, program="jit(f)", outcome=outcome, trace_s=trace_s, lower_s=lower_s,
                build_s=build_s, fetch_s=fetch_s)


def hand_made():
    """The start (process created at 100.0, listening at 116.0), the
    request as committed (117.0 to 157.0), one warm request (158.0 to
    160.0), and one request of the window that built a program."""
    root = span("startup", "process.start", 100.0, 116.0, pid=7, role="master", python_s=0.5)
    spans = [root]
    for i, name in enumerate(("chips", "compile_cache", "backend", "imports", "mesh", "server")):
        spans.append(span("startup", f"startup.{name}", 101.0 + 2 * i, 103.0 + 2 * i, root))
    # built outside any request: under the start's root
    spans.append(program("startup", root, 110.0, trace_s=0.25, lower_s=0.25, fetch_s=0.5))

    first = span("first", "execute_prompt", 117.0, 157.0, prompt_id="p0")
    loader = span("first", "node.CheckpointLoaderSimple", 117.0, 137.0, first, node_id="1")
    second = span("first", "node.VAELoader", 137.0, 139.0, first, node_id="2")
    image = span("first", "node.LoadImage", 139.0, 140.0, first, node_id="3")
    sampler = span("first", "node.KSampler", 140.0, 156.0, first, node_id="4")
    inside = span("first", "lm.prefill", 125.0, 130.0, loader)  # a node's own span between
    spans += [
        span("first", "prompt_queue.wait", 116.5, 117.0), first, loader, second, image, sampler,
        program("first", loader, 118.0, trace_s=1.0, lower_s=3.0, fetch_s=2.0, gap=0.5),  # 6.5 s
        inside, program("first", inside, 126.0, trace_s=0.5),                              # 0.5 s
        program("first", second, 137.5, lower_s=0.5, build_s=0.5),                         # 1.0 s
        program("first", image, 139.0, trace_s=0.25),   # under an input, not a loader
        program("first", sampler, 140.0, trace_s=8.0, lower_s=1.0, fetch_s=4.0),
        span("first", "node.SaveImage", 156.0, None, first),  # still open: not counted
    ]
    warm = span("warm", "execute_prompt", 158.0, 160.0)
    spans += [warm, span("warm", "node.KSampler", 158.0, 159.0, warm)]
    window = span("win", "execute_prompt", 161.0, 170.0)
    node = span("win", "node.CheckpointLoaderSimple", 161.0, 165.0, window)
    spans += [window, node, program("win", node, 162.0, trace_s=1.0, build_s=1.0)]
    return spans


def run_dir(tmp_path, monkeypatch):
    """Where a run.py started with `--out <tmp>` keeps its profile. (A
    plain function: the tier-1 adopter takes this file's checks by name,
    and a fixture would stay behind.)"""
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", "a.cell", "--seed", "1",
                                      "--out", str(tmp_path)])
    setup_spans._LOADED.clear()
    return tmp_path


def write(out, spans):
    folder = out / "profile" / "trace-0001-benchmark"
    folder.mkdir(parents=True)
    (folder / setup_spans.FILE).write_text("".join(json.dumps(s) + "\n" for s in spans))


MATERIAL = {"records": [{"trace_id": "win", "ok": True}, {"trace_id": None, "ok": False}],
            "trace": {"busy_s": 1.0, "window_s": 2.0}}


def test_setup_the_seven_by_hand(tmp_path, monkeypatch):
    out = run_dir(tmp_path, monkeypatch)
    write(out, hand_made())
    found = {name: reader(name)(MATERIAL) for name in SEVEN}
    assert found == {
        "server_start_s": 16.0,
        # the two loaders' 20 + 2 s less the 6.5 + 0.5 + 1.0 s of programs under them
        "loaders_s": 14.0,
        "program_trace_s": 0.25 + 1.0 + 0.5 + 0.25 + 8.0,
        "program_lower_s": 0.25 + 3.0 + 0.5 + 1.0,
        "program_fetch_s": 0.5 + 2.0 + 4.0,
        "program_build_s": 0.5,
        "setup_other_s": 60.0 - 16.0 - 14.0 - 10.0 - 4.75 - 6.5 - 0.5,
    }


def test_setup_the_seven_add_up_to_the_stretch_by_construction(tmp_path, monkeypatch):
    out = run_dir(tmp_path, monkeypatch)
    write(out, hand_made())
    # creation at 100.0, the warm request's execute_prompt ends at 160.0
    assert sum(reader(name)(MATERIAL) for name in SEVEN) == pytest.approx(60.0)
    assert setup_spans.split(MATERIAL) == {name: reader(name)(MATERIAL) for name in SEVEN}


def test_setup_a_request_of_the_window_is_not_set_ups(tmp_path, monkeypatch):
    out = run_dir(tmp_path, monkeypatch)
    write(out, hand_made())
    as_window = reader("program_build_s")(MATERIAL)
    setup_spans._LOADED.clear()
    as_setup = reader("program_build_s")({"records": []})
    assert (as_window, as_setup) == (0.5, 1.5)  # the window request's 1.0 s of build
    assert reader("loaders_s")({"records": []}) == 14.0 + 4.0 - 2.0
    assert sum(reader(name)({"records": []}) for name in SEVEN) == pytest.approx(70.0)


@pytest.mark.parametrize("name", SEVEN)
def test_setup_without_the_file_the_metric_is_left_out(name, tmp_path, monkeypatch):
    out = run_dir(tmp_path, monkeypatch)
    assert reader(name)(MATERIAL) is None  # a traced run of a program that writes none
    (out / "profile" / "trace-0001-benchmark").mkdir(parents=True)
    assert reader(name)(MATERIAL) is None
    monkeypatch.setattr(sys, "argv", ["pytest", "-q"])  # no run.py at all
    assert reader(name)(MATERIAL) is None


def test_setup_a_start_that_has_not_ended_reads_nothing(tmp_path, monkeypatch):
    out = run_dir(tmp_path, monkeypatch)
    spans = hand_made()
    spans[0]["end"] = spans[0]["duration"] = None
    write(out, spans)
    assert [reader(name)(MATERIAL) for name in SEVEN] == [None] * 7


def test_setup_no_request_yet_ends_the_stretch_with_the_start(tmp_path, monkeypatch):
    out = run_dir(tmp_path, monkeypatch)
    write(out, [s for s in hand_made() if s["trace_id"] == "startup"])
    found = setup_spans.split({"records": []})
    assert found["server_start_s"] == 16.0 and found["loaders_s"] == 0.0
    assert sum(found.values()) == pytest.approx(16.0)
    assert found["setup_other_s"] == pytest.approx(-1.0)  # the program inside the start, twice


def test_setup_the_manifest_lists_the_seven_for_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        manifest = json.load(fh)
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    names = list(per_layer)
    first = names.index("server_start_s")
    assert tuple(names[first:first + 7]) == SEVEN  # appended together, in this order
    assert first > names.index("expert_matvec_hbm_pct.lm")  # after PR 48's
    layers = {"server_start_s": "entry and model load", "loaders_s": "entry and model load",
              "setup_other_s": "entry and model load"}
    for name in SEVEN:
        assert per_layer[name] == {
            "name": name, "unit": "s", "better": "lower", "source": "program_span",
            "layer": layers.get(name, "sampling programs"), "moves": "setup_s"}, name
        assert os.path.exists(os.path.join(HERE, "layer_metrics", name + ".py"))
    # the two that moved `setup_s` before stay as they were
    assert per_layer["model_load_s"]["layer"] == "entry and model load"
    assert per_layer["compile_s"]["source"] == "program_counter"
    assert "workloads" not in per_layer["model_load_s"] and "workloads" not in per_layer["compile_s"]
