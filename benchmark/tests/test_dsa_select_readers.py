"""`dsa_select_device_pct.lm` (PR 57) on made traces: the selection
kernel's events inside the language model's programs over those programs'
device time; nothing where the trace has no such kernel (the parent's
programs, which pick by a sort), no such program, or no trace; and the
metric's entry in the manifest.

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

CELL = "glm_5_2_longdoc_txt2img_512.closed2"
METRIC = "dsa_select_device_pct.lm"


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# the made trace, the programs and GLM's node of PR 53's checks; the hand-written
# trace's clock of test_device_readers.py
_attend = _load(os.path.join(HERE, "tests", "test_dsa_attend_readers.py"), "select_uses_attend_readers")
traced, lm_programs, _device, _glm = (
    _attend.traced, _attend.lm_programs, _attend._device, _attend._glm)
read = _load(os.path.join(HERE, "layer_metrics", METRIC + ".py"), "select_layer_metric").read
read_attend = _attend.read


def test_device_the_selection_kernels_share_is_its_events_inside_the_models_programs(
        tmp_path, monkeypatch):
    ms, modules = _device.MS, lm_programs()
    kernel = "%dsa_select.{} = (s32[2048,128]{{1,0}}, s32[8,128]{{1,0}}) custom-call("
    ops = [("%fusion.1 = bf16[8]{0} fusion(", ms, 2 * ms),
           (kernel.format(99), 100, 200_000)]                    # before any of the programs
    for kind, start, end in modules:
        if kind == "jit_prefill":                                # 768 calls of 0.25 ms a prefill
            ops += [(kernel.format(i), start + 3 * i * ms, start + 3 * i * ms + ms // 4)
                    for i in range(768)]
            # the attention kernel beside it, and another name that begins alike
            ops.append(("%dsa_attend.3 = bf16[2048,64,512]{2,1,0} custom-call(",
                        start + ms, start + 2 * ms))
            ops.append(("%dsa_selected.3 = f32[8]{0} fusion(", start + 2 * ms, start + 3 * ms))
    material = traced(tmp_path, monkeypatch, ops, modules)
    # 3 x 192 ms of the kernel in 3 x (3,000 + 500) ms of the two programs
    assert read(material) == pytest.approx(100.0 * 192 / 3500)
    # times the programs' time over a prefill's 768 calls, the kernel's ms a call
    assert read(material) / 100.0 * 3500 / 768 == pytest.approx(0.25)
    # each kernel's reader reads its own events and not the other's
    assert read_attend(material) == pytest.approx(100.0 * 1 / 3500)


def test_device_the_parents_programs_sort_and_say_nothing(tmp_path, monkeypatch):
    ms, modules = _device.MS, lm_programs()
    ops = [("%fusion.1 = bf16[8]{0} fusion(", ms, 2 * ms)] + [
        ("%sort.7 = (f32[128,32768]{1,0}, s32[128,32768]{1,0}) sort(", start + ms, start + 40 * ms)
        for kind, start, end in modules if kind == "jit_prefill"] + [
        ("%dsa_attend.3 = bf16[2048,64,512]{2,1,0} custom-call(", start + 50 * ms, start + 60 * ms)
        for kind, start, end in modules if kind == "jit_prefill"]
    assert read(traced(tmp_path, monkeypatch, ops, modules)) is None


def test_device_a_trace_without_the_models_programs_says_nothing_of_the_selection(
        tmp_path, monkeypatch):
    ms = _device.MS
    modules = _device.txt2img_modules(3, 800)
    ops = [("%dsa_select.1 = s32[8] custom-call(", ms, 2 * ms)]   # in no program of the model
    assert read(traced(tmp_path, monkeypatch, ops, modules)) is None


def test_no_trace_says_nothing_of_the_selection():
    assert read({"spans": {}, "records": [], "trace": None}) is None
    assert read(dict(_glm.material_of(_glm.NODE), trace=None)) is None


def test_the_selection_metric_follows_the_attention_kernels_and_lists_the_glm_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        manifest = json.load(fh)
    names = [m["name"] for m in manifest["per_layer"]]
    assert names.index("dsa_attend_device_pct.lm") < names.index(METRIC)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == METRIC]
    assert entry == {
        "name": METRIC, "unit": "%", "better": "lower", "source": "device_trace",
        "layer": "kernels", "moves": "images_per_s", "workloads": [CELL]}
    # no share of a roofline or of a peak came with the kernel
    assert not [n for n in names if n.startswith("dsa_select") and n != METRIC]
    assert os.path.exists(os.path.join(HERE, "layer_metrics", METRIC + ".py"))
