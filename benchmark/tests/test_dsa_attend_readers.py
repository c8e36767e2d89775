"""`dsa_attend_device_pct.lm` (PR 53) on made traces: the kernel's events
inside the language model's programs over those programs' device time;
nothing where the trace has no such kernel (the parent's programs, whose
gathered form is XLA's), no such program, or no trace; and the metric's
entry in the manifest.

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

import device_modules  # noqa: E402

CELL = "glm_5_2_longdoc_txt2img_512.closed2"
METRIC = "dsa_attend_device_pct.lm"


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# the hand-written trace, the spans and the window of test_device_readers.py; GLM's node
_device = _load(os.path.join(HERE, "tests", "test_device_readers.py"), "dsa_uses_device_readers")
_glm = _load(os.path.join(HERE, "tests", "test_glm_dsa_readers.py"), "dsa_uses_glm_readers")
read = _load(os.path.join(HERE, "layer_metrics", METRIC + ".py"), "layer_metric").read


def traced(tmp_path, monkeypatch, ops, modules):
    """A trace whose first device ran `modules` and, on its operations
    line, `ops`; the material of three GLM requests beside it."""
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", "a.cell", "--seed", "1",
                                      "--out", str(tmp_path)])
    folder = tmp_path / "profile" / "trace-0001-benchmark" / "plugins" / "profile" / "x"
    folder.mkdir(parents=True)
    (folder / "vm.xplane.pb").write_bytes(_device.xspace({
        "/host:CPU": {"python": [("device.watch", 0, 5 * _device.MS)]},
        "/device:TPU:0": {
            "XLA Ops": ops,
            "XLA Modules": [(f"{k}({7 + i})", s, e) for i, (k, s, e) in enumerate(modules)]},
        "/device:TPU:1": {"XLA Ops": [("%dsa_attend.1 = bf16[8] custom-call(", 0, 10**9)]},
    }))
    device_modules._LOADED.clear()
    return _glm.material_of(_glm.NODE)


def lm_programs():
    """Three jobs: a 3 s prefill, 0.5 s of decode, then SD1.5's programs."""
    ms = _device.MS
    return [("jit__clip_apply", 0, 400_000)] + [
        (k, s + ms, e + ms) for k, s, e in _device.lm_modules(3, 4000, 3_000_000, 500_000)]


def test_device_the_kernels_share_is_its_events_inside_the_models_programs(tmp_path, monkeypatch):
    ms, modules = _device.MS, lm_programs()
    kernel = "%dsa_attend.{} = bf16[2048,64,512]{{2,1,0}} custom-call("
    ops = [("%fusion.1 = bf16[8]{0} fusion(", ms, 2 * ms),
           (kernel.format(99), 100, 200_000)]                    # before any of the programs
    for kind, start, end in modules:
        if kind == "jit_prefill":                                # 80 calls of 10 ms a prefill
            ops += [(kernel.format(i), start + (1 + 3 * i) * 10 * ms, start + (2 + 3 * i) * 10 * ms)
                    for i in range(80)]
            ops.append(("%dsa_attendant.3 = f32[8]{0} fusion(", start, start + ms))  # another name
    material = traced(tmp_path, monkeypatch, ops, modules)
    # 3 x 800 ms of the kernel in 3 x (3,000 + 500) ms of the two programs
    assert read(material) == pytest.approx(100.0 * 800 / 3500)
    prefill_ms = _glm.reader("prefill_device_ms.lm")(material)
    assert prefill_ms == pytest.approx(3000.0)
    # with the prefill's share of the programs' time, the kernel's ms a call of a prefill's 80
    assert read(material) / 100.0 * 3500 / 80 == pytest.approx(10.0)


def test_device_the_parents_programs_hold_no_such_kernel_and_say_nothing(tmp_path, monkeypatch):
    ms, modules = _device.MS, lm_programs()
    ops = [("%fusion.1 = bf16[8]{0} fusion(", ms, 2 * ms)] + [
        ("%gather_fusion.4 = bf16[64,2048,576]{2,1,0} fusion(", start + ms, start + 40 * ms)
        for kind, start, end in modules if kind == "jit_prefill"]
    assert read(traced(tmp_path, monkeypatch, ops, modules)) is None


def test_device_a_trace_without_the_models_programs_says_nothing(tmp_path, monkeypatch):
    ms = _device.MS
    modules = _device.txt2img_modules(3, 800)
    ops = [("%dsa_attend.1 = bf16[8] custom-call(", ms, 2 * ms)]   # in no program of the model
    assert read(traced(tmp_path, monkeypatch, ops, modules)) is None


def test_no_trace_says_nothing():
    assert read({"spans": {}, "records": [], "trace": None}) is None
    assert read(dict(_glm.material_of(_glm.NODE), trace=None)) is None


def test_the_metric_is_the_manifests_last_and_lists_the_glm_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        manifest = json.load(fh)
    names = [m["name"] for m in manifest["per_layer"]]
    assert names.index("keys_selected_pct.lm") < names.index(METRIC)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == METRIC]
    assert entry == {
        "name": METRIC, "unit": "%", "better": "lower", "source": "device_trace",
        "layer": "kernels", "moves": "images_per_s", "workloads": [CELL]}
    assert "kernels" in {m["layer"] for m in manifest["per_layer"] if m["name"] != METRIC}
    assert os.path.exists(os.path.join(HERE, "layer_metrics", METRIC + ".py"))
