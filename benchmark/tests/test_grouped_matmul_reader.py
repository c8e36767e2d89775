"""`grouped_matmul_device_pct.lm` (PR 64) on made traces: the grouped
product kernel's events inside the language model's prefill over the
prefill's device time; nothing where the trace has no such kernel (the
parent's programs and LongCat-Flash's, whose rungs keep `ragged-dot`), no
such program, or no trace; and the metric's entry in the manifest.

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

METRIC = "grouped_matmul_device_pct.lm"
# the cells whose served shapes the shipped route gives the kernel
CELLS = [f"{model}_txt2img_512.closed2" for model in (
    "deepseek_v2_rewrite", "solar_open2_rewrite", "k_exaone_rewrite", "ling_flash_rewrite",
    "nemotron3_nano_rewrite", "glm_5_2_longdoc", "sdar_30b_a3b_rewrite", "dots3_note_longdoc")]


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# the made trace, the programs and GLM's node of PR 53's checks
_attend = _load(os.path.join(HERE, "tests", "test_dsa_attend_readers.py"), "gmm_uses_attend_readers")
traced, lm_programs, _device, _glm = (
    _attend.traced, _attend.lm_programs, _attend._device, _attend._glm)
read = _load(os.path.join(HERE, "layer_metrics", METRIC + ".py"), "gmm_layer_metric").read


def test_device_the_grouped_kernels_share_is_its_events_inside_the_prefill(tmp_path, monkeypatch):
    ms, modules = _device.MS, lm_programs()
    kernel = "%grouped_matmul.{} = bf16[8192,3072]{{1,0}} custom-call("
    ops = [("%fusion.1 = bf16[8]{0} fusion(", ms, 2 * ms),
           (kernel.format(99), 100, 200_000)]                    # before any of the programs
    for kind, start, end in modules:
        if kind == "jit_prefill":                                # 32 calls of 2 ms a prefill
            ops += [(kernel.format(i), start + 5 * i * ms, start + (5 * i + 2) * ms)
                    for i in range(32)]
            # the upper rungs' form beside it, and another name that begins alike
            ops.append(("%ragged-dot.3 = bf16[65536,3072]{1,0} custom-call(",
                        start + 3 * ms, start + 4 * ms))
            ops.append(("%grouped_matmuls.3 = f32[8]{0} fusion(", start + 4 * ms, start + 5 * ms))
        if kind == "jit_decode":                                 # not the metric's base
            ops.append((kernel.format(7), start + ms, start + 2 * ms))
    material = traced(tmp_path, monkeypatch, ops, modules)
    # 3 x 64 ms of the kernel in 3 x 3,000 ms of the prefill
    assert read(material) == pytest.approx(100.0 * 64 / 3000)
    prefill_ms = _glm.reader("prefill_device_ms.lm")(material)
    assert read(material) / 100.0 * prefill_ms == pytest.approx(64.0)  # ms a prefill


def test_device_the_parents_programs_keep_ragged_dot_and_say_nothing(tmp_path, monkeypatch):
    ms, modules = _device.MS, lm_programs()
    ops = [("%fusion.1 = bf16[8]{0} fusion(", ms, 2 * ms)] + [
        ("%ragged-dot.7 = bf16[8192,3072]{1,0} custom-call(", start + ms, start + 8 * ms)
        for kind, start, end in modules if kind == "jit_prefill"] + [
        ("%expert_matvec.3 = bf16[8,16,512]{2,1,0} custom-call(", start + ms, start + 2 * ms)
        for kind, start, end in modules if kind == "jit_decode"]
    assert read(traced(tmp_path, monkeypatch, ops, modules)) is None


def test_device_a_trace_without_a_prefill_says_nothing_of_the_grouped_kernel(
        tmp_path, monkeypatch):
    ms = _device.MS
    modules = _device.txt2img_modules(3, 800)
    ops = [("%grouped_matmul.1 = bf16[8] custom-call(", ms, 2 * ms)]  # in no program of the model
    assert read(traced(tmp_path, monkeypatch, ops, modules)) is None


def test_no_trace_says_nothing_of_the_grouped_kernel():
    assert read({"spans": {}, "records": [], "trace": None}) is None
    assert read(dict(_glm.material_of(_glm.NODE), trace=None)) is None


def test_the_grouped_kernels_metric_is_the_manifests_last_and_lists_the_cells_that_take_it():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        manifest = json.load(fh)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == METRIC]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": METRIC, "unit": "%", "better": "lower", "source": "device_trace",
        "layer": "kernels", "moves": "images_per_s"}
    assert entry["workloads"][:len(CELLS)] == CELLS
    cells = {w["name"] for w in manifest["workloads"]}
    assert set(entry["workloads"]) <= cells
    # LongCat-Flash's rungs keep `ragged-dot`, and the cells without an expert layer have none
    assert "longcat_flash_longdoc_txt2img_512.closed2" not in entry["workloads"]
    # no share of a roofline or of a peak came with the kernel
    names = [m["name"] for m in manifest["per_layer"]]
    assert not [n for n in names if n.startswith("grouped_matmul") and n != METRIC]
    assert os.path.exists(os.path.join(HERE, "layer_metrics", METRIC + ".py"))
