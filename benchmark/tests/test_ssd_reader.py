"""`ssd_device_pct.lm` (PR 55) on made-up material: self time under the
`ssd` scope alone, the Pallas kernel's call counted by the path it was
traced under, a `mamba` scope's other parts and a scope that only begins
with the same letters left out; nothing without a trace; and the
manifest's entry for it, which lists the two cells whose models hold a
Mamba-2 layer.

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

import scoped_self_time  # noqa: E402

NAME = "ssd_device_pct.lm"
NEMOTRON_CELL = "nemotron3_nano_rewrite_txt2img_512.closed2"
GRANITE_CELL = "granite_4_0_h_micro_longdoc_txt2img_512.closed2"


def _reader():
    spec = importlib.util.spec_from_file_location(
        "layer_metric", os.path.join(HERE, "layer_metrics", NAME + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_the_scans_share_is_self_time_under_the_ssd_scope_alone():
    ssd = _reader()
    assert (ssd.PROGRAMS, ssd.SCOPE) == (("jit_prefill", "jit_decode"), "ssd")
    layer = "jit(prefill)/while/body/closed_call/layer_0/jit(<unknown>)/mamba/"
    body = "jit(decode)/jit(main)/while/body/"
    operations = [
        (0, 100, layer + "in_proj/dot_general"),
        (100, 150, layer + "ssd/cumsum"),
        (150, 450, layer + "ssd/jit(ssd_chunk)/ssd_chunk/pallas_call"),   # the kernel
        (450, 500, layer + "ssd/mul"),                                      # the skip
        (500, 600, layer + "norm/mul"),
        (600, 700, "jit(prefill)/while/body/closed_call/layer_0/mlp/dot_general"),
        (1000, 3000, "jit(decode)/jit(main)/while"),                        # the loop of 2,000 ...
        (1100, 1300, body + "layer_3/mamba/ssd/mul"),
        (1300, 1500, body + "layer_3/mamba/out_proj/dot_general"),
        (1500, 1600, body + "layer_3/mamba/ssdlike/mul"),                   # no such scope
    ]
    both = [(0, 700), (1000, 3100)]
    # under ssd 50 + 300 + 50 in the prefill and 200 in the decode, of 700 + 2,000
    assert scoped_self_time.self_time_pct(
        operations, both, scoped_self_time.under(ssd.SCOPE)) == pytest.approx(100.0 * 600 / 2700)
    # operations that name no scope at all: no share
    bare = [(start, end, "") for start, end, _ in operations]
    assert scoped_self_time.self_time_pct(bare, both, scoped_self_time.under(ssd.SCOPE)) is None


def test_without_a_trace_the_reader_leaves_its_metric_out():
    read = _reader().read
    assert read({"spans": {}, "records": [], "trace": None, "prompt": {}}) is None
    assert read({"spans": {}, "records": [], "prompt": {}}) is None


def test_the_manifest_lists_it_for_the_two_cells_with_a_mamba_layer():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        manifest = json.load(fh)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower", "source": "device_trace",
        "layer": "sampling programs", "moves": "images_per_s",
        "workloads": [NEMOTRON_CELL, GRANITE_CELL]}
    # the cells the mixer's whole share is listed for, in the same order
    (whole,) = [m for m in manifest["per_layer"] if m["name"] == "ssm_device_pct.lm"]
    assert whole["workloads"][:2] == entry["workloads"]
    assert (whole["layer"], whole["moves"]) == (entry["layer"], entry["moves"])
    cells = {w["name"] for w in manifest["workloads"]}
    assert set(entry["workloads"]) <= cells
    assert os.path.exists(os.path.join(HERE, "layer_metrics", NAME + ".py"))
