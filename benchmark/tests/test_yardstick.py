"""Checks of the yardstick itself, run by hand on the CPU in seconds:

    python -m pytest benchmark/tests -q
"""

import json
import os
import re
import sys
import time

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import client  # noqa: E402
import loadgen  # noqa: E402
import stats  # noqa: E402
import xplane  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
    MANIFEST = json.load(fh)
BURST = {"loop": "open", "schedule_seed": 7, "rate_per_s": 0.5, "burst_mean": 3,
         "burst_cap": 6, "burst_gap_s": 0.1}


def test_union_merges_overlapping_and_touching_intervals():
    merged = stats.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)])
    assert merged == [(0, 4), (5, 7)]
    assert stats.covered(merged) == 6


def test_gaps_include_the_window_edges():
    assert stats.gaps([(2, 4), (6, 8)], (0, 10)) == [(0, 2), (4, 6), (8, 10)]
    assert stats.gaps([(0, 10)], (0, 10)) == []
    assert stats.gaps([], (3, 5)) == [(3, 5)]


@pytest.mark.parametrize("p, expected", [(50, 5), (90, 9), (95, 10), (100, 10), (1, 1)])
def test_percentile_is_nearest_rank(p, expected):
    assert stats.percentile(range(10, 0, -1), p) == expected


def test_schedule_depends_on_its_seed_alone():
    assert loadgen.schedule(BURST, 60) == loadgen.schedule(dict(BURST), 60)
    assert loadgen.schedule(BURST, 60) != loadgen.schedule({**BURST, "schedule_seed": 8}, 60)
    assert loadgen.schedule(BURST, 30) == [t for t in loadgen.schedule(BURST, 60) if t < 30][
        :len(loadgen.schedule(BURST, 30))]


def test_schedule_mean_rate_is_the_stated_one():
    horizon = 40000.0
    rate = len(loadgen.schedule(BURST, horizon)) / horizon
    assert abs(rate / BURST["rate_per_s"] - 1) < 0.05


def test_schedule_bursts_are_capped_and_spaced():
    # bursts far apart, so that two seldom overlap
    due = loadgen.schedule({**BURST, "rate_per_s": 0.005}, 400000)
    runs, run = [], 1
    for a, b in zip(due, due[1:]):
        if abs((b - a) - BURST["burst_gap_s"]) < 1e-6:
            run += 1
        else:
            runs.append(run)
            run = 1
    assert max(runs) == BURST["burst_cap"] and len(runs) > 500
    assert 2.4 < sum(runs) / len(runs) < 3.0  # E[min(G, 6)] = 2.74 for mean 3


def test_closed_loop_keeps_its_clients_busy_and_stops_on_time():
    sent = []

    def send(index, due):
        sent.append(due)
        time.sleep(0.05)
        return {"index": index}

    result = loadgen.run({"loop": "closed", "clients": 2}, 0.3, send)
    assert [r["index"] for r in result["records"]] == list(range(len(sent)))
    assert 10 <= len(sent) <= 14
    assert max(sent) - result["start"] < 0.3


def test_reduce_takes_busy_time_ops_and_gaps_from_a_hand_made_trace():
    ms = 1_000_000
    loaded = {
        "window": (0, 100 * ms),
        "devices": {"/device:TPU:0": {
            xplane.OPS_LINE: [("conv", 10 * ms, 30 * ms), ("conv", 30 * ms, 40 * ms),
                              ("copy", 60 * ms, 80 * ms), ("copy", 65 * ms, 70 * ms)],
            xplane.MODULES_LINE: [("jit_sample", 10 * ms, 45 * ms),
                                  ("jit_decode", 60 * ms, 80 * ms)],
        }},
    }
    reduced = xplane.reduce(loaded)
    assert reduced["busy_s"] == pytest.approx(0.050)
    assert reduced["window_s"] == pytest.approx(0.100)
    # the nested copy is taken out of its parent: self time, by kind
    assert reduced["breakdown"]["device_ops"] == [["conv", pytest.approx(0.030)],
                                                  ["copy", pytest.approx(0.020)]]
    gaps = dict(map(tuple, reduced["breakdown"]["idle_gaps"]))
    assert gaps["after jit_decode | before end of slice"] == pytest.approx(0.020)
    assert gaps["after jit_sample | before jit_decode"] == pytest.approx(0.015)
    assert gaps["after start of slice | before jit_sample"] == pytest.approx(0.010)
    assert gaps["inside programs, between operations"] == pytest.approx(0.005)
    assert xplane.reduce({"window": (0, 1), "devices": {}}) is None
    # held open for 70 ms only: what came later is cut off
    cut = xplane.reduce(loaded, slice_s=0.070)
    assert cut["window_s"] == pytest.approx(0.070)
    assert cut["busy_s"] == pytest.approx(0.040)


def test_operations_are_summed_by_kind_and_by_self_time():
    assert xplane.kind("%flash_attention.117 = bf16[16,4096,128]{2,1,0} custom-call(") == \
        "flash_attention"
    assert xplane.kind("jit_silu(6576635703218322248)") == "jit_silu"
    ops = [("%while.1 = x", 0, 100), ("%a.1 = x", 10, 30), ("%a.2 = x", 30, 50),
           ("%b = x", 60, 70), ("%c.3 = x", 100, 120)]
    assert xplane.self_times(ops) == {"while": 50, "a": 40, "b": 10, "c": 20}


def test_metrics_text_is_parsed():
    text = (
        "# HELP cdt_jax_compiles programs\n"
        "cdt_jax_compiles 157\ncdt_jax_compile_time_seconds 2.5\n"
        'cdt_device_memory_bytes{device="0",stat="peak_bytes_in_use"} 1.1e10\n'
        'cdt_device_memory_bytes{device="0",stat="bytes_limit"} 1.6e10\n'
    )
    parsed = client.parse_metrics(text)
    assert parsed["compiles"] == 157 and parsed["compile_s"] == 2.5
    assert parsed["peak_bytes_in_use"] == {"0": 11_000_000_000}


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def cells_of(metric):
    return metric.get("workloads") or [w["name"] for w in MANIFEST["workloads"]]


def test_manifest_names_and_units_use_the_allowed_characters():
    names = [x["name"] for key in ("configs", "workloads", "end_to_end", "per_layer")
             for x in MANIFEST[key]]
    names += [w[k] for w in MANIFEST["workloads"] for k in ("config", "traffic")]
    assert all(NAME.match(n) for n in names), names
    metrics = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    assert len({m["name"] for m in metrics}) == len(metrics)


def test_every_layer_metric_moves_a_metric_its_cells_report():
    end_to_end = {m["name"]: set(cells_of(m)) for m in MANIFEST["end_to_end"]}
    for metric in MANIFEST["per_layer"]:
        assert set(cells_of(metric)) <= end_to_end[metric["moves"]], metric["name"]
    for cell in MANIFEST["workloads"]:
        mine = [n for n, cells in end_to_end.items() if cell["name"] in cells]
        assert "setup_s" in mine and len(mine) >= 2
        assert any(cell["name"] in cells_of(m) for m in MANIFEST["per_layer"])


def test_every_file_a_cell_names_exists_and_every_configuration_has_a_cell():
    used = {w["config"] for w in MANIFEST["workloads"]}
    assert used == {c["name"] for c in MANIFEST["configs"]}
    for config in MANIFEST["configs"]:
        assert os.path.isfile(os.path.join(ROOT, config["file"]))
    for cell in MANIFEST["workloads"]:
        assert cell["chips"] in (1, 4)
        with open(os.path.join(HERE, "workloads", cell["name"] + ".json")) as fh:
            work = json.load(fh)
        assert os.path.isfile(os.path.join(ROOT, work["workflow"]))
        assert os.path.isfile(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
        assert work["rate"]["metric"] in {m["name"] for m in MANIFEST["end_to_end"]
                                          if cell["name"] in cells_of(m)}
    for metric in MANIFEST["per_layer"]:
        assert os.path.isfile(os.path.join(HERE, "layer_metrics", metric["name"] + ".py"))


@pytest.mark.parametrize("name", sorted(os.listdir(os.path.join(HERE, "workflows"))))
def test_the_benchmarks_workflows_are_the_committed_ones(name):
    with open(os.path.join(HERE, "workflows", name), "rb") as mine:
        with open(os.path.join(ROOT, "workflows", name), "rb") as theirs:
            assert mine.read() == theirs.read()
