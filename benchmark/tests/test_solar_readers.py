"""The Solar-Open2 cell's readers and counts on made-up material: the value
where the spans carry what they read (a model whose state is of two kinds),
None where the program says nothing of it (the parent's, another model's);
the counts against a hand calculation and against the program's own; the
share of the device's time under the `kda` scope on hand-made operations.
Two checks of `test_device_readers.py` pinned what PR 36 found (the manifest's
last twelve metrics, the two `lm_work` files); their forms that hold once a
PR appends a metric or a model are here, and the tier-1 adopter
(`tests/test_benchmark_yardstick.py`) takes these in their place.

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
import solar_counts  # noqa: E402

SOLAR = solar_counts.config()
CELL = "solar_open2_rewrite_txt2img_512.closed2"


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# the hand-written trace, the spans and the window of test_device_readers.py
_device = _load(os.path.join(HERE, "tests", "test_device_readers.py"), "solar_uses_device_readers")

SOLAR_NODE = dict(
    prompt_tokens=8192, new_tokens=256, layers=4, full_layers=1, linear_layers=3,
    experts_held=40, experts_total=320, cache_bytes=8448 * 4096, state_bytes=13025280,
    prefill_chunks=128, prefill_routed_pairs=8192 * 32, prefill_routed_pairs_held=33000,
    decode_routed_pairs=256 * 32, decode_routed_pairs_held=1100)


def solar_reader(name: str):
    """The metric's read(), loaded as run.py loads it."""
    return _load(os.path.join(HERE, "layer_metrics", name + ".py"), "layer_metric").read


def solar_material(node, jobs=3):
    return _device.window(
        [_device.lm_job(1.4 * i, node) for i in range(jobs)],
        _device.lm_prompt(SOLAR["registry_name"]))


def test_state_mb_is_the_nodes_state_bytes_and_cache_gb_what_grows():
    material = solar_material(SOLAR_NODE)
    assert solar_reader("state_mb.lm")(material) == pytest.approx(13.02528)
    assert solar_reader("cache_gb.lm")(material) == pytest.approx(0.034603008)
    assert solar_reader("layer_passes_per_token.lm")(material) == pytest.approx(4.0)
    assert solar_reader("experts_held_share_pct.lm")(material) == pytest.approx(
        100.0 * (33000 + 1100) / (8448 * 32))


def test_state_mb_reads_zero_of_a_model_without_such_state_and_nothing_of_the_parent():
    # DeepSeek-V2 and Ouro since PR 38 say 0; a program from before it says nothing
    assert solar_reader("state_mb.lm")(solar_material(dict(_device.OURO_NODE, state_bytes=0))) == 0
    assert solar_reader("state_mb.lm")(solar_material(_device.DEEPSEEK_NODE)) is None
    assert solar_reader("state_mb.lm")({"spans": {}, "records": [], "trace": None}) is None


def test_solar_counts_are_the_ones_the_issue_worked_out():
    """By hand: the softmax mixer 3 x 4096 x 8192 + 2 x 4096 x 1024 =
    109,051,904; a KDA mixer 4 x 4096 x 8192 = 134,217,728 in q, k, v and o,
    2 x (4096 x 128 + 128 x 8192) = 3,145,728 in the two gates, 262,144 in
    beta, 98,304 in the filters, 64 + 8,192 + 128 small: 137,732,288; an
    expert 3 x 4096 x 1280 = 15,728,640; the router 1,310,720."""
    assert solar_counts.layers(SOLAR) == (1, 3)
    assert solar_counts.gqa_params(SOLAR) == 109051904
    assert solar_counts.kda_params(SOLAR) == 134217728 + 3145728 + 262144 + 98304 + 8384
    assert solar_counts.kda_params(SOLAR) == 137732288
    assert solar_counts.expert_params(SOLAR) == 15728640
    assert solar_counts.always_params(SOLAR) == 1310720 + 15728640
    assert solar_counts.total_params(SOLAR) == SOLAR["as_run"]["parameters"]["lm"] == 3308353344
    assert 2 * solar_counts.total_params(SOLAR) == pytest.approx(6.617e9, rel=1e-4)
    # 8 key heads x 128 x (K + V) x 2 B in the one softmax layer
    assert solar_counts.cache_bytes(SOLAR, 1) == SOLAR["as_run"]["cache_bytes_per_token"] == 4096
    # 3 x (64 x 128 x 128 x 4 B + 3 x 3 x 8192 x 2 B)
    assert solar_counts.state_bytes(SOLAR) == 3 * (4194304 + 147456) == 13025280
    assert solar_counts.state_bytes(SOLAR) == SOLAR["as_run"]["state_bytes"]


def test_a_decode_step_moves_1_57_gb_and_a_prefill_is_12_tflop():
    step = solar_counts.decode_step_bytes(SOLAR, 1.0, 8192 + 128)
    # mixers 1.0445 GB, router and shared 0.1363, one held expert a layer
    # 0.1258, the head 0.2013, 8,320 positions 0.0341, the state twice 0.0261
    by_hand = 1.0445e9 + 0.1363e9 + 0.1258e9 + 0.2013e9 + 0.0341e9 + 0.0261e9
    assert step == pytest.approx(by_hand, rel=1e-3)
    assert step / 819e9 == pytest.approx(1.915e-3, rel=2e-3)        # seconds at the roofline
    # the state is read and written: leaving it out would read 1.7 % low
    assert 2 * solar_counts.state_bytes(SOLAR) / step == pytest.approx(0.0166, abs=2e-4)
    flops = solar_counts.prefill_flops(SOLAR, 8192, 4 * 8192)
    assert flops == pytest.approx(12.02e12, rel=2e-3)
    assert solar_counts.causal_attention_flops(SOLAR, 8192) == pytest.approx(1.0996e12, rel=1e-4)
    # a chunk and head: 5 x 64^2 x 128 + 6 x 64 x 128^2 = 8,912,896; x 128 chunks x 64 heads
    assert solar_counts.delta_rule_flops(SOLAR, 8192) == 8912896 * 128 * 64
    # a short chunk is padded
    assert solar_counts.delta_rule_flops(SOLAR, 8193) == 8912896 * 129 * 64
    peak = solar_counts.peaks("TPU v5 lite")
    least_s = solar_counts.prefill_bytes(SOLAR, 8192) / peak["bytes_per_s"]
    assert flops / peak["flops_per_s"] > least_s
    with pytest.raises(KeyError):
        solar_counts.peaks("TPU v9")


def test_the_sizes_the_solar_counts_read_are_the_registrys():
    sys.path.insert(0, ROOT)
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models import get_config, solar_open2
    from comfyui_distributed_tpu.models.registry import create_model

    model = get_config(SOLAR["registry_name"])
    assert solar_open2.param_count(model) == solar_counts.total_params(SOLAR)
    assert (model.full_layers, model.linear_layers) == solar_counts.layers(SOLAR)
    assert model.kda_chunk == SOLAR["as_run"]["kda_chunk"]
    lm = create_model(SOLAR["registry_name"])
    lm.dtype = jnp.dtype(SOLAR["as_run"]["weights_dtype"])
    described = lm.describe(8448)
    assert described["cache_bytes"] == solar_counts.cache_bytes(SOLAR, 8448)
    assert described["state_bytes"] == solar_counts.state_bytes(SOLAR)


def test_device_the_solar_cells_shares_of_the_peaks_against_solar_counts_by_hand(
        tmp_path, monkeypatch):
    """A synthetic 2.30 ms step and 300 ms prefill."""
    traced = _device.tracing(tmp_path, monkeypatch)
    traced([("jit__clip_apply", 0, 400_000)] + [
        (k, s + _device.MS, e + _device.MS)
        for k, s, e in _device.lm_modules(3, 1400, 300_000, 256 * 2_300)])
    material = solar_material(SOLAR_NODE)
    assert solar_reader("prefill_device_ms.lm")(material) == pytest.approx(300.0)
    assert solar_reader("decode_device_ms_per_token.lm")(material) == pytest.approx(2.30)
    # 1,100 pairs on held experts over 256 steps and 4 layers
    step = solar_counts.decode_step_bytes(SOLAR, 1100 / 1024, 8192 + 128)
    assert solar_reader("decode_hbm_roofline_pct.lm")(material) == pytest.approx(
        100.0 * step / 0.00230 / 819e9)
    assert 82.0 < solar_reader("decode_hbm_roofline_pct.lm")(material) < 85.0
    assert solar_reader("prefill_mxu_peak_pct.lm")(material) == pytest.approx(
        100.0 * solar_counts.prefill_flops(SOLAR, 8192, 33000) / 0.300 / 197e12)
    # the hand-written trace's operations say nothing of a scope: no share
    assert solar_reader("linear_attention_device_pct.lm")(material) is None
    untraced = dict(material, trace=None)
    assert solar_reader("linear_attention_device_pct.lm")(untraced) is None


def test_the_kda_share_is_self_time_under_the_scope_inside_the_two_programs():
    reader = _load(
        os.path.join(HERE, "layer_metrics", "linear_attention_device_pct.lm.py"), "kda_share")
    inside = "jit(prefill)/jit(main)/layer_1/kda/delta/while/body/dot_general"
    other = "jit(prefill)/jit(main)/layer_0/gqa/dot_general"
    operations = [
        (0, 100, other),                        # before any program of the two: not counted
        (1000, 1400, other),                    # 400 outside the scope
        (1400, 2400, "jit(prefill)/jit(main)/layer_1/kda/delta/while"),  # a loop of 1,000 ...
        (1500, 1800, inside), (1900, 2300, inside),                      # ... 700 of it its body
        (2400, 2500, "jit(prefill)/jit(main)/layer_1/moe/kdalike/mul"),  # no such scope
        (5000, 5600, "jit(decode)/jit(main)/while/body/layer_2/kda/gates/mul"),
        (9000, 9900, "jit(vae_apply)/conv"),    # another program
    ]
    programs = [(1000, 2600), (5000, 5700)]
    # under the scope: the loop's own 300 + its body's 700 + 600; all: those + 400 + 100
    assert reader.share_pct(operations, programs) == pytest.approx(100.0 * 1600 / 2100)
    assert reader.share_pct(operations, [(9000, 9950)]) == pytest.approx(0.0)
    # operations that bear no scope path at all: nothing to read, not zero
    bare = [(start, end, "") for start, end, _ in operations]
    assert reader.share_pct(bare, programs) is None
    assert reader.share_pct([], programs) is None
    assert reader.SCOPE.search("a/kda") and not reader.SCOPE.search("a/kda_like/b")


def _scoped_trace() -> bytes:
    """An XSpace whose first device plane says of each operation, in the
    table of event metadata, which program it is of and what scope it was
    traced under, as the TPU's profiler does; times in ns."""
    field = _device._field
    stat_ids = {"program_id": 1, "tf_op": 2, "hlo_category": 3}
    prefill, decode, vae = 17787146694913245026, 4854901138542270445, 99
    entries = [  # (name, program, tf_op)
        ("jit_prefill(%d)" % prefill, None, None), ("jit_decode(%d)" % decode, None, None),
        ("jit_vae_apply(%d)" % vae, None, None),
        ("%fusion.1 = f32[8]{0} fusion(", prefill, "jit(prefill)/layer_0/gqa/dot_general"),
        ("%while.2 = (s32[]) while(", prefill, "jit(prefill)/layer_1/kda/delta/while"),
        ("%fusion.3 = f32[8]{0} fusion(", prefill, "jit(prefill)/layer_1/kda/delta/while/body/dot"),
        ("%copy.4 = f32[8]{0} copy(", prefill, None),  # an operation that says nothing
        ("%fusion.5 = f32[8]{0} fusion(", decode, "jit(decode)/while/body/layer_2/kda/gates/mul"),
        ("%fusion.6 = f32[8]{0} fusion(", vae, "jit(vae_apply)/kda/conv"),  # another program's
    ]
    ids = {name: i for i, (name, _, _) in enumerate(entries, 1)}
    lines = {
        "XLA Modules": [(entries[0][0], 1000, 2600), (entries[1][0], 5000, 5700),
                        (entries[2][0], 9000, 9950)],
        "XLA Ops": [
            (entries[3][0], 1000, 1400), (entries[4][0], 1400, 2400), (entries[5][0], 1500, 1800),
            (entries[5][0], 1900, 2300), (entries[6][0], 2400, 2500), (entries[7][0], 5000, 5600),
            (entries[8][0], 9000, 9900)],
    }
    plane = field(1, 1) + field(2, "/device:TPU:0")
    for line_id, (line_name, events) in enumerate(lines.items(), 1):
        line = field(1, line_id) + field(2, line_name) + field(3, 0)
        for name, start, end in events:
            line += field(4, field(1, ids[name]) + field(2, start * 1000)
                          + field(3, (end - start) * 1000))
        plane += field(3, line)
    for name, program, scope in entries:
        meta = field(1, ids[name]) + field(2, name)
        meta += field(5, field(1, stat_ids["hlo_category"]) + field(5, "fusion"))
        if program is not None:
            meta += field(5, field(1, stat_ids["program_id"]) + field(3, program))
        if scope is not None:
            meta += field(5, field(1, stat_ids["tf_op"]) + field(5, scope))
        plane += field(4, field(1, ids[name]) + field(2, meta))
    for name, i in stat_ids.items():
        plane += field(5, field(1, i) + field(2, field(1, i) + field(2, name)))
    host = field(1, 2) + field(2, "/host:CPU")
    return field(1, host) + field(1, plane)


def test_the_kda_share_finds_an_operations_scope_in_the_planes_event_metadata(
        tmp_path, monkeypatch):
    reader = _load(
        os.path.join(HERE, "layer_metrics", "linear_attention_device_pct.lm.py"), "kda_share")
    _device.tracing(tmp_path, monkeypatch)([("jit_prefill", 0, 1)])  # argv; the folder
    (path,) = tmp_path.rglob("*.xplane.pb")
    path.write_bytes(_scoped_trace())
    device_modules._LOADED.clear()
    table = reader.scopes(str(path))
    # the two programs' operations by name, none of another program's, no program itself
    assert table == {
        "%fusion.1 = f32[8]{0} fusion(": "jit(prefill)/layer_0/gqa/dot_general",
        "%while.2 = (s32[]) while(": "jit(prefill)/layer_1/kda/delta/while",
        "%fusion.3 = f32[8]{0} fusion(": "jit(prefill)/layer_1/kda/delta/while/body/dot",
        "%copy.4 = f32[8]{0} copy(": "",
        "%fusion.5 = f32[8]{0} fusion(": "jit(decode)/while/body/layer_2/kda/gates/mul",
    }
    programs = [(1000, 2600), (5000, 5700)]
    found = reader.operations(str(path), programs)
    assert [op[:2] for op in found] == [
        (1000, 1400), (1400, 2400), (1500, 1800), (1900, 2300), (2400, 2500), (5000, 5600)]
    # under the scope: the loop's own 300 + its body's 700 + 600; all: those + 400 + 100
    material = solar_material(SOLAR_NODE)
    assert reader.read(material) == pytest.approx(100.0 * 1600 / 2100)


def test_the_solar_cell_is_listed_where_its_readers_find_something():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        manifest = json.load(fh)
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    for name in ("state_mb.lm",):
        assert per_layer[name]["workloads"] == [CELL]
        assert (per_layer[name]["source"], per_layer[name]["layer"], per_layer[name]["moves"]) == (
            "program_counter", "sampling programs", "images_per_s")
    for name in ("experts_held_share_pct.lm", "cache_gb.lm", "decode_hbm_roofline_pct.lm",
                 "prefill_mxu_peak_pct.lm", "generate_ms.lm", "layer_passes_per_token.lm"):
        assert per_layer[name]["workloads"][-1] == CELL, name
    (config,) = [c for c in manifest["configs"] if c["name"] == "solar-open2-250b"]
    assert config["file"] == "benchmark/configs/solar-open2-250b.json"
    assert config["source"] == SOLAR["source"] and config["reduced"] == SOLAR["reduced"]


# --- two checks of test_device_readers.py, in the form that outlives a PR ---


def test_device_the_twelve_metrics_of_pr_36_have_their_readers_and_lie_together():
    """`test_device_every_new_metric_has_its_reader_and_names_its_cells`,
    which also held the twelve to be the manifest's *last*: true until a
    PR appends one, as every later PR has to."""
    for name in _device.NEW:
        assert callable(_device.device_reader(name)), name
        assert _device.PER_LAYER[name]["workloads"]
        assert _device.PER_LAYER[name]["source"] == (
            "program_span" if name == _device.FROM_SPANS else "device_trace"), name
    assert [_device.PER_LAYER[n]["layer"] for n in _device.NEW] == (
        ["sampling programs"] * 8 + ["device"] * 4)
    names = list(_device.PER_LAYER)
    first = names.index(_device.NEW[0])
    assert names[first:first + len(_device.NEW)] == _device.NEW  # appended together, none moved
    assert first == 22  # and nothing put in front of them


def test_device_every_configuration_with_an_lm_work_file_is_found_by_its_registry_name(
        tmp_path, monkeypatch):
    """`test_device_the_lm_readers_find_a_models_work_by_the_checkpoint_the_
    workflow_loads`, which also held the files to be DeepSeek's and Ouro's
    alone: no table of models, so a third is found like the two."""
    import ouro_counts

    traced = _device.tracing(tmp_path, monkeypatch)
    traced([("jit__clip_apply", 0, 400_000)] + [
        (k, s + _device.MS, e + _device.MS)
        for k, s, e in _device.lm_modules(3, 3300, 354_000, 64 * 38_400)])
    jobs = [_device.lm_job(3.3 * i, _device.OURO_NODE) for i in range(3)]
    material = _device.window(jobs, _device.lm_prompt("ouro-2.6b"))
    cfg = ouro_counts.config()
    assert solar_reader("decode_hbm_roofline_pct.lm")(material) == pytest.approx(
        100.0 * ouro_counts.decode_step_bytes(cfg, 2048 + 32) / 0.0384 / 819e9)
    assert 73.0 < solar_reader("decode_hbm_roofline_pct.lm")(material) < 75.0
    assert solar_reader("prefill_mxu_peak_pct.lm")(material) == pytest.approx(
        100.0 * ouro_counts.prefill_flops(cfg, 2048) / 0.354 / 197e12)
    stems = {os.path.splitext(f)[0] for f in os.listdir(os.path.join(HERE, "lm_work"))
             if f.endswith(".py")}
    assert stems == {"deepseek-v2", "ouro-2.6b", "solar-open2-250b"}
    for stem in stems:
        with open(os.path.join(HERE, "configs", stem + ".json"), encoding="utf-8") as fh:
            name = json.load(fh)["registry_name"]
        work, found = device_modules.lm_work({"prompt": _device.lm_prompt(name)})
        assert found["registry_name"] == name and callable(work)
    unknown = _device.window(jobs, _device.lm_prompt("some-other-lm"))
    assert device_modules.lm_work(unknown) is None
    assert device_modules.lm_work({"prompt": _device.lm_prompt("sd15")}) is None
    assert solar_reader("decode_hbm_roofline_pct.lm")(unknown) is None
    assert solar_reader("prefill_mxu_peak_pct.lm")(unknown) is None
    assert solar_reader("decode_device_ms_per_token.lm")(unknown) == pytest.approx(38.4)


@pytest.mark.parametrize("mine, theirs", [
    ("workflows/rewrite-txt2img-solar-open2.json", "workflows/rewrite-txt2img-solar-open2.json"),
    ("reference/solar_open2.py", "comfyui_distributed_tpu/reference/solar_open2.py"),
])
def test_the_solar_copies_here_are_the_committed_files(mine, theirs):
    with open(os.path.join(HERE, mine), "rb") as a, open(os.path.join(ROOT, theirs), "rb") as b:
        assert a.read() == b.read()
