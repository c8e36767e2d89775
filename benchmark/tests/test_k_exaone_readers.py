"""The K-EXAONE cell's readers and counts on made-up material: the value
where the spans carry what they read (a model that drafts), None where
the program says nothing of it (the parent's, another model's); the
counts against a hand calculation and against the program's own; the
decode's share of the roofline counted over the steps, not the tokens;
the share of the decode's device time under the `mtp` scope on hand-made
operations. Two checks of `test_solar_readers.py` pinned what PR 38 found
(its cell the last of each list, three `lm_work` files); their forms that
hold once a PR appends a cell or a model are here, and the tier-1 adopter
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
import k_exaone_counts  # noqa: E402

CONFIG = k_exaone_counts.config()
CELL = "k_exaone_rewrite_txt2img_512.closed2"
SOLAR_CELL = "solar_open2_rewrite_txt2img_512.closed2"


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# the hand-written trace, the spans and the window of test_device_readers.py
_device = _load(os.path.join(HERE, "tests", "test_device_readers.py"), "k_exaone_uses_device_readers")

# a request of the cell: 260 steps kept 123 drafts (1 + 260 + 123 = 384)
NODE = dict(
    prompt_tokens=8192, new_tokens=384, draft_tokens=1, decode_steps=260, mtp_drafted=260,
    mtp_accepted=123, layers=5, window_layers=4, full_layers=2, window=128, ring_positions=136,
    experts_held=16, experts_total=128, cache_bytes=8576 * 8192, state_bytes=2228224,
    prefill_layer_passes=8192 * 5, decode_layer_passes=260 * 12, decode_experts_read=2300,
    prefill_routed_pairs=8192 * 32, prefill_routed_pairs_held=33000,
    decode_routed_pairs=260 * 80, decode_routed_pairs_held=2500)


def reader(name: str):
    """The metric's read(), loaded as run.py loads it."""
    return _load(os.path.join(HERE, "layer_metrics", name + ".py"), "layer_metric").read


def material_of(node, jobs=3, name=None):
    return _device.window(
        [_device.lm_job(2.4 * i, node) for i in range(jobs)],
        _device.lm_prompt(name or CONFIG["registry_name"]))


def test_the_share_of_drafts_kept_is_summed_over_the_windows_requests():
    other = dict(NODE, decode_steps=240, mtp_drafted=240, mtp_accepted=143,
                 decode_layer_passes=240 * 12, decode_routed_pairs=240 * 80)
    material = _device.window(
        [_device.lm_job(0.0, NODE), _device.lm_job(2.4, other)],
        _device.lm_prompt(CONFIG["registry_name"]))
    assert reader("mtp_accept_pct.lm")(material) == pytest.approx(100.0 * 266 / 500)
    assert reader("state_mb.lm")(material) == pytest.approx(2.228224)
    assert reader("cache_gb.lm")(material) == pytest.approx(0.070254592)
    assert reader("layer_passes_per_token.lm")(material) == pytest.approx(
        (2 * 8192 * 5 + 500 * 12) / (2 * 8576))
    assert reader("experts_held_share_pct.lm")(material) == pytest.approx(
        100.0 * 2 * 35500 / (2 * 8192 * 32 + 500 * 80))


def test_no_drafts_no_share():
    # without drafting, another model's node, a program from before the module: nothing
    plain = dict(NODE, draft_tokens=0, decode_steps=384, mtp_drafted=0, mtp_accepted=0)
    assert reader("mtp_accept_pct.lm")(material_of(plain)) is None
    assert reader("mtp_accept_pct.lm")(material_of(_device.DEEPSEEK_NODE)) is None
    assert reader("mtp_accept_pct.lm")({"spans": {}, "records": [], "trace": None}) is None
    assert reader("mtp_device_pct.lm")({"spans": {}, "records": [], "trace": None}) is None


def test_k_exaone_counts_are_the_ones_the_issue_worked_out():
    """By hand: a mixer 2 x 6144 x 8192 + 2 x 6144 x 1024 = 113,246,208
    (+ 256 in the two head norms); the dense feed-forward part 3 x 6144 x
    18432 = 339,738,624; an expert 3 x 6144 x 2048 = 37,748,736; a router
    6144 x 128 = 786,432."""
    assert k_exaone_counts.mixer_matrix_params(CONFIG) == 113_246_208
    assert k_exaone_counts.dense_params(CONFIG) == 339_738_624
    assert k_exaone_counts.expert_params(CONFIG) == 37_748_736
    assert k_exaone_counts.always_params(CONFIG) == 786_432 + 37_748_736
    assert k_exaone_counts.layers(CONFIG) == (4, 1)
    assert k_exaone_counts.sparse_layers(CONFIG) == 4
    sparse = k_exaone_counts.sparse_layer_params(CONFIG, 16)
    assert sparse == 113_246_464 + 2 * 6144 + 128 + 786_432 + 17 * 37_748_736  # 755.8 M
    assert k_exaone_counts.mtp_params(CONFIG, 16) == sparse + 2 * 6144 * 6144 + 3 * 6144
    assert k_exaone_counts.total_params(CONFIG) == CONFIG["as_run"]["parameters"]["lm"]
    assert k_exaone_counts.total_params(CONFIG) == 4_543_318_144               # 9.09 GB
    assert k_exaone_counts.cache_bytes(CONFIG, 8576) == 8576 * 8192             # 70.3 MB
    assert k_exaone_counts.state_bytes(CONFIG) == 4 * 136 * 4096 == 2_228_224


def test_a_drafting_step_moves_3_8_gb_a_plain_one_2_7_and_a_prefill_is_22_tflop():
    # 8.85 distinct held experts a step (2,300 over 260 steps), caches at mid-decode
    step = k_exaone_counts.decode_step_bytes(CONFIG, 2300 / 260, 8192 + 192)
    weights = (
        5 * 113_246_464 + 5 * 2 * 6144 + 339_738_624          # mixers, norms, the dense part
        + 4 * (786_432 + 128 + 37_748_736)                     # routers, biases, shared experts
        + 2300 / 260 * 37_748_736                              # the held experts read
        + 6144 + 2 * 19200 * 6144 + 4 * 6144                   # final norm, the head twice, 4 rows
        + 2 * 6144 * 6144 + 3 * 6144                           # W_eh and the module's norms
        + 113_246_464 + 2 * 6144 + 786_432 + 128 + 37_748_736  # its layer without routed experts
    )
    caches = 2 * (8192 + 192) * 4096 + 2_228_224 + 4 * 2 * 4096
    assert step == pytest.approx(2 * weights + caches)
    assert 3.7e9 < step < 3.9e9
    plain = k_exaone_counts.decode_step_bytes(CONFIG, 4.0, 8192 + 192, drafting=False)
    assert 2.5e9 < plain < 2.8e9 and plain < step
    # the bands as the window gives them: 4 x 8192 x (8192 x 128 - 128 x 127 / 2) operations
    band = k_exaone_counts.band_attention_flops(CONFIG, 8192)
    assert band == pytest.approx(4.0 * 8192 * (128 * 129 / 2 + (8192 - 128) * 128))
    assert band < 0.04 * k_exaone_counts.causal_attention_flops(CONFIG, 8192)
    assert k_exaone_counts.band_attention_flops(CONFIG, 100) == (
        k_exaone_counts.causal_attention_flops(CONFIG, 100))  # shorter than the window
    flops = k_exaone_counts.prefill_flops(CONFIG, 8192, 33000)
    per_token = (5 * 113_246_208 + 339_738_624 + 4 * (786_432 + 37_748_736)
                 + 2 * 6144 * 6144 + 2 * 6144 * 1024)
    assert flops == pytest.approx(
        2.0 * 8192 * per_token + 2.0 * 33000 * 37_748_736
        + k_exaone_counts.causal_attention_flops(CONFIG, 8192) + 4 * band + 2.0 * 19200 * 6144)
    assert 22e12 < flops < 23e12
    assert k_exaone_counts.prefill_bytes(CONFIG, 8192) < 2 * k_exaone_counts.total_params(CONFIG)


def test_the_sizes_the_k_exaone_counts_read_are_the_registrys():
    sys.path.insert(0, ROOT)
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models import get_config, k_exaone
    from comfyui_distributed_tpu.models.registry import create_model

    model = get_config(CONFIG["registry_name"])
    assert k_exaone.param_count(model) == k_exaone_counts.total_params(CONFIG)
    assert (model.window_layers, model.full_layers) == k_exaone_counts.layers(CONFIG)
    assert model.ring_positions == CONFIG["as_run"]["ring_positions"]
    shapes = k_exaone.param_shapes(model)
    assert k_exaone.count_params(shapes["mtp"]) == k_exaone_counts.mtp_params(CONFIG, 16)
    lm = create_model(CONFIG["registry_name"])
    lm.dtype = jnp.dtype(CONFIG["as_run"]["weights_dtype"])
    described = lm.describe(8576)
    assert described["cache_bytes"] == k_exaone_counts.cache_bytes(CONFIG, 8576)
    assert described["state_bytes"] == k_exaone_counts.state_bytes(CONFIG)


def test_device_the_cells_shares_of_the_peaks_are_counted_over_the_steps(tmp_path, monkeypatch):
    """A synthetic 5.9 ms step, 260 of them, and a 400 ms prefill."""
    traced = _device.tracing(tmp_path, monkeypatch)
    traced([("jit__clip_apply", 0, 400_000)] + [
        (k, s + _device.MS, e + _device.MS)
        for k, s, e in _device.lm_modules(3, 2400, 400_000, 260 * 5_900)])
    material = material_of(NODE)
    assert reader("prefill_device_ms.lm")(material) == pytest.approx(400.0)
    # the accepted reader divides by the tokens: what a token cost, not a step
    assert reader("decode_device_ms_per_token.lm")(material) == pytest.approx(260 * 5.9 / 384)
    step = k_exaone_counts.decode_step_bytes(CONFIG, 2300 / 260, 8192 + 192)
    assert reader("decode_hbm_roofline_pct.lm")(material) == pytest.approx(
        100.0 * step / 0.0059 / 819e9)
    assert 76.0 < reader("decode_hbm_roofline_pct.lm")(material) < 81.0
    assert reader("prefill_mxu_peak_pct.lm")(material) == pytest.approx(
        100.0 * k_exaone_counts.prefill_flops(CONFIG, 8192, 33000) / 0.400 / 197e12)
    # without drafting the same program is 384 one-position steps
    plain = dict(NODE, draft_tokens=0, decode_steps=384, mtp_drafted=0, mtp_accepted=0,
                 decode_experts_read=1500)
    work, cfg = device_modules.lm_work(material)
    assert work(cfg, plain)["decode"] == pytest.approx(384 * k_exaone_counts.decode_step_bytes(
        CONFIG, 1500 / 384, 8192 + 192, drafting=False))
    # the hand-written trace's operations say nothing of a scope: no share
    assert reader("mtp_device_pct.lm")(material) is None
    assert reader("mtp_device_pct.lm")(dict(material, trace=None)) is None


def test_the_mtp_share_is_self_time_under_the_scope_inside_the_decode():
    import scoped_self_time

    module = _load(os.path.join(HERE, "layer_metrics", "mtp_device_pct.lm.py"), "mtp_share")
    scope = scoped_self_time.under(module.SCOPE)
    inside = "jit(decode)/jit(main)/while/body/mtp/full/dot_general"
    other = "jit(decode)/jit(main)/while/body/layer_3/full/dot_general"
    operations = [
        (0, 100, "jit(prefill)/jit(main)/mtp/dot_general"),   # before the decode: not counted
        (1000, 3000, "jit(decode)/jit(main)/while"),          # the loop of 2,000 ...
        (1100, 1400, inside), (1400, 1500, "jit(decode)/jit(main)/while/body/mtp/head/dot"),
        (1500, 2600, other),                                  # ... 1,100 of its body the main's
        (2600, 2700, "jit(decode)/jit(main)/while/body/verify/mtplike/mul"),  # no such scope
    ]
    programs = [(1000, 3100)]
    # under the scope 300 + 100; all: the loop's own 400 + 300 + 100 + 1,100 + 100
    assert scoped_self_time.self_time_pct(operations, programs, scope) == pytest.approx(
        100.0 * 400 / 2000)
    assert scope.search("a/mtp") and scope.search("a/mtp/b")
    assert not scope.search("a/mtplike/b") and not scope.search("a/kda/b")
    # no operation names a scope: nothing to read
    bare = [(start, end, "") for start, end, _ in operations]
    assert scoped_self_time.self_time_pct(bare, programs, scope) is None
    # the same stack of self times as the accepted reader's, on its own scope
    kda = _load(os.path.join(HERE, "layer_metrics", "linear_attention_device_pct.lm.py"), "kda")
    mixed = [(start, end, text.replace("/mtp/", "/kda/")) for start, end, text in operations]
    assert scoped_self_time.self_time_pct(
        mixed, programs, scoped_self_time.under("kda")) == kda.share_pct(mixed, programs)
    assert module.PROGRAMS == ("jit_decode",)


def test_the_scoped_reading_finds_the_scopes_of_the_programs_it_is_asked_for(
        tmp_path, monkeypatch):
    """`scoped_self_time.py` on the hand-made XSpace of `test_solar_readers.py`:
    what the accepted reader reads of its two programs, and of one alone."""
    import scoped_self_time

    solar = _load(os.path.join(HERE, "tests", "test_solar_readers.py"), "k_exaone_uses_solar_trace")
    _device.tracing(tmp_path, monkeypatch)([("jit_prefill", 0, 1)])  # argv; the folder
    (path,) = tmp_path.rglob("*.xplane.pb")
    path.write_bytes(solar._scoped_trace())
    device_modules._LOADED.clear()
    scoped_self_time._LOADED.clear()
    both = ("jit_prefill", "jit_decode")
    kda = _load(os.path.join(HERE, "layer_metrics", "linear_attention_device_pct.lm.py"), "kda")
    assert scoped_self_time.scopes(str(path), both) == kda.scopes(str(path))
    assert scoped_self_time.scopes(str(path), ("jit_decode",)) == {
        "%fusion.5 = f32[8]{0} fusion(": "jit(decode)/while/body/layer_2/kda/gates/mul"}
    material = material_of(NODE)
    assert scoped_self_time.share_pct(material, both, "kda") == pytest.approx(100.0 * 1600 / 2100)
    assert scoped_self_time.share_pct(material, ("jit_decode",), "kda") == pytest.approx(100.0)
    assert reader("mtp_device_pct.lm")(material) == pytest.approx(0.0)  # scopes, none of them mtp
    assert scoped_self_time.share_pct(material, ("jit_other",), "kda") is None


# --- two checks of test_solar_readers.py, in the form that outlives a PR ------


def test_the_lm_cells_are_listed_where_their_readers_find_something():
    """`test_the_solar_cell_is_listed_where_its_readers_find_something`,
    which also held Solar's cell to be the last of each list: true until a
    PR appends a cell, as this one does."""
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        manifest = json.load(fh)
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    assert per_layer["state_mb.lm"]["workloads"] == [SOLAR_CELL, CELL]
    assert (per_layer["state_mb.lm"]["source"], per_layer["state_mb.lm"]["layer"],
            per_layer["state_mb.lm"]["moves"]) == (
        "program_counter", "sampling programs", "images_per_s")
    assert per_layer["linear_attention_device_pct.lm"]["workloads"] == [SOLAR_CELL]
    for name in ("mtp_accept_pct.lm", "mtp_device_pct.lm"):
        assert per_layer[name]["workloads"] == [CELL]
        assert (per_layer[name]["layer"], per_layer[name]["moves"]) == (
            "sampling programs", "images_per_s")
    assert per_layer["mtp_accept_pct.lm"]["source"] == "program_counter"
    assert per_layer["mtp_device_pct.lm"]["source"] == "device_trace"
    for name in ("experts_held_share_pct.lm", "cache_gb.lm", "decode_hbm_roofline_pct.lm",
                 "prefill_mxu_peak_pct.lm", "generate_ms.lm", "layer_passes_per_token.lm"):
        cells = per_layer[name]["workloads"]
        assert cells.index(SOLAR_CELL) + 1 == cells.index(CELL), name  # appended, in order
    for stem in ("solar-open2-250b", "k-exaone-236b-a23b"):
        (config,) = [c for c in manifest["configs"] if c["name"] == stem]
        assert config["file"] == f"benchmark/configs/{stem}.json"
        with open(os.path.join(ROOT, config["file"]), encoding="utf-8") as fh:
            source = json.load(fh)
        assert config["source"] == source["source"] and config["reduced"] == source["reduced"]


def test_device_every_lm_work_file_is_found_by_its_registry_name_however_many(
        tmp_path, monkeypatch):
    """`test_device_every_configuration_with_an_lm_work_file_is_found_by_
    its_registry_name`, which also held the files to be three: no table
    of models, so a fourth is found like the three. What it read of Ouro's
    cell through the accepted readers is read here as it was."""
    import ouro_counts

    traced = _device.tracing(tmp_path, monkeypatch)
    traced([("jit__clip_apply", 0, 400_000)] + [
        (k, s + _device.MS, e + _device.MS)
        for k, s, e in _device.lm_modules(3, 3300, 354_000, 64 * 38_400)])
    jobs = [_device.lm_job(3.3 * i, _device.OURO_NODE) for i in range(3)]
    ouro = _device.window(jobs, _device.lm_prompt("ouro-2.6b"))
    cfg = ouro_counts.config()
    assert reader("decode_hbm_roofline_pct.lm")(ouro) == pytest.approx(
        100.0 * ouro_counts.decode_step_bytes(cfg, 2048 + 32) / 0.0384 / 819e9)
    assert 73.0 < reader("decode_hbm_roofline_pct.lm")(ouro) < 75.0
    assert reader("prefill_mxu_peak_pct.lm")(ouro) == pytest.approx(
        100.0 * ouro_counts.prefill_flops(cfg, 2048) / 0.354 / 197e12)
    stems = {os.path.splitext(f)[0] for f in os.listdir(os.path.join(HERE, "lm_work"))
             if f.endswith(".py")}
    assert {"deepseek-v2", "ouro-2.6b", "solar-open2-250b", "k-exaone-236b-a23b"} <= stems
    for stem in stems:
        with open(os.path.join(HERE, "configs", stem + ".json"), encoding="utf-8") as fh:
            name = json.load(fh)["registry_name"]
        work, found = device_modules.lm_work({"prompt": _device.lm_prompt(name)})
        assert found["registry_name"] == name and callable(work)
    assert device_modules.lm_work({"prompt": _device.lm_prompt("some-other-lm")}) is None
    assert device_modules.lm_work({"prompt": _device.lm_prompt("sd15")}) is None
    unknown = _device.window(jobs, _device.lm_prompt("some-other-lm"))
    assert device_modules.lm_work(unknown) is None
    assert reader("decode_hbm_roofline_pct.lm")(unknown) is None
    assert reader("prefill_mxu_peak_pct.lm")(unknown) is None
    assert reader("decode_device_ms_per_token.lm")(unknown) == pytest.approx(38.4)
    drafting = material_of(NODE, name="some-other-lm")
    assert reader("decode_hbm_roofline_pct.lm")(drafting) is None
    assert reader("prefill_mxu_peak_pct.lm")(drafting) is None


@pytest.mark.parametrize("mine, theirs", [
    ("workflows/rewrite-txt2img-k-exaone.json", "workflows/rewrite-txt2img-k-exaone.json"),
    ("reference/k_exaone.py", "comfyui_distributed_tpu/reference/k_exaone.py"),
])
def test_the_k_exaone_copies_here_are_the_committed_files(mine, theirs):
    with open(os.path.join(HERE, mine), "rb") as a, open(os.path.join(ROOT, theirs), "rb") as b:
        assert a.read() == b.read()
