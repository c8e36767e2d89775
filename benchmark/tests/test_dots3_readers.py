"""The dots3-note-prev cell's counts and readers on synthetic material
(no chip, no JAX program): `dots3_counts` against the sums the issue
worked out and against the registry entry's own shapes; the cell's
`lm_work` file found by its registry name; the two readers this cell
brings (`window_latent_device_pct.lm`, a scope's self time;
`band_keys_seen_pct.lm`, two counters of the node); the accepted readers
on this cell's node; and the manifest: the cell listed where its readers
find something, each list held from its start and none to its end."""

import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import device_modules  # noqa: E402
import dots3_counts as counts  # noqa: E402

CONFIG = counts.config()
CELL = "dots3_note_longdoc_txt2img_512.closed2"
SDAR_CELL = "sdar_30b_a3b_rewrite_txt2img_512.closed2"
GRANITE_CELL = "granite_4_0_h_micro_longdoc_txt2img_512.closed2"
GLM_CELL = "glm_5_2_longdoc_txt2img_512.closed2"
NEMOTRON_CELL = "nemotron3_nano_rewrite_txt2img_512.closed2"
LING_CELL = "ling_flash_rewrite_txt2img_512.closed2"
K_EXAONE_CELL = "k_exaone_rewrite_txt2img_512.closed2"
SOLAR_CELL = "solar_open2_rewrite_txt2img_512.closed2"
DEEPSEEK_CELL = "deepseek_v2_rewrite_txt2img_512.closed2"
NEW_METRICS = ["window_latent_device_pct.lm", "band_keys_seen_pct.lm",
               "flash_attention_band_roofline_pct.lm"]


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# the hand-written trace, the spans and the window of test_device_readers.py
_device = _load(os.path.join(HERE, "tests", "test_device_readers.py"), "dots3_uses_device_readers")

# a request of the cell: 32,768 prompt tokens in four parts, 256 new tokens, an eighth of
# the routed pairs on held experts, one held expert a step and layer
PROMPT, NEW = 32768, 256
SEEN = 3 * (513 * 514 // 2 + (PROMPT - 513) * 513)             # the band, three layers
XLA = 3 * (256 * 256 + 256 * 512 + 30 * 256 * 768 + 3 * 8192 * 768)
NODE = dict(
    prompt_tokens=PROMPT, new_tokens=NEW, draft_tokens=0, decode_steps=NEW, layers=5,
    full_layers=2, window_layers=3, window=513, ring_positions=520, prefill_parts=4,
    index_topk=2048, experts_held=32, experts_total=256,
    cache_bytes=33024 * 2816, state_bytes=3 * 520 * 2176,
    prefill_layer_passes=PROMPT * 5, decode_layer_passes=NEW * 5, decode_experts_read=NEW * 4,
    prefill_routed_pairs=PROMPT * 32, prefill_routed_pairs_held=PROMPT * 4,
    decode_routed_pairs=NEW * 32, decode_routed_pairs_held=NEW * 4,
    keys_visible=2 * (33024 * 33025 // 2),
    keys_selected=2 * (2048 * 2049 // 2 + (33024 - 2048) * 2048),
    prefill_band_keys_seen=SEEN, prefill_band_keys_computed=XLA, prefill_band_route="xla")


def reader(name: str):
    """The metric's read(), loaded as run.py loads it."""
    return _load(os.path.join(HERE, "layer_metrics", name + ".py"), "layer_metric").read


def material_of(node, jobs=3, name=None):
    return _device.window(
        [_device.lm_job(3.0 * i, node) for i in range(jobs)],
        _device.lm_prompt(name or CONFIG["registry_name"]))


def test_dots3_counts_are_the_ones_the_issue_worked_out():
    assert counts.attention_params(CONFIG, True) + counts.indexer_params(CONFIG) == 144_049_920
    assert counts.indexer_params(CONFIG) == 9_371_904
    assert counts.attention_params(CONFIG, False) == 90_834_944
    assert counts.expert_params(CONFIG) == 23_592_960
    assert counts.always_params(CONFIG) + 256 == 1_310_976 + 23_592_960  # router, bias, shared
    assert counts.layer_params(CONFIG, True, True, 0) == 356_396_800
    assert counts.layer_params(CONFIG, False, True, 32) == 923_938_816
    assert counts.layer_params(CONFIG, False, False, 32) == 870_723_840
    assert counts.layer_params(CONFIG, False, True, 256) == 6_208_761_856
    assert counts.layer_params(CONFIG, False, False, 256) == 6_155_546_880
    assert counts.total_params(CONFIG) == CONFIG["as_run"]["parameters"]["lm"] == 4_087_154_176
    assert counts.published_params(CONFIG) == CONFIG["published"]["parameters"] == 279_551_726_592
    assert (counts.full_layers(CONFIG), counts.window_layers(CONFIG),
            counts.dense_layers(CONFIG)) == (2, 3, 1)
    assert counts.ring_positions(CONFIG) == 520
    assert counts.cache_bytes(CONFIG, 33024) == 33024 * 2816 == 92_995_584
    assert counts.cache_bytes(CONFIG, 1) == CONFIG["as_run"]["cache_bytes_per_token"]
    assert counts.state_bytes(CONFIG) == CONFIG["as_run"]["state_bytes"] == 3_394_560
    assert counts.keys_within(0, 600, 513) == 513 * 514 // 2 + 87 * 513
    assert counts.keys_within(0, 100, 513) == counts.keys_visible(0, 100) == 5050


def test_a_decode_step_moves_2_2_gb_and_a_prefill_is_94_tflop():
    """A step that reads one held expert a sparse layer: layer 0's 713
    MB, layer 1's 385, three sliding layers of 279, the head's 195, and
    the state as the masked form reads it (93 MB of caches whole, 3.4 MB
    of rings): the issue's 2.22 GB, 2.7 ms at the HBM's peak."""
    step = counts.decode_step_bytes(CONFIG, 4.0, PROMPT + NEW // 2)
    weights = 2 * (356_396_800 + (923_938_816 - 31 * 23_592_960)
                   + 3 * (870_723_840 - 31 * 23_592_960) + 5120 + 19008 * 5120 + 5120)
    state = (PROMPT + NEW // 2 + 1) * 2816 + 3 * 520 * 2176 + 3 * 2176
    assert step == weights + state
    assert 2.21e9 < step < 2.23e9 and 2.69 < 1e3 * step / 819e9 < 2.73
    flops = counts.prefill_flops(CONFIG, PROMPT, PROMPT * 4)
    assert 9.3e13 < flops < 9.5e13
    # its parts: the index 64 x 128 x 2 over T^2 / 2 keys a full layer; the chosen rows at
    # 128 heads x (192 + 128) x 2 a pair; a band at 64 heads x (256 + 128) x 2 a pair
    assert counts.index_flops(CONFIG, PROMPT) == 2.0 * 64 * 128 * (PROMPT * (PROMPT + 1) // 2)
    assert counts.attention_flops(CONFIG, True, 10) == 2.0 * 128 * 320 * 10
    assert counts.band_flops(CONFIG, PROMPT) == 2.0 * 64 * 384 * (SEEN // 3)
    assert counts.band_bytes(CONFIG, 8192, 8704) == 2.0 * 64 * (8192 * 384 + 8704 * 384)


def test_the_sizes_the_dots3_counts_read_are_the_registrys():
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models import dots3
    from comfyui_distributed_tpu.models.registry import create_model, get_config

    model = get_config(CONFIG["registry_name"])
    assert dots3.param_count(model) == counts.total_params(CONFIG)
    assert dots3.param_count(dots3.Dots3Config()) == counts.published_params(CONFIG)
    assert list(model.layer_types) == CONFIG["layer_types"] and len(model.layer_types) == 46
    for key, value in CONFIG.items():
        if hasattr(model, key) and key not in ("n_routed_experts", "vocab_size", "layer_types"):
            assert getattr(model, key) == value, key
    assert (len(model.held_experts), model.vocab_held) == (
        CONFIG["n_routed_experts"], CONFIG["vocab_size"])
    assert (model.n_routed_experts, model.vocab_size) == (256, 152064)
    shapes = dots3.param_shapes(model)["layers"]
    assert dots3.count_params(shapes[1]["attn"]) == counts.attention_params(CONFIG, True)
    assert dots3.count_params(shapes[1]["indexer"]) == counts.indexer_params(CONFIG)
    assert dots3.count_params(shapes[2]["attn"]) == counts.attention_params(CONFIG, False)
    lm = create_model(CONFIG["registry_name"])
    lm.dtype = jnp.dtype(CONFIG["as_run"]["weights_dtype"])
    described = lm.describe(33024)
    assert described["cache_bytes"] == counts.cache_bytes(CONFIG, 33024) == NODE["cache_bytes"]
    assert described["state_bytes"] == counts.state_bytes(CONFIG) == NODE["state_bytes"]
    for key in ("layers", "full_layers", "window_layers", "window", "ring_positions",
                "index_topk", "experts_held", "experts_total"):
        assert described[key] == NODE[key], key
    seen, computed, route = dots3.band_keys(model, PROMPT, lm.dtype)
    assert (3 * seen, 3 * computed, route) == (SEEN, XLA, "xla")   # off a TPU: XLA's blocks


def test_the_counter_readers_read_the_dots3_cells_node():
    material = material_of(NODE)
    assert reader("experts_held_share_pct.lm")(material) == pytest.approx(12.5)
    assert reader("keys_selected_pct.lm")(material) == pytest.approx(
        100.0 * NODE["keys_selected"] / NODE["keys_visible"])
    assert 11.5 < reader("keys_selected_pct.lm")(material) < 12.5
    assert reader("cache_gb.lm")(material) == pytest.approx(0.092995584)
    assert reader("state_mb.lm")(material) == pytest.approx(3.39456)
    assert reader("layer_passes_per_token.lm")(material) == pytest.approx(5.0)
    band = reader("band_keys_seen_pct.lm")
    assert band(material) == pytest.approx(100.0 * SEEN / XLA)
    assert 66.0 < band(material) < 68.0                    # 513 of XLA's 255 + 513 keys a row
    on_kernel = dict(NODE, prefill_band_keys_computed=3 * (31 + 3 * 32) * 512 * 512)
    assert 50.0 < band(material_of(on_kernel)) < 51.0      # two blocks of 512 keys a block of rows
    # a node that says nothing of a band (another model's, the parent's): no reading
    silent = {k: v for k, v in NODE.items() if not k.startswith("prefill_band")}
    assert band(material_of(silent)) is None
    assert band({"spans": {}, "records": [], "trace": None}) is None
    assert reader("mtp_accept_pct.lm")(material) is None   # no draft module


def test_the_window_share_is_self_time_under_its_own_scope_and_mla_does_not_count_it():
    import scoped_self_time

    module = _load(os.path.join(HERE, "layer_metrics", "window_latent_device_pct.lm.py"), "window")
    mla = _load(os.path.join(HERE, "layer_metrics", "mla_device_pct.lm.py"), "mla")
    indexer = _load(os.path.join(HERE, "layer_metrics", "indexer_device_pct.lm.py"), "indexer")
    assert module.PROGRAMS == mla.PROGRAMS == ("jit_prefill", "jit_decode")
    assert (module.SCOPE, mla.SCOPE, indexer.SCOPE) == ("window_latent", "mla", "indexer")
    part = "jit(prefill)/jit(main)/while/body/"
    step = "jit(decode)/jit(main)/while/body/"
    operations = [
        (0, 1000, "jit(prefill)/jit(main)/while"),                          # the loop over parts
        (0, 200, part + "layer_1/mla/dsa_attend"),
        (200, 300, part + "layer_1/indexer/select/dsa_select"),
        (300, 350, part + "layer_1/mla/gate/logistic"),
        (350, 500, part + "layer_2/window_latent/branch_1_fun/dot_general"),
        (500, 540, part + "layer_2/window_latent/gate/logistic"),
        (540, 800, part + "layer_2/moe/experts/ragged_dot"),
        (800, 1000, part + "layer_0/mlp/dot_general"),
        (2000, 3000, "jit(decode)/jit(main)/while"),
        (2000, 2100, step + "layer_1/mla/dot_general"),
        (2100, 2400, step + "layer_3/window_latent/dot_general"),
        (2400, 2500, step + "layer_3/window_latentlike/mul"),
        (2500, 3000, step + "head/dot_general"),
    ]
    both = [(0, 1000), (2000, 3000)]
    share = {m.SCOPE: scoped_self_time.self_time_pct(
        operations, both, scoped_self_time.under(m.SCOPE)) for m in (module, mla, indexer)}
    assert share["window_latent"] == pytest.approx(100.0 * (150 + 40 + 300) / 2000)
    assert share["mla"] == pytest.approx(100.0 * (200 + 50 + 100) / 2000)
    assert share["indexer"] == pytest.approx(100.0 * 100 / 2000)
    scope = scoped_self_time.under(module.SCOPE)
    assert scope.search("a/window_latent") and scope.search("a/window_latent/gate/b")
    assert not scope.search("a/window_latentlike/b") and not scope.search("a/mla/b")
    assert not scoped_self_time.under("mla").search("a/window_latent/b")
    # the hand-written trace's operations say nothing of a scope; no trace: no share
    assert module.read({"spans": {}, "records": [], "trace": None}) is None


def test_device_the_dots3_cells_shares_of_the_peaks(tmp_path, monkeypatch):
    """A synthetic 0.85 s decode (256 steps) and a 1.2 s prefill."""
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", "a.cell", "--seed", "1",
                                      "--out", str(tmp_path)])
    ms = _device.MS
    modules = [("jit__clip_apply", 0, 400_000)] + [
        (k, s + ms, e + ms) for k, s, e in _device.lm_modules(3, 3000, 1_200_000, 850_000)]
    folder = tmp_path / "profile" / "trace-0001-benchmark" / "plugins" / "profile" / "x"
    folder.mkdir(parents=True)
    (folder / "vm.xplane.pb").write_bytes(_device.xspace({
        "/host:CPU": {"python": [("device.watch", 0, 5 * ms)]},
        "/device:TPU:0": {
            "XLA Ops": [("%fusion.1 = bf16[8]{0} fusion(", ms, 2 * ms)],
            "XLA Modules": [(f"{k}({7 + i})", s, e) for i, (k, s, e) in enumerate(modules)]},
    }))
    device_modules._LOADED.clear()
    material = material_of(NODE)
    assert reader("prefill_device_ms.lm")(material) == pytest.approx(1200.0)
    assert reader("decode_device_ms_per_token.lm")(material) == pytest.approx(850 / 256)
    step = counts.decode_step_bytes(CONFIG, 4.0, PROMPT + NEW // 2)
    assert reader("decode_hbm_roofline_pct.lm")(material) == pytest.approx(
        100.0 * 256 * step / 0.85 / 819e9)
    assert 80.0 < reader("decode_hbm_roofline_pct.lm")(material) < 85.0
    assert reader("prefill_mxu_peak_pct.lm")(material) == pytest.approx(
        100.0 * counts.prefill_flops(CONFIG, PROMPT, PROMPT * 4) / 1.2 / 197e12)
    assert 35.0 < reader("prefill_mxu_peak_pct.lm")(material) < 45.0
    # no scope in the hand-written trace, no kernel of GLM-5.2's or of the band's in it
    for name in ("window_latent_device_pct.lm", "mla_device_pct.lm", "indexer_device_pct.lm",
                 "dsa_attend_device_pct.lm", "dsa_select_device_pct.lm",
                 "flash_attention_band_roofline_pct.lm"):
        assert reader(name)(material) is None, name


def test_device_the_band_kernels_share_of_its_roofline(tmp_path, monkeypatch):
    """Three prefills of 1.2 s, each with 24 ms of `flash_attention_causal`
    events (six band calls of 4 ms); one such event outside any prefill
    does not count; the slice's first and last programs may be cut, so
    one prefill lies whole in it."""
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", "a.cell", "--seed", "1",
                                      "--out", str(tmp_path)])
    ms = _device.MS
    modules = [("jit__clip_apply", 0, 400_000)] + [
        (k, s + ms, e + ms) for k, s, e in _device.lm_modules(3, 3000, 1_200_000, 850_000)]
    ops = [("%flash_attention_causal.9 = bf16[8192,64,128]{2,1,0} custom-call(", 100, 200)]
    for kind, start, end in modules:
        if kind == "jit_prefill":
            ops += [(f"%flash_attention_causal.{i} = bf16[8192,64,128]{{2,1,0}} custom-call(",
                     start + (1 + 2 * i) * 4 * ms, start + (2 + 2 * i) * 4 * ms)
                    for i in range(6)]
    folder = tmp_path / "profile" / "trace-0001-benchmark" / "plugins" / "profile" / "x"
    folder.mkdir(parents=True)
    (folder / "vm.xplane.pb").write_bytes(_device.xspace({
        "/host:CPU": {"python": [("device.watch", 0, 5 * ms)]},
        "/device:TPU:0": {
            "XLA Ops": ops,
            "XLA Modules": [(f"{k}({7 + i})", s, e) for i, (k, s, e) in enumerate(modules)]},
    }))
    device_modules._LOADED.clear()
    band = _load(os.path.join(HERE, "layer_metrics", "flash_attention_band_roofline_pct.lm.py"),
                 "band_roofline")
    # what the mask lets through, three layers: 64 heads x (256 + 128) x 2 a pair
    least = 3 * counts.band_flops(CONFIG, PROMPT) / 197e12
    moved = 3 * (counts.band_bytes(CONFIG, 8192, 8192) + 3 * counts.band_bytes(CONFIG, 8192, 8704))
    assert least > moved / 819e9  # right of the ridge: the MXU bounds it
    assert band.least_seconds(CONFIG, PROMPT) == pytest.approx(least)
    material = material_of(NODE)
    assert band.read(material) == pytest.approx(100.0 * least / 0.024)
    assert 10.0 < band.read(material) < 100.0
    # another model's workflow: no reading; granite's reader is held to its own configuration
    assert band.read(material_of(NODE, name="granite-4.0-h-micro")) is None
    assert reader("flash_attention_causal_roofline_pct.lm")(material) is None


def test_the_dots3_cells_lm_work_file_is_found_by_its_registry_name():
    material = material_of(NODE)
    work, cfg = device_modules.lm_work(material)
    assert cfg["registry_name"] == CONFIG["registry_name"] == "dots3-note-prev-ep8-5l"
    found = work(cfg, NODE)
    assert found["decode"] == pytest.approx(
        256 * counts.decode_step_bytes(CONFIG, 4.0, PROMPT + NEW // 2))
    assert found["prefill"] == pytest.approx(counts.prefill_flops(CONFIG, PROMPT, PROMPT * 4))
    assert device_modules.lm_work(material_of(NODE, name="no-such-model")) is None


def test_the_dots3_cell_is_listed_where_its_readers_find_something_no_list_held_to_its_end():
    """Every list that held GLM-5.2's cell but the two drafting metrics',
    and `state_mb.lm` (the rings) and `experts_device_pct.lm`; each list
    is held from its start, appended in the cells' order, and none to
    its end; this PR's metrics follow `expert_union_hbm_pct.lm`."""
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        manifest = json.load(fh)
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    order = [w["name"] for w in manifest["workloads"]]
    names = list(per_layer)

    def listed(name, *cells):
        found = per_layer[name]["workloads"]
        assert found[:len(cells)] == list(cells), name
        assert found == sorted(found, key=order.index), name  # appended, in the cells' order
        return found

    assert CELL in listed(
        "state_mb.lm", SOLAR_CELL, K_EXAONE_CELL, LING_CELL, NEMOTRON_CELL, GRANITE_CELL)
    for name in ("mtp_accept_pct.lm", "mtp_device_pct.lm"):
        assert CELL not in listed(name, K_EXAONE_CELL, LING_CELL, GLM_CELL)   # no draft module
    assert CELL in listed("mla_device_pct.lm", DEEPSEEK_CELL, LING_CELL, GLM_CELL)
    assert CELL in listed(
        "experts_held_share_pct.lm", DEEPSEEK_CELL, SOLAR_CELL, K_EXAONE_CELL, LING_CELL,
        NEMOTRON_CELL, GLM_CELL, SDAR_CELL)
    for name in ("indexer_device_pct.lm", "keys_selected_pct.lm", "dsa_attend_device_pct.lm",
                 "dsa_select_device_pct.lm"):
        assert CELL in listed(name, GLM_CELL)
    assert CELL in listed("experts_device_pct.lm", SDAR_CELL)
    for name in ("linear_attention_device_pct.lm", "state_keep_device_pct.lm", "ssm_device_pct.lm",
                 "ssd_device_pct.lm", "attn_device_pct.lm", "mlp_device_pct.lm",
                 "expert_matvec_hbm_pct.lm", "expert_union_hbm_pct.lm",
                 "flash_attention_causal_roofline_pct.lm", "denoise_passes_per_token.lm"):
        assert CELL not in listed(name), name
    # this PR's metrics, after the PR before's, each read in this cell
    start = names.index("expert_union_hbm_pct.lm") + 1
    assert names[start:start + len(NEW_METRICS)] == NEW_METRICS
    window, band, kernel = (per_layer[name] for name in NEW_METRICS)
    assert (window["source"], window["layer"], window["unit"], window["better"]) == (
        "device_trace", "sampling programs", "%", "lower")
    assert (band["source"], band["layer"], band["unit"], band["better"]) == (
        "program_counter", "sampling programs", "%", "higher")
    assert (kernel["source"], kernel["layer"], kernel["unit"], kernel["better"]) == (
        "device_trace", "kernels", "%", "higher")
    for name in NEW_METRICS:
        assert listed(name, CELL) and per_layer[name]["moves"] == "images_per_s"
        assert os.path.exists(os.path.join(HERE, "layer_metrics", name + ".py"))
    for name in ("cache_gb.lm", "decode_hbm_roofline_pct.lm", "prefill_mxu_peak_pct.lm",
                 "generate_ms.lm", "layer_passes_per_token.lm", "prefill_device_ms.lm",
                 "decode_device_ms_per_token.lm", "decode_ms_per_token.lm", "lm_share_pct.rewrite"):
        cells = listed(name)
        assert (cells.index(GLM_CELL) < cells.index(GRANITE_CELL) < cells.index(SDAR_CELL)
                < cells.index(CELL)), name
    # every metric that moves images_per_s says where it is read
    for metric in manifest["per_layer"]:
        if metric["moves"] == "images_per_s":
            assert metric.get("workloads"), metric["name"]
    assert CELL in next(m for m in manifest["end_to_end"] if m["name"] == "images_per_s")[
        "workloads"]
    assert order.index(SDAR_CELL) + 1 == order.index(CELL)
    (entry,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == ("dots3-note-prev", "closed2", 1)
    (config,) = [c for c in manifest["configs"] if c["name"] == "dots3-note-prev"]
    assert config["file"] == "benchmark/configs/dots3-note-prev.json"
    assert config["source"] == CONFIG["source"] and config["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]


# --- two checks of the DSA kernels' files, in the form that outlives a PR -----------------


@pytest.mark.parametrize("metric,after", [
    ("dsa_attend_device_pct.lm", "keys_selected_pct.lm"),
    ("dsa_select_device_pct.lm", "dsa_attend_device_pct.lm")])
def test_a_dsa_kernels_metric_keeps_its_place_and_lists_the_glm_cell_first(metric, after):
    """What `test_the_metric_is_the_manifests_last_and_lists_the_glm_cell`
    and `test_the_selection_metric_follows_the_attention_kernels_and_
    lists_the_glm_cell_alone` assert, with the list held from its start
    and not to its end: a second model with such layers is appended."""
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        manifest = json.load(fh)
    names = [m["name"] for m in manifest["per_layer"]]
    assert names.index(after) < names.index(metric)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == metric]
    cells = entry.pop("workloads")
    assert entry == {
        "name": metric, "unit": "%", "better": "lower", "source": "device_trace",
        "layer": "kernels", "moves": "images_per_s"}
    assert cells[0] == GLM_CELL
    assert "kernels" in {m["layer"] for m in manifest["per_layer"] if m["name"] != metric}
    assert os.path.exists(os.path.join(HERE, "layer_metrics", metric + ".py"))


@pytest.mark.parametrize("mine,theirs", [
    ("reference/dots3.py", "comfyui_distributed_tpu/reference/dots3.py"),
    ("workflows/longdoc-txt2img-dots3-note.json", "workflows/longdoc-txt2img-dots3-note.json"),
])
def test_the_dots3_copies_here_are_the_committed_files(mine, theirs):
    with open(os.path.join(HERE, mine), "rb") as a, open(os.path.join(ROOT, theirs), "rb") as b:
        assert a.read() == b.read()


def test_the_dots3_cells_workflow_is_the_request_the_issue_gives():
    with open(os.path.join(HERE, "workflows", "longdoc-txt2img-dots3-note.json"),
              encoding="utf-8") as fh:
        prompt = json.load(fh)
    with open(os.path.join(HERE, "workflows", "longdoc-txt2img-glm-5.2.json"),
              encoding="utf-8") as fh:
        glm = json.load(fh)
    (node,) = [n for n in prompt.values() if n["class_type"] == "TextGenerate"]
    (theirs,) = [n for n in glm.values() if n["class_type"] == "TextGenerate"]
    assert node["inputs"]["text"] == theirs["inputs"]["text"]        # GLM-5.2's cell's own text
    assert len(node["inputs"]["text"].encode("utf-8")) == 32767      # with the begin id: 32,768
    assert (node["inputs"]["max_new_tokens"], node["inputs"]["temperature"],
            node["inputs"]["draft_tokens"]) == (256, 1.0, 0)
    (loader,) = [n for n in prompt.values() if n["class_type"] == "CheckpointLoaderSimple"]
    assert loader["inputs"]["ckpt_name"] == CONFIG["registry_name"]
    (sampler,) = [n for n in prompt.values() if n["class_type"] == "KSampler"]
    assert (sampler["inputs"]["steps"], sampler["inputs"]["cfg"], sampler["inputs"][
        "sampler_name"], sampler["inputs"]["scheduler"]) == (20, 7.0, "euler", "karras")
    with open(os.path.join(HERE, "workloads", CELL + ".json"), encoding="utf-8") as fh:
        work = json.load(fh)
    assert work["workflow"] == "benchmark/workflows/longdoc-txt2img-dots3-note.json"
    assert work["rehearsal"]["set"][0]["value"] == "tiny-dots3"
    assert work["rate"] == {"metric": "images_per_s", "units_per_job": 1}
