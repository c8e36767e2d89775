"""The LongCat-Flash-Chat cell's counts and readers on synthetic material
(no chip, no JAX program): `longcat_flash_counts` against the sums the
issue worked out and against the registry entry's own shapes; the cell's
`lm_work` file found by its registry name; the three readers this cell
brings (`zero_expert_pairs_pct.lm`, two counters of the node;
`shortcut_device_pct.lm`, a scope's self time;
`flash_attention_latent_roofline_pct.lm`, the causal kernel against the
model's own count); the accepted readers on this cell's node; and the
manifest: the cell listed where its readers find something, each list
held from its start and none to its end."""

import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import device_modules  # noqa: E402
import longcat_flash_counts as counts  # noqa: E402

CONFIG = counts.config()
CELL = "longcat_flash_longdoc_txt2img_512.closed2"
DOTS3_CELL = "dots3_note_longdoc_txt2img_512.closed2"
SDAR_CELL = "sdar_30b_a3b_rewrite_txt2img_512.closed2"
GRANITE_CELL = "granite_4_0_h_micro_longdoc_txt2img_512.closed2"
GLM_CELL = "glm_5_2_longdoc_txt2img_512.closed2"
LING_CELL = "ling_flash_rewrite_txt2img_512.closed2"
DEEPSEEK_CELL = "deepseek_v2_rewrite_txt2img_512.closed2"
NEW_METRICS = ["zero_expert_pairs_pct.lm", "shortcut_device_pct.lm",
               "flash_attention_latent_roofline_pct.lm"]


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# the hand-written trace, the spans and the window of test_device_readers.py
_device = _load(os.path.join(HERE, "tests", "test_device_readers.py"), "longcat_device_readers")

# a request of the cell: 32,768 prompt tokens in four parts, 128 new tokens, 12 ids a token and
# layer of which a third are identities and 8 of 768 fall on held experts; 16 held experts
# read in the 128 steps (0.125 a step and layer)
PROMPT, NEW, LAYERS = 32768, 128, 4
NODE = dict(
    prompt_tokens=PROMPT, new_tokens=NEW, draft_tokens=0, decode_steps=NEW, layers=LAYERS,
    attention_sublayers=8, prefill_part=8192, expert_block=1024, prefill_parts=4,
    experts_held=8, experts_total=512, zero_experts=256,
    cache_bytes=32896 * 9216, state_bytes=0, decode_experts_read=16,
    prefill_routed_pairs=PROMPT * 12 * LAYERS, prefill_routed_pairs_held=PROMPT * LAYERS // 8,
    prefill_zero_pairs=PROMPT * 4 * LAYERS,
    decode_routed_pairs=NEW * 12 * LAYERS, decode_routed_pairs_held=NEW * LAYERS // 8,
    decode_zero_pairs=NEW * 4 * LAYERS, real_experts_per_token_mean=8.0,
    real_experts_per_token_min=2, real_experts_per_token_max=12)


def reader(name: str):
    """The metric's read(), loaded as run.py loads it."""
    return _load(os.path.join(HERE, "layer_metrics", name + ".py"), "layer_metric").read


def material_of(node, jobs=3, name=None):
    return _device.window(
        [_device.lm_job(3.0 * i, node) for i in range(jobs)],
        _device.lm_prompt(name or CONFIG["registry_name"]))


def test_longcat_flash_counts_are_the_ones_the_issue_worked_out():
    assert counts.attention_params(CONFIG) == 90_572_800
    assert counts.dense_params(CONFIG) == 226_492_416
    assert counts.expert_params(CONFIG) == 37_748_736
    assert counts.router_params(CONFIG) == 6144 * 768 and counts.router_width(CONFIG) == 768
    assert counts.layer_params(CONFIG, 0) == 638_874_368
    assert counts.layer_params(CONFIG, 8) == 940_864_256
    assert counts.total_params(CONFIG) == CONFIG["as_run"]["parameters"]["lm"] == 3_964_789_760
    assert counts.published_params(CONFIG) == CONFIG["published"]["parameters"] == 560_664_980_480
    # the published "avg 27B": 8 real experts of the 12 drawn over 512 + 256
    assert counts.active_params(CONFIG, 8) == 28 * 940_864_256 + 805_312_512
    assert 27.0e9 < counts.active_params(CONFIG, 8) < 27.2e9
    assert counts.cache_bytes(CONFIG, 32896) == 32896 * 9216 == 303_169_536
    assert counts.cache_bytes(CONFIG, 1) == CONFIG["as_run"]["cache_bytes_per_token"]
    assert counts.keys_visible(0, 100) == 5050 and counts.keys_visible(90, 100) == 955


def test_a_decode_step_moves_5_65_gb_and_a_prefill_is_345_tflop():
    """A step that reads an eighth of a held expert a layer: four layers
    of 1.28 GB outside their experts, the head's 201 MB, eight caches of
    37.8 MB: the issue's 5.65 GB, 6.9 ms at the HBM's peak; the prefill's
    attention (176 TFLOP) as large as everything a token passes (168)."""
    step = counts.decode_step_bytes(CONFIG, 0.5, PROMPT + NEW // 2)
    weights = 2 * (4 * 638_874_368 + 0.5 * 37_748_736 + 6144 + 16384 * 6144 + 6144)
    state = (PROMPT + NEW // 2 + 1) * 9216 + 8 * 1152
    assert step == weights + state
    assert 5.64e9 < step < 5.66e9 and 6.8 < 1e3 * step / 819e9 < 7.0
    flops = counts.prefill_flops(CONFIG, PROMPT, NODE["prefill_routed_pairs_held"])
    assert 3.44e14 < flops < 3.46e14
    attention = 8 * counts.attention_flops(CONFIG, counts.keys_visible(0, PROMPT))
    assert attention == 8 * 2.0 * 64 * 320 * (PROMPT * (PROMPT + 1) // 2)
    assert 1.75e14 < attention < 1.77e14 and 1.67e14 < flops - attention < 1.69e14
    # the parts' calls: the last rows of the triangle, and together the whole of it
    calls = counts.prefill_causal_calls(CONFIG, PROMPT)
    assert calls == [(8192, 8192), (8192, 16384), (8192, 24576), (8192, 32768)]
    assert counts.prefill_causal_calls(CONFIG, 10000) == [(8192, 8192), (1808, 10000)]
    assert 8 * sum(counts.causal_call_flops(CONFIG, r, k) for r, k in calls) == attention
    assert counts.causal_call_bytes(CONFIG, 8192, 32768) == 2.0 * 64 * 320 * (8192 + 32768)


def test_the_sizes_the_longcat_flash_counts_read_are_the_registrys():
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models import longcat_flash
    from comfyui_distributed_tpu.models.registry import create_model, get_config

    model = get_config(CONFIG["registry_name"])
    assert longcat_flash.param_count(model) == counts.total_params(CONFIG)
    assert longcat_flash.param_count(longcat_flash.LongcatFlashConfig()) == (
        counts.published_params(CONFIG))
    for key, value in CONFIG.items():
        if hasattr(model, key) and key not in ("n_routed_experts", "vocab_size"):
            assert getattr(model, key) == value, key
    assert (len(model.held_experts), model.vocab_held, model.router_width) == (
        CONFIG["n_routed_experts"], CONFIG["vocab_size"], counts.router_width(CONFIG))
    assert (model.n_routed_experts, model.vocab_size) == (512, 131072)
    assert (model.prefill_part, model.expert_block, model.attention_heads_a_call) == tuple(
        CONFIG["as_run"][key] for key in ("prefill_part", "expert_block", "attention_heads_a_call"))
    sub = longcat_flash.param_shapes(model)["layers"][0]["sub"][0]
    assert longcat_flash.count_params(sub["attn"]) == counts.attention_params(CONFIG)
    assert longcat_flash.count_params(sub["mlp"]) == counts.dense_params(CONFIG)
    lm = create_model(CONFIG["registry_name"])
    lm.dtype = jnp.dtype(CONFIG["as_run"]["weights_dtype"])
    described = lm.describe(32896)
    assert described["cache_bytes"] == counts.cache_bytes(CONFIG, 32896) == NODE["cache_bytes"]
    for key in ("layers", "attention_sublayers", "prefill_part", "expert_block", "experts_held",
                "experts_total", "zero_experts", "state_bytes"):
        assert described[key] == NODE[key], key


def test_the_counter_readers_read_the_longcat_flash_cells_node():
    material = material_of(NODE)
    zero = reader("zero_expert_pairs_pct.lm")
    assert zero(material) == pytest.approx(100.0 / 3)
    # by hand from one request's counters, the two phases together
    assert zero(material_of(dict(NODE, prefill_zero_pairs=500_000, decode_zero_pairs=2_048))) == (
        pytest.approx(100.0 * 502_048 / ((PROMPT + NEW) * 48)))
    assert reader("experts_held_share_pct.lm")(material) == pytest.approx(100.0 * 8 / 768)
    assert reader("cache_gb.lm")(material) == pytest.approx(0.303169536)
    assert reader("layer_passes_per_token.lm")(material) == pytest.approx(4.0)
    # a node that says nothing of identities (another model's, the parent's): no reading
    silent = {k: v for k, v in NODE.items() if not k.endswith("_zero_pairs")}
    assert zero(material_of(silent)) is None
    assert zero({"spans": {}, "records": [], "trace": None}) is None
    assert reader("mtp_accept_pct.lm")(material) is None   # no draft module
    assert reader("keys_selected_pct.lm")(material) is None  # no index: every key is seen


def test_the_shortcut_share_is_self_time_under_its_scope_with_the_experts_inside_it():
    import scoped_self_time

    module = _load(os.path.join(HERE, "layer_metrics", "shortcut_device_pct.lm.py"), "shortcut")
    mla = _load(os.path.join(HERE, "layer_metrics", "mla_device_pct.lm.py"), "mla")
    mlp = _load(os.path.join(HERE, "layer_metrics", "mlp_device_pct.lm.py"), "mlp")
    experts = _load(os.path.join(HERE, "layer_metrics", "experts_device_pct.lm.py"), "experts")
    assert module.PROGRAMS == mla.PROGRAMS == ("jit_prefill", "jit_decode")
    assert (module.SCOPE, mla.SCOPE, mlp.SCOPE) == ("shortcut", "mla", "mlp")
    part = "jit(prefill)/jit(main)/while/body/"
    step = "jit(decode)/jit(main)/while/body/"
    operations = [
        (0, 1000, "jit(prefill)/jit(main)/while"),                          # the loop over parts
        (0, 300, part + "layer_1/mla/cond/branch_3_fun/while/body/flash_attention_causal"),
        (300, 500, part + "layer_1/mlp/dot_general"),
        (500, 560, part + "layer_1/shortcut/while/body/router/dot_general"),
        (560, 700, part + "layer_1/shortcut/while/body/experts/cond/ragged_dot"),
        (700, 720, part + "layer_1/shortcut/while/body/zero_experts/mul"),
        (720, 740, part + "layer_1/shortcut/add"),
        (740, 1000, part + "layer_1/mlp/dot_general"),
        (2000, 3000, "jit(decode)/jit(main)/while"),
        (2000, 2300, step + "layer_2/mla/dot_general"),
        (2300, 2340, step + "layer_2/shortcut/experts/expert_matvec"),
        (2340, 2400, step + "layer_2/shortcutlike/mul"),
        (2400, 3000, step + "layer_2/mlp/dot_general"),
    ]
    both = [(0, 1000), (2000, 3000)]
    share = {m.SCOPE: scoped_self_time.self_time_pct(
        operations, both, scoped_self_time.under(m.SCOPE)) for m in (module, mla, mlp)}
    assert share["shortcut"] == pytest.approx(100.0 * (60 + 140 + 20 + 20 + 40) / 2000)
    assert share["mla"] == pytest.approx(100.0 * 600 / 2000)
    assert share["mlp"] == pytest.approx(100.0 * (200 + 260 + 600) / 2000)
    scope = scoped_self_time.under(module.SCOPE)
    assert scope.search("a/shortcut") and scope.search("a/shortcut/experts/b")
    assert not scope.search("a/shortcutlike/b") and not scope.search("a/mlp/b")
    assert experts.SCOPE == "experts"  # inside the branch: counted by both
    # the hand-written trace's operations say nothing of a scope; no trace: no share
    assert module.read({"spans": {}, "records": [], "trace": None}) is None


def _trace(tmp_path, monkeypatch, ops_of):
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", "a.cell", "--seed", "1",
                                      "--out", str(tmp_path)])
    ms = _device.MS
    modules = [("jit__clip_apply", 0, 400_000)] + [
        (k, s + ms, e + ms) for k, s, e in _device.lm_modules(3, 6000, 3_600_000, 1_000_000)]
    folder = tmp_path / "profile" / "trace-0001-benchmark" / "plugins" / "profile" / "x"
    folder.mkdir(parents=True)
    (folder / "vm.xplane.pb").write_bytes(_device.xspace({
        "/host:CPU": {"python": [("device.watch", 0, 5 * ms)]},
        "/device:TPU:0": {
            "XLA Ops": ops_of(modules, ms),
            "XLA Modules": [(f"{k}({7 + i})", s, e) for i, (k, s, e) in enumerate(modules)]},
    }))
    device_modules._LOADED.clear()


def test_device_the_longcat_flash_cells_shares_of_the_peaks(tmp_path, monkeypatch):
    """A synthetic 1.0 s decode (128 steps) and a 3.6 s prefill."""
    _trace(tmp_path, monkeypatch,
           lambda modules, ms: [("%fusion.1 = bf16[8]{0} fusion(", ms, 2 * ms)])
    material = material_of(NODE)
    assert reader("prefill_device_ms.lm")(material) == pytest.approx(3600.0)
    assert reader("decode_device_ms_per_token.lm")(material) == pytest.approx(1000 / 128)
    step = counts.decode_step_bytes(CONFIG, 16 / 128, PROMPT + NEW // 2)
    assert reader("decode_hbm_roofline_pct.lm")(material) == pytest.approx(
        100.0 * 128 * step / 1.0 / 819e9)
    assert 85.0 < reader("decode_hbm_roofline_pct.lm")(material) < 90.0
    assert reader("prefill_mxu_peak_pct.lm")(material) == pytest.approx(
        100.0 * counts.prefill_flops(CONFIG, PROMPT, NODE["prefill_routed_pairs_held"])
        / 3.6 / 197e12)
    assert 45.0 < reader("prefill_mxu_peak_pct.lm")(material) < 50.0
    # no scope in the hand-written trace, no causal kernel in it
    for name in ("shortcut_device_pct.lm", "mla_device_pct.lm", "mlp_device_pct.lm",
                 "experts_device_pct.lm", "flash_attention_latent_roofline_pct.lm"):
        assert reader(name)(material) is None, name


def test_device_the_causal_kernels_share_of_its_roofline_in_the_latent_prefill(
        tmp_path, monkeypatch):
    """Three prefills of 3.6 s, each with 1.6 s of `flash_attention_causal`
    events (32 calls of 50 ms: eight attentions, four parts); one such
    event outside any prefill does not count; the slice's first and last
    programs may be cut, so one prefill lies whole in it."""
    def ops_of(modules, ms):
        ops = [("%flash_attention_causal.9 = bf16[8192,16,128]{2,1,0} custom-call(", 100, 200)]
        for kind, start, _ in modules:
            if kind == "jit_prefill":
                ops += [(f"%flash_attention_causal.{i} = bf16[8192,16,128]{{2,1,0}} custom-call(",
                         start + (1 + 2 * i) * 50 * ms, start + (2 + 2 * i) * 50 * ms)
                        for i in range(32)]
        return ops

    _trace(tmp_path, monkeypatch, ops_of)
    latent = _load(os.path.join(
        HERE, "layer_metrics", "flash_attention_latent_roofline_pct.lm.py"), "latent_roofline")
    # the visible triangle, eight attentions: 64 heads x (192 + 128) x 2 a pair
    least = 8 * counts.attention_flops(CONFIG, counts.keys_visible(0, PROMPT)) / 197e12
    moved = 8 * sum(counts.causal_call_bytes(CONFIG, r, k)
                    for r, k in counts.prefill_causal_calls(CONFIG, PROMPT))
    assert least > moved / 819e9  # right of the ridge: the MXU bounds every call
    assert latent.least_seconds(CONFIG, PROMPT) == pytest.approx(least)
    assert 0.88 < least < 0.90
    material = material_of(NODE)
    assert latent.read(material) == pytest.approx(100.0 * least / 1.6)
    assert 50.0 < latent.read(material) < 60.0
    # another model's workflow: no reading; granite's and dots3's readers are held to their own
    assert latent.read(material_of(NODE, name="granite-4.0-h-micro")) is None
    assert reader("flash_attention_causal_roofline_pct.lm")(material) is None
    assert reader("flash_attention_band_roofline_pct.lm")(material) is None


def test_the_longcat_flash_cells_lm_work_file_is_found_by_its_registry_name():
    material = material_of(NODE)
    work, cfg = device_modules.lm_work(material)
    assert cfg["registry_name"] == CONFIG["registry_name"] == "longcat-flash-chat-ep64-4l"
    found = work(cfg, NODE)
    assert found["decode"] == pytest.approx(
        128 * counts.decode_step_bytes(CONFIG, 16 / 128, PROMPT + NEW // 2))
    assert found["prefill"] == pytest.approx(
        counts.prefill_flops(CONFIG, PROMPT, NODE["prefill_routed_pairs_held"]))
    assert device_modules.lm_work(material_of(NODE, name="no-such-model")) is None


def test_the_longcat_flash_cell_is_listed_where_its_readers_find_something():
    """Every list that held dots3-note-prev's cell but those of an index,
    a window or a ring, and `mlp_device_pct.lm` (the dense feed-forwards);
    each list is held from its start, appended in the cells' order, and
    none to its end; this PR's metrics follow the PR before's."""
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

    assert CELL in listed("mla_device_pct.lm", DEEPSEEK_CELL, LING_CELL, GLM_CELL)
    assert CELL in listed("mlp_device_pct.lm", GRANITE_CELL)
    assert CELL in listed("experts_device_pct.lm", SDAR_CELL, DOTS3_CELL)
    assert CELL in listed("experts_held_share_pct.lm", DEEPSEEK_CELL)
    for name in ("state_mb.lm", "indexer_device_pct.lm", "keys_selected_pct.lm",
                 "dsa_attend_device_pct.lm", "dsa_select_device_pct.lm",
                 "window_latent_device_pct.lm", "band_keys_seen_pct.lm",
                 "flash_attention_band_roofline_pct.lm", "flash_attention_causal_roofline_pct.lm",
                 "mtp_accept_pct.lm", "mtp_device_pct.lm", "attn_device_pct.lm",
                 "ssm_device_pct.lm", "expert_matvec_hbm_pct.lm", "expert_union_hbm_pct.lm"):
        assert CELL not in listed(name), name
    start = names.index("flash_attention_band_roofline_pct.lm") + 1
    assert names[start:start + len(NEW_METRICS)] == NEW_METRICS
    zero, shortcut, kernel = (per_layer[name] for name in NEW_METRICS)
    assert (zero["source"], zero["layer"], zero["unit"], zero["better"]) == (
        "program_counter", "sampling programs", "%", "higher")
    assert (shortcut["source"], shortcut["layer"], shortcut["unit"], shortcut["better"]) == (
        "device_trace", "sampling programs", "%", "lower")
    assert (kernel["source"], kernel["layer"], kernel["unit"], kernel["better"]) == (
        "device_trace", "kernels", "%", "higher")
    for name in NEW_METRICS:
        assert listed(name, CELL) and per_layer[name]["moves"] == "images_per_s"
        assert os.path.exists(os.path.join(HERE, "layer_metrics", name + ".py"))
    for name in ("cache_gb.lm", "decode_hbm_roofline_pct.lm", "prefill_mxu_peak_pct.lm",
                 "generate_ms.lm", "layer_passes_per_token.lm", "prefill_device_ms.lm",
                 "decode_device_ms_per_token.lm", "decode_ms_per_token.lm", "lm_share_pct.rewrite",
                 "execute_ms.txt2img", "between_jobs_ms.txt2img"):
        cells = listed(name)
        assert cells.index(GLM_CELL) < cells.index(DOTS3_CELL) < cells.index(CELL), name
    # every metric that moves images_per_s says where it is read
    for metric in manifest["per_layer"]:
        if metric["moves"] == "images_per_s":
            assert metric.get("workloads"), metric["name"]
    assert CELL in next(m for m in manifest["end_to_end"] if m["name"] == "images_per_s")[
        "workloads"]
    assert order.index(DOTS3_CELL) + 1 == order.index(CELL)
    (entry,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "longcat-flash-chat", "closed2", 1)
    (config,) = [c for c in manifest["configs"] if c["name"] == "longcat-flash-chat"]
    assert config["file"] == "benchmark/configs/longcat-flash-chat.json"
    assert config["source"] == CONFIG["source"] and config["reduced"] == CONFIG["reduced"] == [
        "num_layers", "n_routed_experts", "vocab_size"]


@pytest.mark.parametrize("mine,theirs", [
    ("reference/longcat_flash.py", "comfyui_distributed_tpu/reference/longcat_flash.py"),
    ("workflows/longdoc-txt2img-longcat-flash.json",
     "workflows/longdoc-txt2img-longcat-flash.json"),
])
def test_the_longcat_flash_copies_here_are_the_committed_files(mine, theirs):
    with open(os.path.join(HERE, mine), "rb") as a, open(os.path.join(ROOT, theirs), "rb") as b:
        assert a.read() == b.read()


def test_the_longcat_flash_cells_workflow_is_the_request_the_issue_gives():
    with open(os.path.join(HERE, "workflows", "longdoc-txt2img-longcat-flash.json"),
              encoding="utf-8") as fh:
        prompt = json.load(fh)
    texts = []
    for other in ("longdoc-txt2img-glm-5.2.json", "longdoc-txt2img-dots3-note.json"):
        with open(os.path.join(HERE, "workflows", other), encoding="utf-8") as fh:
            (theirs,) = [n for n in json.load(fh).values() if n["class_type"] == "TextGenerate"]
        texts.append(theirs["inputs"]["text"])
    (node,) = [n for n in prompt.values() if n["class_type"] == "TextGenerate"]
    assert node["inputs"]["text"] == texts[0] == texts[1]   # the three cells differ by model alone
    assert len(node["inputs"]["text"].encode("utf-8")) == 32767      # with the begin id: 32,768
    assert (node["inputs"]["max_new_tokens"], node["inputs"]["temperature"],
            node["inputs"]["draft_tokens"]) == (128, 1.0, 0)
    (loader,) = [n for n in prompt.values() if n["class_type"] == "CheckpointLoaderSimple"]
    assert loader["inputs"]["ckpt_name"] == CONFIG["registry_name"]
    (sampler,) = [n for n in prompt.values() if n["class_type"] == "KSampler"]
    assert (sampler["inputs"]["steps"], sampler["inputs"]["cfg"], sampler["inputs"][
        "sampler_name"], sampler["inputs"]["scheduler"]) == (20, 7.0, "euler", "karras")
    with open(os.path.join(HERE, "workloads", CELL + ".json"), encoding="utf-8") as fh:
        work = json.load(fh)
    assert work["workflow"] == "benchmark/workflows/longdoc-txt2img-longcat-flash.json"
    assert work["rehearsal"]["set"][0]["value"] == "tiny-longcat-flash"
    assert work["rate"] == {"metric": "images_per_s", "units_per_job": 1}
    assert work["trace"] == {"start_s": 5, "slice_s": 20}
