"""The granite-4.0-h-micro cell's readers and counts on made-up material:
the counts against a hand calculation and against the program's own
(`describe(cache_len)`, `param_count`); a step's bytes with the tied
embedding read once as the head; the cell's shares of the peaks; the two
metrics this cell brings (`attn_device_pct.lm`, `mlp_device_pct.lm`) on
hand-made operations, beside `ssm_device_pct.lm`, with which they split
the model's device time; the causal kernel's share of its roofline on a
hand-written trace with the kernel's events inside and outside a
prefill. One check of `test_glm_dsa_readers.py` pinned what PR 52 found
(`ssm_device_pct.lm` listing Nemotron's cell alone); its
form that holds once a PR appends a cell to any list is here, and the
tier-1 adopter (`tests/test_benchmark_yardstick.py`) takes this one in
its place.

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
import granite_hybrid_counts as counts  # noqa: E402

CONFIG = counts.config()
CELL = "granite_4_0_h_micro_longdoc_txt2img_512.closed2"
GLM_CELL = "glm_5_2_longdoc_txt2img_512.closed2"
NEMOTRON_CELL = "nemotron3_nano_rewrite_txt2img_512.closed2"
LING_CELL = "ling_flash_rewrite_txt2img_512.closed2"
K_EXAONE_CELL = "k_exaone_rewrite_txt2img_512.closed2"
SOLAR_CELL = "solar_open2_rewrite_txt2img_512.closed2"
DEEPSEEK_CELL = "deepseek_v2_rewrite_txt2img_512.closed2"


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# the hand-written trace, the spans and the window of test_device_readers.py
_device = _load(
    os.path.join(HERE, "tests", "test_device_readers.py"), "granite_uses_device_readers")

PROMPT, NEW = 65536, 128
NODE = dict(
    prompt_tokens=PROMPT, new_tokens=NEW, draft_tokens=0, decode_steps=NEW, layers=40,
    mamba_layers=36, attention_layers=4, prefill_part=8192, prefill_parts=8, prefill_chunks=256,
    cache_bytes=65664 * 8192, state_bytes=76437504, tied_head_bytes=100352 * 2048 * 2)
MAMBA_MATRICES = 2048 * (4096 + 4352 + 64) + 4096 * 2048      # 25,821,184
MAMBA = MAMBA_MATRICES + 5 * 4352 + 3 * 64 + 4096             # 25,847,232
ATTENTION = 2 * 2048 * 2048 + 2 * 2048 * 512                  # 10,485,760
MLP = 3 * 2048 * 8192                                         # 50,331,648
EMBEDDING = 100352 * 2048                                     # 205,520,896


def reader(name: str):
    """The metric's read(), loaded as run.py loads it."""
    return _load(os.path.join(HERE, "layer_metrics", name + ".py"), "layer_metric").read


def material_of(node, jobs=3, name=None):
    return _device.window(
        [_device.lm_job(8.0 * i, node) for i in range(jobs)],
        _device.lm_prompt(name or CONFIG["registry_name"]))


def test_the_counter_readers_read_the_granite_cells_node():
    material = material_of(NODE, jobs=2)
    assert reader("state_mb.lm")(material) == pytest.approx(76.437504)
    assert reader("cache_gb.lm")(material) == pytest.approx(65664 * 8192 / 1e9)
    assert reader("layer_passes_per_token.lm")(material) == pytest.approx(40.0)
    for name in ("ssm_device_pct.lm", "attn_device_pct.lm", "mlp_device_pct.lm"):
        assert reader(name)({"spans": {}, "records": [], "trace": None, "prompt": {}}) is None
        assert reader(name)(dict(material, trace=None)) is None


def test_granite_counts_are_the_ones_the_issue_worked_out():
    """By hand: a Mamba-2 layer's mixer 25,847,232 (in 17.43 M, out 8.39 M,
    the convolution's 4 x 4,352 filters and 4,352 biases, A_log, dt_bias
    and D, the gated norm's 4,096); an attention layer's 10,485,760; a
    SwiGLU 50,331,648 in every layer; two norms a layer. With those a
    Mamba layer is the issue's 76,182,976 and an attention layer its
    60,821,504."""
    assert counts.layers(CONFIG) == (36, 4)
    assert (counts.head_dim(CONFIG), counts.mamba_inner(CONFIG), counts.conv_channels(CONFIG)) == (
        64, 4096, 4352)
    assert counts.mamba_matrix_params(CONFIG) == MAMBA_MATRICES == 25_821_184
    assert counts.mamba_params(CONFIG) == MAMBA == 25_847_232
    assert counts.attention_params(CONFIG) == ATTENTION == 10_485_760
    assert counts.mlp_params(CONFIG) == MLP == 50_331_648
    assert MAMBA + MLP + 2 * 2048 == 76_182_976 and ATTENTION + MLP + 2 * 2048 == 60_821_504
    assert counts.embedding_params(CONFIG) == EMBEDDING == 205_520_896
    assert counts.small_params(CONFIG) == 81 * 2048
    assert counts.total_params(CONFIG) == 36 * 76_182_976 + 4 * 60_821_504 + EMBEDDING + 2048
    assert counts.total_params(CONFIG) == CONFIG["as_run"]["parameters"]["lm"] == 3_191_396_096
    assert counts.cache_bytes(CONFIG, 65664) == 4 * 65664 * 2 * 8 * 64 * 2 == 537_919_488
    assert counts.cache_bytes(CONFIG, 1) == CONFIG["as_run"]["cache_bytes_per_token"] == 8192
    assert counts.state_bytes(CONFIG) == 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2) == 76_437_504
    assert counts.state_bytes(CONFIG) == CONFIG["as_run"]["state_bytes"]
    assert CONFIG["reduced"] == [] and "published" not in CONFIG


def test_a_step_moves_7_1_gb_and_a_prefill_is_469_tflop():
    step = counts.decode_step_bytes(CONFIG, PROMPT + NEW // 2)
    # every weight once, the tied embedding among them as the head, and its one row
    assert step == pytest.approx(
        2 * (3_191_396_096 + 2048) + (PROMPT + 64) * 8192 + 2 * 76_437_504)
    assert 7.0e9 < step < 7.1e9
    # the tied head is 5.8 % of a step's bytes, the four caches 7.6 %, the states 2.2 %
    assert 0.055 < 2 * EMBEDDING / step < 0.06
    assert 0.07 < (PROMPT + 64) * 8192 / step < 0.08
    assert 0.02 < 2 * 76_437_504 / step < 0.025
    attention = counts.causal_attention_flops(CONFIG, PROMPT)
    assert attention == pytest.approx(4.0 * 2048 * PROMPT * (PROMPT + 1) / 2)
    scan = counts.ssd_flops(CONFIG, PROMPT)
    assert scan == 256 * (256 * 257 * (128 + 64 * 64) + 4 * 256 * 64 * 64 * 128)
    flops = counts.prefill_flops(CONFIG, PROMPT)
    per_token = 36 * MAMBA_MATRICES + 4 * ATTENTION + 40 * MLP
    assert flops == pytest.approx(
        2.0 * PROMPT * per_token + 4 * attention + 36 * scan + 2.0 * EMBEDDING)
    assert 4.6e14 < flops < 4.8e14
    # of the operations on weights the SwiGLUs are two thirds; of all, over half
    assert 0.66 < 40 * MLP / per_token < 0.68
    assert 0.55 < 2.0 * PROMPT * 40 * MLP / flops < 0.58
    # the four causal attentions 15 %, the 36 scans 1.6 %
    assert 0.14 < 4 * attention / flops < 0.16 and 0.015 < 36 * scan / flops < 0.017
    # the work does not depend on the parts; the weights' bytes do (read once a part)
    assert counts.prefill_flops(dict(CONFIG, as_run=dict(CONFIG["as_run"], prefill_part=4096)),
                                PROMPT) == flops
    assert 8 * 2 * (3_191_396_096 - EMBEDDING) < counts.prefill_bytes(CONFIG, PROMPT) < 56e9


def test_the_causal_calls_of_a_layer_add_up_to_its_triangle():
    calls = counts.prefill_causal_calls(CONFIG, PROMPT)
    assert calls == [(8192, 8192 * (i + 1)) for i in range(8)]
    assert sum(counts.causal_call_flops(CONFIG, *call) for call in calls) == pytest.approx(
        counts.causal_attention_flops(CONFIG, PROMPT))
    assert counts.prefill_causal_calls(CONFIG, 20000) == [
        (8192, 8192), (8192, 16384), (3616, 20000)]
    # the last part's call: 8,192 x 32 queries and outputs, 65,536 x 8 keys and values, 64 wide
    assert counts.causal_call_bytes(CONFIG, 8192, 65536) == 2 * 64 * (
        2 * 8192 * 32 + 2 * 65536 * 8)


def test_the_sizes_the_granite_counts_read_are_the_registrys():
    sys.path.insert(0, ROOT)
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models import get_config, granite_hybrid
    from comfyui_distributed_tpu.models.registry import create_model

    model = get_config(CONFIG["registry_name"])
    assert granite_hybrid.param_count(model) == counts.total_params(CONFIG)
    assert (len(model.layers_of("mamba")), len(model.layers_of("attention"))) == (
        counts.layers(CONFIG))
    assert list(model.layer_types) == CONFIG["layer_types"]
    assert model.num_hidden_layers == CONFIG["num_hidden_layers"] == 40
    assert (model.mamba_inner, model.conv_channels, model.head_dim) == (
        counts.mamba_inner(CONFIG), counts.conv_channels(CONFIG), counts.head_dim(CONFIG))
    assert (model.prefill_part, model.mamba_chunk_size) == (
        CONFIG["as_run"]["prefill_part"], CONFIG["as_run"]["prefill_chunk"])
    for key in ("hidden_size", "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups",
                "mamba_d_conv", "mamba_chunk_size", "num_attention_heads", "num_key_value_heads",
                "shared_intermediate_size", "vocab_size", "rms_norm_eps", "embedding_multiplier",
                "attention_multiplier", "residual_multiplier", "logits_scaling"):
        assert getattr(model, key) == CONFIG[key], key
    shapes = granite_hybrid.param_shapes(model)
    assert granite_hybrid.count_params(shapes["layers"][0]["mamba"]) == counts.mamba_params(CONFIG)
    assert granite_hybrid.count_params(shapes["layers"][5]["attn"]) == (
        counts.attention_params(CONFIG))
    assert granite_hybrid.count_params(shapes["layers"][39]["mlp"]) == counts.mlp_params(CONFIG)
    assert "head" not in shapes  # tied
    lm = create_model(CONFIG["registry_name"])
    lm.dtype = jnp.dtype(CONFIG["as_run"]["weights_dtype"])
    described = lm.describe(65664)
    assert described["cache_bytes"] == counts.cache_bytes(CONFIG, 65664)
    assert described["state_bytes"] == counts.state_bytes(CONFIG)
    assert described["tied_head_bytes"] == 2 * counts.embedding_params(CONFIG)
    said = lm.report(PROMPT, NEW, 65664)
    assert {key: said[key] for key in NODE if key in said} == {
        key: NODE[key] for key in NODE if key in said}
    assert set(said) == set(NODE) - {"prompt_tokens", "new_tokens", "draft_tokens", "decode_steps"}


def test_device_the_granite_cells_shares_of_the_peaks(tmp_path, monkeypatch):
    """A synthetic 9.0 ms step, 128 of them, and a 4,000 ms prefill."""
    traced = _device.tracing(tmp_path, monkeypatch)
    ms = _device.MS
    traced([("jit__clip_apply", 0, 400_000)] + [
        (k, s + ms, e + ms) for k, s, e in _device.lm_modules(3, 8000, 4_000_000, 128 * 9_000)])
    material = material_of(NODE)
    assert reader("prefill_device_ms.lm")(material) == pytest.approx(4000.0)
    assert reader("decode_device_ms_per_token.lm")(material) == pytest.approx(9.0)
    step = counts.decode_step_bytes(CONFIG, PROMPT + 64)
    assert reader("decode_hbm_roofline_pct.lm")(material) == pytest.approx(
        100.0 * step / 0.009 / 819e9)
    assert 94.0 < reader("decode_hbm_roofline_pct.lm")(material) < 97.0
    assert reader("prefill_mxu_peak_pct.lm")(material) == pytest.approx(
        100.0 * counts.prefill_flops(CONFIG, PROMPT) / 4.0 / 197e12)
    assert 58.0 < reader("prefill_mxu_peak_pct.lm")(material) < 61.0
    # the hand-written trace's operations say nothing of a scope: no share
    for name in ("ssm_device_pct.lm", "attn_device_pct.lm", "mlp_device_pct.lm"):
        assert reader(name)(material) is None
    # nor does it hold the causal kernel
    assert reader("flash_attention_causal_roofline_pct.lm")(material) is None


def test_device_the_causal_kernels_share_of_its_roofline_in_the_prefill(tmp_path, monkeypatch):
    """Inside each prefill 32 calls of 25 ms, 0.8 s; one such event outside
    any prefill (another program's) does not count."""
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", "a.cell", "--seed", "1",
                                      "--out", str(tmp_path)])
    ms = _device.MS
    modules = [("jit__clip_apply", 0, 400_000)] + [
        (k, s + ms, e + ms) for k, s, e in _device.lm_modules(3, 8000, 4_000_000, 128 * 9_000)]
    ops = [("%fusion.1 = bf16[8]{0} fusion(", ms, 2 * ms),
           ("%flash_attention_causal.9 = bf16[8,8192,128]{2,1,0} custom-call(", 100, 200)]
    for kind, start, end in modules:
        if kind == "jit_prefill":
            ops += [(f"%flash_attention_causal.{i} = bf16[32,8192,128]{{2,1,0}} custom-call(",
                     start + (1 + 4 * i) * 25 * ms, start + (2 + 4 * i) * 25 * ms)
                    for i in range(32)]
        if kind == "jit_decode":
            ops += [("%fusion.7 = bf16[8]{0} fusion(", start + ms, start + 2 * ms)]
    folder = tmp_path / "profile" / "trace-0001-benchmark" / "plugins" / "profile" / "x"
    folder.mkdir(parents=True)
    (folder / "vm.xplane.pb").write_bytes(_device.xspace({
        "/host:CPU": {"python": [("device.watch", 0, 5 * ms)]},
        "/device:TPU:0": {
            "XLA Ops": ops,
            "XLA Modules": [(f"{k}({7 + i})", s, e) for i, (k, s, e) in enumerate(modules)]},
    }))
    device_modules._LOADED.clear()
    material = material_of(NODE)
    roofline = _load(
        os.path.join(HERE, "layer_metrics", "flash_attention_causal_roofline_pct.lm.py"),
        "causal_roofline")
    assert (roofline.KERNEL, roofline.MODULE) == ("flash_attention_causal", "jit_prefill")
    # four layers' triangles at the true width: 7.04e13 FLOP, 0.357 s at the MXU's peak
    least = roofline.least_seconds(CONFIG, PROMPT)
    assert least == pytest.approx(4 * counts.causal_attention_flops(CONFIG, PROMPT) / 197e12)
    assert 0.35 < least < 0.36
    # of three prefills one lies whole in the slice: 32 x 25 ms of the kernel
    share = roofline.read(material)
    assert share == pytest.approx(100.0 * least / 0.8) and 44.0 < share < 45.0
    # another model's workflow, or a prefill without the kernel (the XLA route)
    assert roofline.read(material_of(NODE, name="nemotron3-nano-ep16-52l")) is None
    assert roofline.read(dict(material, trace=None)) is None


def test_the_three_parts_shares_are_self_time_under_their_scopes_and_add_up():
    import scoped_self_time

    parts = {name: _load(os.path.join(HERE, "layer_metrics", f"{name}_device_pct.lm.py"), name)
             for name in ("ssm", "attn", "mlp")}
    assert {name: (m.PROGRAMS, m.SCOPE) for name, m in parts.items()} == {
        "ssm": (("jit_prefill", "jit_decode"), "mamba"),
        "attn": (("jit_prefill", "jit_decode"), "attn"),
        "mlp": (("jit_prefill", "jit_decode"), "mlp")}
    part = "jit(prefill)/jit(main)/while/body/"
    step = "jit(decode)/jit(main)/while/body/"
    operations = [
        (0, 2000, "jit(prefill)/jit(main)/while"),                       # the parts' scan
        (0, 300, part + "layer_0/mamba/jit(mamba_layer)/ssd/dot_general"),
        (300, 700, part + "layer_0/jit(mamba_layer)/mlp/dot_general"),
        (700, 800, part + "layer_0/jit(mamba_layer)/add"),              # the residual sum
        (800, 1300, part + "layer_5/attn/switch/branch_7/flash_attention_causal"),
        (1300, 1400, part + "layer_5/attn/dot_general"),
        (1400, 2000, part + "layer_5/mlp/dot_general"),
        (2000, 2100, "jit(prefill)/jit(main)/head/dot_general"),
        (3000, 4000, "jit(decode)/jit(main)/while"),
        (3000, 3200, step + "layer_0/mamba/ssd/mul"),
        (3200, 3500, step + "layer_0/mlp/dot_general"),
        (3500, 3600, step + "layer_5/attn/dot_general"),
        (3600, 3900, step + "layer_5/mlp/dot_general"),
        (3900, 4000, step + "head/mlplike/dot_general"),                 # no such scope
    ]
    both = [(0, 2100), (3000, 4000)]
    share = {name: scoped_self_time.self_time_pct(
        operations, both, scoped_self_time.under(m.SCOPE)) for name, m in parts.items()}
    assert share["ssm"] == pytest.approx(100.0 * (300 + 200) / 3100)
    assert share["attn"] == pytest.approx(100.0 * (500 + 100 + 100) / 3100)
    assert share["mlp"] == pytest.approx(100.0 * (400 + 600 + 300 + 300) / 3100)
    # what is under none of the three: the residual sum, the head
    assert sum(share.values()) == pytest.approx(100.0 * (3100 - 300) / 3100)


# --- one check of test_glm_dsa_readers.py, in the form that outlives a PR -------


def test_the_lm_cells_are_listed_where_their_readers_find_something_each_list_from_its_start():
    """`test_the_lm_cells_are_listed_where_their_readers_find_something_each_
    after_those_before`, which also held two lists to be what PR 52 found
    (`ssm_device_pct.lm` and `expert_matvec_hbm_pct.lm` listing Nemotron's
    cell alone): true until a PR appends a cell to one of them, as this
    one does. No list is held to end anywhere: a list starts with the
    cells it had, later cells follow in the cells' order; a PR's metrics
    come after those of the PR before."""
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

    assert CELL in listed("state_mb.lm", SOLAR_CELL, K_EXAONE_CELL, LING_CELL, NEMOTRON_CELL)
    assert GLM_CELL not in listed("state_mb.lm")
    listed("linear_attention_device_pct.lm", SOLAR_CELL, LING_CELL)
    for name in ("mtp_accept_pct.lm", "mtp_device_pct.lm"):
        assert CELL not in listed(name, K_EXAONE_CELL, LING_CELL, GLM_CELL)
    listed("state_keep_device_pct.lm", LING_CELL)
    assert CELL not in listed("mla_device_pct.lm", DEEPSEEK_CELL, LING_CELL, GLM_CELL)
    assert CELL in listed("ssm_device_pct.lm", NEMOTRON_CELL)
    assert CELL not in listed("expert_matvec_hbm_pct.lm", NEMOTRON_CELL)   # no experts
    assert CELL not in listed("experts_held_share_pct.lm", DEEPSEEK_CELL)
    for name in ("indexer_device_pct.lm", "keys_selected_pct.lm", "dsa_attend_device_pct.lm"):
        assert CELL not in listed(name, GLM_CELL)
    assert listed("attn_device_pct.lm", NEMOTRON_CELL, CELL) == [NEMOTRON_CELL, CELL]
    assert listed("mlp_device_pct.lm", CELL) == [CELL]
    assert listed("flash_attention_causal_roofline_pct.lm", CELL) == [CELL]
    kernel = per_layer["flash_attention_causal_roofline_pct.lm"]
    assert (kernel["source"], kernel["layer"], kernel["moves"], kernel["unit"],
            kernel["better"]) == ("device_trace", "kernels", "images_per_s", "%", "higher")
    for name in ("state_keep_device_pct.lm", "mla_device_pct.lm", "mtp_device_pct.lm",
                 "linear_attention_device_pct.lm", "ssm_device_pct.lm", "indexer_device_pct.lm",
                 "dsa_attend_device_pct.lm", "attn_device_pct.lm", "mlp_device_pct.lm"):
        metric = per_layer[name]
        assert (metric["source"], metric["moves"], metric["unit"], metric["better"]) == (
            "device_trace", "images_per_s", "%", "lower"), name
    for name in ("attn_device_pct.lm", "mlp_device_pct.lm", "ssm_device_pct.lm"):
        assert per_layer[name]["layer"] == "sampling programs"
    # each PR's metrics after those of the PR before
    assert names.index("state_keep_device_pct.lm") + 1 == names.index("mla_device_pct.lm")
    assert names[names.index("mla_device_pct.lm") + 1:][:2] == [
        "ssm_device_pct.lm", "expert_matvec_hbm_pct.lm"]
    assert names.index("expert_matvec_hbm_pct.lm") < names.index("indexer_device_pct.lm")
    assert names.index("indexer_device_pct.lm") + 1 == names.index("keys_selected_pct.lm")
    assert names[names.index("dsa_attend_device_pct.lm") + 1:][:3] == [
        "attn_device_pct.lm", "mlp_device_pct.lm", "flash_attention_causal_roofline_pct.lm"]
    for name in ("cache_gb.lm", "decode_hbm_roofline_pct.lm", "prefill_mxu_peak_pct.lm",
                 "generate_ms.lm", "layer_passes_per_token.lm", "prefill_device_ms.lm",
                 "decode_device_ms_per_token.lm"):
        cells = listed(name)
        assert (cells.index(SOLAR_CELL) < cells.index(K_EXAONE_CELL) < cells.index(LING_CELL)
                < cells.index(NEMOTRON_CELL) < cells.index(GLM_CELL) < cells.index(CELL)), name
    # every metric that moves images_per_s says where it is read
    for metric in manifest["per_layer"]:
        if metric["moves"] == "images_per_s":
            assert metric.get("workloads"), metric["name"]
    assert order.index(NEMOTRON_CELL) + 1 == order.index(GLM_CELL)
    assert order.index(GLM_CELL) + 1 == order.index(CELL)
    assert all(w["chips"] == 1 for w in manifest["workloads"])
    for stem in ("solar-open2-250b", "k-exaone-236b-a23b", "ling-3.0-flash",
                 "nemotron-3-nano-30b-a3b", "glm-5.2", "granite-4.0-h-micro"):
        (config,) = [c for c in manifest["configs"] if c["name"] == stem]
        assert config["file"] == f"benchmark/configs/{stem}.json"
        with open(os.path.join(ROOT, config["file"]), encoding="utf-8") as fh:
            source = json.load(fh)
        assert config["source"] == source["source"] and config["reduced"] == source["reduced"]


def test_the_granite_cells_lm_work_file_is_found_by_its_registry_name():
    work, found = device_modules.lm_work({"prompt": _device.lm_prompt("granite-4.0-h-micro")})
    assert found["registry_name"] == "granite-4.0-h-micro" and callable(work)
    said = work(found, NODE)
    assert set(said) == {"decode", "prefill"}
    assert said["decode"] == pytest.approx(NEW * counts.decode_step_bytes(CONFIG, PROMPT + 64))
    assert said["prefill"] == pytest.approx(counts.prefill_flops(CONFIG, PROMPT))


@pytest.mark.parametrize("mine, theirs", [
    ("workflows/longdoc-txt2img-granite-4.0-h-micro.json",
     "workflows/longdoc-txt2img-granite-4.0-h-micro.json"),
    ("reference/granite_hybrid.py", "comfyui_distributed_tpu/reference/granite_hybrid.py"),
])
def test_the_granite_copies_here_are_the_committed_files(mine, theirs):
    with open(os.path.join(HERE, mine), "rb") as a, open(os.path.join(ROOT, theirs), "rb") as b:
        assert a.read() == b.read()
