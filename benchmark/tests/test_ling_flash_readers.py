"""The Ling-3.0-flash cell's readers and counts on made-up material: the
counts against a hand calculation and against the program's own
(`describe(cache_len)`, `param_count`); the decode's share of the roofline
counted over the steps with what keeping or dropping a draft adds; the two
scoped shares this cell brings (`state_keep_device_pct.lm`,
`mla_device_pct.lm`) on hand-made operations. One check of
`test_k_exaone_readers.py` pinned what PR 41 found (its cell the last of
each list); its form that holds once a PR appends a cell is here, and the
tier-1 adopter (`tests/test_benchmark_yardstick.py`) takes this one in its
place.

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
import ling_flash_counts  # noqa: E402

CONFIG = ling_flash_counts.config()
CELL = "ling_flash_rewrite_txt2img_512.closed2"
K_EXAONE_CELL = "k_exaone_rewrite_txt2img_512.closed2"
SOLAR_CELL = "solar_open2_rewrite_txt2img_512.closed2"
DEEPSEEK_CELL = "deepseek_v2_rewrite_txt2img_512.closed2"


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# the hand-written trace, the spans and the window of test_device_readers.py
_device = _load(os.path.join(HERE, "tests", "test_device_readers.py"), "ling_flash_uses_device_readers")

# a request of the cell: 690 steps kept 333 drafts (1 + 690 + 333 = 1,024)
NODE = dict(
    prompt_tokens=8192, new_tokens=1024, draft_tokens=1, decode_steps=690, mtp_drafted=690,
    mtp_accepted=333, layers=7, linear_layers=6, latent_layers=2, experts_held=64,
    experts_total=512, cache_bytes=9216 * 2304, state_bytes=26050560, prefill_chunks=128,
    prefill_layer_passes=8192 * 7, decode_layer_passes=690 * 16, decode_experts_read=9000,
    prefill_routed_pairs=8192 * 48, prefill_routed_pairs_held=49000,
    decode_routed_pairs=690 * 112, decode_routed_pairs_held=9600)


def reader(name: str):
    """The metric's read(), loaded as run.py loads it."""
    return _load(os.path.join(HERE, "layer_metrics", name + ".py"), "layer_metric").read


def material_of(node, jobs=3, name=None):
    return _device.window(
        [_device.lm_job(2.4 * i, node) for i in range(jobs)],
        _device.lm_prompt(name or CONFIG["registry_name"]))


def test_the_counter_readers_that_are_there_read_the_cells_node():
    material = material_of(NODE, jobs=2)
    assert reader("mtp_accept_pct.lm")(material) == pytest.approx(100.0 * 333 / 690)
    assert reader("state_mb.lm")(material) == pytest.approx(26.05056)
    assert reader("cache_gb.lm")(material) == pytest.approx(9216 * 2304 / 1e9)
    assert reader("layer_passes_per_token.lm")(material) == pytest.approx(
        (8192 * 7 + 690 * 16) / 9216)
    assert reader("experts_held_share_pct.lm")(material) == pytest.approx(
        100.0 * (49000 + 9600) / (8192 * 48 + 690 * 112))
    for name in ("state_keep_device_pct.lm", "mla_device_pct.lm"):
        assert reader(name)({"spans": {}, "records": [], "trace": None}) is None
        assert reader(name)(dict(material, trace=None)) is None


def test_ling_flash_counts_are_the_ones_the_issue_worked_out():
    """By hand: a KDA mixer's matrices 6 x 2560 x 4096 + 2560 x 32 =
    62,996,480 (qkv 31.46 M, f, g and o 10.49 M each, beta 0.08 M), with
    the filters, A_log, dt_bias and the norm 63,049,888; an MLA mixer's
    2560 x (6144 + 576 + 32) + 2 x 512 x 4096 + 4096 x 2560 = 31,965,184
    (+ 512 in the latent's norm); an expert 3 x 2560 x 768 = 5,898,240; a
    router 2560 x 512 = 1,310,720; the dense part 3 x 2560 x 6144."""
    assert ling_flash_counts.kda_matrix_params(CONFIG) == 62_996_480
    assert ling_flash_counts.kda_params(CONFIG) == 63_049_888
    assert ling_flash_counts.mla_matrix_params(CONFIG) == 31_965_184
    assert ling_flash_counts.mla_params(CONFIG) == 31_965_696
    assert ling_flash_counts.expert_params(CONFIG) == 5_898_240
    assert ling_flash_counts.always_params(CONFIG) == 1_310_720 + 5_898_240
    assert ling_flash_counts.dense_params(CONFIG) == 47_185_920
    assert list(ling_flash_counts.held_layers(CONFIG)) == [1, 2, 3, 4, 5, 6, 7]
    assert ling_flash_counts.layers(CONFIG) == (6, 1)
    assert (ling_flash_counts.dense_layers(CONFIG), ling_flash_counts.sparse_layers(CONFIG)) == (1, 6)
    sparse = ling_flash_counts.sparse_part_params(CONFIG, 64)
    assert sparse == 1_310_720 + 512 + 65 * 5_898_240                          # 384.7 M
    assert ling_flash_counts.mtp_params(CONFIG, 64) == (
        2 * 2560 * 2560 + 5 * 2560 + 31_965_696 + sparse)                       # 429.8 M
    assert ling_flash_counts.total_params(CONFIG) == CONFIG["as_run"]["parameters"]["lm"]
    assert ling_flash_counts.total_params(CONFIG) == 3_296_050_624              # 6.59 GB
    assert ling_flash_counts.cache_bytes(CONFIG, 9216) == 2 * 9216 * 576 * 2    # 21.2 MB
    assert ling_flash_counts.cache_bytes(CONFIG, 1) == CONFIG["as_run"]["cache_bytes_per_token"]
    assert ling_flash_counts.matrix_state_bytes(CONFIG) == 32 * 128 * 128 * 4   # 2.10 MB
    assert ling_flash_counts.tail_bytes(CONFIG) == 3 * 12288 * 2
    assert ling_flash_counts.state_bytes(CONFIG) == 6 * 2 * (2_097_152 + 73_728) == 26_050_560
    assert ling_flash_counts.state_bytes(CONFIG) == CONFIG["as_run"]["state_bytes"]
    assert ling_flash_counts.keep_bytes(CONFIG) == 6 * (2_097_152 + 73_728)     # 13.0 MB a step


def test_a_drafting_step_moves_1_5_gb_a_plain_one_1_2_and_a_prefill_is_6_tflop():
    # 13.04 distinct held experts a step (9,000 over 690 steps), latents at mid-decode
    step = ling_flash_counts.decode_step_bytes(CONFIG, 9000 / 690, 8192 + 512)
    weights = (
        6 * 63_049_888 + 31_965_696 + 7 * 2 * 2560 + 47_185_920      # mixers, norms, the dense part
        + 6 * (1_310_720 + 512 + 5_898_240)                          # routers, biases, shared experts
        + 9000 / 690 * 5_898_240                                     # the held experts read
        + 2560 + 2 * 19648 * 2560 + 4 * 2560                         # final norm, the head twice, 4 rows
        + 2 * 2560 * 2560 + 5 * 2560                                 # W_eh and the module's norms
        + 31_965_696 + 1_310_720 + 512 + 5_898_240                   # its layer without routed experts
    )
    latents = 2 * (8192 + 512 + 2) * 1152
    states = 6 * 3 * (2_097_152 + 73_728)      # read once, written after each of two positions
    assert step == pytest.approx(2 * weights + latents + states)
    assert 1.45e9 < step < 1.55e9
    plain = ling_flash_counts.decode_step_bytes(CONFIG, 6.0, 8192 + 512, drafting=False)
    assert 1.15e9 < plain < 1.25e9 and plain < step
    # what keeping or dropping a draft adds is the one write more a layer
    without = step - ling_flash_counts.keep_bytes(CONFIG)
    assert ling_flash_counts.keep_bytes(CONFIG) / step < 0.01 and without > plain
    attention = ling_flash_counts.causal_attention_flops(CONFIG, 8192)
    assert attention == pytest.approx(2.0 * 32 * 320 * 8192 * 8193 / 2)
    delta = ling_flash_counts.delta_rule_flops(CONFIG, 8192)
    assert delta == 128 * 32 * (5 * 64 * 64 * 128 + 6 * 64 * 128 * 128)
    flops = ling_flash_counts.prefill_flops(CONFIG, 8192, 49000)
    per_token = (6 * 62_996_480 + 31_965_184 + 47_185_920 + 6 * (1_310_720 + 5_898_240)
                 + 2 * 2560 * 2560 + 2560 * 576)
    assert flops == pytest.approx(
        2.0 * 8192 * per_token + 2.0 * 49000 * 5_898_240 + attention + 6 * delta
        + 2.0 * 19648 * 2560)
    assert 9e12 < flops < 10e12
    assert ling_flash_counts.prefill_bytes(CONFIG, 8192) < 2 * ling_flash_counts.total_params(CONFIG)


def test_the_sizes_the_ling_flash_counts_read_are_the_registrys():
    sys.path.insert(0, ROOT)
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models import get_config, ling_flash
    from comfyui_distributed_tpu.models.registry import create_model

    model = get_config(CONFIG["registry_name"])
    assert ling_flash.param_count(model) == ling_flash_counts.total_params(CONFIG)
    assert (model.kda_layers, model.mla_layers) == ling_flash_counts.layers(CONFIG)
    assert list(model.layers) == list(ling_flash_counts.held_layers(CONFIG))
    assert model.sparse_layers == ling_flash_counts.sparse_layers(CONFIG)
    assert (model.first_layer, model.kda_chunk) == (
        CONFIG["as_run"]["first_layer"], CONFIG["as_run"]["kda_chunk"])
    assert ling_flash.DT_BIAS_SHIFT == CONFIG["as_run"]["dt_bias_shift"]
    shapes = ling_flash.param_shapes(model)
    assert ling_flash.count_params(shapes["mtp"]) == ling_flash_counts.mtp_params(CONFIG, 64)
    assert ling_flash.count_params(shapes["layers"][0]["kda"]) == ling_flash_counts.kda_params(CONFIG)
    assert ling_flash.count_params(shapes["layers"][4]["mla"]) == ling_flash_counts.mla_params(CONFIG)
    lm = create_model(CONFIG["registry_name"])
    lm.dtype = jnp.dtype(CONFIG["as_run"]["weights_dtype"])
    described = lm.describe(9216)
    assert described["cache_bytes"] == ling_flash_counts.cache_bytes(CONFIG, 9216)
    assert described["state_bytes"] == ling_flash_counts.state_bytes(CONFIG)
    assert (described["linear_layers"], described["latent_layers"]) == (6, 2)
    # the limits the lists give the held layers and the module
    assert [model.limits(layer) for layer in model.layers] == [
        (CONFIG["expert_swiglu_limit_list"][layer], CONFIG["share_expert_swiglu_limit_list"][layer])
        for layer in ling_flash_counts.held_layers(CONFIG)]
    assert model.limits(-1) == (4, 7)


def test_device_the_ling_flash_cells_shares_of_the_peaks_are_counted_over_its_steps(tmp_path, monkeypatch):
    """A synthetic 2.2 ms step, 690 of them, and a 250 ms prefill."""
    traced = _device.tracing(tmp_path, monkeypatch)
    traced([("jit__clip_apply", 0, 400_000)] + [
        (k, s + _device.MS, e + _device.MS)
        for k, s, e in _device.lm_modules(3, 2400, 250_000, 690 * 2_200)])
    material = material_of(NODE)
    assert reader("prefill_device_ms.lm")(material) == pytest.approx(250.0)
    assert reader("decode_device_ms_per_token.lm")(material) == pytest.approx(690 * 2.2 / 1024)
    step = ling_flash_counts.decode_step_bytes(CONFIG, 9000 / 690, 8192 + 512)
    assert reader("decode_hbm_roofline_pct.lm")(material) == pytest.approx(
        100.0 * step / 0.0022 / 819e9)
    assert 80.0 < reader("decode_hbm_roofline_pct.lm")(material) < 86.0
    assert reader("prefill_mxu_peak_pct.lm")(material) == pytest.approx(
        100.0 * ling_flash_counts.prefill_flops(CONFIG, 8192, 49000) / 0.250 / 197e12)
    assert reader("prefill_mxu_peak_pct.lm")(material) < 25.0
    # without drafting the same program is 1,024 one-position steps
    plain = dict(NODE, draft_tokens=0, decode_steps=1024, mtp_drafted=0, mtp_accepted=0,
                 decode_experts_read=6100)
    work, cfg = device_modules.lm_work(material)
    assert cfg["registry_name"] == "ling-flash-ep8-7l"
    assert work(cfg, plain)["decode"] == pytest.approx(1024 * ling_flash_counts.decode_step_bytes(
        CONFIG, 6100 / 1024, 8192 + 512, drafting=False))
    # the hand-written trace's operations say nothing of a scope: no share
    for name in ("state_keep_device_pct.lm", "mla_device_pct.lm", "mtp_device_pct.lm"):
        assert reader(name)(material) is None


def test_the_two_new_shares_are_self_time_under_their_scopes():
    import scoped_self_time

    keep = _load(os.path.join(HERE, "layer_metrics", "state_keep_device_pct.lm.py"), "keep_share")
    latent = _load(os.path.join(HERE, "layer_metrics", "mla_device_pct.lm.py"), "mla_share")
    assert (keep.PROGRAMS, keep.SCOPE) == (("jit_decode",), "keep")
    assert (latent.PROGRAMS, latent.SCOPE) == (("jit_prefill", "jit_decode"), "mla")
    body = "jit(decode)/jit(main)/while/body/"
    operations = [
        (0, 400, "jit(prefill)/jit(main)/layer_5/mla/dot_general"),   # the prefill: 400 of 500
        (400, 500, "jit(prefill)/jit(main)/layer_4/kda/keep/copy"),   # no such scope there, but named
        (1000, 3000, "jit(decode)/jit(main)/while"),                  # the loop of 2,000 ...
        (1100, 1300, body + "mtp/mla/dot_general"),
        (1300, 1400, body + "layer_2/kda/delta/mul"),
        (1400, 1500, body + "layer_2/kda/keep/dynamic_update_slice"),
        (1500, 1600, body + "layer_5/mla/dot_general"),
        (1600, 1650, body + "keep/select_n"),                         # the flip
        (1650, 2650, body + "layer_6/experts/ragged_dot"),
        (2650, 2700, body + "verify/keeper/mul"),                     # no such scope
    ]
    decode, both = [(1000, 3100)], [(0, 500), (1000, 3100)]
    # under keep in the decode 100 + 50; all: the loop's own 400 and the 1,600 of its body
    assert scoped_self_time.self_time_pct(
        operations, decode, scoped_self_time.under(keep.SCOPE)) == pytest.approx(100.0 * 150 / 2000)
    # under mla in both programs 400 + 200 + 100 of 500 + 2,000
    assert scoped_self_time.self_time_pct(
        operations, both, scoped_self_time.under(latent.SCOPE)) == pytest.approx(100.0 * 700 / 2500)
    scope = scoped_self_time.under("keep")
    assert scope.search("a/keep") and scope.search("a/kda/keep/b")
    assert not scope.search("a/keeper/b") and not scope.search("a/upkeep")
    # DeepSeek's programs bear the scope under their own layer names
    assert scoped_self_time.under("mla").search("jit(decode)/while/body/moe_3/mla/dot_general")


# --- one check of test_k_exaone_readers.py, in the form that outlives a PR ----


def test_the_lm_cells_are_listed_where_their_readers_find_something_whoever_came_last():
    """`test_the_lm_cells_are_listed_where_their_readers_find_something`,
    which also held K-EXAONE's cell to be the last of each list: true
    until a PR appends a cell, as this one does. A list is the cells in
    the order their PRs came, each appended."""
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        manifest = json.load(fh)
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    order = [w["name"] for w in manifest["workloads"]]

    def listed(name, *cells):
        found = per_layer[name]["workloads"]
        assert found[:len(cells)] == list(cells), name
        assert found == sorted(found, key=order.index), name  # appended, in the cells' order
        return found

    assert CELL in listed("state_mb.lm", SOLAR_CELL, K_EXAONE_CELL)
    assert CELL in listed("linear_attention_device_pct.lm", SOLAR_CELL)
    assert K_EXAONE_CELL not in per_layer["linear_attention_device_pct.lm"]["workloads"]
    for name in ("mtp_accept_pct.lm", "mtp_device_pct.lm"):
        assert CELL in listed(name, K_EXAONE_CELL)
    assert listed("state_keep_device_pct.lm", CELL) == [CELL]
    assert listed("mla_device_pct.lm", DEEPSEEK_CELL, CELL) == [DEEPSEEK_CELL, CELL]
    for name in ("state_keep_device_pct.lm", "mla_device_pct.lm", "mtp_device_pct.lm",
                 "linear_attention_device_pct.lm"):
        assert (per_layer[name]["source"], per_layer[name]["layer"], per_layer[name]["moves"],
                per_layer[name]["unit"]) == (
            "device_trace", "sampling programs", "images_per_s", "%")
    assert (per_layer["state_mb.lm"]["source"], per_layer["mtp_accept_pct.lm"]["source"]) == (
        "program_counter", "program_counter")
    assert list(per_layer)[-2:] == ["state_keep_device_pct.lm", "mla_device_pct.lm"]
    for name in ("experts_held_share_pct.lm", "cache_gb.lm", "decode_hbm_roofline_pct.lm",
                 "prefill_mxu_peak_pct.lm", "generate_ms.lm", "layer_passes_per_token.lm"):
        cells = listed(name)
        assert cells.index(SOLAR_CELL) < cells.index(K_EXAONE_CELL) < cells.index(CELL), name
    assert order[-1] == CELL and manifest["workloads"][-1]["chips"] == 1
    for stem in ("solar-open2-250b", "k-exaone-236b-a23b", "ling-3.0-flash"):
        (config,) = [c for c in manifest["configs"] if c["name"] == stem]
        assert config["file"] == f"benchmark/configs/{stem}.json"
        with open(os.path.join(ROOT, config["file"]), encoding="utf-8") as fh:
            source = json.load(fh)
        assert config["source"] == source["source"] and config["reduced"] == source["reduced"]


def test_the_cells_lm_work_file_is_found_by_its_registry_name():
    work, found = device_modules.lm_work({"prompt": _device.lm_prompt("ling-flash-ep8-7l")})
    assert found["registry_name"] == "ling-flash-ep8-7l" and callable(work)
    assert found["published"] == {"num_hidden_layers": 42, "num_experts": 512, "vocab_size": 157184}
    said = work(found, NODE)
    assert set(said) == {"decode", "prefill"}
    assert said["decode"] == pytest.approx(
        690 * ling_flash_counts.decode_step_bytes(CONFIG, 9000 / 690, 8192 + 512))


@pytest.mark.parametrize("mine, theirs", [
    ("workflows/rewrite-txt2img-ling-flash.json", "workflows/rewrite-txt2img-ling-flash.json"),
    ("reference/ling_flash.py", "comfyui_distributed_tpu/reference/ling_flash.py"),
])
def test_the_ling_flash_copies_here_are_the_committed_files(mine, theirs):
    with open(os.path.join(HERE, mine), "rb") as a, open(os.path.join(ROOT, theirs), "rb") as b:
        assert a.read() == b.read()
