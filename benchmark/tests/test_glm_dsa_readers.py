"""The GLM-5.2 cell's readers and counts on made-up material: the two
metrics this cell brings (`keys_selected_pct.lm` from the spans,
`indexer_device_pct.lm` on hand-made operations); the counts against a
hand calculation and against the program's own (`describe(cache_len)`,
`param_count`): a part's operations, a step's bytes, the chosen keys'
closed form, none of which depends on the form the program's attention
took; the cell's shares of the peaks counted over the steps. One check of
`test_nemotron3_nano_readers.py` pinned what PR 48 found (two lists of
cells as they stood); its form that holds once a PR appends a cell to
them is here, and the tier-1 adopter (`tests/test_benchmark_yardstick.py`)
takes this one in its place.

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
import glm_dsa_counts as counts  # noqa: E402

CONFIG = counts.config()
CELL = "glm_5_2_longdoc_txt2img_512.closed2"
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
_device = _load(os.path.join(HERE, "tests", "test_device_readers.py"), "glm_uses_device_readers")

PROMPT, NEW, STEPS = 32768, 128, 87
# a request of the cell: 87 steps kept 40 drafts (1 + 87 + 40 = 128); the prompt's
# positions in five layers, then a step's two in five layers and the module's
VISIBLE = 5 * PROMPT * (PROMPT + 1) // 2 + STEPS * 12 * (PROMPT + 64)
SELECTED = 5 * (2048 * 2049 // 2 + (PROMPT - 2048) * 2048) + STEPS * 12 * 2048
NODE = dict(
    prompt_tokens=PROMPT, new_tokens=NEW, draft_tokens=1, decode_steps=STEPS, mtp_drafted=STEPS,
    mtp_accepted=40, layers=5, index_topk=2048, indexer_layers=2, index_shared_layers=3,
    prefill_part=8192, prefill_parts=4, experts_held=16, experts_total=256,
    cache_bytes=32896 * 7680, indexer_cache_bytes=32896 * 768, state_bytes=0,
    keys_visible=VISIBLE, keys_selected=SELECTED,
    prefill_sparse_attention_form="gathered", decode_sparse_attention_form="masked",
    prefill_layer_passes=PROMPT * 5, decode_layer_passes=STEPS * 12, decode_experts_read=400,
    prefill_routed_pairs=PROMPT * 32, prefill_routed_pairs_held=65000,
    decode_routed_pairs=STEPS * 80, decode_routed_pairs_held=420)


def reader(name: str):
    """The metric's read(), loaded as run.py loads it."""
    return _load(os.path.join(HERE, "layer_metrics", name + ".py"), "layer_metric").read


def material_of(node, jobs=3, name=None):
    return _device.window(
        [_device.lm_job(6.0 * i, node) for i in range(jobs)],
        _device.lm_prompt(name or CONFIG["registry_name"]))


def test_the_share_of_keys_read_is_summed_over_the_windows_requests():
    other = dict(NODE, keys_visible=VISIBLE + 1000, keys_selected=SELECTED + 100)
    material = _device.window(
        [_device.lm_job(0.0, NODE), _device.lm_job(6.0, other)],
        _device.lm_prompt(CONFIG["registry_name"]))
    share = reader("keys_selected_pct.lm")(material)
    assert share == pytest.approx(100.0 * (2 * SELECTED + 100) / (2 * VISIBLE + 1000))
    assert 12.0 < share < 12.5
    assert reader("cache_gb.lm")(material) == pytest.approx(0.25264128)
    assert reader("mtp_accept_pct.lm")(material) == pytest.approx(100.0 * 40 / 87)
    assert reader("layer_passes_per_token.lm")(material) == pytest.approx(
        (PROMPT * 5 + STEPS * 12) / 32896)
    assert reader("experts_held_share_pct.lm")(material) == pytest.approx(
        100.0 * 65420 / (PROMPT * 32 + STEPS * 80))


def test_a_model_that_reads_every_key_says_nothing_of_a_share():
    # another model's node, the parent's program, no request at all: nothing
    assert reader("keys_selected_pct.lm")(material_of(_device.DEEPSEEK_NODE)) is None
    assert reader("keys_selected_pct.lm")({"spans": {}, "records": [], "trace": None}) is None
    assert reader("indexer_device_pct.lm")({"spans": {}, "records": [], "trace": None}) is None
    # the hand-written trace's operations say nothing of a scope: no share
    assert reader("indexer_device_pct.lm")(dict(material_of(NODE), trace=None)) is None


def test_glm_counts_are_the_ones_the_issue_worked_out():
    """By hand: W_dq 6144 x 2048, W_uq 2048 x 16384, W_dkv 6144 x 576, W_o
    16384 x 6144 = 150,339,584; W_uk + W_uv 512 x 64 x 448 = 14,680,064;
    the indexer 2048 x 4096 + 6144 x 128 + 6144 x 32 = 9,371,648 (+ 256 in
    its LayerNorm); an expert 3 x 6144 x 2048 = 37,748,736; a router 6144
    x 256 = 1,572,864."""
    assert counts.mla_projection_params(CONFIG) == 150_339_584
    assert counts.mla_up_params(CONFIG) == 14_680_064
    assert counts.mla_params(CONFIG) == 165_022_208
    assert counts.indexer_matrix_params(CONFIG) == 9_371_648
    assert counts.indexer_params(CONFIG) == 9_371_904
    assert counts.dense_params(CONFIG) == 226_492_416
    assert counts.expert_params(CONFIG) == 37_748_736
    assert counts.always_params(CONFIG) == 1_572_864 + 37_748_736
    assert list(counts.held_layers(CONFIG)) == [2, 3, 4, 5, 6]
    assert (counts.full_layers(CONFIG), counts.dense_layers(CONFIG), counts.sparse_layers(CONFIG)) == (
        2, 1, 4)
    assert counts.layer_params(CONFIG, True, True, 16) == 400_898_816
    assert counts.layer_params(CONFIG, False, False, 16) == 808_336_128
    assert counts.layer_params(CONFIG, False, True, 16) == 817_708_032
    assert counts.mtp_params(CONFIG, 16) == 893_223_936
    assert counts.total_params(CONFIG) == CONFIG["as_run"]["parameters"]["lm"] == 4_774_740_992
    assert counts.cache_bytes(CONFIG, 32896) == 32896 * 7680 == 252_641_280    # 252.6 MB
    assert counts.indexer_cache_bytes(CONFIG, 32896) == 32896 * 768


def test_the_chosen_keys_closed_form_is_the_sum_it_stands_for():
    for first, last, topk in ((0, 50, 8), (0, 5, 8), (3, 40, 8), (20, 40, 8), (8, 9, 8), (0, 8, 8)):
        assert counts.keys_visible(first, last) == sum(t + 1 for t in range(first, last))
        assert counts.keys_chosen(first, last, topk) == sum(
            min(t + 1, topk) for t in range(first, last)), (first, last)
    # the cell's prompt: 12.1 % of what a causal mask allows
    assert counts.keys_chosen(0, PROMPT, 2048) == 65_012_736
    assert counts.keys_visible(0, PROMPT) == 536_887_296
    # four parts add up to the prompt, whichever part a position falls in
    assert sum(counts.keys_chosen(p * 8192, (p + 1) * 8192, 2048) for p in range(4)) == 65_012_736


def test_a_prefill_is_121_tflop_as_the_model_defines_it_and_a_step_moves_3_9_gb():
    # a full layer's index: 2 x 32 x 128 over every visible pair, 4.40 TFLOP
    assert counts.index_flops(CONFIG, PROMPT) == pytest.approx(2.0 * 32 * 128 * 536_887_296)
    assert 4.39e12 < counts.index_flops(CONFIG, PROMPT) < 4.41e12
    # a layer's attention over the chosen keys, expanded: 2 x 64 x (256 + 256) a pair, 4.26
    assert counts.chosen_attention_flops(CONFIG, PROMPT) == pytest.approx(
        2.0 * 64 * 512 * 65_012_736)
    assert 4.25e12 < counts.chosen_attention_flops(CONFIG, PROMPT) < 4.27e12
    flops = counts.prefill_flops(CONFIG, PROMPT, 65000)
    per_token = (
        5 * (150_339_584 + 14_680_064) + 2 * 9_371_648 + 226_492_416
        + 4 * (1_572_864 + 37_748_736)
        + 2 * 6144 * 6144 + 6144 * 576 + 6144 * 128)          # the module: W_eh, W_dkv, W_kI
    assert flops == pytest.approx(
        2.0 * PROMPT * per_token + 2.0 * 65000 * 37_748_736
        + 2 * counts.index_flops(CONFIG, PROMPT)
        + 5 * counts.chosen_attention_flops(CONFIG, PROMPT) + 2.0 * 19360 * 6144)
    assert 115e12 < flops < 125e12
    # a part's share: the first part's queries see the fewest keys
    assert counts.index_flops(CONFIG, 8192) < 0.07 * counts.index_flops(CONFIG, PROMPT)

    step = counts.decode_step_bytes(CONFIG, 400 / 87, PROMPT + 64)
    weights = (
        5 * (165_022_208 + 2 * 6144) + 2 * 9_371_904 + 226_492_416   # attention, indexers, dense
        + 4 * (1_572_864 + 256 + 37_748_736)                   # routers, biases, shared experts
        + 400 / 87 * 37_748_736                                # the held experts read
        + 6144 + 2 * 19360 * 6144 + 4 * 6144                   # final norm, the head twice, 4 rows
        + 2 * 6144 * 6144 + 3 * 6144                           # W_eh and the module's norms
        + 165_022_208 + 2 * 6144 + 9_371_904 + 1_572_864 + 256 + 37_748_736  # its layer
    )
    # each position's 2,048 chosen latents (and its own row written) in six layers; three
    # indexer caches whole at mid-decode
    caches = 6 * 2 * 2049 * 1152 + 3 * (PROMPT + 64 + 2) * 256
    assert step == pytest.approx(2 * weights + caches)
    assert 3.85e9 < step < 3.95e9
    plain = counts.decode_step_bytes(CONFIG, 2.0, PROMPT + 64, drafting=False)
    assert 2.6e9 < plain < 2.9e9 and plain < step
    # a short cache: a query reads what is there, not `index_topk`
    assert counts.decode_step_bytes(CONFIG, 0, 100) < counts.decode_step_bytes(CONFIG, 0, 4096)


def test_the_counts_do_not_depend_on_the_form_the_program_took():
    material = material_of(NODE)
    work, cfg = device_modules.lm_work(material)
    said = work(cfg, NODE)
    for prefill_form in ("gathered", "masked", "kernel"):
        for decode_form in ("gathered", "masked", "kernel"):
            other = dict(NODE, prefill_sparse_attention_form=prefill_form,
                         decode_sparse_attention_form=decode_form)
            assert work(cfg, other) == said
    assert said["decode"] == pytest.approx(
        STEPS * counts.decode_step_bytes(CONFIG, 400 / 87, PROMPT + 64))
    assert said["prefill"] == counts.prefill_flops(CONFIG, PROMPT, 65000)
    assert cfg["registry_name"] == "glm-5.2-ep16-5l"
    assert cfg["published"] == {
        "num_hidden_layers": 78, "n_routed_experts": 256, "vocab_size": 154880}


def test_the_sizes_the_glm_counts_read_are_the_registrys():
    sys.path.insert(0, ROOT)
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models import get_config, glm_dsa
    from comfyui_distributed_tpu.models.registry import create_model

    model = get_config(CONFIG["registry_name"])
    assert glm_dsa.param_count(model) == counts.total_params(CONFIG)
    assert list(model.layers) == list(counts.held_layers(CONFIG))
    assert (model.full_layers, model.sparse_layers) == (
        counts.full_layers(CONFIG), counts.sparse_layers(CONFIG))
    assert (model.index_topk, model.prefill_part) == (
        CONFIG["index_topk"], CONFIG["as_run"]["prefill_part"])
    shapes = glm_dsa.param_shapes(model)
    assert glm_dsa.count_params(shapes["mtp"]) == counts.mtp_params(CONFIG, 16)
    assert glm_dsa.count_params(shapes["layers"][0]["indexer"]) == counts.indexer_params(CONFIG)
    assert glm_dsa.count_params(shapes["layers"][1]["attn"]) == counts.mla_params(CONFIG)
    lm = create_model(CONFIG["registry_name"])
    lm.dtype = jnp.dtype(CONFIG["as_run"]["weights_dtype"])
    described = lm.describe(32896)
    assert described["cache_bytes"] == counts.cache_bytes(CONFIG, 32896)
    assert described["indexer_cache_bytes"] == counts.indexer_cache_bytes(CONFIG, 32896)
    assert described["state_bytes"] == CONFIG["as_run"]["state_bytes"] == 0
    assert (described["indexer_layers"], described["index_shared_layers"],
            described["index_topk"], described["layers"]) == (2, 3, 2048, 5)


def test_device_the_glm_cells_shares_of_the_peaks_are_counted_over_its_steps(tmp_path, monkeypatch):
    """A synthetic 6.5 ms step, 87 of them, and a 4 s prefill."""
    traced = _device.tracing(tmp_path, monkeypatch)
    traced([("jit__clip_apply", 0, 400_000)] + [
        (k, s + _device.MS, e + _device.MS)
        for k, s, e in _device.lm_modules(3, 6000, 4_000_000, STEPS * 6_500)])
    material = material_of(NODE)
    assert reader("prefill_device_ms.lm")(material) == pytest.approx(4000.0)
    assert reader("decode_device_ms_per_token.lm")(material) == pytest.approx(STEPS * 6.5 / NEW)
    step = counts.decode_step_bytes(CONFIG, 400 / 87, PROMPT + 64)
    assert reader("decode_hbm_roofline_pct.lm")(material) == pytest.approx(
        100.0 * step / 0.0065 / 819e9)
    assert 70.0 < reader("decode_hbm_roofline_pct.lm")(material) < 76.0
    assert reader("prefill_mxu_peak_pct.lm")(material) == pytest.approx(
        100.0 * counts.prefill_flops(CONFIG, PROMPT, 65000) / 4.0 / 197e12)
    assert reader("prefill_mxu_peak_pct.lm")(material) < 100.0
    # the hand-written trace's operations say nothing of a scope: no share
    assert reader("indexer_device_pct.lm")(material) is None


def test_the_indexers_share_is_self_time_under_the_scope_in_both_programs():
    import scoped_self_time

    module = _load(os.path.join(HERE, "layer_metrics", "indexer_device_pct.lm.py"), "indexer_share")
    mla = _load(os.path.join(HERE, "layer_metrics", "mla_device_pct.lm.py"), "mla_share")
    assert module.PROGRAMS == mla.PROGRAMS == ("jit_prefill", "jit_decode")
    body = "jit(prefill)/jit(main)/while/body/"
    step = "jit(decode)/jit(main)/while/body/"
    operations = [
        (0, 1000, "jit(prefill)/jit(main)/while"),                         # the parts' scan
        (0, 100, body + "layer_2/mla/dot_general"),
        (100, 300, body + "layer_2/indexer/while/body/scores/dot_general"),
        (300, 500, body + "layer_2/indexer/while/body/select/top_k"),
        (500, 800, body + "layer_2/mla/while/body/gather"),
        (800, 900, body + "layer_3/mla/while/body/gather"),                # a shared layer: no indexer
        (900, 1000, body + "mtp/indexer/dot_general"),
        (2000, 3000, "jit(decode)/jit(main)/while"),
        (2000, 2100, step + "mtp/indexer/select/while/body/reduce"),
        (2100, 2200, step + "mtp/mla/dot_general"),
        (2200, 2300, step + "layer_6/indexer/scores/dot_general"),
        (2300, 3000, step + "layer_6/experts/expert_matvec"),
        (3000, 3100, step + "verify/indexerlike/mul"),                     # no such scope
    ]
    both = [(0, 1000), (2000, 3100)]
    # under indexer 200 + 200 + 100 in the prefill, 100 + 100 in the decode, of 1,000 + 1,100
    assert scoped_self_time.self_time_pct(
        operations, both, scoped_self_time.under(module.SCOPE)) == pytest.approx(
        100.0 * 700 / 2100)
    # beside it and not inside it: what attends reads none of what chose
    assert scoped_self_time.self_time_pct(
        operations, both, scoped_self_time.under(mla.SCOPE)) == pytest.approx(100.0 * 600 / 2100)


# --- one check of test_nemotron3_nano_readers.py, in the form that outlives a PR ---


def test_the_lm_cells_are_listed_where_their_readers_find_something_each_after_those_before():
    """`test_the_lm_cells_are_listed_where_their_readers_find_something_in_
    the_order_they_came`, which also held three lists to be what PR 48
    found (`mtp_accept_pct.lm`, `mtp_device_pct.lm`, `mla_device_pct.lm`):
    true until a PR appends a cell to them, as this one does. A list starts
    with the cells it had, in the order their PRs came; what comes after
    them came later and is in the cells' order too; so are the metrics."""
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

    assert CELL not in listed("state_mb.lm", SOLAR_CELL, K_EXAONE_CELL, LING_CELL, NEMOTRON_CELL)
    assert listed("linear_attention_device_pct.lm", SOLAR_CELL, LING_CELL) == [SOLAR_CELL, LING_CELL]
    for name in ("mtp_accept_pct.lm", "mtp_device_pct.lm"):
        assert CELL in listed(name, K_EXAONE_CELL, LING_CELL)
    assert listed("state_keep_device_pct.lm", LING_CELL) == [LING_CELL]
    assert CELL in listed("mla_device_pct.lm", DEEPSEEK_CELL, LING_CELL)
    assert listed("ssm_device_pct.lm", NEMOTRON_CELL) == [NEMOTRON_CELL]
    assert listed("expert_matvec_hbm_pct.lm", NEMOTRON_CELL) == [NEMOTRON_CELL]
    assert CELL in listed("indexer_device_pct.lm", CELL)
    assert CELL in listed("keys_selected_pct.lm", CELL)
    for name in ("state_keep_device_pct.lm", "mla_device_pct.lm", "mtp_device_pct.lm",
                 "linear_attention_device_pct.lm", "ssm_device_pct.lm", "indexer_device_pct.lm"):
        assert (per_layer[name]["source"], per_layer[name]["layer"], per_layer[name]["moves"],
                per_layer[name]["unit"]) == (
            "device_trace", "sampling programs", "images_per_s", "%")
    kernel = per_layer["expert_matvec_hbm_pct.lm"]
    assert (kernel["source"], kernel["layer"], kernel["moves"], kernel["unit"], kernel["better"]) == (
        "device_trace", "kernels", "images_per_s", "%", "higher")
    share = per_layer["keys_selected_pct.lm"]
    assert (share["source"], share["layer"], share["moves"], share["unit"], share["better"]) == (
        "program_counter", "sampling programs", "images_per_s", "%", "lower")
    assert per_layer["indexer_device_pct.lm"]["better"] == "lower"
    assert (per_layer["state_mb.lm"]["source"], per_layer["mtp_accept_pct.lm"]["source"]) == (
        "program_counter", "program_counter")
    # each PR's metrics after those of the PR before
    assert names.index("state_keep_device_pct.lm") + 1 == names.index("mla_device_pct.lm")
    assert names[names.index("mla_device_pct.lm") + 1:][:2] == [
        "ssm_device_pct.lm", "expert_matvec_hbm_pct.lm"]
    assert names.index("expert_matvec_hbm_pct.lm") < names.index("indexer_device_pct.lm")
    assert names.index("indexer_device_pct.lm") + 1 == names.index("keys_selected_pct.lm")
    for name in ("experts_held_share_pct.lm", "cache_gb.lm", "decode_hbm_roofline_pct.lm",
                 "prefill_mxu_peak_pct.lm", "generate_ms.lm", "layer_passes_per_token.lm"):
        cells = listed(name)
        assert (cells.index(SOLAR_CELL) < cells.index(K_EXAONE_CELL) < cells.index(LING_CELL)
                < cells.index(NEMOTRON_CELL) < cells.index(CELL)), name
    assert order.index(LING_CELL) + 1 == order.index(NEMOTRON_CELL)
    assert order.index(NEMOTRON_CELL) + 1 == order.index(CELL)
    assert all(w["chips"] == 1 for w in manifest["workloads"])
    for stem in ("solar-open2-250b", "k-exaone-236b-a23b", "ling-3.0-flash",
                 "nemotron-3-nano-30b-a3b", "glm-5.2"):
        (config,) = [c for c in manifest["configs"] if c["name"] == stem]
        assert config["file"] == f"benchmark/configs/{stem}.json"
        with open(os.path.join(ROOT, config["file"]), encoding="utf-8") as fh:
            source = json.load(fh)
        assert config["source"] == source["source"] and config["reduced"] == source["reduced"]


def test_the_glm_cells_lm_work_file_is_found_by_its_registry_name():
    work, found = device_modules.lm_work({"prompt": _device.lm_prompt("glm-5.2-ep16-5l")})
    assert found["registry_name"] == "glm-5.2-ep16-5l" and callable(work)
    assert set(work(found, NODE)) == {"decode", "prefill"}


@pytest.mark.parametrize("mine, theirs", [
    ("workflows/longdoc-txt2img-glm-5.2.json", "workflows/longdoc-txt2img-glm-5.2.json"),
    ("reference/glm_dsa.py", "comfyui_distributed_tpu/reference/glm_dsa.py"),
])
def test_the_glm_copies_here_are_the_committed_files(mine, theirs):
    with open(os.path.join(HERE, mine), "rb") as a, open(os.path.join(ROOT, theirs), "rb") as b:
        assert a.read() == b.read()
