"""The SDAR cell's readers and counts on made-up material: the value where
the spans carry what they read (a model that generates by masked diffusion
over blocks), None where the program says nothing of it (the parent's,
another model's); the counts against a hand calculation and against the
program's own (`describe(cache_len)`, `param_count`); the decode's share
of the roofline counted over the passes of both kinds, not the tokens;
the three metrics this cell brings: `denoise_passes_per_token.lm` on the
node's counts, `experts_device_pct.lm` on hand-made operations, and
`expert_union_hbm_pct.lm` on a hand-written trace with the kernel's events
inside and outside the decode program. One check of
`test_granite_hybrid_readers.py` pinned what PR 54 found
(`attn_device_pct.lm` listing Nemotron's and granite's cells alone); its
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
import sdar_counts as counts  # noqa: E402

CONFIG = counts.config()
CELL = "sdar_30b_a3b_rewrite_txt2img_512.closed2"
GRANITE_CELL = "granite_4_0_h_micro_longdoc_txt2img_512.closed2"
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
_device = _load(os.path.join(HERE, "tests", "test_device_readers.py"), "sdar_uses_device_readers")

# a request of the cell at the rule's floor: 128 blocks of 4 denoising passes and a closing
# pass; 29 distinct experts a pass and expert layer (a closing pass runs five of the six)
PROMPT, NEW = 2048, 512
BODIES = 4 * (512 * 6 + 128 * 5)                                # 14,848 layer bodies
READ = 29 * (512 * 6 + 128 * 5)                                 # 107,648 experts read
NODE = dict(
    prompt_tokens=PROMPT, new_tokens=NEW, draft_tokens=0, decode_steps=640, layers=6,
    block_length=4, denoising_steps=4, denoise_passes=512, closing_passes=128,
    transferred_by_threshold=0, transferred_by_floor=512, experts_held=128, experts_total=128,
    cache_bytes=2560 * 12288, state_bytes=0, prefill_layer_passes=PROMPT * 6,
    decode_layer_passes=BODIES, decode_experts_read=READ,
    prefill_routed_pairs=PROMPT * 48, prefill_routed_pairs_held=PROMPT * 48,
    decode_routed_pairs=BODIES * 8, decode_routed_pairs_held=BODIES * 8)
LAYER_WITHOUT_EXPERTS = 2 * 2048 * 4096 + 2 * 2048 * 512 + 256 + 4096 + 2048 * 128  # 19,140,864
EXPERT = 3 * 2048 * 768                                                                # 4,718,592


def reader(name: str):
    """The metric's read(), loaded as run.py loads it."""
    return _load(os.path.join(HERE, "layer_metrics", name + ".py"), "layer_metric").read


def material_of(node, jobs=3, name=None):
    return _device.window(
        [_device.lm_job(3.0 * i, node) for i in range(jobs)],
        _device.lm_prompt(name or CONFIG["registry_name"]))


def test_the_counter_readers_read_the_sdar_cells_node():
    material = material_of(NODE)
    assert reader("denoise_passes_per_token.lm")(material) == pytest.approx(1.25)
    # where every block closes after one pass: 128 + 128 passes for 512 tokens
    quick = dict(NODE, decode_steps=256, denoise_passes=128, closing_passes=128)
    assert reader("denoise_passes_per_token.lm")(material_of(quick)) == pytest.approx(0.5)
    assert reader("experts_held_share_pct.lm")(material) == pytest.approx(100.0)
    assert reader("cache_gb.lm")(material) == pytest.approx(2560 * 12288 / 1e9)
    assert reader("layer_passes_per_token.lm")(material) == pytest.approx(
        (PROMPT * 6 + BODIES) / 2560)
    # a model that emits its tokens in order says nothing of passes: no reading
    ordered = {k: v for k, v in NODE.items() if k not in ("denoise_passes", "closing_passes")}
    assert reader("denoise_passes_per_token.lm")(material_of(ordered)) is None
    empty = {"spans": {}, "records": [], "trace": None, "prompt": {}}
    for name in ("denoise_passes_per_token.lm", "experts_device_pct.lm",
                 "expert_union_hbm_pct.lm"):
        assert reader(name)(empty) is None
    for name in ("experts_device_pct.lm", "expert_union_hbm_pct.lm"):
        assert reader(name)(dict(material, trace=None)) is None


def test_sdar_counts_are_the_ones_the_issue_worked_out():
    """By hand: the attention's matrices 2 x 2048 x 4096 + 2 x 2048 x 512 =
    18,874,368, with the two head norms (256), the layer's two norms
    (4,096) and the router 2048 x 128 = 262,144: 19,140,864; an expert 3
    x 2048 x 768 = 4,718,592; a layer 19,140,864 + 128 x 4,718,592 =
    623,120,640; embedding and head 2 x 151,936 x 2048 = 622,329,856."""
    assert counts.attention_matrix_params(CONFIG) == 18_874_368
    assert counts.layer_params(CONFIG, 0) == LAYER_WITHOUT_EXPERTS == 19_140_864
    assert counts.expert_params(CONFIG) == EXPERT
    assert counts.expert_matrices_bytes(CONFIG) == 9_437_184
    assert counts.layer_params(CONFIG, 128) == 623_120_640
    assert counts.total_params(CONFIG) == 6 * 623_120_640 + 622_329_856 + 2048 == 4_361_055_744
    assert counts.total_params(CONFIG) == CONFIG["as_run"]["parameters"]["lm"]
    assert CONFIG["held"]["parameters"] == 4_361_055_744
    assert counts.total_params(CONFIG, CONFIG["published"]["num_hidden_layers"]) == (
        CONFIG["published"]["parameters"]) == 30_532_122_624
    assert counts.cache_bytes(CONFIG, 2560) == 31_457_280
    assert counts.cache_bytes(CONFIG, 1) == CONFIG["as_run"]["cache_bytes_per_token"] == 12288
    assert counts.expected_experts_read(CONFIG) == pytest.approx(128 * (1 - (120 / 128) ** 4))
    assert 29.0 < counts.expected_experts_read(CONFIG) < 29.2


def test_a_denoising_pass_moves_2_5_gb_a_closing_pass_1_6_and_a_prefill_is_1_7_tflop():
    at = PROMPT + NEW // 2
    denoise = counts.pass_bytes(CONFIG, 29 * 6, at, closing=False)
    weights = (6 * LAYER_WITHOUT_EXPERTS + 29 * 6 * EXPERT + 4 * 2048    # the embedding's rows
               + 2048 + 151_936 * 2048)                                   # the final norm, the head
    assert denoise == pytest.approx(weights * 2 + 12288 * at + 12288 * 4)
    assert 2.4e9 < denoise < 2.6e9
    # a closing pass: no head; of its last layer the first norm, W_k, W_v and the keys' norm
    closing = counts.pass_bytes(CONFIG, 29 * 5, at, closing=True)
    weights = (5 * LAYER_WITHOUT_EXPERTS + 2048 + 2 * 2048 * 512 + 128
               + 29 * 5 * EXPERT + 4 * 2048)
    assert closing == pytest.approx(weights * 2 + 12288 * at * 5 // 6 + 12288 * 4)
    assert 1.5e9 < closing < 1.65e9
    assert counts.decode_bytes(CONFIG, 512, 128, READ, at) == pytest.approx(
        512 * denoise + 128 * closing)
    # position i over 4 floor(i / 4) + 4 keys: 4 x 4 x 512 x 513 / 2 pairs a head's channel
    assert counts.block_attention_flops(CONFIG, PROMPT) == pytest.approx(
        4.0 * 4096 * 16 * 512 * 513 / 2)
    flops = counts.prefill_flops(CONFIG, PROMPT, PROMPT * 48)
    assert flops == pytest.approx(
        2.0 * PROMPT * 6 * (18_874_368 + 262_144) + 2.0 * PROMPT * 48 * EXPERT
        + 6 * counts.block_attention_flops(CONFIG, PROMPT) + 2.0 * 151_936 * 2048)
    assert 1.6e12 < flops < 1.8e12


def test_the_sizes_the_sdar_counts_read_are_the_registrys():
    sys.path.insert(0, ROOT)
    import dataclasses

    import jax.numpy as jnp

    from comfyui_distributed_tpu.models import get_config, sdar
    from comfyui_distributed_tpu.models.registry import create_model

    model = get_config(CONFIG["registry_name"])
    assert sdar.param_count(model) == counts.total_params(CONFIG)
    published = dataclasses.replace(
        model, num_hidden_layers=CONFIG["published"]["num_hidden_layers"])
    assert sdar.param_count(published) == CONFIG["published"]["parameters"]
    for key in ("hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
                "head_dim", "moe_intermediate_size", "num_experts", "num_experts_per_tok",
                "norm_topk_prob", "vocab_size", "rms_norm_eps", "rope_theta"):
        assert getattr(model, key) == CONFIG[key], key
    for key in ("block_length", "denoising_steps", "confidence_threshold", "mask_token_id"):
        assert getattr(model, key) == CONFIG["as_run"][key], key
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    layer = sdar.param_shapes(model)["layers"][0]
    assert sdar.count_params(layer) == counts.layer_params(CONFIG, 128)
    assert sdar.count_params(layer["moe"]["experts"]) == 128 * counts.expert_params(CONFIG)
    lm = create_model(CONFIG["registry_name"])
    lm.dtype = jnp.dtype(CONFIG["as_run"]["weights_dtype"])
    described = lm.describe(2560)
    assert described["cache_bytes"] == counts.cache_bytes(CONFIG, 2560)
    assert described["state_bytes"] == CONFIG["as_run"]["state_bytes"] == 0
    assert (described["layers"], described["block_length"], described["denoising_steps"]) == (
        6, 4, 4)


def test_device_the_sdar_cells_shares_of_the_peaks_and_its_kernels_share_of_the_hbm(
        tmp_path, monkeypatch):
    """A synthetic 2.3 s decode (640 passes) and a 50 ms prefill; inside
    each decode 1.5 s of `expert_matvec` events, and one such event
    outside any decode that does not count."""
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", "a.cell", "--seed", "1",
                                      "--out", str(tmp_path)])
    ms = _device.MS
    modules = [("jit__clip_apply", 0, 400_000)] + [
        (k, s + ms, e + ms) for k, s, e in _device.lm_modules(3, 3000, 50_000, 2_300_000)]
    ops = [("%fusion.1 = bf16[8]{0} fusion(", ms, 2 * ms),
           ("%expert_matvec.9 = bf16[6,32,256]{2,1,0} custom-call(", 100, 200)]  # before any decode
    for kind, start, end in modules:
        if kind == "jit_decode":
            ops += [(f"%expert_matvec.{i} = bf16[6,32,256]{{2,1,0}} custom-call(",
                     start + (1 + 2 * i) * 100 * ms, start + (2 + 2 * i) * 100 * ms)
                    for i in range(10)] + [
                (f"%expert_matvec.{10 + i} = bf16[2,32,1024]{{2,1,0}} custom-call(",
                 start + 2050 * ms + 2 * i * 25 * ms, start + 2050 * ms + (2 * i + 1) * 25 * ms)
                for i in range(4)] + [
                (f"%expert_matvec.{14 + i} = bf16[2,32,1024]{{2,1,0}} custom-call(",
                 start + 50 * ms + 2 * i * 100 * ms, start + 100 * ms + 2 * i * 100 * ms)
                for i in range(8)]
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
    assert reader("prefill_device_ms.lm")(material) == pytest.approx(50.0)
    # the accepted reader divides by the tokens: what a token cost, not a pass
    assert reader("decode_device_ms_per_token.lm")(material) == pytest.approx(2300 / 512)
    decode = counts.decode_bytes(CONFIG, 512, 128, READ, PROMPT + NEW // 2)
    assert reader("decode_hbm_roofline_pct.lm")(material) == pytest.approx(
        100.0 * decode / 2.3 / 819e9)
    assert 75.0 < reader("decode_hbm_roofline_pct.lm")(material) < 85.0
    assert reader("prefill_mxu_peak_pct.lm")(material) == pytest.approx(
        100.0 * counts.prefill_flops(CONFIG, PROMPT, PROMPT * 48) / 0.050 / 197e12)
    # the slice's first and last programs may be cut: of three decodes one lies whole in
    # it, with 10 x 100 + 4 x 25 + 8 x 50 ms of the kernel; 107,648 experts of 9.44 MB each
    share = reader("expert_union_hbm_pct.lm")(material)
    assert share == pytest.approx(100.0 * READ * 9_437_184 / 1.5 / 819e9)
    assert 80.0 < share < 100.0
    # another model's workflow, or a node that says nothing of the experts read
    assert reader("expert_union_hbm_pct.lm")(material_of(NODE, name="k-exaone-ep8-5l")) is None
    silent = {k: v for k, v in NODE.items() if k != "decode_experts_read"}
    assert reader("expert_union_hbm_pct.lm")(material_of(silent)) is None
    # Nemotron-3-Nano's reading of the same kernel is held to its own configuration
    assert reader("expert_matvec_hbm_pct.lm")(material) is None
    # the hand-written trace's operations say nothing of a scope: no share
    assert reader("experts_device_pct.lm")(material) is None
    assert reader("attn_device_pct.lm")(material) is None


def test_the_experts_share_is_self_time_under_the_experts_scope_in_both_programs():
    import scoped_self_time

    module = _load(os.path.join(HERE, "layer_metrics", "experts_device_pct.lm.py"), "experts")
    attn = _load(os.path.join(HERE, "layer_metrics", "attn_device_pct.lm.py"), "attn")
    assert module.PROGRAMS == attn.PROGRAMS == ("jit_prefill", "jit_decode")
    assert (module.SCOPE, attn.SCOPE) == ("experts", "attn")
    layer = "jit(prefill)/jit(main)/layer_3/"
    a_pass = "jit(decode)/jit(main)/while/body/while/body/layer_3/"
    close = "jit(decode)/jit(main)/while/body/close/layer_3/"
    operations = [
        (0, 100, layer + "attn/flash_attention_causal"),
        (100, 150, layer + "moe/router/dot_general"),
        (150, 450, layer + "moe/experts/ragged_dot"),
        (450, 500, "jit(prefill)/jit(main)/head/dot_general"),
        (1000, 2000, "jit(decode)/jit(main)/while"),                     # the loop over blocks
        (1000, 1100, a_pass + "attn/dot_general"),
        (1100, 1150, a_pass + "moe/router/top_k"),
        (1150, 1500, a_pass + "moe/experts/jit(expert_matvec)/expert_matvec"),
        (1500, 1600, "jit(decode)/jit(main)/while/body/while/body/head/dot_general"),
        (1600, 1650, "jit(decode)/jit(main)/while/body/while/body/transfer/expertslike/mul"),
        (1650, 1700, close + "attn/dot_general"),
        (1700, 1900, close + "moe/experts/jit(expert_matvec)/expert_matvec"),
    ]
    both = [(0, 500), (1000, 2000)]
    share = {m.SCOPE: scoped_self_time.self_time_pct(
        operations, both, scoped_self_time.under(m.SCOPE)) for m in (module, attn)}
    assert share["experts"] == pytest.approx(100.0 * (300 + 350 + 200) / 1500)
    assert share["attn"] == pytest.approx(100.0 * (100 + 100 + 50) / 1500)
    scope = scoped_self_time.under(module.SCOPE)
    assert scope.search("a/experts") and scope.search("a/experts/b")
    assert not scope.search("a/expertslike/b") and not scope.search("a/shared/b")


# --- one check of test_granite_hybrid_readers.py, in the form that outlives a PR -----


def test_the_lm_cells_are_listed_where_their_readers_find_something_no_list_held_to_its_end():
    """`test_the_lm_cells_are_listed_where_their_readers_find_something_each_
    list_from_its_start`, which also held one list to be what PR 54 found
    (`attn_device_pct.lm` listing Nemotron's and granite's cells alone):
    true until a PR appends a cell to it, as this one does. No list is
    held to end anywhere: a list starts with the cells it had, later cells
    follow in the cells' order; a PR's metrics come after those of the PR
    before."""
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

    assert CELL not in listed(
        "state_mb.lm", SOLAR_CELL, K_EXAONE_CELL, LING_CELL, NEMOTRON_CELL, GRANITE_CELL)
    listed("linear_attention_device_pct.lm", SOLAR_CELL, LING_CELL)
    for name in ("mtp_accept_pct.lm", "mtp_device_pct.lm"):
        assert CELL not in listed(name, K_EXAONE_CELL, LING_CELL, GLM_CELL)   # no draft module
    listed("state_keep_device_pct.lm", LING_CELL)
    assert CELL not in listed("mla_device_pct.lm", DEEPSEEK_CELL, LING_CELL, GLM_CELL)
    assert CELL not in listed("ssm_device_pct.lm", NEMOTRON_CELL, GRANITE_CELL)
    assert CELL not in listed("expert_matvec_hbm_pct.lm", NEMOTRON_CELL)      # held to Nemotron's
    assert CELL in listed(
        "experts_held_share_pct.lm", DEEPSEEK_CELL, SOLAR_CELL, K_EXAONE_CELL, LING_CELL,
        NEMOTRON_CELL, GLM_CELL)
    for name in ("indexer_device_pct.lm", "keys_selected_pct.lm", "dsa_attend_device_pct.lm",
                 "dsa_select_device_pct.lm"):
        assert CELL not in listed(name, GLM_CELL)
    assert CELL in listed("attn_device_pct.lm", NEMOTRON_CELL, GRANITE_CELL)
    assert CELL not in listed("mlp_device_pct.lm", GRANITE_CELL)
    assert CELL not in listed("flash_attention_causal_roofline_pct.lm", GRANITE_CELL)
    # this PR's three, after the PR before's one, each read in this cell
    assert names[names.index("dsa_select_device_pct.lm") + 1:][:3] == [
        "denoise_passes_per_token.lm", "experts_device_pct.lm", "expert_union_hbm_pct.lm"]
    assert listed("denoise_passes_per_token.lm", CELL)[-1] == CELL
    assert CELL in listed("experts_device_pct.lm")
    assert set(listed("experts_device_pct.lm")) <= set(per_layer["experts_held_share_pct.lm"][
        "workloads"])
    assert CELL in listed("expert_union_hbm_pct.lm")
    passes, experts, union = (per_layer[name] for name in (
        "denoise_passes_per_token.lm", "experts_device_pct.lm", "expert_union_hbm_pct.lm"))
    assert (passes["source"], passes["layer"], passes["unit"], passes["better"]) == (
        "program_counter", "sampling programs", "passes/token", "lower")
    assert (experts["source"], experts["layer"], experts["unit"], experts["better"]) == (
        "device_trace", "sampling programs", "%", "lower")
    assert (union["source"], union["layer"], union["unit"], union["better"]) == (
        "device_trace", "kernels", "%", "higher")
    for name in ("cache_gb.lm", "decode_hbm_roofline_pct.lm", "prefill_mxu_peak_pct.lm",
                 "generate_ms.lm", "layer_passes_per_token.lm", "prefill_device_ms.lm",
                 "decode_device_ms_per_token.lm"):
        cells = listed(name)
        assert (cells.index(SOLAR_CELL) < cells.index(K_EXAONE_CELL) < cells.index(LING_CELL)
                < cells.index(NEMOTRON_CELL) < cells.index(GLM_CELL) < cells.index(GRANITE_CELL)
                < cells.index(CELL)), name
    # every metric that moves images_per_s says where it is read
    for metric in manifest["per_layer"]:
        if metric["moves"] == "images_per_s":
            assert metric.get("workloads"), metric["name"]
    assert CELL in next(m for m in manifest["end_to_end"] if m["name"] == "images_per_s")[
        "workloads"]
    assert order.index(GLM_CELL) + 1 == order.index(GRANITE_CELL)
    assert order.index(GRANITE_CELL) + 1 == order.index(CELL)
    assert all(w["chips"] == 1 for w in manifest["workloads"])
    for stem in ("solar-open2-250b", "k-exaone-236b-a23b", "ling-3.0-flash",
                 "nemotron-3-nano-30b-a3b", "glm-5.2", "granite-4.0-h-micro",
                 "sdar-30b-a3b-chat"):
        (config,) = [c for c in manifest["configs"] if c["name"] == stem]
        assert config["file"] == f"benchmark/configs/{stem}.json"
        with open(os.path.join(ROOT, config["file"]), encoding="utf-8") as fh:
            source = json.load(fh)
        assert config["source"] == source["source"] and config["reduced"] == source["reduced"]


def test_the_sdar_cells_lm_work_file_is_found_by_its_registry_name():
    material = material_of(NODE)
    work, cfg = device_modules.lm_work(material)
    assert cfg["registry_name"] == CONFIG["registry_name"] == "sdar-30b-a3b-pp8-6l"
    found = work(cfg, NODE)
    assert found["decode"] == pytest.approx(
        counts.decode_bytes(CONFIG, 512, 128, READ, PROMPT + NEW // 2))
    assert found["prefill"] == pytest.approx(counts.prefill_flops(CONFIG, PROMPT, PROMPT * 48))
    assert device_modules.lm_work(material_of(NODE, name="no-such-model")) is None


@pytest.mark.parametrize("mine,theirs", [
    ("reference/sdar.py", "comfyui_distributed_tpu/reference/sdar.py"),
    ("workflows/rewrite-txt2img-sdar-30b-a3b.json", "workflows/rewrite-txt2img-sdar-30b-a3b.json"),
])
def test_the_sdar_copies_here_are_the_committed_files(mine, theirs):
    with open(os.path.join(HERE, mine), "rb") as a, open(os.path.join(ROOT, theirs), "rb") as b:
        assert a.read() == b.read()


def test_the_sdar_cells_workflow_is_the_request_the_issue_gives():
    with open(os.path.join(HERE, "workflows", "rewrite-txt2img-sdar-30b-a3b.json"),
              encoding="utf-8") as fh:
        prompt = json.load(fh)
    (node,) = [n for n in prompt.values() if n["class_type"] == "TextGenerate"]
    assert len(node["inputs"]["text"].encode("utf-8")) == 2047       # with the begin id: 2,048
    assert (node["inputs"]["max_new_tokens"], node["inputs"]["temperature"],
            node["inputs"]["draft_tokens"]) == (512, 1.0, 0)
    (sampler,) = [n for n in prompt.values() if n["class_type"] == "KSampler"]
    assert (sampler["inputs"]["steps"], sampler["inputs"]["cfg"], sampler["inputs"][
        "sampler_name"], sampler["inputs"]["scheduler"]) == (20, 7.0, "euler", "karras")
    with open(os.path.join(HERE, "workloads", CELL + ".json"), encoding="utf-8") as fh:
        work = json.load(fh)
    assert work["workflow"] == "benchmark/workflows/rewrite-txt2img-sdar-30b-a3b.json"
    assert work["rehearsal"]["set"][0]["value"] == "tiny-sdar"
