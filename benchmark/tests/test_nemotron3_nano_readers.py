"""The Nemotron-3-Nano cell's readers and counts on made-up material: the
counts against a hand calculation and against the program's own
(`describe(cache_len)`, `param_count`); the decode's share of the roofline
with 23 states read and written a step; the two metrics this cell brings
(`ssm_device_pct.lm` on hand-made operations, `expert_matvec_hbm_pct.lm`
on a hand-written trace with the kernel's events inside and outside the
decode program). One check of `test_ling_flash_readers.py` pinned what
PR 45 found (its cell and its two metrics the last of each list); its
form that holds once a PR appends a cell is here, and the tier-1 adopter
(`tests/test_benchmark_yardstick.py`) takes this one in its place.

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
import nemotron3_nano_counts as counts  # noqa: E402

CONFIG = counts.config()
CELL = "nemotron3_nano_rewrite_txt2img_512.closed2"
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
_device = _load(os.path.join(HERE, "tests", "test_device_readers.py"), "nemotron_uses_device_readers")

# a request of the cell: 4,400 of the 512 steps' 70,656 pairs fell on held experts
NODE = dict(
    prompt_tokens=8192, new_tokens=512, draft_tokens=0, decode_steps=512, layers=52,
    mamba_layers=23, attention_layers=6, sparse_layers=23, experts_held=8, experts_total=128,
    cache_bytes=8704 * 6144, state_bytes=49082368, prefill_chunks=64,
    decode_experts_read=4400, prefill_routed_pairs=8192 * 6 * 23, prefill_routed_pairs_held=70500,
    decode_routed_pairs=512 * 6 * 23, decode_routed_pairs_held=4400)


def reader(name: str):
    """The metric's read(), loaded as run.py loads it."""
    return _load(os.path.join(HERE, "layer_metrics", name + ".py"), "layer_metric").read


def material_of(node, jobs=3, name=None):
    return _device.window(
        [_device.lm_job(3.4 * i, node) for i in range(jobs)],
        _device.lm_prompt(name or CONFIG["registry_name"]))


def test_the_counter_readers_read_the_nemotron3_nano_cells_node():
    material = material_of(NODE, jobs=2)
    assert reader("state_mb.lm")(material) == pytest.approx(49.082368)
    assert reader("cache_gb.lm")(material) == pytest.approx(8704 * 6144 / 1e9)
    assert reader("layer_passes_per_token.lm")(material) == pytest.approx(52.0)
    assert reader("experts_held_share_pct.lm")(material) == pytest.approx(
        100.0 * (70500 + 4400) / (8704 * 6 * 23))
    for name in ("ssm_device_pct.lm", "expert_matvec_hbm_pct.lm"):
        assert reader(name)({"spans": {}, "records": [], "trace": None, "prompt": {}}) is None
        assert reader(name)(dict(material, trace=None)) is None


def test_nemotron3_nano_counts_are_the_ones_the_issue_worked_out():
    """By hand: a Mamba-2 block's matrices 2688 x (4096 + 6144 + 64) + 4096
    x 2688 = 38,707,200 (in 27.70 M, out 11.01 M), with the convolution's
    4 x 6,144 filters and 6,144 biases, A_log, dt_bias and D (64 each) and
    the gated norm's 4,096: 38,742,208; an attention block 2 x 2688 x 4096
    + 2 x 2688 x 256 = 23,396,352; an expert 2 x 2688 x 1856 = 9,977,856;
    a router 2688 x 128 = 344,064; the shared expert 2 x 2688 x 3712."""
    assert counts.blocks(CONFIG) == (23, 23, 6)
    assert (counts.mamba_inner(CONFIG), counts.conv_channels(CONFIG)) == (4096, 6144)
    assert counts.mamba_matrix_params(CONFIG) == 2688 * 10304 + 4096 * 2688 == 38_707_200
    assert counts.mamba_params(CONFIG) == 38_707_200 + 5 * 6144 + 3 * 64 + 4096 == 38_742_208
    assert counts.attention_params(CONFIG) == 23_396_352
    assert counts.expert_params(CONFIG) == 9_977_856
    assert counts.expert_matrices_bytes(CONFIG) == 2 * 9_977_856                # 19.96 MB
    assert counts.always_params(CONFIG) == 344_064 + 19_955_712
    assert counts.small_params(CONFIG) == 53 * 2688 + 23 * 128
    sparse = counts.always_params(CONFIG) + 8 * 9_977_856
    assert sparse == 100_122_624                                                  # 100.1 M
    assert counts.total_params(CONFIG) == (
        23 * 38_742_208 + 6 * 23_396_352 + 23 * sparse + 2 * 16384 * 2688 + 53 * 2688 + 23 * 128)
    # the eight-way cut the issue counted first: 16 experts a block
    assert counts.total_params(dict(CONFIG, n_routed_experts=16)) == 5_258_420_544
    assert counts.total_params(CONFIG) == CONFIG["as_run"]["parameters"]["lm"] == 3_422_495_040
    assert counts.cache_bytes(CONFIG, 8704) == 6 * 8704 * 2 * 2 * 128 * 2        # 53.5 MB
    assert counts.cache_bytes(CONFIG, 1) == CONFIG["as_run"]["cache_bytes_per_token"] == 6144
    assert counts.state_bytes(CONFIG) == 23 * (64 * 64 * 128 * 4 + 3 * 6144 * 2) == 49_082_368
    assert counts.state_bytes(CONFIG) == CONFIG["as_run"]["state_bytes"]


def test_a_step_moves_3_4_gb_and_a_prefill_is_30_tflop():
    # 8.6 distinct held experts a step (4,400 over 512), caches at mid-decode
    step = counts.decode_step_bytes(CONFIG, 4400 / 512, 8192 + 256)
    weights = (
        23 * 38_742_208 + 6 * 23_396_352                 # the mixers
        + 23 * (344_064 + 19_955_712)                    # routers and shared experts
        + 4400 / 512 * 9_977_856                         # the held experts read
        + 53 * 2688 + 23 * 128                           # norms and selection biases
        + 16384 * 2688 + 2688                            # the head, the embedding's row
    )
    caches = 6 * (8192 + 256) * 1024
    assert step == pytest.approx(2 * weights + caches + 2 * 49_082_368)
    assert 3.3e9 < step < 3.5e9
    # the states are 2.9 % of a step's bytes, the Mamba-2 weights half
    assert 0.02 < 2 * 49_082_368 / step < 0.035
    assert 0.47 < 2 * 23 * 38_742_208 / step < 0.57
    attention = counts.causal_attention_flops(CONFIG, 8192)
    assert attention == pytest.approx(4.0 * 4096 * 8192 * 8193 / 2)
    scan = counts.ssd_flops(CONFIG, 8192)
    assert scan == 64 * (128 * 129 * (8 * 128 + 64 * 64) + 4 * 128 * 64 * 64 * 128)
    flops = counts.prefill_flops(CONFIG, 8192, 70500)
    per_token = 23 * 38_707_200 + 6 * 23_396_352 + 23 * (344_064 + 19_955_712)
    assert flops == pytest.approx(
        2.0 * 8192 * per_token + 2.0 * 70500 * 9_977_856 + 6 * attention + 23 * scan
        + 2.0 * 16384 * 2688)
    assert 29e12 < flops < 31e12
    assert counts.prefill_bytes(CONFIG, 8192) < 2 * 2 * counts.total_params(CONFIG)


def test_the_sizes_the_nemotron3_nano_counts_read_are_the_registrys():
    sys.path.insert(0, ROOT)
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models import get_config, nemotron_h
    from comfyui_distributed_tpu.models.registry import create_model

    model = get_config(CONFIG["registry_name"])
    assert nemotron_h.param_count(model) == counts.total_params(CONFIG)
    assert (len(model.blocks_of("M")), len(model.blocks_of("E")), len(model.blocks_of("*"))) == (
        counts.blocks(CONFIG))
    assert model.hybrid_override_pattern == CONFIG["hybrid_override_pattern"]
    assert (model.mamba_inner, model.conv_channels, model.chunk_size) == (
        counts.mamba_inner(CONFIG), counts.conv_channels(CONFIG), CONFIG["chunk_size"])
    shapes = nemotron_h.param_shapes(model)
    assert nemotron_h.count_params(shapes["blocks"][0]["mamba"]) == counts.mamba_params(CONFIG)
    assert nemotron_h.count_params(shapes["blocks"][2]["attn"]) == counts.attention_params(CONFIG)
    last = shapes["blocks"][-1]["moe"]
    assert nemotron_h.count_params(last["experts"]) == 8 * counts.expert_params(CONFIG)
    assert nemotron_h.count_params({"r": last["w_g"], "s": last["shared"]}) == (
        counts.always_params(CONFIG))
    lm = create_model(CONFIG["registry_name"])
    lm.dtype = jnp.dtype(CONFIG["as_run"]["weights_dtype"])
    described = lm.describe(8704)
    assert described["cache_bytes"] == counts.cache_bytes(CONFIG, 8704)
    assert described["state_bytes"] == counts.state_bytes(CONFIG)
    assert (described["mamba_layers"], described["sparse_layers"], described["attention_layers"],
            described["layers"]) == (23, 23, 6, 52)


def test_device_the_nemotron3_nano_cells_shares_of_the_peaks_and_its_kernels_share_of_the_hbm(tmp_path, monkeypatch):
    """A synthetic 5.0 ms step, 512 of them, and a 400 ms prefill; inside
    each decode 60 ms of `expert_matvec` events, and one such event
    outside any decode that does not count."""
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", "a.cell", "--seed", "1",
                                      "--out", str(tmp_path)])
    ms = _device.MS
    modules = [("jit__clip_apply", 0, 400_000)] + [
        (k, s + ms, e + ms) for k, s, e in _device.lm_modules(3, 3400, 400_000, 512 * 5_000)]
    ops = [("%fusion.1 = bf16[8]{0} fusion(", ms, 2 * ms),
           ("%expert_matvec.9 = bf16[16,1856]{1,0} custom-call(", 100, 200)]  # before any decode
    for kind, start, end in modules:
        if kind == "jit_decode":
            ops += [(f"%expert_matvec.{i} = bf16[4,16,464]{{2,1,0}} custom-call(",
                     start + (1 + 2 * i) * 10 * ms, start + (2 + 2 * i) * 10 * ms) for i in range(6)]
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
    assert reader("prefill_device_ms.lm")(material) == pytest.approx(400.0)
    assert reader("decode_device_ms_per_token.lm")(material) == pytest.approx(5.0)
    step = counts.decode_step_bytes(CONFIG, 4400 / 512, 8192 + 256)
    assert reader("decode_hbm_roofline_pct.lm")(material) == pytest.approx(
        100.0 * step / 0.005 / 819e9)
    assert 80.0 < reader("decode_hbm_roofline_pct.lm")(material) < 86.0
    assert reader("prefill_mxu_peak_pct.lm")(material) == pytest.approx(
        100.0 * counts.prefill_flops(CONFIG, 8192, 70500) / 0.400 / 197e12)
    assert 35.0 < reader("prefill_mxu_peak_pct.lm")(material) < 45.0
    # the slice's first and last programs may be cut: of three decodes one lies whole in
    # it, with 6 x 10 ms of the kernel; 4,400 experts of 19.96 MB each
    share = reader("expert_matvec_hbm_pct.lm")(material)
    assert share == pytest.approx(100.0 * 4400 * 2 * 9_977_856 / 0.060 / 819e9)
    assert share > 100.0  # made-up seconds; what a reading above 100 would say of a count
    # another model's workflow, or a node that says nothing of the experts read
    assert reader("expert_matvec_hbm_pct.lm")(material_of(NODE, name="ling-flash-ep8-7l")) is None
    silent = {k: v for k, v in NODE.items() if k != "decode_experts_read"}
    assert reader("expert_matvec_hbm_pct.lm")(material_of(silent)) is None
    # the hand-written trace's operations say nothing of a scope: no share
    assert reader("ssm_device_pct.lm")(material) is None


def test_the_state_space_share_is_self_time_under_the_mamba_scope():
    import scoped_self_time

    ssm = _load(os.path.join(HERE, "layer_metrics", "ssm_device_pct.lm.py"), "ssm_share")
    assert (ssm.PROGRAMS, ssm.SCOPE) == (("jit_prefill", "jit_decode"), "mamba")
    body = "jit(decode)/jit(main)/while/body/"
    operations = [
        (0, 300, "jit(prefill)/jit(main)/block_0/mamba/ssd/dot_general"),
        (300, 400, "jit(prefill)/jit(main)/run_1_4/while/body/mamba/in_proj/dot_general"),
        (400, 500, "jit(prefill)/jit(main)/run_1_4/while/body/experts/ragged_dot"),
        (1000, 3000, "jit(decode)/jit(main)/while"),                  # the loop of 2,000 ...
        (1100, 1400, body + "run_6_11/while/body/mamba/ssd/mul"),
        (1400, 1500, body + "block_5/attn/dot_general"),
        (1500, 2500, body + "run_6_11/while/body/experts/expert_matvec"),
        (2500, 2600, body + "block_0/mamba/out_proj/dot_general"),
        (2600, 2700, body + "head/mambalike/mul"),                    # no such scope
    ]
    both = [(0, 500), (1000, 3100)]
    # under mamba 300 + 100 in the prefill, 300 + 100 in the decode, of 500 + 2,000
    assert scoped_self_time.self_time_pct(
        operations, both, scoped_self_time.under(ssm.SCOPE)) == pytest.approx(100.0 * 800 / 2500)


# --- one check of test_ling_flash_readers.py, in the form that outlives a PR ----


def test_the_lm_cells_are_listed_where_their_readers_find_something_in_the_order_they_came():
    """`test_the_lm_cells_are_listed_where_their_readers_find_something_
    whoever_came_last`, which also held Ling-3.0-flash's cell and its two
    metrics to be the last of each list: true until a PR appends a cell,
    as this one does. A list is the cells in the order their PRs came,
    each appended; so are the metrics."""
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

    assert CELL in listed("state_mb.lm", SOLAR_CELL, K_EXAONE_CELL, LING_CELL)
    assert listed("linear_attention_device_pct.lm", SOLAR_CELL, LING_CELL) == [SOLAR_CELL, LING_CELL]
    for name in ("mtp_accept_pct.lm", "mtp_device_pct.lm"):
        assert listed(name, K_EXAONE_CELL, LING_CELL) == [K_EXAONE_CELL, LING_CELL]
    assert listed("state_keep_device_pct.lm", LING_CELL) == [LING_CELL]
    assert listed("mla_device_pct.lm", DEEPSEEK_CELL, LING_CELL) == [DEEPSEEK_CELL, LING_CELL]
    assert listed("ssm_device_pct.lm", CELL) == [CELL]
    assert listed("expert_matvec_hbm_pct.lm", CELL) == [CELL]
    for name in ("state_keep_device_pct.lm", "mla_device_pct.lm", "mtp_device_pct.lm",
                 "linear_attention_device_pct.lm", "ssm_device_pct.lm"):
        assert (per_layer[name]["source"], per_layer[name]["layer"], per_layer[name]["moves"],
                per_layer[name]["unit"]) == (
            "device_trace", "sampling programs", "images_per_s", "%")
    kernel = per_layer["expert_matvec_hbm_pct.lm"]
    assert (kernel["source"], kernel["layer"], kernel["moves"], kernel["unit"], kernel["better"]) == (
        "device_trace", "kernels", "images_per_s", "%", "higher")
    assert (per_layer["state_mb.lm"]["source"], per_layer["mtp_accept_pct.lm"]["source"]) == (
        "program_counter", "program_counter")
    # each PR's metrics after those of the PR before
    assert names.index("state_keep_device_pct.lm") + 1 == names.index("mla_device_pct.lm")
    assert names[names.index("mla_device_pct.lm") + 1:][:2] == [
        "ssm_device_pct.lm", "expert_matvec_hbm_pct.lm"]
    for name in ("experts_held_share_pct.lm", "cache_gb.lm", "decode_hbm_roofline_pct.lm",
                 "prefill_mxu_peak_pct.lm", "generate_ms.lm", "layer_passes_per_token.lm"):
        cells = listed(name)
        assert (cells.index(SOLAR_CELL) < cells.index(K_EXAONE_CELL) < cells.index(LING_CELL)
                < cells.index(CELL)), name
    assert order.index(LING_CELL) + 1 == order.index(CELL)
    assert all(w["chips"] == 1 for w in manifest["workloads"])
    for stem in ("solar-open2-250b", "k-exaone-236b-a23b", "ling-3.0-flash",
                 "nemotron-3-nano-30b-a3b"):
        (config,) = [c for c in manifest["configs"] if c["name"] == stem]
        assert config["file"] == f"benchmark/configs/{stem}.json"
        with open(os.path.join(ROOT, config["file"]), encoding="utf-8") as fh:
            source = json.load(fh)
        assert config["source"] == source["source"] and config["reduced"] == source["reduced"]


def test_the_nemotron3_nano_cells_lm_work_file_is_found_by_its_registry_name():
    work, found = device_modules.lm_work({"prompt": _device.lm_prompt("nemotron3-nano-ep16-52l")})
    assert found["registry_name"] == "nemotron3-nano-ep16-52l" and callable(work)
    assert found["published"] == {"n_routed_experts": 128, "vocab_size": 131072}
    said = work(found, NODE)
    assert set(said) == {"decode", "prefill"}
    assert said["decode"] == pytest.approx(
        512 * counts.decode_step_bytes(CONFIG, 4400 / 512, 8192 + 256))


@pytest.mark.parametrize("mine, theirs", [
    ("workflows/rewrite-txt2img-nemotron3-nano.json",
     "workflows/rewrite-txt2img-nemotron3-nano.json"),
    ("reference/nemotron_h.py", "comfyui_distributed_tpu/reference/nemotron_h.py"),
])
def test_the_nemotron3_nano_copies_here_are_the_committed_files(mine, theirs):
    with open(os.path.join(HERE, mine), "rb") as a, open(os.path.join(ROOT, theirs), "rb") as b:
        assert a.read() == b.read()
