"""chip_smoke.py's contract where no chip is needed to check it: it
fails, with no result line, when there is no accelerator or no program
beside it; and its own checks reject what they must."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO_ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke_mod", SMOKE)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _result_lines(stdout: str) -> list[dict]:
    out = []
    for line in stdout.splitlines():
        if line.startswith("{"):
            out.append(json.loads(line))
    return out


def test_fails_without_an_accelerator_and_prints_no_result(tmp_path):
    """The sandbox case: JAX finds no TPU, the server refuses the CPU,
    and the smoke exits non-zero with the reason on its last lines —
    it never falls into the rehearsal by itself."""
    proc = subprocess.run(
        [sys.executable, SMOKE, "--out", str(tmp_path / "out")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert not any("ok" in row for row in _result_lines(proc.stdout))
    assert "REHEARSAL" not in proc.stdout
    last = proc.stdout.strip().splitlines()[-3:]
    assert any("FAILED" in line for line in last), last
    assert "refusing to serve on platform 'cpu'" in proc.stdout


def test_fails_alone_in_a_directory(tmp_path):
    """The script is a check of the program, not a stand-in for it."""
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=str(tmp_path),
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "comfyui_distributed_tpu" in proc.stderr


def test_metrics_parse_reads_runtime_gauges(smoke, monkeypatch):
    text = "\n".join([
        "# HELP cdt_jax_compiles programs",
        "cdt_jax_compiles 12",
        "cdt_jax_compile_time_seconds 3.5",
        "cdt_jax_cache_hits 10",
        "cdt_jax_cache_misses 2",
        'cdt_device_memory_bytes{device="tpu:0",stat="peak_bytes_in_use"} 9.5e9',
        'cdt_device_memory_bytes{device="tpu:1",stat="peak_bytes_in_use"} 8e9',
        'cdt_device_memory_bytes{device="tpu:0",stat="bytes_in_use"} 7e9',
        'cdt_tiles_processed_total{role="master"} 9',
    ])
    monkeypatch.setattr(smoke, "http", lambda *a, **k: text)
    metrics = smoke.read_metrics("http://x")
    assert metrics["compiles"] == 12 and metrics["cache_misses"] == 2
    assert metrics["peak_bytes_in_use"] == {
        "tpu:0": 9_500_000_000, "tpu:1": 8_000_000_000,
    }
    assert metrics["tiles"] == {"master": 9}
    delta = smoke.describe_metrics(
        dict(metrics, compiles=10.0, compile_s=1.0, cache_hits=10.0), metrics
    )
    assert delta["compiles"] == 2 and delta["cache_hits"] == 0


def test_image_check_rejects_flat_blocks_and_wrong_shapes(smoke):
    rng = np.random.default_rng(0)
    good = rng.integers(0, 255, size=(128, 128, 3), dtype=np.uint8)
    smoke.check_image("good", good, 128, 64)
    flat_tile = good.copy()
    flat_tile[64:, :64] = 0  # what a NaN tile looks like after encoding
    with pytest.raises(smoke.Failure, match=r"constant 64px block at \(64,0\)"):
        smoke.check_image("nan tile", flat_tile, 128, 64)
    with pytest.raises(smoke.Failure, match="shape"):
        smoke.check_image("small", good[:64], 128, 64)


def test_warm_requests_change_only_the_seed(smoke):
    with open(os.path.join(REPO_ROOT, "workflows", "distributed-txt2img.json")) as fh:
        prompt = json.load(fh)
    assert smoke.committed_seed(prompt) == 42
    warm = smoke.with_seed(prompt, 43)
    assert smoke.committed_seed(prompt) == 42  # the committed graph is untouched
    changed = [
        (node_id, key)
        for node_id, node in prompt.items()
        for key, value in node["inputs"].items()
        if warm[node_id]["inputs"][key] != value
    ]
    assert changed == [("5", "seed")]


def test_the_legs_by_name(smoke):
    assert smoke.LEGS == ("serve", "restart", "attention", "experts", "multichip", "init")
    assert smoke.DEFAULT_LEGS == smoke.LEGS[:-1]  # `init` only when named
    proc = subprocess.run(
        [sys.executable, SMOKE, "--legs", "experts,kernels"],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 2 and "unknown leg(s) ['kernels']" in proc.stderr


def test_the_init_leg_builds_a_bundle_a_program_a_component(tmp_path):
    """The leg at toy size, because the caller said so: a row a component
    with the program's temporaries beside the stored bytes and its cold
    compile seconds (no parent commit is unpacked beside a checkout)."""
    proc = subprocess.run(
        [sys.executable, SMOKE, "--legs", "init", "--rehearsal", "--out", str(tmp_path / "out")],
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stdout[-3000:]
    rows = [json.loads(line.split("init: ", 1)[1]) for line in proc.stdout.splitlines()
            if "init: {" in line]
    built = [row for row in rows if "shape" in row]
    assert [row["shape"] for row in built] == [
        "tiny-unet UNet", "tiny-unet VAE", "tiny-unet TextEncoder"]
    for row in built:
        assert row["ok"] and row["compile_s"] > 0 and row["weights"] > 30
        assert row["stored_bytes"] == 2 * row["values"] and row["temp_bytes"] >= 0


def test_the_experts_leg_times_the_shapes_the_three_models_decode_at(smoke):
    """(pairs a step, experts a token, held, experts, hidden, width) of
    every row are a registered configuration's own numbers."""
    from comfyui_distributed_tpu.models.registry import get_config

    deepseek, solar, exaone = (
        get_config(name) for name in ("deepseek-v2-ep4-5l", "solar-open2-ep8-4l", "k-exaone-ep8-5l"))
    served = {
        "deepseek-v2 step": (
            deepseek.num_experts_per_tok, deepseek.num_experts_per_tok,
            len(deepseek.held_experts), deepseek.n_routed_experts, deepseek.hidden_size,
            deepseek.moe_intermediate_size),
        "solar-open2 step": (
            solar.num_experts_per_tok, solar.num_experts_per_tok, len(solar.held_experts),
            solar.n_routed_experts, solar.hidden_size, solar.moe_intermediate_size),
        "k-exaone two positions": (
            2 * exaone.num_experts_per_tok, exaone.num_experts_per_tok,
            len(exaone.held_experts), exaone.num_experts, exaone.hidden_size,
            exaone.moe_intermediate_size),
    }
    rows = {label: rest for label, *rest in smoke.EXPERT_SHAPES}
    for label, numbers in served.items():
        assert tuple(rows[label]) == numbers, label
    # the lowering question: DeepSeek's widths at 8 rows beside its 6
    assert rows["deepseek-v2 step at 8 rows"][2:] == rows["deepseek-v2 step"][2:]
    assert rows["deepseek-v2 step at 8 rows"][0] == 8


@pytest.mark.parametrize("label, registry, window", [
    ("solar / k-exaone full 8192", "solar-open2-ep8-4l", False),
    ("solar / k-exaone full 8192", "k-exaone-ep8-5l", False),
    ("k-exaone window 8192", "k-exaone-ep8-5l", True),
    ("ouro 2048", "ouro-2.6b", False),
    ("deepseek-v2 mla 2048", "deepseek-v2-ep4-5l", False),
])
def test_the_attention_leg_times_the_causal_calls_the_four_prefills_make(
        smoke, label, registry, window):
    """(query heads, q/k width, key heads, v width, window) of a causal
    row are a registered configuration's own numbers, and its length the
    benchmark cell's prompt."""
    from comfyui_distributed_tpu.models.registry import get_config

    cfg = get_config(registry)
    rows = {row[0]: row[1:] for row in smoke.CAUSAL_SHAPES}
    (_, tokens, heads, width), kv_heads, v_width, band = rows[label]
    if registry.startswith("deepseek"):
        own = (cfg.num_attention_heads, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim,
               cfg.num_attention_heads, cfg.v_head_dim)
    else:
        own = (cfg.num_attention_heads, cfg.head_dim, cfg.num_key_value_heads, cfg.head_dim)
    assert (heads, width, kv_heads, v_width) == own
    assert band == (cfg.sliding_window if window else None)
    workflow = {"ouro-2.6b": "ouro-2.6b", "deepseek-v2-ep4-5l": "deepseek-v2",
                "solar-open2-ep8-4l": "solar-open2", "k-exaone-ep8-5l": "k-exaone"}[registry]
    with open(os.path.join(REPO_ROOT, "workflows", f"rewrite-txt2img-{workflow}.json")) as fh:
        (text,) = [node["inputs"]["text"] for node in json.load(fh).values()
                   if node["class_type"] == "TextGenerate"]
    assert tokens == 1 + len(text.encode("utf-8"))  # the byte tokenizer's, after its BOS


def test_the_legs_have_the_fifth_models_rows_at_its_own_numbers(smoke):
    """Ling-3.0-flash's rows (PR 45): the 2,560-wide `expert_matvec` shapes
    of a drafting and of a plain step, the 32-head MLA row at the cell's
    prompt, and the two-position step over its six KDA layers' states."""
    from comfyui_distributed_tpu.models.registry import get_config

    cfg = get_config("ling-flash-ep8-7l")
    experts = {label: tuple(rest) for label, *rest in smoke.EXPERT_SHAPES}
    per_step = (cfg.num_experts_per_tok, len(cfg.held_experts), cfg.num_experts,
                cfg.hidden_size, cfg.moe_intermediate_size)
    assert experts["ling-flash two positions"] == (2 * cfg.num_experts_per_tok, *per_step)
    assert experts["ling-flash step"] == (cfg.num_experts_per_tok, *per_step)
    causal = {row[0]: row[1:] for row in smoke.CAUSAL_SHAPES}
    (_, tokens, heads, width), kv_heads, v_width, band = causal["ling-flash mla 8192"]
    assert (heads, width, kv_heads, v_width, band) == (
        cfg.num_attention_heads, cfg.qk_head_dim, cfg.num_attention_heads, cfg.v_head_dim, None)
    with open(os.path.join(REPO_ROOT, "workflows", "rewrite-txt2img-ling-flash.json")) as fh:
        (text,) = [node["inputs"]["text"] for node in json.load(fh).values()
                   if node["class_type"] == "TextGenerate"]
    assert tokens == 1 + len(text.encode("utf-8"))
    assert smoke.KDA_STATES[1:] == (cfg.kda_layers, cfg.num_attention_heads, cfg.head_dim)


def test_the_experts_leg_has_the_ninth_models_row_at_its_own_numbers(smoke):
    """SDAR's row (PR 58): a pass's four positions, 8 experts each of the
    128 it holds all of, at 768 columns. Its prefill's call under the
    block mask has no row among `CAUSAL_SHAPES` (a row has no block):
    `test_flash_kernel_v5e.py` compiles it inside the model's prefill."""
    from comfyui_distributed_tpu.models.registry import get_config

    cfg = get_config("sdar-30b-a3b-pp8-6l")
    experts = {label: tuple(rest) for label, *rest in smoke.EXPERT_SHAPES}
    assert experts["sdar pass of four positions"] == (
        cfg.block_length * cfg.num_experts_per_tok, cfg.num_experts_per_tok,
        len(cfg.held_experts), cfg.num_experts, cfg.hidden_size, cfg.moe_intermediate_size)
    assert len(cfg.held_experts) == cfg.num_experts == 128


def test_the_legs_have_the_sixth_models_rows_at_its_own_numbers(smoke):
    """Nemotron-3-Nano's rows (PR 48): the `[2688, 1856]` / `[1856, 2688]`
    `expert_matvec` shapes of its experts without a gate, the 32 : 2
    causal row at the cell's prompt, and a Mamba-2 block's chunk and step
    over its 23 states."""
    from comfyui_distributed_tpu.models.registry import get_config

    cfg = get_config("nemotron3-nano-ep16-52l")
    (step,) = smoke.RELU2_EXPERT_SHAPES
    assert step == ("nemotron3-nano step", cfg.num_experts_per_tok, cfg.num_experts_per_tok,
                    len(cfg.held_experts), cfg.n_routed_experts, cfg.hidden_size,
                    cfg.moe_intermediate_size)
    causal = {row[0]: row[1:] for row in smoke.CAUSAL_SHAPES}
    (_, tokens, heads, width), kv_heads, v_width, band = causal["nemotron3-nano 32:2 8192"]
    assert (heads, width, kv_heads, v_width, band) == (
        cfg.num_attention_heads, cfg.head_dim, cfg.num_key_value_heads, cfg.head_dim, None)
    with open(os.path.join(REPO_ROOT, "workflows", "rewrite-txt2img-nemotron3-nano.json")) as fh:
        (text,) = [node["inputs"]["text"] for node in json.load(fh).values()
                   if node["class_type"] == "TextGenerate"]
    assert tokens == 1 + len(text.encode("utf-8"))
    assert smoke.SSD_SHAPES[0][1:] == (
        tokens, cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups, cfg.ssm_state_size,
        cfg.chunk_size, len(cfg.blocks_of("M")))


def test_the_second_mamba_row_is_a_part_of_the_granite_cells_prompt(smoke):
    """granite-4.0-h-micro's row (PR 55): a part of its document through
    one Mamba-2 layer at the registry's widths, one group and chunks of
    256, and the decode's step over its 36 states; the kernel's plan
    takes both cells' shapes at eight heads a grid step."""
    from comfyui_distributed_tpu.models.registry import get_config
    from comfyui_distributed_tpu.ops import ssd_chunk

    cfg = get_config("granite-4.0-h-micro")
    assert smoke.SSD_SHAPES[1] == (
        "granite-4.0-h-micro mamba-2", cfg.prefill_part, cfg.mamba_n_heads, cfg.mamba_d_head,
        cfg.mamba_n_groups, cfg.mamba_d_state, cfg.mamba_chunk_size,
        sum(kind == "mamba" for kind in cfg.layer_types))
    for _, _, heads, width, groups, n, chunk, _ in smoke.SSD_SHAPES:
        assert ssd_chunk.chunk_plan(heads, width, groups, n, chunk, 2) == ssd_chunk.MAX_HEADS
    assert set(smoke.SSD_SWEEP) >= {ssd_chunk.MAX_HEADS // 2, ssd_chunk.MAX_HEADS}


@pytest.mark.parametrize("which, forms", [(0, {"xla"}), (1, {"xla", "kernel"})])
def test_the_mamba_row_holds_the_chunked_scan_to_the_recurrence(smoke, capsys, which, forms):
    """The rehearsal's toy rows on the CPU: a length that is no whole
    number of chunks, bfloat16 operands against the float32 recurrence,
    and a step over the blocks' states; the second row's widths are on
    the lane tile, so it holds the kernel (interpreted) to the
    recurrence too, though the CPU's route stays the XLA form."""
    import jax

    if jax.default_backend() != "cpu":
        pytest.skip("the rehearsal's row is the CPU's")
    shape = smoke.REHEARSAL_SSD_SHAPES[which]
    assert smoke.ssd_row(True, *shape)
    (row,) = _result_lines(capsys.readouterr().out)
    assert row["ok"] and row["max_rel_diff_y"] < smoke.SSD_TOLERANCE
    assert row["max_rel_diff_state"] < smoke.SSD_TOLERANCE
    assert row["route"] == "xla" and forms == {"xla", "kernel"} & set(row)
    for form in forms:
        assert row[form]["max_rel_diff_y"] < smoke.SSD_TOLERANCE
    assert set(row["step"]) == {"blocks", "first_call_s", "us_a_block", "gb_per_s"}
    assert row["step"]["blocks"] == shape[-1]


def test_the_band_row_with_a_tail_is_at_the_tenth_models_sizes_and_its_routes_agree(
        smoke, capsys):
    """dots3-note-prev's row (PR 61): a part of the committed workflow's
    prompt over the 512 latents before it and its own, the sliding
    kind's heads and widths, the published window; and the rehearsal's
    toy row on the CPU: more keys than queries under a band, the kernel
    (interpreted) and XLA's blocks against the float32 form."""
    import jax

    from comfyui_distributed_tpu.models.registry import get_config

    cfg = get_config("dots3-note-prev-ep8-5l")
    kind = cfg.sliding
    label, q_shape, kv_heads, v_width, window, keys = smoke.BAND_TAIL_SHAPE
    assert q_shape == (1, cfg.prefill_part, kind.heads, kind.width) and kv_heads == kind.heads
    assert (v_width, window, keys) == (
        kind.value, cfg.sliding_window_size, cfg.prefill_part + cfg.tail_positions)
    with open(os.path.join(REPO_ROOT, "workflows", "longdoc-txt2img-dots3-note.json")) as fh:
        (node,) = [node["inputs"] for node in json.load(fh).values()
                   if node["class_type"] == "TextGenerate"]
    assert (1 + len(node["text"].encode("utf-8"))) % cfg.prefill_part == 0
    if jax.default_backend() != "cpu":
        pytest.skip("the rehearsal's row is the CPU's")
    assert smoke.causal_row(True, *smoke.REHEARSAL_BAND_TAIL_SHAPE)
    (row,) = _result_lines(capsys.readouterr().out)
    assert row["ok"] and (row["keys"], row["window"]) == (1409, 130)
    assert row["flash"]["entry"].startswith("flash-causal 1280x1409x256/128 pad")
    assert row["xla"]["entry"] == "xla-causal 1280x1409x256/128 w130 bq256 bf16"
    assert "sweep_ms" not in row  # the caps are swept on the chip alone


def test_the_latent_part_row_is_at_the_eleventh_models_sizes_and_its_routes_agree(
        smoke, capsys):
    """LongCat-Flash-Chat's row (PR 63): the last part of the committed
    workflow's prompt over every position so far, the heads one call
    takes at the published widths, no window; and the rehearsal's toy
    row on the CPU: four times the keys of the queries, the kernel
    (interpreted) and XLA's blocks against the float32 form."""
    import jax

    from comfyui_distributed_tpu.models.registry import get_config

    cfg = get_config("longcat-flash-chat-ep64-4l")
    label, q_shape, kv_heads, v_width, window, keys = smoke.LATENT_PART_SHAPE
    assert q_shape == (1, cfg.prefill_part, cfg.attention_heads_a_call, cfg.qk_head_dim)
    assert (kv_heads, v_width, window) == (cfg.attention_heads_a_call, cfg.v_head_dim, None)
    with open(os.path.join(REPO_ROOT, "workflows", "longdoc-txt2img-longcat-flash.json")) as fh:
        (node,) = [node["inputs"] for node in json.load(fh).values()
                   if node["class_type"] == "TextGenerate"]
    assert keys == 1 + len(node["text"].encode("utf-8")) == 4 * cfg.prefill_part
    if jax.default_backend() != "cpu":
        pytest.skip("the rehearsal's row is the CPU's")
    assert smoke.causal_row(True, *smoke.REHEARSAL_LATENT_PART_SHAPE)
    (row,) = _result_lines(capsys.readouterr().out)
    assert row["ok"] and (row["keys"], row["window"]) == (1024, None)
    assert row["flash"]["entry"].startswith("flash-causal 256x1024x192/128 g1")
    assert row["xla"]["entry"] == "xla-causal 256x1024x192/128 bq256 bf16"


def test_the_dsa_row_is_at_the_glm_cells_sizes_and_its_forms_agree(smoke, capsys):
    """The row's shape is the cell's: the last part of the committed
    workflow's prompt over caches of the request's length, the
    registry's widths; and the rehearsal's toy row on the CPU: the three
    selections (`lax.top_k`, the bisection's mask, the `dsa_select`
    kernel interpreted) one set at the cache's length and at the
    ladder's shorter rungs, the gathered forms (XLA's, and the
    `dsa_attend` kernel interpreted) the masked form's result from
    `lax.top_k`'s positions and from the kernel's ascending ones, a step
    in either form."""
    import jax

    from comfyui_distributed_tpu.models.registry import get_config

    cfg = get_config("glm-5.2-ep16-5l")
    with open(os.path.join(REPO_ROOT, "workflows", "longdoc-txt2img-glm-5.2.json")) as fh:
        (node,) = [node["inputs"] for node in json.load(fh).values()
                   if node["class_type"] == "TextGenerate"]
    tokens = 1 + len(node["text"].encode("utf-8"))
    assert smoke.DSA_SHAPE[1:] == (
        cfg.prefill_part, tokens + node["max_new_tokens"], cfg.index_topk,
        cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
        cfg.kv_lora_rank, cfg.index_n_heads, cfg.index_head_dim)
    assert tokens % cfg.prefill_part == 0
    if jax.default_backend() != "cpu":
        pytest.skip("the rehearsal's row is the CPU's")
    assert smoke.dsa_row(True, *smoke.REHEARSAL_DSA_SHAPE)
    (row,) = _result_lines(capsys.readouterr().out)
    assert row["ok"] and row["selections_equal"]
    for name in ("scores", "select_bisection", "gather_alone", "attend_masked"):
        assert set(row[name]) == {"first_call_s", "ms"}, name
    rungs = {f"ms_at_{length}" for length in (16, 32, 64)}     # under the toy cache's 72 rows
    for name in ("select_top_k", "select_kernel"):
        assert set(row[name]) == {"first_call_s", "ms"} | rungs, name
    for name in ("attend_gathered", "attend_kernel"):
        assert set(row[name]) == {
            "first_call_s", "ms", "ms_ascending", "max_rel_diff", "max_rel_diff_ascending"}, name
        assert row[name]["max_rel_diff"] < smoke.DSA_TOLERANCE
        assert row[name]["max_rel_diff_ascending"] < smoke.DSA_TOLERANCE
    assert set(row["step_masked"]) == set(row["step_gathered"]) == {"first_call_s", "us"}


def test_the_kda_row_agrees_with_itself_in_both_forms(smoke, capsys):
    """Two slots and a flipped bit against one slot and a select, at the
    rehearsal's toy size on the CPU: the same states, the same outputs."""
    import jax

    if jax.default_backend() != "cpu":
        pytest.skip("the rehearsal's row is the CPU's")
    assert smoke.kda_keep_row(True)
    (row,) = _result_lines(capsys.readouterr().out)
    assert row["ok"] and row["max_abs_diff"] < 1e-5
    assert set(row) >= {"slots", "select", "state_mb", "steps"}


def test_the_delta_rows_are_the_two_kda_cells_own_numbers(smoke):
    """(tokens, heads, width, chunk) of a prefill's delta-rule row are a
    registered configuration's own, at its cell's prompt."""
    from comfyui_distributed_tpu.models.registry import get_config

    ling, solar = get_config("ling-flash-ep8-7l"), get_config("solar-open2-ep8-4l")
    causal = {row[0]: row[1] for row in smoke.CAUSAL_SHAPES}
    tokens = causal["ling-flash mla 8192"][1]
    assert [row[1:] for row in smoke.KDA_DELTA_SHAPES] == [
        (tokens, ling.num_attention_heads, ling.head_dim, ling.kda_chunk),
        (tokens, solar.linear_num_heads, solar.linear_head_dim, solar.kda_chunk),
    ]


def test_the_delta_row_times_the_kernel_beside_the_scan(smoke, capsys):
    """The rehearsal's toy row on the CPU, the kernel interpreted: three
    heads in steps of three, two and one, a short last chunk, and the
    two forms within the row's tolerance of each other."""
    import jax

    if jax.default_backend() != "cpu":
        pytest.skip("the rehearsal's row is the CPU's")
    (shape,) = smoke.REHEARSAL_KDA_DELTA_SHAPES
    assert smoke.kda_delta_row(True, *shape)
    (row,) = _result_lines(capsys.readouterr().out)
    assert row["ok"] and row["heads_a_step"] == 3 and set(row["sweep_us"]) == {"1", "2"}
    assert row["max_rel_diff_o"] < smoke.KDA_DELTA_TOLERANCE
    assert row["max_rel_diff_state"] < smoke.KDA_DELTA_TOLERANCE
    assert set(row["kernel"]) == set(row["scan"]) == {"first_call_s", "ms", "us_a_head_chunk"}


def test_a_steps_routing_is_k_distinct_experts_a_token(smoke):
    sizes = smoke.step_sizes(7, steps=200, rows=16, k=8, held=16, experts=128)
    assert sizes.shape == (200, 16) and sizes.max() <= 2  # two tokens: at most two rows an expert
    # an eighth of the experts held: two of a step's 16 pairs on average
    assert 1.5 < sizes.sum(axis=1).mean() < 2.5
    assert (sizes.sum(axis=1) == 0).any()


@pytest.mark.parametrize("label, registry, tokens", [
    ("deepseek-v2 prefill", "deepseek-v2-ep4-5l", 2048),
    ("solar-open2 prefill", "solar-open2-ep8-4l", 8192),
    ("k-exaone prefill", "k-exaone-ep8-5l", 8192),
    ("ling-flash prefill", "ling-flash-ep8-7l", 8192),
    ("nemotron3-nano prefill", "nemotron3-nano-ep16-52l", 8192),
    ("glm-5.2 part", "glm-5.2-ep16-5l", None),
    ("sdar prefill", "sdar-30b-a3b-pp8-6l", 2048),
    ("dots3-note-prev part", "dots3-note-prev-ep8-5l", None),
    ("longcat-flash block", "longcat-flash-chat-ep64-4l", None),
])
def test_the_experts_leg_times_the_nine_models_prefill_shapes(smoke, label, registry, tokens):
    """A `PREFILL_EXPERT_SHAPES` row (PR 64) is a registered
    configuration's own numbers: the tokens a call of `expert_layer`
    sees (the cell's prompt, a part of it, or LongCat-Flash's block),
    the experts a token, those held, the router's width, the hidden size
    and the expert's width, whether it has a gate (Nemotron-H's has
    none, and stands in a scanned run's stack)."""
    from comfyui_distributed_tpu.models.registry import get_config

    cfg = get_config(registry)
    first = lambda *names: next(getattr(cfg, n) for n in names if hasattr(cfg, n))
    if tokens is None:
        tokens = first("expert_block", "prefill_part")
    no_gate = registry.startswith("nemotron")
    (row,) = [rest for name, *rest in smoke.PREFILL_EXPERT_SHAPES if name == label]
    assert tuple(row) == (
        tokens, first("num_experts_per_tok", "moe_topk"), len(cfg.held_experts),
        first("router_width", "n_routed_experts", "num_experts"), cfg.hidden_size,
        first("moe_intermediate_size", "expert_ffn_hidden_size"), not no_gate, no_gate)


def test_the_prefill_rows_routings_hold_the_rung_and_choose_k_distinct_experts(smoke):
    ids = smoke.prefill_routings(3, steps=4, tokens=512, k=3, held=4, experts=16, most=400)
    assert ids.shape == (4, 512, 3)
    assert all(len(set(token)) == 3 for token in ids[0])
    held = (ids < 4).sum(axis=(1, 2))
    assert (held <= 400).all() and held.min() > 300  # a quarter of 1,536 pairs is 384
