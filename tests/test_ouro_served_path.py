"""The committed rewrite-then-txt2img workflow with Ouro in front, through
the graph executor on the tiny presets: a PNG a request, equal bytes for
equal seeds, no program built by a third request; what `node.TextGenerate`
says of a looped model and what it counts; the one contract both language
models meet; and that the benchmark's copies and its configuration file are
what the issue describes."""

import json
import os

import pytest

from comfyui_distributed_tpu.graph.executor import ExecutionContext, GraphExecutor
from comfyui_distributed_tpu.models.lm_common import ByteTokenizer
from comfyui_distributed_tpu.telemetry import get_metrics_registry, get_tracer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKFLOW = os.path.join(ROOT, "workflows", "rewrite-txt2img-ouro-2.6b.json")
DEEPSEEK_WORKFLOW = os.path.join(ROOT, "workflows", "rewrite-txt2img-deepseek-v2.json")
CONFIG = os.path.join(ROOT, "benchmark", "configs", "ouro-2.6b.json")
WORKLOAD = os.path.join(
    ROOT, "benchmark", "workloads", "ouro_2_6b_rewrite_txt2img_512.closed2.json")
CELL = "ouro_2_6b_rewrite_txt2img_512.closed2"
NEW_TOKENS = 8
# tiny-ouro: 4 passes x 3 layers, 4 heads of 16
PASSES, LAYERS, HEADS, HEAD_DIM = 4, 3, 4, 16


def load(path):
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def by_kind(prompt):
    return {n["class_type"]: n["inputs"] for n in prompt.values()}


@pytest.fixture(scope="module")
def graph():
    """The committed graph with the cell's own rehearsal edits."""
    prompt = load(WORKFLOW)
    for edit in load(WORKLOAD)["rehearsal"]["set"]:
        for node in prompt.values():
            if node["class_type"] == edit["class_type"]:
                node["inputs"][edit["input"]] = edit["value"]
    return prompt


@pytest.fixture(scope="module")
def served(graph, tmp_path_factory):
    """Seeds 42, 43 and 42 again through one executor: (PNG bytes, spans,
    outputs, programs built) per request."""
    from comfyui_distributed_tpu.telemetry import runtime

    runtime.install_jax_monitoring()
    out_dir = tmp_path_factory.mktemp("out")
    os.environ["CDT_OUTPUT_DIR"] = str(out_dir)
    executor, tracer, runs = GraphExecutor(ExecutionContext()), get_tracer(), []
    try:
        for seed in (42, 43, 42):
            for node in graph.values():
                if node["class_type"] == "DistributedSeed":
                    node["inputs"]["seed"] = seed
            before = runtime.tallies()["compiles"]
            with tracer.span("execute_prompt") as root:
                outputs = executor.execute(graph)
            built = runtime.tallies()["compiles"] - before
            (name,) = [i["ui"]["images"] for r in outputs.values() for i in r
                       if isinstance(i, dict) and "images" in i.get("ui", {})][0]
            with open(os.path.join(out_dir, name), "rb") as fh:
                runs.append((fh.read(), tracer.spans(root.trace_id), outputs, built))
    finally:
        os.environ.pop("CDT_OUTPUT_DIR", None)
    return runs


def spans_named(spans, name):
    return [s for s in spans if s["name"] == name]


def test_the_workflow_is_the_deepseek_one_with_another_model_and_64_tokens():
    mine, theirs = load(WORKFLOW), load(DEEPSEEK_WORKFLOW)
    assert mine.keys() == theirs.keys()
    differing = {
        (node, key): (mine[node]["inputs"][key], theirs[node]["inputs"][key])
        for node in mine for key in mine[node]["inputs"]
        if mine[node]["inputs"][key] != theirs[node]["inputs"][key]
    }
    assert sorted(differing.values(), key=str) == sorted([
        ("ouro-2.6b", "deepseek-v2-ep4-5l"), (64, 256),
        ("rewrite-txt2img-ouro", "rewrite-txt2img"),
    ], key=str)
    assert by_kind(mine)["CheckpointLoaderSimple"]["ckpt_name"] == load(CONFIG)["registry_name"]
    text = by_kind(mine)["TextGenerate"]["text"]
    assert len(ByteTokenizer().encode(text)) == 2048


def test_a_request_gives_a_png_and_the_text_that_was_drawn(served):
    png, _, outputs, _ = served[0]
    assert png[:8] == b"\x89PNG\r\n\x1a\n"
    texts = [i["ui"]["text"] for r in outputs.values() for i in r
             if isinstance(i, dict) and "text" in i.get("ui", {})]
    assert len(texts) == 1 and len(texts[0]) == 1
    assert 0 < len(texts[0][0].split()) <= NEW_TOKENS


def test_history_outputs_carry_the_text_beside_the_images(served):
    from comfyui_distributed_tpu.api.server import _jsonable_outputs

    entries = _jsonable_outputs(served[0][2])
    assert sorted(key for entry in entries.values() for key in entry) == ["images", "text"]
    json.dumps(entries)


def test_equal_seeds_give_equal_bytes_and_another_seed_other_bytes(served):
    assert served[0][0] == served[2][0]
    assert served[0][0] != served[1][0]


def test_the_third_request_builds_no_program(served):
    assert served[0][3] > 0
    assert served[2][3] == 0


def test_node_textgenerate_says_what_a_looped_model_ran(served):
    (node,) = spans_named(served[1][1], "node.TextGenerate")
    attrs = node["attrs"]
    assert (attrs["prompt_tokens"], attrs["new_tokens"]) == (2048, NEW_TOKENS)
    assert (attrs["ut_steps"], attrs["layers"], attrs["cache_slots"]) == (
        PASSES, LAYERS, PASSES * LAYERS)
    # float32 on the CPU: a key and a value of every head in every slot
    assert attrs["cache_bytes"] == (
        PASSES * LAYERS * 2 * HEADS * (2048 + NEW_TOKENS) * HEAD_DIM * 4)
    assert attrs["prefill_layer_passes"] == 2048 * PASSES * LAYERS
    assert attrs["decode_layer_passes"] == NEW_TOKENS * PASSES * LAYERS
    mass = [attrs[f"exit_mass_{step}"] for step in range(1, PASSES + 1)]
    assert all(m > 0 for m in mass)
    assert sum(mass) == pytest.approx(2048 + NEW_TOKENS, rel=1e-4)
    # nothing of a mixture of experts
    assert not any("expert" in key or "routed" in key for key in attrs)


def test_the_spans_under_the_node_are_dispatch_one_wait_and_detokenize(served):
    spans = served[1][1]
    (node,) = spans_named(spans, "node.TextGenerate")
    below = [s["name"] for s in spans if s["parent_id"] == node["span_id"]]
    assert below == ["lm.prefill", "device.run", "lm.decode", "device.run", "device.wait",
                     "lm.detokenize"]
    assert [s["attrs"]["program"] for s in spans_named(spans, "device.run")
            if s["parent_id"] == node["span_id"]] == ["prefill", "decode"]
    (wait,) = [s for s in spans_named(spans, "device.wait") if s["parent_id"] == node["span_id"]]
    # the ids and the two exit distributions, in one read-back
    assert wait["attrs"]["bytes"] == 4 * (NEW_TOKENS + 2 * PASSES)


def test_only_the_request_that_traced_the_programs_says_which_attention(served):
    (first,) = spans_named(served[0][1], "node.TextGenerate")
    # the decode's single-query attention over a slot (`ops/decode_attention`:
    # the einsum form off a TPU), then the prefill's
    assert first["attrs"]["attention"] == (
        f"decode-xla {HEADS}x{2048 + NEW_TOKENS}x{HEAD_DIM}, xla-causal 2048x2048x16/16 bq256 f32")
    (second,) = spans_named(served[1][1], "node.TextGenerate")
    assert "attention" not in second["attrs"]


def test_tokens_and_layer_passes_are_counted_by_phase(graph, tmp_path, monkeypatch):
    monkeypatch.setenv("CDT_OUTPUT_DIR", str(tmp_path))
    registry = get_metrics_registry()
    tokens = registry.counter("cdt_lm_tokens_total", "", ("phase",))
    passes = registry.counter("cdt_lm_layer_passes_total", "", ("phase",))
    before = {(c, p): c.value(phase=p) for c in (tokens, passes) for p in ("prefill", "decode")}
    GraphExecutor(ExecutionContext()).execute(graph)
    grew = {key: key[0].value(phase=key[1]) - was for key, was in before.items()}
    assert grew[(tokens, "prefill")] == 2048 and grew[(tokens, "decode")] == NEW_TOKENS
    assert grew[(passes, "prefill")] == 2048 * PASSES * LAYERS
    assert grew[(passes, "decode")] == NEW_TOKENS * PASSES * LAYERS


def test_a_model_that_walks_its_layers_once_counts_tokens_times_layers():
    from comfyui_distributed_tpu.models.registry import create_model

    assert create_model("tiny-deepseek-v2").layer_passes == 3
    assert create_model("deepseek-v2-ep4-5l").layer_passes == 5
    assert create_model("ouro-2.6b").layer_passes == 192


@pytest.mark.parametrize("name", ["tiny-ouro", "tiny-deepseek-v2", "ouro-2.6b",
                                  "deepseek-v2-ep4-5l"])
def test_every_language_model_meets_the_one_contract(name):
    """What `TextGenerate` asks of a bundle's `lm` part (`lm_common`)."""
    from comfyui_distributed_tpu.models.registry import create_model, model_family

    assert model_family(name) == "lm"
    lm = create_model(name)
    for attribute in ("cfg", "tokenizer", "layer_passes", "init", "prefill", "decode",
                      "read_back", "describe", "report"):
        assert hasattr(lm, attribute), attribute
    described = lm.describe(128)
    assert described["layers"] == lm.cfg.num_hidden_layers
    assert described["cache_bytes"] > 0 and isinstance(described["cache_bytes"], int)
    assert isinstance(lm.tokenizer, ByteTokenizer)


def test_the_byte_tokenizer_lives_in_one_module_both_models_import():
    from comfyui_distributed_tpu.models import deepseek_v2, lm_common, ouro

    assert deepseek_v2.ByteTokenizer is lm_common.ByteTokenizer is ouro.ByteTokenizer
    for shared in ("rms_norm", "swiglu", "apply_rope", "sample"):
        assert getattr(deepseek_v2, shared) is getattr(lm_common, shared) is getattr(ouro, shared)


@pytest.mark.parametrize("mine, theirs", [
    ("benchmark/workflows/rewrite-txt2img-ouro-2.6b.json",
     "workflows/rewrite-txt2img-ouro-2.6b.json"),
    ("benchmark/reference/ouro.py", "comfyui_distributed_tpu/reference/ouro.py"),
])
def test_the_benchmarks_copies_are_the_committed_files(mine, theirs):
    with open(os.path.join(ROOT, mine), "rb") as a, open(os.path.join(ROOT, theirs), "rb") as b:
        assert a.read() == b.read()


def test_the_reference_imports_nothing_of_the_system():
    with open(os.path.join(ROOT, "comfyui_distributed_tpu/reference/ouro.py"),
              encoding="utf-8") as fh:
        imports = [line for line in fh if line.startswith(("import ", "from "))]
    assert sorted(imports) == sorted([
        "from __future__ import annotations\n", "import dataclasses\n",
        "import jax\n", "import jax.numpy as jnp\n", "import numpy as np\n"])


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    with open(path, encoding="utf-8") as fh:
        return next(row for row in map(json.loads, fh) if row["name"] == "Ouro-2.6B")


PUBLISHED = {
    "hidden_size": 2048, "intermediate_size": 5632, "num_hidden_layers": 48,
    "num_attention_heads": 16, "num_key_value_heads": 16, "head_dim": 128,
    "total_ut_steps": 4, "early_exit_threshold": 1, "vocab_size": 49152,
    "rms_norm_eps": 1e-6, "rope_theta": 1000000, "max_position_embeddings": 65536,
    "tie_word_embeddings": False, "hidden_act": "silu", "model_type": "ouro",
    "rope_scaling": None, "sliding_window": None, "use_sliding_window": False,
}


def test_the_configuration_keeps_every_published_value_and_cuts_nothing():
    config = load(CONFIG)
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    assert config["layer_types"] == ["full_attention"] * 48
    assert config["reduced"] == []
    assert config["source"] == "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
    assert config["reference"] == "benchmark/reference/ouro.py"
    assert config["as_run"]["parameters"] == {"lm": 2667974657}
    assert config["as_run"]["cache_bytes_per_token"] == 1572864
    assumed = " ".join(config["assumed"])
    for word in ("seeded random", "stand-in", "batch is 1", "four RMS norms", "final norm",
                 "bias", "never exit early", "system prompt"):
        assert word in assumed, word
    limits = config["parity"]
    assert 0 < limits["tolerance_rel_l2_median"] <= limits["tolerance_rel_l2_max"] < 0.3
    assert 0 < limits["tolerance_exit_abs_max"] < 0.1


def test_the_configuration_file_is_the_catalogs_row():
    row, config = catalog_row(), load(CONFIG)
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert config[key] == value, key


def test_the_registry_entry_is_the_configuration_file():
    from comfyui_distributed_tpu.models.registry import get_config

    config, cfg = load(CONFIG), get_config(load(CONFIG)["registry_name"])
    for key in PUBLISHED:
        if hasattr(cfg, key):
            assert getattr(cfg, key) == config[key], key
    assert (cfg.num_hidden_layers, cfg.total_ut_steps, cfg.vocab_size) == (48, 4, 49152)


def test_the_manifest_has_the_cell_with_the_issues_traffic_and_lists():
    manifest = load(os.path.join(ROOT, "BENCHMARK.json"))
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("ouro-2.6b", "closed2", 1)
    (config,) = [c for c in manifest["configs"] if c["name"] == "ouro-2.6b"]
    assert config["reduced"] == []
    listed = {m["name"] for m in manifest["per_layer"] + manifest["end_to_end"]
              if CELL in m.get("workloads", [])}
    assert listed == {
        "images_per_s", "execute_ms.txt2img", "host_ms.txt2img", "device_idle_pct.txt2img",
        "decode_dispatch_ms.txt2img", "generate_ms.lm", "decode_ms_per_token.lm",
        "lm_share_pct.rewrite", "cache_gb.lm", "layer_passes_per_token.lm",
        # PR 36: what reads the cell's `device.run` spans
        "sampler_device_ms.txt2img", "vae_device_ms.txt2img", "prefill_device_ms.lm",
        "decode_device_ms_per_token.lm", "decode_hbm_roofline_pct.lm", "prefill_mxu_peak_pct.lm",
        "device_idle_in_pct.txt2img", "between_jobs_ms.txt2img"}
    for name in ("cache_gb.lm", "layer_passes_per_token.lm"):
        (metric,) = [m for m in manifest["per_layer"] if m["name"] == name]
        # later cells are appended behind
        assert metric["workloads"][:2] == ["deepseek_v2_rewrite_txt2img_512.closed2", CELL]
        assert (metric["moves"], metric["source"]) == ("images_per_s", "program_counter")
    work = load(WORKLOAD)
    assert work["seed_nodes"] == ["DistributedSeed"]
    assert work["compute_nodes"] == ["TextGenerate", "KSampler"]
    assert work["rate"] == {"metric": "images_per_s", "units_per_job": 1}
    assert work["trace"] == {"start_s": 5, "slice_s": 12}


@pytest.mark.parametrize("given, loaded", [
    ("ouro-2.6b", "ouro-2.6b"), ("sd15.safetensors", "sd15"), ("tiny-unet", "tiny-unet"),
    ("v1-5-pruned.ckpt", "v1-5-pruned"),
])
def test_the_loader_takes_a_registry_name_with_a_dot_whole(given, loaded, monkeypatch):
    """`ouro-2.6b` is no file name with the extension `.6b`."""
    from comfyui_distributed_tpu.graph import nodes_core

    seen = []
    monkeypatch.setattr(nodes_core, "_get_bundle", lambda context, name: seen.append(name))
    monkeypatch.setattr(nodes_core, "_annotate_load", lambda bundle: None)
    nodes_core.CheckpointLoaderSimple().load(given)
    assert seen == [loaded]
