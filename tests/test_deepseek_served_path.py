"""The committed rewrite-then-txt2img workflow through the graph executor
on the tiny presets: a PNG a request, equal bytes for equal seeds, other
bytes for another seed, no program built by a third request; what
`node.TextGenerate` and its spans say; and that the benchmark's copies of
the workflow and of the reference are the committed files byte for byte."""

import json
import os

import pytest

from comfyui_distributed_tpu.graph import nodes_core
from comfyui_distributed_tpu.graph.executor import ExecutionContext, GraphExecutor
from comfyui_distributed_tpu.models.deepseek_v2 import ByteTokenizer
from comfyui_distributed_tpu.telemetry import get_metrics_registry, get_tracer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKFLOW = os.path.join(ROOT, "workflows", "rewrite-txt2img-deepseek-v2.json")
CONFIG = os.path.join(ROOT, "benchmark", "configs", "deepseek-v2.json")
WORKLOAD = os.path.join(
    ROOT, "benchmark", "workloads", "deepseek_v2_rewrite_txt2img_512.closed2.json")
NEW_TOKENS = 16


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
    """Seeds 42, 43 and 42 again through one executor: (PNG bytes, trace
    id, outputs, programs built) per request."""
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


def test_the_workflow_is_the_one_the_issue_describes():
    prompt = load(WORKFLOW)
    assert sorted(n["class_type"] for n in prompt.values()) == sorted([
        "CheckpointLoaderSimple", "TextGenerate", "CLIPLoader", "UNETLoader", "VAELoader",
        "CLIPTextEncode", "CLIPTextEncode", "EmptyLatentImage", "DistributedSeed", "KSampler",
        "VAEDecode", "DistributedCollector", "SaveImage"])
    kinds = by_kind(prompt)
    sampler, generate = kinds["KSampler"], kinds["TextGenerate"]
    assert (sampler["steps"], sampler["cfg"], sampler["sampler_name"], sampler["scheduler"],
            sampler["denoise"]) == (20, 7.0, "euler", "karras", 1.0)
    assert kinds["EmptyLatentImage"] == {"width": 512, "height": 512, "batch_size": 1}
    assert (generate["max_new_tokens"], generate["temperature"]) == (256, 1.0)
    # the language model's CLIP output feeds TextGenerate; its text feeds the positive prompt
    assert prompt[generate["clip"][0]]["class_type"] == "CheckpointLoaderSimple"
    assert generate["clip"][1] == 1
    positive = prompt[sampler["positive"][0]]
    assert prompt[positive["inputs"]["text"][0]]["class_type"] == "TextGenerate"
    assert prompt[positive["inputs"]["clip"][0]]["class_type"] == "CLIPLoader"
    assert prompt[sampler["model"][0]]["class_type"] == "UNETLoader"
    assert prompt[sampler["negative"][0]]["inputs"]["text"] == "blurry, low quality"
    # one seed for the text and for the image
    assert prompt[generate["seed"][0]]["class_type"] == "DistributedSeed"
    assert generate["seed"] == sampler["seed"]
    assert (kinds["UNETLoader"]["unet_name"], kinds["CLIPLoader"]["clip_name"],
            kinds["VAELoader"]["vae_name"]) == ("sd15", "clip-l", "vae-sd")
    assert kinds["CheckpointLoaderSimple"]["ckpt_name"] == load(CONFIG)["registry_name"]


def test_the_prompt_is_exactly_2048_tokens_of_the_stand_in_tokenizer():
    text = by_kind(load(WORKFLOW))["TextGenerate"]["text"]
    assert len(ByteTokenizer().encode(text)) == 2048
    assert text.isascii() and text.endswith("Prompt: ")


def test_a_request_gives_a_png_and_the_text_that_was_drawn(served):
    png, _, outputs, _ = served[0]
    assert png[:8] == b"\x89PNG\r\n\x1a\n"
    texts = [i["ui"]["text"] for r in outputs.values() for i in r
             if isinstance(i, dict) and "text" in i.get("ui", {})]
    assert len(texts) == 1 and len(texts[0]) == 1
    words = texts[0][0].split()
    assert 0 < len(words) <= NEW_TOKENS and texts[0][0] == texts[0][0].strip()


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
    (node,) = spans_named(served[2][1], "node.TextGenerate")
    assert "compiles" not in node["attrs"]


def test_the_language_model_runs_again_for_a_seed_it_has_seen(served):
    # an output node: the executor's node cache never answers it
    for _, spans, _, _ in served:
        assert len(spans_named(spans, "node.TextGenerate")) == 1
        assert len(spans_named(spans, "lm.prefill")) == 1


def test_node_textgenerate_says_what_ran(served):
    (node,) = spans_named(served[1][1], "node.TextGenerate")
    attrs = node["attrs"]
    assert (attrs["prompt_tokens"], attrs["new_tokens"]) == (2048, NEW_TOKENS)
    assert (attrs["layers"], attrs["experts_held"], attrs["experts_total"]) == (3, 4, 16)
    # float32 on the CPU: 3 layers x (2,048 + 16) positions x (24 + 8) values x 4 bytes
    assert attrs["cache_bytes"] == 3 * (2048 + NEW_TOKENS) * 32 * 4
    # two expert layers, three experts a token
    assert attrs["prefill_routed_pairs"] == 2048 * 2 * 3
    assert attrs["decode_routed_pairs"] == NEW_TOKENS * 2 * 3
    for phase in ("prefill", "decode"):
        held = attrs[f"{phase}_routed_pairs_held"]
        assert 0 < held < attrs[f"{phase}_routed_pairs"]
        # the fullest of 2 layers x 4 held experts
        assert held / 8 <= attrs[f"{phase}_expert_load_max"] <= held
    # the rows the grouped products ran over: a rung a layer of (1536, 3072,
    # 6144) for the prefill's 6,144 pairs, every pair of a decode step's 3
    assert attrs["prefill_expert_rows"] in {2 * 1536, 1536 + 3072, 2 * 3072}
    assert attrs["prefill_routed_pairs_held"] <= attrs["prefill_expert_rows"]
    assert attrs["decode_expert_rows"] == attrs["decode_routed_pairs"]
    assert attrs["decode_expert_route"] == "xla"  # off a TPU


def test_the_spans_under_the_node_are_dispatch_one_wait_and_detokenize(served):
    spans = served[1][1]
    (node,) = spans_named(spans, "node.TextGenerate")
    below = [s["name"] for s in spans if s["parent_id"] == node["span_id"]]
    assert below == ["lm.prefill", "device.run", "lm.decode", "device.run", "device.wait",
                     "lm.detokenize"]
    assert [s["attrs"]["program"] for s in spans_named(spans, "device.run")
            if s["parent_id"] == node["span_id"]] == ["prefill", "decode"]
    (wait,) = [s for s in spans_named(spans, "device.wait") if s["parent_id"] == node["span_id"]]
    # the ids and the two counts of pairs per held expert, in one read-back
    assert wait["attrs"]["bytes"] == 4 * (NEW_TOKENS + 2 * 2 * 4)


def test_only_the_request_that_traced_the_programs_says_which_attention(served):
    (first,) = spans_named(served[0][1], "node.TextGenerate")
    assert first["attrs"]["attention"] == "xla-causal 2048x2048x24/16 bq256 f32"
    (second,) = spans_named(served[1][1], "node.TextGenerate")
    assert "attention" not in second["attrs"]


def test_the_loader_reports_the_lm_part(served):
    (loader,) = spans_named(served[0][1], "node.CheckpointLoaderSimple")
    assert loader["attrs"]["lm_bytes"] == 4 * loader["attrs"]["lm_params"] > 0
    assert not any(key.startswith(("unet_", "vae_", "te_")) for key in loader["attrs"])
    assert spans_named(served[1][1], "node.CheckpointLoaderSimple") == []  # cached


def test_tokens_are_counted_by_phase(graph, tmp_path, monkeypatch):
    monkeypatch.setenv("CDT_OUTPUT_DIR", str(tmp_path))
    GraphExecutor(ExecutionContext()).execute(graph)
    counter = get_metrics_registry().counter(
        "cdt_lm_tokens_total", "", ("phase",))
    assert counter.value(phase="prefill") == 2048
    assert counter.value(phase="decode") == NEW_TOKENS


def test_textgenerate_refuses_a_bundle_without_a_language_model():
    from comfyui_distributed_tpu.graph.nodes_text import TextGenerate
    from comfyui_distributed_tpu.models import pipeline as pl

    bundle = pl.PipelineBundle(model_name="tiny-unet", unet=None, vae=None, text_encoder=None,
                               params={}, tokenizer=None)
    with pytest.raises(ValueError, match="holds none"):
        TextGenerate().generate(bundle, "a cat", 1)


@pytest.mark.parametrize("node, call", [
    ("KSampler", lambda b: nodes_core.KSampler().sample(
        b, 1, 2, 7.0, "euler", "karras", None, None, {"samples": None})),
    ("VAEDecode", lambda b: nodes_core.VAEDecode().decode({"samples": None}, b)),
    ("CLIPTextEncode", lambda b: nodes_core.CLIPTextEncode().encode("a cat", b)),
])
def test_a_node_given_the_language_models_bundle_says_which_part_is_missing(node, call):
    from comfyui_distributed_tpu.models import pipeline as pl

    bundle = pl.load_pipeline("tiny-deepseek-v2")
    with pytest.raises(ValueError, match=f"{node} needs a bundle with a .* holds lm"):
        call(bundle)


@pytest.mark.parametrize("mine, theirs", [
    ("benchmark/workflows/rewrite-txt2img-deepseek-v2.json",
     "workflows/rewrite-txt2img-deepseek-v2.json"),
    ("benchmark/reference/deepseek_v2.py", "comfyui_distributed_tpu/reference/deepseek_v2.py"),
])
def test_the_benchmarks_copies_are_the_committed_files(mine, theirs):
    with open(os.path.join(ROOT, mine), "rb") as a, open(os.path.join(ROOT, theirs), "rb") as b:
        assert a.read() == b.read()


def test_the_reference_imports_nothing_of_the_system():
    with open(os.path.join(ROOT, "comfyui_distributed_tpu/reference/deepseek_v2.py"),
              encoding="utf-8") as fh:
        imports = [line for line in fh if line.startswith(("import ", "from "))]
    assert sorted(imports) == sorted([
        "from __future__ import annotations\n", "import dataclasses\n", "import math\n",
        "import jax\n", "import jax.numpy as jnp\n", "import numpy as np\n"])


PUBLISHED = {
    "hidden_size": 5120, "intermediate_size": 12288, "moe_intermediate_size": 1536,
    "kv_lora_rank": 512, "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "num_attention_heads": 128, "num_key_value_heads": 128,
    "n_shared_experts": 2, "num_experts_per_tok": 6, "n_group": 8, "topk_group": 3,
    "routed_scaling_factor": 16, "first_k_dense_replace": 1, "rms_norm_eps": 1e-6,
    "rope_theta": 10000, "max_position_embeddings": 163840, "norm_topk_prob": False,
    "topk_method": "group_limited_greedy", "scoring_func": "softmax",
}


def test_the_configuration_keeps_every_published_width_and_states_its_cut():
    config = load(CONFIG)
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    assert config["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707, "mscale_all_dim": 0.707,
        "original_max_position_embeddings": 4096, "type": "yarn"}
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert config["published"] == {
        "num_hidden_layers": 60, "n_routed_experts": 160, "vocab_size": 102400}
    assert (config["num_hidden_layers"], config["n_routed_experts"], config["vocab_size"]) == (
        5, 40, 25600)
    assert config["reference"] == "benchmark/reference/deepseek_v2.py"
    assert "rank 0" in config["deployment"] and "four chips" in config["deployment"]
    assert config["assumed"] and config["parity"]["tolerance_rel_l2_median"] > 0


def test_the_registry_entry_is_the_configuration_file():
    from comfyui_distributed_tpu.models.registry import get_config

    config, cfg = load(CONFIG), get_config(load(CONFIG)["registry_name"])
    for key in PUBLISHED:
        if hasattr(cfg, key):
            assert getattr(cfg, key) == config[key], key
    assert cfg.num_hidden_layers == config["num_hidden_layers"]
    assert len(cfg.held_experts) == config["n_routed_experts"]
    assert cfg.n_routed_experts == config["published"]["n_routed_experts"]
    assert cfg.vocab_held == config["vocab_size"]
    scaling = config["rope_scaling"]
    assert (cfg.rope_factor, cfg.rope_beta_fast, cfg.rope_beta_slow, cfg.rope_mscale,
            cfg.rope_mscale_all_dim, cfg.rope_original_max_position_embeddings) == (
        scaling["factor"], scaling["beta_fast"], scaling["beta_slow"], scaling["mscale"],
        scaling["mscale_all_dim"], scaling["original_max_position_embeddings"])
