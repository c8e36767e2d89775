"""The committed rewrite-then-txt2img workflows, one a language model,
through the graph executor on the tiny presets. One table (`MODELS`) has a
row a model; the tests every model shares run once a row: a PNG a request,
equal bytes for equal seeds, no program built by a third request, the
spans under `node.TextGenerate`, exactly the attributes the row says (their
names and, where the shapes give them, their values: written from the
tree before the node stopped merging `describe` and `report` itself), what the node counts,
the workflow, the configuration file, the registry entry, the reference
and the benchmark's copies. What only one model has stays a test of its
own below. The one contract (`models/lm_common`) is held against every
registry entry of family `lm`, and the shared loops (`decode_loop`,
`draft_loop`, `prefill_in_parts`) against a Python loop over each model's
own step or steps and by themselves under arithmetic ones.

A `model_config` PR adds a row here, and edits no other row."""

import dataclasses
import functools
import importlib.util
import json
import os
from typing import Callable

import pytest

from comfyui_distributed_tpu.graph import nodes_core
from comfyui_distributed_tpu.graph.executor import ExecutionContext, GraphExecutor
from comfyui_distributed_tpu.models.lm_common import ByteTokenizer
from comfyui_distributed_tpu.models.registry import MODEL_REGISTRY
from comfyui_distributed_tpu.telemetry import get_metrics_registry, get_tracer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUILD_TALLIES = {"compiles", "compile_s", "cache_hits", "cache_misses", "trace_s", "lower_s",
                 "cache_fetch_s"}
ROUTING = {f"{phase}_{what}" for phase in ("prefill", "decode") for what in (
    "routed_pairs", "routed_pairs_held", "expert_load_max", "expert_rows", "expert_route")}
# the kernel metric of the cells whose prefill's grouped products take `ops/grouped_matmul` (PR 64)
GROUPED = "grouped_matmul_device_pct.lm"
# the metrics every language-model cell lists in BENCHMARK.json
LM_METRICS = {
    "images_per_s", "execute_ms.txt2img", "host_ms.txt2img", "device_idle_pct.txt2img",
    "decode_dispatch_ms.txt2img", "generate_ms.lm", "decode_ms_per_token.lm",
    "lm_share_pct.rewrite", "cache_gb.lm", "layer_passes_per_token.lm",
    "sampler_device_ms.txt2img", "vae_device_ms.txt2img", "prefill_device_ms.lm",
    "decode_device_ms_per_token.lm", "decode_hbm_roofline_pct.lm", "prefill_mxu_peak_pct.lm",
    "device_idle_in_pct.txt2img", "between_jobs_ms.txt2img",
    # the job's record (telemetry/job_record.py), every cell with a served job
    "job_waiting_ms.txt2img", "job_tail_ms.txt2img", "device_starved_pct.txt2img"}
PLAIN_IMPORTS = ["from __future__ import annotations\n", "import dataclasses\n", "import jax\n",
                 "import jax.numpy as jnp\n", "import numpy as np\n"]


def load(path):
    with open(os.path.join(ROOT, path), encoding="utf-8") as fh:
        return json.load(fh)


def by_kind(prompt):
    return {n["class_type"]: n["inputs"] for n in prompt.values()}


def spans_named(spans, name):
    return [s for s in spans if s["name"] == name]


# --- what only one model's row can say ---------------------------------------
# `drawn(attrs)`: the attributes whose values the drawn tokens and the seeded
# weights decide; `published(config)` and `entry(cfg, config)`: the parts of the
# configuration file and of the registry entry no other model has;
# `workflow(mine, deepseeks)`: how the committed graph differs from the first.


def differing(mine, theirs):
    assert mine.keys() == theirs.keys()
    return {(mine[node]["class_type"], key)
            for node in mine for key in mine[node]["inputs"]
            if mine[node]["inputs"][key] != theirs[node]["inputs"].get(key)}


def held_share(attrs, layers, held, low=0.0, high=1.0, decode_layers=None):
    """The routing's drawn attributes, `layers` expert layers of `held`
    held experts: a share of the pairs falls on them, the fullest holds
    its part, and the rows run cover the held pairs."""
    for phase, rows in (("prefill", layers), ("decode", decode_layers or layers)):
        pairs, on_held = attrs[f"{phase}_routed_pairs"], attrs[f"{phase}_routed_pairs_held"]
        assert 0 <= on_held < pairs
        assert on_held / (rows * held) <= attrs[f"{phase}_expert_load_max"] <= on_held
    assert low < attrs["prefill_routed_pairs_held"] / attrs["prefill_routed_pairs"] < high
    assert attrs["prefill_routed_pairs_held"] <= attrs["prefill_expert_rows"]
    assert attrs["prefill_expert_rows"] < attrs["prefill_routed_pairs"]
    assert attrs["prefill_expert_rows"] % 256 == 0  # a rung a layer


def deepseek_drawn(attrs):
    held_share(attrs, layers=2, held=4)
    # a rung a layer of (1536, 3072, 6144) for the prefill's 6,144 pairs
    assert attrs["prefill_expert_rows"] in {2 * 1536, 1536 + 3072, 2 * 3072}


def deepseek_workflow(mine, _):
    assert sorted(n["class_type"] for n in mine.values()) == sorted([
        "CheckpointLoaderSimple", "TextGenerate", "CLIPLoader", "UNETLoader", "VAELoader",
        "CLIPTextEncode", "CLIPTextEncode", "EmptyLatentImage", "DistributedSeed", "KSampler",
        "VAEDecode", "DistributedCollector", "SaveImage"])
    kinds = by_kind(mine)
    sampler, generate = kinds["KSampler"], kinds["TextGenerate"]
    assert (sampler["steps"], sampler["cfg"], sampler["sampler_name"], sampler["scheduler"],
            sampler["denoise"]) == (20, 7.0, "euler", "karras", 1.0)
    assert kinds["EmptyLatentImage"] == {"width": 512, "height": 512, "batch_size": 1}
    assert (generate["max_new_tokens"], generate["temperature"]) == (256, 1.0)
    # the language model's CLIP output feeds TextGenerate; its text feeds the positive prompt
    assert mine[generate["clip"][0]]["class_type"] == "CheckpointLoaderSimple"
    assert generate["clip"][1] == 1
    positive = mine[sampler["positive"][0]]
    assert mine[positive["inputs"]["text"][0]]["class_type"] == "TextGenerate"
    assert mine[positive["inputs"]["clip"][0]]["class_type"] == "CLIPLoader"
    assert mine[sampler["model"][0]]["class_type"] == "UNETLoader"
    assert mine[sampler["negative"][0]]["inputs"]["text"] == "blurry, low quality"
    # one seed for the text and for the image
    assert mine[generate["seed"][0]]["class_type"] == "DistributedSeed"
    assert generate["seed"] == sampler["seed"]
    assert (kinds["UNETLoader"]["unet_name"], kinds["CLIPLoader"]["clip_name"],
            kinds["VAELoader"]["vae_name"]) == ("sd15", "clip-l", "vae-sd")
    assert generate["text"].isascii() and generate["text"].endswith("Prompt: ")


def deepseek_published(config):
    assert config["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707, "mscale_all_dim": 0.707,
        "original_max_position_embeddings": 4096, "type": "yarn"}
    assert "rank 0" in config["deployment"] and "four chips" in config["deployment"]
    assert config["assumed"] and config["parity"]["tolerance_rel_l2_median"] > 0


def deepseek_entry(cfg, config):
    assert len(cfg.held_experts) == config["n_routed_experts"]
    assert cfg.n_routed_experts == config["published"]["n_routed_experts"]
    assert cfg.vocab_held == config["vocab_size"]
    scaling = config["rope_scaling"]
    assert (cfg.rope_factor, cfg.rope_beta_fast, cfg.rope_beta_slow, cfg.rope_mscale,
            cfg.rope_mscale_all_dim, cfg.rope_original_max_position_embeddings) == (
        scaling["factor"], scaling["beta_fast"], scaling["beta_slow"], scaling["mscale"],
        scaling["mscale_all_dim"], scaling["original_max_position_embeddings"])


def ouro_drawn(attrs):
    mass = [attrs[f"exit_mass_{step}"] for step in range(1, 5)]
    assert all(m > 0 for m in mass)
    assert sum(mass) == pytest.approx(attrs["prompt_tokens"] + attrs["new_tokens"], rel=1e-4)


def ouro_workflow(mine, theirs):
    assert differing(mine, theirs) == {
        ("CheckpointLoaderSimple", "ckpt_name"), ("TextGenerate", "max_new_tokens"),
        ("SaveImage", "filename_prefix")}
    assert by_kind(mine)["TextGenerate"]["max_new_tokens"] == 64


def ouro_published(config):
    assert config["layer_types"] == ["full_attention"] * 48
    assert config["as_run"]["parameters"] == {"lm": 2667974657}
    assert config["as_run"]["cache_bytes_per_token"] == 1572864
    limits = config["parity"]
    assert 0 < limits["tolerance_rel_l2_median"] <= limits["tolerance_rel_l2_max"] < 0.3
    assert 0 < limits["tolerance_exit_abs_max"] < 0.1


def ouro_entry(cfg, config):
    assert (cfg.num_hidden_layers, cfg.total_ut_steps, cfg.vocab_size) == (48, 4, 49152)


def solar_drawn(attrs):
    held_share(attrs, layers=4, held=2, low=0.06, high=0.2)  # an eighth in expectation


def solar_workflow(mine, theirs):
    assert differing(mine, theirs) == {
        ("CheckpointLoaderSimple", "ckpt_name"), ("TextGenerate", "text"),
        ("SaveImage", "filename_prefix")}
    generate = by_kind(mine)["TextGenerate"]
    assert (generate["max_new_tokens"], generate["temperature"]) == (256, 1.0)
    text = generate["text"]
    assert len(text.encode("utf-8")) == 8191 and text.isascii()
    # the DeepSeek cell's instruction, a house style guide, worked pairs, the user's line
    theirs_text = by_kind(theirs)["TextGenerate"]["text"]
    assert text.startswith(theirs_text.split("\n\nExample 1\n")[0])
    assert "House style guide" in text and text.count("\nRequest: ") == 13
    assert text.endswith("\n\nRequest: a photograph of a mountain lake at dawn\nPrompt:")
    assert theirs_text.rstrip().endswith(text[-55:])


def solar_published(config):
    assert config["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64, "num_kv_heads": None}
    assert config["gqa_layers"] == list(range(0, 48, 4))
    assert config["as_run"]["parameters"] == {"lm": 3308353344}
    assert config["as_run"]["cache_bytes_per_token"] == 4096
    assert config["as_run"]["state_bytes"] == 13025280
    assert set(config["held"]) == {"layers", "experts", "vocabulary", "state"}
    assert "8 chips of one v5e-8 host" in config["deployment"]
    limits = config["parity"]
    assert 0 < limits["tolerance_rel_l2_median"] <= limits["tolerance_rel_l2_max_unflipped"] < 0.2
    assert 0 < limits["tolerance_expert_set_mismatch"] < 0.5
    assert 0 < limits["tolerance_state_rel_l2"] < 0.2


def solar_entry(cfg, config):
    linear = config["linear_attn_config"]
    assert (cfg.linear_num_heads, cfg.linear_head_dim, cfg.short_conv_kernel_size) == (
        linear["num_heads"], linear["head_dim"], linear["short_conv_kernel_size"])
    assert (cfg.num_hidden_layers, len(cfg.held_experts), cfg.vocab_held) == (
        config["num_hidden_layers"], config["n_routed_experts"], config["vocab_size"])
    assert (cfg.n_routed_experts, cfg.vocab_size, cfg.ep_size, cfg.vocab_shards) == (
        320, 196608, 8, 8)
    assert cfg.kda_chunk == config["as_run"]["kda_chunk"] == 64
    assert [layer for layer in range(48) if type(cfg)().is_full(layer)] == config["gqa_layers"]


def k_exaone_drawn(attrs):
    # four sparse main layers and the MTP module's, 2 of 16 experts held
    held_share(attrs, layers=4, held=2, low=0.06, high=0.2, decode_layers=5)
    # one draft a step, one or two tokens out of each, the first from the prefill
    steps, accepted = attrs["decode_steps"], attrs["mtp_accepted"]
    assert attrs["mtp_drafted"] == steps and 0 <= accepted <= steps
    assert 1 + steps + accepted in (attrs["new_tokens"], attrs["new_tokens"] + 1)
    # two positions a step through five layers and the MTP module's, kept or not
    assert attrs["decode_layer_passes"] == steps * 2 * (5 + 1)
    assert attrs["decode_routed_pairs"] == steps * 2 * (4 + 1) * 4 == attrs["decode_expert_rows"]
    # distinct held experts a step and layer read: never more than the pairs on them
    assert 0 <= attrs["decode_experts_read"] <= min(
        attrs["decode_routed_pairs_held"], steps * (4 + 1) * 2)


def k_exaone_workflow(mine, _):
    solars = load("workflows/rewrite-txt2img-solar-open2.json")
    assert differing(mine, solars) == {
        ("CheckpointLoaderSimple", "ckpt_name"), ("TextGenerate", "max_new_tokens"),
        ("TextGenerate", "draft_tokens"), ("SaveImage", "filename_prefix")}
    generate = by_kind(mine)["TextGenerate"]
    assert (generate["max_new_tokens"], generate["draft_tokens"], generate["temperature"]) == (
        384, 1, 1.0)
    # the Solar cell's 8,191-byte instruction, byte for byte
    assert generate["text"] == by_kind(solars)["TextGenerate"]["text"]


def k_exaone_published(config):
    assert config["rope_parameters"] == {"rope_theta": 1000000, "rope_type": "default"}
    assert config["layer_types"] == (["sliding_attention"] * 3 + ["full_attention"]) * 12
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 47
    assert (config["mtp_layer_types"], config["mtp_sliding_windows"]) == (["full_attention"], [0])
    assert config["as_run"]["parameters"] == {"lm": 4543318144}
    assert set(config["held"]) == {"layers", "experts", "vocabulary", "state"}
    assert "8 chips of one v5e-8 host" in config["deployment"]
    assert "not the trained model's" in config["as_run"]["drafts_kept"]
    limits = config["parity"]
    assert 0 < limits["tolerance_rel_l2_median"] <= limits["tolerance_rel_l2_max_unflipped"] < 0.2
    assert 0 < limits["tolerance_draft_rel_l2_median"] < 0.2
    assert 0 < limits["tolerance_expert_set_mismatch"] < 0.5


def k_exaone_entry(cfg, config):
    assert cfg.rope_theta == config["rope_parameters"]["rope_theta"]
    assert (cfg.num_hidden_layers, len(cfg.held_experts), cfg.vocab_held) == (
        config["num_hidden_layers"], config["num_experts"], config["vocab_size"])
    assert (cfg.num_experts, cfg.vocab_size, cfg.ep_size, cfg.vocab_shards) == (
        128, 153600, 8, 8)
    whole = type(cfg)()
    assert ["sliding_attention" if whole.is_window(i) else "full_attention"
            for i in range(48)] == config["layer_types"]
    assert ["dense" if whole.is_dense(i) else "sparse" for i in range(48)] == (
        config["mlp_layer_types"])


def ling_flash_drawn(attrs):
    # six sparse main layers and the MTP module's, 4 of 32 experts held (routing group 0):
    # group 0 stays for every second token, which then sends it about 1 of its 4
    held_share(attrs, layers=6, held=4, low=0.06, high=0.2, decode_layers=7)
    steps, accepted = attrs["decode_steps"], attrs["mtp_accepted"]
    assert attrs["mtp_drafted"] == steps and 0 <= accepted <= steps
    assert 1 + steps + accepted in (attrs["new_tokens"], attrs["new_tokens"] + 1)
    # two positions a step through seven layers and the MTP module's, kept or not
    assert attrs["decode_layer_passes"] == steps * 2 * (7 + 1)
    assert attrs["decode_routed_pairs"] == steps * 2 * (6 + 1) * 4 == attrs["decode_expert_rows"]
    assert 0 <= attrs["decode_experts_read"] <= min(
        attrs["decode_routed_pairs_held"], steps * (6 + 1) * 4)


def ling_flash_workflow(mine, _):
    theirs = load("workflows/rewrite-txt2img-k-exaone.json")
    assert differing(mine, theirs) == {
        ("CheckpointLoaderSimple", "ckpt_name"), ("TextGenerate", "max_new_tokens"),
        ("SaveImage", "filename_prefix")}
    generate = by_kind(mine)["TextGenerate"]
    assert (generate["max_new_tokens"], generate["draft_tokens"], generate["temperature"]) == (
        1024, 1, 1.0)
    # the K-EXAONE cell's 8,191-byte instruction, byte for byte
    assert generate["text"] == by_kind(theirs)["TextGenerate"]["text"]


def ling_flash_published(config):
    assert config["expert_swiglu_limit_list"] == [0] * 35 + [4] * 7
    assert config["share_expert_swiglu_limit_list"] == [0] * 34 + [5] * 6 + [7] * 2
    assert config["model_type"] == "bailing_hybrid" and config["q_lora_rank"] is None
    assert config["as_run"]["parameters"] == {"lm": 3296050624}
    assert config["as_run"]["cache_bytes_per_token"] == 2304
    assert config["as_run"]["state_bytes"] == 26050560
    assert (config["as_run"]["first_layer"], config["as_run"]["kda_chunk"]) == (1, 64)
    assert set(config["held"]) == {"layers", "experts", "vocabulary", "state"}
    assert "routing group 0 whole" in config["held"]["experts"]
    assert "8 chips of one v5e-8 host" in config["deployment"]
    assert "not the trained model's" in config["as_run"]["drafts_kept"]
    limits = config["parity"]
    assert 0 < limits["tolerance_rel_l2_median"] <= limits["tolerance_rel_l2_max_unflipped"] < 0.2
    assert 0 < limits["tolerance_draft_rel_l2_median"] < 0.2
    assert 0 < limits["tolerance_expert_set_mismatch"] < 0.5


def ling_flash_entry(cfg, config):
    assert (cfg.first_layer, list(cfg.layers)) == (config["as_run"]["first_layer"], list(range(1, 8)))
    assert (cfg.num_hidden_layers, len(cfg.held_experts), cfg.vocab_held) == (
        config["num_hidden_layers"], config["num_experts"], config["vocab_size"])
    assert (cfg.num_experts, cfg.vocab_size, cfg.ep_size, cfg.vocab_shards) == (512, 157184, 8, 8)
    assert list(cfg.expert_swiglu_limit_list) == config["expert_swiglu_limit_list"]
    assert list(cfg.share_expert_swiglu_limit_list) == config["share_expert_swiglu_limit_list"]
    assert cfg.kda_chunk == config["as_run"]["kda_chunk"]
    whole = type(cfg)()
    assert [layer for layer in whole.layers if whole.is_mla(layer)] == [5, 11, 17, 23, 29, 35, 41]
    assert [layer for layer in whole.layers if whole.is_dense(layer)] == [0, 1]


def nemotron_drawn(attrs):
    # six sparse blocks, 2 of 16 experts held, 3 a token
    held_share(attrs, layers=6, held=2, low=0.06, high=0.2)
    # one position a step, whose experts are distinct: what the kernel calls read
    # are the held pairs
    assert attrs["decode_experts_read"] == attrs["decode_routed_pairs_held"]


def nemotron_workflow(mine, _):
    solars = load("workflows/rewrite-txt2img-solar-open2.json")
    assert differing(mine, solars) == {
        ("CheckpointLoaderSimple", "ckpt_name"), ("TextGenerate", "max_new_tokens"),
        ("SaveImage", "filename_prefix")}
    generate = by_kind(mine)["TextGenerate"]
    assert (generate["max_new_tokens"], generate["temperature"]) == (512, 1.0)
    assert "draft_tokens" not in generate
    # the Solar cell's 8,191-byte instruction, byte for byte
    assert generate["text"] == by_kind(solars)["TextGenerate"]["text"]


def nemotron_published(config):
    assert config["hybrid_override_pattern"] == (
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")
    assert config["model_type"] == "nemotron_h" and config["mlp_hidden_act"] == "relu2"
    assert config["as_run"]["parameters"] == {"lm": 3422495040}
    assert config["as_run"]["cache_bytes_per_token"] == 6144
    assert config["as_run"]["state_bytes"] == 49082368
    assert set(config["held"]) == {"layers", "experts", "vocabulary", "state"}
    assert "all 52 blocks" in config["held"]["layers"]
    assert "16 chips of two v5e-8 hosts" in config["deployment"]
    assert "No pipeline stage" in config["deployment"]
    # the issue's fallback, with what was measured on the eight-way cut first
    assert "WHICH CUT WAS BUILT AND WHY" in config["deployment"]
    limits = config["parity"]
    # 52 blocks deep in bfloat16: an unflipped position stays within 0.15, the median
    # over positions most of which a flipped expert has moved within 0.25
    assert 0 < limits["tolerance_rel_l2_max_unflipped"] < limits["tolerance_rel_l2_median"] < 0.3
    assert 0.3 < limits["tolerance_expert_set_mismatch"] < 0.6
    # the first Mamba-2 block's state is arithmetic alone: ten times tighter than the deepest
    assert 0 < 10 * limits["tolerance_first_state_rel_l2"] < limits["tolerance_state_rel_l2"] < 0.2
    assert "carrying S in bfloat16" in limits["why_these_limits"]


def nemotron_entry(cfg, config):
    assert (cfg.num_hidden_layers, len(cfg.held_experts), cfg.vocab_held) == (
        52, config["n_routed_experts"], config["vocab_size"])
    assert (cfg.n_routed_experts, cfg.vocab_size, cfg.ep_size, cfg.vocab_shards) == (
        128, 131072, 16, 8)
    assert cfg.blocks_of("*") == [5, 12, 19, 26, 33, 42]
    assert (len(cfg.blocks_of("M")), len(cfg.blocks_of("E"))) == (23, 23)
    assert cfg.layer_norm_epsilon == config["layer_norm_epsilon"] == config["norm_eps"]
    assert type(cfg)() == dataclasses.replace(cfg, ep_size=1, vocab_shards=1)  # nothing else cut


def glm_drawn(attrs):
    # four sparse main layers and the MTP module's, 2 of 32 experts held (a sixteenth)
    for phase, rows in (("prefill", 4), ("decode", 5)):
        pairs, on_held = attrs[f"{phase}_routed_pairs"], attrs[f"{phase}_routed_pairs_held"]
        assert 0 <= on_held < pairs
        assert on_held / (rows * 2) <= attrs[f"{phase}_expert_load_max"] <= on_held
    assert 0.02 < attrs["prefill_routed_pairs_held"] / attrs["prefill_routed_pairs"] < 0.12
    # a part's 64 pairs a layer are under a tile: a ladder of one rung, a part and layer
    assert attrs["prefill_expert_rows"] == attrs["prefill_routed_pairs"]
    steps, accepted = attrs["decode_steps"], attrs["mtp_accepted"]
    assert attrs["mtp_drafted"] == steps and 0 <= accepted <= steps
    assert 1 + steps + accepted in (attrs["new_tokens"], attrs["new_tokens"] + 1)
    # two positions a step through five layers and the MTP module's, kept or not
    assert attrs["decode_layer_passes"] == steps * 2 * (5 + 1)
    assert attrs["decode_routed_pairs"] == steps * 2 * (4 + 1) * 4 == attrs["decode_expert_rows"]
    assert 0 <= attrs["decode_experts_read"] <= min(
        attrs["decode_routed_pairs_held"], steps * (4 + 1) * 2)
    # the prompt's 2,048 positions in five layers, then what the steps' positions saw:
    # 8 chosen of everything before, a step's 2 x (5 + 1) queries past the prompt
    prompt = 5 * 2048 * 2049 // 2
    assert prompt + steps * 12 * 2048 < attrs["keys_visible"] < prompt + steps * 12 * 2064
    assert attrs["keys_selected"] == 5 * (8 * 9 // 2 + 2040 * 8) + steps * 12 * 8


def glm_workflow(mine, _):
    theirs = load("workflows/rewrite-txt2img-k-exaone.json")
    assert differing(mine, theirs) == {
        ("CheckpointLoaderSimple", "ckpt_name"), ("TextGenerate", "text"),
        ("TextGenerate", "max_new_tokens"), ("SaveImage", "filename_prefix")}
    generate = by_kind(mine)["TextGenerate"]
    assert (generate["max_new_tokens"], generate["draft_tokens"], generate["temperature"]) == (
        128, 1, 1.0)
    # the cells' 8,191-byte style guide byte for byte, then a 24,576-byte manuscript that
    # ends in the line asking for one scene's prompt
    guide = by_kind(theirs)["TextGenerate"]["text"]
    text = generate["text"]
    assert text.startswith(guide) and text.isascii()
    assert (len(guide), len(text) - len(guide)) == (8191, 24576)
    assert text[len(guide):].startswith("\n\nManuscript, chapter nine")
    assert text.count("\nScene ") >= 40 and text.endswith("of the other scenes.\nPrompt:")
    # what `scripts/gen_longdoc_workflow.py` writes, to the byte
    spec = importlib.util.spec_from_file_location(
        "gen_longdoc_workflow", os.path.join(ROOT, "scripts", "gen_longdoc_workflow.py"))
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    assert text == guide + script.manuscript()
    # the rehearsal reads the document's first 2,047 bytes
    edits = load("benchmark/workloads/glm_5_2_longdoc_txt2img_512.closed2.json")["rehearsal"]["set"]
    (short,) = [e["value"] for e in edits if (e["class_type"], e["input"]) == ("TextGenerate", "text")]
    assert short == text[:2047]


def granite_workflow(mine, _):
    theirs = load("workflows/rewrite-txt2img-k-exaone.json")
    assert differing(mine, theirs) == {
        ("CheckpointLoaderSimple", "ckpt_name"), ("TextGenerate", "text"),
        ("TextGenerate", "max_new_tokens"), ("TextGenerate", "draft_tokens"),
        ("SaveImage", "filename_prefix")}
    generate = by_kind(mine)["TextGenerate"]
    assert (generate["max_new_tokens"], generate["draft_tokens"], generate["temperature"]) == (
        128, 0, 1.0)
    # the cells' 8,191-byte style guide byte for byte, then a 57,344-byte manuscript that
    # ends in the line asking for one scene's prompt: 65,535 bytes, 65,536 tokens
    guide = by_kind(theirs)["TextGenerate"]["text"]
    text = generate["text"]
    assert text.startswith(guide) and text.isascii()
    assert (len(guide), len(text) - len(guide)) == (8191, 57344)
    assert text[len(guide):].startswith("\n\nManuscript, chapter nine")
    assert text.count("\nScene ") >= 100 and text.endswith("of the other scenes.\nPrompt:")
    # what `scripts/gen_longdoc_workflow.py` writes, to the byte, and not GLM's manuscript
    spec = importlib.util.spec_from_file_location(
        "gen_longdoc_workflow", os.path.join(ROOT, "scripts", "gen_longdoc_workflow.py"))
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    assert text == guide + script.manuscript(54, 57344)
    assert not text[len(guide):].startswith(script.manuscript()[:4096])
    # the rehearsal reads the document's first 255 bytes: 16 parts of 16 positions
    edits = load(
        "benchmark/workloads/granite_4_0_h_micro_longdoc_txt2img_512.closed2.json")["rehearsal"]["set"]
    (short,) = [e["value"] for e in edits if (e["class_type"], e["input"]) == ("TextGenerate", "text")]
    assert short == text[:255]


def granite_published(config):
    assert config["layer_types"] == [
        "attention" if index % 10 == 5 else "mamba" for index in range(40)]
    assert config["model_type"] == "granitemoehybrid" and config["position_embedding_type"] == "nope"
    assert (config["num_local_experts"], config["num_experts_per_tok"]) == (0, 0)
    assert config["as_run"]["parameters"] == {"lm": 3191396096}
    assert (config["as_run"]["cache_bytes_per_token"], config["as_run"]["state_bytes"]) == (
        8192, 76437504)
    assert (config["as_run"]["prefill_part"], config["as_run"]["prefill_chunk"]) == (8192, 256)
    assert set(config["held"]) == {"layers", "vocabulary", "state"}
    assert "all 40 published layers" in config["held"]["layers"]
    assert "all 100,352 ids" in config["held"]["vocabulary"]
    assert "whole on one v5e chip beside SD1.5" in config["deployment"]
    assert "no layer is shared between chips, no stage is cut" in config["deployment"]
    assert "published" not in config
    limits = config["parity"]
    assert 0 < limits["tolerance_rel_l2_median"] <= limits["tolerance_rel_l2_max"] < 0.3
    # layer 0's state is arithmetic alone: far tighter than the deepest
    assert 0 < 5 * limits["tolerance_first_state_rel_l2"] < limits["tolerance_state_rel_l2"] < 0.3
    assert 0 < limits["tolerance_kv_rel_l2"] < 0.05
    assert "bfloat16 between parts" in limits["why_these_limits"]


def granite_entry(cfg, config):
    assert tuple(config["layer_types"]) == cfg.layer_types
    assert (cfg.num_hidden_layers, cfg.vocab_size, cfg.head_dim) == (40, 100352, 64)
    assert (cfg.prefill_part, cfg.mamba_chunk_size) == (
        config["as_run"]["prefill_part"], config["as_run"]["prefill_chunk"])
    assert type(cfg)() == cfg  # every field the published value: the defaults


def glm_published(config):
    assert config["rope_parameters"] == {"rope_theta": 8000000, "rope_type": "default"}
    assert config["indexer_types"] == ["full"] * 3 + ["shared", "shared", "shared", "full"] * 18 + [
        "shared"] * 3
    assert config["mlp_layer_types"] == ["dense"] * 3 + ["sparse"] * 75
    assert config["model_type"] == "glm_moe_dsa" and config["index_topk_pattern"] is None
    assert config["ep_size"] == 1 and "expert-parallel width is 16" in config["held"]["ep_size"]
    assert config["as_run"]["parameters"] == {"lm": 4774740992}
    assert (config["as_run"]["cache_bytes_per_token"], config["as_run"]["state_bytes"]) == (7680, 0)
    assert (config["first_layer"], config["as_run"]["prefill_part"]) == (2, 8192)
    assert set(config["held"]) == {"layers", "experts", "vocabulary", "state", "ep_size"}
    assert "published layers 2-6 of 78" in config["held"]["layers"]
    assert "shared by 16 chips (two v5e-8 hosts)" in config["deployment"]
    assert "The sixteen-way cut was built" in config["deployment"]
    limits = config["parity"]
    assert 0 < limits["tolerance_rel_l2_median"] <= limits["tolerance_rel_l2_max_unflipped"] < 0.2
    assert 0 < limits["tolerance_draft_rel_l2_median"] < 0.2
    assert 0 < limits["tolerance_expert_set_mismatch"] < 0.5
    assert 0 < limits["tolerance_selection_mismatch"] < 0.5


def glm_entry(cfg, config):
    assert (cfg.first_layer, list(cfg.layers)) == (config["first_layer"], [2, 3, 4, 5, 6])
    assert cfg.rope_theta == config["rope_parameters"]["rope_theta"]
    assert (cfg.num_hidden_layers, len(cfg.held_experts), cfg.vocab_held) == (
        config["num_hidden_layers"], config["n_routed_experts"], config["vocab_size"])
    assert (cfg.n_routed_experts, cfg.vocab_size, cfg.ep_size, cfg.vocab_shards) == (
        256, 154880, 16, 8)
    assert cfg.prefill_part == config["as_run"]["prefill_part"]
    whole = type(cfg)()
    assert ["full" if whole.is_full(i) else "shared" for i in range(78)] == config["indexer_types"]
    assert ["dense" if whole.is_dense(i) else "sparse" for i in range(78)] == (
        config["mlp_layer_types"])
    assert type(cfg)() == dataclasses.replace(
        cfg, num_hidden_layers=78, first_layer=0, ep_size=1, vocab_shards=1)  # nothing else cut


def sdar_drawn(attrs):
    # every expert is held: every pair falls on one, and the prefill's ladder has one rung
    for phase in ("prefill", "decode"):
        pairs = attrs[f"{phase}_routed_pairs"]
        assert attrs[f"{phase}_routed_pairs_held"] == pairs == attrs[f"{phase}_expert_rows"]
        assert pairs / (3 * 8) <= attrs[f"{phase}_expert_load_max"] <= pairs
    # 16 ids are four blocks of 4: a closing pass a block, and at most 4 denoising passes
    denoise, closing = attrs["denoise_passes"], attrs["closing_passes"]
    assert closing == 4 and closing <= denoise <= 4 * closing
    assert attrs["decode_steps"] == denoise + closing
    assert attrs["transferred_by_threshold"] + attrs["transferred_by_floor"] == 16
    assert attrs["transferred_by_floor"] <= denoise
    # four positions a pass through three layers; a closing pass stops at the last one's keys
    assert attrs["decode_layer_passes"] == 4 * (denoise * 3 + closing * 2)
    assert attrs["decode_routed_pairs"] == attrs["decode_layer_passes"] * 2
    # distinct experts a pass and layer read: 2 to 8 of 8 for four positions' two each
    bodies = denoise * 3 + closing * 2
    assert 2 * bodies <= attrs["decode_experts_read"] <= 8 * bodies


def sdar_workflow(mine, deepseeks):
    assert differing(mine, deepseeks) == {
        ("CheckpointLoaderSimple", "ckpt_name"), ("TextGenerate", "max_new_tokens"),
        ("TextGenerate", "draft_tokens"), ("SaveImage", "filename_prefix")}
    generate = by_kind(mine)["TextGenerate"]
    assert (generate["max_new_tokens"], generate["draft_tokens"], generate["temperature"]) == (
        512, 0, 1.0)
    # the DeepSeek cell's 2,047-byte instruction, byte for byte: 512 whole blocks of 4
    assert generate["text"] == by_kind(deepseeks)["TextGenerate"]["text"]


def sdar_published(config):
    assert config["model_type"] == "sdar_moe"
    assert (config["decoder_sparse_step"], config["mlp_only_layers"]) == (1, [])
    assert config["published"] == {"num_hidden_layers": 48, "parameters": 30532122624}
    assert config["held"]["parameters"] == 4361055744
    assert config["as_run"]["parameters"] == {"lm": 4361055744}
    assert (config["as_run"]["cache_bytes_per_token"], config["as_run"]["state_bytes"]) == (
        12288, 0)
    assert (config["as_run"]["block_length"], config["as_run"]["denoising_steps"],
            config["as_run"]["confidence_threshold"], config["as_run"]["mask_token_id"]) == (
        4, 4, 0.85, 151669)
    assert set(config["held"]) == {
        "layers", "experts", "vocabulary", "parameters", "bytes", "state", "bent_by_the_cut"}
    assert "published layers 0-5 of 48" in config["held"]["layers"]
    assert "all 128" in config["held"]["experts"]
    assert "ids 0-151,935" in config["held"]["vocabulary"]
    assert "8 pipeline stages of 6 layers" in config["deployment"]
    assert "no layer is shared between chips" in config["deployment"]
    assert "a quarter" in config["held"]["bent_by_the_cut"]
    limits = config["parity"]
    assert 0 < limits["tolerance_rel_l2_median"] <= limits["tolerance_rel_l2_max_unflipped"] < 0.2
    assert 0 < limits["tolerance_expert_set_mismatch"] < 0.5
    assert 0 <= limits["tolerance_transfer_mismatch"] < 0.5
    # layer 0's keys are arithmetic and storage alone: far tighter than the deepest's
    assert 0 < 2 * limits["tolerance_kv_rel_l2_first"] < limits["tolerance_kv_rel_l2_last"] < 0.1


def sdar_entry(cfg, config):
    assert (cfg.num_hidden_layers, len(cfg.held_experts), cfg.vocab_size) == (6, 128, 151936)
    assert (cfg.block_length, cfg.denoising_steps, cfg.confidence_threshold,
            cfg.mask_token_id) == tuple(config["as_run"][key] for key in (
                "block_length", "denoising_steps", "confidence_threshold", "mask_token_id"))
    assert type(cfg)() == dataclasses.replace(cfg, num_hidden_layers=48)  # nothing else cut


def dots3_drawn(attrs):
    # four sparse layers, 4 of 8 experts held (a half), 2 a token
    for phase in ("prefill", "decode"):
        pairs, on_held = attrs[f"{phase}_routed_pairs"], attrs[f"{phase}_routed_pairs_held"]
        assert 0 <= on_held < pairs
        assert on_held / (4 * 4) <= attrs[f"{phase}_expert_load_max"] <= on_held
    assert 0.3 < attrs["prefill_routed_pairs_held"] / attrs["prefill_routed_pairs"] < 0.7
    # a part's 32 pairs a layer are under a tile: a ladder of one rung, a part and layer
    assert attrs["prefill_expert_rows"] == attrs["prefill_routed_pairs"]
    assert 0 <= attrs["decode_experts_read"] <= min(attrs["decode_routed_pairs_held"], 16 * 4 * 2)


def dots3_workflow(mine, _):
    theirs = load("workflows/longdoc-txt2img-glm-5.2.json")
    assert differing(mine, theirs) == {
        ("CheckpointLoaderSimple", "ckpt_name"), ("TextGenerate", "max_new_tokens"),
        ("TextGenerate", "draft_tokens"), ("SaveImage", "filename_prefix")}
    generate = by_kind(mine)["TextGenerate"]
    assert (generate["max_new_tokens"], generate["draft_tokens"], generate["temperature"]) == (
        256, 0, 1.0)
    # GLM-5.2's cell's own text, byte for byte; the rehearsal reads its first 2,047 bytes
    assert generate["text"] == by_kind(theirs)["TextGenerate"]["text"]
    edits = load("benchmark/workloads/dots3_note_longdoc_txt2img_512.closed2.json")[
        "rehearsal"]["set"]
    (short,) = [e["value"] for e in edits if (e["class_type"], e["input"]) == ("TextGenerate", "text")]
    assert short == generate["text"][:2047]


def dots3_published(config):
    assert config["layer_types"] == ["full_attention"] + [
        "full_attention", "sliding_attention", "sliding_attention", "sliding_attention"] * 11 + [
        "full_attention"]
    assert config["model_type"] == "dots3_note" and config["rope_scaling"] is None
    assert config["as_run"]["parameters"] == {"lm": 4087154176}
    assert config["published"]["parameters"] == 279551726592
    assert (config["as_run"]["cache_bytes_per_token"], config["as_run"]["state_bytes"]) == (
        2816, 3 * 520 * 2176)
    assert (config["first_layer"], config["as_run"]["prefill_part"]) == (0, 8192)
    assert set(config["held"]) == {"layers", "experts", "vocabulary", "state", "ep_size"}
    assert "published layers 0-4 of 46" in config["held"]["layers"]
    assert "shared by the 8 chips of one v5e-8 host" in config["deployment"]
    assert "The eight-way cut was built" in config["deployment"]
    # the limits are wide and say why: under seeded weights the rescale makes the softmax
    # near one-hot; each still lies under the mildest control's reading (0.31 for the median)
    limits = config["parity"]
    assert 0 < limits["tolerance_rel_l2_max_unflipped"] <= limits["tolerance_rel_l2_median"] < 0.31
    assert 0 < limits["tolerance_expert_set_mismatch"] < 0.999
    assert 0 < limits["tolerance_selection_mismatch"] < 0.2
    assert 0 < limits["tolerance_ring_rel_l2"] < 0.338
    assert "near 7 where GLM's have 1" in limits["why_these_limits"]


def dots3_entry(cfg, config):
    assert (cfg.first_layer, list(cfg.layers)) == (config["first_layer"], [0, 1, 2, 3, 4])
    assert list(cfg.layer_types) == config["layer_types"]
    assert (cfg.num_hidden_layers, len(cfg.held_experts), cfg.vocab_held) == (
        config["num_hidden_layers"], config["n_routed_experts"], config["vocab_size"])
    assert (cfg.n_routed_experts, cfg.vocab_size, cfg.ep_size, cfg.vocab_shards) == (
        256, 152064, 8, 8)
    assert cfg.prefill_part == config["as_run"]["prefill_part"]
    assert (cfg.full_layers, cfg.window_layers, cfg.ring_positions) == (2, 3, 520)
    assert type(cfg)() == dataclasses.replace(
        cfg, num_hidden_layers=46, ep_size=1, vocab_shards=1)  # nothing else cut


def longcat_drawn(attrs):
    # two layers, 4 of 8 experts held beside 4 identities (a router of 12), 3 a token
    for phase in ("prefill", "decode"):
        pairs, on_held = attrs[f"{phase}_routed_pairs"], attrs[f"{phase}_routed_pairs_held"]
        assert 0 <= on_held < pairs and 0 <= attrs[f"{phase}_zero_pairs"] < pairs
        assert on_held / (2 * 4) <= attrs[f"{phase}_expert_load_max"] <= on_held
    # even routing: a third of the pairs on the 4 held of 12, a third on the 4 identities
    assert 0.2 < attrs["prefill_routed_pairs_held"] / attrs["prefill_routed_pairs"] < 0.5
    assert 0.2 < attrs["prefill_zero_pairs"] / attrs["prefill_routed_pairs"] < 0.5
    # a block's 24 pairs a layer are under a tile: a ladder of one rung, a block and layer
    assert attrs["prefill_expert_rows"] == attrs["prefill_routed_pairs"]
    assert 0 <= attrs["decode_experts_read"] <= min(attrs["decode_routed_pairs_held"], 16 * 2 * 3)
    low, mean, high = (attrs[f"real_experts_per_token_{what}"] for what in ("min", "mean", "max"))
    assert 0 <= low <= mean <= high <= 3
    zero = attrs["prefill_zero_pairs"] + attrs["decode_zero_pairs"]
    assert mean == pytest.approx(3 - zero / (2064 * 2))


def longcat_workflow(mine, _):
    theirs = load("workflows/longdoc-txt2img-glm-5.2.json")
    assert differing(mine, theirs) == {
        ("CheckpointLoaderSimple", "ckpt_name"), ("TextGenerate", "draft_tokens"),
        ("SaveImage", "filename_prefix")}
    generate = by_kind(mine)["TextGenerate"]
    assert (generate["max_new_tokens"], generate["draft_tokens"], generate["temperature"]) == (
        128, 0, 1.0)
    # GLM-5.2's and dots3-note-prev's cells' own text, byte for byte, so the three differ by
    # model alone; the rehearsal reads its first 2,047 bytes
    assert generate["text"] == by_kind(theirs)["TextGenerate"]["text"]
    edits = load("benchmark/workloads/longcat_flash_longdoc_txt2img_512.closed2.json")[
        "rehearsal"]["set"]
    (short,) = [e["value"] for e in edits if (e["class_type"], e["input"]) == ("TextGenerate", "text")]
    assert short == generate["text"][:2047]


def longcat_published(config):
    assert (config["zero_expert_num"], config["zero_expert_type"]) == (256, "identity")
    assert config["as_run"]["parameters"] == {"lm": 3964789760}
    assert config["published"]["parameters"] == 560664980480
    assert (config["as_run"]["cache_bytes_per_token"], config["as_run"]["state_bytes"]) == (
        8 * 576 * 2, 0)
    assert (config["as_run"]["prefill_part"], config["as_run"]["expert_block"],
            config["as_run"]["attention_heads_a_call"]) == (8192, 1024, 16)
    assert set(config["held"]) == {
        "layers", "experts", "identity_experts", "vocabulary", "state", "ep_size"}
    assert "belong to no chip's share" in config["held"]["identity_experts"]
    assert "each layer shared by 64 chips" in config["deployment"]
    assert "Why 64 ways and not the driver's rough 32" in config["deployment"]
    limits = config["parity"]
    assert 0 < limits["tolerance_rel_l2_max_unflipped"] <= limits["tolerance_rel_l2_median"] < 1
    assert 0 < limits["tolerance_expert_set_mismatch"] < 1
    assert 0 < limits["tolerance_cache_rel_l2"] < 1


def longcat_entry(cfg, config):
    assert (cfg.num_layers, len(cfg.held_experts), cfg.vocab_held) == (
        config["num_layers"], config["n_routed_experts"], config["vocab_size"])
    assert (cfg.n_routed_experts, cfg.zero_expert_num, cfg.router_width, cfg.vocab_size,
            cfg.ep_size, cfg.vocab_shards) == (512, 256, 768, 131072, 64, 8)
    assert (cfg.prefill_part, cfg.expert_block, cfg.attention_heads_a_call) == tuple(
        config["as_run"][key] for key in ("prefill_part", "expert_block", "attention_heads_a_call"))
    assert cfg.qk_head_dim == config["qk_head_dim"] == 192
    assert type(cfg)() == dataclasses.replace(
        cfg, num_layers=28, ep_size=1, vocab_shards=1)  # nothing else cut


@dataclasses.dataclass(frozen=True)
class Model:
    """One language model's row. `attrs`: `node.TextGenerate`'s attributes
    whose values the shapes give (float32 on the CPU), exactly; `drawn`:
    the names of the others; nothing else is on the span but the build's
    tallies and, on the tracing request, `attention`."""

    name: str
    served: str                 # the registry entries: the cell's, and the rehearsal's
    tiny: str
    workflow: str               # under workflows/ and benchmark/workflows/
    config: str                 # under benchmark/configs/
    reference: str              # under comfyui_distributed_tpu/reference/ and benchmark/reference/
    catalog: str                # the model's name in the catalog of architectures
    cell: str
    prompt: int                 # tokens, as the cell's rehearsal runs it
    new_tokens: int
    drafts: int                 # `draft_tokens_max`
    attrs: dict
    drawn: frozenset
    drawn_check: Callable
    wait_bytes: int             # the one read-back: the ids and what `read_back` names
    attention: str              # on the request that traced the programs
    passes: Callable            # attrs -> layer bodies (prefill, decode) the counter takes
    widths: dict                # the published configuration's values, kept
    reduced: dict               # key -> (published, held)
    assumed: tuple              # words its `assumed` list has to say
    published: Callable
    entry: Callable
    check_workflow: Callable
    metrics: frozenset = frozenset()   # what its cell lists beyond `LM_METRICS`
    imports: tuple = tuple(PLAIN_IMPORTS)
    cell_prompt: int = 0        # tokens of the committed workflow, where the rehearsal cuts it
    trace: tuple = (5, 12)      # the cell's traced slice: (start_s, slice_s)
    # (tokens, experts a token, held, experts, hidden, width[, no gate]) of the tiny
    # preset's expert layer where a prefill's rungs are more than a tile: their entries
    grouped: tuple = ()

    def __str__(self):
        return self.name

    def traced_routes(self) -> str:
        """`attention` on the request that traced the programs: the row's
        entries and, a rung and product of the expert layer's ladder,
        `gmm-xla ...` (the CPU's route; PR 64)."""
        entries = set(self.attention.split(", "))
        if self.grouped:
            from comfyui_distributed_tpu.models import moe

            tokens, k, held, experts, hidden, width, *no_gate = self.grouped
            first = (hidden, width, " out-major") if no_gate else (hidden, 2 * width, "")
            for rung in moe.row_ladder(tokens * k, held, experts):
                entries |= {f"gmm-xla {rung}x{first[0]}x{first[1]} g{held} f32{first[2]}",
                            f"gmm-xla {rung}x{width}x{hidden} g{held} f32"}
        return ", ".join(sorted(entries))


MODELS = [
    Model(
        name="deepseek-v2", served="deepseek-v2-ep4-5l", tiny="tiny-deepseek-v2",
        workflow="rewrite-txt2img-deepseek-v2.json", config="deepseek-v2.json",
        reference="deepseek_v2.py", catalog="DeepSeek-V2",
        cell="deepseek_v2_rewrite_txt2img_512.closed2", prompt=2048, new_tokens=16, drafts=0,
        # tiny-deepseek-v2: 3 layers (one dense), a latent of 24 + 8, 4 of 16 experts
        # held, 3 a token
        attrs={
            "prompt_tokens": 2048, "new_tokens": 16, "draft_tokens": 0, "decode_steps": 16,
            "layers": 3, "experts_held": 4, "experts_total": 16,
            "cache_bytes": 3 * (2048 + 16) * 32 * 4, "state_bytes": 0,
            "prefill_routed_pairs": 2048 * 2 * 3, "decode_routed_pairs": 16 * 2 * 3,
            "decode_expert_rows": 16 * 2 * 3, "decode_expert_route": "xla",
            "prefill_expert_route": "xla", "node_id": "6"},
        drawn=frozenset(ROUTING - {"prefill_routed_pairs", "decode_routed_pairs",
                                   "decode_expert_rows", "decode_expert_route",
                                   "prefill_expert_route"}),
        drawn_check=deepseek_drawn,
        wait_bytes=4 * (16 + 2 * 2 * 4),  # the ids, the pairs per held expert of either program
        attention="xla-causal 2048x2048x24/16 bq256 f32",
        grouped=(2048, 3, 4, 16, 64, 32),
        passes=lambda attrs: (2048 * 3, 16 * 3),
        widths={
            "hidden_size": 5120, "intermediate_size": 12288, "moe_intermediate_size": 1536,
            "kv_lora_rank": 512, "q_lora_rank": 1536, "qk_nope_head_dim": 128,
            "qk_rope_head_dim": 64, "v_head_dim": 128, "num_attention_heads": 128,
            "num_key_value_heads": 128, "n_shared_experts": 2, "num_experts_per_tok": 6,
            "n_group": 8, "topk_group": 3, "routed_scaling_factor": 16,
            "first_k_dense_replace": 1, "rms_norm_eps": 1e-6, "rope_theta": 10000,
            "max_position_embeddings": 163840, "norm_topk_prob": False,
            "topk_method": "group_limited_greedy", "scoring_func": "softmax"},
        reduced={"num_hidden_layers": (60, 5), "n_routed_experts": (160, 40),
                 "vocab_size": (102400, 25600)},
        assumed=("seeded random", "stand-in", "batch is 1"),
        published=deepseek_published, entry=deepseek_entry, check_workflow=deepseek_workflow,
        # `mla_device_pct.lm` since PR 45, whose reader finds this cell's `mla` scope too
        metrics=frozenset({GROUPED, "experts_held_share_pct.lm", "mla_device_pct.lm"}),
        imports=tuple(PLAIN_IMPORTS + ["import math\n"]),
    ),
    Model(
        name="ouro", served="ouro-2.6b", tiny="tiny-ouro",
        workflow="rewrite-txt2img-ouro-2.6b.json", config="ouro-2.6b.json",
        reference="ouro.py", catalog="Ouro-2.6B",
        cell="ouro_2_6b_rewrite_txt2img_512.closed2", prompt=2048, new_tokens=8, drafts=0,
        # tiny-ouro: 4 passes x 3 layers, 4 heads of 16; a key and a value of every
        # head in every slot
        attrs={
            "prompt_tokens": 2048, "new_tokens": 8, "draft_tokens": 0, "decode_steps": 8,
            "ut_steps": 4, "layers": 3, "cache_slots": 12,
            "cache_bytes": 12 * 2 * 4 * (2048 + 8) * 16 * 4, "state_bytes": 0,
            "prefill_layer_passes": 2048 * 12, "decode_layer_passes": 8 * 12, "node_id": "6"},
        drawn=frozenset({"exit_mass_1", "exit_mass_2", "exit_mass_3", "exit_mass_4"}),
        drawn_check=ouro_drawn,
        wait_bytes=4 * (8 + 2 * 4),  # the ids and the two exit distributions
        # the decode's single-query attention over a slot (the einsum form off a
        # TPU), then the prefill's
        attention="decode-xla 4x2056x16, xla-causal 2048x2048x16/16 bq256 f32",
        passes=lambda attrs: (2048 * 12, 8 * 12),
        widths={
            "hidden_size": 2048, "intermediate_size": 5632, "num_hidden_layers": 48,
            "num_attention_heads": 16, "num_key_value_heads": 16, "head_dim": 128,
            "total_ut_steps": 4, "early_exit_threshold": 1, "vocab_size": 49152,
            "rms_norm_eps": 1e-6, "rope_theta": 1000000, "max_position_embeddings": 65536,
            "tie_word_embeddings": False, "hidden_act": "silu", "model_type": "ouro",
            "rope_scaling": None, "sliding_window": None, "use_sliding_window": False},
        reduced={},
        assumed=("seeded random", "stand-in", "batch is 1", "four RMS norms", "final norm",
                 "bias", "never exit early", "system prompt"),
        published=ouro_published, entry=ouro_entry, check_workflow=ouro_workflow,
    ),
    Model(
        name="solar-open2", served="solar-open2-ep8-4l", tiny="tiny-solar-open2",
        workflow="rewrite-txt2img-solar-open2.json", config="solar-open2-250b.json",
        reference="solar_open2.py", catalog="Solar-Open2-250B",
        cell="solar_open2_rewrite_txt2img_512.closed2", prompt=8192, new_tokens=16, drafts=0,
        # tiny-solar-open2: a gated NoPE layer (4 query heads over 2 key heads of 16)
        # and three KDA layers (4 heads of 16, chunks of 32), 2 of 16 experts held, 4
        # a token. What grows: a key and a value of each key head in the one softmax
        # layer; what does not: a matrix state a KDA head, the convolutions' last 3 inputs
        attrs={
            "prompt_tokens": 8192, "new_tokens": 16, "draft_tokens": 0, "decode_steps": 16,
            "layers": 4, "full_layers": 1, "linear_layers": 3, "experts_held": 2,
            "experts_total": 16, "cache_bytes": 2 * 2 * (8192 + 16) * 16 * 4,
            "state_bytes": 3 * (4 * 16 * 16 * 4 + 3 * 3 * 4 * 16 * 4),
            "prefill_chunks": 8192 // 32, "kda_form": "scan",
            "prefill_routed_pairs": 8192 * 4 * 4, "decode_routed_pairs": 16 * 4 * 4,
            "decode_expert_rows": 16 * 4 * 4,
            "decode_expert_route": "xla", "prefill_expert_route": "xla", "node_id": "6"},
        drawn=frozenset(ROUTING - {"prefill_routed_pairs", "decode_routed_pairs",
                                   "decode_expert_rows", "decode_expert_route",
                                   "prefill_expert_route"}),
        drawn_check=solar_drawn,
        wait_bytes=4 * (16 + 2 * 4 * 2),  # nothing of the state tree leaves the device
        # the decode's one softmax layer in four (the einsum form, a key head
        # serving two queries), the prefill's three KDA layers (d = 16: the XLA
        # form on any backend), then its softmax layer
        attention=("decode-xla 4x8208x16, kda-scan 8192x4x16 c32 f32, "
                   "xla-causal 8192x8192x16/16 bq256 f32"),
        grouped=(8192, 4, 2, 16, 64, 32),
        passes=lambda attrs: (8192 * 4, 16 * 4),
        widths={
            "hidden_size": 4096, "num_attention_heads": 64, "num_key_value_heads": 8,
            "head_dim": 128, "moe_intermediate_size": 1280, "intermediate_size": 10240,
            "num_experts_per_tok": 8, "n_shared_experts": 1, "rms_norm_eps": 1e-5,
            "gqa_interval": 3, "use_rope": False, "use_gqa_gate": True,
            "kda_use_full_proj": False, "kda_allow_neg_eigval": True, "norm_topk_prob": True,
            "routed_scaling_factor": 1, "first_k_dense_replace": 0,
            "max_position_embeddings": 1048576, "tie_word_embeddings": False},
        reduced={"num_hidden_layers": (48, 4), "n_routed_experts": (320, 40),
                 "vocab_size": (196608, 24576)},
        assumed=("low-rank", "element-wise", "sigmoids", "seeded random", "stand-in",
                 "batch is 1", "dt_bias", "house style guide"),
        published=solar_published, entry=solar_entry, check_workflow=solar_workflow,
        metrics=frozenset({GROUPED, "experts_held_share_pct.lm", "state_mb.lm",
                           "linear_attention_device_pct.lm"}),
    ),
    Model(
        name="k-exaone", served="k-exaone-ep8-5l", tiny="tiny-k-exaone",
        workflow="rewrite-txt2img-k-exaone.json", config="k-exaone-236b-a23b.json",
        reference="k_exaone.py", catalog="K-EXAONE-236B-A23B",
        cell="k_exaone_rewrite_txt2img_512.closed2", prompt=8192, new_tokens=16, drafts=1,
        # tiny-k-exaone: a dense layer and four sparse ones (window, window, window,
        # full, window over all five), 4 query heads over 2 key heads of 16, a window
        # of 12 in a ring of 16, 2 of 16 experts held, 4 a token, the MTP module. What
        # grows: the full layer's cache and the MTP module's; what does not: four rings
        attrs={
            "prompt_tokens": 8192, "new_tokens": 16, "draft_tokens": 1,
            "layers": 5, "window_layers": 4, "full_layers": 2, "window": 12,
            "ring_positions": 16, "experts_held": 2, "experts_total": 16,
            "cache_bytes": 2 * 2 * 2 * (8192 + 16) * 16 * 4, "state_bytes": 4 * 2 * 2 * 16 * 16 * 4,
            "prefill_layer_passes": 8192 * 5, "prefill_routed_pairs": 8192 * 4 * 4,
            "decode_expert_route": "xla", "prefill_expert_route": "xla", "node_id": "6"},
        drawn=frozenset(ROUTING - {"prefill_routed_pairs", "decode_expert_route",
        "prefill_expert_route"}) | {
            "decode_steps", "mtp_drafted", "mtp_accepted", "decode_layer_passes",
            "decode_experts_read"},
        drawn_check=k_exaone_drawn,
        # the ids, the pairs per held expert of either program (the decode's with the
        # MTP module's row) and the four counts
        wait_bytes=4 * (16 + 4 * 2 + (4 + 1) * 2 + 4),
        # a step's two queries over a ring and over a growing cache; the prefill's
        # full route beside the windowed one
        attention=("decode-xla 4x16x16, decode-xla 4x8208x16, "
                   "xla-causal 8192x8192x16/16 bq256 f32, "
                   "xla-causal 8192x8192x16/16 w12 bq256 f32"),
        grouped=(8192, 4, 2, 16, 64, 32),
        passes=lambda attrs: (8192 * 5, attrs["decode_steps"] * 2 * (5 + 1)),
        widths={
            "hidden_size": 6144, "num_attention_heads": 64, "num_key_value_heads": 8,
            "head_dim": 128, "intermediate_size": 18432, "moe_intermediate_size": 2048,
            "num_experts_per_tok": 8, "num_shared_experts": 1, "rms_norm_eps": 1e-5,
            "sliding_window": 128, "sliding_window_pattern": "LLLG", "first_k_dense_replace": 1,
            "norm_topk_prob": True, "routed_scaling_factor": 2.5, "num_nextn_predict_layers": 1,
            "n_group": 1, "topk_group": 1, "scoring_func": "sigmoid",
            "max_position_embeddings": 262144, "tie_word_embeddings": False},
        reduced={"num_hidden_layers": (48, 5), "num_experts": (128, 16),
                 "vocab_size": (153600, 19200)},
        assumed=("pre-norm", "QK norm", "which layers rotate", "window's convention",
                 "selection bias", "DeepSeek-V3's", "before the final norm", "seeded random",
                 "stand-in", "batch is 1", "share of drafts kept", "house style guide"),
        published=k_exaone_published, entry=k_exaone_entry, check_workflow=k_exaone_workflow,
        metrics=frozenset({GROUPED, "experts_held_share_pct.lm", "state_mb.lm", "mtp_accept_pct.lm",
                           "mtp_device_pct.lm"}),
    ),
    Model(
        name="ling-flash", served="ling-flash-ep8-7l", tiny="tiny-ling-flash",
        workflow="rewrite-txt2img-ling-flash.json", config="ling-3.0-flash.json",
        reference="ling_flash.py", catalog="Ling-3.0-flash",
        cell="ling_flash_rewrite_txt2img_512.closed2", prompt=8192, new_tokens=16, drafts=1,
        # tiny-ling-flash: published layers 1-7, a dense KDA layer and six sparse ones (KDA,
        # KDA, KDA, MLA, KDA, KDA), 4 heads of 16, a latent of 24 + 8, chunks of 32, 4 of
        # 32 experts held, 4 a token, the MTP module. What grows: the MLA layer's latents
        # and the MTP module's; what does not: six KDA layers' matrix states and tails,
        # two slots each
        attrs={
            "prompt_tokens": 8192, "new_tokens": 16, "draft_tokens": 1,
            "layers": 7, "linear_layers": 6, "latent_layers": 2, "experts_held": 4,
            "experts_total": 32, "cache_bytes": 2 * (8192 + 16) * 32 * 4,
            "state_bytes": 6 * 2 * (4 * 16 * 16 * 4 + 3 * 3 * 4 * 16 * 4),
            "prefill_chunks": 8192 // 32, "kda_form": "scan", "prefill_layer_passes": 8192 * 7,
            "prefill_routed_pairs": 8192 * 6 * 4, "decode_expert_route": "xla",
            "prefill_expert_route": "xla", "node_id": "6"},
        drawn=frozenset(ROUTING - {"prefill_routed_pairs", "decode_expert_route",
        "prefill_expert_route"}) | {
            "decode_steps", "mtp_drafted", "mtp_accepted", "decode_layer_passes",
            "decode_experts_read"},
        drawn_check=ling_flash_drawn,
        # the ids, the pairs per held expert of either program (the decode's with the
        # MTP module's row) and the four counts
        wait_bytes=4 * (16 + 6 * 4 + (6 + 1) * 4 + 4),
        # the prefill's six KDA layers (d = 16: the XLA form) and its expanded latent
        # attention (24-wide queries and keys, 16-wide values); the decode's absorbed
        # form is plain einsums and logs no route
        attention="kda-scan 8192x4x16 c32 f32, xla-causal 8192x8192x24/16 bq256 f32",
        grouped=(8192, 4, 4, 32, 64, 32),
        passes=lambda attrs: (8192 * 7, attrs["decode_steps"] * 2 * (7 + 1)),
        widths={
            "hidden_size": 2560, "num_attention_heads": 32, "num_key_value_heads": 32,
            "head_dim": 128, "intermediate_size": 6144, "moe_intermediate_size": 768,
            "moe_shared_expert_intermediate_size": 768, "num_experts_per_tok": 8,
            "num_shared_experts": 1, "n_group": 8, "topk_group": 4, "kv_lora_rank": 512,
            "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
            "qk_head_dim": 192, "v_head_dim": 128, "rope_theta": 6000000, "rotary_dim": 64,
            "layer_group_size": 6, "first_k_dense_replace": 2, "short_conv_kernel_size": 4,
            "kda_lower_bound": -5, "kda_safe_gate": True, "no_kda_lora": True,
            "use_kda_lora": False, "routed_scaling_factor": 2.5, "norm_topk_prob": True,
            "score_function": "sigmoid", "topk_method": "noaux_tc",
            "gated_attention_proj_granularity_type": "head_wise",
            "num_nextn_predict_layers": 1, "mtp_use_kda": False, "rms_norm_eps": 1e-6,
            "max_position_embeddings": 262144, "tie_word_embeddings": False},
        reduced={"num_hidden_layers": (42, 7), "num_experts": (512, 64),
                 "vocab_size": (157184, 19648)},
        assumed=("pre-norm", "layer_group_size 6", "full rank", "kda_safe_gate", "use_qk_norm",
                 "head_wise", "sum of its two largest", "the clamp", "DeepSeek-V3's",
                 "before the final norm", "seeded random", "shifted down by 6", "stand-in",
                 "batch is 1", "share of drafts kept", "house style guide"),
        published=ling_flash_published, entry=ling_flash_entry,
        check_workflow=ling_flash_workflow,
        metrics=frozenset({GROUPED, "experts_held_share_pct.lm", "state_mb.lm", "mtp_accept_pct.lm",
                           "mtp_device_pct.lm", "linear_attention_device_pct.lm",
                           "state_keep_device_pct.lm", "mla_device_pct.lm"}),
        imports=tuple(PLAIN_IMPORTS[:4]),  # no numpy of its own
    ),
    Model(
        name="nemotron3-nano", served="nemotron3-nano-ep16-52l", tiny="tiny-nemotron3-nano",
        workflow="rewrite-txt2img-nemotron3-nano.json", config="nemotron-3-nano-30b-a3b.json",
        reference="nemotron_h.py", catalog="NVIDIA-Nemotron-3-Nano-30B-A3B-BF16",
        cell="nemotron3_nano_rewrite_txt2img_512.closed2", prompt=8192, new_tokens=16, drafts=0,
        # tiny-nemotron3-nano: 13 blocks MEMEM*EMEMEME, each one part alone: six Mamba-2
        # blocks (4 heads of 8 over a state of 16, 2 groups, chunks of 32), one attention
        # block (4 query heads over 1 key head of 16), six sparse blocks (2 of 16 experts
        # held, 3 a token, no gate). What grows: the one attention block's keys and values;
        # what does not: six float32 matrix states and the convolutions' last 3 inputs
        attrs={
            "prompt_tokens": 8192, "new_tokens": 16, "draft_tokens": 0, "decode_steps": 16,
            "layers": 13, "mamba_layers": 6, "attention_layers": 1, "sparse_layers": 6,
            "experts_held": 2, "experts_total": 16, "cache_bytes": 2 * (8192 + 16) * 16 * 4,
            "state_bytes": 6 * (4 * 8 * 16 * 4 + 3 * (32 + 2 * 2 * 16) * 4),
            "prefill_chunks": 8192 // 32,
            "prefill_routed_pairs": 8192 * 6 * 3, "decode_routed_pairs": 16 * 6 * 3,
            "decode_expert_rows": 16 * 6 * 3,
            "decode_expert_route": "xla", "prefill_expert_route": "xla", "node_id": "6"},
        drawn=frozenset(ROUTING - {"prefill_routed_pairs", "decode_routed_pairs",
                                   "decode_expert_rows", "decode_expert_route",
                                   "prefill_expert_route"}) | {
            "decode_experts_read"},
        drawn_check=nemotron_drawn,
        wait_bytes=4 * (16 + 2 * 6 * 2),  # nothing of the state tree leaves the device
        # the decode's one attention block (the einsum form, one key head serving four
        # queries), then the prefill's state-space blocks' chunked scan (PR 55: widths
        # off the lane tile, never the kernel) and its attention block
        attention=("decode-xla 4x8208x16, ssd-xla 8192x4x8 g2 n16 c32 f32, "
                   "xla-causal 8192x8192x16/16 bq256 f32"),
        grouped=(8192, 3, 2, 16, 64, 24, True),
        passes=lambda attrs: (8192 * 13, 16 * 13),
        widths={
            "hidden_size": 2688, "num_hidden_layers": 52, "mamba_num_heads": 64,
            "mamba_head_dim": 64, "ssm_state_size": 128, "n_groups": 8, "conv_kernel": 4,
            "chunk_size": 128, "expand": 2, "time_step_min": 0.001, "time_step_max": 0.1,
            "time_step_floor": 0.0001, "num_attention_heads": 32, "num_key_value_heads": 2,
            "head_dim": 128, "intermediate_size": 1856, "moe_intermediate_size": 1856,
            "moe_shared_expert_intermediate_size": 3712, "num_experts_per_tok": 6,
            "n_shared_experts": 1, "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
            "routed_scaling_factor": 2.5, "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
            "use_conv_bias": True, "mamba_proj_bias": False, "attention_bias": False,
            "layer_norm_epsilon": 1e-5, "rope_theta": 10000, "partial_rotary_factor": 1,
            "residual_in_fp32": False, "max_position_embeddings": 262144,
            "tie_word_embeddings": False},
        reduced={"n_routed_experts": (128, 8), "vocab_size": (131072, 16384)},
        assumed=("pre-norm", "no rotary embedding", "expand 2 is read by nothing",
                 "head h reads group h // 8", "gated by silu(z) first", "relu2",
                 "no group step", "seeded random", "no bias is shifted", "stand-in",
                 "batch is 1", "house style guide", "no MTP module"),
        published=nemotron_published, entry=nemotron_entry, check_workflow=nemotron_workflow,
        metrics=frozenset({GROUPED, "experts_held_share_pct.lm", "state_mb.lm", "ssm_device_pct.lm",
                           "ssd_device_pct.lm", "expert_matvec_hbm_pct.lm",
                           "attn_device_pct.lm"}),
    ),
    Model(
        name="glm-5.2", served="glm-5.2-ep16-5l", tiny="tiny-glm-dsa",
        workflow="longdoc-txt2img-glm-5.2.json", config="glm-5.2.json",
        reference="glm_dsa.py", catalog="GLM-5.2",
        cell="glm_5_2_longdoc_txt2img_512.closed2", prompt=2048, cell_prompt=32768,
        new_tokens=16, drafts=1,
        # tiny-glm-dsa: published layers 2-6, a dense layer with an indexer, three sparse
        # ones that attend by its selection, a sparse one with its own; 4 heads (12 + 8
        # wide, values 16), latents of 24 + 8, indexer keys of 16, 8 positions chosen, parts
        # of 16 positions, 2 of 32 experts held, 4 a token, the MTP module. What grows: a
        # latent a layer and the module's, an indexer key a full layer and the module's
        attrs={
            "prompt_tokens": 2048, "new_tokens": 16, "draft_tokens": 1,
            "layers": 5, "index_topk": 8, "indexer_layers": 2, "index_shared_layers": 3,
            "prefill_part": 16, "prefill_parts": 128, "experts_held": 2, "experts_total": 32,
            "cache_bytes": (2048 + 16) * (6 * 32 + 3 * 16) * 4,
            "indexer_cache_bytes": (2048 + 16) * 3 * 16 * 4, "state_bytes": 0,
            "prefill_sparse_attention_form": "gathered",
            "prefill_selection_form": "sort",
            "decode_sparse_attention_form": "masked",
            "prefill_layer_passes": 2048 * 5, "prefill_routed_pairs": 2048 * 4 * 4,
            "decode_expert_route": "xla", "prefill_expert_route": "xla", "node_id": "6"},
        drawn=frozenset(ROUTING - {"prefill_routed_pairs", "decode_expert_route",
        "prefill_expert_route"}) | {
            "decode_steps", "mtp_drafted", "mtp_accepted", "decode_layer_passes",
            "decode_experts_read", "keys_visible", "keys_selected"},
        drawn_check=glm_drawn,
        # the ids; a part's pairs per held expert and keys seen a layer (128 parts); the
        # decode's of both (the MTP module's row and column) and the four counts
        wait_bytes=4 * (16 + 128 * 4 * 2 + 128 * 2 * 5 + (4 + 1) * 2 + 2 * (5 + 1) + 4),
        # a part's 16 queries over the rows a top-k chose, gathered; a step's two under
        # the mask the bisection gave; the top-k a sort at each rung of the lengths' ladder
        attention=", ".join(sorted(
            ["dsa-gathered 16x2064 k8 h4 f32", "dsa-masked 2x2064 k2064 h4 f32"]
            + [f"dsa-select-sort 16x{length} k8"
               for length in (16, 32, 64, 128, 256, 512, 1024, 2048, 2064)])),
        passes=lambda attrs: (2048 * 5, attrs["decode_steps"] * 2 * (5 + 1)),
        widths={
            "hidden_size": 6144, "num_attention_heads": 64, "num_key_value_heads": 64,
            "head_dim": 192, "q_lora_rank": 2048, "kv_lora_rank": 512, "qk_nope_head_dim": 192,
            "qk_rope_head_dim": 64, "qk_head_dim": 256, "v_head_dim": 256,
            "index_n_heads": 32, "index_head_dim": 128, "index_topk": 2048,
            "index_topk_freq": 4, "index_skip_topk_offset": 3,
            "index_share_for_mtp_iteration": True, "indexer_rope_interleave": True,
            "rope_interleave": True, "intermediate_size": 12288, "moe_intermediate_size": 2048,
            "num_experts_per_tok": 8, "n_shared_experts": 1, "n_group": 1, "topk_group": 1,
            "norm_topk_prob": True, "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
            "topk_method": "noaux_tc", "first_k_dense_replace": 3, "moe_layer_freq": 1,
            "num_nextn_predict_layers": 1, "rms_norm_eps": 1e-5, "attention_bias": False,
            "max_position_embeddings": 1048576, "tie_word_embeddings": False},
        reduced={"num_hidden_layers": (78, 5), "n_routed_experts": (256, 16),
                 "vocab_size": (154880, 19360)},
        assumed=("pre-norm", "rotated in pairs", "published DSA's", "the first 64",
                 "no Hadamard rotation", "ties in the selection", "holds no indexer weights",
                 "module's layer is full", "selection bias", "DeepSeek-V3's",
                 "before the final norm", "seeded random", "stand-in", "batch is 1",
                 "share of drafts kept", "style guide"),
        published=glm_published, entry=glm_entry, check_workflow=glm_workflow,
        metrics=frozenset({GROUPED, "experts_held_share_pct.lm", "mtp_accept_pct.lm",
                           "mtp_device_pct.lm", "mla_device_pct.lm", "indexer_device_pct.lm",
                           "keys_selected_pct.lm", "dsa_attend_device_pct.lm",
                           "dsa_select_device_pct.lm"}),
        imports=tuple(PLAIN_IMPORTS[:4]),  # no numpy of its own
    ),
    Model(
        name="granite-4.0-h-micro", served="granite-4.0-h-micro", tiny="tiny-granite-hybrid",
        workflow="longdoc-txt2img-granite-4.0-h-micro.json", config="granite-4.0-h-micro.json",
        reference="granite_hybrid.py", catalog="granite-4.0-h-micro",
        cell="granite_4_0_h_micro_longdoc_txt2img_512.closed2", prompt=256, cell_prompt=65536,
        new_tokens=16, drafts=0, trace=(5, 20),
        # tiny-granite-hybrid: 8 layers, each a mixer and a SwiGLU of 96: six Mamba-2 (4 heads
        # of 8 over a state of 16, one group, chunks of 8) and attention at 2 and 6 (4 query
        # heads over 2 key heads of 16), parts of 16 positions, a tied head of 4,096 ids. What
        # grows: two layers' keys and values; what does not: six float32 matrix states and
        # the convolutions' last 3 inputs
        attrs={
            "prompt_tokens": 256, "new_tokens": 16, "draft_tokens": 0, "decode_steps": 16,
            "layers": 8, "mamba_layers": 6, "attention_layers": 2, "prefill_part": 16,
            "prefill_parts": 16, "prefill_chunks": 32,
            "cache_bytes": 2 * 2 * 2 * (256 + 16) * 16 * 4,
            "state_bytes": 6 * (4 * 8 * 16 * 4 + 3 * (32 + 2 * 16) * 4),
            "tied_head_bytes": 4096 * 64 * 4, "node_id": "6"},
        drawn=frozenset(), drawn_check=lambda attrs: None,
        wait_bytes=4 * 16,  # the ids: the model reads nothing else back
        # the decode's two attention layers (the einsum form, a key head serving two
        # queries), a part's chunked scans (PR 55: the XLA form at these widths), then a
        # causal call a part of the prompt, each over the keys so far
        attention=", ".join(sorted(
            ["decode-xla 4x272x16", "ssd-xla 16x4x8 g1 n16 c8 f32"]
            + [f"xla-causal 16x{16 * (part + 1)}x16/16 bq16 f32" for part in range(16)])),
        passes=lambda attrs: (256 * 8, 16 * 8),
        widths={
            "hidden_size": 2048, "num_hidden_layers": 40, "num_attention_heads": 32,
            "num_key_value_heads": 8, "intermediate_size": 8192, "shared_intermediate_size": 8192,
            "vocab_size": 100352, "mamba_n_heads": 64, "mamba_d_head": 64, "mamba_d_state": 128,
            "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2, "mamba_chunk_size": 256,
            "mamba_conv_bias": True, "mamba_proj_bias": False, "attention_bias": False,
            "embedding_multiplier": 12, "attention_multiplier": 0.015625,
            "residual_multiplier": 0.22, "logits_scaling": 8, "rms_norm_eps": 1e-5,
            "hidden_act": "silu", "normalization_function": "rmsnorm", "rope_theta": 10000,
            "rope_scaling": None, "max_position_embeddings": 131072,
            "tie_word_embeddings": True},
        reduced={},
        assumed=("letter for letter", "pre-norm, two norms a layer", "no experts",
                 "no rotary embedding", "mamba_expand 2", "normed over all 4,096 channels",
                 "seeded random", "drawn as a head is", "hard-wired", "stand-in",
                 "ids 0-256 of 100,352", "batch is 1", "long document", "no MTP module"),
        published=granite_published, entry=granite_entry, check_workflow=granite_workflow,
        metrics=frozenset({"state_mb.lm", "ssm_device_pct.lm", "ssd_device_pct.lm",
                           "attn_device_pct.lm", "mlp_device_pct.lm",
                           "flash_attention_causal_roofline_pct.lm"}),
    ),
    Model(
        name="sdar-30b-a3b", served="sdar-30b-a3b-pp8-6l", tiny="tiny-sdar",
        workflow="rewrite-txt2img-sdar-30b-a3b.json", config="sdar-30b-a3b-chat.json",
        reference="sdar.py", catalog="SDAR-30B-A3B-Chat",
        cell="sdar_30b_a3b_rewrite_txt2img_512.closed2", prompt=2048, new_tokens=16, drafts=0,
        # tiny-sdar: 3 layers, 4 query heads over 2 key heads of 16, 8 experts of 32 columns
        # all held, 2 a token, 512 ids of which the last is the mask's, blocks of 4 filled in
        # by at most 4 passes. What grows: three layers' keys and values; nothing else
        attrs={
            "prompt_tokens": 2048, "new_tokens": 16, "draft_tokens": 0,
            "layers": 3, "block_length": 4, "denoising_steps": 4,
            "experts_held": 8, "experts_total": 8,
            "cache_bytes": 3 * 2 * 2 * (2048 + 16) * 16 * 4, "state_bytes": 0,
            "prefill_layer_passes": 2048 * 3, "prefill_routed_pairs": 2048 * 3 * 2,
            "prefill_routed_pairs_held": 2048 * 3 * 2, "prefill_expert_rows": 2048 * 3 * 2,
            "decode_expert_route": "xla", "prefill_expert_route": "xla", "node_id": "6"},
        drawn=frozenset(ROUTING - {"prefill_routed_pairs", "prefill_routed_pairs_held",
                                   "prefill_expert_rows", "decode_expert_route",
                                   "prefill_expert_route"}) | {
            "decode_steps", "denoise_passes", "closing_passes", "transferred_by_threshold",
            "transferred_by_floor", "decode_layer_passes", "decode_experts_read"},
        drawn_check=sdar_drawn,
        # the ids, the pairs per expert of either program and the five counts
        wait_bytes=4 * (16 + 3 * 8 + 3 * 8 + 5),
        # a pass's four queries over the cache, the block's own entries among what they see;
        # the prefill under the block mask
        attention="decode-xla 4x2064x16, xla-causal 2048x2048x16/16 b4 bq256 f32",
        grouped=(2048, 2, 8, 8, 64, 32),
        passes=lambda attrs: (2048 * 3, attrs["decode_layer_passes"]),
        widths={
            "hidden_size": 2048, "num_attention_heads": 32, "num_key_value_heads": 4,
            "head_dim": 128, "intermediate_size": 6144, "moe_intermediate_size": 768,
            "num_experts": 128, "num_experts_per_tok": 8, "norm_topk_prob": True,
            "decoder_sparse_step": 1, "mlp_only_layers": [], "vocab_size": 151936,
            "rms_norm_eps": 1e-6, "rope_theta": 1000000, "rope_scaling": None,
            "attention_bias": False, "hidden_act": "silu", "sliding_window": None,
            "use_sliding_window": False, "max_window_layers": 48,
            "max_position_embeddings": 32768, "tie_word_embeddings": False,
            "model_type": "sdar_moe"},
        reduced={"num_hidden_layers": (48, 6)},
        assumed=("generate.py", "low_confidence_dynamic", "kept as a boolean",
                 "closing pass runs no head", "Qwen3-MoE", "seeded random", "stand-in",
                 "batch is 1", "house style guide"),
        published=sdar_published, entry=sdar_entry, check_workflow=sdar_workflow,
        metrics=frozenset({GROUPED, "experts_held_share_pct.lm", "attn_device_pct.lm",
                           "denoise_passes_per_token.lm", "experts_device_pct.lm",
                           "expert_union_hbm_pct.lm"}),
    ),
    Model(
        name="dots3-note-prev", served="dots3-note-prev-ep8-5l", tiny="tiny-dots3",
        workflow="longdoc-txt2img-dots3-note.json", config="dots3-note-prev.json",
        reference="dots3.py", catalog="dots3-note-prev",
        cell="dots3_note_longdoc_txt2img_512.closed2", prompt=2048, cell_prompt=32768,
        new_tokens=16, drafts=0,
        # tiny-dots3: published layers 0-4, two full layers (4 heads 8 + 8 wide, latents of
        # 16 + 8, an index of 2 heads of 16 keeping 8 positions) and three sliding ones (2
        # heads 12 + 8 wide, latents of 32 + 8, a window of 5 on a ring of 8), parts of 16
        # positions, 4 of 8 experts held, 2 a token. What grows: a latent and an index key a
        # full layer; what does not: three rings
        attrs={
            "prompt_tokens": 2048, "new_tokens": 16, "draft_tokens": 0, "decode_steps": 16,
            "layers": 5, "full_layers": 2, "window_layers": 3, "window": 5, "ring_positions": 8,
            "index_topk": 8, "indexer_layers": 2, "prefill_part": 16, "prefill_parts": 128,
            "experts_held": 4, "experts_total": 8,
            "cache_bytes": (2048 + 16) * 2 * (24 + 16) * 4,
            "indexer_cache_bytes": (2048 + 16) * 2 * 16 * 4, "state_bytes": 3 * 8 * 40 * 4,
            "prefill_sparse_attention_form": "gathered", "prefill_selection_form": "sort",
            "decode_sparse_attention_form": "masked",
            # every position once in two full layers: t + 1 visible, min(t + 1, 8) read
            "keys_visible": 2 * (2064 * 2065 // 2),
            "keys_selected": 2 * (8 * 9 // 2 + (2064 - 8) * 8),
            # the band over the prompt in three sliding layers: min(t + 1, 5) seen; XLA's
            # block of a part's 16 rows over the 4 before it and its own
            "prefill_band_keys_seen": 3 * (5 * 6 // 2 + (2048 - 5) * 5),
            "prefill_band_keys_computed": 3 * (16 * 16 + 127 * 16 * 20),
            "prefill_band_route": "xla",
            "prefill_layer_passes": 2048 * 5, "decode_layer_passes": 16 * 5,
            "prefill_routed_pairs": 2048 * 4 * 2, "decode_routed_pairs": 16 * 4 * 2,
            "decode_expert_rows": 16 * 4 * 2,
            "decode_expert_route": "xla", "prefill_expert_route": "xla", "node_id": "6"},
        drawn=frozenset(ROUTING - {"prefill_routed_pairs", "decode_routed_pairs",
                                   "decode_expert_rows", "decode_expert_route",
                                   "prefill_expert_route"}) | {
            "decode_experts_read"},
        drawn_check=dots3_drawn,
        # the ids; a part's pairs per held expert and keys seen a full layer (128 parts);
        # the decode's of both and the experts it read
        wait_bytes=4 * (16 + 128 * 4 * 4 + 128 * 2 * 2 + 4 * 4 + 2 * 2 + 1),
        # a part's 16 queries over the rows a top-k chose, gathered, and a step's one under
        # the mask the bisection gave; the top-k a sort at each rung of the lengths' ladder;
        # a part's band over its own latents (the first) and over the 4 before them too
        attention=", ".join(sorted(
            ["dsa-gathered 16x2064 k8 h4 f32", "dsa-masked 1x2064 k2064 h4 f32",
             "xla-causal 16x16x20/8 w5 bq16 f32", "xla-causal 16x20x20/8 w5 bq16 f32"]
            + [f"dsa-select-sort 16x{length} k8"
               for length in (16, 32, 64, 128, 256, 512, 1024, 2048, 2064)])),
        passes=lambda attrs: (2048 * 5, 16 * 5),
        widths={
            "hidden_size": 5120, "num_attention_heads": 128, "num_key_value_heads": 128,
            "q_lora_rank": 1024, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
            "qk_rope_head_dim": 64, "v_head_dim": 128, "rope_theta": 80000000,
            "index_n_heads": 64, "index_head_dim": 128, "index_topk": 2048,
            "sliding_window_size": 513, "swa_num_attention_heads": 64,
            "swa_num_key_value_heads": 64, "swa_q_lora_rank": 1024, "swa_kv_lora_rank": 1024,
            "swa_qk_nope_head_dim": 192, "swa_qk_rope_head_dim": 64, "swa_v_head_dim": 128,
            "swa_rope_theta": 50000, "attention_gate_type": "headwise",
            "swa_attention_gate_type": "headwise", "apply_mla_qkv_lora_rescale": True,
            "intermediate_size": 13824, "moe_intermediate_size": 1536,
            "num_experts_per_tok": 8, "n_shared_experts": 1, "norm_topk_prob": True,
            "routed_scaling_factor": 1, "scoring_func": "sigmoid", "topk_method": "noaux_tc",
            "first_k_dense_replace": 1, "moe_layer_freq": 1, "rms_norm_eps": 1e-5,
            "attention_bias": False, "hidden_act": "silu", "rope_scaling": None,
            "max_position_embeddings": 524288, "tie_word_embeddings": False},
        reduced={"num_hidden_layers": (46, 5), "n_routed_experts": (256, 32),
                 "vocab_size": (152064, 19008)},
        assumed=("pre-norm", "mla_scale_q_lora", "arXiv:2505.06708", "rotated in pairs",
                 "published DSA's", "only full_attention layers have an index",
                 "counts the query's own position", "selection bias",
                 "no MTP module and no tower", "seeded random", "stand-in", "batch is 1",
                 "style guide"),
        published=dots3_published, entry=dots3_entry, check_workflow=dots3_workflow,
        metrics=frozenset({GROUPED, "experts_held_share_pct.lm", "state_mb.lm", "mla_device_pct.lm",
                           "indexer_device_pct.lm", "keys_selected_pct.lm",
                           "dsa_attend_device_pct.lm", "dsa_select_device_pct.lm",
                           "experts_device_pct.lm", "window_latent_device_pct.lm",
                           "band_keys_seen_pct.lm", "flash_attention_band_roofline_pct.lm"}),
        imports=tuple(PLAIN_IMPORTS[:4]),  # no numpy of its own
    ),
    Model(
        name="longcat-flash-chat", served="longcat-flash-chat-ep64-4l", tiny="tiny-longcat-flash",
        workflow="longdoc-txt2img-longcat-flash.json", config="longcat-flash-chat.json",
        reference="longcat_flash.py", catalog="LongCat-Flash-Chat",
        cell="longcat_flash_longdoc_txt2img_512.closed2", prompt=2048, cell_prompt=32768,
        new_tokens=16, drafts=0, trace=(5, 20),
        # tiny-longcat-flash: 2 layers of two latent attentions (4 heads 8 + 8 wide, latents
        # of 16 + 8, two heads a call), two dense feed-forwards and the expert branch: a
        # router of 12 over 8 experts (4 held) and 4 identities, 3 a token; parts of 16
        # positions, the branch in blocks of 8. What grows: four latent caches
        attrs={
            "prompt_tokens": 2048, "new_tokens": 16, "draft_tokens": 0, "decode_steps": 16,
            "layers": 2, "attention_sublayers": 4, "prefill_part": 16, "expert_block": 8,
            "prefill_parts": 128, "experts_held": 4, "experts_total": 8, "zero_experts": 4,
            "cache_bytes": (2048 + 16) * 4 * 24 * 4, "state_bytes": 0,
            "prefill_routed_pairs": 2048 * 2 * 3, "decode_routed_pairs": 16 * 2 * 3,
            "decode_expert_rows": 16 * 2 * 3, "decode_expert_route": "xla",
            "prefill_expert_route": "xla", "node_id": "6"},
        drawn=frozenset(ROUTING - {"prefill_routed_pairs", "decode_routed_pairs",
                                   "decode_expert_rows", "decode_expert_route",
                                   "prefill_expert_route"}) | {
            "decode_experts_read", "prefill_zero_pairs", "decode_zero_pairs",
            "real_experts_per_token_mean", "real_experts_per_token_min",
            "real_experts_per_token_max"},
        drawn_check=longcat_drawn,
        # the ids; a part's pairs per held expert a layer and block and its histogram of real
        # experts a token (128 parts); the decode's of both and the experts it read
        wait_bytes=4 * (16 + 128 * 2 * 2 * 4 + 128 * 4 + 2 * 4 + 4 + 1),
        # a part's 16 queries, two heads a call, over the rows so far: a call a count of keys
        attention=", ".join(sorted(
            f"xla-causal 16x{keys}x16/8 bq16 f32" for keys in range(16, 2049, 16))),
        passes=lambda attrs: (2048 * 2, 16 * 2),
        widths={
            "hidden_size": 6144, "num_attention_heads": 64, "q_lora_rank": 1536,
            "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
            "v_head_dim": 128, "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
            "moe_topk": 12, "zero_expert_num": 256, "zero_expert_type": "identity",
            "routed_scaling_factor": 6, "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
            "rope_theta": 10000000, "rms_norm_eps": 1e-5, "attention_bias": False,
            "attention_method": "MLA", "max_position_embeddings": 131072, "hidden_act": "silu",
            "tie_word_embeddings": False},
        reduced={"num_layers": (28, 4), "n_routed_experts": (512, 8),
                 "vocab_size": (131072, 16384)},
        assumed=("modeling_longcat_flash.py", "lines 418-480", "default 1e-6",
                 "apply_rotary_pos_emb_interleave", "e_score_correction_bias", "nn.Identity",
                 "no multi-token-prediction layer", "seeded random", "stand-in", "batch is 1",
                 "style guide"),
        published=longcat_published, entry=longcat_entry, check_workflow=longcat_workflow,
        metrics=frozenset({"experts_held_share_pct.lm", "mla_device_pct.lm", "mlp_device_pct.lm",
                           "experts_device_pct.lm", "zero_expert_pairs_pct.lm",
                           "shortcut_device_pct.lm", "flash_attention_latent_roofline_pct.lm"}),
        imports=tuple(PLAIN_IMPORTS[:4]),  # no numpy of its own
    ),
]
BY_NAME = {m.name: m for m in MODELS}
LM_ENTRIES = sorted(name for name, entry in MODEL_REGISTRY.items() if entry["family"] == "lm")


def rehearsed(model, **text_generate):
    """A committed graph with its cell's own rehearsal edits."""
    prompt = load(os.path.join("workflows", model.workflow))
    for edit in load(f"benchmark/workloads/{model.cell}.json")["rehearsal"]["set"]:
        for node in prompt.values():
            if node["class_type"] == edit["class_type"]:
                node["inputs"][edit["input"]] = edit["value"]
    by_kind(prompt)["TextGenerate"].update(text_generate)
    return prompt


def counted():
    """The three `cdt_lm_*` counters as they stand."""
    registry = get_metrics_registry()
    values = {"steps": registry.counter("cdt_lm_decode_steps_total", "").value()}
    for name in ("cdt_lm_tokens_total", "cdt_lm_layer_passes_total"):
        counter = registry.counter(name, "", ("phase",))
        for phase in ("prefill", "decode"):
            values[name, phase] = counter.value(phase=phase)
    return values


def node_attrs(prompt):
    tracer = get_tracer()
    with tracer.span("execute_prompt") as root:
        GraphExecutor(ExecutionContext()).execute(prompt)
    (node,) = spans_named(tracer.spans(root.trace_id), "node.TextGenerate")
    return node["attrs"]


@pytest.fixture(scope="module", params=MODELS, ids=str)
def model(request):
    return request.param


@pytest.fixture(scope="module")
def served(model, tmp_path_factory):
    """Seeds 42, 43 and 42 again through one executor, which builds the
    model's programs once for every test of its row: (PNG bytes, spans,
    outputs, programs built, what the counters grew by) per request."""
    from comfyui_distributed_tpu.telemetry import runtime

    runtime.install_jax_monitoring()
    graph = rehearsed(model)
    out_dir = tmp_path_factory.mktemp("out")
    os.environ["CDT_OUTPUT_DIR"] = str(out_dir)
    executor, tracer, runs = GraphExecutor(ExecutionContext()), get_tracer(), []
    try:
        for seed in (42, 43, 42):
            by_kind(graph)["DistributedSeed"]["seed"] = seed
            compiles, counters = runtime.tallies()["compiles"], counted()
            with tracer.span("execute_prompt") as root:
                outputs = executor.execute(graph)
            built = runtime.tallies()["compiles"] - compiles
            grew = {key: value - counters[key] for key, value in counted().items()}
            (name,) = [i["ui"]["images"] for r in outputs.values() for i in r
                       if isinstance(i, dict) and "images" in i.get("ui", {})][0]
            with open(os.path.join(out_dir, name), "rb") as fh:
                runs.append((fh.read(), tracer.spans(root.trace_id), outputs, built, grew))
    finally:
        os.environ.pop("CDT_OUTPUT_DIR", None)
    return runs


# --- what every model's served path does ---------------------------------------


def test_a_request_gives_a_png_and_the_text_that_was_drawn(model, served):
    png, _, outputs, _, _ = served[0]
    assert png[:8] == b"\x89PNG\r\n\x1a\n"
    texts = [i["ui"]["text"] for r in outputs.values() for i in r
             if isinstance(i, dict) and "text" in i.get("ui", {})]
    assert len(texts) == 1 and len(texts[0]) == 1
    words = texts[0][0].split()
    assert 0 < len(words) <= model.new_tokens and texts[0][0] == texts[0][0].strip()


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
    for _, spans, _, _, _ in served:
        assert len(spans_named(spans, "node.TextGenerate")) == 1
        assert len(spans_named(spans, "lm.prefill")) == 1


def test_node_textgenerate_says_what_its_row_says_and_nothing_else(model, served):
    """The attributes' names are the row's exactly, whatever model ran
    before in this process; the values the shapes give are the row's,
    the drawn ones pass the row's own check."""
    (node,) = spans_named(served[1][1], "node.TextGenerate")
    attrs = node["attrs"]
    assert set(attrs) - BUILD_TALLIES == set(model.attrs) | model.drawn
    assert {key: attrs[key] for key in model.attrs} == model.attrs
    model.drawn_check(attrs)
    # and on the request that traced the programs, which attention they took
    (first,) = spans_named(served[0][1], "node.TextGenerate")
    assert set(first["attrs"]) - BUILD_TALLIES == set(model.attrs) | model.drawn | {"attention"}


def test_the_spans_under_the_node_are_dispatch_one_wait_and_detokenize(model, served):
    spans = served[1][1]
    (node,) = spans_named(spans, "node.TextGenerate")
    below = [s["name"] for s in spans if s["parent_id"] == node["span_id"]]
    assert below == ["lm.prefill", "device.run", "lm.decode", "device.run", "device.wait",
                     "lm.detokenize"]
    assert [s["attrs"]["program"] for s in spans_named(spans, "device.run")
            if s["parent_id"] == node["span_id"]] == ["prefill", "decode"]
    (wait,) = [s for s in spans_named(spans, "device.wait") if s["parent_id"] == node["span_id"]]
    assert wait["attrs"]["bytes"] == model.wait_bytes


def test_only_the_request_that_traced_the_programs_says_which_attention(model, served):
    (first,) = spans_named(served[0][1], "node.TextGenerate")
    assert first["attrs"]["attention"] == model.traced_routes()
    (second,) = spans_named(served[1][1], "node.TextGenerate")
    assert "attention" not in second["attrs"]


def test_tokens_steps_and_layer_passes_are_counted_by_phase(model, served):
    """Three counters, fed from the span's attributes and the contract's
    defaults: a model that says nothing of steps or passes counts a step a
    token and tokens x layers."""
    for _, spans, _, _, grew in served:
        (node,) = spans_named(spans, "node.TextGenerate")
        prefill_passes, decode_passes = model.passes(node["attrs"])
        assert grew == {
            "steps": node["attrs"]["decode_steps"],
            ("cdt_lm_tokens_total", "prefill"): model.prompt,
            ("cdt_lm_tokens_total", "decode"): model.new_tokens,
            ("cdt_lm_layer_passes_total", "prefill"): prefill_passes,
            ("cdt_lm_layer_passes_total", "decode"): decode_passes,
        }


def test_the_loader_reports_the_lm_part(served):
    (loader,) = spans_named(served[0][1], "node.CheckpointLoaderSimple")
    assert loader["attrs"]["lm_bytes"] == 4 * loader["attrs"]["lm_params"] > 0
    assert not any(key.startswith(("unet_", "vae_", "te_")) for key in loader["attrs"])
    assert spans_named(served[1][1], "node.CheckpointLoaderSimple") == []  # cached


# --- the committed files of every model ------------------------------------------


def test_the_workflow_is_the_first_one_but_for_what_its_row_names(model):
    mine = load(os.path.join("workflows", model.workflow))
    model.check_workflow(mine, load(os.path.join("workflows", MODELS[0].workflow)))
    inputs = by_kind(mine)
    config = load(os.path.join("benchmark/configs", model.config))
    assert inputs["CheckpointLoaderSimple"]["ckpt_name"] == config["registry_name"] == model.served
    # a token a byte, and begin-of-sentence: the rehearsal keeps the cell's prompt
    # (or, the long document's, its first bytes)
    assert len(ByteTokenizer().encode(inputs["TextGenerate"]["text"])) == (
        model.cell_prompt or model.prompt)


@pytest.mark.parametrize("kind", ["workflows", "reference"])
def test_the_benchmarks_copies_are_the_committed_files(model, kind):
    mine, theirs = {
        "workflows": (f"benchmark/workflows/{model.workflow}", f"workflows/{model.workflow}"),
        "reference": (f"benchmark/reference/{model.reference}",
                      f"comfyui_distributed_tpu/reference/{model.reference}"),
    }[kind]
    with open(os.path.join(ROOT, mine), "rb") as a, open(os.path.join(ROOT, theirs), "rb") as b:
        assert a.read() == b.read()


def test_the_reference_imports_nothing_of_the_system(model):
    with open(os.path.join(ROOT, "comfyui_distributed_tpu/reference", model.reference),
              encoding="utf-8") as fh:
        imports = [line for line in fh if line.startswith(("import ", "from "))]
    assert sorted(imports) == sorted(model.imports)


def test_the_configuration_keeps_every_published_width_and_states_its_cut(model):
    config = load(os.path.join("benchmark/configs", model.config))
    for key, value in model.widths.items():
        assert config[key] == value, key
    assert config["reduced"] == list(model.reduced)
    for key, (published, held) in model.reduced.items():
        assert (config["published"][key], config[key]) == (published, held), key
    assert config["reference"] == f"benchmark/reference/{model.reference}"
    assumed = " ".join(config["assumed"])
    for word in model.assumed:
        assert word in assumed, word
    model.published(config)


def test_the_configuration_file_is_the_catalogs_row_but_for_the_cut(model):
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    with open(path, encoding="utf-8") as fh:
        row = next(row for row in map(json.loads, fh) if row["name"] == model.catalog)
    config = load(os.path.join("benchmark/configs", model.config))
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in model.reduced:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key


def test_the_registry_entry_is_the_configuration_file(model):
    from comfyui_distributed_tpu.models.registry import get_config

    config = load(os.path.join("benchmark/configs", model.config))
    cfg = get_config(config["registry_name"])
    for key in model.widths:
        if hasattr(cfg, key):
            assert getattr(cfg, key) == config[key], key
    depth = "num_hidden_layers" if "num_hidden_layers" in config else "num_layers"
    assert getattr(cfg, depth) == config[depth]
    model.entry(cfg, config)


def test_the_manifest_has_the_cell_its_configuration_and_its_metrics(model):
    manifest = load("BENCHMARK.json")
    (cell,) = [w for w in manifest["workloads"] if w["name"] == model.cell]
    name = model.config.removesuffix(".json")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (name, "closed2", 1)
    assert len(cell["why"]) <= 200
    (config,) = [c for c in manifest["configs"] if c["name"] == name]
    assert config["reduced"] == list(model.reduced)
    assert config["file"] == f"benchmark/configs/{model.config}"
    assert config["source"] == load(config["file"])["source"]
    metrics = manifest["per_layer"] + manifest["end_to_end"]
    listed = {m["name"] for m in metrics if model.cell in m.get("workloads", [])}
    assert listed == LM_METRICS | model.metrics
    for metric in manifest["per_layer"]:
        if metric["name"] in model.metrics | {"cache_gb.lm", "layer_passes_per_token.lm"}:
            assert metric["moves"] == "images_per_s"
            assert os.path.exists(
                os.path.join(ROOT, "benchmark", "layer_metrics", metric["name"] + ".py"))
    # the cells in the order the models came: a later one is appended, never put inside
    assert [w["name"] for w in manifest["workloads"]][-len(MODELS):] == [m.cell for m in MODELS]
    work = load(f"benchmark/workloads/{model.cell}.json")
    assert work["workflow"] == f"benchmark/workflows/{model.workflow}"
    assert work["seed_nodes"] == ["DistributedSeed"]
    assert work["compute_nodes"] == ["TextGenerate", "KSampler"]
    assert work["rate"] == {"metric": "images_per_s", "units_per_job": 1}
    assert work["trace"] == dict(zip(("start_s", "slice_s"), model.trace))
    edits = {(e["class_type"], e["input"]): e["value"] for e in work["rehearsal"]["set"]}
    assert edits["CheckpointLoaderSimple", "ckpt_name"] == model.tiny
    assert edits["TextGenerate", "max_new_tokens"] == model.new_tokens


# --- the one contract, over every registry entry of the family -----------------


@pytest.mark.parametrize("name", LM_ENTRIES)
def test_every_language_model_meets_the_one_contract(name):
    """What `TextGenerate` asks of a bundle's `lm` part (`lm_common`); the
    model, not the node, says how many bytes each kind of state is, and
    the class, not the node, what a model that says nothing counts."""
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models.registry import create_model

    assert {m.served for m in MODELS} | {m.tiny for m in MODELS} == set(LM_ENTRIES)
    lm = create_model(name)
    for attribute in ("cfg", "tokenizer", "dtype", "layer_passes", "init", "prefill", "decode",
                      "read_back", "report", "counted", "draft_tokens_max"):
        assert hasattr(lm, attribute), attribute
    assert isinstance(lm.tokenizer, ByteTokenizer)
    (model,) = [m for m in MODELS if name in (m.served, m.tiny)]
    assert lm.draft_tokens_max == model.drafts
    with pytest.raises(ValueError, match="draft_tokens"):
        lm.decode(None, None, None, 0, None, 4, 1.0, draft_tokens=model.drafts + 1)

    held, top = len(getattr(lm.cfg, "held_experts", ())), getattr(lm.cfg, "moe_topk", 0)
    # the blocks the expert branch cuts a part of the 100 tokens below into
    blocks = -(-min(100, getattr(lm.cfg, "prefill_part", 100)) // getattr(lm.cfg, "expert_block", 100))
    read = {
        "ouro": ([1.0] * 4, [0.5] * 4),
        # a part's loads and keys seen (visible, read) a layer, then the decode's
        "glm-5.2": ([[[3] * held]], [[[9], [5]]], [[1] * held], [[7], [2]]),
        "granite-4.0-h-micro": (),  # nothing is read back beside the ids
        # the loads of either program and the decode's five counts: one block of one pass
        "sdar-30b-a3b": ([[3] * held], [[1] * held], [1, 1, 0, 4, 9]),
        # a part's loads and keys seen (visible, read) a full layer, the decode's, its reads
        "dots3-note-prev": ([[[3] * held]], [[[9, 9], [5, 5]]], [[1] * held], [[7, 7], [2, 2]], 2),
        # a part's loads a layer and block and its histogram of real experts a token, the
        # decode's of both, its reads
        "longcat-flash-chat": (
            [[[[3] * held] * blocks]], [[1] * (top + 1)], [[1] * held], [1] * (top + 1), 2),
    }.get(model.name, ([[3] * held], [[1] * held]))
    if model.drafts:
        read += ([4, 0, 0, 2],)

    def report(cache_len):
        return lm.report(100, 4, cache_len, *read)

    said = report(128)
    assert said["layers"] == (
        lm.cfg.num_layers if model.name == "longcat-flash-chat" else lm.cfg.num_hidden_layers)
    assert said["cache_bytes"] > 0 and isinstance(said["cache_bytes"], int)
    assert said["state_bytes"] >= 0 and isinstance(said["state_bytes"], int)
    assert report(256)["cache_bytes"] == 2 * said["cache_bytes"]
    assert report(256)["state_bytes"] == said["state_bytes"]
    # the part the shapes alone give, which the benchmark's reader tests call by itself
    assert lm.describe(128).items() <= said.items()
    # a step a token and tokens x layers, unless the model's own report says them
    assert lm.counted({}, 100, 4) == {
        "decode_steps": 4, "prefill_layer_passes": 100 * lm.layer_passes,
        "decode_layer_passes": 4 * lm.layer_passes}
    assert lm.counted(said, 100, 4) == {
        key: said.get(key, value) for key, value in lm.counted({}, 100, 4).items()}
    assert lm.counted({"decode_steps": 3, "decode_layer_passes": 7, "layers": 9}, 100, 4) == {
        "decode_steps": 3, "prefill_layer_passes": 100 * lm.layer_passes,
        "decode_layer_passes": 7}
    lm.dtype = jnp.dtype(jnp.bfloat16)  # what `init(key, bfloat16)` records
    assert report(128)["cache_bytes"] == said["cache_bytes"] // 2


@pytest.mark.parametrize("name, passes", [
    ("tiny-deepseek-v2", 3), ("deepseek-v2-ep4-5l", 5), ("ouro-2.6b", 192),
    ("solar-open2-ep8-4l", 4), ("k-exaone-ep8-5l", 5), ("ling-flash-ep8-7l", 7),
    ("nemotron3-nano-ep16-52l", 52), ("glm-5.2-ep16-5l", 5), ("granite-4.0-h-micro", 40),
    ("sdar-30b-a3b-pp8-6l", 6), ("dots3-note-prev-ep8-5l", 5),
    ("longcat-flash-chat-ep64-4l", 4)])
def test_a_token_walks_its_layers_once_for_each_pass_of_the_loop(name, passes):
    from comfyui_distributed_tpu.models.registry import create_model

    assert create_model(name).layer_passes == passes


# --- the shared decode loop against each model's own step ----------------------


def _step_of(name):
    """(module, `step(cfg, params, cache, token, position) -> (logits,
    cache, what the decode sums over its steps)`) of a tiny model."""
    from comfyui_distributed_tpu.models import (
        deepseek_v2, dots3, glm_dsa, granite_hybrid, k_exaone, ling_flash, longcat_flash,
        nemotron_h, ouro, solar_open2)

    def dots3_step(cfg, params, cache, token, position):
        row, cache, _, loads, _, _ = dots3.decode_step(cfg, params, dict(cache), token, position)
        return row, cache, loads

    def granite_step(cfg, params, cache, token, position):
        return (*granite_hybrid.decode_step(cfg, params, cache, token, position), 0)

    def glm_step(cfg, params, cache, token, position):
        rows, _, cache, _, loads, _, _ = glm_dsa.main_step(
            cfg, params, dict(cache), token[None], position)
        return rows[0], cache, loads

    def one_position(module):
        def step(cfg, params, cache, token, position):
            rows, _, cache, _, loads = module.main_step(
                cfg, params, dict(cache), token[None], position)
            return rows[0], cache, loads
        return step

    def ouro_step(cfg, params, cache, token, position):
        logits, cache, _, exits = ouro.decode_step(cfg, params, cache, token, position)
        return logits, cache, exits

    def with_loads(module):
        def step(cfg, params, cache, token, position):
            logits, cache, _, loads = module.decode_step(cfg, params, cache, token, position)
            return logits, cache, loads
        return step

    return {
        "deepseek-v2": (deepseek_v2, with_loads(deepseek_v2)), "ouro": (ouro, ouro_step),
        "solar-open2": (solar_open2, with_loads(solar_open2)),
        "k-exaone": (k_exaone, one_position(k_exaone)),
        "ling-flash": (ling_flash, one_position(ling_flash)),
        "nemotron3-nano": (nemotron_h, with_loads(nemotron_h)),
        "glm-5.2": (glm_dsa, glm_step),
        "granite-4.0-h-micro": (granite_hybrid, granite_step),
        "dots3-note-prev": (dots3, dots3_step),
        "longcat-flash-chat": (longcat_flash, with_loads(longcat_flash)),
    }[name]


# the models that emit their tokens in order; SDAR's decode is `denoise_loop`, held below
IN_ORDER = [name for name in BY_NAME if name != "sdar-30b-a3b"]


@pytest.mark.parametrize("steps", [1, 5])
@pytest.mark.parametrize("name", IN_ORDER)
def test_decode_is_the_models_own_step_walked_with_the_same_folded_keys(name, steps):
    """`lm_common.decode_loop` under each model's `decode`: the ids are a
    Python loop's over the model's own step (`sample` of the logits under
    the key folded by the step's index), `steps` of them always, every
    kept row of logits is that step's, and the tally is the steps' sum."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from comfyui_distributed_tpu.models.lm_common import sample
    from comfyui_distributed_tpu.models.registry import create_model

    prompt, temperature = 12, jnp.float32(0.8)
    module, step = _step_of(name)
    step = jax.jit(step, static_argnums=0)
    lm = create_model(BY_NAME[name].tiny)
    cfg, key = lm.cfg, jax.random.key(steps)
    params = lm.init(jax.random.key(1))
    ids = jax.random.randint(jax.random.key(2), (prompt,), 0, getattr(
        cfg, "vocab_held", cfg.vocab_size))

    walked = lm.prefill(params, ids, prompt + steps)  # the decode below may take its own by donation
    cache, logits, tokens, rows, tally = walked.cache, walked.logits, [], [], 0
    for i in range(steps):
        tokens.append(sample(logits, jax.random.fold_in(key, i), temperature))
        logits, cache, added = step(cfg, params, cache, tokens[-1], jnp.int32(prompt + i))
        rows.append(logits)
        tally = tally + np.asarray(added)

    first = lm.prefill(params, ids, prompt + steps)
    decode = lm.decode(params, first.cache, first.logits, prompt, key, steps, temperature, True)
    assert decode.ids.shape == (steps,) and decode.ids.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(decode.ids), np.asarray(jnp.stack(tokens)))
    drafts = BY_NAME[name].drafts
    kept = decode.logits if hasattr(decode, "logits") else decode.kept["logits"]
    np.testing.assert_allclose(np.asarray(kept), np.asarray(jnp.stack(rows)), rtol=1e-5, atol=1e-5)
    if name == "ouro":
        np.testing.assert_allclose(np.asarray(decode.exit), tally, rtol=1e-5)
        return
    if not hasattr(decode, "loads"):  # a step that adds nothing to a tally: no router, no exit
        return
    loads = np.asarray(decode.loads)
    if drafts:  # the MTP module's row last, which no plain step runs
        assert not loads[-1].any()
        assert np.asarray(decode.counts).tolist()[:3] == [steps, 0, 0]
        loads = loads[:-1]
    np.testing.assert_array_equal(loads, tally)
    assert loads.sum() <= steps * loads.shape[0] * (
        cfg.moe_topk if name == "longcat-flash-chat" else cfg.num_experts_per_tok)


@pytest.mark.parametrize("collect", [False, True])
def test_the_loop_sums_what_a_step_adds_and_keeps_what_it_hands_over(collect):
    """The loop by itself, under a step that is arithmetic: the cache is
    threaded, the summed tree is added leaf by leaf from zero (its shapes
    are the step's own, asked of it without running it), the kept rows
    are the steps' in order, and a step that keeps None has none."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from comfyui_distributed_tpu.models.lm_common import decode_loop

    def step(cache, token, position):
        logits = jnp.zeros((7,)).at[(position + 1) % 7].set(50.0)  # the next id: position + 1
        kept = {"at": position, "token": token} if collect else None
        return logits, cache + 1, (jnp.ones((2,), jnp.int32), token), kept

    start_logits = jnp.zeros((7,)).at[3].set(50.0)
    cache, ids, (ones, tokens), rows = jax.jit(lambda: decode_loop(
        step, jnp.int32(0), start_logits, jnp.int32(3), jax.random.key(0), jnp.float32(0.0), 4))()
    assert int(cache) == 4 and np.asarray(ids).tolist() == [3, 4, 5, 6]
    assert np.asarray(ones).tolist() == [4, 4] and int(tokens) == 3 + 4 + 5 + 6
    if collect:
        assert np.asarray(rows["at"]).tolist() == np.asarray(rows["token"]).tolist() == [3, 4, 5, 6]
    else:
        assert rows is None


# --- the shared drafting loop against each drafting model's own two steps --------


@functools.lru_cache(maxsize=None)  # a model's steps are compiled once for its four cases
def _drafting_steps_of(name):
    """(`mtp(cfg, params, cache, h, after, position) -> (logits, cache, what
    the decode sums of the module)`, `main(cfg, params, cache, tokens,
    position) -> (logits, h, cache, what it sums of the main layers)`,
    what the model does to its state once a draft's fate is known,
    `summed(decode) -> (the module's, the main layers')`) of a tiny model."""
    import jax

    from comfyui_distributed_tpu.models import glm_dsa, k_exaone, ling_flash

    module = {"k-exaone": k_exaone, "ling-flash": ling_flash, "glm-5.2": glm_dsa}[name]

    def mtp(cfg, params, cache, h, after, position):
        out = module.mtp_step(cfg, params, dict(cache), h, after, position)
        if module is glm_dsa:
            logits, cache, loads, _, keys = out
            return logits, cache, (loads, keys)
        return out[0], out[1], (out[3],)

    def main(cfg, params, cache, tokens, position):
        rows, h, cache, _, *added = module.main_step(cfg, params, dict(cache), tokens, position)
        return rows, h, cache, (added[0], added[2]) if module is glm_dsa else (added[0],)

    def settle(cache, accepted):
        if module is ling_flash:
            return {**cache, "slot": ling_flash.standing(cache["slot"], accepted)}
        return cache

    def summed(decode):
        loads = decode.loads
        if module is glm_dsa:
            return (loads[-1], decode.keys[:, -1:]), (loads[:-1], decode.keys[:, :-1])
        return (loads[-1],), (loads[:-1],)

    return (jax.jit(mtp, static_argnums=0), jax.jit(main, static_argnums=0), jax.jit(settle),
            summed)


@pytest.mark.parametrize("temperature", [0.0, 0.8])
@pytest.mark.parametrize("name, steps", [  # a step count is a program: one id for one model here,
    ("k-exaone", 1), *((m.name, 5) for m in MODELS if m.drafts)])  # for each in its own file
def test_drafting_is_the_models_own_two_steps_walked_with_the_same_folded_keys(
        name, steps, temperature):
    """`lm_common.draft_loop` under each drafting model's `decode`: the ids
    are a Python loop's over the model's own `mtp_step`, `main_step` and
    `verify` (id 0 under the key folded by 0, step s under the key folded
    by s + 1 and split in two), `steps` of them always, the counts are the
    steps it took and the drafts it kept, the tallies the steps' sums, the
    module's apart, and every kept row that step's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from comfyui_distributed_tpu.models.lm_common import sample, verify
    from comfyui_distributed_tpu.models.registry import create_model

    prompt, temperature = 12, jnp.float32(temperature)
    mtp, main, settle, summed = _drafting_steps_of(name)
    lm = create_model(BY_NAME[name].tiny)
    cfg, key = lm.cfg, jax.random.key(steps)
    params = lm.init(jax.random.key(1))
    ids = jax.random.randint(jax.random.key(2), (prompt,), 0, cfg.vocab_held)

    walked = lm.prefill(params, ids, prompt + 5)  # the decode below takes its own by donation
    cache = walked.cache
    first = sample(walked.logits, jax.random.fold_in(key, 0), temperature)
    tokens, last, waiting, after = [first], first, 1, jnp.stack([first, jnp.int32(0)])
    h = jnp.stack([cache["h"], jnp.zeros_like(cache["h"])])
    rows, fates, positions, of_module, of_main = [], [], [], [], []
    while len(tokens) < steps:
        n = prompt + len(tokens) - 1
        key_draft, key_verify = jax.random.split(jax.random.fold_in(key, len(rows) + 1))
        drafted, cache, added = mtp(cfg, params, cache, h, after, jnp.int32(n - waiting))
        of_module.append(added)
        draft = sample(drafted[waiting - 1], key_draft, temperature)
        logits, h, cache, added = main(cfg, params, cache, jnp.stack([last, draft]), jnp.int32(n))
        of_main.append(added)
        accepted, one, two = verify(logits, drafted[waiting - 1], draft, key_verify, temperature)
        cache = settle(cache, accepted)
        tokens += [one, two] if accepted else [one]
        last, after, waiting = (two if accepted else one), jnp.stack([one, two]), 1 + int(accepted)
        rows.append(logits)
        fates.append(bool(accepted))
        positions.append(n)

    start = lm.prefill(params, ids, prompt + 5)
    decode = lm.decode(params, start.cache, start.logits, prompt, key, steps, temperature, True, 1)
    assert decode.ids.shape == (steps,) and decode.ids.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(decode.ids), np.asarray(jnp.stack(tokens[:steps])))
    taken = len(rows)
    assert np.asarray(decode.counts).tolist()[:3] == [taken, taken, sum(fates)]
    assert np.asarray(decode.kept["position"]).tolist() == positions + [-1] * (
        max(steps - 1, 1) - taken)
    assert np.asarray(decode.kept["accepted"]).tolist()[:taken] == fates
    for mine, theirs in ((of_module, summed(decode)[0]), (of_main, summed(decode)[1])):
        for i, total in enumerate(theirs):
            want = sum(np.asarray(added[i]) for added in mine) if mine else 0
            np.testing.assert_array_equal(np.asarray(total), want + np.zeros_like(total))
    for i, logits in enumerate(rows):
        np.testing.assert_allclose(
            np.asarray(decode.kept["logits"][i]), np.asarray(logits), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("collect", [False, True])
@pytest.mark.parametrize("keeping", [True, False])
@pytest.mark.parametrize("steps", [1, 6])
def test_the_drafting_loop_emits_two_ids_for_a_kept_draft_and_one_for_a_dropped(
        steps, keeping, collect):
    """The loop by itself, under steps that are arithmetic at temperature
    0 (the token at position p is p): a module that drafts position i's
    token i + 2 has every draft kept, two ids a step, and the second id
    that would be id `steps` is not written; one that drafts i + 3 has
    none kept, one id a step; one id asked for is the prefill's and no
    step. The model's hook sees each step's
    `accepted`, the module's and the main steps' trees are summed apart
    from zero, the kept rows are the steps' in order (of the module's the
    row the draft was drawn from), and a main step that keeps None has
    none."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from comfyui_distributed_tpu.models.lm_common import draft_loop

    vocab, start = 32, 3

    def peaked(at):
        return jnp.zeros((vocab,)).at[at % vocab].set(50.0)

    def mtp_step(cache, h, after, position):
        rows = jnp.stack([peaked(position + row + (2 if keeping else 3)) for row in range(2)])
        return rows, None, cache, (position,), {"confirmed": position + jnp.arange(2)}

    def main_step(cache, tokens, position):
        rows = jnp.stack([peaked(position + 1), peaked(position + 2)])
        h = jnp.stack([cache["h"] + position, cache["h"] + position + 1])
        kept = {"at": position, "tokens": tokens} if collect else None
        return rows, h, {**cache, "ran": cache["ran"] + 1}, (jnp.ones((2,), jnp.int32),), kept

    def settle(cache, accepted):
        return {**cache, "fates": cache["fates"] * 2 + accepted}

    cache = {"h": jnp.zeros((4,)), "ran": jnp.int32(0), "fates": jnp.int32(1)}
    cache, ids, counts, ((positions,), (ones,)), rows = jax.jit(lambda: draft_loop(
        mtp_step, main_step, cache, peaked(start), jnp.int32(start), jax.random.key(0),
        jnp.float32(0.0), steps, settle))()
    assert np.asarray(ids).tolist() == list(range(start, start + steps))
    taken = 0 if steps == 1 else 3 if keeping else 5
    assert np.asarray(counts).tolist() == [taken, taken, taken if keeping else 0]
    assert int(cache["ran"]) == taken
    # a bit a step, after the 1
    assert int(cache["fates"]) == (1 if steps == 1 else 0b1111 if keeping else 0b100000)
    at = [start + (2 if keeping else 1) * step for step in range(taken)]  # each step's n
    waiting = [1] + [2 if keeping else 1] * (taken - 1)
    assert np.asarray(ones).tolist() == [taken, taken]
    assert int(positions) == sum(n - w for n, w in zip(at, waiting))
    if not collect:
        assert rows is None
        return
    assert set(rows) == {"at", "tokens", "confirmed", "logits", "draft_logits", "position",
                         "accepted"}
    most = max(steps - 1, 1)
    assert rows["logits"].shape == (most, 2, vocab)
    assert np.asarray(rows["position"]).tolist() == at + [-1] * (most - taken)
    assert np.asarray(rows["at"]).tolist()[:taken] == at
    assert np.asarray(rows["accepted"]).tolist() == [keeping] * taken + [False] * (most - taken)
    # the row the draft was drawn from is of the newest confirmed position, n - 1
    assert np.asarray(rows["confirmed"]).tolist()[:taken] == [n - 1 for n in at]
    assert np.asarray(rows["tokens"])[:taken, 0].tolist() == at


@pytest.mark.parametrize("tokens, parts", [(12, [4, 4, 4]), (3, [3]), (14, [4, 4, 4, 2])])
def test_the_walk_in_parts_hands_each_body_its_start_and_ends_and_joins_in_order(tokens, parts):
    """`lm_common.prefill_in_parts` under a part that is arithmetic: whole
    parts only, a remainder only, both. Every array is cut alike, the
    whole parts are one body and the remainder one (each traced with the
    ends the parts it serves may have), each part starts where the one
    before ended over the state it left, and the outputs come back in
    order along a leading parts axis."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from comfyui_distributed_tpu.models.lm_common import parts_of, prefill_in_parts

    bodies = []

    def body(state, cuts, start, ends):
        ids, after = cuts
        bodies.append((ids.shape[0], ends))
        out = {"start": start, "before": state, "first": ids[0], "next": after[-1]}
        return state + ids.sum(), out

    ids = jnp.arange(100, 100 + tokens)
    state, outs = jax.jit(lambda: prefill_in_parts(body, jnp.int32(0), (ids, ids + 1), 4))()
    assert parts_of(tokens, 4) == (len([p for p in parts if p == 4]), tokens % 4)
    whole = [(4, tuple(range(4, tokens + 1, 4)))] * (tokens >= 4)
    assert list(dict.fromkeys(bodies)) == whole + [(tokens % 4, (tokens,))] * bool(tokens % 4)
    starts = np.cumsum([0] + parts[:-1]).tolist()
    assert int(state) == int(ids.sum())
    assert np.asarray(outs["start"]).tolist() == starts
    assert np.asarray(outs["first"]).tolist() == [100 + s for s in starts]
    assert np.asarray(outs["next"]).tolist() == [100 + s + p for s, p in zip(starts, parts)]
    assert np.asarray(outs["before"]).tolist() == [
        int(ids[:s].sum()) for s in starts]


# --- what only one model has -----------------------------------------------------


@pytest.mark.parametrize("name, layers, sparse", [
    ("k-exaone", 5, 4), ("ling-flash", 7, 6), ("glm-5.2", 5, 4)])
def test_without_drafting_a_drafting_models_node_reports_a_step_a_token(
        name, layers, sparse, tmp_path, monkeypatch):
    monkeypatch.setenv("CDT_OUTPUT_DIR", str(tmp_path))
    before = counted()
    attrs = node_attrs(rehearsed(BY_NAME[name], draft_tokens=0))
    assert (attrs["draft_tokens"], attrs["decode_steps"]) == (0, 16)
    assert (attrs["mtp_drafted"], attrs["mtp_accepted"]) == (0, 0)
    assert attrs["decode_layer_passes"] == 16 * layers
    assert attrs["decode_routed_pairs"] == 16 * sparse * 4
    model = BY_NAME[name]
    assert set(attrs) - BUILD_TALLIES - {"attention"} == set(model.attrs) | model.drawn
    after = counted()
    assert after["steps"] - before["steps"] == 16
    key = ("cdt_lm_layer_passes_total", "decode")
    assert after[key] - before[key] == 16 * layers


@pytest.mark.parametrize("name, kind", [
    ("deepseek-v2", "DeepSeekV2"), ("ouro", "Ouro"), ("solar-open2", "SolarOpen2"),
    ("nemotron3-nano", "NemotronH")])
def test_a_model_without_a_draft_module_refuses_to_draft(name, kind, tmp_path, monkeypatch):
    """`draft_tokens` is an optional input: the committed workflows that do
    not give it run as before and say 0; anything else is refused by the
    model's class, by name."""
    monkeypatch.setenv("CDT_OUTPUT_DIR", str(tmp_path))
    assert "draft_tokens" not in by_kind(rehearsed(BY_NAME[name]))["TextGenerate"]
    with pytest.raises(Exception, match=f"{kind} has no draft module"):
        GraphExecutor(ExecutionContext()).execute(rehearsed(BY_NAME[name], draft_tokens=1))


def test_granite_refuses_to_draft(tmp_path, monkeypatch):
    """Its committed workflow gives `draft_tokens` 0, which is served;
    anything else is refused by the model's class, by name."""
    monkeypatch.setenv("CDT_OUTPUT_DIR", str(tmp_path))
    model = BY_NAME["granite-4.0-h-micro"]
    assert by_kind(rehearsed(model))["TextGenerate"]["draft_tokens"] == 0
    with pytest.raises(Exception, match="GraniteHybrid has no draft module"):
        GraphExecutor(ExecutionContext()).execute(rehearsed(model, draft_tokens=1))


def test_k_exaones_served_share_holds_2_mb_of_rings_and_8_kb_a_position():
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models.registry import create_model

    lm = create_model("k-exaone-ep8-5l")
    lm.dtype = jnp.dtype(jnp.bfloat16)
    counts = [300, 300, 140, 700]
    attrs = lm.report(8192, 384, 8576, [[2000] * 16] * 4, [[10] * 16] * 5, counts)
    config = load("benchmark/configs/k-exaone-236b-a23b.json")
    # two caches that grow (layer 3's and the MTP module's), 4,096 B a position each
    assert attrs["cache_bytes"] == 8576 * 8192 == 8576 * config["as_run"]["cache_bytes_per_token"]
    # four rings of 136 entries
    assert attrs["state_bytes"] == 4 * 136 * 4096 == config["as_run"]["state_bytes"]
    assert attrs["ring_positions"] == config["as_run"]["ring_positions"] == 136
    assert (attrs["window_layers"], attrs["full_layers"], attrs["window"]) == (4, 2, 128)
    assert attrs["decode_layer_passes"] == 300 * 2 * 6 and attrs["decode_steps"] == 300
    assert (attrs["mtp_drafted"], attrs["mtp_accepted"]) == (300, 140)
    # each layer's 32,000 held pairs take the rung of 32,768 rows
    assert attrs["prefill_expert_rows"] == 4 * 32768


def test_lings_served_share_holds_26_mb_of_state_in_two_slots_and_2_kb_a_position():
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models.registry import create_model

    lm = create_model("ling-flash-ep8-7l")
    lm.dtype = jnp.dtype(jnp.bfloat16)
    counts = [690, 690, 333, 9000]
    attrs = lm.report(8192, 1024, 9216, [[128] * 64] * 6, [[20] * 64] * 7, counts)
    config = load("benchmark/configs/ling-3.0-flash.json")
    # two latent caches (layer 5's and the MTP module's), 576 x 2 B a position each
    assert attrs["cache_bytes"] == 9216 * 2304 == 9216 * config["as_run"]["cache_bytes_per_token"]
    # six KDA layers, two slots each: float32 matrix states whatever the weights' dtype,
    # bfloat16 tails
    assert attrs["state_bytes"] == 6 * 2 * (32 * 128 * 128 * 4 + 3 * 12288 * 2) == (
        config["as_run"]["state_bytes"])
    assert (attrs["linear_layers"], attrs["latent_layers"], attrs["prefill_chunks"]) == (6, 2, 128)
    assert attrs["decode_layer_passes"] == 690 * 2 * 8 and attrs["decode_steps"] == 690
    assert (attrs["mtp_drafted"], attrs["mtp_accepted"]) == (690, 333)
    assert attrs["decode_routed_pairs"] == 690 * 2 * 7 * 8
    # each layer's 8,192 held pairs take the rung of an eighth of the 65,536
    assert attrs["prefill_expert_rows"] == 6 * 8192


def test_solars_served_share_holds_13_mb_of_state_and_4_kb_a_position():
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models.registry import create_model

    lm = create_model("solar-open2-ep8-4l")
    lm.dtype = jnp.dtype(jnp.bfloat16)
    attrs = lm.report(8192, 256, 8448, [[0]], [[0]])
    assert attrs["cache_bytes"] == 8448 * 4096
    # the matrix states float32 whatever the weights' dtype, the tails bfloat16
    assert attrs["state_bytes"] == 3 * 64 * 128 * 128 * 4 + 3 * 3 * 24576 * 2 == 13_025_280
    assert (lm.layer_passes, attrs["linear_layers"], attrs["full_layers"]) == (4, 3, 1)
    assert attrs["prefill_chunks"] == 128


def test_nemotrons_served_share_holds_48_mb_of_state_and_6_kb_a_position():
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models.registry import create_model

    lm = create_model("nemotron3-nano-ep16-52l")
    lm.dtype = jnp.dtype(jnp.bfloat16)
    attrs = lm.report(8192, 512, 8704, [[384] * 8] * 23, [[24] * 8] * 23)
    config = load("benchmark/configs/nemotron-3-nano-30b-a3b.json")
    # six key/value caches, 2 key heads x 128 x 2 x 2 B a position each
    assert attrs["cache_bytes"] == 8704 * 6144 == 8704 * config["as_run"]["cache_bytes_per_token"]
    # 23 Mamba-2 blocks: float32 matrix states whatever the weights' dtype (48.2 MB),
    # bfloat16 tails of 3 x 6,144
    assert attrs["state_bytes"] == 23 * (64 * 64 * 128 * 4 + 3 * 6144 * 2) == (
        config["as_run"]["state_bytes"]) == 49_082_368
    assert 23 * 64 * 64 * 128 * 4 == 48_234_496
    assert (lm.layer_passes, attrs["layers"], attrs["mamba_layers"], attrs["sparse_layers"],
            attrs["attention_layers"]) == (52, 52, 23, 23, 6)
    assert (attrs["experts_held"], attrs["experts_total"], attrs["prefill_chunks"]) == (8, 128, 64)
    assert attrs["decode_routed_pairs"] == 512 * 23 * 6
    assert attrs["decode_experts_read"] == attrs["decode_routed_pairs_held"] == 23 * 8 * 24
    # each block's 3,072 held pairs take the rung of a sixteenth of the 49,152
    assert attrs["prefill_expert_rows"] == 23 * 3072
    assert lm.counted(attrs, 8192, 512) == {
        "decode_steps": 512, "prefill_layer_passes": 8192 * 52, "decode_layer_passes": 512 * 52}


def test_glms_served_share_holds_two_caches_of_7680_bytes_a_position():
    import jax.numpy as jnp
    import numpy as np

    from comfyui_distributed_tpu.models.registry import create_model

    lm = create_model("glm-5.2-ep16-5l")
    lm.dtype = jnp.dtype(jnp.bfloat16)
    # four parts of 8,192: a sixteenth of a part's 65,536 pairs on the 16 held experts
    loads = np.full((4, 4, 16), 256)
    keys = np.zeros((4, 2, 5), np.int64)
    for part in range(4):
        first, last = part * 8192, (part + 1) * 8192
        keys[part, 0] = (last * (last + 1) - first * (first + 1)) // 2
        keys[part, 1] = 8192 * 2048 - (2048 * 2047 // 2 if part == 0 else 0)
    attrs = lm.report(
        32768, 128, 32896, loads, keys, [[6] * 16] * 5, np.zeros((2, 6), np.int64), [87, 87, 40, 700])
    config = load("benchmark/configs/glm-5.2.json")
    # a latent of 512 + 64 in six layers (the module's among them), an indexer key of
    # 128 in three: 6 x 1,152 + 3 x 256 B a position
    assert attrs["cache_bytes"] == 32896 * 7680 == 32896 * config["as_run"]["cache_bytes_per_token"]
    assert attrs["indexer_cache_bytes"] == 32896 * 3 * 256 and attrs["state_bytes"] == 0
    assert (lm.layer_passes, attrs["layers"], attrs["indexer_layers"],
            attrs["index_shared_layers"], attrs["index_topk"]) == (5, 5, 2, 3, 2048)
    assert (attrs["prefill_parts"], attrs["prefill_part"]) == (4, 8192)
    assert (attrs["experts_held"], attrs["experts_total"]) == (16, 256)
    # a part's 4,096 held pairs a layer take the ladder's lowest rung, 4,096 rows
    assert attrs["prefill_routed_pairs"] == 32768 * 8 * 4
    assert attrs["prefill_routed_pairs_held"] == attrs["prefill_expert_rows"] == 4 * 4 * 4096
    # 12.1 % of what a causal mask allows over the prompt
    assert attrs["keys_visible"] == 5 * 32768 * 32769 // 2
    assert attrs["keys_selected"] == 5 * (2048 * 2049 // 2 + 30720 * 2048)
    assert 0.121 < attrs["keys_selected"] / attrs["keys_visible"] < 0.1212
    assert (attrs["prefill_sparse_attention_form"], attrs["decode_sparse_attention_form"]) == (
        "gathered", "masked")
    assert (attrs["decode_steps"], attrs["mtp_drafted"], attrs["mtp_accepted"]) == (87, 87, 40)
    assert attrs["decode_layer_passes"] == 87 * 2 * 6
    assert lm.counted(attrs, 32768, 128) == {
        "decode_steps": 87, "prefill_layer_passes": 32768 * 5, "decode_layer_passes": 87 * 12}


def test_the_seventh_model_is_written_from_the_modules_the_others_are():
    """Shared-code identities: the expert layer, the one sigmoid rule, the
    latents' one body, the drafting rule and the decode loop are the
    modules' own, and `glm_dsa.py` spells none of them out again."""
    from comfyui_distributed_tpu.models import (
        deepseek_v2, dsa, glm_dsa, k_exaone, ling_flash, lm_common, mla, moe)

    assert glm_dsa.expert_layer is moe.expert_layer is k_exaone.expert_layer
    assert glm_dsa.sigmoid_route is moe.sigmoid_route is k_exaone.sigmoid_route
    assert glm_dsa.mla is mla is ling_flash.mla is deepseek_v2.mla and dsa.mla is mla
    assert glm_dsa.draft_loop is lm_common.draft_loop and glm_dsa.mtp_input is lm_common.mtp_input
    assert glm_dsa.decode_loop is lm_common.decode_loop
    assert glm_dsa.head is lm_common.head and glm_dsa.rms_norm is lm_common.rms_norm
    with open(glm_dsa.__file__, encoding="utf-8") as fh:
        source = fh.read()
    for gone in ("top_k(", "ragged_dot(", "softmax(", "cumsum(", "einsum(", "argsort("):
        assert gone not in source, gone
    assert source.count("mla.latents(") == 2  # a layer's and the module's in the prefill


def test_the_sixth_model_is_written_from_the_modules_the_others_are():
    """Shared-code identities: the expert layer and the sigmoid rule are
    `moe.py`'s (groups 1 and 1: Solar-Open2's and K-EXAONE's rule as it
    is), the causal convolution in front of the recurrence is
    `lm_common.short_conv` for KDA and Mamba-2 alike, the decode loop, the
    head and the norm `lm_common.py`'s; the state-space layer is
    `mamba2.py`'s alone and the model file keeps no body of it."""
    from comfyui_distributed_tpu.models import (
        kda, lm_common, mamba2, moe, nemotron_h, solar_open2)

    assert nemotron_h.expert_layer is moe.expert_layer
    assert nemotron_h.sigmoid_route is moe.sigmoid_route is solar_open2.sigmoid_route
    assert mamba2.short_conv is lm_common.short_conv is kda.short_conv
    assert nemotron_h.decode_loop is lm_common.decode_loop
    assert nemotron_h.head is lm_common.head and nemotron_h.rms_norm is lm_common.rms_norm
    for module, gone in ((nemotron_h, ("top_k(", "ragged_dot(", "softmax(", "cumsum(",
                                       "softplus(", "def ssd_", "silu(")),
                         (kda, ("window = jnp.concatenate",)),
                         (mamba2, ("window = jnp.concatenate", "ragged_dot(", "top_k("))):
        with open(module.__file__, encoding="utf-8") as fh:
            source = fh.read()
        for body in gone:
            assert body not in source, (module.__name__, body)


def test_the_three_models_with_experts_call_the_one_expert_layer_and_two_the_one_rule():
    from comfyui_distributed_tpu.models import deepseek_v2, k_exaone, moe, solar_open2

    assert deepseek_v2.expert_layer is moe.expert_layer is solar_open2.expert_layer
    assert k_exaone.expert_layer is moe.expert_layer
    assert k_exaone.sigmoid_route is moe.sigmoid_route is solar_open2.sigmoid_route
    for module in (deepseek_v2, solar_open2, k_exaone):
        with open(module.__file__, encoding="utf-8") as fh:
            source = fh.read()
        assert "ragged_dot(" not in source
        # the rule's top-k is written once, in moe.py (DeepSeek's grouped rule is its own)
        assert ("top_k(" in source) == (module is deepseek_v2)


def test_the_fifth_model_is_written_from_the_modules_the_others_are():
    """Shared-code identities: the expert layer and the sigmoid rule are
    `moe.py`'s, the delta rule `kda.py`'s for Solar-Open2 and Ling alike,
    the two forms of latent attention `mla.py`'s for DeepSeek-V2 and Ling
    alike, the drafting rule `lm_common.py`'s for K-EXAONE and Ling alike;
    none of the importers keeps a body of its own."""
    from comfyui_distributed_tpu.models import (
        deepseek_v2, k_exaone, kda, ling_flash, lm_common, mla, moe, solar_open2)

    assert ling_flash.expert_layer is moe.expert_layer
    assert ling_flash.sigmoid_route is moe.sigmoid_route
    for module in (solar_open2, ling_flash):
        assert module.kda_chunked is kda.kda_chunked and module.kda_step is kda.kda_step
        assert module.conv_qkv is kda.conv_qkv and module.gated_output is kda.gated_output
    for module in (deepseek_v2, ling_flash):
        assert module.mla is mla
    for module in (k_exaone, ling_flash):
        assert module.draft_loop is lm_common.draft_loop  # which calls the rule, `verify`
    for module, gone in ((solar_open2, ("def kda_chunked", "def kda_step", "def decay_products",
                                        "def unit_lower_solve", "def _l2norm")),
                         (deepseek_v2, ("def _latents", "thc,sc->ths")),
                         (k_exaone, ("def verify", "def residual", "def accept_probability")),
                         (ling_flash, ("top_k(", "ragged_dot(", "softmax(", "def verify",
                                       "def kda_step"))):
        with open(module.__file__, encoding="utf-8") as fh:
            source = fh.read()
        for body in gone:
            assert body not in source, (module.__name__, body)


def test_solars_prefill_on_the_kernels_route_gives_the_xla_routes_logits(monkeypatch):
    """What a TPU does with the prefill's causal call (PR 43), forced here
    in the Pallas interpreter: the softmax layer's attention in
    `flash_attention` under its mask, two query heads a key head read where
    it lies, 16-wide heads folded into the batch; the logits and the KDA
    states are the XLA route's to what float32 rounding does to a router. The
    route is the test's to steer: no option of the program chooses it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from comfyui_distributed_tpu.models import solar_open2
    from comfyui_distributed_tpu.models.registry import get_config
    from comfyui_distributed_tpu.ops import attention

    cfg = get_config("tiny-solar-open2")
    params = solar_open2.init_params(cfg, jax.random.key(3), jnp.float32)
    ids = jax.random.randint(jax.random.key(4), (640,), 0, cfg.vocab_size)
    with attention.route_log() as routes:
        want = solar_open2.prefill(cfg, params, ids, cache_len=672, collect=True)
    delta_rule = ["kda-scan 640x4x16 c32 f32"] * cfg.linear_layers  # d = 16: never the kernel
    grouped = sorted(r for r in routes if r.startswith("gmm-"))  # the expert layers' rungs (PR 64)
    assert grouped and all(r.startswith("gmm-xla ") for r in grouped)
    assert sorted(routes) == grouped + delta_rule + ["xla-causal 640x640x16/16 bq256 f32"]

    monkeypatch.setattr(attention, "causal_route", lambda *operands: "flash")
    kernel = attention.flash_attention
    monkeypatch.setattr(
        attention, "flash_attention",
        lambda *operands, **options: kernel(*operands, **{**options, "interpret": True}))
    with attention.route_log() as routes:  # another cache length: traced anew
        got = solar_open2.prefill(cfg, params, ids, cache_len=704, collect=True)
    assert sorted(routes) == (
        ["flash-causal 640x640x16/16 g2 bq128 bk640 f32 blocks5/5"] + grouped + delta_rule)
    # another order of summation moves a score in its last digit, and a router
    # over seeded weights then gives a few (token, layer) pairs another expert
    # (the XLA form in blocks of 128 rows for 256: 5 of 10,240 choices; the
    # kernel 14, the logits 1.1e-3 and the states 3.0e-3 off by relative L2
    # norm); a key head read in another's place moves the logits by their own
    # size
    assert float(np.mean(np.asarray(got.chosen) != np.asarray(want.chosen))) < 0.005
    distance = lambda a, b: float(np.linalg.norm(np.asarray(a) - np.asarray(b)) / np.linalg.norm(b))
    assert distance(got.logits, want.logits) < 1e-2
    assert distance(got.cache["state"], want.cache["state"]) < 1e-2
    np.testing.assert_array_equal(  # layer 0 writes them before it attends
        np.asarray(got.cache["kv"][..., :640, :]), np.asarray(want.cache["kv"][..., :640, :]))


# --- the nodes around the language model ---------------------------------------


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


@pytest.mark.parametrize("given, loaded", [
    ("ouro-2.6b", "ouro-2.6b"), ("sd15.safetensors", "sd15"), ("tiny-unet", "tiny-unet"),
    ("v1-5-pruned.ckpt", "v1-5-pruned"),
])
def test_the_loader_takes_a_registry_name_with_a_dot_whole(given, loaded, monkeypatch):
    """`ouro-2.6b` is no file name with the extension `.6b`."""
    seen = []
    monkeypatch.setattr(nodes_core, "_get_bundle", lambda context, name: seen.append(name))
    monkeypatch.setattr(nodes_core, "_annotate_load", lambda bundle: None)
    nodes_core.CheckpointLoaderSimple().load(given)
    assert seen == [loaded]
