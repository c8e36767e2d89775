"""The committed rewrite-then-txt2img workflow with K-EXAONE in front,
through the graph executor on the tiny presets: a PNG a request, equal
bytes for equal seeds, no program built by a third request; what
`node.TextGenerate` says of a model that keeps rings beside growing
caches and decodes by self-speculation, and what it counts; that
`draft_tokens` is refused by a model without a draft module and that the
other models' attributes are what they were but for `draft_tokens` and
`decode_steps`; the one contract all four language models meet; and that
the benchmark's copies and its configuration file are what the issue
describes."""

import json
import os

import pytest

from comfyui_distributed_tpu.graph.executor import ExecutionContext, GraphExecutor
from comfyui_distributed_tpu.models.lm_common import ByteTokenizer
from comfyui_distributed_tpu.telemetry import get_metrics_registry, get_tracer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKFLOW = os.path.join(ROOT, "workflows", "rewrite-txt2img-k-exaone.json")
SOLAR_WORKFLOW = os.path.join(ROOT, "workflows", "rewrite-txt2img-solar-open2.json")
CONFIG = os.path.join(ROOT, "benchmark", "configs", "k-exaone-236b-a23b.json")
CELL = "k_exaone_rewrite_txt2img_512.closed2"
SOLAR_CELL = "solar_open2_rewrite_txt2img_512.closed2"
WORKLOAD = os.path.join(ROOT, "benchmark", "workloads", CELL + ".json")
PROMPT, NEW_TOKENS = 8192, 16
# tiny-k-exaone: a dense layer and four sparse ones (window, window, window,
# full, window over all five), 4 query heads over 2 key heads of 16, a
# window of 12 in a ring of 16, 2 of 16 experts held, 4 a token, the MTP module
LAYERS, WINDOW_LAYERS, SPARSE, KV_HEADS, HEAD_DIM, HELD, TOP_K = 5, 4, 4, 2, 16, 2, 4
WINDOW, RING = 12, 16


def load(path):
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def by_kind(prompt):
    return {n["class_type"]: n["inputs"] for n in prompt.values()}


def rehearsed(workflow, workload, **text_generate):
    """A committed graph with its cell's own rehearsal edits."""
    prompt = load(workflow)
    for edit in load(workload)["rehearsal"]["set"]:
        for node in prompt.values():
            if node["class_type"] == edit["class_type"]:
                node["inputs"][edit["input"]] = edit["value"]
    by_kind(prompt)["TextGenerate"].update(text_generate)
    return prompt


def node_attrs(prompt):
    tracer = get_tracer()
    with tracer.span("execute_prompt") as root:
        GraphExecutor(ExecutionContext()).execute(prompt)
    (node,) = spans_named(tracer.spans(root.trace_id), "node.TextGenerate")
    return node["attrs"]


@pytest.fixture(scope="module")
def graph():
    return rehearsed(WORKFLOW, WORKLOAD)


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


def test_the_workflow_is_the_solar_one_with_another_model_more_tokens_and_drafting():
    mine, theirs = load(WORKFLOW), load(SOLAR_WORKFLOW)
    assert mine.keys() == theirs.keys()
    differing = {
        (mine[node]["class_type"], key)
        for node in mine for key in mine[node]["inputs"]
        if mine[node]["inputs"][key] != theirs[node]["inputs"].get(key)
    }
    assert differing == {
        ("CheckpointLoaderSimple", "ckpt_name"), ("TextGenerate", "max_new_tokens"),
        ("TextGenerate", "draft_tokens"), ("SaveImage", "filename_prefix")}
    inputs = by_kind(mine)
    assert inputs["CheckpointLoaderSimple"]["ckpt_name"] == load(CONFIG)["registry_name"]
    generate = inputs["TextGenerate"]
    assert (generate["max_new_tokens"], generate["draft_tokens"], generate["temperature"]) == (
        384, 1, 1.0)
    # the Solar cell's 8,191-byte instruction, byte for byte
    assert generate["text"] == by_kind(theirs)["TextGenerate"]["text"]
    assert len(ByteTokenizer().encode(generate["text"])) == PROMPT


def test_a_request_gives_a_png_and_the_text_that_was_drawn(served):
    png, _, outputs, _ = served[0]
    assert png[:8] == b"\x89PNG\r\n\x1a\n"
    texts = [i["ui"]["text"] for r in outputs.values() for i in r
             if isinstance(i, dict) and "text" in i.get("ui", {})]
    assert len(texts) == 1 and len(texts[0]) == 1
    assert 0 < len(texts[0][0].split()) <= NEW_TOKENS


def test_equal_seeds_give_equal_bytes_and_another_seed_other_bytes(served):
    assert served[0][0] == served[2][0]
    assert served[0][0] != served[1][0]


def test_the_third_request_builds_no_program(served):
    assert served[0][3] > 0
    assert served[2][3] == 0


def test_node_textgenerate_says_what_a_drafting_model_with_rings_ran(served):
    (node,) = spans_named(served[1][1], "node.TextGenerate")
    attrs = node["attrs"]
    assert (attrs["prompt_tokens"], attrs["new_tokens"]) == (PROMPT, NEW_TOKENS)
    assert (attrs["layers"], attrs["window_layers"], attrs["full_layers"]) == (
        LAYERS, WINDOW_LAYERS, 2)  # the main model's one full layer and the MTP module's
    assert (attrs["window"], attrs["ring_positions"]) == (WINDOW, RING)
    assert (attrs["experts_held"], attrs["experts_total"]) == (HELD, 16)
    # float32 on the CPU. What grows: a key and a value of each key head
    # a position, in the full layer and in the MTP module's
    assert attrs["cache_bytes"] == 2 * 2 * KV_HEADS * (PROMPT + NEW_TOKENS) * HEAD_DIM * 4
    # what does not: the four rings
    assert attrs["state_bytes"] == WINDOW_LAYERS * 2 * KV_HEADS * RING * HEAD_DIM * 4
    # the steps: one draft a step, one or two tokens out of each, the first from the prefill
    steps, accepted = attrs["decode_steps"], attrs["mtp_accepted"]
    assert attrs["draft_tokens"] == 1 and attrs["mtp_drafted"] == steps
    assert 1 + steps + accepted in (NEW_TOKENS, NEW_TOKENS + 1) and 0 <= accepted <= steps
    # two positions a step through five layers and the MTP module's, kept or not
    assert attrs["prefill_layer_passes"] == PROMPT * LAYERS
    assert attrs["decode_layer_passes"] == steps * 2 * (LAYERS + 1)
    assert attrs["prefill_routed_pairs"] == PROMPT * SPARSE * TOP_K
    assert attrs["decode_routed_pairs"] == steps * 2 * (SPARSE + 1) * TOP_K
    held = attrs["prefill_routed_pairs_held"]
    assert 0.06 < held / attrs["prefill_routed_pairs"] < 0.2  # an eighth in expectation
    assert held / (SPARSE * HELD) <= attrs["prefill_expert_load_max"] <= held
    assert 0 <= attrs["decode_routed_pairs_held"] < attrs["decode_routed_pairs"]
    # distinct held experts a step and layer read: never more than the pairs on them
    assert 0 <= attrs["decode_experts_read"] <= min(
        attrs["decode_routed_pairs_held"], steps * (SPARSE + 1) * HELD)
    assert held <= attrs["prefill_expert_rows"] < attrs["prefill_routed_pairs"]
    assert attrs["decode_expert_rows"] == attrs["decode_routed_pairs"]
    assert attrs["decode_expert_route"] == "xla"  # off a TPU
    assert not any(key.startswith(("exit_mass", "linear_layers", "prefill_chunks"))
                   for key in attrs)


def test_the_spans_under_the_node_are_dispatch_one_wait_and_detokenize(served):
    spans = served[1][1]
    (node,) = spans_named(spans, "node.TextGenerate")
    below = [s["name"] for s in spans if s["parent_id"] == node["span_id"]]
    assert below == ["lm.prefill", "device.run", "lm.decode", "device.run", "device.wait",
                     "lm.detokenize"]
    assert [s["attrs"]["program"] for s in spans_named(spans, "device.run")
            if s["parent_id"] == node["span_id"]] == ["prefill", "decode"]
    (wait,) = [s for s in spans_named(spans, "device.wait") if s["parent_id"] == node["span_id"]]
    # the ids, the pairs per held expert of either program (the decode's
    # with the MTP module's row) and the four counts, in one read-back
    assert wait["attrs"]["bytes"] == 4 * (NEW_TOKENS + SPARSE * HELD + (SPARSE + 1) * HELD + 4)


def test_only_the_request_that_traced_the_programs_says_which_attention(served):
    (first,) = spans_named(served[0][1], "node.TextGenerate")
    # the windowed route beside the full one
    assert first["attrs"]["attention"] == (
        f"xla-causal {PROMPT}x{PROMPT}x16/16 bq256 f32, "
        f"xla-causal {PROMPT}x{PROMPT}x16/16 w{WINDOW} bq256 f32")
    (second,) = spans_named(served[1][1], "node.TextGenerate")
    assert "attention" not in second["attrs"]


def test_steps_drafts_and_the_models_own_layer_passes_are_counted(graph, tmp_path, monkeypatch):
    monkeypatch.setenv("CDT_OUTPUT_DIR", str(tmp_path))
    registry = get_metrics_registry()
    by_phase = {name: registry.counter(name, "", ("phase",)) for name in (
        "cdt_lm_tokens_total", "cdt_lm_layer_passes_total")}
    steps = registry.counter("cdt_lm_decode_steps_total", "")
    drafts = registry.counter("cdt_lm_draft_tokens_total", "", ("outcome",))
    before = {
        "steps": steps.value(), "accepted": drafts.value(outcome="accepted"),
        "rejected": drafts.value(outcome="rejected"),
        **{(name, phase): counter.value(phase=phase)
           for name, counter in by_phase.items() for phase in ("prefill", "decode")}}
    attrs = node_attrs(graph)
    taken = attrs["decode_steps"]
    assert steps.value() - before["steps"] == taken
    assert drafts.value(outcome="accepted") - before["accepted"] == attrs["mtp_accepted"]
    assert drafts.value(outcome="rejected") - before["rejected"] == taken - attrs["mtp_accepted"]
    for phase, tokens, passes in (("prefill", PROMPT, PROMPT * LAYERS),
                                  ("decode", NEW_TOKENS, taken * 2 * (LAYERS + 1))):
        name = "cdt_lm_tokens_total"
        assert by_phase[name].value(phase=phase) - before[name, phase] == tokens
        name = "cdt_lm_layer_passes_total"
        assert by_phase[name].value(phase=phase) - before[name, phase] == passes


def test_without_drafting_the_node_reports_a_step_a_token(tmp_path, monkeypatch):
    monkeypatch.setenv("CDT_OUTPUT_DIR", str(tmp_path))
    attrs = node_attrs(rehearsed(WORKFLOW, WORKLOAD, draft_tokens=0))
    assert (attrs["draft_tokens"], attrs["decode_steps"]) == (0, NEW_TOKENS)
    assert (attrs["mtp_drafted"], attrs["mtp_accepted"]) == (0, 0)
    assert attrs["decode_layer_passes"] == NEW_TOKENS * LAYERS
    assert attrs["decode_routed_pairs"] == NEW_TOKENS * SPARSE * TOP_K


def test_a_model_without_a_draft_module_refuses_to_draft(tmp_path, monkeypatch):
    monkeypatch.setenv("CDT_OUTPUT_DIR", str(tmp_path))
    prompt = rehearsed(
        SOLAR_WORKFLOW, os.path.join(ROOT, "benchmark", "workloads", SOLAR_CELL + ".json"),
        draft_tokens=1)
    with pytest.raises(Exception, match="SolarOpen2 has no draft module"):
        GraphExecutor(ExecutionContext()).execute(prompt)


def test_solars_attributes_are_what_they_were_but_for_three(tmp_path, monkeypatch):
    """`draft_tokens` is an optional input: Solar's committed workflow,
    which does not give it, runs as before and says 0 and a step a token
    (and, since PR 42, what multiplied a decode step's pairs)."""
    monkeypatch.setenv("CDT_OUTPUT_DIR", str(tmp_path))
    attrs = node_attrs(rehearsed(
        SOLAR_WORKFLOW, os.path.join(ROOT, "benchmark", "workloads", SOLAR_CELL + ".json")))
    build = {"compiles", "compile_s", "cache_hits", "cache_misses", "trace_s", "lower_s",
             "cache_fetch_s"}
    # `attention` only on the request that traced the programs in this process
    assert set(attrs) - build - {"attention"} == {
        "prompt_tokens", "new_tokens", "layers", "full_layers", "linear_layers", "experts_held",
        "experts_total", "cache_bytes", "state_bytes", "prefill_chunks", "prefill_routed_pairs",
        "prefill_routed_pairs_held", "prefill_expert_load_max", "decode_routed_pairs",
        "decode_routed_pairs_held", "decode_expert_load_max", "prefill_expert_rows",
        "decode_expert_rows", "decode_expert_route", "node_id", "draft_tokens", "decode_steps"}
    assert (attrs["draft_tokens"], attrs["decode_steps"]) == (0, attrs["new_tokens"])


@pytest.mark.parametrize("name, drafts", [
    ("tiny-k-exaone", 1), ("k-exaone-ep8-5l", 1), ("tiny-solar-open2", 0), ("tiny-ouro", 0),
    ("tiny-deepseek-v2", 0)])
def test_every_language_model_meets_the_one_contract(name, drafts):
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models.registry import create_model, model_family

    assert model_family(name) == "lm"
    lm = create_model(name)
    for attribute in ("cfg", "tokenizer", "dtype", "layer_passes", "init", "prefill", "decode",
                      "read_back", "describe", "report", "draft_tokens_max"):
        assert hasattr(lm, attribute), attribute
    assert lm.draft_tokens_max == drafts
    with pytest.raises(ValueError, match="draft_tokens"):
        lm.decode(None, None, None, 0, None, 4, 1.0, draft_tokens=drafts + 1)
    described = lm.describe(128)
    assert described["layers"] == lm.cfg.num_hidden_layers
    assert described["cache_bytes"] > 0 and isinstance(described["cache_bytes"], int)
    assert lm.describe(256)["cache_bytes"] == 2 * described["cache_bytes"]
    assert lm.describe(256)["state_bytes"] == described["state_bytes"]
    lm.dtype = jnp.dtype(jnp.bfloat16)  # what `init(key, bfloat16)` records
    assert lm.describe(128)["cache_bytes"] == described["cache_bytes"] // 2


def test_the_served_share_holds_2_mb_of_rings_and_8_kb_a_position():
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models.registry import create_model

    lm = create_model("k-exaone-ep8-5l")
    lm.dtype = jnp.dtype(jnp.bfloat16)
    described = lm.describe(8576)
    config = load(CONFIG)
    # two caches that grow (layer 3's and the MTP module's), 4,096 B a position each
    assert described["cache_bytes"] == 8576 * 8192 == 8576 * config["as_run"]["cache_bytes_per_token"]
    # four rings of 136 entries
    assert described["state_bytes"] == 4 * 136 * 4096 == config["as_run"]["state_bytes"]
    assert described["ring_positions"] == config["as_run"]["ring_positions"] == 136
    assert (described["window_layers"], described["full_layers"], described["window"]) == (4, 2, 128)
    counts = [300, 300, 140, 700]
    attrs = lm.report(8192, 384, [[2000] * 16] * 4, [[10] * 16] * 5, counts)
    assert attrs["decode_layer_passes"] == 300 * 2 * 6 and attrs["decode_steps"] == 300
    # each layer's 32,000 held pairs take the rung of 32,768 rows
    assert attrs["prefill_expert_rows"] == 4 * 32768


@pytest.mark.parametrize("mine, theirs", [
    ("benchmark/workflows/rewrite-txt2img-k-exaone.json", "workflows/rewrite-txt2img-k-exaone.json"),
    ("benchmark/reference/k_exaone.py", "comfyui_distributed_tpu/reference/k_exaone.py"),
])
def test_the_benchmarks_copies_are_the_committed_files(mine, theirs):
    with open(os.path.join(ROOT, mine), "rb") as a, open(os.path.join(ROOT, theirs), "rb") as b:
        assert a.read() == b.read()


def test_the_reference_imports_nothing_of_the_system():
    with open(os.path.join(ROOT, "comfyui_distributed_tpu/reference/k_exaone.py"),
              encoding="utf-8") as fh:
        imports = [line for line in fh if line.startswith(("import ", "from "))]
    assert sorted(imports) == sorted([
        "from __future__ import annotations\n", "import dataclasses\n",
        "import jax\n", "import jax.numpy as jnp\n", "import numpy as np\n"])


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


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    with open(path, encoding="utf-8") as fh:
        return next(row for row in map(json.loads, fh) if row["name"] == "K-EXAONE-236B-A23B")


REDUCED = {"num_hidden_layers": (48, 5), "num_experts": (128, 16), "vocab_size": (153600, 19200)}
WIDTHS = {
    "hidden_size": 6144, "num_attention_heads": 64, "num_key_value_heads": 8, "head_dim": 128,
    "intermediate_size": 18432, "moe_intermediate_size": 2048, "num_experts_per_tok": 8,
    "num_shared_experts": 1, "rms_norm_eps": 1e-5, "sliding_window": 128,
    "sliding_window_pattern": "LLLG", "first_k_dense_replace": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "num_nextn_predict_layers": 1, "n_group": 1, "topk_group": 1,
    "scoring_func": "sigmoid", "max_position_embeddings": 262144, "tie_word_embeddings": False,
}


def test_the_configuration_keeps_every_published_width_and_states_its_cut():
    config = load(CONFIG)
    for key, value in WIDTHS.items():
        assert config[key] == value, key
    assert config["rope_parameters"] == {"rope_theta": 1000000, "rope_type": "default"}
    assert config["layer_types"] == (["sliding_attention"] * 3 + ["full_attention"]) * 12
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 47
    assert (config["mtp_layer_types"], config["mtp_sliding_windows"]) == (["full_attention"], [0])
    assert config["reduced"] == list(REDUCED)
    for key, (published, held) in REDUCED.items():
        assert (config["published"][key], config[key]) == (published, held), key
    assert config["source"] == (
        "https://huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B/blob/main/config.json")
    assert config["reference"] == "benchmark/reference/k_exaone.py"
    assert config["as_run"]["parameters"] == {"lm": 4543318144}
    assert set(config["held"]) == {"layers", "experts", "vocabulary", "state"}
    assert "8 chips of one v5e-8 host" in config["deployment"]
    assert "not the trained model's" in config["as_run"]["drafts_kept"]
    assumed = " ".join(config["assumed"])
    for word in ("pre-norm", "QK norm", "which layers rotate", "window's convention",
                 "selection bias", "DeepSeek-V3's", "before the final norm", "seeded random",
                 "stand-in", "batch is 1", "share of drafts kept", "house style guide"):
        assert word in assumed, word
    limits = config["parity"]
    assert 0 < limits["tolerance_rel_l2_median"] <= limits["tolerance_rel_l2_max_unflipped"] < 0.2
    assert 0 < limits["tolerance_draft_rel_l2_median"] < 0.2
    assert 0 < limits["tolerance_expert_set_mismatch"] < 0.5


def test_the_configuration_file_is_the_catalogs_row_but_for_the_cut():
    row, config = catalog_row(), load(CONFIG)
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in REDUCED:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key


def test_the_registry_entry_is_the_configuration_file():
    from comfyui_distributed_tpu.models.registry import get_config

    config, cfg = load(CONFIG), get_config(load(CONFIG)["registry_name"])
    for key in WIDTHS:
        if hasattr(cfg, key):
            assert getattr(cfg, key) == config[key], key
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


def test_the_manifest_has_the_cell_with_the_issues_traffic_and_lists():
    manifest = load(os.path.join(ROOT, "BENCHMARK.json"))
    assert manifest["workloads"][-1]["name"] == CELL  # put at the end of its list
    cell = manifest["workloads"][-1]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("k-exaone-236b-a23b", "closed2", 1)
    assert len(cell["why"]) <= 200
    config = manifest["configs"][-1]
    assert config["name"] == "k-exaone-236b-a23b" and config["reduced"] == list(REDUCED)
    assert config["file"] == "benchmark/configs/k-exaone-236b-a23b.json"
    assert config["source"] == load(CONFIG)["source"]
    metrics = manifest["per_layer"] + manifest["end_to_end"]
    listed = {m["name"] for m in metrics if CELL in m.get("workloads", [])}
    solar = {m["name"] for m in metrics if SOLAR_CELL in m.get("workloads", [])}
    new = {"mtp_accept_pct.lm", "mtp_device_pct.lm"}
    assert listed - new == solar - {"linear_attention_device_pct.lm"}
    assert new <= listed and {"state_mb.lm", "experts_held_share_pct.lm", "images_per_s"} <= listed
    for name in new:
        (metric,) = [m for m in manifest["per_layer"] if m["name"] == name]
        assert metric["workloads"] == [CELL] and metric["moves"] == "images_per_s"
        assert metric["layer"] == "sampling programs" and metric["unit"] == "%"
        assert os.path.exists(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py"))
    assert [m["name"] for m in manifest["per_layer"][-2:]] == sorted(new)
    for metric in metrics:  # a cell is appended to a list, never put inside it
        if CELL in metric.get("workloads", []):
            assert metric["workloads"][-1] == CELL
    work = load(WORKLOAD)
    assert work["workflow"] == "benchmark/workflows/rewrite-txt2img-k-exaone.json"
    assert work["seed_nodes"] == ["DistributedSeed"]
    assert work["compute_nodes"] == ["TextGenerate", "KSampler"]
    assert work["rate"] == {"metric": "images_per_s", "units_per_job": 1}
    assert work["trace"] == {"start_s": 5, "slice_s": 12}
    edits = {(e["class_type"], e["input"]): e["value"] for e in work["rehearsal"]["set"]}
    assert edits["CheckpointLoaderSimple", "ckpt_name"] == "tiny-k-exaone"
    assert edits["TextGenerate", "max_new_tokens"] == NEW_TOKENS
