"""The committed rewrite-then-txt2img workflow with Solar-Open2 in front,
through the graph executor on the tiny presets: a PNG a request, equal
bytes for equal seeds, no program built by a third request; what
`node.TextGenerate` says of a model whose state is a tree of three kinds
and what it counts; that DeepSeek-V2's and Ouro's attributes are what they
were; the one contract all three language models meet; and that the
benchmark's copies and its configuration file are what the issue
describes."""

import json
import os

import pytest

from comfyui_distributed_tpu.graph.executor import ExecutionContext, GraphExecutor
from comfyui_distributed_tpu.models.lm_common import ByteTokenizer
from comfyui_distributed_tpu.telemetry import get_metrics_registry, get_tracer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKFLOW = os.path.join(ROOT, "workflows", "rewrite-txt2img-solar-open2.json")
DEEPSEEK_WORKFLOW = os.path.join(ROOT, "workflows", "rewrite-txt2img-deepseek-v2.json")
CONFIG = os.path.join(ROOT, "benchmark", "configs", "solar-open2-250b.json")
CELL = "solar_open2_rewrite_txt2img_512.closed2"
WORKLOAD = os.path.join(ROOT, "benchmark", "workloads", CELL + ".json")
PROMPT, NEW_TOKENS = 8192, 16
# tiny-solar-open2: a gated NoPE layer (4 query heads over 2 key heads of
# 16) and three KDA layers (4 heads of 16), 2 of 16 experts held, 4 a token
LAYERS, LINEAR, KV_HEADS, HEADS, HEAD_DIM, HELD, TOP_K, CHUNK = 4, 3, 2, 4, 16, 2, 4, 32


def load(path):
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def by_kind(prompt):
    return {n["class_type"]: n["inputs"] for n in prompt.values()}


def rehearsed(workflow, workload):
    """A committed graph with its cell's own rehearsal edits."""
    prompt = load(workflow)
    for edit in load(workload)["rehearsal"]["set"]:
        for node in prompt.values():
            if node["class_type"] == edit["class_type"]:
                node["inputs"][edit["input"]] = edit["value"]
    return prompt


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


def test_the_workflow_is_the_deepseek_one_with_another_model_and_an_8_kb_instruction():
    mine, theirs = load(WORKFLOW), load(DEEPSEEK_WORKFLOW)
    assert mine.keys() == theirs.keys()
    differing = {
        (mine[node]["class_type"], key)
        for node in mine for key in mine[node]["inputs"]
        if mine[node]["inputs"][key] != theirs[node]["inputs"][key]
    }
    assert differing == {
        ("CheckpointLoaderSimple", "ckpt_name"), ("TextGenerate", "text"),
        ("SaveImage", "filename_prefix")}
    inputs = by_kind(mine)
    assert inputs["CheckpointLoaderSimple"]["ckpt_name"] == load(CONFIG)["registry_name"]
    assert (inputs["TextGenerate"]["max_new_tokens"], inputs["TextGenerate"]["temperature"]) == (
        256, 1.0)
    text = inputs["TextGenerate"]["text"]
    assert len(text.encode("utf-8")) == 8191 and text.isascii()
    assert len(ByteTokenizer().encode(text)) == PROMPT  # a token a byte, and begin-of-sentence
    # the DeepSeek cell's instruction, a house style guide, worked pairs, the user's line
    theirs_text = by_kind(theirs)["TextGenerate"]["text"]
    assert text.startswith(theirs_text.split("\n\nExample 1\n")[0])
    assert "House style guide" in text and text.count("\nRequest: ") == 13
    assert text.endswith("\n\nRequest: a photograph of a mountain lake at dawn\nPrompt:")
    assert theirs_text.rstrip().endswith(text[-55:])


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


def test_node_textgenerate_says_what_a_model_with_two_kinds_of_state_ran(served):
    (node,) = spans_named(served[1][1], "node.TextGenerate")
    attrs = node["attrs"]
    assert (attrs["prompt_tokens"], attrs["new_tokens"]) == (PROMPT, NEW_TOKENS)
    assert (attrs["layers"], attrs["full_layers"], attrs["linear_layers"]) == (
        LAYERS, LAYERS - LINEAR, LINEAR)
    assert (attrs["experts_held"], attrs["experts_total"]) == (HELD, 16)
    # float32 on the CPU. What grows: a key and a value of each key head
    # in the one full-attention layer, a position
    assert attrs["cache_bytes"] == 2 * KV_HEADS * (PROMPT + NEW_TOKENS) * HEAD_DIM * 4
    # what does not: a matrix state a KDA head, the convolutions' last 3 inputs
    assert attrs["state_bytes"] == LINEAR * (
        HEADS * HEAD_DIM * HEAD_DIM * 4 + 3 * 3 * HEADS * HEAD_DIM * 4)
    assert attrs["prefill_chunks"] == PROMPT // CHUNK
    assert attrs["prefill_routed_pairs"] == PROMPT * LAYERS * TOP_K
    assert attrs["decode_routed_pairs"] == NEW_TOKENS * LAYERS * TOP_K
    held = attrs["prefill_routed_pairs_held"]
    # an eighth in expectation, random weights
    assert 0.06 < held / attrs["prefill_routed_pairs"] < 0.2
    assert held / (LAYERS * HELD) <= attrs["prefill_expert_load_max"] <= held
    assert 0 <= attrs["decode_routed_pairs_held"] < attrs["decode_routed_pairs"]
    # the rows the grouped products ran over: a rung a layer, every pair of a step
    assert held <= attrs["prefill_expert_rows"] < attrs["prefill_routed_pairs"]
    assert attrs["prefill_expert_rows"] % 256 == 0
    assert attrs["decode_expert_rows"] == attrs["decode_routed_pairs"]
    assert attrs["decode_expert_route"] == "xla"  # off a TPU
    # nothing of a looped model
    assert not any(key.startswith(("exit_mass", "ut_steps", "cache_slots")) for key in attrs)


def test_the_spans_under_the_node_are_dispatch_one_wait_and_detokenize(served):
    spans = served[1][1]
    (node,) = spans_named(spans, "node.TextGenerate")
    below = [s["name"] for s in spans if s["parent_id"] == node["span_id"]]
    assert below == ["lm.prefill", "device.run", "lm.decode", "device.run", "device.wait",
                     "lm.detokenize"]
    assert [s["attrs"]["program"] for s in spans_named(spans, "device.run")
            if s["parent_id"] == node["span_id"]] == ["prefill", "decode"]
    (wait,) = [s for s in spans_named(spans, "device.wait") if s["parent_id"] == node["span_id"]]
    # the ids and the two counts of pairs per held expert, in one
    # read-back; nothing of the state tree leaves the device
    assert wait["attrs"]["bytes"] == 4 * (NEW_TOKENS + 2 * LAYERS * HELD)


def test_only_the_request_that_traced_the_programs_says_which_attention(served):
    (first,) = spans_named(served[0][1], "node.TextGenerate")
    # the one softmax layer of the prefill, its 2 key heads read where
    # they lie; the decode's einsum form has no route to report
    assert first["attrs"]["attention"] == f"xla-causal {PROMPT}x{PROMPT}x16/16 bq256 f32"
    (second,) = spans_named(served[1][1], "node.TextGenerate")
    assert "attention" not in second["attrs"]


def test_tokens_layer_passes_and_linear_layer_passes_are_counted_by_phase(
        graph, tmp_path, monkeypatch):
    monkeypatch.setenv("CDT_OUTPUT_DIR", str(tmp_path))
    registry = get_metrics_registry()
    counters = {
        name: registry.counter(name, "", ("phase",)) for name in (
            "cdt_lm_tokens_total", "cdt_lm_layer_passes_total",
            "cdt_lm_linear_layer_passes_total")}
    GraphExecutor(ExecutionContext()).execute(graph)
    for phase, tokens in (("prefill", PROMPT), ("decode", NEW_TOKENS)):
        assert counters["cdt_lm_tokens_total"].value(phase=phase) == tokens
        assert counters["cdt_lm_layer_passes_total"].value(phase=phase) == tokens * LAYERS
        assert counters["cdt_lm_linear_layer_passes_total"].value(phase=phase) == tokens * LINEAR


# --- the models that were there: their attributes as PR 37 left them --------

DEEPSEEK_ATTRS = {
    "prompt_tokens", "new_tokens", "layers", "experts_held", "experts_total", "cache_bytes",
    "prefill_routed_pairs", "prefill_routed_pairs_held", "prefill_expert_load_max",
    "decode_routed_pairs", "decode_routed_pairs_held", "decode_expert_load_max",
    "prefill_expert_rows", "decode_expert_rows", "decode_expert_route", "attention", "node_id"}
OURO_ATTRS = {
    "prompt_tokens", "new_tokens", "ut_steps", "layers", "cache_slots", "cache_bytes",
    "prefill_layer_passes", "decode_layer_passes", "exit_mass_1", "exit_mass_2", "exit_mass_3",
    "exit_mass_4", "attention", "node_id"}
BUILD_TALLIES = {"compiles", "compile_s", "cache_hits", "cache_misses", "trace_s", "lower_s",
                 "cache_fetch_s"}


@pytest.mark.parametrize("name, cell, names, values", [
    ("deepseek-v2", "deepseek_v2_rewrite_txt2img_512.closed2", DEEPSEEK_ATTRS, {
        "layers": 3, "experts_held": 4, "experts_total": 16,
        "cache_bytes": 3 * (2048 + 16) * 32 * 4, "prefill_routed_pairs": 2048 * 2 * 3,
        "decode_routed_pairs": 16 * 2 * 3, "decode_expert_rows": 16 * 2 * 3}),
    ("ouro-2.6b", "ouro_2_6b_rewrite_txt2img_512.closed2", OURO_ATTRS, {
        "ut_steps": 4, "layers": 3, "cache_slots": 12,
        "cache_bytes": 12 * 2 * 4 * (2048 + 8) * 16 * 4,
        "prefill_layer_passes": 2048 * 12, "decode_layer_passes": 8 * 12}),
])
def test_the_other_models_attributes_keep_their_names_and_values(
        name, cell, names, values, tmp_path, monkeypatch):
    """`describe` lost its `itemsize` argument and gained `state_bytes`:
    DeepSeek-V2 and Ouro say what they said, and that they hold no state
    of fixed size; since PR 41 also that they draft nothing and take a
    step a token."""
    monkeypatch.setenv("CDT_OUTPUT_DIR", str(tmp_path))
    prompt = rehearsed(
        os.path.join(ROOT, "workflows", f"rewrite-txt2img-{name}.json"),
        os.path.join(ROOT, "benchmark", "workloads", cell + ".json"))
    tracer = get_tracer()
    with tracer.span("execute_prompt") as root:
        GraphExecutor(ExecutionContext()).execute(prompt)
    (node,) = spans_named(tracer.spans(root.trace_id), "node.TextGenerate")
    attrs = node["attrs"]
    # `attention` only on the request that traced the programs in this process: another
    # file's test on the same worker may have run this graph first
    assert set(attrs) - BUILD_TALLIES - {"attention"} == (names - {"attention"}) | {
        "state_bytes", "draft_tokens", "decode_steps"}
    assert attrs["state_bytes"] == 0
    assert (attrs["draft_tokens"], attrs["decode_steps"]) == (0, attrs["new_tokens"])
    for key, value in values.items():
        assert attrs[key] == value, key


@pytest.mark.parametrize("name", ["tiny-solar-open2", "solar-open2-ep8-4l", "tiny-ouro",
                                  "tiny-deepseek-v2"])
def test_every_language_model_meets_the_one_contract(name):
    """What `TextGenerate` asks of a bundle's `lm` part (`lm_common`);
    the model, not the node, says how many bytes each kind of state is."""
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models.registry import create_model, model_family

    assert model_family(name) == "lm"
    lm = create_model(name)
    for attribute in ("cfg", "tokenizer", "dtype", "layer_passes", "init", "prefill", "decode",
                      "read_back", "describe", "report"):
        assert hasattr(lm, attribute), attribute
    described = lm.describe(128)
    assert described["layers"] == lm.cfg.num_hidden_layers
    assert described["cache_bytes"] > 0 and isinstance(described["cache_bytes"], int)
    assert described["state_bytes"] >= 0 and isinstance(described["state_bytes"], int)
    assert lm.describe(256)["cache_bytes"] == 2 * described["cache_bytes"]
    assert lm.describe(256)["state_bytes"] == described["state_bytes"]
    lm.dtype = jnp.dtype(jnp.bfloat16)  # what `init(key, bfloat16)` records
    assert lm.describe(128)["cache_bytes"] == described["cache_bytes"] // 2


def test_the_served_share_holds_13_mb_of_state_and_4_kb_a_position():
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models.registry import create_model

    lm = create_model("solar-open2-ep8-4l")
    lm.dtype = jnp.dtype(jnp.bfloat16)
    described = lm.describe(8448)
    assert described["cache_bytes"] == 8448 * 4096
    # the matrix states float32 whatever the weights' dtype, the tails bfloat16
    assert described["state_bytes"] == 3 * 64 * 128 * 128 * 4 + 3 * 3 * 24576 * 2 == 13_025_280
    assert (lm.layer_passes, described["linear_layers"], described["full_layers"]) == (4, 3, 1)
    assert lm.report(8192, 256, [[0]], [[0]])["prefill_chunks"] == 128


def test_the_nodes_file_no_longer_looks_inside_the_cache():
    with open(os.path.join(ROOT, "comfyui_distributed_tpu", "graph", "nodes_text.py"),
              encoding="utf-8") as fh:
        source = fh.read()
    assert "cache.dtype" not in source and "itemsize" not in source


@pytest.mark.parametrize("mine, theirs", [
    ("benchmark/workflows/rewrite-txt2img-solar-open2.json",
     "workflows/rewrite-txt2img-solar-open2.json"),
    ("benchmark/reference/solar_open2.py", "comfyui_distributed_tpu/reference/solar_open2.py"),
])
def test_the_benchmarks_copies_are_the_committed_files(mine, theirs):
    with open(os.path.join(ROOT, mine), "rb") as a, open(os.path.join(ROOT, theirs), "rb") as b:
        assert a.read() == b.read()


def test_the_reference_imports_nothing_of_the_system():
    with open(os.path.join(ROOT, "comfyui_distributed_tpu/reference/solar_open2.py"),
              encoding="utf-8") as fh:
        imports = [line for line in fh if line.startswith(("import ", "from "))]
    assert sorted(imports) == sorted([
        "from __future__ import annotations\n", "import dataclasses\n",
        "import jax\n", "import jax.numpy as jnp\n", "import numpy as np\n"])


def test_both_models_with_experts_call_the_one_expert_layer():
    from comfyui_distributed_tpu.models import deepseek_v2, moe, solar_open2

    assert deepseek_v2.expert_layer is moe.expert_layer is solar_open2.expert_layer
    for module in (deepseek_v2, solar_open2):
        with open(module.__file__, encoding="utf-8") as fh:
            assert "ragged_dot(" not in fh.read()


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not on this machine")
    with open(path, encoding="utf-8") as fh:
        return next(row for row in map(json.loads, fh) if row["name"] == "Solar-Open2-250B")


REDUCED = {"num_hidden_layers": (48, 4), "n_routed_experts": (320, 40),
           "vocab_size": (196608, 24576)}
WIDTHS = {
    "hidden_size": 4096, "num_attention_heads": 64, "num_key_value_heads": 8, "head_dim": 128,
    "moe_intermediate_size": 1280, "intermediate_size": 10240, "num_experts_per_tok": 8,
    "n_shared_experts": 1, "rms_norm_eps": 1e-5, "gqa_interval": 3, "use_rope": False,
    "use_gqa_gate": True, "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
    "norm_topk_prob": True, "routed_scaling_factor": 1, "first_k_dense_replace": 0,
    "max_position_embeddings": 1048576, "tie_word_embeddings": False,
}


def test_the_configuration_keeps_every_published_width_and_states_its_cut():
    config = load(CONFIG)
    for key, value in WIDTHS.items():
        assert config[key] == value, key
    assert config["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64, "num_kv_heads": None}
    assert config["gqa_layers"] == list(range(0, 48, 4))
    assert config["reduced"] == sorted(REDUCED, key=list(REDUCED).index)
    for key, (published, held) in REDUCED.items():
        assert (config["published"][key], config[key]) == (published, held), key
    assert config["source"] == (
        "https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json")
    assert config["reference"] == "benchmark/reference/solar_open2.py"
    assert config["as_run"]["parameters"] == {"lm": 3308353344}
    assert config["as_run"]["cache_bytes_per_token"] == 4096
    assert config["as_run"]["state_bytes"] == 13025280
    assert set(config["held"]) == {"layers", "experts", "vocabulary", "state"}
    assert "8 chips of one v5e-8 host" in config["deployment"]
    assumed = " ".join(config["assumed"])
    for word in ("low-rank", "element-wise", "sigmoids", "seeded random", "stand-in",
                 "batch is 1", "dt_bias", "house style guide"):
        assert word in assumed, word
    limits = config["parity"]
    assert 0 < limits["tolerance_rel_l2_median"] <= limits["tolerance_rel_l2_max_unflipped"] < 0.2
    assert 0 < limits["tolerance_expert_set_mismatch"] < 0.5
    assert 0 < limits["tolerance_state_rel_l2"] < 0.2


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
    linear = config["linear_attn_config"]
    assert (cfg.linear_num_heads, cfg.linear_head_dim, cfg.short_conv_kernel_size) == (
        linear["num_heads"], linear["head_dim"], linear["short_conv_kernel_size"])
    assert (cfg.num_hidden_layers, len(cfg.held_experts), cfg.vocab_held) == (
        config["num_hidden_layers"], config["n_routed_experts"], config["vocab_size"])
    assert (cfg.n_routed_experts, cfg.vocab_size, cfg.ep_size, cfg.vocab_shards) == (
        320, 196608, 8, 8)
    assert cfg.kda_chunk == config["as_run"]["kda_chunk"] == 64
    assert [layer for layer in range(48) if type(cfg)().is_full(layer)] == config["gqa_layers"]


def test_the_manifest_has_the_cell_with_the_issues_traffic_and_lists():
    manifest = load(os.path.join(ROOT, "BENCHMARK.json"))
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "solar-open2-250b", "closed2", 1)
    (config,) = [c for c in manifest["configs"] if c["name"] == "solar-open2-250b"]
    assert config["reduced"] == list(REDUCED)
    assert config["file"] == "benchmark/configs/solar-open2-250b.json"
    listed = {m["name"] for m in manifest["per_layer"] + manifest["end_to_end"]
              if CELL in m.get("workloads", [])}
    ouro = {m["name"] for m in manifest["per_layer"] + manifest["end_to_end"]
            if "ouro_2_6b_rewrite_txt2img_512.closed2" in m.get("workloads", [])}
    new = listed - ouro - {"experts_held_share_pct.lm"}
    assert ouro < listed and "experts_held_share_pct.lm" in listed
    assert "state_mb.lm" in new and new <= {"state_mb.lm", "linear_attention_device_pct.lm"}
    for name in new:
        (metric,) = [m for m in manifest["per_layer"] if m["name"] == name]
        # its own cell first; a later model's cell may follow (PR 41's, under state_mb.lm)
        assert metric["workloads"][0] == CELL and metric["moves"] == "images_per_s"
        assert os.path.exists(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py"))
    work = load(WORKLOAD)
    assert work["workflow"] == "benchmark/workflows/rewrite-txt2img-solar-open2.json"
    assert work["seed_nodes"] == ["DistributedSeed"]
    assert work["compute_nodes"] == ["TextGenerate", "KSampler"]
    assert work["rate"] == {"metric": "images_per_s", "units_per_job": 1}
    assert work["trace"] == {"start_s": 5, "slice_s": 12}


def test_the_prefill_on_the_kernels_route_gives_the_xla_routes_logits(monkeypatch):
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
    assert routes == ["xla-causal 640x640x16/16 bq256 f32"]

    monkeypatch.setattr(attention, "causal_route", lambda *operands: "flash")
    kernel = attention.flash_attention
    monkeypatch.setattr(
        attention, "flash_attention",
        lambda *operands, **options: kernel(*operands, **{**options, "interpret": True}))
    with attention.route_log() as routes:  # another cache length: traced anew
        got = solar_open2.prefill(cfg, params, ids, cache_len=704, collect=True)
    assert routes == ["flash-causal 640x640x16/16 g2 bq128 bk640 f32 blocks5/5"]
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
