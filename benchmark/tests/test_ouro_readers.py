"""The Ouro-2.6B cell's readers and counts on made-up material: the value
where the spans carry what they read (a looped model's attributes, and a
model's that walks its layers once, as the DeepSeek cell's and the parent's
do), None where the program has no such node; the counts against a hand
calculation and against the program's own.

    python -m pytest benchmark/tests -q
"""

import importlib.util
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import ouro_counts  # noqa: E402

OURO = ouro_counts.config()

LOOPED = dict(prompt_tokens=2048, new_tokens=64, ut_steps=4, layers=48, cache_slots=192,
              cache_bytes=3321888768, prefill_layer_passes=2048 * 192,
              decode_layer_passes=64 * 192, exit_mass_1=1700.0, exit_mass_2=250.0,
              exit_mass_3=100.0, exit_mass_4=62.0)
ONCE = dict(prompt_tokens=2048, new_tokens=256, layers=5, experts_held=40, experts_total=160,
            cache_bytes=13271040)


def ouro_reader(name: str):
    """The metric's read(), loaded as run.py loads it."""
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("layer_metric", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def lm_span(name, span_id, parent_id, duration, **attrs):
    return {"name": name, "span_id": span_id, "parent_id": parent_id,
            "start": 0.0, "end": duration, "duration": duration, "attrs": attrs}


def lm_request(attrs):
    spans = [lm_span("execute_prompt", "e", None, 3.4),
             lm_span("node.KSampler", "k", "e", 0.004, evals=40)]
    if attrs is not None:
        spans += [lm_span("node.TextGenerate", "g", "e", 2.9, **attrs),
                  lm_span("device.wait", "w", "g", 2.85)]
    return spans


def lm_material(attrs, jobs=5):
    return {"spans": {f"t{i}": lm_request(attrs) for i in range(jobs)},
            "records": [], "trace": None}


def test_cache_gb_is_the_nodes_cache_bytes():
    assert ouro_reader("cache_gb.lm")(lm_material(LOOPED)) == pytest.approx(3.321888768)
    assert ouro_reader("cache_gb.lm")(lm_material(ONCE)) == pytest.approx(0.01327104)


def test_layer_passes_per_token_counts_the_loop():
    assert ouro_reader("layer_passes_per_token.lm")(lm_material(LOOPED)) == pytest.approx(192.0)


def test_a_model_that_says_only_its_layers_walks_each_once():
    # the DeepSeek cell's attributes, which this PR leaves as the parent's
    assert ouro_reader("layer_passes_per_token.lm")(lm_material(ONCE)) == pytest.approx(5.0)


def test_layer_passes_per_token_weighs_requests_by_their_tokens():
    short = dict(LOOPED, prompt_tokens=100, new_tokens=28, prefill_layer_passes=100 * 192,
                 decode_layer_passes=28 * 96)              # half the passes on its new tokens
    both = {"spans": {"a": lm_request(LOOPED), "b": lm_request(short)}}
    walked = (2048 + 64) * 192 + 100 * 192 + 28 * 96
    assert ouro_reader("layer_passes_per_token.lm")(both) == pytest.approx(walked / (2112 + 128))


@pytest.mark.parametrize("where", [lm_material(None), {"spans": {}, "records": [], "trace": None}],
                         ids=["no_node", "empty"])
@pytest.mark.parametrize("name", ["cache_gb.lm", "layer_passes_per_token.lm"])
def test_ouro_reader_gives_none_where_there_is_nothing_to_read(name, where):
    assert ouro_reader(name)(where) is None


def test_the_accepted_lm_readers_read_the_looped_models_node_too():
    material = lm_material(LOOPED)
    assert ouro_reader("generate_ms.lm")(material) == pytest.approx(2900.0)
    assert ouro_reader("decode_ms_per_token.lm")(material) == pytest.approx(1e3 * 2.85 / 64)
    assert ouro_reader("lm_share_pct.rewrite")(material) == pytest.approx(100 * 2.9 / 3.4)


def test_ouro_counts_are_the_ones_the_issue_worked_out():
    """By hand: attention 4 x 2048^2 = 16,777,216; SwiGLU 3 x 2048 x 5632 =
    34,603,008; four norms 8,192: 51,388,416 a layer, x 48 = 2,466,643,968;
    embedding + untied head 2 x 49,152 x 2,048 = 201,326,592; final norm
    2,048; exit gate 2,049."""
    assert ouro_counts.layer_params(OURO) == 16777216 + 34603008 + 8192 == 51388416
    assert ouro_counts.total_params(OURO) == 2466643968 + 201326592 + 2048 + 2049
    assert ouro_counts.total_params(OURO) == OURO["as_run"]["parameters"]["lm"] == 2667974657
    assert 2 * ouro_counts.total_params(OURO) == pytest.approx(5.336e9, rel=1e-4)
    assert ouro_counts.layer_passes(OURO) == 192
    # 4 x 48 x (K + V) x 16 heads x 128 x 2 B
    assert ouro_counts.cache_bytes_per_token(OURO) == 4 * 48 * 8192 == 1572864
    assert ouro_counts.cache_bytes_per_token(OURO) == OURO["as_run"]["cache_bytes_per_token"]
    assert ouro_counts.cache_bytes(OURO, 2112) == pytest.approx(3.322e9, rel=1e-4)
    assert ouro_counts.cache_bytes(OURO, OURO["as_run"]["cache_positions"]) == 3321888768


def test_a_decode_step_reads_the_layers_four_times_and_a_prefill_is_44_tflop():
    step = ouro_counts.decode_step_bytes(OURO, 2112)
    # 4.93 GB of layer weights four times, 0.2 GB of head, 3.32 GB of cache
    by_hand = 4 * 4.933e9 + 0.2013e9 + 3.3219e9
    assert step == pytest.approx(by_hand, rel=1e-3)
    assert step / 819e9 == pytest.approx(28.4e-3, rel=5e-3)         # seconds at the roofline
    assert ouro_counts.decode_step_flops(OURO, 2112) / step < 2.5   # far left of the ridge
    flops = ouro_counts.prefill_flops(OURO, 2048)
    # 2 x 2.4663 B x 4 x 2,048 = 40.4 TFLOP of products + 3.3 of attention
    assert flops == pytest.approx(40.4e12 + 3.3e12, rel=5e-3)
    assert 192 * ouro_counts.causal_attention_flops(OURO, 2048) == pytest.approx(3.3e12, rel=5e-3)
    peak = ouro_counts.peaks("TPU v5 lite")
    assert flops / peak["flops_per_s"] > ouro_counts.prefill_bytes(OURO, 2048) / peak["bytes_per_s"]
    with pytest.raises(KeyError):
        ouro_counts.peaks("TPU v9")


def test_the_sizes_the_ouro_counts_read_are_the_registrys():
    sys.path.insert(0, os.path.dirname(HERE))
    from comfyui_distributed_tpu.models import get_config, ouro

    model = get_config(OURO["registry_name"])
    assert ouro.param_count(model) == ouro_counts.total_params(OURO)
    positions = OURO["as_run"]["cache_positions"]
    held = 1
    for size in model.cache_shape(positions):
        held *= size
    assert held * 2 == ouro_counts.cache_bytes(OURO, positions)
    assert model.layer_passes == ouro_counts.layer_passes(OURO)


@pytest.mark.parametrize("mine, theirs", [
    ("workflows/rewrite-txt2img-ouro-2.6b.json", "workflows/rewrite-txt2img-ouro-2.6b.json"),
    ("reference/ouro.py", "comfyui_distributed_tpu/reference/ouro.py"),
])
def test_the_ouro_copies_here_are_the_committed_files(mine, theirs):
    root = os.path.dirname(HERE)
    with open(os.path.join(HERE, mine), "rb") as a, open(os.path.join(root, theirs), "rb") as b:
        assert a.read() == b.read()
