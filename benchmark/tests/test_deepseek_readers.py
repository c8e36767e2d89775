"""The DeepSeek-V2 cell's readers and counts on made-up material: the
value where the spans carry what they read, None where the program (the
parent's) has no such node; the counts against a hand calculation.

    python -m pytest benchmark/tests -q
"""

import importlib.util
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import deepseek_counts  # noqa: E402
import deepseek_reduce  # noqa: E402

CFG = deepseek_counts.config()


def reader(name: str):
    """The metric's read(), loaded as run.py loads it."""
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("layer_metric", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def span(name, span_id, parent_id, duration, **attrs):
    return {"name": name, "span_id": span_id, "parent_id": parent_id,
            "start": 0.0, "end": duration, "duration": duration, "attrs": attrs}


def request(wait_s, held=(3000, 400), node=True):
    """A job of wait_s + 0.55 s in the executor; the language model's
    node holds wait_s + 0.05 s of it."""
    spans = [
        span("execute_prompt", "e", None, wait_s + 0.55),
        span("node.KSampler", "k", "e", 0.004, evals=40),
        span("node.SaveImage", "n", "e", 0.45),
        span("device.wait", "w2", "n", 0.43),
    ]
    if node:
        spans += [
            span("node.TextGenerate", "g", "e", wait_s + 0.05, prompt_tokens=2048,
                 new_tokens=256, layers=5, experts_held=40, experts_total=160,
                 cache_bytes=13271040, prefill_routed_pairs=49152,
                 prefill_routed_pairs_held=held[0], prefill_expert_load_max=120,
                 decode_routed_pairs=6144, decode_routed_pairs_held=held[1],
                 decode_expert_load_max=9),
            span("lm.prefill", "p", "g", 0.01),
            span("lm.decode", "d", "g", 0.01),
            span("device.wait", "w", "g", wait_s),
            span("lm.detokenize", "t", "g", 0.001),
        ]
    return spans


def material(**kwargs):
    """Five jobs whose waits for the language model are 1.0 .. 1.4 s."""
    return {"spans": {f"t{i}": request(1.0 + 0.1 * i, **kwargs) for i in range(5)},
            "records": [], "trace": None}


def test_generate_ms_is_the_median_node():
    assert reader("generate_ms.lm")(material()) == pytest.approx(1e3 * 1.25)


def test_decode_ms_per_token_is_the_nodes_wait_over_the_new_tokens():
    assert reader("decode_ms_per_token.lm")(material()) == pytest.approx(1e3 * 1.2 / 256)


def test_lm_share_is_the_node_over_the_executors_span():
    assert reader("lm_share_pct.rewrite")(material()) == pytest.approx(100 * 1.25 / 1.75)


def test_held_share_sums_both_phases():
    expected = 100.0 * (3000 + 400) / (49152 + 6144)
    assert reader("experts_held_share_pct.lm")(material()) == pytest.approx(expected)
    even = material(held=(12288, 1536))
    assert reader("experts_held_share_pct.lm")(even) == pytest.approx(25.0)


PARENT = material(node=False)  # a program without the node
EMPTY = {"spans": {}, "records": [], "trace": None}


@pytest.mark.parametrize("where", [PARENT, EMPTY], ids=["parent", "empty"])
@pytest.mark.parametrize("name", [
    "generate_ms.lm", "decode_ms_per_token.lm", "lm_share_pct.rewrite",
    "experts_held_share_pct.lm",
])
def test_lm_reader_gives_none_where_there_is_nothing_to_read(name, where):
    assert reader(name)(where) is None


def test_the_wait_is_the_one_under_the_node():
    assert deepseek_reduce.wait_seconds(request(1.0)) == 1.0
    assert deepseek_reduce.wait_seconds(request(1.0, node=False)) is None
    assert deepseek_reduce.attrs_of(request(1.0))["new_tokens"] == 256
    assert deepseek_reduce.attrs_of(request(1.0, node=False)) == {}


def test_deepseek_counts_are_the_ones_the_issue_worked_out():
    """By hand, in millions of parameters: attention 5120 x 1536 = 7.86,
    1536 x 128 x 192 = 37.75, 5120 x 576 = 2.95, 512 x 128 x 256 = 16.78,
    16384 x 5120 = 83.89; the dense SwiGLU 3 x 5120 x 12288 = 188.74; an
    expert 3 x 5120 x 1536 = 23.59; the shared pair 47.19; the router
    5120 x 160 = 0.82; embedding and head slices 2 x 25600 x 5120."""
    assert deepseek_counts.attention_params(CFG) == 7864320 + 37748736 + 2949120 + 16777216 + 83886080
    assert deepseek_counts.attention_params(CFG) == pytest.approx(149.23e6, rel=1e-4)
    assert deepseek_counts.expert_params(CFG) == 23592960
    assert deepseek_counts.shared_params(CFG) == 47185920
    assert deepseek_counts.router_params(CFG) == 819200
    expert_layer = 149225472 + 819200 + 47185920 + 40 * 23592960
    assert expert_layer == pytest.approx(1140.96e6, rel=1e-5)
    dense_layer = 149225472 + 188743680
    assert dense_layer == pytest.approx(337.98e6, rel=1e-4)
    norms = 5 * (2 * 5120 + 1536 + 512) + 5120
    assert deepseek_counts.total_params(CFG) == dense_layer + 4 * expert_layer + 262144000 + norms
    assert deepseek_counts.total_params(CFG) == CFG["as_run"]["parameters"]["lm"]
    assert 2 * deepseek_counts.total_params(CFG) == pytest.approx(10.33e9, rel=1e-3)
    assert deepseek_counts.cache_bytes(CFG, 1) == CFG["as_run"]["cache_bytes_per_token"] == 5760
    assert deepseek_counts.cache_bytes(CFG, 2304) == pytest.approx(13.27e6, rel=1e-3)


def test_a_decode_step_reads_2_8_gb_and_a_prefill_is_6_tflop():
    # the dense layer 0.68 GB, four expert layers at 0.39 + 1.5 experts of 47 MB, the head 0.26
    step = deepseek_counts.decode_step_bytes(CFG, 1.5, 2048 + 128)
    by_hand = 2 * (337.98e6 + 4 * (197.23e6 + 1.5 * 23.59e6) + 131.07e6) + 2176 * 5760
    assert step == pytest.approx(by_hand, rel=1e-3)
    assert step == pytest.approx(2.81e9, rel=5e-3)
    assert 256 * step / 819e9 == pytest.approx(0.88, rel=0.01)  # seconds a job at the roofline
    # about two operations a byte: far left of the ridge
    assert deepseek_counts.decode_step_flops(CFG, 1.5, 2176) / step < 3
    flops = deepseek_counts.prefill_flops(CFG, 2048, 4 * 3072)
    assert flops == pytest.approx(6.06e12, rel=0.01)
    attention = 5 * deepseek_counts.causal_attention_flops(CFG, 2048)
    assert attention == pytest.approx(0.86e12, rel=0.01)
    assert deepseek_counts.roofline_seconds(flops, deepseek_counts.prefill_bytes(CFG, 2048),
                                            "TPU v5 lite") == pytest.approx(flops / 197e12)


def test_deepseek_counts_refuse_an_unknown_device():
    with pytest.raises(KeyError):
        deepseek_counts.peaks("TPU v9")


def test_the_sizes_the_deepseek_counts_read_are_the_registrys():
    sys.path.insert(0, os.path.dirname(HERE))
    from comfyui_distributed_tpu.models import deepseek_v2, get_config

    model = get_config(CFG["registry_name"])
    assert deepseek_v2.param_count(model) == deepseek_counts.total_params(CFG)
    assert model.cache_width * 2 * model.num_hidden_layers == deepseek_counts.cache_bytes(CFG, 1)


@pytest.mark.parametrize("mine, theirs", [
    ("workflows/rewrite-txt2img-deepseek-v2.json", "workflows/rewrite-txt2img-deepseek-v2.json"),
    ("reference/deepseek_v2.py", "comfyui_distributed_tpu/reference/deepseek_v2.py"),
])
def test_the_copies_here_are_the_committed_files(mine, theirs):
    root = os.path.dirname(HERE)
    with open(os.path.join(HERE, mine), "rb") as a, open(os.path.join(root, theirs), "rb") as b:
        assert a.read() == b.read()
