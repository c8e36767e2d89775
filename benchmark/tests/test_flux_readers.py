"""The FLUX cell's readers and counts on made-up material: the value
where the spans, the records and the trace carry what they read, None
where the program (the parent's) or the run (untraced) has none.

    python -m pytest benchmark/tests -q
"""

import importlib.util
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import flux_counts  # noqa: E402
import flux_reduce  # noqa: E402


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


def request(wait_s, evals=20):
    sampler = {"evals": evals, "family": "mmdit", "tokens": 4608} if evals else {}
    return [
        span("execute_prompt", "e", None, wait_s + 0.5),
        span("node.KSampler", "k", "e", 0.004, **sampler),
        span("node.SaveImage", "n", "e", wait_s + 0.1),
        span("device.wait", "w", "n", wait_s),
        span("device.wait", "stray", None, 100.0),  # not below execute_prompt
    ]


CFG = flux_counts.config()
N = flux_counts.tokens(CFG)
CALLS = 20 * flux_counts.attention_calls_per_evaluation(CFG)
LEAST_S = flux_counts.roofline_seconds(
    flux_counts.attention_flops(CFG, N), flux_counts.attention_bytes(CFG, N), "TPU v5 lite")


def material(evals=20, kernel_s=4.0, period_s=5.0):
    """Five jobs, one every `period_s`; a 15 s slice, 12 s of it busy,
    `kernel_s` of it in the kernel."""
    ops = [["fusion", 7.0], ["copy", 1.0]]
    if kernel_s:
        ops.insert(1, ["flash_attention", kernel_s])
    return {
        "spans": {f"t{i}": request(wait_s=4.0 + i, evals=evals) for i in range(5)},
        "records": [{"ok": True, "end": 100.0 + period_s * i} for i in range(5)]
        + [{"ok": False, "end": 500.0}],
        "trace": {"busy_s": 12.0, "window_s": 15.0, "breakdown": {"device_ops": ops}},
    }


def test_eval_ms_is_the_device_wait_of_the_median_request_over_its_evaluations():
    assert reader("eval_ms.flux")(material()) == pytest.approx(1e3 * 6.0 / 20)


def test_kernel_share_of_busy_time():
    assert reader("flash_attention_pct.flux")(material()) == pytest.approx(100 * 4.0 / 12.0)


def test_roofline_share_follows_its_stated_formula():
    # the kernel holds 4 of the slice's 15 s, a job takes 5 s: 4/3 s a job
    expected = 100.0 * CALLS * LEAST_S / (4.0 / 15.0 * 5.0)
    assert reader("flash_attention_roofline_pct.flux")(material()) == pytest.approx(expected)
    assert 0 < expected < 100
    # a kernel at its roofline reads 100
    at_peak = material(kernel_s=CALLS * LEAST_S / 5.0 * 15.0)
    assert reader("flash_attention_roofline_pct.flux")(at_peak) == pytest.approx(100.0)


PARENT = material(evals=None)           # a program that sets no `evals`
NO_KERNEL = material(kernel_s=None)     # a trace without the kernel
UNTRACED = {**material(), "trace": None}
EMPTY = {"spans": {}, "records": [], "trace": None}


@pytest.mark.parametrize("name, where", [
    ("eval_ms.flux", PARENT), ("eval_ms.flux", EMPTY),
    ("flash_attention_pct.flux", NO_KERNEL), ("flash_attention_pct.flux", UNTRACED),
    ("flash_attention_pct.flux", EMPTY),
    ("flash_attention_roofline_pct.flux", PARENT),
    ("flash_attention_roofline_pct.flux", NO_KERNEL),
    ("flash_attention_roofline_pct.flux", UNTRACED),
    ("flash_attention_roofline_pct.flux", EMPTY),
])
def test_reader_gives_none_where_there_is_nothing_to_read(name, where):
    assert reader(name)(where) is None


def test_job_period_needs_two_finished_jobs():
    assert flux_reduce.job_period_seconds(material()) == pytest.approx(5.0)
    assert flux_reduce.job_period_seconds({"records": [{"ok": True, "end": 1.0}]}) is None


def test_counts_are_the_ones_the_issue_worked_out():
    assert N == 4608
    assert flux_counts.attention_flops(CFG, N) == pytest.approx(0.261e12, rel=0.01)
    per_block = flux_counts.evaluation_flops(CFG, N) / 15
    assert per_block == pytest.approx(1.3e12, rel=0.02)  # 1.04 linear + 0.26 attention
    assert 20 * flux_counts.evaluation_flops(CFG, N) == pytest.approx(392e12, rel=0.01)
    # compute-bound: the kernel's intensity is far right of the ridge
    intensity = flux_counts.attention_flops(CFG, N) / flux_counts.attention_bytes(CFG, N)
    assert intensity > 197e12 / 819e9
    assert LEAST_S == pytest.approx(flux_counts.attention_flops(CFG, N) / 197e12)


def test_an_unknown_device_is_an_error_not_a_default():
    with pytest.raises(KeyError):
        flux_counts.peaks("TPU v9")


def test_the_sizes_the_counts_read_are_the_registrys():
    sys.path.insert(0, os.path.dirname(HERE))
    from comfyui_distributed_tpu.models import get_config

    model = get_config(CFG["registry_name"])
    t5 = get_config("t5-xxl-6l")
    assert (model.double_depth, model.single_depth) == (CFG["num_layers"], CFG["num_single_layers"])
    assert (model.heads, model.head_dim) == (CFG["num_attention_heads"], CFG["attention_head_dim"])
    assert model.hidden_dim == 3072 and model.mlp_width == 12288
    assert model.context_dim == CFG["joint_attention_dim"]
    assert model.vec_dim == CFG["pooled_projection_dim"]
    assert model.in_channels * model.patch_size ** 2 == CFG["in_channels"]
    assert list(model.axes_dim) == CFG["axes_dims_rope"]
    assert model.guidance_embed is CFG["guidance_embeds"]
    enc = CFG["text_encoder_2"]
    assert (t5.layers, t5.d_model, t5.d_ff, t5.heads, t5.d_kv, t5.vocab_size, t5.max_length) == (
        enc["num_layers"], enc["d_model"], enc["d_ff"], enc["num_heads"], enc["d_kv"],
        enc["vocab_size"], 512)
    # depth is the only cut: the full-depth entries differ in nothing else
    import dataclasses

    full = get_config("flux-dev")
    assert dataclasses.replace(full, double_depth=5, single_depth=10) == model
    assert dataclasses.replace(get_config("t5-xxl"), layers=6) == t5
    assert (full.double_depth, full.single_depth) == (
        CFG["published"]["num_layers"], CFG["published"]["num_single_layers"])
