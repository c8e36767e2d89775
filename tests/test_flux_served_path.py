"""The committed FLUX workflow through the graph executor on tiny-flux:
what its `node.KSampler` and `node.CheckpointLoaderSimple` spans say, and
that the benchmark's copies of the workflow and of the reference are the
committed files byte for byte."""

import json
import os

import pytest

from comfyui_distributed_tpu.graph.executor import ExecutionContext, GraphExecutor
from comfyui_distributed_tpu.telemetry import get_tracer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKFLOW = os.path.join(ROOT, "workflows", "txt2img-flux-dev.json")
CONFIG = os.path.join(ROOT, "benchmark", "configs", "flux.1-dev.json")


def spans_of(trace_id, name):
    return [s for s in get_tracer().spans(trace_id) if s["name"] == name]


@pytest.fixture(scope="module")
def graph():
    with open(WORKFLOW, encoding="utf-8") as fh:
        prompt = json.load(fh)
    (loader,) = [n for n in prompt.values() if n["class_type"] == "CheckpointLoaderSimple"]
    (latent,) = [n for n in prompt.values() if n["class_type"] == "EmptyLatentImage"]
    loader["inputs"]["ckpt_name"] = "tiny-flux"
    latent["inputs"].update(width=64, height=32)
    return prompt


def test_the_workflow_is_the_one_the_issue_describes():
    with open(WORKFLOW, encoding="utf-8") as fh:
        prompt = json.load(fh)
    kinds = sorted(n["class_type"] for n in prompt.values())
    assert kinds == sorted([
        "CheckpointLoaderSimple", "CLIPTextEncode", "CLIPTextEncode", "FluxGuidance",
        "EmptyLatentImage", "DistributedSeed", "KSampler", "VAEDecode",
        "DistributedCollector", "SaveImage"])
    by_kind = {n["class_type"]: n["inputs"] for n in prompt.values()}
    sampler = by_kind["KSampler"]
    assert (sampler["steps"], sampler["cfg"], sampler["sampler_name"], sampler["scheduler"],
            sampler["denoise"]) == (20, 1.0, "euler", "simple", 1.0)
    assert by_kind["FluxGuidance"]["guidance"] == 3.5
    assert prompt[sampler["positive"][0]]["class_type"] == "FluxGuidance"
    assert by_kind["EmptyLatentImage"] == {"width": 1024, "height": 1024, "batch_size": 1}
    with open(CONFIG, encoding="utf-8") as fh:
        assert by_kind["CheckpointLoaderSimple"]["ckpt_name"] == json.load(fh)["registry_name"]


def test_the_workflow_runs_on_tiny_flux_and_its_spans_say_what_ran(graph, tmp_path, monkeypatch):
    monkeypatch.setenv("CDT_OUTPUT_DIR", str(tmp_path))
    executor = GraphExecutor(ExecutionContext())
    tracer = get_tracer()
    traces = []
    for seed in (42, 43):
        for node in graph.values():
            if node["class_type"] == "DistributedSeed":
                node["inputs"]["seed"] = seed
        with tracer.span("execute_prompt") as root:
            executor.execute(graph)
        traces.append(root.trace_id)
    assert len([f for f in os.listdir(tmp_path) if f.startswith("flux-dev")]) == 2

    (first,) = spans_of(traces[0], "node.KSampler")
    # tiny-vae-flux halves the image: a 32 x 16 latent in 2 x 2 patches, and
    # tiny-t5-shared's 16 text tokens in the same sequence
    assert first["attrs"]["family"] == "mmdit"
    assert first["attrs"]["tokens"] == 16 * 8 + 16
    # cfg 1.0: one evaluation a step, the negative is not evaluated
    assert first["attrs"]["evals"] == 20
    # the request that built the program says which attention it took
    assert first["attrs"]["attention"] == "xla 144x144x16"
    (second,) = spans_of(traces[1], "node.KSampler")
    assert "attention" not in second["attrs"] and "compiles" not in second["attrs"]
    assert (second["attrs"]["tokens"], second["attrs"]["evals"]) == (144, 20)

    (loader,) = spans_of(traces[0], "node.CheckpointLoaderSimple")
    for part in ("unet", "vae", "te", "te2"):
        # float32 on the CPU: four bytes a parameter
        assert loader["attrs"][f"{part}_bytes"] == 4 * loader["attrs"][f"{part}_params"] > 0
    assert spans_of(traces[1], "node.CheckpointLoaderSimple") == []  # cached


def test_evals_count_the_negative_and_second_order_samplers():
    from comfyui_distributed_tpu.graph import nodes_core
    from comfyui_distributed_tpu.models import pipeline as pl
    from comfyui_distributed_tpu.ops.conditioning import Conditioning
    import jax.numpy as jnp

    bundle = pl.PipelineBundle(model_name="tiny-unet", unet=None, vae=None, text_encoder=None,
                               params={}, tokenizer=None)
    positive = [Conditioning(context=jnp.zeros((1, 16, 64)))]
    tracer = get_tracer()
    seen = {}
    for cfg, sampler in ((1.0, "euler"), (7.0, "euler"), (7.0, "heun")):
        with tracer.span("node.KSampler") as span:
            nodes_core._annotate_sampling(
                bundle, jnp.zeros((1, 8, 6, 4)), positive, 20, cfg, sampler)
        seen[cfg, sampler] = span.attrs
    assert seen[1.0, "euler"] == {"family": "unet", "tokens": 48, "evals": 20}
    assert seen[7.0, "euler"]["evals"] == 40
    assert seen[7.0, "heun"]["evals"] == 78  # 2 x 20 - 1 evaluations, each with its negative


@pytest.mark.parametrize("mine, theirs", [
    ("benchmark/workflows/txt2img-flux-dev.json", "workflows/txt2img-flux-dev.json"),
    ("benchmark/reference/flux.py", "comfyui_distributed_tpu/reference/flux.py"),
])
def test_the_benchmarks_copies_are_the_committed_files(mine, theirs):
    with open(os.path.join(ROOT, mine), "rb") as a, open(os.path.join(ROOT, theirs), "rb") as b:
        assert a.read() == b.read()


def test_the_configuration_names_the_benchmarks_copy_of_the_reference():
    with open(CONFIG, encoding="utf-8") as fh:
        config = json.load(fh)
    assert config["reference"] == "benchmark/reference/flux.py"
    assert os.path.isfile(os.path.join(ROOT, config["reference"]))
