"""`node.UltimateSDUpscaleDistributed` says which attention its program
took, on the request that traces the program and on no other, as
`node.KSampler` does (`ops/attention.route_log`)."""

import jax
import jax.numpy as jnp
import numpy as np

from comfyui_distributed_tpu.graph.executor import ExecutionContext
from comfyui_distributed_tpu.graph.nodes_upscale import UltimateSDUpscaleDistributed
from comfyui_distributed_tpu.models import pipeline as pl
from comfyui_distributed_tpu.telemetry import get_tracer


def _upscale(bundle, pos, neg, image, seed, **sizes):
    with get_tracer().span("node.UltimateSDUpscaleDistributed") as span:
        (out,) = UltimateSDUpscaleDistributed().run(
            image=image, model=bundle, positive=pos, negative=neg, vae=bundle,
            seed=seed, steps=1, cfg=1.0, sampler_name="euler", scheduler="karras",
            denoise=0.3, upscale_by=2.0, context=ExecutionContext(), **sizes,
        )
    return out, dict(span.attrs)


def test_the_tracing_request_names_the_short_kernel_where_a_tpu_s_rule_sends_a_call(monkeypatch):
    """On a TPU's routes (the backend patched, both kernels interpreted)
    the attribute carries `ops/short_attention.py`'s grammar for the
    calls its rule takes and the other routes' for the rest. The toy
    UNet is given SDXL's 64-wide heads (2 and 4 of them) and the rule's
    table the toy's rows and keys: what is pinned is that a call the rule
    names reaches the kernel through the node and logs its own entry."""
    import dataclasses

    from comfyui_distributed_tpu.models import registry
    from comfyui_distributed_tpu.ops import attention, short_attention

    tiny = registry.MODEL_REGISTRY["tiny-unet"]
    wide = dataclasses.replace(tiny["config"], model_channels=128, head_dim=64, dtype="bfloat16")
    monkeypatch.setitem(registry.MODEL_REGISTRY, "tiny-unet", dict(tiny, config=wide))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        short_attention, "WINNING_SHAPES", (((400, 1600), (16, 16)), ((400, 400), (400, 400))))
    for module, name in ((short_attention, "short_attention"), (attention, "flash_attention")):
        def interpreted(*operands, kernel=getattr(module, name), **options):
            return kernel(*operands, **dict(options, interpret=True))

        monkeypatch.setattr(module, name, interpreted)
    bundle = pl.load_pipeline("tiny-unet")
    pos, neg = pl.encode_text(bundle, ["p"]), pl.encode_text(bundle, [""])
    image = jnp.asarray(np.random.default_rng(3).random((1, 32, 32, 3)), jnp.float32)
    out, attrs = _upscale(
        bundle, pos, neg, image, 1, tile_width=32, tile_height=32, tile_padding=8)
    assert out.shape == (1, 64, 64, 3) and bool(jnp.isfinite(out).all())
    # an 80 px tile is a 40 x 40 latent: 1,600 tokens at 2 heads, 400 at 4, over 16 text keys;
    # the 32-wide head is the VAE's
    assert attrs["attention"].split(", ") == [
        "flash 1600x1600x32 pad1600x1792 bq400 bk896 bf16",
        "flash 1600x1600x64 pad1600x1792 bq400 bk896 bf16",
        "short 1600x16x64 pad1600x128 h2 bq400 bf16 inplace",
        "short 400x16x64 pad400x128 h4 bq400 bf16 inplace",
        "short 400x400x64 pad400x512 h4 bq400 bf16 inplace",
    ]


def test_the_tracing_request_carries_the_attention_routes():
    bundle = pl.load_pipeline("tiny-unet")
    pos, neg = pl.encode_text(bundle, ["p"]), pl.encode_text(bundle, [""])
    image = jnp.asarray(np.random.default_rng(2).random((1, 64, 64, 3)), jnp.float32)
    attrs = []
    for seed in (1, 2):
        out, attr = _upscale(
            bundle, pos, neg, image, seed, tile_width=64, tile_height=64, tile_padding=16)
        assert out.shape == (1, 128, 128, 3)
        attrs.append(attr)
    # tiny-vae halves the image: a 96 px tile is a 48 x 48 latent, 2,304
    # and 576 tokens over themselves and over 16 text keys; off a TPU
    # every call is XLA's
    routes = attrs[0]["attention"].split(", ")
    assert "xla 2304x2304x16" in routes and "xla 576x16x32" in routes
    assert all(route.startswith("xla ") for route in routes)
    assert routes == sorted(set(routes))
    assert "attention" not in attrs[1]
