"""`node.UltimateSDUpscaleDistributed` says which attention its program
took, on the request that traces the program and on no other, as
`node.KSampler` does (`ops/attention.route_log`)."""

import jax.numpy as jnp
import numpy as np

from comfyui_distributed_tpu.graph.executor import ExecutionContext
from comfyui_distributed_tpu.graph.nodes_upscale import UltimateSDUpscaleDistributed
from comfyui_distributed_tpu.models import pipeline as pl
from comfyui_distributed_tpu.telemetry import get_tracer


def test_the_tracing_request_carries_the_attention_routes():
    bundle = pl.load_pipeline("tiny-unet")
    pos, neg = pl.encode_text(bundle, ["p"]), pl.encode_text(bundle, [""])
    image = jnp.asarray(np.random.default_rng(2).random((1, 64, 64, 3)), jnp.float32)
    tracer = get_tracer()
    attrs = []
    for seed in (1, 2):
        with tracer.span("node.UltimateSDUpscaleDistributed") as span:
            (out,) = UltimateSDUpscaleDistributed().run(
                image=image, model=bundle, positive=pos, negative=neg, vae=bundle,
                seed=seed, steps=1, cfg=1.0, sampler_name="euler", scheduler="karras",
                denoise=0.3, upscale_by=2.0, tile_width=64, tile_height=64,
                tile_padding=16, context=ExecutionContext(),
            )
        assert out.shape == (1, 128, 128, 3)
        attrs.append(dict(span.attrs))
    # tiny-vae halves the image: a 96 px tile is a 48 x 48 latent, 2,304
    # and 576 tokens over themselves and over 16 text keys; off a TPU
    # every call is XLA's
    routes = attrs[0]["attention"].split(", ")
    assert "xla 2304x2304x16" in routes and "xla 576x16x32" in routes
    assert all(route.startswith("xla ") for route in routes)
    assert routes == sorted(set(routes))
    assert "attention" not in attrs[1]
