"""CDT_PARAMS_DTYPE: bf16 weight storage for memory-constrained chips.

The reference inherits fp16/bf16 weight handling from ComfyUI's model
management (reference README "Lowvram" notes); here the env knob casts
floating-point params at bundle-build time in EVERY loader (pipeline,
video, VAE, ControlNet, upscaler) while integer leaves (embedding ids,
schedule tables) stay untouched. Unset ⇒ f32, the dtype the committed
goldens are pinned at.
"""

import jax.numpy as jnp
import pytest

from comfyui_distributed_tpu.models.pipeline import maybe_cast_params

pytestmark = pytest.mark.fast


def _tree():
    return {
        "w": jnp.ones((2, 2), jnp.float32),
        "ids": jnp.arange(3),
        "nested": {"b": jnp.zeros((4,), jnp.float32)},
    }


def test_unset_is_identity(monkeypatch):
    monkeypatch.delenv("CDT_PARAMS_DTYPE", raising=False)
    out = maybe_cast_params(_tree())
    assert out["w"].dtype == jnp.float32
    assert out["nested"]["b"].dtype == jnp.float32


def test_empty_string_is_identity(monkeypatch):
    monkeypatch.setenv("CDT_PARAMS_DTYPE", "")
    assert maybe_cast_params(_tree())["w"].dtype == jnp.float32


def test_bfloat16_casts_floats_only(monkeypatch):
    monkeypatch.setenv("CDT_PARAMS_DTYPE", "bfloat16")
    out = maybe_cast_params(_tree())
    assert out["w"].dtype == jnp.bfloat16
    assert out["nested"]["b"].dtype == jnp.bfloat16
    assert out["ids"].dtype == jnp.int32  # integer leaves untouched


def test_all_loaders_route_through_cast():
    """Every bundle-building loader must apply maybe_cast_params —
    an unrouted loader keeps float32 weights on the chip, and SDXL in
    float32 does not leave a 16 GB chip room to run."""
    import inspect

    from comfyui_distributed_tpu.models import (
        controlnet,
        pipeline,
        upscaler,
        video_pipeline,
    )

    for fn in (
        pipeline.load_pipeline,
        pipeline.load_vae,
        pipeline.load_unet,
        pipeline.load_clip,
        video_pipeline.load_video_pipeline,
        controlnet.load_controlnet,
        upscaler.load_upscale_model,
    ):
        src = inspect.getsource(fn)
        assert "maybe_cast_params" in src, fn.__qualname__
