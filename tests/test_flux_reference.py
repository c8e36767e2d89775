"""The FLUX denoiser and its served sampling path against the plain
reference (comfyui_distributed_tpu/reference/flux.py) on seeded random
weights: tiny-flux and a tiny 2 + 4 cut, on the CPU.

What is compared is the relative L2 error of a whole velocity field or
latent, ||system - reference|| / ||reference||, and the largest absolute
error over the largest reference value.

- float32 compute, F32_TOL 1e-5: both sides compute in float32 and differ
  only in the order of their sums (flax's fused dense and LayerNorm
  against the reference's spelled-out ones, `jax.nn.dot_product_attention`
  against per-head softmax); first measured 3e-7 to 6e-7 for one
  evaluation. Twenty times that leaves room for another backend's
  reductions and is 500 times below what bfloat16 compute gives, so any
  product computed below float32 fails it (checked below).
- bfloat16 compute, BF16_TOL 2.5e-2: the configuration's stated
  precision, eps 2**-8 per rounding through up to six blocks; first
  measured 4e-3 to 8e-3. The reference with every product's operands
  rounded one precision lower (float8 e4m3, eps 2**-4) reads 9e-2 to
  1.5e-1, so the limit sits between the two readings, three times above
  the one and a quarter of the other (checked below).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.graph.nodes_core import KSampler
from comfyui_distributed_tpu.models import get_config
from comfyui_distributed_tpu.models import pipeline as pl
from comfyui_distributed_tpu.models.mmdit import MMDiT
from comfyui_distributed_tpu.ops.conditioning import Conditioning
from comfyui_distributed_tpu.reference import flux as reference

F32_TOL = 1e-5
BF16_TOL = 2.5e-2
TOL = {"float32": F32_TOL, "bfloat16": BF16_TOL}
CUTS = {"tiny-flux": (1, 1), "tiny-2+4": (2, 4)}
LATENT = (1, 8, 12, 16)  # not square: 4 x 6 patches
SHIFT = 3.0  # the served configuration's; tiny-flux's own is 1.0


def errors(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    diff = np.abs(got - want)
    return (float(np.linalg.norm(diff) / np.linalg.norm(want)),
            float(diff.max() / np.abs(want).max()))


@pytest.fixture(scope="module", params=sorted(CUTS))
def case(request):
    """A cut's float32 weights, inputs and reference sizes. flax
    initialises biases to zero and norm scales to one; every leaf is
    perturbed so that no term of the block drops out of the comparison."""
    double, single = CUTS[request.param]
    cfg = dataclasses.replace(
        get_config("tiny-flux"), double_depth=double, single_depth=single, dtype="float32")
    keys = jax.random.split(jax.random.key(27), 6)
    x = jax.random.normal(keys[0], LATENT)
    context = jax.random.normal(keys[1], (1, 16, cfg.context_dim))
    pooled = jax.random.normal(keys[2], (1, cfg.vec_dim))
    params = MMDiT(cfg).init(keys[3], x, jnp.zeros((1,)), context, y=pooled)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    leaves = [leaf + 0.05 * jax.random.normal(k, leaf.shape)
              for leaf, k in zip(leaves, jax.random.split(keys[4], len(leaves)))]
    sizes = reference.Sizes(heads=cfg.heads, axes_dim=cfg.axes_dim, patch=cfg.patch_size,
                            theta=cfg.theta, freq_dim=cfg.freq_dim)
    return {"cfg": cfg, "params": jax.tree_util.tree_unflatten(treedef, leaves),
            "x": x, "context": context, "pooled": pooled, "sizes": sizes}


def system_velocity(case, dtype, guidance):
    module = MMDiT(dataclasses.replace(case["cfg"], dtype=dtype))
    g = None if guidance is None else jnp.array([guidance], jnp.float32)
    return jax.jit(module.apply)(
        case["params"], case["x"], jnp.array([0.7]), case["context"], y=case["pooled"],
        guidance=g)


def reference_velocity(case, guidance, round_to=None):
    # without a value the system embeds its default, 3.5; the published
    # model refuses, so the reference is given the 3.5
    g = jnp.array([3.5 if guidance is None else guidance], jnp.float32)
    return reference.velocity(case["params"], case["sizes"], case["x"], jnp.array([0.7]),
                              case["context"], case["pooled"], g, round_to)


@pytest.mark.parametrize("guidance", [None, 2.0], ids=["default_guidance", "guidance_2"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_one_evaluation_agrees_with_the_reference(case, dtype, guidance):
    rel_l2, max_abs = errors(system_velocity(case, dtype, guidance),
                             reference_velocity(case, guidance))
    assert rel_l2 < TOL[dtype], (rel_l2, max_abs)
    if dtype == "float32":
        assert max_abs < F32_TOL


def test_the_guidance_value_moves_the_velocity(case):
    """So that the two guidance cases above are two cases."""
    rel_l2, _ = errors(reference_velocity(case, 2.0), reference_velocity(case, None))
    assert rel_l2 > 100 * F32_TOL


def test_each_limit_fails_the_precision_below_the_one_it_states(case):
    exact = reference_velocity(case, 2.0)
    in_bfloat16, _ = errors(system_velocity(case, "bfloat16", 2.0), exact)
    assert in_bfloat16 > 100 * F32_TOL
    in_float8, _ = errors(reference_velocity(case, 2.0, round_to=jnp.float8_e4m3fn), exact)
    assert in_float8 > 3 * BF16_TOL
    # and rounding the reference's operands to bfloat16 is what the
    # system's bfloat16 compute amounts to: inside the limit
    rounded, _ = errors(reference_velocity(case, 2.0, round_to=jnp.bfloat16), exact)
    assert rounded < BF16_TOL


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_four_sampled_steps_through_ksampler_agree_with_the_reference_loop(case, dtype):
    """The node's own path: `KSampler.sample` -> `pipeline.img2img_latents`
    -> `guided_model` at cfg 1.0 -> `samplers.sample("euler")` over the
    flow sigmas, from the noise that path draws for the seed."""
    module = MMDiT(dataclasses.replace(case["cfg"], dtype=dtype))
    bundle = pl.PipelineBundle(
        model_name="tiny-flux", unet=module, vae=None, text_encoder=None,
        params={"unet": case["params"]}, tokenizer=None, latent_channels=LATENT[-1],
        latent_scale=2, flow_shift_override=SHIFT)
    positive = Conditioning(context=case["context"], pooled=case["pooled"], guidance=3.5)
    negative = Conditioning(context=0 * case["context"], pooled=0 * case["pooled"])
    seed, steps = 5, 4
    (out,) = KSampler().sample(
        bundle, seed, steps, 1.0, "euler", "simple", positive, negative,
        {"samples": jnp.zeros(LATENT)}, denoise=1.0)
    noise_key, _ = jax.random.split(jax.random.key(seed))
    noise = pl._batch_noise(noise_key, LATENT, False)
    want = reference.sample_euler(
        case["params"], case["sizes"], noise, case["context"], case["pooled"],
        jnp.array([3.5]), steps=steps, shift=SHIFT)
    rel_l2, max_abs = errors(out["samples"], want)
    assert rel_l2 < TOL[dtype], (rel_l2, max_abs)
    # the loop moved the latent: the comparison is not noise against noise
    assert errors(want, noise)[0] > 0.1


def test_the_reference_imports_nothing_of_what_it_is_compared_with():
    with open(reference.__file__, encoding="utf-8") as fh:
        source = fh.read()
    imports = [line for line in source.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == [
        "from __future__ import annotations", "import dataclasses", "import functools",
        "import math", "import jax", "import jax.numpy as jnp", "import numpy as np"]
    assert "import" not in source.split("import numpy as np", 1)[1].replace("imports", "")
