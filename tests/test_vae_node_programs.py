"""The graph's VAE nodes dispatch one jitted program per pass
(ops/tiled_vae.vae_apply): same arithmetic as the eager `apply`, one
trace per (VAE module, shape), the weights an argument, nothing
donated, and the mesh branch left alone. Tiny VAE, CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.graph import nodes_core
from comfyui_distributed_tpu.graph.executor import ExecutionContext, GraphExecutor
from comfyui_distributed_tpu.graph.registry import NODE_REGISTRY
from comfyui_distributed_tpu.models import create_model, get_config
from comfyui_distributed_tpu.models import pipeline as pl
from comfyui_distributed_tpu.models.registry import MODEL_REGISTRY, model_family
from comfyui_distributed_tpu.ops.tiled_vae import vae_apply
from comfyui_distributed_tpu.telemetry import get_tracer, runtime

# The tiny VAE computes in bfloat16 (eps 2**-8) on values of order one.
# Inside one program XLA may keep a fused intermediate wider than
# bfloat16 where the eager pass rounds after every operation, so the
# two differ by a few roundings (0.012 at most as first measured), not
# by a dtype: four eps.
BF16_TOL = 4 * 2.0 ** -8


def bundle(seed: int = 0) -> pl.VAEBundle:
    """What VAELoader hands the nodes, with the weights made in one
    program instead of load_vae's eager initialisation."""
    module = create_model("tiny-vae")
    cfg = get_config("tiny-vae")
    params = jax.jit(module.init)(jax.random.key(seed), jnp.zeros((1, 16, 16, 3)))
    return pl.VAEBundle(vae=module, params={"vae": params},
                        latent_channels=cfg.latent_channels, latent_scale=cfg.downscale)


@pytest.fixture(scope="module")
def vae():
    return bundle(0)


@pytest.fixture(scope="module")
def other_vae():
    """The same module and shapes with other weights."""
    return bundle(1)


def pass_input(method: str, batch: int, hw: tuple) -> jax.Array:
    key = jax.random.key(batch * 100 + hw[0])
    if method == "decode":
        return jax.random.normal(key, (batch, *hw, 4))
    return jax.random.uniform(key, (batch, 2 * hw[0], 2 * hw[1], 3))


@pytest.mark.parametrize("batch", [1, 2])
@pytest.mark.parametrize("hw", [(8, 8), (12, 8)])
@pytest.mark.parametrize("method", ["decode", "encode"])
def test_one_program_pass_equals_the_eager_apply(vae, method, hw, batch):
    x = pass_input(method, batch, hw)
    eager = vae.vae.apply(vae.params["vae"], x, method=method)
    jitted = vae_apply(vae.vae, vae.params["vae"], x, method=method)
    assert jitted.dtype == eager.dtype and jitted.shape == eager.shape
    np.testing.assert_allclose(np.asarray(jitted), np.asarray(eager), rtol=0, atol=BF16_TOL)
    if method == "decode":
        assert 0.0 <= float(jitted.min()) and float(jitted.max()) <= 1.0


class VaeTestSource:
    """The loader and sampler of a txt2img graph in one cacheable node:
    a latent that follows `seed`, and the bundle the test holds."""

    bundles: dict = {}

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"seed": ("INT", {"default": 0}), "h": ("INT", {"default": 8}),
                             "which": ("STRING", {"default": "a"})}}

    RETURN_TYPES = ("LATENT", "VAE")
    FUNCTION = "make"

    def make(self, seed, h, which):
        latent = jax.random.normal(jax.random.key(int(seed)), (1, int(h), 8, 4))
        return ({"samples": latent}, self.bundles[which])


class VaeTestSink:
    OUTPUT_NODE = True
    RETURN_TYPES = ()
    FUNCTION = "take"

    @classmethod
    def INPUT_TYPES(cls):
        return {"required": {"images": ("IMAGE",)}}

    def take(self, images):
        return (images,)


def graph(seed: int, h: int = 8, which: str = "a") -> dict:
    return {
        "1": {"class_type": "VaeTestSource", "inputs": {"seed": seed, "h": h, "which": which}},
        "2": {"class_type": "VAEDecode", "inputs": {"samples": ["1", 0], "vae": ["1", 1]}},
        "3": {"class_type": "VaeTestSink", "inputs": {"images": ["2", 0]}},
    }


@pytest.fixture()
def executor(vae, other_vae, monkeypatch):
    monkeypatch.setitem(NODE_REGISTRY, "VaeTestSource", VaeTestSource)
    monkeypatch.setitem(NODE_REGISTRY, "VaeTestSink", VaeTestSink)
    monkeypatch.setattr(VaeTestSource, "bundles", {"a": vae, "b": other_vae})
    runtime.install_jax_monitoring()
    return GraphExecutor(ExecutionContext())


def run(executor, trace_id: str, **kwargs):
    """One request: the image, and its `node.VAEDecode` span's attributes."""
    tracer = get_tracer()
    with tracer.span("execute_prompt", trace_id=trace_id):
        (image,) = executor.execute(graph(**kwargs))["3"]
    (span,) = [s for s in tracer.spans(trace_id) if s["name"] == "node.VAEDecode"]
    return image, span["attrs"]


def test_two_requests_of_one_graph_trace_the_decode_once(executor):
    # 10 latent rows: a shape no other test of this file decodes
    _, first = run(executor, "t1", seed=1, h=10)
    size = vae_apply._cache_size()
    image, second = run(executor, "t2", seed=2, h=10)
    assert first["programs"] == 1 and first["trace_s"] > 0
    assert second["programs"] == 1
    assert not {"trace_s", "lower_s", "compiles", "compile_s"} & set(second)
    assert vae_apply._cache_size() == size
    assert image.shape == (1, 20, 16, 3)

    _, other_shape = run(executor, "t3", seed=3, h=14)
    assert other_shape["trace_s"] > 0 and vae_apply._cache_size() == size + 1
    _, first_shape_again = run(executor, "t4", seed=4, h=10)
    assert "trace_s" not in first_shape_again and vae_apply._cache_size() == size + 1


def test_weights_are_an_argument_two_trees_share_one_program(executor):
    image_a, _ = run(executor, "a1", seed=5, which="a")
    size = vae_apply._cache_size()
    image_b, attrs = run(executor, "b1", seed=5, which="b")
    assert vae_apply._cache_size() == size and "trace_s" not in attrs
    assert float(jnp.abs(image_a - image_b).max()) > 1e-3


def test_the_latent_is_not_donated(vae):
    latent = jax.random.normal(jax.random.key(7), (1, 8, 8, 4))
    before = np.asarray(latent).copy()
    first = nodes_core.VAEDecode().decode({"samples": latent}, vae)[0]
    assert not latent.is_deleted()
    np.testing.assert_array_equal(np.asarray(latent), before)
    # the benchmark's repeat request: the cached latent decodes again to the same bytes
    again = nodes_core.VAEDecode().decode({"samples": latent}, vae)[0]
    np.testing.assert_array_equal(np.asarray(first), np.asarray(again))


def test_a_participant_major_batch_on_a_mesh_still_takes_decode_mesh(vae, monkeypatch):
    from comfyui_distributed_tpu.parallel.mesh import DATA_AXIS

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), (DATA_AXIS,))
    calls = []
    real = nodes_core._decode_mesh

    def spy(*args):
        calls.append("mesh")
        return real(*args)

    monkeypatch.setattr(nodes_core, "_decode_mesh", spy)
    monkeypatch.setattr(nodes_core, "vae_apply", lambda *a, **k: calls.append("one_chip"))
    latent = jax.random.normal(jax.random.key(8), (2, 8, 8, 4))
    tracer = get_tracer()
    with tracer.span("node.VAEDecode", trace_id="m1"):
        (image,) = nodes_core.VAEDecode().decode(
            {"samples": latent, "participant_major": True}, vae,
            context=ExecutionContext(mesh=mesh))
    assert calls == ["mesh"] and image.shape == (2, 16, 16, 3)
    # the node, beside a `program.build` span for each program built under it
    (span,) = [s for s in tracer.spans("m1") if s["name"] == "node.VAEDecode"]
    assert span["attrs"] == {"mesh_programs": 1}
    # without the flag the same context decodes as one program on one chip
    nodes_core.VAEDecode().decode({"samples": latent}, vae, context=ExecutionContext(mesh=mesh))
    assert calls == ["mesh", "one_chip"]


@pytest.mark.parametrize("node, passes", [
    ("VAEEncode", 1), ("VAEEncodeForInpaint", 1), ("InpaintModelConditioning", 2)])
def test_the_encode_nodes_take_the_one_program_path(vae, monkeypatch, node, passes):
    seen = []
    real = nodes_core.vae_apply

    def spy(module, params, x, method):
        seen.append(method)
        return real(module, params, x, method=method)

    monkeypatch.setattr(nodes_core, "vae_apply", spy)
    kwargs = {"pixels": jax.random.uniform(jax.random.key(9), (1, 16, 16, 3)), "vae": vae}
    if node != "VAEEncode":
        kwargs["mask"] = jnp.zeros((16, 16)).at[4:8, 4:8].set(1.0)
    if node == "InpaintModelConditioning":
        kwargs.update(positive=[], negative=[])
    latent = NODE_REGISTRY[node]().encode(**kwargs)[-1]
    assert seen == ["encode"] * passes and latent["samples"].shape == (1, 8, 8, 4)


@pytest.mark.parametrize(
    "name", sorted(n for n in MODEL_REGISTRY if model_family(n) == "vae"))
def test_every_image_vae_module_is_a_stable_static_key(name):
    """`jax.jit` keys the program on the module: two builds of one
    registry entry must hash and compare equal, or every rebuilt bundle
    would trace again."""
    one, other = create_model(name), create_model(name)
    assert one is not other and one == other and hash(one) == hash(other)
