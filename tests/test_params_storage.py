"""A seeded-random bundle is built in the dtype its weights are stored
in: `pipeline.init_params` builds a component with one compiled program
(`pipeline.init_program`: flax's initializers and each weight's cast
inside one `jax.jit`, equal weights drawn by one loop), so a load
builds one program a component, never holds a float32 copy of a
component it keeps in bfloat16, and the values are the eager float32
ones rounded once."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.models import create_model
from comfyui_distributed_tpu.models import pipeline as pl
from comfyui_distributed_tpu.telemetry import Tracer, get_tracer, runtime, set_tracer

MODELS = ["tiny-unet", "tiny-flux", "tiny-sd3", "tiny-dit"]


def components(name, monkeypatch):
    """(module, dummy arguments) of every component a load of `name`
    builds, from a load that is only traced."""
    seen, real = [], pl.init_params

    def record(module, key, *args, settle=True, **kwargs):
        seen.append((module, args, kwargs))
        return real(module, key, *args, settle=settle, **kwargs)

    with monkeypatch.context() as patch:
        patch.setattr(pl, "init_params", record)
        jax.eval_shape(lambda: pl.load_pipeline(name).params)
    return seen


@pytest.mark.parametrize("name", MODELS)
def test_a_bfloat16_bundle_is_built_without_a_float32_copy_of_it(name, monkeypatch):
    """What a component's one program holds beside its result is the
    compiler's buffer assignment, so it is read from the compiled
    program. On the CPU that is two things. The backend does not fuse
    threefry's passes into the rounding, so a weight costs four 32-bit
    arrays of its size while it is drawn: 4.00 of the largest weight's
    float32 bytes where that is all (the encoders, whose table is most
    of them). And the weights that one loop draws stand stacked, in the
    stored dtype, until they are copied out: at most the component's
    stored bytes together (a VAE's convolutions read 0.94-1.11 of them,
    9.1-10.1 of its largest weight; a denoiser 4.0-4.5 of its largest).
    Both are far from a float32 copy of a bundle; a TPU's compiler
    fuses the passes and holds a loop's results alone, 0 to an eighth
    of the stored bytes (`tests/test_flash_kernel_v5e.py`,
    `chip_smoke.py --legs init`). The CPU's default schedule is made
    for concurrency and starts many weights at once (4.0-13.9 of the
    largest weight at these toy sizes), so it is turned off for this
    compile: the question is what the program needs."""
    monkeypatch.setenv("CDT_PARAMS_DTYPE", "bfloat16")
    built = components(name, monkeypatch)
    assert len(built) >= 3
    for module, args, kwargs in built:
        key = jax.random.key(0)
        program = pl.init_program(module, jnp.dtype(jnp.bfloat16), key, *args, **kwargs)
        stored = jax.tree_util.tree_leaves(jax.eval_shape(program, key))
        floating = [a for a in stored if jnp.issubdtype(a.dtype, jnp.floating)]
        assert len(floating) > 10 and all(a.dtype == jnp.bfloat16 for a in floating)
        memory = program.lower(key).compile(
            compiler_options={"xla_cpu_enable_concurrency_optimized_scheduler": False}
        ).memory_analysis()
        largest = 4 * max(a.size for a in stored)
        held = sum(a.size * a.dtype.itemsize for a in stored)
        assert memory.temp_size_in_bytes <= 4.2 * largest + held, (
            type(module).__name__, memory.temp_size_in_bytes, largest, held)


@pytest.mark.parametrize("name", ["tiny-vae-flux", "tiny-t5-shared"])
def test_the_stored_weights_are_the_eager_float32_ones_rounded_once(name, monkeypatch):
    module = create_model(name)
    dummy = jnp.zeros((1, 16, 16, 3)) if "vae" in name else jnp.zeros((1, 16), jnp.int32)
    # without a storage dtype (the CPU default, which the committed goldens
    # pin) `init_params` is flax's `lazy_init`, value for value
    assert pl.params_storage_dtype() is None
    full = pl.init_params(module, jax.random.key(3), dummy)
    want = module.lazy_init(jax.random.key(3), jax.ShapeDtypeStruct(dummy.shape, dummy.dtype))
    assert jax.tree_util.tree_structure(full) == jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(full), jax.tree_util.tree_leaves(want)):
        assert a.dtype == jnp.float32 and np.array_equal(np.asarray(a), np.asarray(b))

    monkeypatch.setenv("CDT_PARAMS_DTYPE", "bfloat16")
    stored = pl.init_params(module, jax.random.key(3), dummy)
    assert jax.tree_util.tree_structure(stored) == jax.tree_util.tree_structure(full)
    for a, b in zip(jax.tree_util.tree_leaves(stored), jax.tree_util.tree_leaves(full)):
        assert a.dtype == jnp.bfloat16
        assert np.array_equal(np.asarray(a, np.float32), np.asarray(b.astype(jnp.bfloat16), np.float32))


def test_settle_false_keeps_float32_for_a_checkpoint_to_map_onto(monkeypatch):
    monkeypatch.setenv("CDT_PARAMS_DTYPE", "bfloat16")
    module = create_model("tiny-vae-flux")
    params = pl.init_params(module, jax.random.key(0), jnp.zeros((1, 16, 16, 3)), settle=False)
    assert all(leaf.dtype == jnp.float32 for leaf in jax.tree_util.tree_leaves(params))


def test_a_load_builds_one_program_a_component(monkeypatch):
    """The `program.build` spans under a loader, as `benchmark/span_tree.py`
    counts them on the chip: one a component, and beside them only the
    ten of `load_pipeline`'s own eager lines (the seed's key and its
    split, 4; the zeros of five dummy inputs; one cast), where an eager
    walk of the initializers built one more for every distinct operation
    of theirs (over a hundred for this bundle)."""
    monkeypatch.setenv("CDT_PARAMS_DTYPE", "bfloat16")
    runtime.install_jax_monitoring()
    jax.clear_caches()  # what an earlier test built in this process would not be built again
    before, tracer = get_tracer(), Tracer()
    set_tracer(tracer)
    try:
        runtime.close_programs()  # what earlier tests only traced on this thread is not the load's
        with tracer.span("node.CheckpointLoaderSimple", trace_id="t") as loader:
            pl.load_pipeline("tiny-unet")
            runtime.close_programs()
    finally:
        set_tracer(before)
    spans = [s for s in tracer.spans("t") if s["name"] == "program.build"]
    assert all(s["parent_id"] == loader.span_id for s in spans)
    # flax's `lazy_init` is traced once a component, outside the program built from it
    traced = [s["attrs"]["program"] for s in spans if s["attrs"]["outcome"] == "traced"]
    assert traced == ["build"] * 3
    programs = [s["attrs"]["program"] for s in spans if s["attrs"]["outcome"] != "traced"]
    assert sorted(p for p in programs if "init_" in p) == [
        "jit(init_TextEncoder)", "jit(init_UNet)", "jit(init_VAE)"]
    assert len(programs) <= 3 + 10, programs
