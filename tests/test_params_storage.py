"""A seeded-random bundle is built in the dtype its weights are stored
in: `pipeline.init_params` runs flax's initializers operation by
operation and stores each weight the moment it exists
(`pipeline._run_storing`), so a load never holds a float32 copy of a
component it keeps in bfloat16, and the values are the eager float32
ones rounded once."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import Literal

from comfyui_distributed_tpu.models import create_model
from comfyui_distributed_tpu.models import pipeline as pl

MODELS = ["tiny-unet", "tiny-flux", "tiny-sd3", "tiny-dit"]


@pytest.mark.parametrize("name", MODELS)
def test_a_bfloat16_bundle_is_built_without_a_float32_copy_of_it(name, monkeypatch):
    """Shape level: the load is traced, nothing is compiled or run. In
    the order the load runs its operations, at most a few float32
    arrays of a weight's rank are alive at any moment (a weight and the
    temporaries of its own initializer), where the bundle has hundreds
    of weights."""
    monkeypatch.setenv("CDT_PARAMS_DTYPE", "bfloat16")
    traced = jax.make_jaxpr(lambda: pl.load_pipeline(name).params)()
    stored = jax.tree_util.tree_leaves(traced.out_avals)
    floating = [a for a in stored if jnp.issubdtype(a.dtype, jnp.floating)]
    assert len(floating) > 100 and all(a.dtype == jnp.bfloat16 for a in floating)

    eqns = traced.jaxpr.eqns
    last_use = {}
    for i, eqn in enumerate(eqns):
        for v in eqn.invars:
            if not isinstance(v, Literal):
                last_use[v] = i
    alive, peak, dying = 0, 0, {}
    for i, eqn in enumerate(eqns):
        for v in eqn.outvars:
            aval = v.aval
            if getattr(aval, "dtype", None) == jnp.float32 and aval.ndim >= 2 and v in last_use:
                alive += 1
                dying.setdefault(last_use[v], []).append(v)
        peak = max(peak, alive)
        alive -= len(dying.pop(i, []))
    assert 1 <= peak <= 4, peak


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
