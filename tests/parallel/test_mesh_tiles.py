"""Mesh-parallel tile execution: GrantSampler sharded dispatch parity,
bucket rounding, knob-driven worker-mesh construction, and the
tensor-parallel parameter sharding tier.

The tier-1 conftest forces 8 virtual CPU devices, so 4-participant
meshes exist without hardware; the dedicated CI job re-runs this suite
under XLA_FLAGS=--xla_force_host_platform_device_count=4 to pin the
exact fleet shape the acceptance names.
"""

import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.graph.tile_pipeline import GrantSampler
from comfyui_distributed_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    advertised_capacity,
    auto_tp_size,
    build_mesh,
    mesh_summary,
    worker_mesh,
)
from comfyui_distributed_tpu.utils.exceptions import MeshError
from comfyui_distributed_tpu.parallel.sharding import (
    maybe_shard_params,
    params_byte_size,
)

pytestmark = pytest.mark.skipif(
    jax.local_device_count() < 4, reason="needs >=4 (virtual) devices"
)


def _processor(params, tile, key, pos, neg, yx):
    """Deterministic per-tile stand-in: keyed noise + position term, the
    same shape contract as the production jitted tile processor."""
    noise = jax.random.normal(key, tile.shape)
    return tile * 2.0 + 0.05 * noise + yx[0] * 0.001


def _fixtures(num_tiles=16):
    extracted = (
        jnp.linspace(0.0, 1.0, num_tiles * 1 * 8 * 8 * 3)
        .reshape(num_tiles, 1, 8, 8, 3)
        .astype(jnp.float32)
    )
    positions = jnp.arange(num_tiles * 2).reshape(num_tiles, 2)
    return extracted, positions, jax.random.key(0)


def _mesh(n=4):
    return build_mesh(
        {DATA_AXIS: n, MODEL_AXIS: 1}, devices=jax.local_devices()[:n]
    )


# --- sharded dispatch parity ----------------------------------------------


@pytest.mark.parametrize("jit", [True, False], ids=["jitted", "eager-stub"])
def test_sampler_mesh_parity_full_ragged_and_single(jit):
    """The acceptance property at the sampler level: a 4-participant
    sharded dispatch produces byte-identical per-tile outputs to the
    1-device path — full buckets, ragged chunks (wraparound padding),
    and single tiles alike, for the jitted production shape AND the
    eager stub shape the chaos harness runs."""
    extracted, positions, key = _fixtures()
    process = jax.jit(_processor) if jit else _processor
    one = GrantSampler(
        process, None, extracted, key, positions, None, None, k_max=8
    )
    four = GrantSampler(
        process, None, extracted, key, positions, None, None, k_max=8,
        mesh=_mesh(4),
    )
    assert four.data_parallel == 4
    for idxs in ([0, 1, 2, 3, 4, 5, 6, 7], [3, 9, 11], [5], [1, 2]):
        a = np.asarray(one.sample(idxs))
        b = np.asarray(four.collect(four.sample(idxs)))
        np.testing.assert_array_equal(a, b)


def test_mesh_buckets_are_multiples_of_data_width():
    """Buckets round up to multiples of the data-axis width so the
    NamedSharding splits evenly, and the set stays bounded."""
    extracted, positions, key = _fixtures()
    sampler = GrantSampler(
        _processor, None, extracted, key, positions, None, None,
        k_max=8, mesh=_mesh(4),
    )
    assert sampler.buckets == (4, 8)
    assert all(b % 4 == 0 for b in sampler.buckets)
    # a 3-tile ragged chunk pads to the 4-bucket, not a fresh shape
    out = sampler.collect(sampler.sample([3, 9, 11]))
    assert np.asarray(out).shape[0] == 3
    assert sampler.buckets_used == {4}
    assert sampler.padded_tiles == 1


def test_sampled_batch_is_actually_sharded():
    """The dispatch must place the batch across the mesh (one shard per
    participant), not silently replicate onto one device."""
    extracted, positions, key = _fixtures()
    mesh = _mesh(4)
    sampler = GrantSampler(
        jax.jit(_processor), None, extracted, key, positions, None, None,
        k_max=8, mesh=mesh,
    )
    result = sampler.sample([0, 1, 2, 3, 4, 5, 6, 7])
    assert len(result.sharding.device_set) == 4
    host = sampler.collect(result)
    assert isinstance(host, np.ndarray) and host.shape[0] == 8


def test_sampler_k_max_clamps_to_data_width():
    """A caller-passed k_max below the participant count would starve
    chips every dispatch; the sampler clamps it up."""
    extracted, positions, key = _fixtures()
    sampler = GrantSampler(
        _processor, None, extracted, key, positions, None, None,
        k_max=1, mesh=_mesh(4),
    )
    assert sampler.k_max == 4
    assert sampler.chunks([0, 1, 2, 3, 4]) == [[0, 1, 2, 3], [4]]


# --- worker mesh construction (knob pair) ----------------------------------


def test_worker_mesh_cpu_defaults_off_and_knob_opts_in():
    assert worker_mesh() is None  # CPU: forced devices are a test artifact
    with mock.patch.dict(os.environ, {"CDT_MESH_SHAPE": "4,1"}):
        mesh = worker_mesh()
    assert mesh_summary(mesh) == {"data": 4, "model": 1, "devices": 4}
    assert advertised_capacity(mesh) == 4
    assert advertised_capacity(None) == 1


def test_worker_mesh_tp_knob_and_inference():
    n = jax.local_device_count()
    with mock.patch.dict(os.environ, {"CDT_TP_SIZE": "2"}):
        mesh = worker_mesh()
    summary = mesh_summary(mesh)
    assert summary["model"] == 2
    assert summary["data"] == n // 2
    # capacity advertises the DATA width only: model-axis chips serve
    # the same tiles, not more of them
    assert advertised_capacity(mesh) == n // 2
    with mock.patch.dict(os.environ, {"CDT_MESH_SHAPE": "-1,2"}):
        inferred = worker_mesh()
    assert mesh_summary(inferred) == summary


def test_worker_mesh_malformed_shape_raises():
    with mock.patch.dict(os.environ, {"CDT_MESH_SHAPE": "banana"}):
        with pytest.raises(MeshError, match="CDT_MESH_SHAPE"):
            worker_mesh()


def test_worker_mesh_tp_keeps_explicit_data_pin():
    """CDT_TP_SIZE overrides only the model entry of CDT_MESH_SHAPE —
    an explicit data pin (chip subsetting on a shared host) survives,
    and only a combination exceeding the host reverts data to
    inferred."""
    n = jax.local_device_count()
    env = {"CDT_MESH_SHAPE": "2,1", "CDT_TP_SIZE": "2"}
    with mock.patch.dict(os.environ, env):
        mesh = worker_mesh()
    assert mesh_summary(mesh) == {"data": 2, "model": 2, "devices": 4}
    # conflicting pin (data x tp > host): data reverts to inferred
    env = {"CDT_MESH_SHAPE": f"{n},1", "CDT_TP_SIZE": "2"}
    with mock.patch.dict(os.environ, env):
        mesh = worker_mesh()
    assert mesh_summary(mesh) == {
        "data": n // 2, "model": 2, "devices": n,
    }


def test_tp_only_mesh_still_gauges_shape():
    """A tensor-parallel-only mesh (data=1, model>1 — the over-HBM
    sharded checkpoint) has no data fan-out but must still report its
    shape on cdt_mesh_devices."""
    from comfyui_distributed_tpu.telemetry.instruments import mesh_devices

    extracted, positions, key = _fixtures()
    tp_mesh = build_mesh(
        {DATA_AXIS: 1, MODEL_AXIS: 4}, devices=jax.local_devices()[:4]
    )
    sampler = GrantSampler(
        _processor, None, extracted, key, positions, None, None,
        k_max=4, role="tp-gauge-probe", mesh=tp_mesh,
    )
    assert sampler.data_parallel == 1
    g = mesh_devices()
    assert g.value(role="tp-gauge-probe", axis="model") == 4
    assert g.value(role="tp-gauge-probe", axis="data") == 1
    assert g.value(role="tp-gauge-probe", axis="total") == 4


def test_serving_mesh_summary_reports_recorded_mesh():
    """Status surfaces must report the mesh the elastic loop actually
    built — a knob-only re-derivation diverges exactly when the
    auto-TP budget rule shrank the data axis (it needs params_bytes
    the route doesn't have)."""
    import comfyui_distributed_tpu.parallel.mesh as mesh_mod

    saved = mesh_mod._serving_mesh_summary
    try:
        mesh_mod.note_serving_mesh(_mesh(4))
        assert mesh_mod.serving_mesh_summary() == {
            "data": 4, "model": 1, "devices": 4,
        }
        # the recorded shape wins over any knob-only resolution
        with mock.patch.dict(os.environ, {"CDT_MESH_SHAPE": "2,1"}):
            assert mesh_mod.serving_mesh_summary()["data"] == 4
        mesh_mod._serving_mesh_summary = None
        with mock.patch.dict(os.environ, {"CDT_MESH_SHAPE": "2,1"}):
            assert mesh_mod.serving_mesh_summary()["data"] == 2
    finally:
        mesh_mod._serving_mesh_summary = saved


def test_worker_mesh_non_divisible_knob_raises():
    """A tp that doesn't divide the host is a misconfiguration, not a
    request for one silent participant: it raises where the mesh is
    built (server start-up) instead of serving on a fraction of the
    host."""
    if jax.local_device_count() % 3 == 0:
        pytest.skip("tp=3 divides this host; not the non-divisible case")
    with mock.patch.dict(os.environ, {"CDT_TP_SIZE": "3"}):
        with pytest.raises(MeshError):
            worker_mesh()


# --- tensor-parallel tier (HBM budget rule + param sharding) ---------------


def test_auto_tp_size_budget_rule():
    gib = 1 << 30
    with mock.patch.dict(os.environ, {"CDT_MESH_HBM_GB": "1"}):
        assert auto_tp_size(3 * gib, 8) == 4   # 3G/4 fits 1G budget
        assert auto_tp_size(100, 8) == 1       # already fits
        assert auto_tp_size(64 * gib, 4) == 4  # clamped to the fleet
        # non-power-of-two fleets clamp to the largest pow2 DIVIDING
        # the host — the data axis infers as n/tp, so tp=4 on 6 chips
        # would fail mesh construction instead of loading sharded
        assert auto_tp_size(64 * gib, 6) == 2
        assert auto_tp_size(64 * gib, 12) == 4
    # unset/zero budget disables the rule entirely
    assert auto_tp_size(64 * gib, 8) == 1


def test_maybe_shard_params_shards_model_axis_only_when_present():
    params = {"w": jnp.ones((16, 8)), "b": jnp.ones((3,))}
    assert maybe_shard_params(params, None) is params
    data_only = _mesh(4)
    assert maybe_shard_params(params, data_only) is params
    tp_mesh = build_mesh(
        {DATA_AXIS: 2, MODEL_AXIS: 2}, devices=jax.local_devices()[:4]
    )
    sharded = maybe_shard_params(params, tp_mesh)
    # largest divisible axis shards along the model axis; tiny
    # non-divisible leaves replicate
    assert str(sharded["w"].sharding.spec) == str((MODEL_AXIS, None)) or (
        sharded["w"].sharding.spec[0] == MODEL_AXIS
    )
    assert all(s is None for s in sharded["b"].sharding.spec)
    np.testing.assert_array_equal(np.asarray(sharded["w"]), np.ones((16, 8)))


def test_params_byte_size_counts_stored_bytes():
    params = {"w": jnp.ones((16, 8), jnp.float32), "b": jnp.ones((3,), jnp.bfloat16)}
    assert params_byte_size(params) == 16 * 8 * 4 + 3 * 2
