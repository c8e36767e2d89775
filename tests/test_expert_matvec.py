"""A decode step's grouped products as a Pallas kernel
(`ops/expert_matvec.py`), interpreted on the CPU: against
`jax.lax.ragged_dot` at the row counts a decode step has, the plan and
the route from the shape, and `models/moe.expert_layer` through the
kernel against the same layer through `ragged_dot` for the models that
call it (four with SwiGLU experts, one whose experts have no gate and an
up-projection stored out by in), with the prefill left where it was."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.models import (
    deepseek_v2, k_exaone, ling_flash, moe, nemotron_h, solar_open2)
from comfyui_distributed_tpu.models.registry import get_config
from comfyui_distributed_tpu.ops import expert_matvec as em

K_IN, N_OUT = 128, 384


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Blocks of 128 columns at the tests' sizes (32 KB), so that a
    product walks several blocks a group as the served shapes do."""
    monkeypatch.setattr(em, "MIN_BLOCK_BYTES", 2**14)


def sizes_of(case: str, rows: int, groups: int) -> np.ndarray:
    sizes = np.zeros(groups, np.int32)
    if case == "empty groups between":
        sizes[[1, groups // 2, groups - 1]] = [1, 2, 1]
    elif case == "two rows on one expert":
        sizes[3] = 2
    elif case == "no held row":
        pass
    elif case == "all rows held":
        sizes[: rows - 1] = 1
        sizes[groups - 1] += rows - int(sizes.sum())
    elif case == "all rows on the first":
        sizes[0] = rows
    return sizes


@pytest.mark.parametrize("dtype,tolerance", [(jnp.bfloat16, 2e-2), (jnp.float32, 1e-4)])
@pytest.mark.parametrize("case", [
    "empty groups between", "two rows on one expert", "no held row", "all rows held",
    "all rows on the first"])
@pytest.mark.parametrize("groups", [16, 40])
@pytest.mark.parametrize("rows", [6, 8, 16])
def test_kernel_gives_what_ragged_dot_gives(rows, groups, case, dtype, tolerance):
    """Rows of held experts as `ragged_dot` has them, rows past
    `sizes.sum()` zero; 6 rows are no multiple of either sublane tile."""
    sizes = sizes_of(case, rows, groups)
    keys = jax.random.split(jax.random.key(rows * 100 + groups), 2)
    x = jax.random.normal(keys[0], (rows, K_IN)).astype(dtype)
    w = (K_IN ** -0.5 * jax.random.normal(keys[1], (groups, K_IN, N_OUT))).astype(dtype)
    assert em.matvec_plan(rows, K_IN, N_OUT, jnp.dtype(dtype).itemsize)[1] == 128
    got = em.expert_matvec(x, w, jnp.asarray(sizes), interpret=True)
    want = jax.lax.ragged_dot(x, w, jnp.asarray(sizes))
    assert got.shape == want.shape and got.dtype == want.dtype
    held = int(sizes.sum())
    np.testing.assert_allclose(
        np.asarray(got[:held], np.float32), np.asarray(want[:held], np.float32),
        rtol=tolerance, atol=tolerance)
    assert not np.asarray(got[held:], np.float32).any()


N_OFF = 240  # off the lane tile: 1.875 tiles, as 1,856 is 14.5


@pytest.mark.parametrize("dtype,tolerance,blocks", [(jnp.bfloat16, 2e-2, 3), (jnp.float32, 1e-4, 6)])
@pytest.mark.parametrize("case", [
    "empty groups between", "two rows on one expert", "no held row", "all rows held",
    "all rows on the first"])
@pytest.mark.parametrize("rows", [6, 16])
def test_kernel_gives_what_ragged_dot_gives_over_weights_stored_out_by_in(
        rows, case, dtype, tolerance, blocks):
    """A width off the lane tile, the weights [groups, N, K]: the kernel
    walks blocks of rows of the stored array (80 of 240 in bfloat16, 40
    in float32), each block's product final; `ragged_dot` over the
    transposes says what it has to give."""
    groups = 16
    sizes = sizes_of(case, rows, groups)
    keys = jax.random.split(jax.random.key(rows * 100 + groups), 2)
    x = jax.random.normal(keys[0], (rows, K_IN)).astype(dtype)
    w = (K_IN ** -0.5 * jax.random.normal(keys[1], (groups, N_OFF, K_IN))).astype(dtype)
    itemsize = jnp.dtype(dtype).itemsize
    assert em.matvec_plan(rows, K_IN, N_OFF, itemsize) is None
    assert N_OFF // em.matvec_plan(rows, K_IN, N_OFF, itemsize, out_major=True)[1] == blocks
    got = em.expert_matvec(x, w, jnp.asarray(sizes), out_major=True, interpret=True)
    want = jax.lax.ragged_dot(x, w.swapaxes(1, 2), jnp.asarray(sizes))
    assert got.shape == want.shape and got.dtype == want.dtype
    held = int(sizes.sum())
    np.testing.assert_allclose(
        np.asarray(got[:held], np.float32), np.asarray(want[:held], np.float32),
        rtol=tolerance, atol=tolerance)
    assert not np.asarray(got[held:], np.float32).any()
    np.testing.assert_allclose(
        np.asarray(em.grouped_xla(x, w, jnp.asarray(sizes), out_major=True)[:held], np.float32),
        np.asarray(want[:held], np.float32), rtol=tolerance, atol=tolerance)


def test_a_shared_expert_is_fetched_once_and_an_unchosen_one_never():
    """What the kernel is told: the chosen groups ascending, each once,
    with its first row and row count; nothing for a group without rows."""
    sizes = jnp.asarray([0, 2, 0, 0, 1, 0, 3, 0])
    chosen = np.asarray(em.chosen_groups(sizes, 6)).tolist()
    assert chosen[0] == 3
    assert chosen[1:7] == [1, 4, 6, 0, 0, 0]      # their ids
    assert chosen[7:13] == [0, 2, 3, 0, 0, 0]     # each one's first row
    assert chosen[13:] == [2, 1, 3, 0, 0, 0]      # and its rows
    assert not np.asarray(em.chosen_groups(jnp.zeros((8,), jnp.int32), 6)).any()


# (rows, hidden, width): a decode step of DeepSeek-V2, Solar-Open2 and
# K-EXAONE (two positions; one in its MTP module) at the published widths
SERVED = [(6, 5120, 1536), (8, 4096, 1280), (16, 6144, 2048), (8, 6144, 2048)]


@pytest.mark.parametrize("rows,hidden,width", SERVED)
def test_the_plan_at_the_served_shapes(rows, hidden, width, monkeypatch):
    """Blocks of one to two megabytes, both buffers inside the budget."""
    monkeypatch.setattr(em, "MIN_BLOCK_BYTES", 2**20)
    for k, n in ((hidden, 2 * width), (width, hidden)):
        padded, block = em.matvec_plan(rows, k, n, 2)
        assert padded == 16 and n % block == 0 and block % 128 == 0
        assert 2**20 <= k * block * 2 <= 2**21
        assert em.matvec_vmem_bytes(padded, k, n, block, 2) <= 12 * 2**20


@pytest.mark.parametrize("rows,k,n,itemsize", [
    (257, 4096, 2560, 2),    # more than a tile of rows: the prefill's
    (0, 4096, 2560, 2),
    (8, 4096, 2500, 2),      # columns off the lane tile
    (8, 4100, 2560, 2),      # a contraction off the sublane tile
    (8, 4096, 2560, 1),      # no tile for one-byte operands
    (8, 2**20, 128, 2),      # a block that no VMEM holds
])
def test_no_plan(rows, k, n, itemsize):
    assert em.matvec_plan(rows, k, n, itemsize) is None


def test_the_route_is_the_backend_and_the_plan(monkeypatch):
    assert em.expert_matvec_route(6, 5120, 3072, jnp.bfloat16) == "xla"  # off a TPU
    assert moe.decode_route(6, 5120, 1536, jnp.bfloat16) == "xla"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert em.expert_matvec_route(6, 5120, 3072, jnp.bfloat16) == "kernel"
    assert em.expert_matvec_route(6, 5120, 3000, jnp.bfloat16) == "xla"
    for rows, hidden, width in SERVED:
        assert moe.decode_route(rows, hidden, width, jnp.bfloat16) == "kernel"
    assert moe.decode_route(moe.ROW_TILE, 4096, 1280, jnp.bfloat16) == "kernel"
    # more than a tile of pairs is a prefill's, whatever the ladder
    assert moe.decode_route(moe.ROW_TILE + 1, 4096, 1280, jnp.bfloat16) == "xla"
    assert moe.decode_route(8, 4096, 1250, jnp.bfloat16) == "xla"


def test_a_call_without_a_plan_raises():
    x, w = jnp.zeros((8, 128), jnp.bfloat16), jnp.zeros((4, 128, 100), jnp.bfloat16)
    with pytest.raises(ValueError, match="no plan"):
        em.expert_matvec(x, w, jnp.zeros((4,), jnp.int32), interpret=True)


def route_to_the_kernel(monkeypatch):
    """`expert_layer` routed as a TPU routes it, the kernel interpreted."""
    monkeypatch.setattr(moe, "expert_matvec_route", lambda *shape, **how: "kernel")
    monkeypatch.setattr(
        moe, "expert_matvec", functools.partial(em.expert_matvec, interpret=True))


def _calls(jaxpr) -> int:
    """`pallas_call`s anywhere in a jaxpr."""
    found = 0
    for eqn in jaxpr.eqns:
        found += eqn.primitive.name == "pallas_call"
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found += _calls(inner)
    return found


def layer_of(name: str):
    """(a step's rows -> the expert layer's output, tokens a step): the
    model's own call of `moe.expert_layer` under its own routing rule,
    its tiny configuration at widths the kernel tiles (128, 64)."""
    module, tokens = {
        "tiny-deepseek-v2": (deepseek_v2, 1), "tiny-solar-open2": (solar_open2, 1),
        "tiny-k-exaone": (k_exaone, 2), "tiny-ling-flash": (ling_flash, 2),
        "tiny-nemotron3-nano": (nemotron_h, 1),
    }[name]
    if module is nemotron_h:  # its own width of 24: off every tile, the up stored out by in
        cfg = dataclasses.replace(get_config(name), hidden_size=128)
        block = nemotron_h.unstacked(
            cfg, nemotron_h.init_params(cfg, jax.random.key(3), jnp.float32))["blocks"][12]
        return (lambda x: nemotron_h.moe(cfg, block["moe"], x)), tokens
    cfg = dataclasses.replace(get_config(name), hidden_size=128, moe_intermediate_size=64)
    block = next(
        b for b in module.init_params(cfg, jax.random.key(3), jnp.float32)["layers"]
        if "moe" in b)
    if module is k_exaone:
        return (lambda x: k_exaone._feed_forward(cfg, block, x)), tokens
    if module is ling_flash:  # published layer 2: grouped routing, no clamp
        return (lambda x: ling_flash._feed_forward(cfg, block, x, 2)), tokens
    return (lambda x: module.moe(cfg, block["moe"], x)), tokens


@pytest.mark.parametrize(
    "name", ["tiny-deepseek-v2", "tiny-solar-open2", "tiny-k-exaone", "tiny-ling-flash",
             "tiny-nemotron3-nano"])
def test_a_models_decode_step_through_the_kernel_is_the_step_through_ragged_dot(
        name, monkeypatch):
    layer, tokens = layer_of(name)
    steps = jax.random.normal(jax.random.key(4), (24, tokens, 128))
    assert _calls(jax.make_jaxpr(layer)(steps[0]).jaxpr) == 0
    wanted = [layer(x) for x in steps]
    route_to_the_kernel(monkeypatch)
    layer, _ = layer_of(name)  # a function no trace of which is kept
    assert _calls(jax.make_jaxpr(layer)(steps[0]).jaxpr) == 2  # gate-up (or up), down
    held_pairs = 0
    for x, (want, want_ids, want_sizes) in zip(steps, wanted):
        out, ids, sizes = layer(x)
        np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_ids))
        np.testing.assert_array_equal(np.asarray(sizes), np.asarray(want_sizes))
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5, atol=1e-5)
        held_pairs += int(np.asarray(sizes).sum())
    # steps with held pairs and steps without were both among them
    assert 0 < held_pairs < steps.shape[0] * tokens * 3


@pytest.mark.parametrize("name,prompt", [
    ("tiny-deepseek-v2", 512), ("tiny-solar-open2", 512), ("tiny-k-exaone", 512),
    ("tiny-ling-flash", 512), ("tiny-nemotron3-nano", 512)])
def test_a_prefill_never_reaches_the_kernel(name, prompt, monkeypatch):
    """A ladder of several rungs is `ragged_dot` under `lax.switch`, as
    it was, on a backend that would route a decode step to the kernel."""
    route_to_the_kernel(monkeypatch)
    layer, _ = layer_of(name)
    x = jax.random.normal(jax.random.key(5), (prompt, 128))
    jaxpr = jax.make_jaxpr(layer)(x).jaxpr
    assert _calls(jaxpr) == 0
    assert "ragged_dot" in str(jaxpr) and "cond" in str(jaxpr)
