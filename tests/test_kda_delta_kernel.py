"""A prefill's chunked delta rule as a Pallas kernel
(`ops/kda_delta.py`), interpreted on the CPU: against the recurrence
itself (`kda.kda_step`, token by token) and against the XLA form
(`kda.kda_chunked_scan`) at a lane tile's width, the plan and the route
from the shape, and what the route log and a model's `report` say of
the form `kda.kda_chunked` took."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.models import kda, ling_flash, solar_open2
from comfyui_distributed_tpu.models.registry import get_config
from comfyui_distributed_tpu.ops import attention, kda_delta

D = 128


def rule_inputs(tokens, heads, dtype, g_low=-1.0, beta_of=None, seed=0):
    """q, k (unit length; q times d^-1/2) and v in `dtype`, a log-decay
    in (`g_low`, 0), beta in (0, 2) or `beta_of` everywhere, and a state
    to start from that is not zero."""
    keys = jax.random.split(jax.random.key(seed), 6)
    shape = (tokens, heads, D)
    q = (kda._l2norm(jax.random.normal(keys[0], shape)) * D ** -0.5).astype(dtype)
    k = kda._l2norm(jax.random.normal(keys[1], shape)).astype(dtype)
    v = jax.random.normal(keys[2], shape).astype(dtype)
    g = g_low * jax.nn.sigmoid(jax.random.normal(keys[3], shape))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(keys[4], (tokens, heads)))
    if beta_of is not None:
        beta = jnp.full_like(beta, beta_of)
    return q, k, v, g, beta, 0.3 * jax.random.normal(keys[5], (heads, D, D))


def recurrence(q, k, v, g, beta, state):
    def token(state, xs):
        o, state = kda.kda_step(*xs, state)
        return state, o

    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    state, o = jax.lax.scan(token, state, (q, k, v, g, beta))
    return o, state


def rel_l2(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# (tokens, heads, heads a grid step, storage dtype, g's lower bound, beta)
CASES = {
    "two heads in one step": (128, 2, 2, jnp.float32, -1.0, None),
    "four heads, steps of two": (128, 4, 2, jnp.bfloat16, -1.0, None),
    "four heads, steps of three: the last step half empty": (128, 4, 3, jnp.float32, -1.0, None),
    "two heads, a head a step": (64, 2, 1, jnp.bfloat16, -1.0, None),
    "a short last chunk": (150, 2, 2, jnp.float32, -1.0, None),
    "a short last chunk, bfloat16, a step past the heads": (75, 4, 3, jnp.bfloat16, -1.0, None),
    "fewer tokens than a chunk": (5, 2, 2, jnp.float32, -1.0, None),
    "g at ling's bound": (128, 2, 2, jnp.float32, -5.0, None),
    "g near 0": (128, 2, 2, jnp.float32, -1e-3, None),
    "g near 0, bfloat16": (128, 2, 2, jnp.bfloat16, -1e-3, None),
    "beta 0: nothing is written": (128, 2, 2, jnp.float32, -1.0, 0.0),
    "beta 2: solar's negative eigenvalues": (128, 2, 2, jnp.float32, -1.0, 2.0),
    "beta 2 at ling's bound, bfloat16": (128, 4, 4, jnp.bfloat16, -5.0, 2.0),
}


@pytest.mark.parametrize("case", CASES)
def test_the_kernel_is_the_recurrence_and_the_xla_form(case):
    """The state carried in is not zero and the state carried out is
    compared too. Float32 storage: rounding against either form.
    bfloat16: the XLA form rounds the same operands at the same places,
    so the two agree far closer than either does with the float32
    recurrence."""
    tokens, heads, group, dtype, g_low, beta_of = CASES[case]
    xs = rule_inputs(tokens, heads, dtype, g_low, beta_of, seed=len(case))
    o, after = kda_delta.kda_delta(*xs, chunk=64, group=group, interpret=True)
    assert o.shape == (tokens, heads, D) and o.dtype == jnp.float32
    assert after.shape == (heads, D, D) and after.dtype == jnp.float32
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(after)).all()
    o_scan, after_scan = kda.kda_chunked_scan(*xs, 64)
    o_step, after_step = recurrence(*xs)
    stored = dtype == jnp.bfloat16
    for got, scan, step in ((o, o_scan, o_step), (after, after_scan, after_step)):
        assert rel_l2(got, scan) < (2e-3 if stored else 1e-5)
        assert rel_l2(got, step) < (2e-2 if stored else 1e-5)
    if beta_of == 0.0:  # the state only decays, and o reads it
        np.testing.assert_allclose(
            np.asarray(after), np.asarray(jnp.exp(xs[3].sum(0))[:, :, None] * xs[5]),
            rtol=1e-5, atol=1e-7)


def test_a_decay_that_forgets_a_chunk_in_a_token_stays_finite():
    """exp(-40 x 16) is 0 in float32 and its reciprocal infinite: every
    ratio of decays the kernel forms is the exponential of a difference
    that is <= 0."""
    q, k, v, g, beta, state = rule_inputs(128, 2, jnp.float32)
    g = 80.0 * g
    o, after = kda_delta.kda_delta(q, k, v, g, beta, state, chunk=64, interpret=True)
    o_step, after_step = recurrence(q, k, v, g, beta, state)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(after)).all()
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_step), rtol=1e-5, atol=3e-6)
    np.testing.assert_allclose(np.asarray(after), np.asarray(after_step), rtol=1e-5, atol=3e-6)


@pytest.mark.parametrize("chunk", [16, 32, 48])
def test_a_chunk_of_any_multiple_of_the_subchunk(chunk):
    """One block, two, and three (the merge of pairs of blocks has a
    block left over)."""
    xs = rule_inputs(100, 2, jnp.float32, seed=chunk)
    o, after = kda_delta.kda_delta(*xs, chunk=chunk, interpret=True)
    o_step, after_step = recurrence(*xs)
    assert rel_l2(o, o_step) < 1e-5 and rel_l2(after, after_step) < 1e-5


@pytest.mark.parametrize("heads, d, chunk, itemsize, group", [
    (32, 128, 64, 2, kda_delta.MAX_HEADS),   # ling-flash's held heads
    (64, 128, 64, 2, kda_delta.MAX_HEADS),   # solar-open2's
    (2, 128, 64, 4, 2),                      # fewer heads than a step takes
    (64, 256, 64, 2, 4),                     # wider heads: fewer fit a step
    (3, 16, 32, 4, None),                    # the registry's tiny models
    (32, 128, 24, 2, None),                  # a chunk off the subchunk
    (32, 128, 64, 1, None),                  # no tile for a byte
    (32, 192, 64, 2, None),
])
def test_the_plan_is_from_the_shape(heads, d, chunk, itemsize, group):
    assert kda_delta.delta_plan(heads, d, chunk, itemsize) == group
    if group:
        assert kda_delta.delta_vmem_bytes(group, d, chunk, itemsize) <= attention.VMEM_BUDGET


def test_a_shape_without_a_plan_is_refused():
    xs = rule_inputs(64, 2, jnp.float32)
    with pytest.raises(ValueError, match="no plan"):
        kda_delta.kda_delta(*xs, chunk=24, interpret=True)


@pytest.mark.parametrize("backend, heads, d, chunk, form", [
    ("cpu", 32, 128, 64, "scan"),
    ("tpu", 32, 128, 64, "kernel"),
    ("tpu", 64, 128, 64, "kernel"),
    ("tpu", 3, 16, 32, "scan"),     # d = 16
    ("tpu", 32, 128, 24, "scan"),   # a chunk that is no multiple of 16
    ("gpu", 32, 128, 64, "scan"),
])
def test_the_route_is_from_the_backend_and_the_shape(monkeypatch, backend, heads, d, chunk, form):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert kda_delta.kda_delta_route(heads, d, chunk, jnp.bfloat16) == form


def test_on_the_cpu_the_chunked_rule_is_the_scan_and_the_route_log_says_so():
    """`kda_chunked` traces to `kda_chunked_scan`'s program, and leaves
    one entry a call; outside a `route_log` block nothing is collected."""
    xs = rule_inputs(70, 2, jnp.float32)
    with attention.route_log() as routes:
        mine = jax.make_jaxpr(lambda *a: kda.kda_chunked(*a, 32))(*xs)
    assert routes == ["kda-scan 70x2x128 c32 f32"]
    assert str(mine) == str(jax.make_jaxpr(lambda *a: kda.kda_chunked_scan(*a, 32))(*xs))
    kda.kda_chunked(*xs, 32)


def test_on_a_tpu_the_chunked_rule_is_the_kernel_and_the_route_log_says_so(monkeypatch):
    """The route forced by the backend's name (what it reads while a
    program is traced) and the kernel interpreted: no option of the
    program."""
    calls, compiled = [], kda_delta.kda_delta

    def interpreted(*xs, chunk):
        calls.append(chunk)
        return compiled(*xs, chunk=chunk, interpret=True)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(kda_delta, "kda_delta", interpreted)
    xs = rule_inputs(100, 8, jnp.bfloat16)
    with attention.route_log() as routes:
        o, after = kda.kda_chunked(*xs, 64)
    assert calls == [64]
    assert routes == [f"kda-kernel 100x8x128 c64 hb{kda_delta.MAX_HEADS} bf16"]
    o_scan, after_scan = kda.kda_chunked_scan(*xs, 64)
    assert rel_l2(o, o_scan) < 2e-3 and rel_l2(after, after_scan) < 2e-3


@pytest.mark.parametrize("module, name, published", [
    (solar_open2, "tiny-solar-open2", "solar-open2-ep8-4l"),
    (ling_flash, "tiny-ling-flash", "ling-flash-ep8-7l"),
])
def test_a_models_report_names_the_form(monkeypatch, module, name, published):
    """`kda_form` beside `prefill_chunks`: the tiny model's d = 16 is
    the scan's on any backend, the published widths are the kernel's on
    a TPU and the scan's on the CPU."""
    model_class = {solar_open2: solar_open2.SolarOpen2, ling_flash: ling_flash.LingFlash}[module]
    loads = np.zeros((1, 1), np.int32)
    counts = () if module is solar_open2 else (np.array([4, 0, 0, 0]),)

    def form(cfg, backend):
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        model = model_class(cfg)
        model.dtype = jnp.dtype(jnp.bfloat16)
        report = model.report(100, 4, 128, loads, loads, *counts)
        assert report["prefill_chunks"] == -(-100 // cfg.kda_chunk)
        return report["kda_form"]

    assert form(get_config(name), "cpu") == form(get_config(name), "tpu") == "scan"
    assert form(get_config(published), "cpu") == "scan"
    assert form(get_config(published), "tpu") == "kernel"
