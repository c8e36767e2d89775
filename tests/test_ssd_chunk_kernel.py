"""A prefill's chunked Mamba-2 scan as a Pallas kernel
(`ops/ssd_chunk.py`), interpreted on the CPU: against the recurrence
itself (`mamba2.ssm_step`, token by token) and against the XLA form
(`mamba2.ssd_chunked_xla`) at widths on the lane tile, the plan and the
route from the shape, and what the route log says of the form
`mamba2.ssd_chunked` took."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.models import mamba2
from comfyui_distributed_tpu.models.lm_common import init_from_shapes
from comfyui_distributed_tpu.ops import attention, ssd_chunk

N = 128


def scan_inputs(tokens, heads, width, groups, dtype, seed=0, rate=1.0):
    """u, B (of length about 1) and C in `dtype`, a step in (0, 0.7
    `rate`), A in (-16, -1), and a state to start from that is not
    zero."""
    keys = jax.random.split(jax.random.key(seed), 6)
    u = jax.random.normal(keys[0], (tokens, heads, width)).astype(dtype)
    b = (jax.random.normal(keys[1], (tokens, groups, N)) * N ** -0.5).astype(dtype)
    c = jax.random.normal(keys[2], (tokens, groups, N)).astype(dtype)
    step = rate * jax.nn.softplus(jax.random.normal(keys[3], (tokens, heads)) - 2.0)
    a = -jax.random.uniform(keys[4], (heads,), jnp.float32, 1.0, 16.0)
    return u, b, c, step, a, jax.random.normal(keys[5], (heads, width, N))


def recurrence(u, b, c, step, a, state):
    def token(state, xs):
        y, state = mamba2.ssm_step(*xs, a, state)
        return state, y

    u, b, c = (t.astype(jnp.float32) for t in (u, b, c))
    state, y = jax.lax.scan(token, state, (u, b, c, step))
    return y, state


def rel_l2(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# (tokens, heads, width, groups, chunk, heads a grid step, storage dtype)
CASES = {
    "one group, chunks of 128": (256, 4, 64, 1, 128, None, jnp.float32),
    "one group, chunks of 256, bfloat16": (512, 4, 64, 1, 256, None, jnp.bfloat16),
    "one group, chunks of 256, two heads a step": (256, 4, 64, 1, 256, 2, jnp.float32),
    "two groups, chunks of 128, bfloat16": (256, 4, 64, 2, 128, None, jnp.bfloat16),
    "two groups, chunks of 256": (512, 8, 64, 2, 256, None, jnp.float32),
    "eight groups, chunks of 128": (256, 16, 64, 8, 128, None, jnp.float32),
    "eight groups, chunks of 256, bfloat16": (256, 16, 64, 8, 256, None, jnp.bfloat16),
    "a short last chunk": (300, 4, 64, 2, 128, None, jnp.float32),
    "a short last chunk of 256, bfloat16": (300, 4, 64, 1, 256, None, jnp.bfloat16),
    "fewer tokens than a chunk": (5, 2, 64, 1, 128, None, jnp.float32),
    "heads a lane tile wide, a head a step": (200, 2, 128, 1, 128, 1, jnp.bfloat16),
    "heads two lane tiles wide": (130, 2, 256, 2, 128, None, jnp.float32),
}


@pytest.mark.parametrize("case", CASES)
def test_the_kernel_is_the_recurrence_and_the_xla_form(case):
    """The state carried in is not zero and the state carried out is
    compared too. Float32 storage: rounding against either form.
    bfloat16: the XLA form rounds the same operands at the same places,
    so the two agree far closer than either does with the float32
    recurrence."""
    tokens, heads, width, groups, chunk, block, dtype = CASES[case]
    xs = scan_inputs(tokens, heads, width, groups, dtype, seed=len(case))
    y, after = ssd_chunk.ssd_chunk(*xs, chunk=chunk, block=block, interpret=True)
    assert y.shape == (tokens, heads, width) and y.dtype == jnp.float32
    assert after.shape == (heads, width, N) and after.dtype == jnp.float32
    assert np.isfinite(np.asarray(y)).all() and np.isfinite(np.asarray(after)).all()
    y_xla, after_xla = mamba2.ssd_chunked_xla(*xs, chunk)
    y_step, after_step = recurrence(*xs)
    stored = dtype == jnp.bfloat16
    for got, xla, step in ((y, y_xla, y_step), (after, after_xla, after_step)):
        assert rel_l2(got, xla) < (2e-3 if stored else 1e-5)
        assert rel_l2(got, step) < (2e-2 if stored else 1e-5)


def test_a_decay_that_forgets_a_chunk_in_a_token_stays_finite():
    """exp(-16 x 40) is 0 in float32 and its reciprocal infinite: every
    ratio of decays the kernel forms is the exponential of a difference
    that is <= 0, and above the diagonal of -inf."""
    xs = scan_inputs(256, 2, 64, 1, jnp.float32, rate=300.0)
    y, after = ssd_chunk.ssd_chunk(*xs, chunk=128, interpret=True)
    y_step, after_step = recurrence(*xs)
    assert np.isfinite(np.asarray(y)).all() and np.isfinite(np.asarray(after)).all()
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_step), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(after), np.asarray(after_step), rtol=1e-4, atol=1e-4)


def test_a_step_of_zero_changes_nothing():
    """What fills a short last chunk: the state stays and a token reads
    nothing of its own chunk."""
    u, b, c, step, a, state = scan_inputs(128, 2, 64, 1, jnp.float32)
    y, after = ssd_chunk.ssd_chunk(u, b, c, 0.0 * step, a, state, chunk=128, interpret=True)
    np.testing.assert_allclose(np.asarray(after), np.asarray(state), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(jnp.einsum("hpn,tn->thp", state, c[:, 0])), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("heads, width, groups, n, chunk, itemsize, block", [
    (64, 64, 1, 128, 256, 2, ssd_chunk.MAX_HEADS),   # granite-4.0-h-micro's
    (64, 64, 8, 128, 128, 2, ssd_chunk.MAX_HEADS),   # nemotron-3-nano's: a group a step
    (48, 64, 8, 128, 128, 2, 6),                     # a group's six heads
    (4, 64, 2, 128, 128, 4, 2),                      # fewer heads than a step takes
    (24, 128, 2, 128, 256, 2, 6),                    # twelve heads a group: no eight divide them
    (64, 64, 64, 128, 128, 2, None),                 # a head a group: half a lane tile a step
    (4, 8, 2, 16, 32, 4, None),                      # the registry's tiny models
    (64, 64, 1, 128, 64, 2, None),                   # a chunk off the lane tile
    (64, 64, 1, 64, 256, 2, None),                   # a state off the lane tile
    (64, 96, 1, 128, 256, 2, None),                  # a width that is neither
    (64, 64, 3, 128, 256, 2, None),                  # heads that are no whole groups
    (64, 64, 1, 128, 256, 1, None),                  # no tile for a byte
])
def test_the_plan_is_from_the_shape(heads, width, groups, n, chunk, itemsize, block):
    assert ssd_chunk.chunk_plan(heads, width, groups, n, chunk, itemsize) == block
    if block:
        assert ssd_chunk.chunk_vmem_bytes(block, width, n, chunk, itemsize) <= (
            attention.VMEM_BUDGET)


def test_a_shape_without_a_plan_and_a_step_that_tiles_no_group_are_refused():
    xs = scan_inputs(64, 2, 64, 1, jnp.float32)
    with pytest.raises(ValueError, match="no plan"):
        ssd_chunk.ssd_chunk(*xs, chunk=64, interpret=True)
    with pytest.raises(ValueError, match="do not tile"):
        ssd_chunk.ssd_chunk(*xs, chunk=128, block=1, interpret=True)


@pytest.mark.parametrize("backend, heads, width, groups, n, chunk, form", [
    ("cpu", 64, 64, 1, 128, 256, "xla"),
    ("tpu", 64, 64, 1, 128, 256, "kernel"),
    ("tpu", 64, 64, 8, 128, 128, "kernel"),
    ("tpu", 4, 8, 2, 16, 32, "xla"),       # widths off the lane tile
    ("tpu", 64, 96, 1, 128, 256, "xla"),
    ("tpu", 64, 64, 1, 128, 192, "xla"),   # a chunk that is no multiple of 128
    ("gpu", 64, 64, 1, 128, 256, "xla"),
])
def test_the_route_is_from_the_backend_and_the_shape(
        monkeypatch, backend, heads, width, groups, n, chunk, form):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ssd_chunk.ssd_route(heads, width, groups, n, chunk, jnp.bfloat16) == form


def test_on_the_cpu_the_chunked_scan_is_the_xla_form_and_the_route_log_says_so():
    """`ssd_chunked` traces to `ssd_chunked_xla`'s program, and leaves
    one entry a call; outside a `route_log` block nothing is collected."""
    xs = scan_inputs(200, 4, 64, 2, jnp.float32)
    with attention.route_log() as routes:
        mine = jax.make_jaxpr(lambda *a: mamba2.ssd_chunked(*a, 128))(*xs)
    assert routes == ["ssd-xla 200x4x64 g2 n128 c128 f32"]
    assert str(mine) == str(jax.make_jaxpr(lambda *a: mamba2.ssd_chunked_xla(*a, 128))(*xs))
    mamba2.ssd_chunked(*xs, 128)


def test_on_a_tpu_the_chunked_scan_is_the_kernel_and_the_route_log_says_so(monkeypatch):
    """The route forced by the backend's name (what it reads while a
    program is traced) and the kernel interpreted: no option of the
    program."""
    calls, compiled = [], ssd_chunk.ssd_chunk

    def interpreted(*xs, chunk):
        calls.append(chunk)
        return compiled(*xs, chunk=chunk, interpret=True)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ssd_chunk, "ssd_chunk", interpreted)
    xs = scan_inputs(300, 16, 64, 2, jnp.bfloat16)
    with attention.route_log() as routes:
        y, after = mamba2.ssd_chunked(*xs, 128)
    assert calls == [128]
    assert routes == [f"ssd-kernel 300x16x64 g2 n128 c128 hb{ssd_chunk.MAX_HEADS} bf16"]
    y_xla, after_xla = mamba2.ssd_chunked_xla(*xs, 128)
    assert rel_l2(y, y_xla) < 2e-3 and rel_l2(after, after_xla) < 2e-3


def test_a_mixer_on_a_tpus_route_is_the_mixer_on_the_xla_form(monkeypatch):
    """The whole layer (`mamba2.mixer`) over a sequence with the scan in
    the kernel, against the same layer on the CPU's route: the skip, the
    gated norm and the output projection read what the kernel wrote."""
    heads, width, groups, hidden, kernel = 4, 64, 2, 64, 4
    keys = jax.random.split(jax.random.key(7), 4)
    p = init_from_shapes(mamba2.shapes(hidden, heads, width, groups, N, kernel), keys[0])
    p.update(mamba2.init_steps(keys[1], (heads,), 0.001, 0.1, 1e-4))
    x = jax.random.normal(keys[2], (200, hidden))
    tail = jnp.zeros((kernel - 1, heads * width + 2 * groups * N))
    state = 0.1 * jax.random.normal(keys[3], (heads, width, N))
    want = mamba2.mixer(p, x, tail, state, heads, width, groups, N, 128, 1e-5)

    compiled = ssd_chunk.ssd_chunk
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ssd_chunk, "ssd_chunk",
                        lambda *xs, chunk: compiled(*xs, chunk=chunk, interpret=True))
    with attention.route_log() as routes:
        got = mamba2.mixer(p, x, tail, state, heads, width, groups, N, 128, 1e-5)
    assert routes == ["ssd-kernel 200x4x64 g2 n128 c128 hb2 f32"]
    for mine, theirs in zip(got, want):
        assert rel_l2(mine, theirs) < 1e-5
