"""Latent attention over a selection's chosen rows as a Pallas kernel
(`ops/dsa_attend.py`), interpreted on the CPU: against the XLA block
`models/dsa.attend` keeps for every other backend and against
`mla.absorbed` under the selection's mask, the words the kernel holds
of a cache, the plan and the route from the shape, and what the route
log says of the form `dsa.attend` took, and the tiny model's prefill
on the kernel's route against its prefill on XLA's."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.models import dsa, glm_dsa, mla
from comfyui_distributed_tpu.models.registry import create_model, get_config
from comfyui_distributed_tpu.ops import attention, dsa_attend, dsa_select

SCALE = 0.25


def operands(queries, rows, k, heads, nope, rope, v, rank, dtype, seed=0, visible=None):
    """A part's operands as `dsa.attend` takes them: the last `queries`
    positions of a cache of `rows` rows, each query's k positions drawn
    among those it may see (`visible` of them at most, where given: the
    rest of its k do not count), in no order."""
    keys = jax.random.split(jax.random.key(seed), 7)
    normal = lambda key, *shape: jax.random.normal(key, shape).astype(dtype)  # noqa: E731
    positions = jnp.arange(rows - queries, rows)
    index = jnp.where(jnp.arange(rows)[None, :] <= positions[:, None],
                      jax.random.uniform(keys[0], (queries, rows)), -jnp.inf)
    if visible is not None:
        index = jnp.where(jnp.arange(rows)[None, :] < visible[:, None], index, -jnp.inf)
    selection = dsa.top(index, k)
    return (normal(keys[1], queries, heads, nope), normal(keys[2], queries, heads, rope),
            normal(keys[3], rows, rank + rope), selection,
            (jax.random.normal(keys[4], (rank, heads, nope)) * rank ** -0.5).astype(dtype),
            (jax.random.normal(keys[5], (rank, heads, v)) * rank ** -0.5).astype(dtype))


def xla_block(*xs):
    """The form every backend but a TPU runs."""
    return dsa.attend_gathered(*xs, SCALE)


def through_the_kernel(*xs):
    """The kernel, interpreted, between the two products left outside it."""
    return dsa.attend_kernel(*xs, SCALE, interpret=True)


def worst(got, want) -> float:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


# (queries, cache rows, k, heads, nope, rope, v, rank, the most a query sees)
CASES = {
    "the toy shape of chip_smoke's rehearsal": (40, 72, 8, 4, 12, 8, 16, 24, None),
    "every query short of k positions": (24, 24, 32, 4, 12, 8, 16, 24, None),
    "some queries short of k, some not": (40, 80, 64, 8, 16, 8, 16, 32, None),
    "a query count off the block of eight": (13, 90, 16, 4, 12, 8, 16, 24, None),
    "k off a lane multiple, past one": (16, 300, 130, 4, 12, 8, 16, 24, None),
    "lane tiles whole: rank 128, rope 64": (16, 300, 128, 16, 32, 64, 32, 128, None),
    "a row wider than two tiles of words": (8, 64, 16, 4, 16, 72, 16, 256, None),
    "one position that counts": (8, 64, 8, 4, 12, 8, 16, 24, 1),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(CASES), ids=list(CASES))
def test_the_kernel_is_the_xla_block_and_the_masked_form(case, dtype):
    *shape, most = CASES[case]
    queries, rows, k = shape[:3]
    visible = None if most is None else jnp.full((queries,), most)
    xs = operands(*shape, dtype, seed=len(case), visible=visible)
    selection = xs[3]
    counted = np.asarray(selection.counts).sum(axis=1)
    assert counted.min() >= 1 and (case.count("short") == 0 or counted.min() < k)
    got = through_the_kernel(*xs)
    block = xla_block(*xs)
    masked = mla.absorbed(*xs[:3], dsa.as_mask(selection, rows), *xs[4:], SCALE)
    # float32: the same sums in another order; bfloat16: the same roundings, a
    # probability or an output an ulp apart here and there
    limit = 2e-5 if dtype == jnp.float32 else 1e-2
    assert worst(got, block) <= limit and worst(got, masked) <= 2 * limit


def test_the_rows_past_a_querys_count_change_nothing():
    """Whatever positions stand where `counts` is false, zero or not."""
    xs = operands(16, 16, 32, 4, 12, 8, 16, 24, jnp.float32)
    chosen, counts = xs[3]
    assert not np.asarray(counts).all()
    other = dsa.Selection(jnp.where(counts, chosen, 15 - chosen % 16), counts)
    got = through_the_kernel(*xs)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(through_the_kernel(*xs[:3], other, *xs[4:])))


@pytest.mark.parametrize("dtype, rank, rope, tiles, key_width", [
    (jnp.bfloat16, 512, 64, 3, 640), (jnp.bfloat16, 24, 8, 1, 256), (jnp.bfloat16, 100, 130, 2, 384),
    (jnp.float32, 512, 64, 5, 640)])
def test_the_words_the_kernel_holds_are_the_cache(dtype, rank, rope, tiles, key_width):
    """`table` and `_rows_of` are each other's inverse: a row is a run
    of `tiles` sublanes, and its low halves then its high halves are
    its columns again: the values, zeros to a whole lane tile, the
    other channels, zeros."""
    cache = jax.random.normal(jax.random.key(1), (21, rank + rope)).astype(dtype)
    words = dsa_attend.table(cache, rank)
    assert words.shape == (24 * tiles, 128)
    assert words.dtype == (jnp.uint32 if dtype == jnp.bfloat16 else jnp.float32)
    by_row = words.reshape(24, tiles, 128)
    rows = np.asarray(dsa_attend._rows_of([by_row[:, j] for j in range(tiles)], dtype, key_width))
    assert rows.shape == (24, key_width) and rows.dtype == dtype
    value_width = -(-rank // 128) * 128
    np.testing.assert_array_equal(rows[:21, :rank], np.asarray(cache[:, :rank]))
    np.testing.assert_array_equal(
        rows[:21, value_width:value_width + rope], np.asarray(cache[:, rank:]))
    assert not rows[21:].any() and not rows[:, rank:value_width].any()
    assert not rows[:, value_width + rope:].any()


@pytest.mark.parametrize("heads, width, rank, k, rows, itemsize, sizes", [
    (64, 576, 512, 2048, 32896, 2, (64, 2048, 384, 640, 512)),   # glm-5.2's part
    (64, 576, 512, 2048, 8320, 2, (64, 2048, 384, 640, 512)),    # a short prompt's cache
    (64, 576, 512, 2048, 32896, 4, None),                        # float32: 84 MB resident
    (4, 32, 24, 8, 72, 4, (8, 128, 256, 256, 128)),              # the tiny model's, padded
    (4, 32, 24, 8, 72, 2, (16, 128, 128, 256, 128)),
    (64, 576, 512, 2048, 70000, 2, None),                        # a cache past the budget
    (64, 576, 512, 8192, 32896, 2, None),                        # positions past the SMEM blocks
    (64, 576, 512, 2048, 32896, 1, None),                        # no words for a byte
    (64, 576, 576, 2048, 32896, 2, None),                        # no channel beside the values
])
def test_the_plan_is_from_the_shape(heads, width, rank, k, rows, itemsize, sizes):
    plan = dsa_attend.plan(heads, width, rank, k, rows, itemsize)
    assert (plan and plan[:5]) == sizes
    if plan:
        assert plan.vmem_bytes <= dsa_attend.VMEM_RESIDENT_BUDGET
        assert plan.vmem_bytes >= -(-rows // 8) * 8 * plan.lanes * 4


def test_a_shape_without_a_plan_is_refused():
    xs = operands(8, 64, 8, 4, 12, 8, 16, 24, jnp.float32)
    q_lat, words = jnp.einsum("thd,chd->thc", xs[0], xs[4]), dsa_attend.table(xs[2], 24)
    call = functools.partial(dsa_attend.dsa_attend, scale=SCALE, interpret=True)
    o_lat = call(q_lat, xs[1], words, *xs[3])
    assert o_lat.shape == (8, 4, 24) and o_lat.dtype == jnp.float32
    for wrong in (words[:-1],                                          # no whole rows
                  dsa_attend.table(xs[2].astype(jnp.bfloat16), 24),    # another dtype's words
                  jnp.tile(words, (20000, 1))):                        # 1.28 M rows: past the budget
        with pytest.raises(ValueError, match="no plan"):
            call(q_lat, xs[1], wrong, *xs[3])
    with pytest.raises(ValueError, match="no plan"):
        call(q_lat, xs[1].astype(jnp.bfloat16), words, *xs[3])


@pytest.mark.parametrize("backend, rows, dtype, form", [
    ("cpu", 32896, jnp.bfloat16, "gathered"),
    ("tpu", 32896, jnp.bfloat16, "kernel"),
    ("tpu", 72, jnp.float32, "kernel"),
    ("tpu", 32896, jnp.float32, "gathered"),    # does not fit beside a query's rows
    ("tpu", 32896, jnp.float16, "gathered"),    # a half is no float32's top
    ("gpu", 32896, jnp.bfloat16, "gathered"),
])
def test_the_route_is_from_the_backend_and_the_shape(monkeypatch, backend, rows, dtype, form):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert dsa_attend.route(64, 576, 512, 2048, rows, dtype) == form


def test_on_the_cpu_the_gathered_form_is_the_xla_block_and_the_route_log_says_so():
    """`dsa.attend` leaves one entry a call; outside a `route_log` block
    nothing is collected."""
    xs = operands(40, 72, 8, 4, 12, 8, 16, 24, jnp.float32)
    with attention.route_log() as routes:
        text = str(jax.make_jaxpr(lambda *a: dsa.attend(*a[:3], dsa.Selection(*a[3:5]), *a[5:], SCALE))(
            *xs[:3], *xs[3], *xs[4:]))
    assert routes == ["dsa-gathered 40x72 k8 h4 f32"]
    assert "pallas_call" not in text and "gather" in text
    dsa.attend(*xs, SCALE)


def test_on_a_tpu_the_gathered_form_is_the_kernel_and_the_route_log_says_so(monkeypatch):
    """The route forced by the backend's name (what it reads while a
    program is traced) and the kernel interpreted: no option of the
    program."""
    calls, compiled = [], dsa_attend.dsa_attend

    def interpreted(*xs, scale, interpret):
        calls.append((scale, interpret))
        return compiled(*xs, scale=scale, interpret=True)

    xs = operands(40, 72, 8, 4, 12, 8, 16, 24, jnp.bfloat16)
    want = xla_block(*xs)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(dsa_attend, "dsa_attend", interpreted)
    with attention.route_log() as routes:
        got = dsa.attend(*xs, SCALE)
    assert calls == [(SCALE, False)]
    assert routes == ["dsa-kernel 40x72 k8 h4 bf16"]
    assert worst(got, want) <= 1e-2
    # a step's queries never reach it
    masked = dsa.Selection(None, dsa.as_mask(dsa.Selection(*(a[:2] for a in xs[3])), 72))
    with attention.route_log() as routes:
        dsa.attend(xs[0][:2], xs[1][:2], xs[2], masked, *xs[4:], SCALE)
    assert calls == [(SCALE, False)] and routes == ["dsa-masked 2x72 k72 h4 bf16"]


def test_glm_dsas_prefill_on_the_tpus_route_is_its_prefill_on_the_cpus(monkeypatch):
    """The tiny model's prefill in parts, traced anew under a TPU's
    route with the kernel interpreted: every layer's part attends in the
    kernel (the remainder's eight queries take the masked form), the
    logits are the XLA route's, and the report still names the
    selection's form, which is `dsa.form`'s by the number of queries
    alone."""
    cfg = get_config("tiny-glm-dsa")
    lm = create_model("tiny-glm-dsa")
    params = lm.init(jax.random.key(0))
    ids = jax.random.randint(jax.random.key(1), (40,), 0, cfg.vocab_size)
    want = lm.prefill(params, ids, 48)
    compiled, selecting = dsa_attend.dsa_attend, dsa_select.dsa_select
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(dsa_attend, "dsa_attend", lambda *xs, scale, interpret: compiled(
        *xs, scale=scale, interpret=True))
    # the selection takes a TPU's route with it (PR 57; `tests/test_dsa_select_kernel.py`)
    monkeypatch.setattr(dsa_select, "dsa_select", lambda index, k, interpret: selecting(
        index, k=k, interpret=True))
    # under a function of this test's own: `jit` keeps a trace by the function it
    # wraps, so two tests that wrap `prefill.__wrapped__` itself share one trace in a
    # worker, and the second meets the first's routes and an empty log (PR 64)
    anew = jax.jit(
        lambda cfg, *operands, cache_len: glm_dsa.prefill.__wrapped__(
            cfg, *operands, cache_len=cache_len),
        static_argnums=0, static_argnames="cache_len")
    with attention.route_log() as routes:
        got = anew(cfg, params, ids, cache_len=48)
    layers = cfg.num_hidden_layers
    attending = [r for r in routes if not r.startswith("dsa-select")]
    assert attending == (
        ["dsa-kernel 16x48 k8 h4 f32"] * layers + ["dsa-masked 8x48 k48 h4 f32"] * layers)
    np.testing.assert_allclose(np.asarray(got.logits), np.asarray(want.logits), atol=2e-5)
    assert dsa.form(cfg.prefill_part) == "gathered" and dsa.form(8) == dsa.form(2) == "masked"
    published = get_config("glm-5.2-ep16-5l")
    assert dsa_attend.route(
        published.num_attention_heads, published.cache_width, published.kv_lora_rank,
        published.index_topk, 32896, jnp.bfloat16) == "kernel"
