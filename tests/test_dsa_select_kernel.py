"""The exact top-k as positions without a sort (`ops/dsa_select.py`),
interpreted on the CPU: against `dsa.top` (`lax.top_k`) as sets over
shapes and contents that break selections, the plan and the route from
the shape, what the route log says of the form `dsa.select` took a rung,
and the tiny model's prefill on a TPU's route against its prefill on
XLA's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.models import dsa, glm_dsa
from comfyui_distributed_tpu.models.registry import create_model, get_config
from comfyui_distributed_tpu.ops import attention, dsa_attend, dsa_select


def as_scores_leave_them(index, visible):
    """I as `dsa.scores` hands it on: minus infinity from a query's
    `visible`-th position on, +0 the only zero."""
    index = jnp.asarray(index, jnp.float32)
    seen = jnp.arange(index.shape[1])[None, :] < jnp.asarray(visible)[:, None]
    return jnp.where(seen, jnp.where(index == 0, 0.0, index), -jnp.inf)


def drawn(queries, positions, seed, step=None):
    rng = np.random.default_rng(seed)
    index = rng.standard_normal((queries, positions)).astype(np.float32)
    return index if step is None else np.round(index / step) * step


def every_position(queries, positions):
    return np.full(queries, positions)


def up_to_the_query(queries, positions):
    """The last `queries` positions of a cache, as a part's queries."""
    return np.arange(positions - queries, positions) + 1


def _ties_across_the_threshold():
    """Rows whose k-th best score is shared by many positions, some of
    them before and some after positions that score higher."""
    index = np.full((6, 300), 1.0, np.float32)
    index[:, 5::7] = 2.0         # 43 above
    index[:, 3::11] = -1.0
    index[3:, :] *= -1.0         # the same under the other sign: the threshold is -1
    return index, every_position(6, 300)


def _both_zeros():
    index = drawn(5, 256, 3, step=1.0)
    index[:, ::2] *= -0.0        # zeros of both signs, and nothing else, on the even positions
    return index, every_position(5, 256)


def _nothing_but_the_first():
    index = drawn(4, 200, 4)
    return index, np.array([1, 1, 2, 200])


# name -> (scores [T, S], the positions a query sees, k)
CASES = {
    "the toy shape of chip_smoke's rehearsal": (drawn(40, 72, 0), up_to_the_query(40, 72), 8),
    "positions off a power of two, a part's last block": (
        drawn(9, 32896, 1), up_to_the_query(9, 32896), 2048),
    "positions off a lane multiple": (drawn(7, 1000, 2), every_position(7, 1000), 128),
    "k off a lane multiple": (drawn(7, 640, 5), every_position(7, 640), 100),
    "k off a sublane multiple": (drawn(3, 130, 6), every_position(3, 130), 13),
    "fewer than k visible": (drawn(24, 512, 7), np.arange(1, 25) * 9, 128),
    "k is every position": (drawn(5, 128, 8), every_position(5, 128), 128),
    "k past every position": (drawn(5, 96, 9), every_position(5, 96), 200),
    "rows of equal scores": (np.zeros((4, 384), np.float32) + np.array(
        [[0.0], [1.5], [-2.0], [3e38]], np.float32), np.array([384, 200, 384, 3]), 64),
    "ties across the threshold": (*_ties_across_the_threshold(), 60),
    "coarse scores, many ties": (drawn(33, 777, 10, step=0.5), up_to_the_query(33, 777), 96),
    "negative scores only": (-np.abs(drawn(6, 300, 11)) - 1.0, every_position(6, 300), 32),
    "zeros of both signs": (*_both_zeros(), 40),
    "nothing visible but position 0": (*_nothing_but_the_first(), 16),
    "a row count off the block": (drawn(130, 256, 12, step=0.25), up_to_the_query(130, 256), 24),
    "two blocks and a remainder": (drawn(300, 160, 13), every_position(300, 160), 16),
}


@pytest.mark.parametrize("case", list(CASES), ids=list(CASES))
def test_the_kernel_chooses_what_top_k_chooses(case):
    """The same set, ties included; as many count as min(k, visible);
    a query's positions ascending and inside the scores; what does not
    count points at row 0."""
    raw, visible, k = CASES[case]
    index = as_scores_leave_them(raw, visible)
    positions = index.shape[1]
    got = dsa.top_compacted(index, k, interpret=True)
    want = dsa.top(index, k)
    assert got.chosen.shape == want.chosen.shape == got.counts.shape == (len(raw), min(k, positions))
    assert got.chosen.dtype == jnp.int32 and got.counts.dtype == jnp.bool_
    np.testing.assert_array_equal(dsa.as_mask(got, positions), dsa.as_mask(want, positions))
    chosen, counts = np.asarray(got.chosen), np.asarray(got.counts)
    np.testing.assert_array_equal(counts.sum(axis=1), np.minimum(k, visible))
    first = np.arange(counts.shape[1])[None, :] < counts.sum(axis=1)[:, None]
    np.testing.assert_array_equal(counts, first)                   # those that count come first
    assert (chosen >= 0).all() and (chosen < positions).all() and not chosen[~counts].any()
    steps = np.diff(chosen, axis=1)
    assert (steps[counts[:, 1:]] > 0).all()                        # ascending


def test_a_zero_of_the_other_sign_orders_as_top_k_orders_it():
    """`scores` lets only +0 through; the keys would tell the two apart
    as `lax.top_k`'s total order does."""
    index = jnp.asarray([[0.0, -0.0, -1.0, 0.0, -0.0, 1.0, -0.0, 0.0]], jnp.float32)
    for k in (2, 3, 4, 6):
        got, want = dsa.top_compacted(index, k, interpret=True), dsa.top(index, k)
        np.testing.assert_array_equal(dsa.as_mask(got, 8), dsa.as_mask(want, 8))


@pytest.mark.parametrize("rows, positions, k, sizes", [
    (128, 32768, 2048, (128, 32768, 2048, 15)),
    (128, 32896, 2048, (128, 32896, 2048, 16)),      # one stage more than a power of two
    (40, 72, 8, (128, 128, 128, 7)),
    (130, 1000, 100, (256, 1024, 128, 10)),
    (8, 96, 200, (128, 128, 128, 7)),                # k past the positions
    (128, 64000, 2048, (128, 64000, 2048, 16)),      # the longest that fits
])
def test_the_plan_is_from_the_shape(rows, positions, k, sizes):
    plan = dsa_select.plan(rows, positions, k)
    assert plan[:4] == sizes
    # two buffers of a block's keys and one of its words, in whole trips
    assert plan.vmem_bytes >= 3 * plan.positions * 128 * 4
    assert plan.vmem_bytes <= dsa_attend.VMEM_RESIDENT_BUDGET


@pytest.mark.parametrize("rows, positions, k", [
    (128, 65537, 2048),      # a word's half cannot name the last position
    (128, 131072, 2048),
    (128, 65536, 2048),      # nameable, and past what the chip holds beside the pipeline's buffers
    (128, 0, 8), (0, 128, 8), (128, 128, 0),
])
def test_a_shape_without_a_plan_is_refused(rows, positions, k):
    assert dsa_select.plan(rows, positions, k) is None
    if min(rows, positions) > 0:
        with pytest.raises(ValueError, match="no plan"):
            jax.eval_shape(
                lambda i: dsa_select.dsa_select(i, k=k),
                jax.ShapeDtypeStruct((rows, positions), jnp.float32))


def test_scores_of_another_dtype_are_refused():
    with pytest.raises(ValueError, match="no plan"):
        dsa_select.dsa_select(jnp.zeros((8, 128), jnp.bfloat16), k=8, interpret=True)


@pytest.mark.parametrize("backend, queries, positions, form", [
    ("cpu", 8192, 32768, "sort"),
    ("gpu", 8192, 32768, "sort"),
    ("tpu", 8192, 32768, "kernel"),
    ("tpu", 8192, 32896, "kernel"),
    ("tpu", 16, 48, "kernel"),
    ("tpu", 8192, 65664, "sort"),        # granite's length: past a word's half
    ("tpu", 8, 32896, "bisection"),      # a step's queries, whatever the backend
    ("cpu", 2, 32896, "bisection"),
])
def test_the_form_is_from_the_backend_and_the_shape(monkeypatch, backend, queries, positions, form):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert dsa.selection_form(queries, positions, 2048) == form
    if form != "bisection":
        assert dsa_select.route(min(queries, dsa.BLOCK_ROWS), positions, 2048) == form


def indexer_operands(queries, rows, heads=2, width=16, seed=0):
    keys = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(keys[0], (queries, heads, width)),
            jax.random.normal(keys[1], (queries, heads)),
            jax.random.normal(keys[2], (rows, width)), jnp.arange(rows - queries, rows))


def interpreted(monkeypatch, calls=None):
    """A TPU's routes with both kernels interpreted: the backend's name
    is what the routes read while a program is traced; no option of the
    program."""
    select, attend = dsa_select.dsa_select, dsa_attend.dsa_attend

    def selecting(index, *, k, interpret):
        if calls is not None:
            calls.append((index.shape, k, interpret))
        return select(index, k=k, interpret=True)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(dsa_select, "dsa_select", selecting)
    monkeypatch.setattr(dsa_attend, "dsa_attend", lambda *xs, scale, interpret: attend(
        *xs, scale=scale, interpret=True))


def test_on_the_cpu_a_rung_sorts_and_the_route_log_says_so():
    """`dsa.select` leaves one entry a rung of its ladder; outside a
    `route_log` block nothing is collected."""
    q, w, cached, positions = indexer_operands(40, 72)
    with attention.route_log() as routes:
        text = str(jax.make_jaxpr(lambda *a: tuple(dsa.select(*a, 8)))(q, w, cached, positions))
    assert routes == [f"dsa-select-sort 40x{length} k8" for length in (16, 32, 64, 72)]
    assert "pallas_call" not in text and "top_k" in text
    dsa.select(q, w, cached, positions, 8)


def test_on_a_tpu_a_rung_is_the_kernel_and_the_route_log_says_so(monkeypatch):
    q, w, cached, positions = indexer_operands(40, 72)
    want = dsa.select(q, w, cached, positions, 8)
    calls = []
    interpreted(monkeypatch, calls)
    with attention.route_log() as routes:
        got = dsa.select(q, w, cached, positions, 8)
    assert routes == [f"dsa-select-kernel 40x{length} k8" for length in (16, 32, 64, 72)]
    assert calls == [((40, length), 8, False) for length in (16, 32, 64, 72)]
    np.testing.assert_array_equal(dsa.as_mask(got, 72), dsa.as_mask(want, 72))
    assert (np.diff(np.asarray(got.chosen), axis=1)[np.asarray(got.counts)[:, 1:]] > 0).all()
    # a step's queries never reach it: their mask is the bisection's
    with attention.route_log() as routes:
        step = dsa.select(q[-2:], w[-2:], cached, positions[-2:], 8)
    assert routes == [] and len(calls) == 4 and step.chosen is None
    np.testing.assert_array_equal(step.counts, dsa.as_mask(want, 72)[-2:])


def test_glm_dsas_prefill_on_the_tpus_route_is_its_prefill_on_the_cpus(monkeypatch):
    """The tiny model's prefill in parts, traced anew under a TPU's
    routes with both kernels interpreted: every `full` layer picks its
    keys in the selection kernel a rung and every layer attends in the
    attention kernel, the positions now ascending; the logits are the
    XLA routes' within what the tiny model's tests allow of a form."""
    cfg = get_config("tiny-glm-dsa")
    lm = create_model("tiny-glm-dsa")
    params = lm.init(jax.random.key(0))
    ids = jax.random.randint(jax.random.key(1), (40,), 0, cfg.vocab_size)
    want = lm.prefill(params, ids, 48)
    interpreted(monkeypatch)
    # under a function of this test's own: `jit` keeps a trace by the function it
    # wraps, so two tests that wrap `prefill.__wrapped__` itself share one trace in a
    # worker, and the second meets the first's routes and an empty log (PR 64)
    anew = jax.jit(
        lambda cfg, *operands, cache_len: glm_dsa.prefill.__wrapped__(
            cfg, *operands, cache_len=cache_len),
        static_argnums=0, static_argnames="cache_len")
    with attention.route_log() as routes:
        got = anew(cfg, params, ids, cache_len=48)
    full = sum(cfg.is_full(i) for i in cfg.layers)
    selecting = [r for r in routes if r.startswith("dsa-select")]
    # the scanned whole parts' body; the remainder's eight queries take the masked form
    assert selecting == [f"dsa-select-kernel 16x{length} k8" for length in (16, 32, 48)] * full
    assert routes.count("dsa-kernel 16x48 k8 h4 f32") == cfg.num_hidden_layers
    np.testing.assert_allclose(np.asarray(got.logits), np.asarray(want.logits), atol=2e-5)
    published = get_config("glm-5.2-ep16-5l")
    assert [dsa.selection_form(published.prefill_part, length, published.index_topk)
            for length in dsa.length_ladder(32896, published.index_topk)] == ["kernel"] * 5
    assert dsa.selection_form(2, 32896, published.index_topk) == "bisection"
