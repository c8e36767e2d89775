"""A prefill's grouped products as a Pallas kernel
(`ops/grouped_matmul.py`), interpreted on the CPU: against
`expert_matvec.grouped_xla` (`jax.lax.ragged_dot`) over the row counts,
group counts and layouts the expert models serve, the walk's table
against a walk written out, the plan and the route from the shape, the
rung rule of `grouped_rows`, what the route log says on both backends,
and three tiny models' whole prefills with the kernel on the lowest
rung against their prefills on `ragged_dot`."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.models import (
    deepseek_v2, k_exaone, ling_flash, longcat_flash, moe, nemotron_h, sdar, solar_open2)
from comfyui_distributed_tpu.models.registry import get_config
from comfyui_distributed_tpu.ops import attention
from comfyui_distributed_tpu.ops import expert_matvec as em
from comfyui_distributed_tpu.ops import grouped_matmul as gmm

# tiles of 128 rows in blocks of 32 and 128 columns a sweep: a test's few
# hundred rows walk several tiles, blocks and sweeps as the served shapes do
SMALL = (128, 32, 400 * 2**10)
MID = (128, 32, 440 * 2**10)  # the same at a K of 128
WIDE = (128, 32, 2**20)  # for an N that goes whole


def tiles(monkeypatch, caps):
    """The module's tile rows, block rows and VMEM budget set to `caps`
    for a test (None: its own); `plan` reads them at every call, so a
    call has to be traced afresh under them: `kernel` below."""
    if caps is not None:
        for name, value in zip(("TILE_ROWS", "BLOCK_ROWS", "VMEM_BLOCK_BUDGET"), caps):
            monkeypatch.setattr(gmm, name, value)


# the kernel interpreted and not jitted: `jax.jit` would hand back a trace made under other tiles
kernel = functools.partial(gmm.grouped_matmul.__wrapped__, interpret=True)


def even(rows: int, groups: int, held: float = 1.0) -> list[int]:
    """`groups` sizes that add up to `held` of `rows`, a few rows apart."""
    base = int(rows * held) // groups
    sizes = [base + (i % 3) - 1 for i in range(groups)]
    sizes[-1] += int(rows * held) - sum(sizes)
    return sizes


# name -> (rows, K, N, sizes [groups], what else the call is given)
CASES = {
    "a group of no rows first and last": (600, 64, 256, [0, 100, 30, 20, 250, 0, 200, 0], {}),
    "a tile that straddles three groups": (512, 64, 128, [120, 3, 2, 140, 247], {}),
    "the rows short of the sizes' sum by whole tiles": (1024, 64, 256, [90, 110, 60], {}),
    "every row held": (640, 64, 256, [300, 40, 300], {}),
    "no row held": (400, 64, 128, [0, 0, 0, 0], {}),
    "one row held, on the last group": (400, 64, 128, [0, 0, 0, 1], {}),
    "rows off the tile": (700, 64, 256, [100, 200, 300, 50], {}),
    "K off the lane tile": (520, 48, 256, [200, 120, 200], {}),
    "N off the lane tile": (520, 64, 200, [100, 0, 300], {"caps": WIDE}),
    "out by in": (520, 128, 256, [100, 0, 300, 64], {"out_major": True, "caps": MID}),
    "out by in, N off the lane tile": (
        520, 128, 200, [100, 0, 300, 64], {"out_major": True, "caps": WIDE}),
    "a layer of a stack": (700, 64, 256, [100, 200, 300, 50], {"layers": 3}),
    "a layer of a stack, out by in": (
        600, 128, 72, [250, 0, 200, 100], {"layers": 2, "out_major": True}),
    "8 groups": (1024, 64, 256, even(1024, 8, 0.9), {}),
    "40 groups": (1000, 64, 384, even(1000, 40, 0.97), {}),
    "128 groups": (1152, 64, 128, even(1152, 128), {}),
    "the module's own tiles": (1100, 64, 256, [300, 0, 450, 280], {"caps": None}),
}


def operands(rows, k, n, sizes, dtype, out_major=False, layers=None):
    keys = jax.random.split(jax.random.key(rows + k + n), 2)
    shape = (len(sizes), n, k) if out_major else (len(sizes), k, n)
    if layers:
        shape = (layers, *shape)
    x = jax.random.normal(keys[0], (rows, k)).astype(dtype)
    w = (k ** -0.5 * jax.random.normal(keys[1], shape)).astype(dtype)
    return x, w, jnp.asarray(sizes, jnp.int32), (jnp.int32(layers - 1) if layers else None)


@pytest.mark.parametrize("dtype,tolerance", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("case", CASES)
def test_kernel_gives_what_ragged_dot_gives(monkeypatch, case, dtype, tolerance):
    """The groups' rows as `ragged_dot` has them; every row past
    `sizes.sum()` zero, whatever tile it lies in."""
    rows, k, n, sizes, how = CASES[case]
    how = dict(how)
    tiles(monkeypatch, how.pop("caps", SMALL))
    out_major = how.get("out_major", False)
    x, w, sizes, layer = operands(rows, k, n, sizes, dtype, **how)
    # traced under `jit`, as a scan's body hands the layer
    got = jax.jit(lambda x, w, sizes, layer: kernel(
        x, w, sizes, layer, out_major=out_major))(x, w, sizes, layer)
    want = em.grouped_xla(x, w, sizes, layer, out_major=out_major)
    held = int(sizes.sum())
    assert got.shape == (rows, n) and got.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(got[:held], np.float32), np.asarray(want[:held], np.float32),
        rtol=tolerance, atol=tolerance)
    assert not np.asarray(got[held:], np.float32).any()


def walk_written_out(sizes, rows, tile):
    """The (tile, group, first row, row after the last) of every pair of
    a row tile and a group that share rows, in row order."""
    pairs, start = [], 0
    for group, size in enumerate(sizes):
        if size:
            for at in range(start // tile, (start + size - 1) // tile + 1):
                pairs.append((at, group, start, start + size))
        start += size
    return pairs


@pytest.mark.parametrize("case", CASES)
def test_the_table_is_the_walk_and_the_tiles_past_it_get_zeros_and_nothing_else(case):
    """Every (tile, group) pair once, in row order; then a step with an
    empty range of rows for each tile no group reaches; the rest of the
    grid idle on the last tile. A step that multiplies nothing names the
    last group that has rows, so it fetches no weight."""
    rows, _, _, sizes, _ = CASES[case]
    tile = SMALL[0]
    tiles, groups = -(-rows // tile), len(sizes)
    steps = tiles + groups - 1
    table = np.asarray(gmm.visits(jnp.asarray(sizes, jnp.int32), rows, tile)).reshape(6, steps)
    at, group, low, high, first, read = table
    pairs = walk_written_out(sizes, rows, tile)
    assert len(pairs) <= steps
    assert list(zip(at, group, low, high))[:len(pairs)] == pairs
    touched = -(-sum(sizes) // tile)
    rest = slice(len(pairs), None)
    assert (low[rest] == high[rest]).all()
    assert list(at[rest]) == [min(touched + i, tiles - 1) for i in range(steps - len(pairs))]
    assert (group[rest] == (max(g for g, n in enumerate(sizes) if n) if any(sizes) else 0)).all()
    # a tile's first step, and no other, clears what its group does not own
    assert list(first) == [int(i == 0 or at[i] != at[i - 1]) for i in range(steps)]
    assert set(at[first == 1]) == set(range(tiles))
    # a step that multiplies reads its own tile's rows, every other one the last that was read
    assert list(read[:len(pairs)]) == list(at[:len(pairs)])
    assert (read[rest] == max(touched - 1, 0)).all()


@pytest.mark.parametrize("shape,why", [
    ((256, 5120, 3072, 40, 2), "a tile or fewer rows: `expert_matvec`'s"),
    ((8192, 5120, 3072, 0, 2), "no group"),
    ((8192, 5120, 3072, 32, 1), "no tile for one-byte operands"),
    ((8192, 5128, 3072, 32, 2), "K off the sublane tile"),
    ((8192, 2**22, 3072, 32, 2), "a K of which no lane tile of columns fits"),
    ((8192, 2**19, 1856, 8, 2), "an N off the lane tile that does not fit whole"),
])
def test_no_plan(shape, why):
    assert gmm.plan(*shape) is None, why


def test_out_by_in_takes_an_n_off_the_lane_tile_whole():
    assert gmm.plan(3072, 2688, 1856, 8, 2, out_major=True)[:3] == (512, 128, 1856)


@pytest.mark.parametrize("shape,columns", [
    ((8192, 5120, 3072, 32), 1024),     # dots3's gate-up: three sweeps
    ((8192, 1536, 5120, 32), 2560),     # its down
    ((4096, 6144, 4096, 16), 1024),     # GLM-5.2's
    ((16384, 2048, 1536, 128), 1536),   # SDAR's: one sweep
    ((3072, 1856, 2688, 8), 2688),      # Nemotron's down, K off the lane tile
])
def test_the_plan_takes_the_most_columns_that_fit(shape, columns):
    taken = gmm.plan(*shape, 2)
    assert taken[:3] == (gmm.TILE_ROWS, gmm.BLOCK_ROWS, columns)
    assert taken.vmem_bytes <= gmm.VMEM_BLOCK_BUDGET
    assert taken.vmem_bytes == gmm.vmem_bytes(*taken[:2], shape[1], columns, 2)


def test_a_rung_of_fewer_rows_than_a_tile_is_one_tile_of_whole_blocks(monkeypatch):
    assert gmm.plan(300, 64, 256, 4, 4)[:2] == (384, 128)
    tiles(monkeypatch, SMALL)
    assert gmm.plan(300, 64, 256, 4, 4)[:3] == (128, 32, 128)


# (rows, K, N, groups) of the served prefills' lowest rung, gate-up or up
SERVED = {
    "deepseek-v2": (3072, 5120, 3072, 40), "solar-open2": (8192, 4096, 2560, 40),
    "k-exaone": (8192, 6144, 4096, 16), "ling-flash": (8192, 2560, 1536, 64),
    "glm-5.2": (4096, 6144, 4096, 16), "sdar": (16384, 2048, 1536, 128),
    "dots3-note-prev": (8192, 5120, 3072, 32),
}


@pytest.mark.parametrize("name", SERVED)
def test_the_route_is_the_backend_the_dtype_and_the_shape(monkeypatch, name):
    shape = SERVED[name]
    assert gmm.route(*shape, jnp.bfloat16) == "xla"  # off a TPU
    for backend, form in (("tpu", "kernel"), ("gpu", "xla"), ("cpu", "xla")):
        monkeypatch.setattr(jax, "default_backend", lambda backend=backend: backend)
        assert gmm.route(*shape, jnp.bfloat16) == form
        assert gmm.route(*shape, jnp.float16) == "xla"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert gmm.route(*shape, jnp.float32) == "kernel"
    assert gmm.route(*shape, jnp.float8_e4m3fn) == "xla"
    assert gmm.route(moe.ROW_TILE, *shape[1:], jnp.bfloat16) == "xla"  # a decode step's


def test_every_shape_with_a_plan_goes_whatever_the_rows_a_group(monkeypatch):
    """No rule on the rows a group: the chip's timings gave the kernel
    every rung it has a plan for, LongCat-Flash's second (8 groups, 512
    rows) and Nemotron's out by in among them."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert gmm.route(512, 6144, 4096, 8, jnp.bfloat16) == gmm.route(
        512, 2048, 6144, 8, jnp.bfloat16) == "kernel"
    assert gmm.route(3072, 2688, 1856, 8, jnp.bfloat16, out_major=True) == "kernel"
    assert gmm.route(3072, 1856, 2688, 8, jnp.bfloat16) == "kernel"
    assert gmm.route(8192, 5120, 3072, 256, jnp.bfloat16) == "kernel"  # 32 rows a group


@pytest.mark.parametrize("name,tokens,how", [
    ("deepseek-v2-ep4-5l", 2048, "kernel"), ("solar-open2-ep8-4l", 8192, "kernel"),
    ("k-exaone-ep8-5l", 8192, "kernel"), ("ling-flash-ep8-7l", 8192, "kernel"),
    ("nemotron3-nano-ep16-52l", 8192, "kernel"), ("glm-5.2-ep16-5l", 8192, "kernel"),
    ("sdar-30b-a3b-pp8-6l", 2048, "kernel"), ("dots3-note-prev-ep8-5l", 8192, "kernel"),
    ("longcat-flash-chat-ep64-4l", 1024, "xla"),
])
def test_the_served_models_prefill_route(monkeypatch, name, tokens, how):
    """`moe.prefill_route` of a call's tokens at the published widths: a
    TPU's for eight of the nine (LongCat-Flash's lowest rung is a tile:
    its ladder keeps `ragged_dot`), `ragged_dot` everywhere else."""
    cfg = get_config(name)
    k = getattr(cfg, "num_experts_per_tok", None) or cfg.moe_topk
    width = getattr(cfg, "moe_intermediate_size", None) or cfg.expert_ffn_hidden_size
    experts = next(getattr(cfg, n) for n in ("router_width", "n_routed_experts", "num_experts")
                   if hasattr(cfg, n))
    shape = (tokens, k, len(cfg.held_experts), experts, cfg.hidden_size, width, jnp.bfloat16)
    gate = not name.startswith("nemotron")
    assert moe.prefill_route(*shape, with_gate=gate) == "xla"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert moe.prefill_route(*shape, with_gate=gate) == how


def interpreted(monkeypatch, caps=SMALL):
    """The grouped products routed as a TPU routes them and the kernel
    interpreted under small tiles: `grouped_rows` reads both names from
    the module while a program is traced; no option of the program."""
    tiles(monkeypatch, caps)
    monkeypatch.setattr(gmm, "route", lambda rows, k, n, groups, dtype, out_major=False: (
        "kernel" if gmm.plan(rows, k, n, groups, jnp.dtype(dtype).itemsize, out_major)
        else "xla"))
    monkeypatch.setattr(gmm, "grouped_matmul", kernel)


@pytest.mark.parametrize("lowest,forms", [
    (512, ["kernel", "xla", "xla"]), (1024, ["kernel", "kernel", "xla"]), (256, ["xla"] * 3)])
def test_a_rung_above_the_lowest_keeps_ragged_dot(monkeypatch, lowest, forms):
    """`grouped_rows(lowest)`: the kernel on the ladder's lowest rung,
    `ragged_dot` above, an entry a call either way; a lowest rung of a
    tile or less leaves the whole ladder to `ragged_dot`, and a tile or
    fewer rows leave no entry."""
    interpreted(monkeypatch)
    grouped = gmm.grouped_rows(lowest)
    w = jnp.ones((4, 64, 128), jnp.float32)
    sizes = jnp.asarray([100, 50, 0, 70], jnp.int32)
    with attention.route_log() as routes:
        for rows in (512, 1024, 2048, 256):
            out = grouped(jnp.ones((rows, 64), jnp.float32), w, sizes)
            np.testing.assert_array_equal(np.asarray(out[:220]), 64.0)
    assert routes == [
        f"gmm-{form} {rows}x64x128 g4 f32" for form, rows in zip(forms, (512, 1024, 2048))]


def test_operands_of_two_dtypes_keep_ragged_dot(monkeypatch):
    interpreted(monkeypatch)
    with attention.route_log() as routes:
        gmm.grouped_rows(512)(
            jnp.ones((512, 64), jnp.bfloat16), jnp.ones((4, 64, 128), jnp.float32),
            jnp.asarray([100, 50, 0, 70], jnp.int32))
    assert routes == ["gmm-xla 512x64x128 g4 bf16"]


def test_a_call_without_a_plan_raises():
    x, w = jnp.zeros((200, 128), jnp.bfloat16), jnp.zeros((4, 128, 256), jnp.bfloat16)
    with pytest.raises(ValueError, match="no plan"):
        gmm.grouped_matmul(x, w, jnp.zeros((4,), jnp.int32), interpret=True)


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


def tiny(name: str):
    """(module, configuration, tokens, the rungs its expert layers run
    over, (rows, K, N) of a rung's first product as the log writes it)."""
    if name == "tiny-longcat-flash":  # one block of 512 tokens: a ladder above a tile
        cfg = dataclasses.replace(get_config(name), expert_block=512, prefill_part=512)
        return longcat_flash, cfg, 512, moe.row_ladder(512 * 3, 4, 12), "64x64"
    if name == "tiny-nemotron3-nano":
        return nemotron_h, get_config(name), 1024, moe.row_ladder(1024 * 3, 2, 16), "64x24"
    return deepseek_v2, get_config(name), 512, moe.row_ladder(512 * 3, 4, 16), "64x64"


@pytest.mark.parametrize("name", ["tiny-deepseek-v2", "tiny-nemotron3-nano", "tiny-longcat-flash"])
def test_a_models_prefill_through_the_kernel_is_its_prefill_through_ragged_dot(name, monkeypatch):
    """The whole prefill traced twice under functions of its own (no
    trace of either is kept where another test could meet it): on
    `ragged_dot`, as the CPU runs it, and with the lowest rung of
    every expert layer in the interpreted kernel (a SwiGLU's two
    products; Nemotron's up stored out by in, of a stack inside a
    scanned run; LongCat's block beside its identities). The rungs
    above stay `ragged_dot` under the same `lax.switch`."""
    module, cfg, tokens, ladder, first = tiny(name)
    params = module.init_params(cfg, jax.random.key(0), jnp.float32)
    ids = jax.random.randint(jax.random.key(1), (tokens,), 0, cfg.vocab_size)
    run = lambda: jax.jit(lambda w, i: module.prefill.__wrapped__(
        cfg, w, i, cache_len=tokens + 8, collect=True))
    with attention.route_log() as routes:
        want = run()(params, ids)
    mine = [r for r in routes if r.startswith("gmm-")]
    assert mine and all(r.startswith("gmm-xla ") for r in mine)
    interpreted(monkeypatch)
    with attention.route_log() as routes:
        jaxpr = jax.make_jaxpr(run())(params, ids)
    entries = [r for r in routes if r.startswith("gmm-")]
    assert [r.replace("gmm-kernel", "gmm-xla") for r in entries] == mine
    assert len(ladder) >= 3
    for rung in ladder:
        form = "kernel" if rung == ladder[0] else "xla"
        assert any(r.startswith(f"gmm-{form} {rung}x{first}") for r in entries), (rung, entries)
        assert not any(r.startswith(f"gmm-{'xla' if form == 'kernel' else 'kernel'} {rung}x")
                       for r in entries)
    assert _calls(jaxpr.jaxpr) == sum(r.startswith("gmm-kernel") for r in entries)
    got = run()(params, ids)
    np.testing.assert_array_equal(np.asarray(got.chosen), np.asarray(want.chosen))
    np.testing.assert_allclose(np.asarray(got.logits), np.asarray(want.logits), atol=2e-5)


@pytest.mark.parametrize("module,name", [
    (deepseek_v2, "tiny-deepseek-v2"), (solar_open2, "tiny-solar-open2"),
    (k_exaone, "tiny-k-exaone"), (ling_flash, "tiny-ling-flash"),
    (nemotron_h, "tiny-nemotron3-nano"), (sdar, "tiny-sdar")])
def test_the_collecting_prefill_is_the_served_program(module, name):
    """The six models whose parity scripts hold the served ids equal to
    the collecting programs': the prefill keeps the chosen experts
    whatever `collect`, so both are one program, text for text. (On a
    v5e the compiler rounds its own products by the VMEM it is left, a
    kernel's scoped request shrinks that, and two programs that differ
    by an output parted by 5e-3 at DeepSeek's logits: PERF.md §6, PR 64.)"""
    cfg = get_config(name)
    params = jax.eval_shape(lambda: module.init_params(cfg, jax.random.key(0), jnp.bfloat16))
    ids = jax.ShapeDtypeStruct((96,), jnp.int32)
    served, collecting = (
        module.prefill.lower(cfg, params, ids, cache_len=128, collect=collect)
        for collect in (False, True))
    assert served.as_text() == collecting.as_text()
    assert served.out_info.chosen is not None
