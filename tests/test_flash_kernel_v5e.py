"""The flash-attention kernel at the shapes the served models give it,
compiled for a TPU v5e that is described and not attached: what the
chip's compiler refuses (VMEM, layouts) shows here at no chip time. No
result and no timing comes from this file. All such compiles live in
this one file (one process loads the TPU's library; see the fixture)."""

import functools
import os
import re

import chip_smoke
import jax
import jax.numpy as jnp
import pytest

from comfyui_distributed_tpu.ops import attention as attn
from comfyui_distributed_tpu.ops import qk_norm_rope
from test_causal_attention import _eqns

# Every served shape is compiled on the kernel, the ones the shape rule
# leaves to XLA too: the rule rests on both routes' times, which
# `chip_smoke.py`'s attention leg takes on the chip.
SHAPES = list(chip_smoke.SERVED_SHAPES)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def grouped_entries(tokens, k, held, experts, hidden, width, gate=True):
    """An expert layer's entries in the route log on a TPU, a rung of its
    ladder after the other (PR 64): the lowest rung's two grouped
    products in `ops/grouped_matmul` where the shape rule takes them and
    the lowest is more than a tile (a tile or fewer rows leave no entry:
    `expert_matvec`'s or XLA's), the rungs above on `ragged_dot`."""
    from comfyui_distributed_tpu.models import moe
    from comfyui_distributed_tpu.ops import grouped_matmul as gmm

    ladder = moe.row_ladder(tokens * k, held, experts)
    first = (hidden, 2 * width if gate else width)
    entries = []
    for rung in ladder:
        if rung <= moe.ROW_TILE:
            continue
        for (rows_k, n), out_major in ((first, not gate), ((width, hidden), False)):
            mine = moe.ROW_TILE < ladder[0] == rung
            form = gmm.route(rung, rows_k, n, held, jnp.bfloat16, out_major) if mine else "xla"
            entries.append(f"gmm-{form} {rung}x{rows_k}x{n} g{held} bf16"
                           + " out-major" * out_major)
    return entries


def test_the_served_shapes_that_reach_the_kernel():
    assert [
        label for label, q_shape, m in SHAPES if attn.kernel_wins(q_shape[1], m)
    ] == [
        "sd15 self 64x64", "sd15 self 32x32", "sd15 self 16x16", "sd15 vae mid 64x64",
        "sdxl tile self 36x36", "sdxl tile vae mid 72x72",
        "flux joint 4608", "flux vae mid 128x128",
    ]


@pytest.mark.parametrize("name,dtype", [("bf16", jnp.bfloat16), ("f32", jnp.float32)])
@pytest.mark.parametrize(
    "q_shape,m", [s[1:] for s in SHAPES], ids=[s[0] for s in SHAPES]
)
def test_kernel_compiles_for_v5e_at_served_shape(one_chip, q_shape, m, name, dtype):
    b, n, h, d = q_shape
    q = jax.ShapeDtypeStruct(q_shape, dtype, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, m, h, d), dtype, sharding=one_chip)
    fn = jax.jit(functools.partial(attn.dot_product_attention, force_flash=True))
    with attn.route_log() as routes:
        compiled = fn.lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()
    n_pad, m_pad, block_q, block_k = attn.flash_plan(
        n, m, d + -d % 128, jnp.dtype(dtype).itemsize
    )
    pad = "" if (n_pad, m_pad) == (n, m) else f" pad{n_pad}x{m_pad}"
    inplace = "" if d % 128 else " inplace"
    assert routes == [f"flash {n}x{m}x{d}{pad} bq{block_q} bk{block_k} {name}{inplace}"]


def test_kernel_compiles_for_v5e_under_the_tile_axis_vmap(one_chip):
    """The scan tier reaches the kernel under `jax.vmap` over 8 tiles
    (`ops/upscale._scan_tiles`), CFG's batch of 2 inside: the batching
    rule puts the tile axis in front of the grid, one kernel as before."""
    q = jax.ShapeDtypeStruct((8, 2, 1296, 10, 64), jnp.bfloat16, sharding=one_chip)
    fn = jax.jit(jax.vmap(functools.partial(attn.dot_product_attention, force_flash=True)))
    text = fn.lower(q, q, q).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1


SHORT = [s for s in SHAPES if s[0].startswith("sdxl tile") and "vae" not in s[0]]


@pytest.mark.parametrize("tiled", [False, True], ids=["batch 16", "vmap over 8 tiles of batch 2"])
@pytest.mark.parametrize("q_shape,m", [s[1:] for s in SHORT], ids=[s[0] for s in SHORT])
def test_short_attention_compiles_for_v5e_at_sdxl_s_tile_shapes(one_chip, q_shape, m, tiled):
    """`ops/short_attention.py` (PR 60) at SDXL's four attention shapes,
    the one its rule leaves to XLA too (the rule rests on both routes'
    times), alone and as the scan tier reaches it: one kernel, and
    nothing padded, transposed or copied around it (blocks that reach
    past an array's end are the kernel's to mask)."""
    from comfyui_distributed_tpu.ops import short_attention

    b, n, h, d = q_shape
    lead = (8, b // 8) if tiled else (b,)
    q = jax.ShapeDtypeStruct((*lead, n, h, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((*lead, m, h, d), jnp.bfloat16, sharding=one_chip)
    fn = attn.short_attend
    with attn.route_log() as routes:
        compiled = jax.jit(jax.vmap(fn) if tiled else fn).lower(q, kv, kv).compile()
    assert routes == [short_attention.entry(n, m, h, jnp.bfloat16)]
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "short_attention" in text and " pad(" not in text and " transpose(" not in text


def test_route_log_entries():
    """What the sampler and upscale nodes write into their spans as
    `attention`: the blocks and operand dtype for a flash call, the
    padded lengths too where a length was padded, `inplace` where the
    kernel reads the heads where the caller left them (a width that is
    a multiple of 128), `xla` entries as ever."""
    flux = jax.ShapeDtypeStruct((1, 4608, 24, 128), jnp.bfloat16)
    sd15 = jax.ShapeDtypeStruct((2, 4096, 8, 40), jnp.bfloat16)
    text = jax.ShapeDtypeStruct((2, 77, 8, 40), jnp.bfloat16)
    sdxl = jax.ShapeDtypeStruct((16, 1296, 10, 64), jnp.bfloat16)
    vae = jax.ShapeDtypeStruct((8, 5184, 1, 512), jnp.bfloat16)
    flash = functools.partial(attn.dot_product_attention, force_flash=True)
    with attn.route_log() as routes:
        jax.eval_shape(flash, flux, flux, flux)
        jax.eval_shape(flash, sd15, sd15, sd15)
        jax.eval_shape(attn.dot_product_attention, sd15, text, text)
        jax.eval_shape(flash, sdxl, sdxl, sdxl)
        jax.eval_shape(flash, vae, vae, vae)
    assert routes == [
        "flash 4608x4608x128 bq512 bk1536 bf16 inplace",
        "flash 4096x4096x40 bq512 bk1024 bf16",
        "xla 4096x77x40",
        "flash 1296x1296x64 pad1296x1408 bq432 bk1408 bf16",
        "flash 5184x5184x512 pad5280x5376 bq480 bk896 bf16 inplace",
    ]


CAUSAL_ENTRIES = [
    "flash-causal 8192x8192x128/128 g8 bq512 bk1024 bf16 inplace blocks72/128",
    # the shape rule leaves this one to XLA (a band of 128 is all edge); it compiles all the same
    "flash-causal 8192x8192x128/128 w128 g8 bq512 bk512 bf16 inplace blocks31/256",
    "flash-causal 2048x2048x128/128 g1 bq512 bk1024 bf16 inplace blocks6/8",
    "flash-causal 2048x2048x192/128 g1 bq512 bk1024 bf16 inplace blocks6/8",
    "flash-causal 8192x8192x192/128 g1 bq512 bk1024 bf16 inplace blocks72/128",
    # Nemotron-3-Nano's 32 : 2: sixteen query heads read one key head where it lies
    "flash-causal 8192x8192x128/128 g16 bq512 bk1024 bf16 inplace blocks72/128",
    # granite-4.0-h-micro's first part: 64-wide heads padded to the lane tile and folded
    "flash-causal 8192x8192x64/64 g4 bq512 bk1024 bf16 blocks72/128",
]


@pytest.mark.parametrize(
    "shape,entry", list(zip(chip_smoke.CAUSAL_SHAPES, CAUSAL_ENTRIES)),
    ids=[s[0] for s in chip_smoke.CAUSAL_SHAPES])
def test_causal_kernel_compiles_for_v5e_at_the_prefills_shapes(one_chip, shape, entry):
    """The four language models' causal calls on the kernel under its
    mask: key heads read where they lie, q and k of DeepSeek-V2's 192
    padded where they lie beside a v of 128, the band's clamped index
    maps. The route entry is the one the traced request reports."""
    _, q_shape, kv_heads, v_width, window = shape
    b, n, h, d = q_shape
    place = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=one_chip)
    fn = jax.jit(functools.partial(attn.causal_attention, window=window, force_flash=True))
    with attn.route_log() as routes:
        compiled = fn.lower(
            place(*q_shape), place(b, n, kv_heads, d), place(b, n, kv_heads, v_width)).compile()
    assert routes == [entry]
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "%flash_attention_causal" in text


def _kernel_refs(fn, *operands):
    """(blocks, scratch): the avals of the refs the one kernel of `fn` takes."""
    (call,) = [
        e for e in _eqns(jax.make_jaxpr(fn)(*operands).jaxpr) if e.primitive.name == "pallas_call"]
    refs = [v.aval for v in call.params["jaxpr"].invars]
    held = call.params["grid_mapping"].num_scratch_operands
    return refs[:-held], refs[-held:]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_flash_vmem_bytes_bounds_the_blocks_and_scratch_of_every_served_call(dtype):
    """What `flash_plan` fits to `VMEM_BUDGET` counts at least what the
    call that the tests above compile asks of VMEM: q, k, v and output
    blocks twice (the pipeline double-buffers them) and the carried
    scratch, the accumulator beside a running max and sum that are a lane
    tile wide each (PR 51), at every served and causal shape."""
    size = lambda avals: sum(a.size * a.dtype.itemsize for a in avals)
    place = lambda *dims: jax.ShapeDtypeStruct(dims, dtype)
    cases = [
        (label, attn.flash_attention, (b, n, h, d), (b, m, h, d), (b, m, h, d), False, None)
        for label, (b, n, h, d), m in SHAPES
    ] + [
        (label, functools.partial(attn.flash_attention, causal=True, window=window),
         (b, n, h, d), (b, n, kv_heads, d), (b, n, kv_heads, v_width), True, window)
        for label, (b, n, h, d), kv_heads, v_width, window in chip_smoke.CAUSAL_SHAPES
    ]
    for label, fn, q, k, v, causal, window in cases:
        blocks, scratch = _kernel_refs(fn, place(*q), place(*k), place(*v))
        width = max(x + -x % 128 for x in (q[3], v[3]))
        itemsize = jnp.dtype(dtype).itemsize
        _, _, block_q, block_k = attn.flash_plan(
            q[1], k[1], width, itemsize, causal=causal, window=window)
        assert [a.shape for a in scratch] == [
            (block_q, v[3] + -v[3] % 128), (block_q, 128), (block_q, 128)], label
        assert all(a.dtype == jnp.float32 for a in scratch), label
        counted = attn.flash_vmem_bytes(block_q, block_k, width, itemsize)
        assert 2 * size(blocks) + size(scratch) <= counted <= attn.VMEM_BUDGET, (label, counted)


def test_ouro_prefill_attends_in_the_causal_kernel(one_chip, monkeypatch):
    """Ouro-2.6B's whole prefill at the cell's 2,048 tokens, compiled as
    a TPU routes it: one kernel in the layer body of the two scans, and
    no temporary the size of the cache. The kernel takes keys and values
    token-major and the cache is head-major, so left alone the compiler
    orders the cache's axes by what writes it and copies all 3.3 GB at
    the program's end (a job then waits for memory: 52 ms of `job_s.p50`
    on the chip, PERF.md §6, PR 43); `prefill` holds the cache's layout
    as `layer_cached` does."""
    from comfyui_distributed_tpu.models import ouro

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = ouro.OuroConfig()
    place = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    params = jax.tree.map(place, jax.eval_shape(
        lambda: ouro.init_params(cfg, jax.random.key(0), jnp.bfloat16)))
    with attn.route_log() as routes:
        compiled = ouro.prefill.lower(
            cfg, params, jax.ShapeDtypeStruct((2048,), jnp.int32, sharding=one_chip),
            cache_len=2080,  # a length of this test's own: the route is read while tracing
        ).compile()
    assert routes == [CAUSAL_ENTRIES[2]]
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


# (tokens, width of the projection, lane the heads start at): q and k of a
# FLUX single block's fused linear, k of a double block's image stream, q
# of its text stream
NORM_ROPE_SHAPES = [(4608, 21504, 0), (4608, 21504, 3072), (4096, 9216, 3072), (512, 9216, 0)]


@pytest.mark.parametrize("n,width,offset", NORM_ROPE_SHAPES)
def test_norm_rope_compiles_for_v5e_at_flux_shapes(one_chip, n, width, offset):
    x = jax.ShapeDtypeStruct((1, n, width), jnp.bfloat16, sharding=one_chip)
    scale = jax.ShapeDtypeStruct((128,), jnp.float32, sharding=one_chip)
    freqs = jax.ShapeDtypeStruct((n, 64, 2), jnp.float32, sharding=one_chip)
    fn = jax.jit(functools.partial(qk_norm_rope.norm_rope, heads=24, offset=offset))
    compiled = fn.lower(x, scale, freqs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


def test_a_flux_single_block_moves_no_tensor_between_its_kernels(one_chip, monkeypatch):
    """The block at FLUX.1-dev's widths, compiled as a TPU routes it: q
    and k go from the fused linear through `qk_norm_rope` into
    `flash_attention`, whose output the next linear reads, and no `copy`,
    `transpose` or `reshape` of a whole [4608, 3072] tensor is left
    between them (this pattern finds ten in the parent of PR 35: PERF.md §6)."""
    import re

    from comfyui_distributed_tpu.models import mmdit

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    dim, heads, n = 3072, 24, 4608
    block = mmdit._SingleBlock(heads=heads, mlp_width=4 * dim, dtype=jnp.bfloat16)
    args = (
        jax.ShapeDtypeStruct((1, n, dim), jnp.bfloat16),
        jax.ShapeDtypeStruct((1, dim), jnp.bfloat16),
        jax.ShapeDtypeStruct((n, dim // heads // 2, 2), jnp.float32),
    )
    params = jax.eval_shape(lambda *a: block.init(jax.random.key(0), *a), *args)
    place = lambda s, dtype: jax.ShapeDtypeStruct(s.shape, dtype, sharding=one_chip)
    params = jax.tree.map(lambda s: place(s, jnp.bfloat16), params)
    with attn.route_log() as routes:
        text = jax.jit(block.apply).lower(
            params, *(place(a, a.dtype) for a in args)
        ).compile().as_text()
    assert routes == ["flash 4608x4608x128 bq512 bk1536 bf16 inplace"]
    entry = text[text.index("ENTRY"):]
    assert len(re.findall(r'custom_call_target="tpu_custom_call"', entry)) == 3
    assert entry.count("%qk_norm_rope") >= 2 and "%flash_attention" in entry
    moved = re.findall(
        r"= (?:bf16|f32)\[(?:1,)?4608,(?:3072|24,128|24,64,2)\]\S* (?:copy|transpose|reshape)\(", entry
    )
    assert not moved, moved


# Ouro-2.6B's carried cache at the benchmark cell's lengths (2,048 + 64
# positions): [passes, layers, keys | values, heads, positions, head_dim]
OURO_CACHE = (4, 48, 2, 16, 2112, 128)
# an instruction whose result is one (pass, layer) slot of it, or one half
SLOT_SHAPED = r"= (?:bf16|f32)\[(?:1,1,)?(?:2,)?16,2112,128\]\S* ([\w-]+)\("


@pytest.mark.parametrize("name,dtype", [("bf16", jnp.bfloat16), ("f32", jnp.float32)])
def test_decode_attention_compiles_for_v5e_over_the_whole_cache(one_chip, name, dtype):
    """The single-query kernel takes the slot out of the cache it is
    given: no temporary, so no copy of the cache in front of the call."""
    from comfyui_distributed_tpu.ops import decode_attention as da

    q = jax.ShapeDtypeStruct((16, 128), dtype, sharding=one_chip)
    cache = jax.ShapeDtypeStruct(OURO_CACHE, dtype, sharding=one_chip)
    index = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    fn = jax.jit(lambda q, cache, t, l, p: da.decode_attention(q, cache, (t, l), p))
    compiled = fn.lower(q, cache, index, index, index).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


# the route is chosen while the program is traced, and `jax.jit` keeps a
# trace by its static arguments: each case takes a step count of its own
@pytest.mark.parametrize("backend,steps,entry,calls", [
    ("tpu", 64, "decode-kernel 16x2112x128 h1 bf16", 1),
    ("cpu", 2, "decode-xla 16x2112x128", 0),  # the einsum form: what `SLOT_SHAPED` is there to find
])
def test_ouro_decode_reads_the_slot_where_it_lies(
        one_chip, monkeypatch, loop_body_ops, backend, steps, entry, calls):
    """The whole decode at the published sizes (64 steps, the cell's),
    compiled as a TPU routes it: one kernel in the layer body, the cache
    carried in place (a cache-sized temporary would be a copy around the
    call) and no instruction left whose result is a slot."""
    import re

    from comfyui_distributed_tpu.models import ouro

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg = ouro.OuroConfig()
    place = lambda s, dtype=None: jax.ShapeDtypeStruct(
        s.shape, dtype or s.dtype, sharding=one_chip)
    params = jax.tree.map(
        lambda s: place(s, jnp.bfloat16),
        jax.eval_shape(lambda: ouro.init_params(cfg, jax.random.key(0), jnp.bfloat16)))
    scalar = lambda dtype: jax.ShapeDtypeStruct((), dtype, sharding=one_chip)
    with attn.route_log() as routes:
        compiled = ouro.decode.lower(
            cfg, params, jax.ShapeDtypeStruct(OURO_CACHE, jnp.bfloat16, sharding=one_chip),
            jax.ShapeDtypeStruct((cfg.vocab_size,), jnp.float32, sharding=one_chip),
            scalar(jnp.int32), place(jax.eval_shape(lambda: jax.random.key(0))),
            scalar(jnp.float32), steps=steps,
        ).compile()
    assert routes == [entry]
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == calls
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20
    assert bool(re.findall(SLOT_SHAPED, text)) == (backend == "cpu")
    if backend == "tpu":
        # what the layer body launches (`scripts/loop_body_ops.py`; 26 before PR 62): the
        # four products, nine of the norms and residual adds, silu x up and the nine that
        # turn the qkv row into the kernel's operands and its result into w_o's
        body = loop_body_ops.body_rows(text)
        costed = loop_body_ops.costed(body)
        assert len(costed) <= 23, [row["name"] for row in costed]
        # no scanned index sliced out, no stacked slot, no one-row matrix before the split
        assert not {"s32[1]", "s32[2]", "bf16[1,11264]"} & {row["result"] for row in body}


def test_solar_decode_carries_its_state_tree_in_place(one_chip):
    """Solar-Open2's whole decode at the served share's sizes (256 steps
    over 8,448 positions): the tree of keys and values (34.6 MB), float32
    KDA states (12.6 MB) and convolution tails is donated, carried through
    the loop and written where it lies. What the loop needs beside it is
    12 MB; a temporary the size of either part would be a copy of it a
    step."""
    from comfyui_distributed_tpu.models import solar_open2
    from comfyui_distributed_tpu.models.registry import get_config

    cfg = get_config("solar-open2-ep8-4l")
    place = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    params = jax.tree.map(place, jax.eval_shape(
        lambda: solar_open2.init_params(cfg, jax.random.key(0), jnp.bfloat16)))
    state = jax.tree.map(place, solar_open2.state_shapes(cfg, 8448, jnp.bfloat16))
    scalar = lambda dtype: jax.ShapeDtypeStruct((), dtype, sharding=one_chip)
    compiled = solar_open2.decode.lower(
        cfg, params, state,
        jax.ShapeDtypeStruct((cfg.vocab_held,), jnp.float32, sharding=one_chip),
        scalar(jnp.int32), place(jax.eval_shape(lambda: jax.random.key(0))),
        scalar(jnp.float32), steps=256,
    ).compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 24 * 2**20
    # the donated tree is the output's: nothing of its size is allocated anew
    assert memory.alias_size_in_bytes >= 34_603_008 + 13_025_280


def test_k_exaone_drafting_decode_carries_rings_and_caches_in_place(one_chip):
    """K-EXAONE's self-speculative decode at the served share's sizes (384
    ids over 8,576 positions): a `while` loop (the steps it takes are not
    known when it is built) that carries the donated tree of two growing
    caches (70.3 MB) and four rings of 136 entries (2.2 MB) and writes a
    step's two positions where they lie. What the loop needs beside the
    tree is 13 MB; a temporary the size of the caches would be a copy of
    them a step."""
    from comfyui_distributed_tpu.models import k_exaone
    from comfyui_distributed_tpu.models.registry import get_config

    cfg = get_config("k-exaone-ep8-5l")
    place = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    params = jax.tree.map(place, jax.eval_shape(
        lambda: k_exaone.init_params(cfg, jax.random.key(0), jnp.bfloat16)))
    state = jax.tree.map(place, k_exaone.state_shapes(cfg, 8576, jnp.bfloat16))
    scalar = lambda dtype: jax.ShapeDtypeStruct((), dtype, sharding=one_chip)
    compiled = k_exaone.decode.lower(
        cfg, params, state,
        jax.ShapeDtypeStruct((cfg.vocab_held,), jnp.float32, sharding=one_chip),
        scalar(jnp.int32), place(jax.eval_shape(lambda: jax.random.key(0))),
        scalar(jnp.float32), steps=384, draft_tokens=1,
    ).compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 24 * 2**20
    # the donated tree is the output's: nothing of its size is allocated anew
    assert memory.alias_size_in_bytes >= 8576 * 8192 + 4 * 136 * 4096
    text = compiled.as_text()
    assert " while(" in text and "conditional(" not in text  # one rung a step: no branch


def test_ling_flash_drafting_decode_keeps_or_drops_a_draft_without_a_copy_of_its_state(
        one_chip, monkeypatch):
    """Ling-3.0-flash's self-speculative decode at the served share's sizes
    (1,024 ids over 9,216 positions), routed as a TPU routes it: a `while`
    loop that carries the donated tree of two latent caches (21.2 MB) and
    six KDA layers' states and tails in two slots each (26.05 MB); a
    step's two grouped products a sparse layer and the MTP module's in
    the `expert_matvec` kernel (14 calls), and what the loop needs beside
    the tree stays under the size of the tree."""
    from comfyui_distributed_tpu.models import ling_flash
    from comfyui_distributed_tpu.models.registry import get_config

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config("ling-flash-ep8-7l")
    place = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    params = jax.tree.map(place, jax.eval_shape(
        lambda: ling_flash.init_params(cfg, jax.random.key(0), jnp.bfloat16)))
    state = jax.tree.map(place, ling_flash.state_shapes(cfg, 9216, jnp.bfloat16))
    scalar = lambda dtype: jax.ShapeDtypeStruct((), dtype, sharding=one_chip)
    compiled = ling_flash.decode.lower(
        cfg, params, state,
        jax.ShapeDtypeStruct((cfg.vocab_held,), jnp.float32, sharding=one_chip),
        scalar(jnp.int32), place(jax.eval_shape(lambda: jax.random.key(0))),
        scalar(jnp.float32), steps=1000, draft_tokens=1,  # a count of this test's own
    ).compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 44 * 2**20
    assert memory.alias_size_in_bytes >= 9216 * 2304 + 26_050_560
    text = compiled.as_text()
    assert " while(" in text and "conditional(" not in text  # one rung a step: no branch
    assert text.count('custom_call_target="tpu_custom_call"') == 14


@pytest.mark.parametrize("label,tokens,heads,d,chunk", chip_smoke.KDA_DELTA_SHAPES)
def test_kda_delta_compiles_for_v5e_at_the_prefills_shapes(
        one_chip, label, tokens, heads, d, chunk):
    """A KDA layer's delta rule over the cells' prompt (`ops/kda_delta`),
    at Ling-3.0-flash's and Solar-Open2's held heads, between the
    `[T, H d]` arrays a model's projections give and take: one kernel
    and no copy of an operand in front of it."""
    from comfyui_distributed_tpu.ops import kda_delta

    def call(q, k, v, g, beta, state):
        o, state = kda_delta.kda_delta(
            *(a.reshape(tokens, heads, d) for a in (q, k, v, g)), beta, state, chunk=chunk)
        return o.reshape(tokens, heads * d), state

    place = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = jax.jit(call).lower(
        *(place((tokens, heads * d), jnp.bfloat16) for _ in range(3)),
        place((tokens, heads * d), jnp.float32), place((tokens, heads), jnp.float32),
        place((heads, d, d), jnp.float32),
    ).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "%kda_delta" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


@pytest.mark.parametrize("label,tokens,heads,width,groups,n,chunk,block", [
    (*shape[:-1], block) for shape in chip_smoke.SSD_SHAPES for block in chip_smoke.SSD_SWEEP
    if block <= shape[2] // shape[4]])
def test_ssd_chunk_compiles_for_v5e_at_the_prefills_shapes(
        one_chip, label, tokens, heads, width, groups, n, chunk, block):
    """A Mamba-2 layer's chunked scan over a part of the cells' prompts
    (`ops/ssd_chunk`), at Nemotron-3-Nano's eight groups and chunks of
    128 and at granite-4.0-h-micro's one group and chunks of 256,
    between the `[T, H P]` array a model's convolution gives and the one
    its gated norm takes: one kernel, no copy of u in front of it, and
    beside it only the steps' running sums and the state turned round
    (megabytes: no `[chunks, H, Q, Q]` array of weights, 537 MB at
    granite's sizes). At every count of heads a grid step that
    `chip_smoke.ssd_row` times, the plan's among them: a slice that
    compiled at eight heads a step failed on the chip at two (PR 55)."""
    from comfyui_distributed_tpu.ops import ssd_chunk

    def call(u, b, c, step, a, state):
        y, state = ssd_chunk.ssd_chunk(
            u.reshape(tokens, heads, width), b.reshape(tokens, groups, n),
            c.reshape(tokens, groups, n), step, a, state, chunk=chunk, block=block)
        return y.reshape(tokens, heads * width), state

    place = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = jax.jit(call).lower(
        place((tokens, heads * width), jnp.bfloat16),
        *(place((tokens, groups * n), jnp.bfloat16) for _ in range(2)),
        place((tokens, heads), jnp.float32), place((heads,), jnp.float32),
        place((heads, width, n), jnp.float32),
    ).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "%ssd_chunk" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**20


def test_ling_flash_prefill_walks_its_six_kda_layers_in_the_kernel(one_chip, monkeypatch):
    """Ling-3.0-flash's whole prefill at the cell's 8,192 tokens, routed
    as a TPU routes it: the delta rule of each of the six KDA layers is
    one `kda_delta` call (the route log says so as it is traced), the
    MLA layer's attention the causal kernel; the grouped products are
    the compiler's own."""
    import re

    from comfyui_distributed_tpu.models import ling_flash
    from comfyui_distributed_tpu.models.registry import get_config

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config("ling-flash-ep8-7l")
    place = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    params = jax.tree.map(place, jax.eval_shape(
        lambda: ling_flash.init_params(cfg, jax.random.key(0), jnp.bfloat16)))
    with attn.route_log() as routes:
        compiled = ling_flash.prefill.lower(
            cfg, params, jax.ShapeDtypeStruct((8192,), jnp.int32, sharding=one_chip),
            cache_len=8200,  # a length of this test's own: the route is read while tracing
        ).compile()
    assert [r for r in routes if r.startswith("kda-")] == (
        ["kda-kernel 8192x32x128 c64 hb8 bf16"] * cfg.kda_layers)
    text = compiled.as_text()
    assert len(re.findall(r"%kda_delta[.\d]* = ", text)) == cfg.kda_layers
    assert "%flash_attention_causal" in text


@pytest.mark.parametrize(
    "label,tokens,k,held,experts,hidden,width,gated,stacked",
    [s for s in chip_smoke.PREFILL_EXPERT_SHAPES if s[0].split()[0] in ("deepseek-v2", "dots3-note-prev")],
    ids=["deepseek-v2", "dots3-note-prev"])
def test_an_expert_layers_lowest_rung_compiles_in_the_grouped_kernel_for_v5e(
        one_chip, monkeypatch, label, tokens, k, held, experts, hidden, width, gated, stacked):
    """`moe.expert_layer` over a prefill (DeepSeek-V2's 2,048 tokens, a
    part of dots3-note-prev's 8,192) at the published widths, routed as
    a TPU routes it (PR 64): under the ladder's `lax.switch` the lowest
    rung holds two `grouped_matmul` calls, the rungs above two
    `ragged-dot`s each, and the log says so; what a call's blocks take
    of VMEM twice over (the pipeline's two buffers) is under what its
    plan counts, and that under the budget the plan fits."""
    from comfyui_distributed_tpu.models import moe
    from comfyui_distributed_tpu.ops import grouped_matmul as gmm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    place = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=one_chip)
    p = {"w_g": place(hidden, experts), "experts": {
        "w_gate_up": place(held, hidden, 2 * width), "w_down": place(held, width, hidden)}}

    def route(logits):
        weights, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
        return ids, weights

    with attn.route_log() as routes:
        compiled = jax.jit(
            lambda p, x: moe.expert_layer(p, x, range(held), route)[0]
        ).lower(p, place(tokens, hidden)).compile()
    ladder = moe.row_ladder(tokens * k, held, experts)
    assert len(ladder) >= 3 and ladder[1] == 2 * ladder[0]
    assert routes == grouped_entries(tokens, k, held, experts, hidden, width)
    assert [r.split()[0] for r in routes] == ["gmm-kernel"] * 2 + ["gmm-xla"] * 2 * (len(ladder) - 1)
    text = compiled.as_text()
    assert len(re.findall(r"%grouped_matmul[.\d]* = ", text)) == 2
    assert len(re.findall(r"%ragged-dot-none[.\d]* = ", text)) == 2 * (len(ladder) - 1)
    for rung in ladder[:1]:
        for rows_k, n in ((hidden, 2 * width), (width, hidden)):
            taken = gmm.plan(rung, rows_k, n, held, 2)
            (call,) = [e for e in _eqns(jax.make_jaxpr(gmm.grouped_matmul)(
                jax.ShapeDtypeStruct((rung, rows_k), jnp.bfloat16),
                jax.ShapeDtypeStruct((held, rows_k, n), jnp.bfloat16),
                jax.ShapeDtypeStruct((held,), jnp.int32)).jaxpr) if e.primitive.name == "pallas_call"]
            blocks = [v.aval for v in call.params["jaxpr"].invars if len(v.aval.shape) == 2]
            assert [b.shape for b in blocks] == [
                (taken.tile, rows_k), (rows_k, taken.columns), (taken.tile, taken.columns)]
            held_bytes = 2 * sum(b.size * b.dtype.itemsize for b in blocks)
            assert held_bytes <= taken.vmem_bytes <= gmm.VMEM_BLOCK_BUDGET, (rung, rows_k, n)


@pytest.mark.parametrize("label,rows,k,held,experts,hidden,width", chip_smoke.EXPERT_SHAPES)
def test_expert_matvec_compiles_for_v5e_at_the_decode_shapes(
        one_chip, label, rows, k, held, experts, hidden, width):
    """A decode step's two grouped products at each model's published
    widths: the stacked weights stay where they are (no temporary: no
    slice of the stack in front of the call)."""
    from comfyui_distributed_tpu.ops import expert_matvec as em

    sizes = jax.ShapeDtypeStruct((held,), jnp.int32, sharding=one_chip)
    for contraction, columns in ((hidden, 2 * width), (width, hidden)):
        compiled = jax.jit(em.expert_matvec).lower(
            jax.ShapeDtypeStruct((rows, contraction), jnp.bfloat16, sharding=one_chip),
            jax.ShapeDtypeStruct((held, contraction, columns), jnp.bfloat16, sharding=one_chip),
            sizes,
        ).compile()
        assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1
        assert compiled.memory_analysis().temp_size_in_bytes < 2**20


@pytest.mark.parametrize("label,rows,k,held,experts,hidden,width", chip_smoke.RELU2_EXPERT_SHAPES)
@pytest.mark.parametrize("pairs", [0, 4])
def test_expert_matvec_compiles_for_v5e_out_by_in_and_out_of_a_stack(
        one_chip, label, rows, k, held, experts, hidden, width, pairs):
    """Nemotron-3-Nano's two products: the up-projection stored out by in
    (1,856 rows of 2,688: the width is off the lane tile, so the kernel
    walks blocks of rows of the stored array) and the down-projection by
    columns, each alone and read out of a run's stack of four pairs by the
    pair's index: no temporary, no slice of the stack in front of the
    call. The array the other way round, [2688, 1856], has no plan."""
    from comfyui_distributed_tpu.ops import expert_matvec as em

    assert em.matvec_plan(rows, hidden, width, 2) is None
    sizes = jax.ShapeDtypeStruct((held,), jnp.int32, sharding=one_chip)
    layer = (jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),) if pairs else ()
    lead = (pairs,) if pairs else ()
    for contraction, out_major in ((hidden, True), (width, False)):
        compiled = jax.jit(functools.partial(em.expert_matvec, out_major=out_major)).lower(
            jax.ShapeDtypeStruct((rows, contraction), jnp.bfloat16, sharding=one_chip),
            jax.ShapeDtypeStruct((*lead, held, width, hidden), jnp.bfloat16, sharding=one_chip),
            sizes, *layer,
        ).compile()
        assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1
        assert compiled.memory_analysis().temp_size_in_bytes < 2**20


def test_nemotron_decode_reads_its_experts_where_they_lie_and_carries_its_state_in_place(
        one_chip, monkeypatch):
    """Nemotron-3-Nano's whole decode at the served share's sizes (512
    steps over 8,704 positions), routed as a TPU routes it: a step walks
    the 52 blocks one after another, so two `expert_matvec` calls a
    sparse block (46), each on the block's own experts; the tree of six
    key/value caches (53.5 MB), 23 float32 states and 23 tails (49.1
    MB), a leaf each, is donated and written where it lies. What the
    loop needs beside it is under 64 MB: a temporary of gigabytes would
    be a weight turned round (a width off the lane tile stored last:
    PERF.md section 6, PR 48). And the program holds no `copy` of a
    megabyte: not of a state (the loop copied seven stacks of them, 46
    MB, into its carry every step while the runs of pairs were scans
    over stacks: PERF.md section 6, PR 49), not of a weight (the largest
    left are the 37 KB tails)."""
    import math
    import re

    from comfyui_distributed_tpu.models import nemotron_h
    from comfyui_distributed_tpu.models.registry import get_config

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config("nemotron3-nano-ep16-52l")
    place = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    params = jax.tree.map(place, jax.eval_shape(
        lambda: nemotron_h.init_params(cfg, jax.random.key(0), jnp.bfloat16)))
    state = jax.tree.map(place, nemotron_h.state_shapes(cfg, 8704, jnp.bfloat16))
    scalar = lambda dtype: jax.ShapeDtypeStruct((), dtype, sharding=one_chip)
    compiled = nemotron_h.decode.lower(
        cfg, params, state,
        jax.ShapeDtypeStruct((cfg.vocab_held,), jnp.float32, sharding=one_chip),
        scalar(jnp.int32), place(jax.eval_shape(lambda: jax.random.key(0))),
        scalar(jnp.float32), steps=510,  # a step count of its own: the route is read while tracing
    ).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 46
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 64 * 2**20
    assert memory.alias_size_in_bytes >= 8704 * 6144 + 49_082_368
    copied = re.findall(r" = (\w+)\[([\d,]*)\]\S* copy\(", text)
    assert copied  # the tails, among others
    assert not [(dtype, dims) for dtype, dims in copied
                if math.prod(int(d) for d in dims.split(",") if d) >= 2**19]


def test_granite_prefill_in_parts_attends_64_wide_heads_in_the_kernel_and_its_decode_carries_40_leaves_in_place(
        one_chip, monkeypatch):
    """granite-4.0-h-micro's two programs at the cell's sizes (65,536 ids
    in eight parts of 8,192 over caches of 65,664 positions), routed as a
    TPU routes them. The prefill is one `while` over the parts whose body
    holds, for each of the four attention layers, a causal kernel call a
    possible count of keys (32 in all), 64-wide heads padded to the lane
    tile; what it holds beside its arguments is a part's working set
    (1.8 GB: a SwiGLU's middle, a part's folded keys; a Mamba layer's
    chunk weights stay in VMEM, `ops/ssd_chunk`), not the prompt's. The
    decode carries the donated tree of four caches (537.9 MB) and 36 states and tails (76.4 MB), a leaf a
    layer, through its loop and copies none of them."""
    import math
    import re

    from comfyui_distributed_tpu.models import granite_hybrid
    from comfyui_distributed_tpu.models.registry import get_config

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config("granite-4.0-h-micro")
    place = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    params = jax.tree.map(place, jax.eval_shape(
        lambda: granite_hybrid.init_params(cfg, jax.random.key(0), jnp.bfloat16)))
    with attn.route_log() as routes:
        prefill = granite_hybrid.prefill.lower(
            cfg, params, jax.ShapeDtypeStruct((65536,), jnp.int32, sharding=one_chip),
            cache_len=65664,
        ).compile()
    assert [r for r in routes if r.startswith("flash-")] == [
        f"flash-causal 8192x{8192 * k}x64/64 g4 bq512 bk1024 bf16 blocks{128 * k - 56}/{128 * k}"
        for k in range(1, 9)] * 4
    # the 36 Mamba layers' chunked scans in the kernel of `ops/ssd_chunk` (PR 55)
    assert {r for r in routes if not r.startswith("flash-")} == {
        "ssd-kernel 8192x64x64 g1 n128 c256 hb8 bf16"}
    memory = prefill.memory_analysis()
    assert memory.temp_size_in_bytes < 2.0e9      # 1.78 GB: a part's, whatever the parts' number
    assert memory.output_size_in_bytes >= 65664 * 8192 + 76_437_504
    text = prefill.as_text()
    assert " while(" in text and text.count('custom_call_target="tpu_custom_call"') == 32 + 36
    assert "%flash_attention_causal" in text
    assert len(re.findall(r"%ssd_chunk[.\d]* = ", text)) == 36
    # no chunk's weights in HBM: 32 x 64 x 256 x 256 entries a layer and part
    assert "[32,1,64,256,256]" not in text and "[32,64,256,256]" not in text

    state = jax.tree.map(place, granite_hybrid.state_shapes(cfg, 65664, jnp.bfloat16))
    scalar = lambda dtype: jax.ShapeDtypeStruct((), dtype, sharding=one_chip)
    with attn.route_log() as routes:
        decode = granite_hybrid.decode.lower(
            cfg, params, state,
            jax.ShapeDtypeStruct((cfg.vocab_size,), jnp.float32, sharding=one_chip),
            scalar(jnp.int32), place(jax.eval_shape(lambda: jax.random.key(0))),
            scalar(jnp.float32), steps=126,  # a step count of its own: the route is read while tracing
        ).compile()
    assert routes == ["decode-xla 32x65664x64"] * 4
    memory = decode.memory_analysis()
    assert memory.temp_size_in_bytes < 128 * 2**20    # 66.5 MB
    assert memory.alias_size_in_bytes >= 65664 * 8192 + 76_437_504
    copied = re.findall(r" = (\w+)\[([\d,]*)\]\S* copy\(", decode.as_text())
    assert not [(dtype, dims) for dtype, dims in copied
                if math.prod(int(d) for d in dims.split(",") if d) >= 2**19]


# as above: a step count a case, since the route is read while the program is traced
@pytest.mark.parametrize("backend,steps,calls,masked", [("tpu", 256, 8, 0), ("cpu", 3, 0, 8)])
def test_deepseek_decode_multiplies_its_six_pairs_in_the_kernel(
        one_chip, monkeypatch, backend, steps, calls, masked):
    """DeepSeek-V2's decode at the served share's sizes. Six rows are no
    multiple of the sublane tile, so the compiler has no grouped kernel
    of its own for them: left to it, `ragged_dot` is a product with every
    one of the 40 held experts under a mask (`bf16[40,6,...]`, two a
    layer); routed as a TPU routes it, two `expert_matvec` calls a layer
    and no such product."""
    import re

    from comfyui_distributed_tpu.models import deepseek_v2
    from comfyui_distributed_tpu.models.registry import get_config

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg = get_config("deepseek-v2-ep4-5l")
    place = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    params = jax.tree.map(place, jax.eval_shape(
        lambda: deepseek_v2.init_params(cfg, jax.random.key(0), jnp.bfloat16)))
    scalar = lambda dtype: jax.ShapeDtypeStruct((), dtype, sharding=one_chip)
    text = deepseek_v2.decode.lower(
        cfg, params,
        jax.ShapeDtypeStruct(
            (cfg.num_hidden_layers, 2304, cfg.cache_width), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((cfg.vocab_held,), jnp.float32, sharding=one_chip),
        scalar(jnp.int32), place(jax.eval_shape(lambda: jax.random.key(0))),
        scalar(jnp.float32), steps=steps,
    ).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == calls
    assert len(re.findall(r"= bf16\[40,6,\d+\]\S* convolution\(", text)) == masked


def test_dsa_attend_compiles_for_v5e_at_a_parts_shape(one_chip):
    """Latent attention over the chosen rows at `chip_smoke.DSA_SHAPE`,
    a part of GLM-5.2's prompt over the cell's cache, as
    `dsa.attend_kernel` runs it: the `dsa_attend` kernel a block of
    `dsa.KERNEL_ROWS` queries, holding the layer's cache in VMEM (the
    compiler takes the raised limit; the default 16 MiB would refuse
    it), and no gathered row in HBM. What the program holds beside its
    arguments and its result is a block's folded queries, latent
    outputs and selection as int32, and the cache's words."""
    from comfyui_distributed_tpu.models import dsa
    from comfyui_distributed_tpu.ops import dsa_attend

    _, queries, rows, top, heads, nope, rope, v, rank, *_ = chip_smoke.DSA_SHAPE
    sizes = dsa_attend.plan(heads, rank + rope, rank, top, rows, 2)
    assert 16 * 2**20 < sizes.vmem_bytes <= dsa_attend.VMEM_RESIDENT_BUDGET
    place = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = jax.jit(
        lambda a, b, cache, c, d, w_uk, w_uv: dsa.attend_kernel(
            a, b, cache, dsa.Selection(c, d), w_uk, w_uv, 0.0625)
    ).lower(
        place((queries, heads, nope), jnp.bfloat16), place((queries, heads, rope), jnp.bfloat16),
        place((rows, rank + rope), jnp.bfloat16),
        place((queries, top), jnp.int32), place((queries, top), jnp.bool_),
        place((rank, heads, nope), jnp.bfloat16), place((rank, heads, v), jnp.bfloat16),
    ).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1 and "%dsa_attend" in text
    assert " gather(" not in text
    block = dsa.KERNEL_ROWS * (heads * (2 * sizes.value_width + sizes.key_width) * 2 + 2 * top * 4)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * block + 2 * rows * sizes.lanes * 4


def test_dsa_select_compiles_for_v5e_at_a_parts_shape(one_chip, monkeypatch):
    """The indexer's selection at `chip_smoke.DSA_SHAPE`, a part of
    GLM-5.2's prompt over the cell's cache, as `dsa.select` runs it on a
    TPU: the scores a block of `dsa.BLOCK_ROWS` queries, and their 2,048
    best picked by the `dsa_select` kernel, one custom call a rung of the
    ladder, a block's keys and words in VMEM (the compiler takes the
    raised limit; the default 16 MiB would refuse it). No sort is left,
    and what the program holds beside its arguments and its result is a
    block's: the heads' products, the keys turned round and the
    positions, not a part's scores."""
    from comfyui_distributed_tpu.models import dsa
    from comfyui_distributed_tpu.ops import dsa_select

    _, queries, rows, top, *_, index_heads, index_width = chip_smoke.DSA_SHAPE
    ladder = dsa.length_ladder(rows, top)
    assert ladder == (4096, 8192, 16384, 32768, 32896)
    sizes = dsa_select.plan(dsa.BLOCK_ROWS, rows, top)
    assert 16 * 2**20 < sizes.vmem_bytes <= dsa_select.VMEM_RESIDENT_BUDGET
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    place = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    with attn.route_log() as routes:
        compiled = jax.jit(lambda q, w, cached, at: tuple(dsa.select(q, w, cached, at, top))).lower(
            place((queries, index_heads, index_width), jnp.bfloat16),
            place((queries, index_heads), jnp.float32),
            place((rows, index_width), jnp.bfloat16), place((queries,), jnp.int32),
        ).compile()
    assert routes == [f"dsa-select-kernel {queries}x{length} k{top}" for length in ladder]
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == len(ladder)
    assert "%dsa_select" in text and " sort(" not in text
    block = dsa.BLOCK_ROWS * rows * 4
    assert compiled.memory_analysis().temp_size_in_bytes < (index_heads + 4) * block


def test_glm_prefill_in_parts_is_one_scanned_body_and_its_decode_carries_two_caches_in_place(
        one_chip, monkeypatch):
    """GLM-5.2's two programs at the cell's sizes (32,768 ids in four parts
    of 8,192 over caches of 32,896 positions), routed as a TPU routes
    them. The prefill is one `while` over the parts: what it holds beside
    its arguments is a part's working set (the expert ladder's top rung,
    the indexer's scores, a part's folded queries), not the prompt's;
    each indexer layer picks its keys in the `dsa_select` kernel and no
    sort over a cache's positions is in the program; each attention
    layer attends in the `dsa_attend` kernel and no gathered row is in
    the program. The
    drafting decode carries the donated tree of six latent caches and
    three indexer caches (252.6 MB) through its loop, a step's queries
    take the masked form, and a step's grouped products run in the
    `expert_matvec` kernel (two a sparse layer and the MTP module's)."""
    from comfyui_distributed_tpu.models import glm_dsa
    from comfyui_distributed_tpu.models.registry import get_config

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config("glm-5.2-ep16-5l")
    place = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    params = jax.tree.map(place, jax.eval_shape(
        lambda: glm_dsa.init_params(cfg, jax.random.key(0), jnp.bfloat16)))
    with attn.route_log() as routes:
        prefill = glm_dsa.prefill.lower(
            cfg, params, jax.ShapeDtypeStruct((32768,), jnp.int32, sharding=one_chip),
            cache_len=32896,
        ).compile()
    picks = [f"dsa-select-kernel 8192x{length} k2048" for length in (4096, 8192, 16384, 32768, 32896)]
    assert [r for r in routes if r.startswith("dsa-select")] == picks * cfg.full_layers
    assert routes.count("dsa-kernel 8192x32896 k2048 h64 bf16") == cfg.num_hidden_layers
    # a sparse layer's ladder (4,096 ... 65,536): the lowest rung in the grouped kernel
    grouped = grouped_entries(8192, 8, 16, 256, 6144, 2048)
    assert [r.split()[0] for r in grouped] == ["gmm-kernel"] * 2 + ["gmm-xla"] * 8
    assert [r for r in routes if r.startswith("gmm-")] == grouped * cfg.sparse_layers
    assert len(routes) == (
        len(picks) * cfg.full_layers + cfg.num_hidden_layers + len(grouped) * cfg.sparse_layers)
    memory = prefill.memory_analysis()
    assert memory.temp_size_in_bytes < 4.5e9      # 4.13 GB: a part's, whatever the parts' number
    assert memory.output_size_in_bytes >= 32896 * 7680
    text = prefill.as_text()
    # the parts' scan; the 2,048 best of up to 32,896 are picked in the `dsa_select` kernel,
    # so no sort over a cache's positions is left (the router's top 8 of 256 is one)
    assert " while(" in text and not [
        line for line in text.splitlines()
        if " sort(" in line and re.search(r"\[\d+,(4096|8192|16384|32768|32896)\]", line)]
    assert text.count("%dsa_select") >= 1
    assert not [line for line in text.splitlines() if " gather(" in line and "2048,576" in line]
    assert text.count("%dsa_attend") >= 1
    # a call a product and rung in each of the two bodies that hold a sparse layer's switch
    assert len(re.findall(r"%grouped_matmul[.\d]* = ", text)) >= 4 and "ragged-dot" in text

    state = jax.tree.map(place, glm_dsa.state_shapes(cfg, 32896, jnp.bfloat16))
    scalar = lambda dtype: jax.ShapeDtypeStruct((), dtype, sharding=one_chip)
    with attn.route_log() as routes:
        decode = glm_dsa.decode.lower(
            cfg, params, state,
            jax.ShapeDtypeStruct((cfg.vocab_held,), jnp.float32, sharding=one_chip),
            scalar(jnp.int32), place(jax.eval_shape(lambda: jax.random.key(0))),
            scalar(jnp.float32), steps=120, draft_tokens=1,  # a count of this test's own
        ).compile()
    assert routes == ["dsa-masked 2x32896 k32896 h64 bf16"] * (cfg.num_hidden_layers + 1)
    memory = decode.memory_analysis()
    assert memory.temp_size_in_bytes < 0.5e9
    assert memory.alias_size_in_bytes >= 32896 * 7680
    text = decode.as_text()
    # neither a sort nor a gather over a cache's positions in a step (the router's own
    # top 8 of 256 is a sort; the embedding's rows are a gather)
    assert not [line for line in text.splitlines()
                if (" sort(" in line or " gather(" in line) and "32896" in line]
    assert text.count('custom_call_target="tpu_custom_call"') == 2 * (cfg.sparse_layers + 1)


def test_the_band_kernel_compiles_for_v5e_over_a_tail_of_latents(one_chip):
    """dots3-note-prev's sliding layers' call (`chip_smoke.BAND_TAIL_SHAPE`):
    8,192 queries over the 512 latents before the part and its own, 64
    heads 256 wide beside values of 128, under a band of 513, on the
    kernel: fewer queries than keys, two blocks of 512 keys a block of
    rows. The shape rule sends it where `MIN_BAND_WINDOW` says; it
    compiles all the same."""
    _, q_shape, kv_heads, v_width, window, keys = chip_smoke.BAND_TAIL_SHAPE
    b, n, h, d = q_shape
    place = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=one_chip)
    fn = jax.jit(functools.partial(attn.causal_attention, window=window, force_flash=True))
    with attn.route_log() as routes:
        compiled = fn.lower(
            place(*q_shape), place(b, keys, kv_heads, d), place(b, keys, kv_heads, v_width)
        ).compile()
    assert routes == [
        "flash-causal 8192x8704x256/128 w513 g1 bq512 bk512 bf16 inplace blocks32/272"]
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "%flash_attention_causal" in text
    assert attn.causal_pairs_computed("flash", n, keys, d, v_width, 2, window) == 32 * 512 * 512


def test_dots3_prefill_in_parts_hands_tails_on_and_its_decode_carries_caches_and_rings_in_place(
        one_chip, monkeypatch):
    """dots3-note-prev's two programs at the cell's sizes (32,768 ids in
    four parts of 8,192 over caches of 33,024 positions), routed as a TPU
    routes them. The prefill is one `while` over the parts: each full
    layer picks its keys in the `dsa_select` kernel (a rung of the
    lengths' ladder each) and attends in the `dsa_attend` kernel at 128
    heads; each sliding layer holds two band calls under a `lax.switch`,
    the first part's over its own 8,192 latents and a later part's over
    8,704; what the program holds beside its arguments is a part's
    working set. The decode carries the donated tree of two latent
    caches, two index caches and three rings (96.4 MB) through its
    loop, a step's query takes the masked form in both full layers, and
    a step's grouped products run in the `expert_matvec` kernel (two a
    sparse layer)."""
    from comfyui_distributed_tpu.models import dots3
    from comfyui_distributed_tpu.models.registry import get_config

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config("dots3-note-prev-ep8-5l")
    place = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    params = jax.tree.map(place, jax.eval_shape(
        lambda: dots3.init_params(cfg, jax.random.key(0), jnp.bfloat16)))
    with attn.route_log() as routes:
        prefill = dots3.prefill.lower(
            cfg, params, jax.ShapeDtypeStruct((32768,), jnp.int32, sharding=one_chip),
            cache_len=33024,
        ).compile()
    picks = [f"dsa-select-kernel 8192x{length} k2048" for length in (4096, 8192, 16384, 32768, 33024)]
    assert [r for r in routes if r.startswith("dsa-select")] == picks * cfg.full_layers
    assert routes.count("dsa-kernel 8192x33024 k2048 h128 bf16") == cfg.full_layers
    route = "flash" if attn.causal_kernel_wins(8704, 128, 513) else "xla"
    bands = [r for r in routes if " w513 " in r]
    assert len(bands) == 2 * cfg.window_layers and all(r.startswith(route + "-causal") for r in bands)
    assert [r.split()[1] for r in bands] == ["8192x8192x256/128", "8192x8704x256/128"] * 3
    # a sparse layer's ladder (8,192 ... 65,536): the lowest rung in the grouped kernel
    grouped = grouped_entries(8192, 8, 32, 256, 5120, 1536)
    assert [r.split()[0] for r in grouped] == ["gmm-kernel"] * 2 + ["gmm-xla"] * 6
    assert [r for r in routes if r.startswith("gmm-")] == grouped * cfg.sparse_layers
    assert len(routes) == (
        (len(picks) + 1) * cfg.full_layers + len(bands) + len(grouped) * cfg.sparse_layers)
    memory = prefill.memory_analysis()
    assert memory.temp_size_in_bytes < 3.6e9      # 3.18 GB: a part's, whatever the parts' number
    assert memory.output_size_in_bytes >= 33024 * 2816 + 3 * 520 * 2176
    text = prefill.as_text()
    assert " while(" in text and not [
        line for line in text.splitlines()
        if " sort(" in line and re.search(r"\[\d+,(4096|8192|16384|32768|33024)\]", line)]
    assert text.count("%dsa_select") >= 1 and text.count("%dsa_attend") >= 1
    assert not [line for line in text.splitlines() if " gather(" in line and "2048,576" in line]
    assert len(re.findall(r"%grouped_matmul[.\d]* = ", text)) >= 4 and "ragged-dot" in text

    state = jax.tree.map(place, dots3.state_shapes(cfg, 33024, jnp.bfloat16))
    scalar = lambda dtype: jax.ShapeDtypeStruct((), dtype, sharding=one_chip)
    with attn.route_log() as routes:
        decode = dots3.decode.lower(
            cfg, params, state,
            jax.ShapeDtypeStruct((cfg.vocab_held,), jnp.float32, sharding=one_chip),
            scalar(jnp.int32), place(jax.eval_shape(lambda: jax.random.key(0))),
            scalar(jnp.float32), steps=256,
        ).compile()
    assert routes == ["dsa-masked 1x33024 k33024 h128 bf16"] * cfg.full_layers
    memory = decode.memory_analysis()
    assert memory.temp_size_in_bytes < 0.2e9     # 100 MB
    assert memory.alias_size_in_bytes >= 33024 * 2816 + 3 * 520 * 2176
    text = decode.as_text()
    assert not [line for line in text.splitlines()
                if (" sort(" in line or " gather(" in line) and "33024" in line]
    assert text.count('custom_call_target="tpu_custom_call"') == 2 * cfg.sparse_layers


def test_longcat_flash_prefill_rebuilds_16_heads_keys_at_a_time_and_its_decode_carries_eight_caches(
        one_chip, monkeypatch):
    """LongCat-Flash-Chat's two programs at the cell's sizes (32,768 ids
    in four parts of 8,192 over eight caches of 32,896 positions), routed
    as a TPU routes them. The prefill is one `while` over the parts: each
    of the eight attentions holds a causal kernel call a possible count
    of keys under a `lax.switch` (8,192 queries over 8,192 to 32,768 keys,
    192 beside 128 wide), inside a loop over groups of 16 heads, so that
    one group's rebuilt keys and values are alive at once; the expert
    branch runs over blocks of 1,024 tokens, so its ladder's top rung is
    12,288 rows. What the program holds beside its arguments and the
    caches it hands on stays under 2.1 GB (1.89: with 32 heads a call and
    blocks of 2,048 it held 2.5, with the groups as a row of calls and
    not a loop 2.8, whatever the group). The decode carries the donated
    tree of eight caches (303 MB) through its loop and a step's grouped
    products run in the `expert_matvec` kernel (two a layer)."""
    from comfyui_distributed_tpu.models import longcat_flash
    from comfyui_distributed_tpu.models.registry import get_config

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config("longcat-flash-chat-ep64-4l")
    place = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    params = jax.tree.map(place, jax.eval_shape(
        lambda: longcat_flash.init_params(cfg, jax.random.key(0), jnp.bfloat16)))
    with attn.route_log() as routes:
        prefill = longcat_flash.prefill.lower(
            cfg, params, jax.ShapeDtypeStruct((32768,), jnp.int32, sharding=one_chip),
            cache_len=32896,
        ).compile()
    # a block's ladder starts at a tile (256 ... 12,288): every rung keeps `ragged_dot` (PR 64)
    grouped = grouped_entries(1024, 12, 8, 768, 6144, 2048)
    assert len(grouped) == 12 and all(r.startswith("gmm-xla ") for r in grouped)
    assert [r for r in routes if r.startswith("gmm-")] == grouped * cfg.num_layers
    routes = [r for r in routes if not r.startswith("gmm-")]
    counts = (8192, 16384, 24576, 32768)
    assert [r.split()[:2] for r in routes] == [
        ["flash-causal", f"8192x{keys}x192/128"] for keys in counts] * cfg.attention_sublayers
    assert all(" g1 bq512 bk1024 bf16 inplace " in r for r in routes)
    cache_bytes = cfg.attention_sublayers * 32896 * cfg.cache_width * 2
    memory = prefill.memory_analysis()
    assert memory.output_size_in_bytes >= cache_bytes
    assert memory.temp_size_in_bytes - cache_bytes < 2.1e9
    text = prefill.as_text()
    assert " while(" in text and text.count("%flash_attention_causal") >= len(counts)

    state = jax.tree.map(place, longcat_flash.state_shapes(cfg, 32896, jnp.bfloat16))
    scalar = lambda dtype: jax.ShapeDtypeStruct((), dtype, sharding=one_chip)
    with attn.route_log() as routes:
        decode = longcat_flash.decode.lower(
            cfg, params, state,
            jax.ShapeDtypeStruct((cfg.vocab_held,), jnp.float32, sharding=one_chip),
            scalar(jnp.int32), place(jax.eval_shape(lambda: jax.random.key(0))),
            scalar(jnp.float32), steps=128,
        ).compile()
    assert routes == []  # the absorbed form: no causal call
    memory = decode.memory_analysis()
    assert memory.temp_size_in_bytes < 0.5e9     # 358 MB
    assert memory.alias_size_in_bytes >= cache_bytes
    assert decode.as_text().count('custom_call_target="tpu_custom_call"') == 2 * cfg.num_layers


def test_sdar_prefill_masks_by_block_in_the_causal_kernel_and_its_decode_carries_six_leaves_in_place(
        one_chip, monkeypatch):
    """SDAR's two programs at the served stage's sizes (2,048 prompt
    tokens, 512 ids over 2,560 positions) on the TPU's route: the prefill
    attends a layer in the causal kernel under the block mask (the tiles
    of the plain causal call, `b4` in the route's entry); the decode is
    two nested `while` loops that carry the donated leaf a layer (31.5 MB
    together) and write a block's four positions where they lie; a
    denoising pass multiplies its 32 pairs in `expert_matvec` (two calls a
    layer), a closing pass stops at the last layer's keys and values (two
    calls fewer). What the loops need beside the tree is some 20 MB (a
    pass's float32 logits and their draws)."""
    from comfyui_distributed_tpu.models import sdar
    from comfyui_distributed_tpu.models.registry import get_config

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = get_config("sdar-30b-a3b-pp8-6l")
    place = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    params = jax.tree.map(place, jax.eval_shape(
        lambda: sdar.init_params(cfg, jax.random.key(0), jnp.bfloat16)))
    with attn.route_log() as routes:
        compiled = sdar.prefill.lower(
            cfg, params, jax.ShapeDtypeStruct((2048,), jnp.int32, sharding=one_chip),
            cache_len=2560).compile()
    # every expert held: a ladder of one rung, its two products in the grouped kernel (PR 64)
    grouped = grouped_entries(2048, 8, 128, 128, 2048, 768)
    assert grouped == ["gmm-kernel 16384x2048x1536 g128 bf16", "gmm-kernel 16384x768x2048 g128 bf16"]
    assert routes == ([
        "flash-causal 2048x2048x128/128 b4 g8 bq512 bk1024 bf16 inplace blocks6/8"] + grouped) * 6
    text = compiled.as_text()
    assert len(re.findall(r"%flash_attention_causal[.\d]* = ", text)) == 6
    assert len(re.findall(r"%grouped_matmul[.\d]* = ", text)) == 12 and "ragged-dot" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 400 * 2**20

    state = jax.tree.map(place, sdar.state_shapes(cfg, 2560, jnp.bfloat16))
    scalar = lambda dtype: jax.ShapeDtypeStruct((), dtype, sharding=one_chip)
    with attn.route_log() as routes:
        compiled = sdar.decode.lower(
            cfg, params, state,
            jax.ShapeDtypeStruct((cfg.vocab_size,), jnp.float32, sharding=one_chip),
            scalar(jnp.int32), place(jax.eval_shape(lambda: jax.random.key(0))),
            scalar(jnp.float32), steps=512).compile()
    assert set(routes) == {"decode-xla 32x2560x128"}
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 32 * 2**20
    # the donated tree is the output's: nothing of its size is allocated anew
    assert memory.alias_size_in_bytes >= 31_457_280
    text = compiled.as_text()
    assert text.count(" while(") >= 2
    assert len(re.findall(r"%expert_matvec[.\d]* = ", text)) == 2 * 6 + 2 * 5


@pytest.mark.parametrize("bundle,component", [("sd15", "TextEncoder"), ("sdxl", "UNet")])
def test_a_components_init_program_holds_under_a_quarter_of_its_stored_bytes(
        one_chip, bundle, component, monkeypatch):
    """`models/pipeline.init_program` at published widths: what the one
    program that builds a component's seeded weights holds beside them is
    a loop's stacked results at most (an eighth of the stored bytes;
    SDXL's UNet read 86 % before its loops were put in sequence), never a
    float32 copy of the component. CLIP-L draws in six loops and holds
    nothing; SDXL's UNet, 1,680 weights, in 22."""
    from comfyui_distributed_tpu.models import pipeline as pl
    from test_params_storage import components

    monkeypatch.setenv("CDT_PARAMS_DTYPE", "bfloat16")
    module, args, kwargs = next(
        c for c in components(bundle, monkeypatch) if type(c[0]).__name__ == component)
    key = jax.random.key(0)
    program = pl.init_program(module, jnp.dtype(jnp.bfloat16), key, *args, **kwargs)
    spec = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip)
    memory = program.lower(spec).compile().memory_analysis()
    assert memory.output_size_in_bytes > 2e8
    assert memory.temp_size_in_bytes <= memory.output_size_in_bytes / 4
