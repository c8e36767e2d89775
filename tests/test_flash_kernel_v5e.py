"""The flash-attention kernel at the shapes the served models give it,
compiled for a TPU v5e that is described and not attached: what the
chip's compiler refuses (VMEM, layouts) shows here at no chip time. No
result and no timing comes from this file. All such compiles live in
this one file (one process loads the TPU's library; see the fixture)."""

import functools
import os

import chip_smoke
import jax
import jax.numpy as jnp
import pytest

from comfyui_distributed_tpu.ops import attention as attn

# chip_smoke.SERVED_SHAPES whose lengths reach the kernel
SHAPES = [
    (label, q_shape, m) for label, q_shape, m in chip_smoke.SERVED_SHAPES
    if q_shape[1] % attn.ROUTE_MULTIPLE == 0 and m % attn.ROUTE_MULTIPLE == 0
]


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


def test_the_served_shapes_that_reach_the_kernel():
    labels = [label for label, _, _ in SHAPES]
    assert "flux joint 4608" in labels and "flux vae mid 128x128" in labels
    assert "sd15 self 64x64" in labels and "sd15 vae mid 64x64" in labels
    assert not any(label.startswith("sdxl") for label in labels)


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
    block_q, block_k = attn.flash_blocks(n, m, d + -d % 128, jnp.dtype(dtype).itemsize)
    assert routes == [f"flash {n}x{m}x{d} bq{block_q} bk{block_k} {name}"]


def test_route_log_entries():
    """What the sampler node writes into its span as `attention`: the
    blocks and operand dtype for a flash call, `xla` entries as ever."""
    flux = jax.ShapeDtypeStruct((1, 4608, 24, 128), jnp.bfloat16)
    sd15 = jax.ShapeDtypeStruct((2, 4096, 8, 40), jnp.bfloat16)
    text = jax.ShapeDtypeStruct((2, 77, 8, 40), jnp.bfloat16)
    flash = functools.partial(attn.dot_product_attention, force_flash=True)
    with attn.route_log() as routes:
        jax.eval_shape(flash, flux, flux, flux)
        jax.eval_shape(flash, sd15, sd15, sd15)
        jax.eval_shape(attn.dot_product_attention, sd15, text, text)
    assert routes == [
        "flash 4608x4608x128 bq512 bk1536 bf16",
        "flash 4096x4096x40 bq512 bk1024 bf16",
        "xla 4096x77x40",
    ]
