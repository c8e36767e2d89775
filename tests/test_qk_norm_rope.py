"""`ops/qk_norm_rope.norm_rope` (Pallas interpret mode on the CPU): the
per-head RMS norm and the rotation of an MMDiT block's q and k in one
pass, against the XLA operations it stands for on a TPU."""

import functools
import zlib

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from comfyui_distributed_tpu.models import mmdit
from comfyui_distributed_tpu.models.dit import apply_rope
from comfyui_distributed_tpu.ops import qk_norm_rope
from comfyui_distributed_tpu.ops.qk_norm_rope import norm_rope, norm_rope_plan


def _freqs(n, d, seed=2):
    angle = jax.random.uniform(jax.random.key(seed), (n, d // 2)) * 6.28
    return jnp.stack([jnp.cos(angle), jnp.sin(angle)], axis=-1)


def _as_the_model_does(x, scale, freqs, heads, offset, dtype):
    """Slice, reshape, flax's RMSNorm in float32, the rounding to the
    compute dtype, `apply_rope`: `models/mmdit._qk_norm_rope` off a TPU."""
    b, n, _ = x.shape
    d = 2 * freqs.shape[1]
    heads_of = x[..., offset:offset + heads * d].reshape(b, n, heads, d)
    norm = nn.RMSNorm(epsilon=1e-6, dtype=jnp.float32)
    normed = norm.apply({"params": {"scale": scale}}, heads_of)
    return apply_rope(normed.astype(dtype), freqs).reshape(b, n, heads * d)


# (label, B, N, heads, D, width of x, lane the heads start at)
CASES = [
    ("q of a fused linear", 2, 64, 4, 128, 3 * 512 + 256, 0),
    ("k of a fused linear", 2, 64, 4, 128, 3 * 512 + 256, 512),
    ("heads in groups of 8", 1, 32, 24, 128, 3 * 3072, 3072),
    ("an offset only one head divides", 1, 48, 6, 128, 4 * 768, 128 * 7),
    ("a 256-wide head", 1, 32, 3, 256, 2 * 768, 768),
    ("rows in several blocks", 1, 1536, 1, 128, 256, 128),
]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize(
    "b,n,heads,d,width,offset", [c[1:] for c in CASES], ids=[c[0] for c in CASES]
)
def test_norm_rope_is_the_models_norm_and_rotation(b, n, heads, d, width, offset, dtype):
    x = (3.0 * jax.random.normal(jax.random.key(n + heads), (b, n, width))).astype(dtype)
    scale = 1.0 + 0.3 * jax.random.normal(jax.random.key(1), (d,))
    freqs = _freqs(n, d)
    out = norm_rope(x, scale, freqs, heads=heads, offset=offset, interpret=True)
    want = _as_the_model_does(x, scale, freqs, heads, offset, dtype)
    assert out.shape == (b, n, heads * d) and out.dtype == dtype
    # the same float32 arithmetic in the same order but for the mean's sum:
    # a value may fall on the other side of a rounding, no further
    ulp = 2.0 ** -7 if dtype == jnp.bfloat16 else 2.0 ** -20
    got, want = np.asarray(out, np.float32), np.asarray(want, np.float32)
    assert np.all(np.abs(got - want) <= ulp * np.maximum(1.0, np.abs(want)))
    if dtype == jnp.bfloat16:  # whose rounding hides the sum's last bit nearly always
        assert np.mean(got != want) < 1e-3


def test_heads_keep_their_places_and_their_own_statistic():
    """Head i scaled by 10**i: a statistic taken over another head's
    lanes, or a head written in another's place, is far off."""
    heads, d, n = 4, 128, 32
    x = jax.random.normal(jax.random.key(0), (1, n, heads, d))
    x = (x * (10.0 ** jnp.arange(heads))[:, None]).reshape(1, n, heads * d)
    freqs = _freqs(n, d)
    out = norm_rope(x, jnp.ones((d,)), freqs, heads=heads, interpret=True)
    # an RMS-normed head has mean square 1 whatever its scale, and a
    # rotation keeps it
    mean_sq = jnp.mean(out.reshape(1, n, heads, d) ** 2, axis=-1)
    np.testing.assert_allclose(np.asarray(mean_sq), 1.0, rtol=1e-4)
    want = _as_the_model_does(x, jnp.ones((d,)), freqs, heads, 0, jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-6, atol=2e-6)


# (n, heads, d, offset) -> (rows, heads) of a block, or None
PLANS = [
    ((4608, 24, 128, 0), (512, 8)), ((4608, 24, 128, 3072), (512, 8)),
    ((4096, 24, 128, 3072), (512, 8)), ((512, 24, 128, 0), (512, 8)),
    ((64, 4, 128, 512), (64, 4)), ((48, 6, 128, 896), (48, 1)),
    ((32, 3, 256, 768), (32, 3)), ((4608, 2, 512, 1024), (512, 2)),
    ((4608, 24, 64, 0), None),      # a width off the lane tile
    ((100, 4, 128, 0), None),       # no divisor that is a multiple of 16
    ((64, 4, 128, 64), None),       # an offset inside a head
    ((0, 4, 128, 0), None),
]


@pytest.mark.parametrize("shape,plan", PLANS, ids=[str(p[0]) for p in PLANS])
def test_plan_and_route_follow_from_the_shape(shape, plan, monkeypatch):
    assert norm_rope_plan(*shape) == plan
    assert qk_norm_rope.norm_rope_route(*shape) == "xla"  # off a TPU, as the attention route
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert qk_norm_rope.norm_rope_route(*shape) == ("pallas" if plan else "xla")
    if plan is None and shape[0] > 0:
        n, heads, d, offset = shape
        with pytest.raises(ValueError, match="no block"):
            jax.eval_shape(
                functools.partial(norm_rope, heads=heads, offset=offset),
                jax.ShapeDtypeStruct((1, n, offset + heads * d), jnp.bfloat16),
                jax.ShapeDtypeStruct((d,), jnp.float32),
                jax.ShapeDtypeStruct((n, d // 2, 2), jnp.float32),
            )


@pytest.mark.parametrize("kind", ["single", "double"])
def test_an_mmdit_block_gives_the_same_on_either_route(kind, monkeypatch):
    """The block as a TPU routes it (the kernel interpreted here) against
    the block as the CPU runs it: same parameters, same tree."""
    heads, dim, nt, ni = 2, 256, 16, 48
    n = nt + ni
    freqs = _freqs(n, dim // heads)
    keys = jax.random.split(jax.random.key(5), 4)
    vec = jax.random.normal(keys[0], (1, dim), jnp.bfloat16)
    if kind == "single":
        block = mmdit._SingleBlock(heads=heads, mlp_width=2 * dim, dtype=jnp.bfloat16)
        args = (jax.random.normal(keys[1], (1, n, dim), jnp.bfloat16), vec, freqs)
    else:
        block = mmdit._DoubleBlock(heads=heads, mlp_width=2 * dim, dtype=jnp.bfloat16)
        args = (
            jax.random.normal(keys[1], (1, ni, dim), jnp.bfloat16),
            jax.random.normal(keys[2], (1, nt, dim), jnp.bfloat16), vec, freqs,
        )
    params = block.init(jax.random.key(0), *args)
    # head-norm scales off 1, or a scale read from the wrong norm would not show
    def off_one(path, leaf):
        name = jax.tree_util.keystr(path)
        if "norm_" not in name:
            return leaf
        return leaf + 0.3 * jax.random.normal(jax.random.key(zlib.crc32(name.encode())), leaf.shape)

    params = jax.tree_util.tree_map_with_path(off_one, params)
    plain = block.apply(params, *args)

    monkeypatch.setattr(mmdit, "norm_rope_route", lambda *shape: "pallas")
    monkeypatch.setattr(mmdit, "norm_rope", functools.partial(norm_rope, interpret=True))
    routed_params = block.init(jax.random.key(0), *args)
    assert jax.tree.structure(routed_params) == jax.tree.structure(params)
    assert jax.tree.map(jnp.shape, routed_params) == jax.tree.map(jnp.shape, params)
    routed = block.apply(params, *args)
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(routed)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.all(np.abs(a - b) <= 2.0 ** -6 * np.maximum(1.0, np.abs(a)))
        assert np.mean(a != b) < 0.02
