"""Plain reference of the FLUX.1 denoiser and its flow-euler sampling loop.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: no kernels, no flax, no cache,
no batching tricks, and no import from the code it is compared with
(`models/mmdit.py`, `models/dit.py`, `ops/`). It follows the published
implementation (github.com/black-forest-labs/flux, `model.py`,
`modules/layers.py`, `math.py`, `sampling.py`) layer by layer, and reads
the system's own parameter tree (the flax names mirror the published
state-dict keys: `double_blocks_3/img_attn_qkv` is
`double_blocks.3.img_attn.qkv`), upcasting it one block at a time so
that at published widths it fits on a chip beside the system's bfloat16
weights.

Departures from the published description, each kept because the system
under test makes the same choice and the two must be given the same
problem:

- latents are NHWC here, `[B, h, w, C]`; the published code is NCHW. The
  patch order `(c, ph, pw)` inside a token is the published one.
- the flow schedule takes a fixed `shift` (the system's 3.0) where the
  published `get_schedule` derives `exp(mu)` from the image's token count
  (`exp(1.15)` = 3.16 at 4,096 tokens). The formula is the same:
  `s * t / (1 + (s - 1) * t)`.
- `guidance` is required, as published for the guidance-distilled model;
  the system falls back to 3.5 when a conditioning carries none, so a
  comparison without a value passes 3.5 here.
- the text encoders and the autoencoder are outside this file: `context`
  and `pooled` are inputs.

`round_to` rounds both operands of every matrix product (the linear
layers and the two products of the attention) to that dtype before
multiplying in float32. It exists for one purpose: the comparison's limit
is set between what the system gives and what this reference gives when
computed one precision below the one the configuration states.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the parameter tree does not say about the architecture."""

    heads: int = 24
    axes_dim: tuple = (16, 56, 56)  # rope width per (index, row, column) axis
    patch: int = 2
    theta: float = 10000.0
    freq_dim: int = 256  # width of the sinusoidal embedding


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _mm(a, b, round_to):
    if round_to is not None:
        a = a.astype(round_to).astype(jnp.float32)
        b = b.astype(round_to).astype(jnp.float32)
    return jnp.matmul(a, b)


def _linear(p, x, round_to):
    return _mm(x, p["kernel"], round_to) + p["bias"]


def _layer_norm(x):
    """LayerNorm(elementwise_affine=False, eps=1e-6)."""
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + 1e-6)


def _rms_norm(x, scale):
    """The published RMSNorm over the head dimension, eps 1e-6."""
    return x * jax.lax.rsqrt((x * x).mean(axis=-1, keepdims=True) + 1e-6) * scale


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _timestep_embedding(t, dim):
    """`timestep_embedding(t, dim, max_period=10000, time_factor=1000)`."""
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    args = (1000.0 * t.astype(jnp.float32))[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


def _mlp_embedder(p, x, round_to):
    return _linear(p["out_layer"], _silu(_linear(p["in_layer"], x, round_to)), round_to)


def _modulation(p, vec, n, round_to):
    """`Modulation`: lin(silu(vec)) cut into n chunks of [B, 1, width]."""
    out = _linear(p, _silu(vec), round_to)[:, None, :]
    return jnp.split(out, n, axis=-1)


def rope_table(sizes: Sizes, txt_len: int, rows: int, cols: int) -> np.ndarray:
    """`EmbedND` over the position ids: text tokens at (0, 0, 0), image
    tokens at (0, row, column). Returns cos and sin, each
    [txt_len + rows * cols, head_dim / 2], in float64 as published."""
    ids = np.zeros((txt_len + rows * cols, 3), np.float64)
    ids[txt_len:, 1] = np.repeat(np.arange(rows), cols)
    ids[txt_len:, 2] = np.tile(np.arange(cols), rows)
    angles = []
    for axis, dim in enumerate(sizes.axes_dim):
        omega = 1.0 / (sizes.theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
        angles.append(ids[:, axis:axis + 1] * omega[None, :])
    angle = np.concatenate(angles, axis=-1)
    return np.cos(angle), np.sin(angle)


def _apply_rope(x, cos, sin):
    """x [B, L, H, D]; rotate adjacent pairs (x0, x1) by the token's angle."""
    pairs = x.reshape(*x.shape[:-1], -1, 2)
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    out = jnp.stack(
        [cos * pairs[..., 0] - sin * pairs[..., 1],
         sin * pairs[..., 0] + cos * pairs[..., 1]], axis=-1)
    return out.reshape(x.shape)


def _attention(q, k, v, cos, sin, round_to):
    """Rope on q and k, then softmax(q k^T / sqrt(D)) v over the whole
    sequence; [B, L, H, D] in, [B, L, H * D] out. One head at a time
    (`lax.map`), so that the [L, L] scores of 24 heads at 4,608 tokens
    are never all in memory at once; the arithmetic is per head anyway."""
    q, k = _apply_rope(q, cos, sin), _apply_rope(k, cos, sin)

    def one_head(qkv):
        qh, kh, vh = qkv  # [B, L, D]
        scores = _mm(qh, kh.transpose(0, 2, 1), round_to) / math.sqrt(qh.shape[-1])
        return _mm(jax.nn.softmax(scores, axis=-1), vh, round_to)

    out = jax.lax.map(one_head, tuple(a.transpose(2, 0, 1, 3) for a in (q, k, v)))
    h, b, n, d = out.shape
    return out.transpose(1, 2, 0, 3).reshape(b, n, h * d)


def _heads(qkv, heads):
    """"B L (K H D) -> K B L H D" with K = 3."""
    b, n, width = qkv.shape
    q, k, v = jnp.split(qkv, 3, axis=-1)
    shape = (b, n, heads, width // 3 // heads)
    return q.reshape(shape), k.reshape(shape), v.reshape(shape)


@functools.partial(jax.jit, static_argnames=("heads", "round_to"))
def _double_block(p, img, txt, vec, cos, sin, heads, round_to):
    """`DoubleStreamBlock`: two streams with their own weights, one
    attention over [txt; img]."""
    p = _f32(p)
    streams = {}
    for name, x in (("img", img), ("txt", txt)):
        mod = _modulation(p[f"{name}_mod_lin"], vec, 6, round_to)
        h = (1 + mod[1]) * _layer_norm(x) + mod[0]
        q, k, v = _heads(_linear(p[f"{name}_attn_qkv"], h, round_to), heads)
        q = _rms_norm(q, p[f"{name}_attn_norm_q"]["scale"])
        k = _rms_norm(k, p[f"{name}_attn_norm_k"]["scale"])
        streams[name] = (x, mod, q, k, v)
    q, k, v = (
        jnp.concatenate([streams["txt"][i], streams["img"][i]], axis=1) for i in (2, 3, 4)
    )
    attn = _attention(q, k, v, cos, sin, round_to)
    txt_len = txt.shape[1]
    out = {}
    for name, a in (("img", attn[:, txt_len:]), ("txt", attn[:, :txt_len])):
        x, mod = streams[name][:2]
        x = x + mod[2] * _linear(p[f"{name}_attn_proj"], a, round_to)
        h = (1 + mod[4]) * _layer_norm(x) + mod[3]
        h = _gelu_tanh(_linear(p[f"{name}_mlp_0"], h, round_to))
        out[name] = x + mod[5] * _linear(p[f"{name}_mlp_2"], h, round_to)
    return out["img"], out["txt"]


@functools.partial(jax.jit, static_argnames=("heads", "round_to"))
def _single_block(p, x, vec, cos, sin, heads, round_to):
    """`SingleStreamBlock`: qkv and the MLP's first layer in one linear,
    the attention's projection and the MLP's second in another."""
    p = _f32(p)
    width = x.shape[-1]
    shift, scale, gate = _modulation(p["modulation_lin"], vec, 3, round_to)
    h = (1 + scale) * _layer_norm(x) + shift
    fused = _linear(p["linear1"], h, round_to)
    q, k, v = _heads(fused[..., : 3 * width], heads)
    q = _rms_norm(q, p["norm_q"]["scale"])
    k = _rms_norm(k, p["norm_k"]["scale"])
    attn = _attention(q, k, v, cos, sin, round_to)
    both = jnp.concatenate([attn, _gelu_tanh(fused[..., 3 * width:])], axis=-1)
    return x + gate * _linear(p["linear2"], both, round_to)


@functools.partial(jax.jit, static_argnames=("sizes", "round_to"))
def _embed(p, x, t, context, pooled, guidance, sizes, round_to):
    """img_in over 2x2 patches, txt_in, and the conditioning vector:
    time_in + guidance_in + vector_in."""
    p = _f32(p)
    b, hh, ww, c = x.shape
    ps = sizes.patch
    # "b c (h ph) (w pw) -> b (h w) (c ph pw)" from an NHWC latent
    tokens = x.reshape(b, hh // ps, ps, ww // ps, ps, c)
    tokens = tokens.transpose(0, 1, 3, 5, 2, 4).reshape(b, -1, c * ps * ps)
    img = _linear(p["img_in"], tokens.astype(jnp.float32), round_to)
    txt = _linear(p["txt_in"], context.astype(jnp.float32), round_to)
    vec = _mlp_embedder(p["time_in"], _timestep_embedding(t, sizes.freq_dim), round_to)
    if "guidance_in" in p:
        vec = vec + _mlp_embedder(
            p["guidance_in"], _timestep_embedding(guidance, sizes.freq_dim), round_to)
    vec = vec + _mlp_embedder(p["vector_in"], pooled.astype(jnp.float32), round_to)
    return img, txt, vec


@functools.partial(jax.jit, static_argnames=("shape", "patch", "round_to"))
def _final(p, img, vec, shape, patch, round_to):
    """`LastLayer`, then tokens back to an NHWC latent."""
    p = _f32(p)
    shift, scale = _modulation(p["final_layer_adaLN_lin"], vec, 2, round_to)
    out = _linear(p["final_layer_linear"], (1 + scale) * _layer_norm(img) + shift, round_to)
    b, hh, ww, c = shape
    out = out.reshape(b, hh // patch, ww // patch, c, patch, patch)
    return out.transpose(0, 1, 4, 2, 5, 3).reshape(b, hh, ww, c)


def velocity(params, sizes: Sizes, x, t, context, pooled, guidance, round_to=None):
    """One evaluation: the velocity the model predicts for latents `x`
    [B, h, w, C] at flow time `t` [B], text states `context` [B, T, 4096],
    pooled text `pooled` [B, 768] and distilled guidance `guidance` [B].
    `params` is the system's tree for the denoiser."""
    p = params.get("params", params)
    with jax.default_matmul_precision("highest"):
        top = {k: v for k, v in p.items()
               if not k.startswith(("double_blocks_", "single_blocks_", "final_layer_"))}
        img, txt, vec = _embed(top, x, t, context, pooled, guidance, sizes, round_to)
        cos, sin = rope_table(
            sizes, txt.shape[1], x.shape[1] // sizes.patch, x.shape[2] // sizes.patch)
        cos, sin = jnp.asarray(cos, jnp.float32), jnp.asarray(sin, jnp.float32)
        depth = lambda kind: sum(k.startswith(kind) for k in p)  # noqa: E731
        for i in range(depth("double_blocks_")):
            img, txt = _double_block(
                p[f"double_blocks_{i}"], img, txt, vec, cos, sin, sizes.heads, round_to)
        stream = jnp.concatenate([txt, img], axis=1)
        for i in range(depth("single_blocks_")):
            stream = _single_block(
                p[f"single_blocks_{i}"], stream, vec, cos, sin, sizes.heads, round_to)
        last = {k: v for k, v in p.items() if k.startswith("final_layer_")}
        return _final(last, stream[:, txt.shape[1]:], vec, x.shape, sizes.patch, round_to)


def flow_sigmas(steps: int, shift: float) -> np.ndarray:
    """steps + 1 flow times from 1 to 0, uniform and then shifted:
    `time_shift` of the published `get_schedule` with exp(mu) = shift."""
    t = np.linspace(1.0, 0.0, steps + 1)
    return (shift * t / (1.0 + (shift - 1.0) * t)).astype(np.float32)


def sample_euler(params, sizes: Sizes, noise, context, pooled, guidance,
                 steps: int = 20, shift: float = 3.0, round_to=None):
    """The published `denoise` loop from pure noise:
    `x += (s_next - s) * v(x, s)` over `flow_sigmas`."""
    x = jnp.asarray(noise, jnp.float32)
    sigmas = flow_sigmas(steps, shift)
    for s, s_next in zip(sigmas[:-1], sigmas[1:]):
        t = jnp.full((x.shape[0],), s, jnp.float32)
        x = x + (s_next - s) * velocity(
            params, sizes, x, t, context, pooled, guidance, round_to)
    return x
