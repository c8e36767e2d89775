"""Image multimodal diffusion transformer (Flux class), flax.linen.

The rectified-flow image family the reference serves through ComfyUI's
model zoo (its conditioning utilities explicitly handle Flux-class
`reference_latents`, reference utils/usdu_utils.py crop_cond), rebuilt
TPU-native and *checkpoint-faithful* to the published Flux layout:

- 2x2 patchified 16-channel latents and T5 text tokens run as two
  streams through `double_blocks` (separate modulation/attention/MLP
  params, one joint attention over [txt; img]), then concatenated
  through fused `single_blocks` (qkv+MLP in one linear pair);
- per-head RMS Q/K norm (query_norm/key_norm.scale over head_dim —
  unlike WAN's full-width norms, dit.py);
- 3-axis rotary embeddings with an explicit per-axis frequency budget
  (`axes_dim`, default 16/56/56 of head_dim 128): text tokens sit at
  position 0 of every axis, image tokens at (0, y, x);
- conditioning vector = time MLP + CLIP pooled MLP (+ distilled
  guidance MLP when `guidance_embed`), modulating every block (adaLN)
  and the final layer.

Flax submodule names mirror the original state-dict keys
(double_blocks_N/img_attn_qkv ↔ double_blocks.N.img_attn.qkv, ...) so
the key schedule in sd_checkpoint stays a straight rename.

The model predicts rectified-flow velocity v = noise - x0; with the
sampler eps contract (denoised = x - sigma*eps) v IS eps, so the
deterministic k-diffusion samplers (euler, ddim, heun, dpmpp_2m, ...)
apply unchanged — models/pipeline.py selects the flow sigma schedule
and interpolation noising via `parameterization == "flow"`. Stochastic
renoising is a different story: the VE rule (x += noise*sigma_up) is
off the flow marginal x_t = (1-s)x0 + s*n, so ops/samplers.sample
routes euler_ancestral to an RF-correct rule and rejects the other
stochastic samplers for flow models.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .layers import timestep_embedding
from .dit import _axis_freqs, apply_rope
from ..ops.qk_norm_rope import norm_rope, norm_rope_route
from ..ops.attention import dot_product_attention


@dataclasses.dataclass(frozen=True)
class MMDiTConfig:
    in_channels: int = 16          # VAE latent channels
    patch_size: int = 2
    hidden_dim: int = 3072
    double_depth: int = 19
    single_depth: int = 38
    heads: int = 24
    # rope frequency budget per (const, y, x) axis; must sum to head_dim
    axes_dim: tuple[int, int, int] = (16, 56, 56)
    context_dim: int = 4096        # T5 hidden width
    vec_dim: int = 768             # CLIP pooled width
    mlp_ratio: float = 4.0
    freq_dim: int = 256            # sinusoidal embedding width
    theta: float = 10000.0
    # guidance-distilled variants (flux-dev) embed the guidance scale;
    # schnell-class models don't
    guidance_embed: bool = True
    guidance_default: float = 3.5
    # rectified flow: pipeline selects flow sigmas + interpolation
    # noising off this marker (models/pipeline.py, ops/samplers.py)
    parameterization: str = "flow"
    # static timestep-shift of the flow schedule (t' = s*t/(1+(s-1)t));
    # ~= the 1MP-resolution shift of the published dev config
    flow_shift: float = 3.0
    dtype: str = "bfloat16"
    remat: bool = False

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def head_dim(self) -> int:
        return self.hidden_dim // self.heads

    @property
    def adm_in_channels(self) -> int:
        """Hooks the pooled-text plumbing in pipeline._make_model_fn:
        the CLIP pooled vector feeds vector_in."""
        return self.vec_dim

    @property
    def mlp_width(self) -> int:
        return int(self.hidden_dim * self.mlp_ratio)


def rope_freqs_image(
    axes_dim: tuple[int, int, int],
    txt_len: int,
    gh: int,
    gw: int,
    theta: float = 10000.0,
    ref_grids: tuple[tuple[int, int], ...] = (),
) -> np.ndarray:
    """[txt_len + gh*gw + sum(ref), head_dim/2, 2] cos/sin table: text
    tokens at position 0 of every axis (identity rotation), image
    tokens at (0, y, x), and each reference-latent grid at
    (1 + ref_index, y, x) — the Flux / Flux-Kontext position-id
    convention (reference images are offset along the first axis)."""
    k0, kh, kw = axes_dim[0] // 2, axes_dim[1] // 2, axes_dim[2] // 2
    t0 = _axis_freqs(2 * k0, len(ref_grids) + 1, theta)

    def grid(g_h: int, g_w: int, idx0: int) -> np.ndarray:
        th = _axis_freqs(2 * kh, g_h, theta)
        tw = _axis_freqs(2 * kw, g_w, theta)
        return np.concatenate(
            [
                np.broadcast_to(t0[idx0][None, None], (g_h, g_w, k0, 2)),
                np.broadcast_to(th[:, None], (g_h, g_w, kh, 2)),
                np.broadcast_to(tw[None, :], (g_h, g_w, kw, 2)),
            ],
            axis=2,
        ).reshape(g_h * g_w, -1, 2)

    img = grid(gh, gw, 0)
    pairs = img.shape[1]
    txt = np.broadcast_to(
        np.stack([np.ones(pairs), np.zeros(pairs)], axis=-1)[None],
        (txt_len, pairs, 2),
    )
    sections = [txt, img] + [
        grid(rh, rw, i + 1) for i, (rh, rw) in enumerate(ref_grids)
    ]
    return np.concatenate(sections, axis=0)


class _MLPEmbedder(nn.Module):
    """Flux MLPEmbedder: in_layer → silu → out_layer (time_in /
    vector_in / guidance_in)."""

    width: int

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        h = nn.Dense(self.width, dtype=jnp.float32, name="in_layer")(
            x.astype(jnp.float32)
        )
        return nn.Dense(self.width, dtype=jnp.float32, name="out_layer")(
            nn.silu(h)
        )


def _modulation(vec: jax.Array, n: int, width: int, name: str) -> list[jax.Array]:
    """silu(vec) → Dense(n*width) → n [B, 1, width] chunks (Flux
    Modulation; name maps <name>.lin)."""
    out = nn.Dense(n * width, dtype=jnp.float32, name=f"{name}_lin")(
        nn.silu(vec.astype(jnp.float32))
    )
    return [out[:, None, i * width:(i + 1) * width] for i in range(n)]


def _qk_norm_rope(
    proj: jax.Array, freqs: jax.Array, heads: int, dtype: jnp.dtype, name: str,
) -> tuple[jax.Array, jax.Array]:
    """q and k out of a block's projection `proj` ([B, N, >= 2*dim]: q
    in the first `dim` lanes, k in the next), each head RMS-normed over
    head_dim (scale params are [D] — the Flux query_norm/key_norm.scale
    layout), rounded to `dtype` and rotated by `freqs` [N, D/2, 2];
    [B, N, H, D] each. On a TPU one Pallas pass a tensor
    (`ops/qk_norm_rope.norm_rope`: it reads the heads where the linear
    left them and writes them where the attention kernel reads them),
    elsewhere the XLA operations it stands for."""
    prefix = f"{name}_" if name else ""
    b, n, _ = proj.shape
    hd = 2 * freqs.shape[1]
    dim = heads * hd
    out = []
    for i, which in enumerate("qk"):
        norm = nn.RMSNorm(epsilon=1e-6, dtype=jnp.float32, name=f"{prefix}norm_{which}")
        if norm_rope_route(n, heads, hd, i * dim) == "pallas":
            norm(jnp.zeros((1, hd), jnp.float32))  # declares `scale`; no work is left of it
            x = norm_rope(
                proj, norm.variables["params"]["scale"], freqs,
                heads=heads, offset=i * dim, epsilon=norm.epsilon,
            )
        else:
            x = proj[..., i * dim:(i + 1) * dim].reshape(b, n, heads, hd)
            x = apply_rope(norm(x).astype(dtype), freqs)
        out.append(x.reshape(b, n, heads, hd))
    return out[0], out[1]


class _DoubleBlock(nn.Module):
    """Flux DoubleStreamBlock: separate img/txt streams, one joint
    attention over [txt; img] tokens."""

    heads: int
    mlp_width: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(
        self,
        img: jax.Array,     # [B, Ni, H]
        txt: jax.Array,     # [B, Nt, H]
        vec: jax.Array,     # [B, H]
        freqs: jax.Array,   # [Nt+Ni, D/2, 2]
    ) -> tuple[jax.Array, jax.Array]:
        dim = img.shape[-1]
        hd = dim // self.heads
        b, ni, _ = img.shape
        nt = txt.shape[1]

        i_sh1, i_sc1, i_g1, i_sh2, i_sc2, i_g2 = _modulation(vec, 6, dim, "img_mod")
        t_sh1, t_sc1, t_g1, t_sh2, t_sc2, t_g2 = _modulation(vec, 6, dim, "txt_mod")

        def qkv(x, n, sh, sc, rope, name):
            h = nn.LayerNorm(
                use_bias=False, use_scale=False, dtype=jnp.float32,
                name=f"{name}_norm1",
            )(x.astype(jnp.float32))
            h = (h * (1 + sc) + sh).astype(self.dtype)
            proj = nn.Dense(3 * dim, dtype=self.dtype, name=f"{name}_attn_qkv")(h)
            q, k = _qk_norm_rope(proj, rope, self.heads, self.dtype, f"{name}_attn")
            return q, k, proj[..., 2 * dim:].reshape(b, n, self.heads, hd)

        # joint attention, text tokens first (Flux token order)
        iq, ik, iv = qkv(img, ni, i_sh1, i_sc1, freqs[nt:], "img")
        tq, tk, tv = qkv(txt, nt, t_sh1, t_sc1, freqs[:nt], "txt")
        q = jnp.concatenate([tq, iq], axis=1)
        k = jnp.concatenate([tk, ik], axis=1)
        v = jnp.concatenate([tv, iv], axis=1)
        with jax.named_scope("joint_attn"):
            attn = dot_product_attention(q, k, v).reshape(b, nt + ni, dim)
        t_attn, i_attn = attn[:, :nt], attn[:, nt:]

        def stream(x, a, sh2, sc2, g1, g2, name):
            x = (
                x.astype(jnp.float32)
                + nn.Dense(dim, dtype=self.dtype, name=f"{name}_attn_proj")(
                    a
                ).astype(jnp.float32) * g1
            )
            h = nn.LayerNorm(
                use_bias=False, use_scale=False, dtype=jnp.float32,
                name=f"{name}_norm2",
            )(x)
            h = (h * (1 + sc2) + sh2).astype(self.dtype)
            h = nn.Dense(self.mlp_width, dtype=self.dtype, name=f"{name}_mlp_0")(h)
            h = nn.gelu(h, approximate=True)
            y = nn.Dense(dim, dtype=self.dtype, name=f"{name}_mlp_2")(h)
            return (x + y.astype(jnp.float32) * g2).astype(self.dtype)

        img = stream(img, i_attn, i_sh2, i_sc2, i_g1, i_g2, "img")
        txt = stream(txt, t_attn, t_sh2, t_sc2, t_g1, t_g2, "txt")
        return img, txt


class _SingleBlock(nn.Module):
    """Flux SingleStreamBlock: fused qkv+MLP linear over the
    concatenated [txt; img] stream."""

    heads: int
    mlp_width: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(
        self, x: jax.Array, vec: jax.Array, freqs: jax.Array
    ) -> jax.Array:
        dim = x.shape[-1]
        hd = dim // self.heads
        b, n, _ = x.shape

        sh, sc, gate = _modulation(vec, 3, dim, "modulation")
        h = nn.LayerNorm(
            use_bias=False, use_scale=False, dtype=jnp.float32, name="pre_norm"
        )(x.astype(jnp.float32))
        h = (h * (1 + sc) + sh).astype(self.dtype)
        fused = nn.Dense(
            3 * dim + self.mlp_width, dtype=self.dtype, name="linear1"
        )(h)
        mlp = fused[..., 3 * dim:]
        # single_blocks.N.norm.{query,key}_norm
        q, k = _qk_norm_rope(fused, freqs, self.heads, self.dtype, "")
        v = fused[..., 2 * dim:3 * dim].reshape(b, n, self.heads, hd)
        with jax.named_scope("joint_attn"):
            attn = dot_product_attention(q, k, v).reshape(b, n, dim)
        out = nn.Dense(dim, dtype=self.dtype, name="linear2")(
            jnp.concatenate([attn, nn.gelu(mlp, approximate=True)], axis=-1)
        )
        return (x.astype(jnp.float32) + out.astype(jnp.float32) * gate).astype(
            x.dtype
        )


class MMDiT(nn.Module):
    config: MMDiTConfig

    @nn.compact
    def __call__(
        self,
        x: jax.Array,           # [B, h, w, C] noisy latents (NHWC)
        timesteps: jax.Array,   # [B] flow time in [0, 1]
        context: jax.Array,     # [B, T, context_dim] T5 hidden states
        y: jax.Array | None = None,        # [B, vec_dim] CLIP pooled
        control: jax.Array | None = None,  # rejected (Flux ControlNet
        #                                    is a separate architecture)
        guidance: jax.Array | None = None,  # [B] distilled guidance
        ref_latents: list | None = None,   # Kontext: [B, h, w, C] each
    ) -> jax.Array:
        cfg = self.config
        dt = cfg.compute_dtype
        if control is not None:
            # silent no-op would waste the caller's ControlNet compute
            # and produce an uncontrolled image with no explanation
            raise ValueError(
                "Flux-class MMDiT has no ControlNet input path "
                "(Flux ControlNets are a separate architecture)"
            )
        b, hh, ww, c = x.shape
        p = cfg.patch_size
        assert hh % p == 0 and ww % p == 0, "patch misalign"
        assert sum(cfg.axes_dim) == cfg.head_dim, "axes_dim != head_dim"
        gh, gw = hh // p, ww // p
        ni = gh * gw

        def patchify(arr):
            bb, ah, aw, ac = arr.shape
            assert ah % p == 0 and aw % p == 0, "ref patch misalign"
            t = arr.reshape(bb, ah // p, p, aw // p, p, ac)
            return t.transpose(0, 1, 3, 5, 2, 4).reshape(
                bb, (ah // p) * (aw // p), ac * p * p
            )

        # 2x2 patchify; flatten order (c, ph, pw) matches the original
        # rearrange 'b c (h ph) (w pw) -> b (h w) (c ph pw)'
        img_in = nn.Dense(cfg.hidden_dim, dtype=dt, name="img_in")
        img = img_in(patchify(x).astype(dt))
        ref_grids: tuple = ()
        if ref_latents:
            # Flux-Kontext editing: reference latents ride as extra
            # image-stream tokens (same img_in projection, first rope
            # axis offset per reference); only the main image's tokens
            # are unpatchified at the output
            refs = []
            grids = []
            for r in ref_latents:
                # edge-pad odd ref grids to the patch multiple (the
                # parity behavior; the main latent stays strict)
                ph_pad = (-r.shape[1]) % p
                pw_pad = (-r.shape[2]) % p
                if ph_pad or pw_pad:
                    r = jnp.pad(
                        r, ((0, 0), (0, ph_pad), (0, pw_pad), (0, 0)),
                        mode="edge",
                    )
                grids.append((r.shape[1] // p, r.shape[2] // p))
                refs.append(img_in(patchify(r).astype(dt)))
            ref_grids = tuple(grids)
            img = jnp.concatenate([img] + refs, axis=1)
        txt = nn.Dense(cfg.hidden_dim, dtype=dt, name="txt_in")(
            context.astype(dt)
        )
        nt = txt.shape[1]

        # conditioning vector: time + pooled text (+ distilled guidance)
        vec = _MLPEmbedder(cfg.hidden_dim, name="time_in")(
            timestep_embedding(timesteps.astype(jnp.float32) * 1000.0, cfg.freq_dim)
        )
        if cfg.guidance_embed:
            g = (
                guidance
                if guidance is not None
                else jnp.full((b,), cfg.guidance_default, jnp.float32)
            )
            vec = vec + _MLPEmbedder(cfg.hidden_dim, name="guidance_in")(
                timestep_embedding(g.astype(jnp.float32) * 1000.0, cfg.freq_dim)
            )
        if y is None:
            y = jnp.zeros((b, cfg.vec_dim), jnp.float32)
        vec = vec + _MLPEmbedder(cfg.hidden_dim, name="vector_in")(y)

        freqs = jnp.asarray(
            rope_freqs_image(
                cfg.axes_dim, nt, gh, gw, cfg.theta, ref_grids=ref_grids
            ),
            jnp.float32,
        )

        double_cls = (
            nn.remat(_DoubleBlock, static_argnums=()) if cfg.remat else _DoubleBlock
        )
        single_cls = (
            nn.remat(_SingleBlock, static_argnums=()) if cfg.remat else _SingleBlock
        )
        # block scopes in the operations' metadata, as the UNet's
        # down_N / mid / up_N: a device trace groups time by them
        for i in range(cfg.double_depth):
            with jax.named_scope(f"double_{i}"):
                img, txt = double_cls(
                    cfg.heads, cfg.mlp_width, dt, name=f"double_blocks_{i}"
                )(img, txt, vec, freqs)
        stream = jnp.concatenate([txt, img], axis=1)
        for i in range(cfg.single_depth):
            with jax.named_scope(f"single_{i}"):
                stream = single_cls(
                    cfg.heads, cfg.mlp_width, dt, name=f"single_blocks_{i}"
                )(stream, vec, freqs)
        img = stream[:, nt:nt + ni]  # reference tokens are dropped

        # final layer: adaLN (shift, scale) then linear to patch pixels
        with jax.named_scope("final"):
            sh, sc = _modulation(vec, 2, cfg.hidden_dim, "final_layer_adaLN")
            h = nn.LayerNorm(
                use_bias=False, use_scale=False, dtype=jnp.float32
            )(img.astype(jnp.float32))
            h = h * (1 + sc) + sh
            out = nn.Dense(
                c * p * p, dtype=jnp.float32, name="final_layer_linear"
            )(h)
        out = out.reshape(b, gh, gw, c, p, p)
        out = out.transpose(0, 1, 4, 2, 5, 3).reshape(b, hh, ww, c)
        return out
