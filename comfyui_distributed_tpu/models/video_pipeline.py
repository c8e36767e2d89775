"""Video generation pipeline (WAN-class t2v / i2v).

The model family behind the reference's WAN workflows (reference
workflows/distributed-wan*.json), end to end: text → video frames.
Latents are [B, F, h, w, C]; the image VAE decodes frames via vmap
over the frame axis (temporal-compression VAEs slot in behind the
same decode_frames interface).

Distribution:
- seed-parallel: one video per mesh participant (t2v_parallel), the
  reference's Image-Batch-Divider fan-out collapsed into SPMD;
- context-parallel: frames sharded + ring attention for videos whose
  sequence exceeds one chip (parallel/sequence.py) — beyond-reference.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..ops import samplers as smp
from ..parallel.mesh import DATA_AXIS, data_axis_size, shard_map_compat
from ..parallel.seeds import participant_keys
from .pipeline import _Static, maybe_cast_params
from .registry import create_model, get_config, model_family
from .t5_encoder import T5Tokenizer
from .text_encoder import Tokenizer


@dataclasses.dataclass
class VideoPipelineBundle:
    model_name: str
    dit: Any
    vae: Any
    text_encoder: Any
    params: dict[str, Any]
    tokenizer: Tokenizer
    latent_channels: int
    latent_scale: int
    flow_shift: float = 3.0
    # i2v: CLIP vision tower for image conditioning (WAN i2v layout)
    clip_vision: Any = None
    # 1 for per-frame 2D VAEs; the WAN causal VAE compresses 4x with
    # the 4n+1 pixel-frame contract
    temporal_scale: int = 1

    def latent_frames(self, frames: int) -> int:
        if self.temporal_scale == 1:
            return frames
        if (frames - 1) % self.temporal_scale != 0:
            raise ValueError(
                f"frame count {frames} must be {self.temporal_scale}n+1 "
                "for this VAE (WAN causal contract)"
            )
        return (frames - 1) // self.temporal_scale + 1


def load_video_pipeline(
    model_name: str = "tiny-dit",
    vae_name: str | None = None,
    te_name: str | None = None,
    seed: int = 0,
    checkpoint: str | None = None,
) -> VideoPipelineBundle:
    """Build a video pipeline; load real DiT weights when a checkpoint
    resolves (explicit `checkpoint` arg, then
    `CDT_CHECKPOINT_DIR/<model_name>.{safetensors,ckpt,gguf}`). WAN 2.x
    DiT state dicts — original `blocks.N.*` layout or ComfyUI-repacked
    `model.diffusion_model.*` — map key-by-key into the VideoDiT tree
    (sd_checkpoint.wan_schedule). A T5-family encoder (te_name=
    "umt5-xxl") and a video-VAE family VAE (vae_name="wan-vae")
    likewise load their own checkpoint files when they resolve by
    name — the full real-weight WAN stack is DiT + umt5-xxl +
    wan-vae (+ clip-vision-h for i2v)."""
    from . import sd_checkpoint as sdc

    tiny = model_name.startswith("tiny")
    # non-tiny video models default to the causal WAN VAE (the real
    # stack); tiny tests keep the cheap per-frame 2D VAE. The text
    # encoder defaults to CLIP-L for init cost — pass te_name=
    # "umt5-xxl" for the full real-weight WAN stack (a random-init
    # UMT5-XXL is ~6B params, pointless without its checkpoint).
    vae_name = vae_name or ("tiny-vae-video" if tiny else "wan-vae")
    te_name = te_name or ("tiny-te" if tiny else "clip-l")

    dit = create_model(model_name)
    vae = create_model(vae_name)
    te = create_model(te_name)
    dit_cfg = get_config(model_name)
    te_cfg = get_config(te_name)
    vae_cfg = get_config(vae_name)

    root = jax.random.key(seed)
    k_dit, k_vae, k_te = jax.random.split(root, 3)
    lat = jnp.zeros((1, 4, 8, 8, dit_cfg.in_channels))
    ctx = jnp.zeros((1, te_cfg.max_length, dit_cfg.context_dim))
    i2v = getattr(dit_cfg, "i2v", False)
    clip_vision = None
    cv_params = None
    if i2v:
        from .clip_vision import build_clip_vision

        cv_name = "tiny-clip-vision" if tiny else "clip-vision-h"
        clip_vision, cv_cfg, cv_params = build_clip_vision(
            cv_name, jax.random.fold_in(k_te, 7)
        )
        embeds = jnp.zeros((1, cv_cfg.tokens, dit_cfg.img_dim))
        dit_params = dit.init(k_dit, lat, jnp.zeros((1,)), ctx, embeds)
    else:
        dit_params = dit.init(k_dit, lat, jnp.zeros((1,)), ctx)
    video_vae = model_family(vae_name) == "video_vae"
    if video_vae:
        tds = vae_cfg.temporal_downscale
        vae_params = vae.init(k_vae, jnp.zeros((1, tds + 1, 32, 32, 3)))
        vae_ckpt = sdc.find_checkpoint(vae_name)
        if vae_ckpt:
            from ..utils.logging import log

            log(f"loading WAN VAE checkpoint {vae_ckpt} for {vae_name}")
            vae_params, _ = sdc.load_wan_vae_weights(
                sdc.read_checkpoint(vae_ckpt), vae_cfg, vae_params
            )
    else:
        vae_params = vae.init(k_vae, jnp.zeros((1, 32, 32, 3)))
    te_params = te.init(k_te, jnp.zeros((1, te_cfg.max_length), jnp.int32))

    ckpt_path = checkpoint or sdc.find_checkpoint(model_name)
    if ckpt_path:
        from ..utils.logging import log

        log(f"loading WAN checkpoint {ckpt_path} for {model_name}")
        state_dict = sdc.read_checkpoint(ckpt_path)
        dit_params, _problems = sdc.load_wan_weights(
            state_dict, dit_cfg, dit_params
        )

    # T5-family encoder: its own checkpoint file (the reference loads
    # umt5 separately through CLIPLoader) resolves by encoder name
    if model_family(te_name) == "t5_encoder":
        te_ckpt = sdc.find_checkpoint(te_name)
        if te_ckpt:
            from ..utils.logging import log

            log(f"loading T5 encoder checkpoint {te_ckpt} for {te_name}")
            te_params, _ = sdc.load_t5_weights(
                sdc.read_checkpoint(te_ckpt), te_cfg, te_params
            )
        tokenizer = T5Tokenizer(
            max_length=te_cfg.max_length, vocab_size=te_cfg.vocab_size
        )
    else:
        tokenizer = Tokenizer(
            max_length=te_cfg.max_length,
            pad_id=getattr(te_cfg, "pad_token_id", None),
        )

    params = {"unet": dit_params, "vae": vae_params, "te": te_params}
    if cv_params is not None:
        params["clip_vision"] = cv_params
    return VideoPipelineBundle(
        model_name=model_name,
        dit=dit,
        vae=vae,
        text_encoder=te,
        params=maybe_cast_params(params),
        tokenizer=tokenizer,
        latent_channels=vae_cfg.latent_channels,
        latent_scale=vae_cfg.downscale,
        clip_vision=clip_vision,
        temporal_scale=(
            vae_cfg.temporal_downscale if video_vae else 1
        ),
    )


def encode_video_text(bundle: VideoPipelineBundle, texts: list[str]) -> jax.Array:
    tokens = jnp.asarray(bundle.tokenizer.encode_batch(texts))
    hidden, _ = bundle.text_encoder.apply(bundle.params["te"], tokens)
    ctx_dim = get_config(bundle.model_name).context_dim
    if hidden.shape[-1] < ctx_dim:
        hidden = jnp.pad(hidden, ((0, 0), (0, 0), (0, ctx_dim - hidden.shape[-1])))
    elif hidden.shape[-1] > ctx_dim:
        hidden = hidden[..., :ctx_dim]
    return hidden


def decode_frames(bundle: VideoPipelineBundle, latents: jax.Array) -> jax.Array:
    """[B, F_lat, h, w, C] latents → [B, F, H, W, 3] frames. Per-frame
    2D VAEs decode frame-wise (F == F_lat); the causal 3D VAE expands
    time 4x (F = 4(F_lat - 1) + 1)."""
    if bundle.temporal_scale != 1:
        return bundle.vae.apply(bundle.params["vae"], latents, method="decode")
    b, f = latents.shape[:2]
    flat = latents.reshape((b * f,) + latents.shape[2:])
    frames = bundle.vae.apply(bundle.params["vae"], flat, method="decode")
    return frames.reshape((b, f) + frames.shape[1:])


def _video_model_fn(bundle: VideoPipelineBundle, params):
    def model_fn(x, t_batch, context):
        return bundle.dit.apply(params["unet"], x, t_batch, context).astype(x.dtype)

    return model_fn


@partial(
    jax.jit,
    static_argnames=(
        "bundle_static", "frames", "height", "width", "steps", "cfg_scale",
        "batch",
    ),
)
def _t2v_jit(
    bundle_static, params, pos, neg, key,
    frames: int, height: int, width: int, steps: int, cfg_scale: float,
    batch: int,
):
    bundle = bundle_static.value
    lh, lw = height // bundle.latent_scale, width // bundle.latent_scale
    timesteps = smp.get_flow_timesteps(steps, bundle.flow_shift)
    lf = bundle.latent_frames(frames)
    x = jax.random.normal(
        key, (batch, lf, lh, lw, bundle.latent_channels)
    )
    model = smp.cfg_flow_model(_video_model_fn(bundle, params), cfg_scale)
    latents = smp.sample_flow(model, x, timesteps, (pos, neg))
    return decode_frames(bundle, latents)


def t2v(
    bundle: VideoPipelineBundle,
    prompt: str,
    negative_prompt: str = "",
    frames: int = 17,
    height: int = 256,
    width: int = 256,
    steps: int = 20,
    cfg_scale: float = 5.0,
    seed: int = 0,
    batch: int = 1,
) -> jax.Array:
    """Text→video; returns [batch, frames, H, W, 3] in [0,1]."""
    pos = encode_video_text(bundle, [prompt] * batch)
    neg = encode_video_text(bundle, [negative_prompt] * batch)
    return _t2v_jit(
        _Static(bundle), bundle.params, pos, neg, jax.random.key(seed),
        frames, height, width, steps, float(cfg_scale), batch,
    )


@partial(
    jax.jit,
    static_argnames=(
        "bundle_static", "mesh_static", "frames", "height", "width", "steps",
        "cfg_scale",
    ),
)
def _t2v_parallel_jit(
    bundle_static, mesh_static, params, keys, pos, neg,
    frames: int, height: int, width: int, steps: int, cfg_scale: float,
):
    bundle = bundle_static.value
    mesh = mesh_static.value
    lh, lw = height // bundle.latent_scale, width // bundle.latent_scale
    timesteps = smp.get_flow_timesteps(steps, bundle.flow_shift)

    def per_chip(keys_shard, params, pos, neg):
        key = keys_shard[0]
        lf = bundle.latent_frames(frames)
        x = jax.random.normal(key, (1, lf, lh, lw, bundle.latent_channels))
        model = smp.cfg_flow_model(_video_model_fn(bundle, params), cfg_scale)
        latents = smp.sample_flow(model, x, timesteps, (pos, neg))
        return decode_frames(bundle, latents)

    return shard_map_compat(
        per_chip,
        mesh=mesh,
        in_specs=(P(DATA_AXIS), P(), P(), P()),
        out_specs=P(DATA_AXIS),
        check=False,
    )(keys, params, pos, neg)


def t2v_parallel(
    bundle: VideoPipelineBundle,
    mesh,
    prompt: str,
    negative_prompt: str = "",
    frames: int = 17,
    height: int = 256,
    width: int = 256,
    steps: int = 20,
    cfg_scale: float = 5.0,
    seed: int = 0,
) -> jax.Array:
    """One video per mesh participant from independent folded seeds;
    returns [n_participants, frames, H, W, 3] participant-major."""
    n = data_axis_size(mesh)
    keys = participant_keys(jax.random.key(seed), n)
    keys = jax.device_put(keys, NamedSharding(mesh, P(DATA_AXIS)))
    pos = encode_video_text(bundle, [prompt])
    neg = encode_video_text(bundle, [negative_prompt])
    params = jax.device_put(bundle.params, NamedSharding(mesh, P()))
    return _t2v_parallel_jit(
        _Static(bundle), _Static(mesh), params, keys,
        jax.device_put(pos, NamedSharding(mesh, P())),
        jax.device_put(neg, NamedSharding(mesh, P())),
        frames, height, width, steps, float(cfg_scale),
    )


# --- image-to-video -------------------------------------------------------

def encode_frames(bundle: VideoPipelineBundle, frames: jax.Array) -> jax.Array:
    """[B, F, H, W, 3] → [B, F_lat, h, w, C] VAE encode (per-frame for
    2D VAEs; 4x temporal compression for the causal 3D VAE)."""
    if bundle.temporal_scale != 1:
        return bundle.vae.apply(bundle.params["vae"], frames, method="encode")
    b, f = frames.shape[:2]
    flat = frames.reshape((b * f,) + frames.shape[2:])
    z = bundle.vae.apply(bundle.params["vae"], flat, method="encode")
    return z.reshape((b, f) + z.shape[1:])


@partial(
    jax.jit,
    static_argnames=("bundle_static", "frames", "steps", "cfg_scale"),
)
def _i2v_jit(
    bundle_static, params, ref_latent, pos, neg, key,
    frames: int, steps: int, cfg_scale: float,
):
    bundle = bundle_static.value
    b = ref_latent.shape[0]
    lh, lw, c = ref_latent.shape[2], ref_latent.shape[3], ref_latent.shape[4]
    timesteps = smp.get_flow_timesteps(steps, bundle.flow_shift)
    noise_key, _ = jax.random.split(key)
    lf = bundle.latent_frames(frames)
    noise = jax.random.normal(noise_key, (b, lf, lh, lw, c))
    # known region = latent frame 0 carries the reference latent
    known = jnp.concatenate(
        [ref_latent, jnp.zeros((b, lf - 1, lh, lw, c))], axis=1
    )
    mask = jnp.zeros((1, lf, 1, 1, 1)).at[:, 0].set(1.0)
    model = smp.cfg_flow_model(_video_model_fn(bundle, params), cfg_scale)
    latents = smp.sample_flow_masked(
        model, noise, timesteps, (pos, neg), known, mask, noise
    )
    return decode_frames(bundle, latents)


@partial(
    jax.jit,
    static_argnames=("bundle_static", "frames", "steps", "cfg_scale"),
)
def _i2v_native_jit(
    bundle_static, params, y, image_embeds, pos, neg, key,
    frames: int, steps: int, cfg_scale: float,
):
    """WAN-i2v-layout sampling: the model input is
    [noise 16 | mask 4 | conditioning latent 16] per frame, with image
    cross-attention over CLIP tokens (models/dit.py i2v branch).

    `y` is the VAE encoding of the full padded PIXEL clip (reference
    first frame + mid-gray blanks), matching the reference WAN i2v
    conditioning — NOT zero latents, which are off the VAE manifold."""
    bundle = bundle_static.value
    b, lf, lh, lw, c = y.shape
    timesteps = smp.get_flow_timesteps(steps, bundle.flow_shift)
    noise = jax.random.normal(key, (b, lf, lh, lw, c))
    # conditioning channels: 4-channel latent-frame mask (1 = given) +
    # the padded-clip encoding, fixed across steps
    mask = jnp.zeros((b, lf, lh, lw, 4)).at[:, 0].set(1.0)
    cond_channels = jnp.concatenate([mask, y], axis=-1)

    def model_fn(x, t_batch, context):
        # the CFG wrapper doubles the batch (pos|neg); the image
        # conditioning is identical for both halves
        reps = x.shape[0] // cond_channels.shape[0]
        cc = jnp.tile(cond_channels, (reps, 1, 1, 1, 1))
        emb = jnp.tile(image_embeds, (reps, 1, 1))
        inp = jnp.concatenate([x, cc], axis=-1)
        return bundle.dit.apply(
            params["unet"], inp, t_batch, context, emb
        ).astype(x.dtype)

    model = smp.cfg_flow_model(model_fn, cfg_scale)
    latents = smp.sample_flow(model, noise, timesteps, (pos, neg))
    return decode_frames(bundle, latents)


def encode_image_embeds(bundle: VideoPipelineBundle, image: jax.Array) -> jax.Array:
    """[B, H, W, 3] → CLIP penultimate tokens [B, T, width] (i2v only)."""
    return bundle.clip_vision.apply(bundle.params["clip_vision"], image)


def i2v(
    bundle: VideoPipelineBundle,
    image: jax.Array,            # [B, H, W, 3] first frame
    prompt: str,
    negative_prompt: str = "",
    frames: int = 17,
    steps: int = 20,
    cfg_scale: float = 5.0,
    seed: int = 0,
) -> jax.Array:
    """Image-to-video; returns [B, frames, H, W, 3] (the WAN i2v
    workflow role, reference workflows/distributed-wan i2v variant).

    i2v-layout models (cfg.i2v) run the native WAN conditioning:
    channel-concat mask + reference latent, plus CLIP-token image
    cross-attention. Other video models fall back to clamping frame 0
    to the reference latent along the flow path (masked flow)."""
    b = int(image.shape[0])
    pos = encode_video_text(bundle, [prompt] * b)
    neg = encode_video_text(bundle, [negative_prompt] * b)
    cfg = get_config(bundle.model_name)
    if getattr(cfg, "i2v", False):
        embeds = encode_image_embeds(bundle, image)
        # conditioning latent = encoding of the padded PIXEL clip
        # (reference frame + mid-gray blanks), the reference WAN i2v
        # construction
        blanks = jnp.full(
            (image.shape[0], frames - 1) + image.shape[1:], 0.5, image.dtype
        )
        y = encode_frames(
            bundle, jnp.concatenate([image[:, None], blanks], axis=1)
        )
        return _i2v_native_jit(
            _Static(bundle), bundle.params, y, embeds, pos, neg,
            jax.random.key(seed), frames, steps, float(cfg_scale),
        )
    ref = encode_frames(bundle, image[:, None])  # [B, 1, h, w, C]
    return _i2v_jit(
        _Static(bundle), bundle.params, ref, pos, neg,
        jax.random.key(seed), frames, steps, float(cfg_scale),
    )
