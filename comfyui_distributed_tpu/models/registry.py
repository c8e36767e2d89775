"""Model family registry: named configs → constructors.

The TPU analog of ComfyUI's checkpoint loader surface the reference
leans on (CheckpointLoaderSimple in reference workflows/*.json): a
model name resolves to (module, config). Weights load from safetensors
when present (utils in io.py), else deterministic random init — the
distributed machinery is weight-agnostic.

`tiny-*` variants are real instances of the same code small enough for
hermetic CPU tests and multi-chip dry runs.
"""

from __future__ import annotations

from typing import Any, Callable

from .clip_vision import ClipVisionConfig, ClipVisionEncoder
from .deepseek_v2 import DeepSeekV2, DeepSeekV2Config
from .dit import DiTConfig, VideoDiT
from .mmdit import MMDiT, MMDiTConfig
from .ouro import Ouro, OuroConfig
from .sd3 import SD3Config, SD3MMDiT
from .dots3 import Dots3, Dots3Config
from .glm_dsa import GlmDsa, GlmDsaConfig
from .granite_hybrid import GraniteHybrid, GraniteHybridConfig
from .k_exaone import KExaone, KExaoneConfig
from .ling_flash import LingFlash, LingFlashConfig
from .longcat_flash import LongcatFlash, LongcatFlashConfig
from .nemotron_h import NemotronH, NemotronHConfig
from .sdar import Sdar, SdarConfig
from .solar_open2 import SolarOpen2, SolarOpen2Config
from .t5_encoder import T5Encoder, T5EncoderConfig
from .text_encoder import TextEncoder, TextEncoderConfig
from .unet import UNet, UNetConfig
from .vae import VAE, VAEConfig
from .video_vae import VideoVAE, VideoVAEConfig

MODEL_REGISTRY: dict[str, dict[str, Any]] = {
    # --- UNet diffusion backbones ---
    "sd15": {
        "family": "unet",
        "config": UNetConfig(
            model_channels=320,
            channel_mult=(1, 2, 4, 4),
            transformer_depth=(1, 1, 1, 0),
            context_dim=768,
            num_heads=8,
            remat=True,
        ),
    },
    # SD1.5 inpainting UNet (runwayml sd-v1-5-inpainting layout):
    # input = concat(noisy latents 4, mask 1, masked-image latents 4)
    # — the InpaintModelConditioning node assembles the extra channels
    "sd15-inpaint": {
        "family": "unet",
        "config": UNetConfig(
            in_channels=9,
            model_channels=320,
            channel_mult=(1, 2, 4, 4),
            transformer_depth=(1, 1, 1, 0),
            context_dim=768,
            num_heads=8,
            remat=True,
        ),
    },
    "sdxl": {
        "family": "unet",
        "config": UNetConfig(
            model_channels=320,
            channel_mult=(1, 2, 4),
            transformer_depth=(0, 2, 10),
            context_dim=2048,
            head_dim=64,  # SDXL num_head_channels convention
            adm_in_channels=2816,
            remat=True,
        ),
    },
    # SD2.1-768-v: SD1.x topology with OpenCLIP-H conditioning
    # (context 1024), num_head_channels=64, velocity prediction
    "sd21": {
        "family": "unet",
        "config": UNetConfig(
            model_channels=320,
            channel_mult=(1, 2, 4, 4),
            transformer_depth=(1, 1, 1, 0),
            context_dim=1024,
            head_dim=64,
            parameterization="v",
            remat=True,
        ),
    },
    # SD2.1-base (512px): same network, epsilon prediction
    "sd21-base": {
        "family": "unet",
        "config": UNetConfig(
            model_channels=320,
            channel_mult=(1, 2, 4, 4),
            transformer_depth=(1, 1, 1, 0),
            context_dim=1024,
            head_dim=64,
            remat=True,
        ),
    },
    "tiny-unet": {
        "family": "unet",
        "config": UNetConfig(
            model_channels=32,
            channel_mult=(1, 2),
            num_res_blocks=1,
            transformer_depth=(1, 1),
            context_dim=64,
            num_heads=2,
        ),
    },
    # tiny inpaint-model variant (9-channel input): exercises the
    # concat-conditioning path of InpaintModelConditioning
    "tiny-unet-inpaint": {
        "family": "unet",
        "config": UNetConfig(
            in_channels=9,
            model_channels=32,
            channel_mult=(1, 2),
            num_res_blocks=1,
            transformer_depth=(1, 1),
            context_dim=64,
            num_heads=2,
        ),
    },
    # tiny v-prediction variant (SD2.x-768-class parameterization):
    # exercises the v->eps conversion through every sampler path
    "tiny-unet-v": {
        "family": "unet",
        "config": UNetConfig(
            model_channels=32,
            channel_mult=(1, 2),
            num_res_blocks=1,
            transformer_depth=(1, 1),
            context_dim=64,
            num_heads=2,
            parameterization="v",
        ),
    },
    # tiny SDXL-shaped variant: dual text encoders + pooled/size adm
    # conditioning (context 64+96, adm = 96 pooled + 6x256 size embs)
    "tiny-unet-adm": {
        "family": "unet",
        "config": UNetConfig(
            model_channels=32,
            channel_mult=(1, 2),
            num_res_blocks=1,
            transformer_depth=(1, 1),
            context_dim=160,
            num_heads=2,
            adm_in_channels=96 + 6 * 256,
        ),
    },
    # --- image MMDiT backbones (Flux checkpoint-faithful dims) ---
    # guidance-distilled dev config; flow_shift 3.0 ~= the published
    # dynamic shift at 1MP resolution
    "flux-dev": {
        "family": "mmdit",
        "config": MMDiTConfig(remat=True),
    },
    # timestep-distilled schnell: no guidance embedding, unshifted
    # schedule, 1-4 steps typical
    "flux-schnell": {
        "family": "mmdit",
        "config": MMDiTConfig(
            guidance_embed=False, flow_shift=1.0, remat=True
        ),
    },
    # flux-dev at a quarter of its depth, the 1 : 2 ratio of block
    # kinds kept and every width as published: what one 16 GB chip
    # holds of the model beside its text encoders (the benchmark's
    # flux.1-dev configuration says what the cut stands for)
    "flux-dev-5x10": {
        "family": "mmdit",
        "config": MMDiTConfig(double_depth=5, single_depth=10, remat=True),
    },
    "tiny-flux": {
        "family": "mmdit",
        "config": MMDiTConfig(
            hidden_dim=32, double_depth=1, single_depth=1, heads=2,
            axes_dim=(4, 6, 6), context_dim=64, vec_dim=64,
            flow_shift=1.0,
        ),
    },
    # --- SD3-class image MMDiT (joint blocks, learned pos table) ---
    # SD3-medium (2B): depth 24 -> hidden 1536, no QK norm
    "sd3-medium": {
        "family": "sd3",
        "config": SD3Config(depth=24, remat=True),
    },
    # SD3.5-large (8B): depth 38, hidden 2432, per-head RMS QK norm
    "sd35-large": {
        "family": "sd3",
        "config": SD3Config(
            depth=38, hidden_dim=2432, heads=38, qk_norm=True, remat=True
        ),
    },
    # SD3.5-medium (2.5B, MMDiT-X): depth 24 -> hidden 1536, QK norm,
    # 384-wide learned pos table, and a second image-only attention
    # branch (attn2, 9-way adaLN) in the first 13 x_blocks
    "sd35-medium": {
        "family": "sd3",
        "config": SD3Config(
            depth=24, qk_norm=True, pos_embed_max=384,
            dual_attn_blocks=13, remat=True,
        ),
    },
    # tiny MMDiT-X: one dual-attention block + one plain, for hermetic
    # forward/schedule/golden coverage of the attn2 branch
    "tiny-sd35m": {
        "family": "sd3",
        "config": SD3Config(
            depth=2, hidden_dim=32, heads=2, context_dim=160,
            pooled_dim=160, pos_embed_max=32, qk_norm=True,
            dual_attn_blocks=1, flow_shift=1.0,
        ),
    },
    # tiny: context 160 = tiny CLIP-L(64) ++ CLIP-G(96) = T5 width;
    # pos table covers USDU's padded 96px tiles (latent 48 / patch 2)
    "tiny-sd3": {
        "family": "sd3",
        "config": SD3Config(
            depth=2, hidden_dim=32, heads=2, context_dim=160,
            pooled_dim=160, pos_embed_max=32, qk_norm=True,
            flow_shift=1.0,
        ),
    },
    # --- video DiT backbones (WAN 2.x checkpoint-faithful dims) ---
    "wan-1.3b": {
        "family": "dit",
        "config": DiTConfig(
            hidden_dim=1536, ffn_dim=8960, depth=30, heads=12, context_dim=4096
        ),
    },
    "wan-14b": {
        "family": "dit",
        "config": DiTConfig(
            hidden_dim=5120, ffn_dim=13824, depth=40, heads=40, context_dim=4096
        ),
    },
    "tiny-dit": {
        "family": "dit",
        "config": DiTConfig(hidden_dim=64, depth=2, heads=2, context_dim=64),
    },
    # i2v variants: [noise 16 | mask 4 | cond latent 16] = 36 input
    # channels, 16 output; image cross-attention branch over CLIP
    # ViT-H penultimate tokens (WAN 2.x i2v checkpoint layout)
    "wan-14b-i2v": {
        "family": "dit",
        "config": DiTConfig(
            hidden_dim=5120, ffn_dim=13824, depth=40, heads=40,
            context_dim=4096, in_channels=36, out_channels=16, i2v=True,
        ),
    },
    "tiny-dit-i2v": {
        "family": "dit",
        "config": DiTConfig(
            hidden_dim=64, depth=2, heads=2, context_dim=64,
            in_channels=36, out_channels=16, i2v=True, img_dim=48,
        ),
    },
    # --- VAEs ---
    "vae-sd": {"family": "vae", "config": VAEConfig()},
    # 16-channel latent 2D VAE (per-frame fallback for the WAN-class
    # DiT latent space; the real WAN VAE is wan-vae below)
    "vae-video": {
        "family": "vae",
        "config": VAEConfig(latent_channels=16, scaling_factor=1.0),
    },
    # causal 3D WAN VAE: 8x spatial / 4x temporal, 4n+1 frame contract.
    # latents_mean/std are the fixed per-channel constants the official
    # Wan2.1 wrapper normalizes with before the DiT.
    "wan-vae": {
        "family": "video_vae",
        "config": VideoVAEConfig(
            latents_mean=(
                -0.7571, -0.7089, -0.9113, 0.1075, -0.1745, 0.9653,
                -0.1517, 1.5508, 0.4134, -0.0715, 0.5517, -0.3632,
                -0.1922, -0.9497, 0.2503, -0.2921,
            ),
            latents_std=(
                2.8184, 1.4541, 2.3275, 2.5017, 2.3632, 2.0435,
                3.3086, 3.0723, 2.0365, 1.9887, 2.6244, 2.0905,
                2.3852, 1.4049, 2.5648, 2.7630,
            ),
        ),
    },
    "tiny-video-vae-3d": {
        "family": "video_vae",
        "config": VideoVAEConfig(
            base_dim=16, dim_mult=(1, 2), num_res_blocks=1,
            temporal_down=(True,),
        ),
    },
    # Flux-class 16-channel AE: (mean - shift) * scale boundary, no
    # 1x1 quant convs in the published layout
    "vae-flux": {
        "family": "vae",
        "config": VAEConfig(
            latent_channels=16, scaling_factor=0.3611, shift_factor=0.1159,
            use_quant_conv=False,
        ),
    },
    "tiny-vae-flux": {
        "family": "vae",
        "config": VAEConfig(
            base_channels=16, channel_mult=(1, 2), num_res_blocks=1,
            latent_channels=16, scaling_factor=0.3611, shift_factor=0.1159,
            use_quant_conv=False,
        ),
    },
    # SD3-class 16ch AE: scale 1.5305, shift 0.0609, no quant convs
    "vae-sd3": {
        "family": "vae",
        "config": VAEConfig(
            latent_channels=16, scaling_factor=1.5305, shift_factor=0.0609,
            use_quant_conv=False,
        ),
    },
    "tiny-vae-sd3": {
        "family": "vae",
        "config": VAEConfig(
            base_channels=16, channel_mult=(1, 2), num_res_blocks=1,
            latent_channels=16, scaling_factor=1.5305, shift_factor=0.0609,
            use_quant_conv=False,
        ),
    },
    "tiny-vae": {
        "family": "vae",
        "config": VAEConfig(base_channels=16, channel_mult=(1, 2), num_res_blocks=1),
    },
    "tiny-vae-video": {
        "family": "vae",
        "config": VAEConfig(
            base_channels=16, channel_mult=(1, 2), num_res_blocks=1,
            latent_channels=16, scaling_factor=1.0,
        ),
    },
    # --- text encoders ---
    "clip-l": {"family": "text_encoder", "config": TextEncoderConfig()},
    # SDXL pair: CLIP-L penultimate + OpenCLIP bigG penultimate w/
    # text projection (pooled source)
    "clip-l-sdxl": {
        "family": "text_encoder",
        "config": TextEncoderConfig(penultimate_hidden=True),
    },
    # SD3's CLIP-L half: penultimate hidden + PROJECTED pooled (the
    # files bundle CLIPTextModelWithProjection with a 768x768 table)
    "clip-l-sd3": {
        "family": "text_encoder",
        "config": TextEncoderConfig(penultimate_hidden=True, proj_dim=768),
    },
    "clip-g": {
        "family": "text_encoder",
        "config": TextEncoderConfig(
            width=1280, layers=32, heads=20, activation="gelu",
            penultimate_hidden=True, proj_dim=1280,
            pad_token_id=0,  # open_clip.tokenize pads with 0, not EOS
        ),
    },
    # OpenCLIP ViT-H/14 text tower (SD2.x conditioning; packed under
    # cond_stage_model.model.* in SD2 single-file checkpoints).
    # final_ln_on_hidden: SD2 norms the penultimate context (ComfyUI
    # SD2ClipHModel layer_norm_hidden_state=True); SDXL's bigG doesn't.
    "clip-h": {
        "family": "text_encoder",
        "config": TextEncoderConfig(
            width=1024, layers=24, heads=16, activation="gelu",
            penultimate_hidden=True, proj_dim=1024,
            final_ln_on_hidden=True, pad_token_id=0,
        ),
    },
    "tiny-te": {
        "family": "text_encoder",
        "config": TextEncoderConfig(width=64, layers=2, heads=2, max_length=16),
    },
    # tiny SDXL-shaped dual pair (concat width 64+96=160)
    "tiny-te-l": {
        "family": "text_encoder",
        "config": TextEncoderConfig(
            width=64, layers=2, heads=2, max_length=16, penultimate_hidden=True
        ),
    },
    "tiny-te-g": {
        "family": "text_encoder",
        "config": TextEncoderConfig(
            width=96, layers=2, heads=2, max_length=16, activation="gelu",
            penultimate_hidden=True, proj_dim=96, pad_token_id=0,
        ),
    },
    # --- T5-class encoders (WAN conditioning; UMT5-XXL dims) ---
    "umt5-xxl": {
        "family": "t5_encoder",
        "config": T5EncoderConfig(
            d_model=4096, d_ff=10240, layers=24, heads=64, d_kv=64,
        ),
    },
    # classic T5 v1.1 XXL (the Flux text encoder): stack-shared
    # relative-position bias, sentencepiece vocab 32128
    "t5-xxl": {
        "family": "t5_encoder",
        "config": T5EncoderConfig(
            vocab_size=32128, d_model=4096, d_ff=10240, layers=24,
            heads=64, d_kv=64, per_layer_rel_bias=False,
        ),
    },
    # the same encoder at 6 of its 24 layers, widths unchanged: the
    # text side of flux-dev-5x10
    "t5-xxl-6l": {
        "family": "t5_encoder",
        "config": T5EncoderConfig(
            vocab_size=32128, d_model=4096, d_ff=10240, layers=6,
            heads=64, d_kv=64, per_layer_rel_bias=False,
        ),
    },
    # SD3's T5 slot: same weights, 77-token padding (the reference
    # stack pads T5 to 77 for SD3; Flux uses the long padding)
    "t5-xxl-sd3": {
        "family": "t5_encoder",
        "config": T5EncoderConfig(
            vocab_size=32128, d_model=4096, d_ff=10240, layers=24,
            heads=64, d_kv=64, per_layer_rel_bias=False, max_length=77,
        ),
    },
    # tiny T5 at the tiny-SD3 context width (160 = tiny CLIP concat)
    "tiny-t5-sd3": {
        "family": "t5_encoder",
        "config": T5EncoderConfig(
            vocab_size=49408, d_model=160, d_ff=320, layers=2, heads=2,
            d_kv=32, max_length=16, per_layer_rel_bias=False,
        ),
    },
    # tiny shared-bias variant (Flux layout) for hermetic tests; vocab
    # covers the CLIP-BPE fallback id space like tiny-t5
    "tiny-t5-shared": {
        "family": "t5_encoder",
        "config": T5EncoderConfig(
            vocab_size=49408, d_model=64, d_ff=128, layers=2, heads=2,
            d_kv=32, max_length=16, per_layer_rel_bias=False,
        ),
    },
    # tiny variant: vocab covers the CLIP-BPE fallback id space so the
    # placeholder tokenizer can't index out of the embedding table
    "tiny-t5": {
        "family": "t5_encoder",
        "config": T5EncoderConfig(
            vocab_size=49408, d_model=64, d_ff=128, layers=2, heads=2,
            d_kv=32, max_length=16,
        ),
    },
    # --- CLIP vision towers (WAN i2v image conditioning; ViT-H/14) ---
    "clip-vision-h": {
        "family": "clip_vision",
        "config": ClipVisionConfig(),
    },
    "tiny-clip-vision": {
        "family": "clip_vision",
        "config": ClipVisionConfig(
            image_size=32, patch_size=8, width=48, layers=3, heads=2,
        ),
    },
    # --- language models (a bundle with an `lm` part and no denoiser) ---
    # DeepSeek-V2 as one chip's share of a four-chip host, every width as
    # published: the leading dense layer and four expert layers of 60,
    # experts 0-39 of 160 (rank 0 of 4), the first quarter of the
    # vocabulary (the benchmark's deepseek-v2 configuration says what the
    # cut stands for)
    "deepseek-v2-ep4-5l": {
        "family": "lm",
        "config": DeepSeekV2Config(
            num_hidden_layers=5, ep_size=4, ep_rank=0, vocab_shards=4,
        ),
    },
    # every mechanism at a size for the CPU: a dense layer and two expert
    # layers, 16 experts in 4 groups (2 groups and 3 experts a token, one
    # shared), YaRN on, rank 0 of 4 holding experts 0-3
    "tiny-deepseek-v2": {
        "family": "lm",
        "config": DeepSeekV2Config(
            hidden_size=64, num_hidden_layers=3, num_attention_heads=4,
            q_lora_rank=32, kv_lora_rank=24, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
            moe_intermediate_size=32, n_routed_experts=16,
            n_shared_experts=1, num_experts_per_tok=3, n_group=4,
            topk_group=2, vocab_size=2048, ep_size=4, ep_rank=0,
            vocab_shards=4,
        ),
    },
    # Ouro-2.6B whole: every field the published value (48 layers walked
    # 4 times, 49,152 ids), 2,667,974,657 parameters
    "ouro-2.6b": {"family": "lm", "config": OuroConfig()},
    # the loop at a size for the CPU: 4 passes kept, 3 layers, narrow
    "tiny-ouro": {
        "family": "lm",
        "config": OuroConfig(
            hidden_size=64, num_hidden_layers=3, num_attention_heads=4,
            num_key_value_heads=4, head_dim=16, intermediate_size=160,
            vocab_size=2048,
        ),
    },
    # Solar-Open2-250B as one chip's share of an eight-chip host, every
    # width as published: one whole period of 48 layers (softmax attention
    # with grouped queries, then three KDA layers), experts 0-39 of 320
    # (rank 0 of 8), the first eighth of the vocabulary (the benchmark's
    # solar-open2-250b configuration says what the cut stands for)
    "solar-open2-ep8-4l": {
        "family": "lm",
        "config": SolarOpen2Config(
            num_hidden_layers=4, ep_size=8, ep_rank=0, vocab_shards=8,
        ),
    },
    # every mechanism at a size for the CPU: one period, 4 query heads over
    # 2 key heads, 16 experts (4 a token) of which rank 0 of 8 holds two, a
    # chunk of 32 tokens (two blocks of `solar_open2.KDA_SUBCHUNK`)
    "tiny-solar-open2": {
        "family": "lm",
        "config": SolarOpen2Config(
            hidden_size=64, num_hidden_layers=4, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, linear_num_heads=4,
            linear_head_dim=16, kda_chunk=32, moe_intermediate_size=32,
            n_routed_experts=16, num_experts_per_tok=4, vocab_size=4096,
            ep_size=8, ep_rank=0, vocab_shards=8,
        ),
    },
    # K-EXAONE-236B-A23B as one chip's share of an eight-chip host, every
    # width as published: the dense layer 0 and one whole period of the
    # pattern after it (window, window, full, window), the MTP module,
    # experts 0-15 of 128 (rank 0 of 8), the first eighth of the vocabulary
    # (the benchmark's k-exaone-236b-a23b configuration says what the cut
    # stands for)
    "k-exaone-ep8-5l": {
        "family": "lm",
        "config": KExaoneConfig(
            num_hidden_layers=5, ep_size=8, ep_rank=0, vocab_shards=8,
        ),
    },
    # every mechanism at a size for the CPU: the dense layer and one
    # period, 4 query heads over 2 key heads, a window of 12 (a ring of
    # 16), 16 experts (4 a token) of which rank 0 of 8 holds two, the MTP
    # module
    "tiny-k-exaone": {
        "family": "lm",
        "config": KExaoneConfig(
            hidden_size=64, num_hidden_layers=5, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, sliding_window=12,
            intermediate_size=160, moe_intermediate_size=32, num_experts=16,
            num_experts_per_tok=4, vocab_size=4096, ep_size=8, ep_rank=0,
            vocab_shards=8,
        ),
    },
    # Ling-3.0-flash as one chip's share of an eight-chip host, every width
    # as published: published layers 1-7 (the dense layer 1, then one whole
    # group of six at its 5 KDA : 1 MLA), the MTP module, experts 0-63 of
    # 512 (rank 0 of 8: routing group 0 whole), the first eighth of the
    # vocabulary (the benchmark's ling-3.0-flash configuration says what
    # the cut stands for)
    "ling-flash-ep8-7l": {
        "family": "lm",
        "config": LingFlashConfig(
            num_hidden_layers=7, first_layer=1, ep_size=8, ep_rank=0, vocab_shards=8,
        ),
    },
    # every mechanism at a size for the CPU: the same seven layers, 4 heads
    # of 16, a latent of 24 + 8, chunks of 32, 32 experts in 8 groups (4
    # groups and 4 experts a token) of which rank 0 of 8 holds group 0, the
    # MTP module, and limits low enough that the clamps act (routed 0.5 in
    # layers 5-7, shared 0.75 in 4-5 and 1 in 6-7)
    "tiny-ling-flash": {
        "family": "lm",
        "config": LingFlashConfig(
            hidden_size=64, num_hidden_layers=7, first_layer=1, num_attention_heads=4,
            head_dim=16, kda_chunk=32, kv_lora_rank=24, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, intermediate_size=160,
            moe_intermediate_size=32, moe_shared_expert_intermediate_size=32,
            num_experts=32, num_experts_per_tok=4, vocab_size=4096,
            expert_swiglu_limit_list=(0, 0, 0, 0, 0, 0.5, 0.5, 0.5),
            share_expert_swiglu_limit_list=(0, 0, 0, 0, 0.75, 0.75, 1.0, 1.0),
            ep_size=8, ep_rank=0, vocab_shards=8,
        ),
    },
    # NVIDIA-Nemotron-3-Nano-30B-A3B as one chip's share of two eight-chip
    # hosts that hold the whole model, every width as published and at full
    # depth: all 52 blocks of the published string (23 Mamba-2, 23 sparse, 6
    # attention), experts 0-7 of 128 (rank 0 of 16), the first eighth of the
    # vocabulary (the benchmark's nemotron-3-nano-30b-a3b configuration says
    # what the cut stands for, and why it is not the eight-way one)
    "nemotron3-nano-ep16-52l": {
        "family": "lm",
        "config": NemotronHConfig(ep_size=16, ep_rank=0, vocab_shards=8),
    },
    # every mechanism at a size for the CPU: all three kinds of block and
    # `EM` runs of two lengths (2 and 3 pairs: one M, a run, attention, a
    # run, one E), 4 Mamba-2 heads of 8 over a state of 16 in 2 groups,
    # chunks of 32, 4 query heads over 1 key head of 16, 16 experts of 24
    # columns (off any tile; 3 a token) of which rank 0 of 8 holds two
    "tiny-nemotron3-nano": {
        "family": "lm",
        "config": NemotronHConfig(
            hidden_size=64, hybrid_override_pattern="MEMEM*EMEMEME", mamba_num_heads=4,
            mamba_head_dim=8, ssm_state_size=16, n_groups=2, chunk_size=32,
            num_attention_heads=4, num_key_value_heads=1, head_dim=16, n_routed_experts=16,
            moe_intermediate_size=24, moe_shared_expert_intermediate_size=48,
            num_experts_per_tok=3, vocab_size=4096, ep_size=8, ep_rank=0, vocab_shards=8,
        ),
    },
    # GLM-5.2 as one chip's share of a sixteen-way expert-parallel deployment,
    # every width as published: published layers 2-6 (the dense layer 2, which
    # computes an index, layers 3-5 that attend by it, layer 6 with an index
    # of its own: one whole period of the indexer's pattern), the MTP module,
    # experts 0-15 of 256 (rank 0 of 16), the first eighth of the vocabulary
    # (the benchmark's glm-5.2 configuration says what the cut stands for)
    "glm-5.2-ep16-5l": {
        "family": "lm",
        "config": GlmDsaConfig(
            num_hidden_layers=5, first_layer=2, ep_size=16, ep_rank=0, vocab_shards=8,
        ),
    },
    # every mechanism at a size for the CPU: the same five layers, 4 heads
    # (12 + 8 wide, values 16), a query latent of 32 and a latent of 24, an
    # indexer of 2 heads of 16 that keeps 8 positions (so the selection binds
    # from the ninth position on), parts of 16 positions, 32 experts (4 a
    # token) of which rank 0 of 16 holds two, the MTP module
    "tiny-glm-dsa": {
        "family": "lm",
        "config": GlmDsaConfig(
            hidden_size=64, num_hidden_layers=5, first_layer=2, num_attention_heads=4,
            q_lora_rank=32, kv_lora_rank=24, qk_nope_head_dim=12, qk_rope_head_dim=8,
            v_head_dim=16, index_n_heads=2, index_head_dim=16, index_topk=8,
            intermediate_size=160, moe_intermediate_size=32, n_routed_experts=32,
            num_experts_per_tok=4, vocab_size=4096, ep_size=16, ep_rank=0, vocab_shards=8,
            prefill_part=16,
        ),
    },
    # ibm-granite/granite-4.0-h-micro whole, every field as published: all 40
    # layers (36 Mamba-2, attention at 5, 15, 25, 35), all 100,352 ids, a
    # one-chip replica (3.19 B parameters, 6.38 GB in bfloat16); nothing is a
    # share of anything (the benchmark's granite-4.0-h-micro configuration)
    "granite-4.0-h-micro": {
        "family": "lm",
        "config": GraniteHybridConfig(),
    },
    # every mechanism at a size for the CPU: 8 layers with attention at 2 and
    # 6, so Mamba runs of three lengths (2, 3, 1) stand before, between and
    # after the attention layers; 4 Mamba-2 heads of 8 over a state of 16 in
    # one group, chunks of 8 (a part is two), 4 query heads over 2 key heads
    # of 16, a SwiGLU of 96, parts of 16 positions, the four multipliers at
    # their published values
    "tiny-granite-hybrid": {
        "family": "lm",
        "config": GraniteHybridConfig(
            hidden_size=64,
            layer_types=tuple(
                "attention" if index in (2, 6) else "mamba" for index in range(8)),
            mamba_n_heads=4, mamba_d_head=8, mamba_d_state=16, mamba_chunk_size=8,
            num_attention_heads=4, num_key_value_heads=2, shared_intermediate_size=96,
            vocab_size=4096, prefill_part=16,
        ),
    },
    # JetLM/SDAR-30B-A3B-Chat as stage 0 of an eight-stage pipeline, every
    # width as published: published layers 0-5 of 48, all 128 experts of each,
    # all 151,936 ids (4.36 B parameters, 8.72 GB in bfloat16; the benchmark's
    # sdar-30b-a3b-chat configuration says what the cut stands for)
    "sdar-30b-a3b-pp8-6l": {
        "family": "lm",
        "config": SdarConfig(num_hidden_layers=6),
    },
    # every mechanism at a size for the CPU: 3 layers, 4 query heads over 2
    # key heads of 16, 8 experts of 32 columns (2 a token), 512 ids of which
    # the last is the mask's, blocks of 4 filled in by at most 4 passes
    "tiny-sdar": {
        "family": "lm",
        "config": SdarConfig(
            hidden_size=64, num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
            vocab_size=512, mask_token_id=511,
        ),
    },
    # dots-studio/dots3-note-prev's text path as rank 0 of the eight chips
    # that share each layer, every width of both layer kinds as published:
    # published layers 0-4 (the dense layer 0 and layer 1, both full with an
    # index of their own; layers 2-4 sliding: one whole period of `full,
    # sliding, sliding, sliding` after the dense layer), experts 0-31 of 256,
    # the first eighth of the vocabulary (the benchmark's dots3-note-prev
    # configuration says what the cut stands for); no tower, no MTP module
    "dots3-note-prev-ep8-5l": {
        "family": "lm",
        "config": Dots3Config(num_hidden_layers=5, ep_size=8, ep_rank=0, vocab_shards=8),
    },
    # every mechanism at a size for the CPU: the same five layers; full
    # layers 4 heads (8 + 8 wide, values 8) over a latent of 16 with an index
    # of 2 heads of 16 that keeps 8 positions; sliding layers 2 heads (12 + 8
    # wide, values 8) over a latent of 32, a window of 5 (a ring of 8); a
    # query latent of 32 in both; 8 experts of 32 columns (2 a token) of
    # which rank 0 of 2 holds four; 512 ids; parts of 16 positions
    "tiny-dots3": {
        "family": "lm",
        "config": Dots3Config(
            hidden_size=64, num_hidden_layers=5, num_attention_heads=4, q_lora_rank=32,
            kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
            index_n_heads=2, index_head_dim=16, index_topk=8, sliding_window_size=5,
            swa_num_attention_heads=2, swa_q_lora_rank=32, swa_kv_lora_rank=32,
            swa_qk_nope_head_dim=12, swa_qk_rope_head_dim=8, swa_v_head_dim=8,
            intermediate_size=160, moe_intermediate_size=32, n_routed_experts=8,
            num_experts_per_tok=2, vocab_size=512, ep_size=2, ep_rank=0, prefill_part=16,
        ),
    },
    # meituan-longcat/LongCat-Flash-Chat as rank 0 of the 64 chips that share
    # each layer, every width as published: layers 0-3 of 28 (a layer is two
    # latent attentions, two dense feed-forwards and the expert layer on the
    # shortcut), experts 0-7 of 512 beside the 256 identity experts, which are
    # no chip's (the router keeps its 768 outputs), the first eighth of the
    # vocabulary (the benchmark's longcat-flash-chat configuration says what
    # the cut stands for, and why it is not the 32-way one); no MTP layer
    "longcat-flash-chat-ep64-4l": {
        "family": "lm",
        "config": LongcatFlashConfig(num_layers=4, ep_size=64, ep_rank=0, vocab_shards=8),
    },
    # every mechanism at a size for the CPU: 2 layers (four attentions: 4
    # heads, 8 + 8 wide, values 8, over a latent of 16, a query latent of 32,
    # two heads a call), dense feed-forwards of 160, 8 experts of 32 columns
    # beside 4 identities (3 a token of the router's 12) of which rank 0 of 2
    # holds four; 512 ids; parts of 16 positions, the branch in blocks of 8
    "tiny-longcat-flash": {
        "family": "lm",
        "config": LongcatFlashConfig(
            hidden_size=64, num_layers=2, num_attention_heads=4, q_lora_rank=32,
            kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
            ffn_hidden_size=160, expert_ffn_hidden_size=32, n_routed_experts=8,
            zero_expert_num=4, moe_topk=3, vocab_size=512, ep_size=2, ep_rank=0,
            prefill_part=16, expert_block=8, attention_heads_a_call=2,
        ),
    },
}

# Models whose conditioning comes from TWO encoders (SDXL layout):
# context = concat(hidden_1, hidden_2); pooled = projected pooled_2.
DUAL_TEXT_ENCODERS: dict[str, tuple[str, str]] = {
    "sdxl": ("clip-l-sdxl", "clip-g"),
    "tiny-unet-adm": ("tiny-te-l", "tiny-te-g"),
}

# Single-encoder models whose default differs from the CLIP-L fallback.
DEFAULT_TEXT_ENCODERS: dict[str, str] = {
    "sd21": "clip-h",
    "sd21-base": "clip-h",
}

# Flux-layout conditioning: hidden states from a T5-class encoder,
# pooled vector from a CLIP-class encoder — no concat, no padding
# (models/pipeline._encode_raw).
HIDDEN_POOLED_ENCODERS: dict[str, tuple[str, str]] = {
    "flux-dev": ("t5-xxl", "clip-l"),
    "flux-schnell": ("t5-xxl", "clip-l"),
    "flux-dev-5x10": ("t5-xxl-6l", "clip-l"),
    "tiny-flux": ("tiny-t5-shared", "tiny-te"),
}

# SD3-layout conditioning: (CLIP-L, CLIP-G, T5) — CLIP hiddens concat
# on features, zero-pad to the T5 width, sequence-concat with T5;
# pooled = CLIP-L pooled ++ CLIP-G pooled (models/pipeline._encode_raw).
TRIPLE_TEXT_ENCODERS: dict[str, tuple[str, str, str]] = {
    "sd3-medium": ("clip-l-sd3", "clip-g", "t5-xxl-sd3"),
    "sd35-large": ("clip-l-sd3", "clip-g", "t5-xxl-sd3"),
    "sd35-medium": ("clip-l-sd3", "clip-g", "t5-xxl-sd3"),
    "tiny-sd3": ("tiny-te-l", "tiny-te-g", "tiny-t5-sd3"),
    "tiny-sd35m": ("tiny-te-l", "tiny-te-g", "tiny-t5-sd3"),
}

_CONSTRUCTORS: dict[str, Callable[[Any], Any]] = {
    "unet": lambda cfg: UNet(cfg),
    "dit": lambda cfg: VideoDiT(cfg),
    "mmdit": lambda cfg: MMDiT(cfg),
    "sd3": lambda cfg: SD3MMDiT(cfg),
    "vae": lambda cfg: VAE(cfg),
    "text_encoder": lambda cfg: TextEncoder(cfg),
    "t5_encoder": lambda cfg: T5Encoder(cfg),
    "clip_vision": lambda cfg: ClipVisionEncoder(cfg),
    "video_vae": lambda cfg: VideoVAE(cfg),
    "lm": lambda cfg: _LANGUAGE_MODELS[type(cfg)](cfg),
}

# family "lm" holds more than one architecture: the configuration's type
# says which (each meets the contract in `lm_common`)
_LANGUAGE_MODELS: dict[type, Callable[[Any], Any]] = {
    DeepSeekV2Config: DeepSeekV2,
    OuroConfig: Ouro,
    SolarOpen2Config: SolarOpen2,
    KExaoneConfig: KExaone,
    LingFlashConfig: LingFlash,
    NemotronHConfig: NemotronH,
    GlmDsaConfig: GlmDsa,
    GraniteHybridConfig: GraniteHybrid,
    SdarConfig: Sdar,
    Dots3Config: Dots3,
    LongcatFlashConfig: LongcatFlash,
}


def model_family(name: str) -> str:
    return _entry(name)["family"]


def _entry(name: str) -> dict[str, Any]:
    if name not in MODEL_REGISTRY:
        raise KeyError(
            f"unknown model {name!r}; available: {sorted(MODEL_REGISTRY)}"
        )
    return MODEL_REGISTRY[name]


def get_config(name: str) -> Any:
    return _entry(name)["config"]


def create_model(name: str) -> Any:
    entry = _entry(name)
    return _CONSTRUCTORS[entry["family"]](entry["config"])
