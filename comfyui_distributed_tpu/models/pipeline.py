"""Diffusion pipelines: model bundle + jitted txt2img / img2img steps.

The glue the reference gets from ComfyUI's executor + common_ksampler
(checkpoint → CLIP encode → KSampler → VAE decode), re-assembled as
pure functions over a parameter bundle so the whole generation is one
jit-compiled XLA program per static shape. The graph executor (graph/)
calls these; the distributed layers shard their inputs.
"""

from __future__ import annotations

import dataclasses
import os
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..ops import samplers as smp
from .registry import create_model, get_config
from .text_encoder import Tokenizer


def params_storage_dtype():
    """The dtype floating-point weights are STORED in (the models
    already compute in bfloat16 either way). CDT_PARAMS_DTYPE names it
    outright; unset, the platform decides: bfloat16 on an accelerator —
    SDXL's ~3.5 B weights are ~13.9 GB in float32, which does not leave
    a 16 GB chip room to run — and float32 on the CPU, where the
    committed goldens pin float32 weights. Returns None for "leave the
    weights as initialised" (float32)."""
    want = os.environ.get("CDT_PARAMS_DTYPE", "")
    if want:
        return jnp.dtype(want)
    return None if jax.default_backend() == "cpu" else jnp.dtype(jnp.bfloat16)


def _needs_cast(x, dt):
    """Whether a leaf is a floating-point weight in another dtype than
    the storage dtype `dt`."""
    return (
        hasattr(x, "dtype")
        and jnp.issubdtype(x.dtype, jnp.floating)
        and x.dtype != dt
    )


def _stored_bytes(x, dt):
    """Bytes of a leaf once stored: in `dt` where it is cast to it."""
    return x.size * (dt if _needs_cast(x, dt) else x.dtype).itemsize


def maybe_cast_params(tree):
    """Cast floating-point weights to params_storage_dtype(). Applied
    by every model/VAE/TE/ControlNet/upscaler loader at bundle-build
    time.

    Takes OWNERSHIP of the tree: each source buffer is freed as soon
    as its cast completes, so the transient peak stays at the f32
    footprint instead of f32+bf16 — the difference between fitting
    and OOMing an SDXL load on a 16G chip. Callers must not reuse the
    input tree afterwards (every loader discards it immediately)."""
    dt = params_storage_dtype()
    if dt is None:
        return tree

    def cast(x):
        if _needs_cast(x, dt):
            y = x.astype(dt)
            if isinstance(x, jax.Array):
                y.block_until_ready()
                x.delete()
            return y
        return x

    return jax.tree_util.tree_map(cast, tree)


def _bind(eqn, values):
    subfuns, params = eqn.primitive.get_bind_params(eqn.params)
    out = eqn.primitive.bind(*subfuns, *values, **params)
    return out if eqn.primitive.multiple_results else [out]


def _hashed(value):
    """`value` where it can key a dictionary, else what tells it from
    every other object (a traced inner function is shared between the
    calls that gave it the same shapes)."""
    try:
        hash(value)
    except TypeError:
        return id(value)
    return value


def _equal_weights_once(closed, dtype, budget):
    """A traced initializer (key -> leaves) as a function that gives
    the leaves stored in `dtype` and draws
    the weights its equations make in the same way with ONE loop
    (`lax.map`), so that the compiler builds one draw for them where
    it would build one apiece: it shares nothing between equal fusions
    of a program, and a draw is most of a threefry-and-erf-inv
    fusion's 0.1-4 s of compiling. A weight's own equations are the
    ones between the key and itself (what the forward pass leaves
    behind on the weights is on no such path); two weights are equal
    in this sense when those equations and their wiring are, whatever
    their literals: the literals that differ (the hash of a weight's
    path folded into the key, a variance) are what the loop runs over.
    A loop's result is its weights stacked, which the program holds
    until they are copied out: a loop takes at most `budget` bytes of
    them, the rest of the group goes to the next loop."""
    import numpy as np
    from jax.extend.core import Literal

    jaxpr = closed.jaxpr
    (root,) = jaxpr.invars
    producer = {v: (i, eqn) for i, eqn in enumerate(jaxpr.eqns) for v in eqn.outvars}

    def own(out):
        """(equations, their literals in order, signature) of one leaf."""
        found, seen, todo = {}, set(), [out]
        while todo:
            v = todo.pop()
            if isinstance(v, Literal) or v in seen:
                continue
            seen.add(v)
            if v in producer:
                at, eqn = producer[v]
                found[at] = eqn
                todo.extend(eqn.invars)
        eqns = [found[i] for i in sorted(found)]
        number, literals, signature = {}, [], []
        for eqn in eqns:
            wiring = []
            for v in eqn.invars:
                if isinstance(v, Literal):
                    literals.append(v)
                    wiring.append(str(v.aval))
                else:
                    wiring.append(number.get(v, id(v)))  # an argument stands for itself
            for v in eqn.outvars:
                number[v] = len(number)
            params = tuple((k, _hashed(v)) for k, v in sorted(eqn.params.items()))
            signature.append(
                (eqn.primitive, params, tuple(wiring), tuple(str(v.aval) for v in eqn.outvars))
            )
        # what is drawn from no key is a constant: its literals are its value
        fixed = None if root in seen else tuple(repr(v.val) for v in literals)
        return eqns, literals, (tuple(signature), number.get(out, repr(out)), fixed)

    groups = {}  # signature -> (the first such leaf, its equations, [(leaf's index, its literals)])
    for index, out in enumerate(jaxpr.outvars):
        eqns, literals, signature = own(out)
        groups.setdefault(signature, (out, eqns, []))[2].append((index, literals))

    def build(key):
        given = dict(zip(jaxpr.constvars, closed.consts))
        given[root] = key
        leaves = [None] * len(jaxpr.outvars)
        for out, eqns, members in groups.values():

            def draw(swapped, out=out, eqns=eqns):
                """The leaf, with the literals at `swapped`'s places taken from it."""
                local, at = dict(given), 0
                for eqn in eqns:
                    values = []
                    for v in eqn.invars:
                        if isinstance(v, Literal):
                            values.append(swapped.get(at, v.val))
                            at += 1
                        else:
                            values.append(local[v])
                    local.update(zip(eqn.outvars, _bind(eqn, values)))
                leaf = out.val if isinstance(out, Literal) else local[out]
                return leaf.astype(dtype) if _needs_cast(leaf, dtype) else leaf

            columns = [
                np.asarray([literals[i].val for _, literals in members], literal.aval.dtype)
                for i, literal in enumerate(members[0][1])
            ]
            differ = [i for i, column in enumerate(columns) if (column != column[0]).any()]
            if not differ:  # one value, handed out to all
                leaf = draw({})
                for index, _ in members:
                    leaves[index] = leaf
                continue
            step = max(1, budget // max(_stored_bytes(out.aval, dtype), 1))
            for start in range(0, len(members), step):
                batch = members[start:start + step]
                if len(batch) == 1:
                    leaves[batch[0][0]] = draw({i: columns[i][start] for i in differ})
                    continue
                drawn = jax.lax.map(
                    lambda values: draw(dict(zip(differ, values))),
                    tuple(columns[i][start:start + step] for i in differ),
                )
                # the next loop waits for this one's weights to be copied
                # out: else the compiler may run every loop first and hold
                # all their stacked results at once (SDXL's UNet: 4.4 GB)
                given[root], copied = jax.lax.optimization_barrier(
                    (given[root], [drawn[i] for i in range(len(batch))])
                )
                for member, leaf in zip(batch, copied):
                    leaves[member[0]] = leaf
        return leaves

    return build


def init_program(module, dtype, key, *args, **kwargs):
    """The one compiled program that builds `module`'s seeded weights
    stored in `dtype`, a function of the key that gives the stored
    tree: flax's `lazy_init` and each weight's cast, traced once and
    built with equal weights drawn by one loop (`_equal_weights_once`).
    A weight's float32 value lives inside the fusion that rounds it;
    what the program holds beside its result is
    `.lower(key).compile().memory_analysis()`'s `temp_size_in_bytes`,
    which the tests and `chip_smoke.py --legs init` read."""
    abstract_args, abstract_kwargs = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), (args, kwargs)
    )

    def build(k):
        return module.lazy_init(k, *abstract_args, **abstract_kwargs)

    closed, shapes = jax.make_jaxpr(build, return_shape=True)(key)
    leaves, tree = jax.tree_util.tree_flatten(shapes)
    stored = sum(_stored_bytes(leaf, dtype) for leaf in leaves)
    draw = _equal_weights_once(closed, dtype, stored // 8)

    def init(k):
        return tree.unflatten(draw(k))

    # the name a `program.build` span carries: one a component
    init.__name__ = f"init_{type(module).__name__}"
    return jax.jit(init)


def init_params(module, key, *args, settle: bool = True, **kwargs):
    """Seeded random parameters for `module`: flax's `lazy_init`, which
    runs the parameter initializers and only shape-evaluates the
    forward pass; the values are bit-identical to `module.init` on the
    same dummy inputs. Where the weights are stored in another dtype
    than float32 one compiled program builds the component in it
    (`init_program`): a start builds or fetches one program a
    component, not one for every distinct operation of its
    initializers, and a load never holds a float32 copy of a component
    (a 3.2 B-parameter denoiser is 12.7 GB in float32, on a 16 GB chip
    that also holds its text encoders).
    `settle=False` keeps float32 for a caller about to map a checkpoint
    onto the tree."""
    abstract_args, abstract_kwargs = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), (args, kwargs)
    )

    def build(k):
        return module.lazy_init(k, *abstract_args, **abstract_kwargs)

    dtype = params_storage_dtype() if settle else None
    if dtype is None:
        return build(key)
    return init_program(module, dtype, key, *args, **kwargs)(key)


@dataclasses.dataclass
class PipelineBundle:
    """A checkpoint: diffusion backbone + VAE + text encoder(s) + params."""

    model_name: str
    unet: Any
    vae: Any
    text_encoder: Any
    params: dict[str, Any]          # {"unet", "vae", "te"[, "te2"]}
    tokenizer: Tokenizer
    latent_channels: int = 4
    latent_scale: int = 8           # spatial down factor of the VAE
    # SDXL-class second encoder (context concat + pooled source)
    text_encoder_2: Any = None
    # second encoder's tokenizer: OpenCLIP towers pad with 0, CLIP-L
    # with EOS, so the dual path tokenizes per encoder (None = share)
    tokenizer_2: Tokenizer | None = None
    # SD3-class third encoder (T5; CLIP-L/G are te/te2)
    text_encoder_3: Any = None
    tokenizer_3: Any = None
    # registry names the encoders were built from (LoRA mapping needs
    # the real configs, not a guess from model_name)
    te_name: str | None = None
    te2_name: str | None = None
    te3_name: str | None = None
    # skip-layer guidance (SD3.5): set by the SkipLayerGuidanceSD3
    # node via dataclasses.replace — a new bundle instance, so the
    # jitted samplers recompile for the patched model exactly once
    slg: "SLGSpec | None" = None
    # clip-skip (CLIPSetLastLayer): how many final CLIP blocks to
    # exclude from the hidden/context output; None = each tower's
    # configured default. Applies to CLIP towers only (T5 unaffected)
    clip_skip: int | None = None
    # ModelSampling* node overrides (ComfyUI patches the model's
    # sampling object; here a replaced bundle recompiles the jitted
    # samplers exactly once). None = the registry config's values.
    flow_shift_override: float | None = None
    parameterization_override: str | None = None
    # RescaleCFG patch: std-rescale multiplier of the guided x0
    # prediction (None = plain CFG)
    cfg_rescale: float | None = None
    # DualCFGGuider: when set, sampling positives must be the 2-tuple
    # (cond1, cond2) and guided_model dispatches smp.dual_cfg_model
    # (the outer cfg knob is cfg_conds). None = single-cond CFG.
    dual_cfg: "DualCFGSpec | None" = None
    # PerturbedAttentionGuidance patch (UNet family only; the node
    # guards the family). None = no PAG pass.
    pag: "PAGSpec | None" = None
    # SelfAttentionGuidance patch (UNet family only). None = no SAG.
    sag: "SAGSpec | None" = None
    # PerpNegGuider composition. None = plain CFG.
    perp_neg: "PerpNegSpec | None" = None
    # a language model (TextGenerate): its weights are params["lm"] and
    # `tokenizer` is its own; such a bundle has no unet, vae or encoder
    lm: Any = None


@dataclasses.dataclass
class VAEBundle:
    """A standalone VAE (the VAELoader node's output): satisfies the
    attribute protocol the VAE-consuming nodes use (`.vae`,
    `.params["vae"]`, `.latent_channels`, `.latent_scale`) so it can
    replace a checkpoint's bundled VAE anywhere one is accepted."""

    vae: Any
    params: dict[str, Any]
    latent_channels: int
    latent_scale: int


def load_vae(
    vae_name: str = "vae-sd",
    checkpoint: str | None = None,
    seed: int = 0,
) -> VAEBundle:
    """Build a standalone VAE; load real weights when a checkpoint
    resolves (explicit arg or CDT_CHECKPOINT_DIR/<vae_name>.*).
    Standalone VAE files ship bare `encoder./decoder.` keys (e.g.
    vae-ft-mse, Flux ae.safetensors); full checkpoints carry
    `first_stage_model.*` — both layouts map."""
    from . import sd_checkpoint as sdc
    from .registry import model_family

    if model_family(vae_name) != "vae":
        raise ValueError(
            f"{vae_name!r} is not an image-VAE config "
            f"(family {model_family(vae_name)!r}); use a vae-* registry "
            "name"
        )
    cfg = get_config(vae_name)
    vae = create_model(vae_name)
    ckpt = checkpoint or sdc.find_checkpoint(vae_name)
    params = init_params(
        vae, jax.random.key(seed), jnp.zeros((1, 32, 32, 3)), settle=not ckpt
    )
    if ckpt:
        from ..utils.logging import log

        log(f"loading VAE checkpoint {ckpt} for {vae_name}")
        params, _problems = sdc.load_vae_weights(
            sdc.read_checkpoint(ckpt), cfg, params
        )
    return VAEBundle(
        vae=vae,
        params=maybe_cast_params({"vae": params}),
        latent_channels=cfg.latent_channels,
        latent_scale=cfg.downscale,
    )


@dataclasses.dataclass(frozen=True)
class PAGSpec:
    """Perturbed-attention guidance (PerturbedAttentionGuidance node):
    the guided result gains scale * (cond - cond_with_identity_attn),
    where the perturbed pass runs the middle-block self-attention as
    identity (models/unet.py pag flag)."""

    scale: float = 3.0


@dataclasses.dataclass(frozen=True)
class SAGSpec:
    """Self-attention guidance (SelfAttentionGuidance node, Hong et
    al. 2023): blur the uncond x0 estimate where the middle-block
    self-attention concentrates, re-noise, and guide away from that
    degraded prediction."""

    scale: float = 0.5
    blur_sigma: float = 2.0


@dataclasses.dataclass(frozen=True)
class PerpNegSpec:
    """PerpNegGuider parameters: only the component of the negative
    orthogonal to the positive pushes away (smp.perp_neg_model).
    Sampling positives must be the 2-tuple (positive, negative) and
    the sampler's negative slot carries the EMPTY conditioning."""

    neg_scale: float = 1.0


@dataclasses.dataclass(frozen=True)
class DualCFGSpec:
    """DualCFGGuider parameters riding on the bundle (the outer
    cfg_conds travels as the sampler's cfg knob; see
    smp.dual_cfg_model for the regular/nested formulas)."""

    cfg_cond2_negative: float
    nested: bool = False


@dataclasses.dataclass(frozen=True)
class SLGSpec:
    """Skip-layer guidance parameters (reference SkipLayerGuidanceDiT:
    scale * (cond - cond_with_layers_skipped) over a sampling-progress
    window)."""

    layers: tuple
    scale: float = 3.0
    start_percent: float = 0.01
    end_percent: float = 0.15


def load_pipeline(
    model_name: str = "tiny-unet",
    vae_name: str | None = None,
    te_name: str | None = None,
    seed: int = 0,
    checkpoint: str | None = None,
) -> PipelineBundle:
    """Build a pipeline; load real weights when a checkpoint resolves.

    Checkpoint resolution order: explicit `checkpoint` arg, then
    `CDT_CHECKPOINT_DIR/<model_name>.{safetensors,ckpt}` (the dir env
    var may also point directly at a file). Single-file SD layout
    (model.diffusion_model / first_stage_model / cond_stage_model) is
    mapped key-by-key into the flax trees (models/sd_checkpoint.py).
    Without a checkpoint the weights are deterministic random init —
    the distributed machinery upstream is weight-agnostic.
    """
    from .registry import (
        DEFAULT_TEXT_ENCODERS,
        DUAL_TEXT_ENCODERS,
        HIDDEN_POOLED_ENCODERS,
        TRIPLE_TEXT_ENCODERS,
        model_family,
    )

    tiny = model_name.startswith("tiny")
    family = model_family(model_name)
    if family == "lm":
        return load_language_model(model_name, seed=seed)
    dual = DUAL_TEXT_ENCODERS.get(model_name)
    hidden_pooled = HIDDEN_POOLED_ENCODERS.get(model_name)
    triple = TRIPLE_TEXT_ENCODERS.get(model_name)
    vae_name = vae_name or _family_vae_name(model_name, family)
    te3_name = None
    if triple:
        # SD3 layout: CLIP-L + CLIP-G + T5
        te_name = te_name or triple[0]
        te2_name = triple[1]
        te3_name = triple[2]
    elif hidden_pooled:
        # Flux layout: hidden states from a T5-class encoder, pooled
        # vector from a CLIP-class encoder
        te_name = te_name or hidden_pooled[0]
        te2_name = hidden_pooled[1]
    elif dual:
        te_name = te_name or dual[0]
        te2_name = dual[1]
    else:
        te_name = te_name or DEFAULT_TEXT_ENCODERS.get(model_name) or (
            "tiny-te" if tiny else "clip-l"
        )
        te2_name = None

    unet = create_model(model_name)
    vae = create_model(vae_name)
    te = create_model(te_name)
    te_cfg = get_config(te_name)
    unet_cfg = get_config(model_name)
    vae_cfg = get_config(vae_name)

    root = jax.random.key(seed)
    k_unet, k_vae, k_te = jax.random.split(root, 3)

    # Seeded-random bundles settle each component into its storage
    # dtype as it is built; with a checkpoint to map onto them the
    # float32 templates stay as they are until the final cast.
    settle = not (checkpoint or os.environ.get("CDT_CHECKPOINT_DIR"))

    # Init with minimal dummy shapes; flax params are shape-polymorphic
    # across batch/spatial dims for these architectures.
    lat = jnp.zeros((1, 16, 16, vae_cfg.latent_channels))
    ctx = jnp.zeros((1, te_cfg.max_length, unet_cfg.context_dim))
    ts = jnp.zeros((1,))
    if family == "dit":  # video DiT
        lat5 = jnp.zeros((1, 4, 16, 16, unet_cfg.in_channels))
        unet_params = init_params(unet, k_unet, lat5, ts, ctx, settle=settle)
    elif family in ("mmdit", "sd3"):
        unet_params = init_params(
            unet, k_unet, lat, ts, ctx, settle=settle,
            y=jnp.zeros((1, unet_cfg.adm_in_channels)),
        )
    else:
        unet_params = init_params(
            unet, k_unet, _unet_init_latents(unet_cfg, lat.shape[-1]), ts,
            ctx, settle=settle,
        )
    img = jnp.zeros((1, 32, 32, 3))
    vae_params = init_params(vae, k_vae, img, settle=settle)
    tokens = jnp.zeros((1, te_cfg.max_length), jnp.int32)
    te_params = init_params(te, k_te, tokens, settle=settle)

    te2 = None
    te2_params = None
    if te2_name:
        te2 = create_model(te2_name)
        te2_cfg = get_config(te2_name)
        tokens2 = jnp.zeros((1, te2_cfg.max_length), jnp.int32)
        te2_params = init_params(
            te2, jax.random.fold_in(k_te, 2), tokens2, settle=settle
        )
    te3 = None
    te3_params = None
    if te3_name:
        te3 = create_model(te3_name)
        te3_cfg = get_config(te3_name)
        tokens3 = jnp.zeros((1, te3_cfg.max_length), jnp.int32)
        te3_params = init_params(
            te3, jax.random.fold_in(k_te, 3), tokens3, settle=settle
        )

    from . import sd_checkpoint as sdc

    ckpt_supplied: set[str] = set()
    ckpt_path = checkpoint or sdc.find_checkpoint(model_name)
    if ckpt_path:
        from ..utils.logging import log

        log(f"loading checkpoint {ckpt_path} for {model_name}")
        state_dict = sdc.read_checkpoint(ckpt_path)
        templates = {"unet": unet_params, "vae": vae_params, "te": te_params}
        if te2_params is not None:
            templates["te2"] = te2_params
        if te3_params is not None:
            templates["te3"] = te3_params
        mapped, _problems = sdc.load_sd_weights(
            state_dict, unet_cfg, vae_cfg, te_cfg, templates,
            te2_cfg=get_config(te2_name) if te2_name else None,
            te3_cfg=get_config(te3_name) if te3_name else None,
            family=family,
        )
        unet_params = mapped["unet"]
        vae_params = mapped["vae"]
        te_params = mapped["te"]
        te2_params = mapped.get("te2", te2_params)
        te3_params = mapped.get("te3", te3_params)
        # which encoder parts the FILE actually carried — a fine-tuned
        # checkpoint's own encoders must not be clobbered by a
        # same-named standalone file below. Detection mirrors each
        # family loader's own part sniffing: for mmdit (Flux) te is
        # the T5 and te2 the CLIP (load_flux_weights); the SD/SDXL/SD3
        # layouts use their published key prefixes.
        if family == "mmdit":
            if any("layer.0.SelfAttention.q.weight" in k for k in state_dict):
                ckpt_supplied.add("te")
            if any("text_model.encoder.layers.0" in k for k in state_dict):
                ckpt_supplied.add("te2")
        else:
            _te_markers = {
                "te": (
                    "cond_stage_model.", "conditioner.embedders.0.",
                    "text_encoders.clip_l.",
                ),
                "te2": (
                    "conditioner.embedders.1.", "text_encoders.clip_g.",
                ),
                "te3": ("text_encoders.t5xxl.",),
            }
            for part, markers in _te_markers.items():
                if any(k.startswith(markers) for k in state_dict):
                    ckpt_supplied.add(part)

    # Separate-file text encoders (the real Flux/SD3 distribution
    # format: t5xxl_fp16.safetensors / clip_l.safetensors / ... — what
    # ComfyUI's CLIPLoader family consumes): a file resolving under the
    # ENCODER's registry name fills encoders the main checkpoint did
    # NOT supply (checkpoint-bundled fine-tuned encoders win).
    def _load_te_file(name, params_, part):
        if not name or params_ is None or part in ckpt_supplied:
            return params_
        return _load_te_checkpoint(name, params_)

    te_params = _load_te_file(te_name, te_params, "te")
    te2_params = _load_te_file(te2_name, te2_params, "te2")
    te3_params = _load_te_file(te3_name, te3_params, "te3")

    from .t5_encoder import T5Tokenizer

    if family == "mmdit":
        tokenizer = T5Tokenizer(
            max_length=te_cfg.max_length, vocab_size=te_cfg.vocab_size
        )
    else:
        tokenizer = Tokenizer(
            max_length=te_cfg.max_length, pad_id=te_cfg.pad_token_id
        )

    params = {"unet": unet_params, "vae": vae_params, "te": te_params}
    if te2_params is not None:
        params["te2"] = te2_params
    if te3_params is not None:
        params["te3"] = te3_params
    params = maybe_cast_params(params)
    return PipelineBundle(
        model_name=model_name,
        unet=unet,
        vae=vae,
        text_encoder=te,
        params=params,
        tokenizer=tokenizer,
        latent_channels=vae_cfg.latent_channels,
        latent_scale=vae_cfg.downscale,
        text_encoder_2=te2,
        tokenizer_2=(
            Tokenizer(
                max_length=te2_cfg.max_length, pad_id=te2_cfg.pad_token_id
            )
            if te2_name
            else None
        ),
        text_encoder_3=te3,
        tokenizer_3=(
            T5Tokenizer(
                max_length=te3_cfg.max_length, vocab_size=te3_cfg.vocab_size
            )
            if te3_name
            else None
        ),
        te_name=te_name,
        te2_name=te2_name,
        te3_name=te3_name,
    )


def load_language_model(model_name: str, seed: int = 0) -> PipelineBundle:
    """A bundle that holds a language model and nothing else (any model
    of family `lm`: the registry picks the class from the configuration's
    type): seeded random weights built in their storage dtype, weight by
    weight (10.3 GB in bfloat16 for the largest the benchmark loads; no
    float32 copy is ever made), `tokenizer` the model's own."""
    lm = create_model(model_name)
    dtype = params_storage_dtype() or jnp.float32
    return PipelineBundle(
        model_name=model_name,
        unet=None,
        vae=None,
        text_encoder=None,
        params={"lm": lm.init(jax.random.key(seed), dtype)},
        tokenizer=lm.tokenizer,
        lm=lm,
    )


def _unet_init_latents(unet_cfg, latent_channels: int):
    """Dummy latents for UNet-family init, honoring in_channels-widened
    inpaint configs (9 = 4 + mask + masked-image latents). Shared by
    load_pipeline and load_unet."""
    in_ch = getattr(unet_cfg, "in_channels", latent_channels)
    return jnp.zeros((1, 16, 16, in_ch))


def _load_te_checkpoint(name: str, params_):
    """Fill a text-encoder param tree from a separate-file checkpoint
    resolving under the encoder's registry name (no-op when none
    does). Shared by load_pipeline and load_clip."""
    from . import sd_checkpoint as sdc
    from .registry import model_family

    ckpt_ = sdc.find_checkpoint(name)
    if not ckpt_:
        return params_
    from ..utils.logging import log

    log(f"loading text-encoder checkpoint {ckpt_} for {name}")
    sd_dict = sdc.read_checkpoint(ckpt_)
    if model_family(name) == "t5_encoder":
        out, _problems = sdc.load_t5_weights(sd_dict, get_config(name), params_)
    else:
        out, _problems = sdc.load_clip_te_weights(
            sd_dict, get_config(name), params_
        )
    return out


def _family_vae_name(model_name: str, family: str) -> str:
    """The default VAE registry name for a diffusion family (the
    latent-geometry source shared by load_pipeline and load_unet)."""
    tiny = model_name.startswith("tiny")
    if family == "mmdit":
        return "tiny-vae-flux" if tiny else "vae-flux"
    if family == "sd3":
        return "tiny-vae-sd3" if tiny else "vae-sd3"
    return "tiny-vae" if tiny else "vae-sd"


def load_unet(
    model_name: str,
    seed: int = 0,
    checkpoint: str | None = None,
) -> PipelineBundle:
    """Diffusion-backbone-only bundle (the ComfyUI UNETLoader: real
    Flux/SD3.5 distributions ship the transformer as its own file and
    load text encoders / VAE separately). The bundle carries no VAE or
    text encoders — wire VAELoader / CLIPLoader outputs alongside it;
    latent geometry comes from the family's default VAE config.
    Checkpoint resolution mirrors load_pipeline
    (CDT_CHECKPOINT_DIR/<model_name>.*); both bare diffusion-file keys
    and model.diffusion_model.-nested layouts map
    (sd_checkpoint.load_diffusion_weights)."""
    from . import sd_checkpoint as sdc
    from .registry import model_family

    family = model_family(model_name)
    if family not in ("unet", "mmdit", "sd3"):
        raise ValueError(
            f"{model_name!r} (family {family!r}) is not an image diffusion "
            "backbone; UNETLoader loads unet/mmdit/sd3 models"
        )
    unet = create_model(model_name)
    unet_cfg = get_config(model_name)
    vae_cfg = get_config(_family_vae_name(model_name, family))

    lat = jnp.zeros((1, 16, 16, vae_cfg.latent_channels))
    ctx = jnp.zeros((1, 8, unet_cfg.context_dim))
    ts = jnp.zeros((1,))
    k_unet = jax.random.key(seed)
    ckpt_path = checkpoint or sdc.find_checkpoint(model_name)
    # as in load_pipeline: seeded-random weights are built in their
    # storage dtype, so no float32 copy lies beside what is resident
    if family in ("mmdit", "sd3"):
        unet_params = init_params(
            unet, k_unet, lat, ts, ctx, settle=not ckpt_path,
            y=jnp.zeros((1, unet_cfg.adm_in_channels)),
        )
    else:
        unet_params = init_params(
            unet, k_unet, _unet_init_latents(unet_cfg, lat.shape[-1]), ts, ctx,
            settle=not ckpt_path,
        )

    if ckpt_path:
        from ..utils.logging import log

        log(f"loading diffusion-model checkpoint {ckpt_path} for {model_name}")
        unet_params, _problems = sdc.load_diffusion_weights(
            sdc.read_checkpoint(ckpt_path), unet_cfg, unet_params, family
        )
    return PipelineBundle(
        model_name=model_name,
        unet=unet,
        vae=None,
        text_encoder=None,
        params=maybe_cast_params({"unet": unet_params}),
        tokenizer=None,
        latent_channels=vae_cfg.latent_channels,
        latent_scale=vae_cfg.downscale,
    )


def _order_clip_towers(names: list[str]) -> list[str]:
    """(CLIP-L, CLIP-G) ordering for the sdxl/sd3 layouts, sniffed by
    tower width (G is the wider 1280-d tower) — the reference stack
    identifies towers from the weights, so ported workflows pass the
    two names in either order. Equal widths keep the given order."""
    if len(names) == 2:
        w0 = getattr(get_config(names[0]), "width", 0)
        w1 = getattr(get_config(names[1]), "width", 0)
        if w0 > w1:
            return [names[1], names[0]]
    return list(names)


# CLIP-loader layouts → the representative diffusion family whose
# conditioning composition _encode_raw applies (the bundle's own
# encoders do the work; the name only picks the branch).
_CLIP_LAYOUT_FAMILIES = {
    "sd": None,      # default branch: single tower / SDXL-style concat
    "sdxl": None,
    "flux": ("tiny-flux", "flux-dev"),
    "sd3": ("tiny-sd3", "sd3-medium"),
}


def load_clip(
    te_names: list[str],
    layout: str = "sd",
    seed: int = 0,
) -> PipelineBundle:
    """Text-encoder-only bundle (the ComfyUI CLIPLoader /
    DualCLIPLoader / TripleCLIPLoader family): encoders resolve by
    registry name, real weights load from separate-file checkpoints
    when they resolve (CDT_CHECKPOINT_DIR/<te_name>.*), and `layout`
    picks the conditioning composition:

      sd    — one CLIP tower (hidden + pooled)
      sdxl  — CLIP-L + CLIP-G: feature concat, pooled from G
      flux  — T5 hidden states + CLIP pooled (encoder order is
              sniffed by family, so either argument order works)
      sd3   — CLIP-L + CLIP-G [+ T5]: the SD3 composition; without a
              T5 the CLIP sequence zero-pads to the backbone width
              (the reference stack's low-memory SD3 mode)
    """
    from . import sd_checkpoint as sdc
    from .registry import model_family
    from .t5_encoder import T5Tokenizer

    names = [str(n) for n in te_names]
    expected = {"sd": 1, "sdxl": 2, "flux": 2, "sd3": (2, 3)}
    if layout not in expected:
        raise ValueError(
            f"unknown CLIP layout {layout!r}; use {sorted(expected)}"
        )
    want = expected[layout]
    ok = len(names) in want if isinstance(want, tuple) else len(names) == want
    if not ok:
        raise ValueError(
            f"layout {layout!r} takes {want} encoder name(s), got {names}"
        )

    t5s = [n for n in names if model_family(n) == "t5_encoder"]
    clips = [n for n in names if model_family(n) != "t5_encoder"]
    if layout == "flux":
        if len(t5s) != 1 or len(clips) != 1:
            raise ValueError(
                f"flux layout needs one T5-family and one CLIP-family "
                f"encoder, got {names}"
            )
        ordered = [t5s[0], clips[0]]          # te = T5, te2 = CLIP
    elif layout == "sd3":
        if len(clips) != 2 or len(t5s) > 1:
            raise ValueError(
                f"sd3 layout needs two CLIP-family encoders and at most "
                f"one T5, got {names}"
            )
        ordered = _order_clip_towers(clips) + t5s  # te = L, te2 = G [, T5]
    else:
        if t5s:
            raise ValueError(
                f"layout {layout!r} takes CLIP-family encoders only, "
                f"got {names}"
            )
        ordered = (
            _order_clip_towers(names) if layout == "sdxl" else names
        )

    rep_family = _CLIP_LAYOUT_FAMILIES[layout]
    if rep_family is None:
        bundle_name = ordered[0]
    else:
        tiny = all(n.startswith("tiny") for n in ordered)
        bundle_name = rep_family[0] if tiny else rep_family[1]

    encoders, tokenizers, params = [], [], {}
    root = jax.random.key(seed)
    for i, name in enumerate(ordered):
        cfg = get_config(name)
        enc = create_model(name)
        tokens = jnp.zeros((1, cfg.max_length), jnp.int32)
        p = init_params(
            enc, jax.random.fold_in(root, i), tokens,
            settle=not sdc.find_checkpoint(name),
        )
        p = _load_te_checkpoint(name, p)
        encoders.append(enc)
        if model_family(name) == "t5_encoder":
            tokenizers.append(
                T5Tokenizer(max_length=cfg.max_length, vocab_size=cfg.vocab_size)
            )
        else:
            tokenizers.append(
                Tokenizer(max_length=cfg.max_length, pad_id=cfg.pad_token_id)
            )
        params["te" if i == 0 else f"te{i + 1}"] = p

    def slot(seq, i):
        return seq[i] if len(seq) > i else None

    return PipelineBundle(
        model_name=bundle_name,
        unet=None,
        vae=None,
        text_encoder=encoders[0],
        params=maybe_cast_params(params),
        tokenizer=tokenizers[0],
        text_encoder_2=slot(encoders, 1),
        tokenizer_2=slot(tokenizers, 1),
        text_encoder_3=slot(encoders, 2),
        tokenizer_3=slot(tokenizers, 2),
        te_name=ordered[0],
        te2_name=slot(ordered, 1),
        te3_name=slot(ordered, 2),
    )


# --- conditioning --------------------------------------------------------

@partial(jax.jit, static_argnames=("encoder", "eos_id", "skip_last"))
def _clip_apply(encoder, params, tokens, eos_id, skip_last):
    """One CLIP tower over `tokens` as one program, as `vae_apply` is
    for the VAE: called eagerly the pass is several hundred
    one-operation programs with the device idle between them, 0.85 s of
    host a request where the text changes every request (a prompt that
    `TextGenerate` rewrote; a fixed prompt is answered by the node
    cache). The flax module is the static key."""
    return encoder.apply(params, tokens, eos_id=eos_id, skip_last=skip_last)


def _encode_raw(bundle: PipelineBundle, texts: list[str]):
    """Prompts → (hidden [B, T, D], pooled [B, P]).

    Dual-encoder bundles (SDXL layout): context is the channel concat
    of both encoders' hidden states and pooled comes from the second
    (projected) encoder — the real SDXL conditioning, replacing the
    round-1 zero-pad hack. Single-encoder bundles pad/truncate to the
    backbone's context_dim only when they genuinely mismatch.
    """
    from .registry import model_family

    if model_family(bundle.model_name) == "sd3":
        # SD3 layout: CLIP-L/G penultimate states concatenated on
        # features, zero-padded to the T5 width, sequence-concatenated
        # with T5 states; pooled = CLIP-L pooled ++ CLIP-G pooled.
        # A missing T5 (DualCLIPLoader type=sd3 — the reference
        # stack's low-memory SD3 mode) keeps the CLIP-only sequence,
        # padded to the backbone's context width.
        if bundle.text_encoder_2 is None:
            raise ValueError(
                f"{bundle.model_name}: sd3 bundles need at least the two "
                "CLIP encoders (CLIP-L, CLIP-G)"
            )
        tokens = jnp.asarray(bundle.tokenizer.encode_batch(texts))
        h_l, p_l = _clip_apply(
            bundle.text_encoder, bundle.params["te"], tokens,
            eos_id=bundle.tokenizer.eos_id, skip_last=bundle.clip_skip,
        )
        tok2 = bundle.tokenizer_2
        tokens2 = jnp.asarray(tok2.encode_batch(texts))
        h_g, p_g = _clip_apply(
            bundle.text_encoder_2, bundle.params["te2"], tokens2,
            eos_id=tok2.eos_id, skip_last=bundle.clip_skip,
        )
        clip_ctx = jnp.concatenate(
            [h_l.astype(jnp.float32), h_g.astype(jnp.float32)], axis=-1
        )
        if bundle.text_encoder_3 is not None:
            tokens3 = jnp.asarray(bundle.tokenizer_3.encode_batch(texts))
            h_t5, _ = bundle.text_encoder_3.apply(
                bundle.params["te3"], tokens3
            )
            width = h_t5.shape[-1]
        else:
            h_t5 = None
            width = getattr(
                get_config(bundle.model_name), "context_dim",
                clip_ctx.shape[-1],
            )
        if clip_ctx.shape[-1] < width:
            clip_ctx = jnp.pad(
                clip_ctx, ((0, 0), (0, 0), (0, width - clip_ctx.shape[-1]))
            )
        hidden = (
            jnp.concatenate([clip_ctx, h_t5.astype(jnp.float32)], axis=1)
            if h_t5 is not None
            else clip_ctx
        )
        pooled = jnp.concatenate(
            [p_l.astype(jnp.float32), p_g.astype(jnp.float32)], axis=-1
        )
        return hidden, pooled

    if model_family(bundle.model_name) == "mmdit":
        return _encode_flux_parts(bundle, texts, texts)

    tokens = jnp.asarray(bundle.tokenizer.encode_batch(texts))
    hidden, pooled = _clip_apply(
        bundle.text_encoder, bundle.params["te"], tokens,
        eos_id=bundle.tokenizer.eos_id, skip_last=bundle.clip_skip,
    )
    if bundle.text_encoder_2 is not None:
        tok2 = bundle.tokenizer_2 or bundle.tokenizer
        tokens2 = jnp.asarray(tok2.encode_batch(texts))
        hidden2, pooled2 = _clip_apply(
            bundle.text_encoder_2, bundle.params["te2"], tokens2,
            eos_id=tok2.eos_id, skip_last=bundle.clip_skip,
        )
        hidden = jnp.concatenate(
            [hidden.astype(jnp.float32), hidden2.astype(jnp.float32)], axis=-1
        )
        pooled = pooled2
    ctx_dim = getattr(get_config(bundle.model_name), "context_dim", hidden.shape[-1])
    if hidden.shape[-1] < ctx_dim:
        hidden = jnp.pad(hidden, ((0, 0), (0, 0), (0, ctx_dim - hidden.shape[-1])))
    elif hidden.shape[-1] > ctx_dim:
        hidden = hidden[..., :ctx_dim]
    return hidden, pooled


def encode_text(bundle: PipelineBundle, texts: list[str]) -> jax.Array:
    """Prompts → [B, T, context_dim] context."""
    hidden, _pooled = _encode_raw(bundle, texts)
    return hidden


def encode_text_pooled(bundle: PipelineBundle, texts: list[str]):
    """Prompts → Conditioning with pooled vector (SDXL-class adm
    conditioning: pooled text is part of the UNet's label embedding)."""
    from ..ops.conditioning import Conditioning

    hidden, pooled = _encode_raw(bundle, texts)
    return Conditioning(context=hidden, pooled=pooled)


def _encode_flux_parts(
    bundle: PipelineBundle, texts_t5: list[str], texts_clip: list[str]
):
    """Flux layout (mmdit): T5 hidden states are the context; the
    pooled vector comes from the CLIP encoder — no concat, no padding.
    Both encoders (and their distinct tokenizers) are mandatory for
    this family; a T5 tokenizer feeding the CLIP tower would be
    silently wrong, so no fallback exists. Shared by _encode_raw
    (same text to both towers) and CLIPTextEncodeFlux (per-tower
    prompts)."""
    if bundle.text_encoder_2 is None or bundle.tokenizer_2 is None:
        raise ValueError(
            f"{bundle.model_name}: mmdit bundles need text_encoder_2/"
            "tokenizer_2 (CLIP pooled source)"
        )
    tokens = jnp.asarray(bundle.tokenizer.encode_batch(texts_t5))
    hidden, _ = bundle.text_encoder.apply(bundle.params["te"], tokens)
    tok2 = bundle.tokenizer_2
    tokens2 = jnp.asarray(tok2.encode_batch(texts_clip))
    _, pooled = _clip_apply(
        bundle.text_encoder_2, bundle.params["te2"], tokens2,
        eos_id=tok2.eos_id, skip_last=bundle.clip_skip,
    )
    return hidden, pooled


def encode_text_pooled_flux(
    bundle: PipelineBundle,
    texts_t5: list[str],
    texts_clip: list[str],
    guidance: float | None = None,
):
    """Per-tower Flux encoding (CLIPTextEncodeFlux parity): t5xxl text
    feeds the T5 context, clip_l text the CLIP pooled vector, and the
    distilled guidance rides on the conditioning (same slot the
    FluxGuidance node writes). With identical prompts and
    guidance=None this reduces exactly to encode_text_pooled on an
    mmdit bundle."""
    from ..ops.conditioning import Conditioning
    from .registry import model_family

    if model_family(bundle.model_name) != "mmdit":
        raise ValueError(
            f"{bundle.model_name}: CLIPTextEncodeFlux needs a Flux-layout "
            "(mmdit) bundle"
        )
    hidden, pooled = _encode_flux_parts(bundle, texts_t5, texts_clip)
    return Conditioning(
        context=hidden, pooled=pooled,
        guidance=None if guidance is None else float(guidance),
    )


def encode_text_pooled_sdxl(
    bundle: PipelineBundle,
    texts_g: list[str],
    texts_l: list[str],
    size_cond: tuple | None = None,
):
    """Per-tower SDXL encoding (CLIPTextEncodeSDXL parity): text_l
    feeds the CLIP-L tower, text_g the CLIP-G tower; context is the
    feature concat, pooled comes from the projected G tower, and
    size_cond carries the six adm size ints. With identical prompts
    this reduces exactly to encode_text_pooled on a dual bundle."""
    from ..ops.conditioning import Conditioning

    if bundle.text_encoder_2 is None:
        raise ValueError(
            f"{bundle.model_name}: CLIPTextEncodeSDXL needs a dual-tower "
            "(SDXL-layout) CLIP bundle"
        )
    tokens = jnp.asarray(bundle.tokenizer.encode_batch(texts_l))
    h_l, _p_l = _clip_apply(
        bundle.text_encoder, bundle.params["te"], tokens,
        eos_id=bundle.tokenizer.eos_id, skip_last=bundle.clip_skip,
    )
    tok2 = bundle.tokenizer_2 or bundle.tokenizer
    tokens2 = jnp.asarray(tok2.encode_batch(texts_g))
    h_g, p_g = _clip_apply(
        bundle.text_encoder_2, bundle.params["te2"], tokens2,
        eos_id=tok2.eos_id, skip_last=bundle.clip_skip,
    )
    hidden = jnp.concatenate(
        [h_l.astype(jnp.float32), h_g.astype(jnp.float32)], axis=-1
    )
    ctx_dim = getattr(
        get_config(bundle.model_name), "context_dim", hidden.shape[-1]
    )
    if hidden.shape[-1] < ctx_dim:
        hidden = jnp.pad(
            hidden, ((0, 0), (0, 0), (0, ctx_dim - hidden.shape[-1]))
        )
    elif hidden.shape[-1] > ctx_dim:
        hidden = hidden[..., :ctx_dim]
    return Conditioning(context=hidden, pooled=p_g, size_cond=size_cond)


# --- model fn (VP eps / v / rectified-flow parameterisations) ------------

def model_schedule_info(bundle: PipelineBundle) -> tuple[str, float]:
    """(parameterization, flow_shift) of the bundle's backbone — the
    knobs that pick the sigma schedule and img2img noising rule
    (ops/samplers.get_model_sigmas / noise_latents). Flow-matching
    families (Flux class) carry parameterization == "flow". The
    ModelSampling* nodes override either knob per bundle."""
    cfg = get_config(bundle.model_name)
    param = bundle.parameterization_override or getattr(
        cfg, "parameterization", "eps"
    )
    shift = bundle.flow_shift_override
    if shift is None:
        shift = getattr(cfg, "flow_shift", 3.0)
    return (param, float(shift))


def _make_model_fn(
    bundle: PipelineBundle, params, skip_layers: tuple = (),
    pag: bool = False, sag_capture: bool = False,
):
    """sag_capture=True changes the RETURN CONTRACT: model_fn yields
    (eps, attn_probs, (mid_h, mid_w)) — the SAG capture pass. Only
    smp.sag_cfg_model consumes that form."""
    from ..ops.conditioning import Conditioning

    def model_fn(x, sigma_batch, cond):
        is_flow = model_schedule_info(bundle)[0] == "flow"
        context = cond.context if isinstance(cond, Conditioning) else cond
        if (
            context.shape[0] != x.shape[0]
            and x.shape[0] % context.shape[0] == 0
        ):
            # conditioning broadcast across a larger latent batch
            # (ComfyUI semantics — e.g. a participant-major batch from
            # a mesh pass refined with one prompt). jnp.repeat keeps
            # the CFG concat layout aligned: [pos;neg] doubling of x
            # pairs with [pos*k;neg*k]
            context = jnp.repeat(
                context, x.shape[0] // context.shape[0], axis=0
            )
        control = None
        if (
            isinstance(cond, Conditioning)
            and cond.control_hint is not None
            and cond.control_module is not None
        ):
            if is_flow:
                raise ValueError(
                    "ControlNet conditioning is not supported for "
                    "Flux-class models (Flux ControlNets are a separate "
                    "architecture)"
                )
            feats = cond.control_module.apply(cond.control_params, cond.control_hint)
            lh, lw = x.shape[1], x.shape[2]
            if feats.shape[1] != lh or feats.shape[2] != lw:
                feats = jax.image.resize(
                    feats, (feats.shape[0], lh, lw, feats.shape[3]), method="linear"
                )
            if feats.shape[0] == 1 and x.shape[0] > 1:
                feats = jnp.broadcast_to(feats, (x.shape[0],) + feats.shape[1:])
            control = feats * cond.control_strength
            if cond.control_range is not None:
                # ControlNetApplyAdvanced scheduling window: arithmetic
                # gate on the per-step scalar sigma keeps the
                # trajectory one XLA program
                p2s = percent_converter(bundle)
                sig_hi = p2s(float(cond.control_range[0]))
                sig_lo = p2s(float(cond.control_range[1]))
                s0 = sigma_batch[0]
                gate = ((s0 <= sig_hi) & (s0 > sig_lo)).astype(control.dtype)
                control = control * gate
        if (
            is_flow
            and isinstance(cond, Conditioning)
            and cond.concat_latent is not None
        ):
            raise ValueError(
                "concat-channel inpaint conditioning "
                "(InpaintModelConditioning) applies to SD-class inpaint "
                "UNets; flow-family models have no c_concat input"
            )
        if (
            not is_flow
            and isinstance(cond, Conditioning)
            and cond.reference_latents
        ):
            # loud like the SD3 module's own rejection — a silent drop
            # reads as the feature working
            raise ValueError(
                "reference latents are a Flux-Kontext capability; this "
                "model family has no reference token path"
            )
        y = None
        adm = getattr(get_config(bundle.model_name), "adm_in_channels", 0)
        if adm and isinstance(cond, Conditioning) and cond.pooled is not None:
            pooled = cond.pooled
            size_dims = adm - pooled.shape[-1]
            if size_dims == 6 * 256:
                # real SDXL adm layout: pooled text + six 256-d Fourier
                # size embeddings (orig_h, orig_w, crop_t, crop_l,
                # target_h, target_w) — the CLIPTextEncodeSDXL node
                # overrides them via cond.size_cond; the default is
                # crops 0 with sizes from the latent
                from .layers import timestep_embedding

                if cond.size_cond is not None:
                    vals = jnp.asarray(
                        [float(v) for v in cond.size_cond], jnp.float32
                    )
                else:
                    h_px = x.shape[1] * bundle.latent_scale
                    w_px = x.shape[2] * bundle.latent_scale
                    vals = jnp.asarray(
                        [h_px, w_px, 0.0, 0.0, h_px, w_px], jnp.float32
                    )
                size_emb = timestep_embedding(vals, 256).reshape(1, -1)
                pooled = jnp.concatenate(
                    [
                        pooled.astype(jnp.float32),
                        jnp.broadcast_to(
                            size_emb, (pooled.shape[0], size_emb.shape[-1])
                        ),
                    ],
                    axis=-1,
                )
            elif pooled.shape[-1] < adm:
                pooled = jnp.pad(pooled, ((0, 0), (0, adm - pooled.shape[-1])))
            elif pooled.shape[-1] > adm:
                pooled = pooled[..., :adm]
            if (
                pooled.shape[0] != x.shape[0]
                and x.shape[0] % pooled.shape[0] == 0
            ):
                # repeat, not pooled[:1]-broadcast: under the CFG
                # concat the second half is the NEGATIVE pooled vector
                pooled = jnp.repeat(
                    pooled, x.shape[0] // pooled.shape[0], axis=0
                )
            y = pooled
        if is_flow:
            # rectified flow (Flux class): t IS sigma, no input scaling,
            # and the velocity prediction equals eps under the sampler
            # contract denoised = x - sigma*eps. The distilled guidance
            # scale comes from the conditioning (FluxGuidance node);
            # None falls back to the config default inside the model.
            g = None
            if isinstance(cond, Conditioning) and cond.guidance is not None:
                g = jnp.full((x.shape[0],), float(cond.guidance), jnp.float32)
            kwargs = {}
            if isinstance(cond, Conditioning) and cond.reference_latents:
                # Flux-Kontext editing: reference latents join the
                # image token stream (models/mmdit.py); SD3-class
                # models reject them explicitly
                kwargs["ref_latents"] = [
                    r.astype(x.dtype) for r in cond.reference_latents
                ]
            if skip_layers:
                # skip-layer guidance pass (SD3-class only; the node
                # guards the family)
                kwargs["skip_layers"] = tuple(skip_layers)
            out = bundle.unet.apply(
                params["unet"], x, sigma_batch, context, y=y, guidance=g,
                **kwargs,
            )
            return out.astype(x.dtype)
        c_in = (1.0 / jnp.sqrt(sigma_batch**2 + 1.0)).reshape(
            (-1,) + (1,) * (x.ndim - 1)
        )
        t = smp.sigma_to_timestep(sigma_batch)
        x_in = x * c_in
        if isinstance(cond, Conditioning) and cond.concat_latent is not None:
            # inpaint-model channels join AFTER the VP input scaling
            # (reference c_concat convention: only the noisy latents
            # are scaled). The backbone must be an in_channels-widened
            # config (sd15-inpaint class) — a 4-channel model fails its
            # input conv shape check loudly.
            extra = cond.concat_latent.astype(x_in.dtype)
            if extra.shape[0] != x_in.shape[0]:
                extra = jnp.repeat(
                    extra, x_in.shape[0] // extra.shape[0], axis=0
                )
            if extra.shape[1:3] != x_in.shape[1:3]:
                extra = jax.image.resize(
                    extra,
                    (extra.shape[0],) + x_in.shape[1:3] + (extra.shape[3],),
                    method="linear",
                )
            x_in = jnp.concatenate([x_in, extra], axis=-1)
        unet_kwargs = {"pag": True} if pag else {}
        probs = None
        if sag_capture:
            out, mut = bundle.unet.apply(
                params["unet"], x_in, t, context, y=y, control=control,
                sag_capture=True, mutable=["intermediates"],
                **unet_kwargs,
            )
            probs = jax.tree_util.tree_leaves(mut)[0]
        else:
            out = bundle.unet.apply(
                params["unet"], x_in, t, context, y=y, control=control,
                **unet_kwargs,
            )
        if model_schedule_info(bundle)[0] == "v":
            # SD2.x-768-class velocity prediction. With the VP scalings
            # (c_skip = 1/(sigma^2+1), c_out = -sigma/sqrt(sigma^2+1)):
            #   denoised = x/(sigma^2+1) - v*sigma/sqrt(sigma^2+1)
            # Converted exactly to the sampler's eps contract
            # (denoised = x - sigma*eps):
            #   eps = x*sigma/(sigma^2+1) + v/sqrt(sigma^2+1)
            sig = sigma_batch.reshape((-1,) + (1,) * (x.ndim - 1))
            out = x * (sig / (sig**2 + 1.0)) + out / jnp.sqrt(sig**2 + 1.0)
        if sag_capture:
            levels = len(get_config(bundle.model_name).channel_mult)
            # per-level ceil-div: Downsample is a stride-2 pad-1 conv,
            # so each level yields ceil(H/2) — a single floor division
            # disagrees whenever an intermediate dim is odd
            mid_h, mid_w = x.shape[1], x.shape[2]
            for _ in range(levels - 1):
                mid_h = (mid_h + 1) // 2
                mid_w = (mid_w + 1) // 2
            return out.astype(x.dtype), probs, (mid_h, mid_w)
        return out.astype(x.dtype)

    return model_fn


def percent_converter(bundle: PipelineBundle):
    """The bundle-aware sampling-progress-percent → sigma converter
    (timestep-window gates of multi-entry conditioning and scheduled
    ControlNet hints)."""
    param, shift = model_schedule_info(bundle)

    def p2s(percent: float) -> float:
        return smp.percent_to_sigma(percent, param, shift)

    return p2s


def reject_existing_guidance_patches(bundle, node_name: str) -> None:
    """Patch-time exclusivity shared by the guidance patch nodes (SLG,
    RescaleCFG, DualCFGGuider, PAG): their compositions are mutually
    ambiguous, so the SECOND patch node fails at graph-build time
    naming both nodes (guided_model re-checks at sample time as the
    backstop for hand-built bundles)."""
    existing = [
        name
        for name, active in (
            ("SkipLayerGuidanceSD3", getattr(bundle, "slg", None) is not None),
            (
                "RescaleCFG",
                getattr(bundle, "cfg_rescale", None) is not None,
            ),
            (
                "DualCFGGuider",
                getattr(bundle, "dual_cfg", None) is not None,
            ),
            (
                "PerturbedAttentionGuidance",
                getattr(bundle, "pag", None) is not None,
            ),
            (
                "SelfAttentionGuidance",
                getattr(bundle, "sag", None) is not None,
            ),
            (
                "PerpNegGuider",
                getattr(bundle, "perp_neg", None) is not None,
            ),
        )
        if active
    ]
    if existing:
        raise ValueError(
            f"{node_name} cannot combine with {existing[0]} on the "
            "same model"
        )


def guided_model(bundle: PipelineBundle, params, cfg_scale: float):
    """The guidance composition every sampling path shares: CFG (with
    multi-entry conditioning composition), plus skip-layer guidance
    when the bundle carries an SLGSpec (set by the
    SkipLayerGuidanceSD3 node)."""
    slg = getattr(bundle, "slg", None)
    dual = getattr(bundle, "dual_cfg", None)
    pag = getattr(bundle, "pag", None)
    sag = getattr(bundle, "sag", None)
    perp = getattr(bundle, "perp_neg", None)
    patches = [
        name
        for name, active in (
            ("DualCFGGuider", dual is not None),
            ("SkipLayerGuidance", slg is not None),
            ("RescaleCFG", bundle.cfg_rescale is not None),
            ("PerturbedAttentionGuidance", pag is not None),
            ("SelfAttentionGuidance", sag is not None),
            ("PerpNegGuider", perp is not None),
        )
        if active
    ]
    if len(patches) > 1:
        raise ValueError(
            f"guidance patches cannot combine on one model: {patches}"
        )
    base_fn = _make_model_fn(bundle, params)
    p2s = percent_converter(bundle)
    if dual is not None:
        return smp.dual_cfg_model(
            base_fn, cfg_scale, float(dual.cfg_cond2_negative),
            p2s=p2s, nested=bool(dual.nested),
        )
    if pag is not None:
        return smp.pag_cfg_model(
            base_fn,
            _make_model_fn(bundle, params, pag=True),
            cfg_scale,
            float(pag.scale),
            p2s=p2s,
        )
    if perp is not None:
        return smp.perp_neg_model(
            base_fn, cfg_scale, float(perp.neg_scale), p2s=p2s
        )
    if sag is not None:
        return smp.sag_cfg_model(
            base_fn,
            _make_model_fn(bundle, params, sag_capture=True),
            cfg_scale,
            float(sag.scale),
            float(sag.blur_sigma),
            p2s=p2s,
        )
    if bundle.cfg_rescale is not None:
        return smp.rescale_cfg_model(
            base_fn, cfg_scale, float(bundle.cfg_rescale), p2s=p2s
        )
    if not slg:
        return smp.cfg_model(base_fn, cfg_scale, p2s=p2s)
    return smp.slg_cfg_model(
        base_fn,
        _make_model_fn(bundle, params, skip_layers=slg.layers),
        cfg_scale,
        slg.scale,
        p2s(slg.start_percent),
        p2s(slg.end_percent),
        p2s=p2s,
    )


# --- generation ----------------------------------------------------------

@partial(
    jax.jit,
    static_argnames=(
        "bundle_static", "height", "width", "steps", "sampler", "scheduler",
        "batch", "cfg_scale",
    ),
)
def _txt2img_jit(
    bundle_static,  # hashable closure carrier (see txt2img)
    params,
    context_pos,
    context_neg,
    key,
    height: int,
    width: int,
    steps: int,
    sampler: str,
    scheduler: str,
    cfg_scale: float,
    batch: int,
):
    bundle = bundle_static.value
    lh, lw = height // bundle.latent_scale, width // bundle.latent_scale
    param, shift = model_schedule_info(bundle)
    sigmas = smp.get_model_sigmas(param, scheduler, steps, flow_shift=shift)
    key, noise_key, anc_key = jax.random.split(key, 3)
    x = jax.random.normal(
        noise_key, (batch, lh, lw, bundle.latent_channels)
    ) * sigmas[0]
    model = guided_model(bundle, params, cfg_scale)
    latents = smp.sample(
        model, x, sigmas, (context_pos, context_neg), sampler, anc_key,
        flow=(param == "flow"),
    )
    return bundle.vae.apply(params["vae"], latents, method="decode")


class _Static:
    """Wrap a python object as a hashable static jit argument."""

    def __init__(self, value):
        self.value = value

    def __hash__(self):
        return id(self.value)

    def __eq__(self, other):
        return isinstance(other, _Static) and other.value is self.value


def txt2img(
    bundle: PipelineBundle,
    prompt: str,
    negative_prompt: str = "",
    height: int = 512,
    width: int = 512,
    steps: int = 20,
    sampler: str = "euler",
    scheduler: str = "karras",
    cfg_scale: float = 7.0,
    seed: int = 0,
    batch: int = 1,
) -> jax.Array:
    """Full text→image generation; returns [batch, H, W, 3] in [0,1]."""
    # pooled conditioning rides along for SDXL-adm / Flux-vector models
    # (families without pooled conditioning ignore the field)
    pos = encode_text_pooled(bundle, [prompt] * batch)
    neg = encode_text_pooled(bundle, [negative_prompt] * batch)
    key = jax.random.key(seed)
    return _txt2img_jit(
        _Static(bundle),
        bundle.params,
        pos,
        neg,
        key,
        height,
        width,
        steps,
        sampler,
        scheduler,
        float(cfg_scale),
        batch,
    )


def _batch_noise(key, shape, fixed: bool):
    """Initial-noise policy (LatentBatchSeedBehavior): fixed=True
    repeats index 0's noise across the batch (ComfyUI seed_behavior
    'fixed' — every batch element renders the same trajectory);
    False is fresh noise per element ('random', the default)."""
    if not fixed:
        return jax.random.normal(key, shape)
    one = jax.random.normal(key, (1,) + tuple(shape[1:]))
    return jnp.broadcast_to(one, shape)


@partial(
    jax.jit,
    static_argnames=(
        "bundle_static", "steps", "sampler", "scheduler", "cfg_scale",
        "denoise", "batch_fixed_noise",
    ),
)
def _img2img_jit(
    bundle_static,
    params,
    latents,
    context_pos,
    context_neg,
    key,
    steps: int,
    sampler: str,
    scheduler: str,
    cfg_scale: float,
    denoise: float,
    noise_mask=None,
    batch_fixed_noise: bool = False,
):
    bundle = bundle_static.value
    param, shift = model_schedule_info(bundle)
    sigmas = smp.get_model_sigmas(
        param, scheduler, steps, denoise=denoise, flow_shift=shift
    )
    noise_key, anc_key = jax.random.split(key)
    noise = _batch_noise(noise_key, latents.shape, batch_fixed_noise)
    x = smp.noise_latents(param, latents, noise, sigmas[0])
    return _masked_sample(
        bundle, params, cfg_scale, param, latents, noise, x, sigmas,
        (context_pos, context_neg), sampler, anc_key, noise_mask,
    )


def advanced_window_sigmas(
    parameterization: str,
    scheduler: str,
    steps: int,
    start_at_step: int,
    end_at_step: int,
    force_full_denoise: bool,
    shift: float,
) -> jnp.ndarray:
    """KSamplerAdvanced's schedule slice (ComfyUI common_ksampler with
    start_step/last_step/force_full_denoise): the full [steps+1] grid
    windowed to [start, end], with the final sigma forced to 0 when the
    caller wants full denoise despite stopping early."""
    full = smp.get_model_sigmas(
        parameterization, scheduler, int(steps), flow_shift=shift
    )
    start = min(max(int(start_at_step), 0), int(steps))
    end = min(max(int(end_at_step), start), int(steps))
    window = full[start:end + 1]
    if force_full_denoise and window.shape[0] > 1:
        window = window.at[-1].set(0.0)
    return window


@partial(
    jax.jit,
    static_argnames=(
        "bundle_static", "steps", "sampler", "scheduler", "cfg_scale",
        "start_at_step", "end_at_step", "add_noise", "force_full_denoise",
        "batch_fixed_noise",
    ),
)
def _advanced_jit(
    bundle_static,
    params,
    latents,
    context_pos,
    context_neg,
    key,
    steps: int,
    sampler: str,
    scheduler: str,
    cfg_scale: float,
    start_at_step: int,
    end_at_step: int,
    add_noise: bool,
    force_full_denoise: bool,
    noise_mask=None,
    batch_fixed_noise: bool = False,
):
    bundle = bundle_static.value
    param, shift = model_schedule_info(bundle)
    window = advanced_window_sigmas(
        param, scheduler, steps, start_at_step, end_at_step,
        force_full_denoise, shift,
    )
    noise_key, anc_key = jax.random.split(key)
    # add_noise=False (the refine pass of a two-pass workflow): the
    # trajectory starts from the latents as-is AND the masked-region
    # pin uses ZERO noise — ComfyUI's disable_noise semantics; pinning
    # with a fresh Gaussian the trajectory never saw would corrupt the
    # preserved-region context at every step
    noise = (
        _batch_noise(noise_key, latents.shape, batch_fixed_noise)
        if add_noise
        else jnp.zeros_like(latents)
    )
    x = (
        smp.noise_latents(param, latents, noise, window[0])
        if add_noise
        else latents
    )
    if window.shape[0] < 2:
        # empty step window: nothing to sample — but the mask contract
        # (preserved region survives intact) still holds
        if noise_mask is not None:
            mask = jnp.clip(noise_mask.astype(jnp.float32), 0.0, 1.0)
            return x * mask + latents * (1.0 - mask)
        return x
    return _masked_sample(
        bundle, params, cfg_scale, param, latents, noise, x, window,
        (context_pos, context_neg), sampler, anc_key, noise_mask,
    )


def _masked_sample(
    bundle, params, cfg_scale, param, latents, noise, x, sigmas, cond,
    sampler, anc_key, noise_mask,
):
    """Guidance + optional masked-inpaint wrap + trajectory + mask
    composite — the sampling core shared by _img2img_jit and
    _advanced_jit (one place to maintain the inpaint pin semantics)."""
    model = guided_model(bundle, params, cfg_scale)
    if noise_mask is not None:
        # inpainting (reference-substrate SetLatentNoiseMask /
        # VAEEncodeForInpaint semantics)
        mask = jnp.clip(noise_mask.astype(jnp.float32), 0.0, 1.0)
        model = smp.masked_inpaint_model(model, param, latents, noise, mask)
    out = smp.sample(
        model, x, sigmas, cond, sampler, anc_key, flow=(param == "flow")
    )
    if noise_mask is not None:
        out = out * mask + latents * (1.0 - mask)
    return out


def img2img_latents_advanced(
    bundle: PipelineBundle,
    latents: jax.Array,
    context_pos: jax.Array,
    context_neg: jax.Array,
    steps: int = 20,
    sampler: str = "euler",
    scheduler: str = "karras",
    cfg_scale: float = 7.0,
    seed: int = 0,
    start_at_step: int = 0,
    end_at_step: int = 10000,
    add_noise: bool = True,
    force_full_denoise: bool = True,
    noise_mask: jax.Array | None = None,
    batch_fixed_noise: bool = False,
) -> jax.Array:
    """KSamplerAdvanced core: sample a [start_at_step, end_at_step]
    window of the full schedule, optionally without adding noise (the
    second pass of a two-pass workflow) and optionally leaving leftover
    noise (force_full_denoise=False)."""
    key = jax.random.key(seed)
    return _advanced_jit(
        _Static(bundle),
        bundle.params,
        latents,
        context_pos,
        context_neg,
        key,
        int(steps),
        sampler,
        scheduler,
        float(cfg_scale),
        int(start_at_step),
        int(end_at_step),
        bool(add_noise),
        bool(force_full_denoise),
        noise_mask=noise_mask,
        batch_fixed_noise=bool(batch_fixed_noise),
    )


@partial(
    jax.jit,
    static_argnames=(
        "bundle_static", "sigmas_t", "sampler", "cfg_scale", "add_noise",
        "batch_fixed_noise",
    ),
)
def _custom_sigmas_jit(
    bundle_static,
    params,
    latents,
    context_pos,
    context_neg,
    key,
    sigmas_t: tuple,
    sampler: str,
    cfg_scale: float,
    add_noise: bool,
    noise_mask=None,
    batch_fixed_noise: bool = False,
):
    """Sampling over an EXPLICIT sigma grid (the SamplerCustom /
    SamplerCustomAdvanced substrate: the schedule arrives as a SIGMAS
    value from a scheduler node instead of being derived from
    steps+scheduler here). sigmas_t is a static tuple so multistep
    samplers that precompute numpy coefficients from the grid (lms)
    keep working, exactly as they do when the grid is built inside the
    other jits. Returns (output, denoised_output): when the grid stops
    above sigma 0 (leftover-noise workflows), denoised is the model's
    x0 prediction at the final point — one extra guided eval — else it
    is the output itself (ComfyUI SamplerCustom's two-output contract).
    """
    bundle = bundle_static.value
    param, _shift = model_schedule_info(bundle)
    sigmas = jnp.asarray(sigmas_t, jnp.float32)
    noise_key, anc_key = jax.random.split(key)
    noise = (
        _batch_noise(noise_key, latents.shape, batch_fixed_noise)
        if add_noise
        else jnp.zeros_like(latents)
    )
    x = (
        smp.noise_latents(param, latents, noise, sigmas[0])
        if add_noise
        else latents
    )
    mask = None
    if noise_mask is not None:
        mask = jnp.clip(noise_mask.astype(jnp.float32), 0.0, 1.0)
    if len(sigmas_t) < 2:
        out = x if mask is None else x * mask + latents * (1.0 - mask)
        return out, out
    out = _masked_sample(
        bundle, params, cfg_scale, param, latents, noise, x, sigmas,
        (context_pos, context_neg), sampler, anc_key, noise_mask,
    )
    if float(sigmas_t[-1]) == 0.0:
        return out, out
    model = guided_model(bundle, params, cfg_scale)
    sig = jnp.broadcast_to(sigmas[-1], (out.shape[0],))
    eps = model(out, sig, (context_pos, context_neg))
    denoised = out - sigmas[-1] * eps
    if mask is not None:
        denoised = denoised * mask + latents * (1.0 - mask)
    return out, denoised


def sample_custom_sigmas(
    bundle: PipelineBundle,
    latents: jax.Array,
    context_pos,
    context_neg,
    sigmas,
    sampler: str = "euler",
    cfg_scale: float = 1.0,
    seed: int = 0,
    add_noise: bool = True,
    noise_mask: jax.Array | None = None,
    batch_fixed_noise: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """SamplerCustom/SamplerCustomAdvanced core: run `sampler` over an
    explicit sigma grid. Returns (output, denoised_output)."""
    import numpy as np

    sig_t = tuple(float(s) for s in np.asarray(sigmas, dtype=np.float32))
    key = jax.random.key(int(seed))
    return _custom_sigmas_jit(
        _Static(bundle),
        bundle.params,
        latents,
        context_pos,
        context_neg,
        key,
        sig_t,
        sampler,
        float(cfg_scale),
        bool(add_noise),
        noise_mask=noise_mask,
        batch_fixed_noise=bool(batch_fixed_noise),
    )


@partial(jax.jit, static_argnames=("bundle_static", "cfg_scale", "sigma"))
def _denoised_at_jit(bundle_static, params, x, pos, neg, cfg_scale, sigma):
    bundle = bundle_static.value
    model = guided_model(bundle, params, cfg_scale)
    sig = jnp.broadcast_to(jnp.float32(sigma), (x.shape[0],))
    eps = model(x, sig, (pos, neg))
    return x - sigma * eps


def denoised_prediction(
    bundle: PipelineBundle, x: jax.Array, pos, neg, cfg_scale: float,
    sigma: float,
) -> jax.Array:
    """The model's x0 prediction for latents sitting at `sigma` — one
    guided eval (denoised = x - sigma*eps, the uniform contract across
    eps/v/flow parameterizations). Backs the denoised_output of
    SamplerCustom(-Advanced) when a trajectory stops above sigma 0 and
    the sampling ran somewhere the prediction wasn't computed inline
    (the mesh fan-out path)."""
    return _denoised_at_jit(
        _Static(bundle), bundle.params, x, pos, neg, float(cfg_scale),
        float(sigma),
    )


def img2img_latents(
    bundle: PipelineBundle,
    latents: jax.Array,
    context_pos: jax.Array,
    context_neg: jax.Array,
    steps: int = 20,
    sampler: str = "euler",
    scheduler: str = "karras",
    cfg_scale: float = 7.0,
    denoise: float = 0.5,
    seed: int = 0,
    noise_mask: jax.Array | None = None,
    batch_fixed_noise: bool = False,
) -> jax.Array:
    """Latent-space img2img (the tile re-diffusion core of USDU):
    noise to sigma[denoise], sample back down. Returns latents.

    `noise_mask` ([B, lh, lw, 1], 1 = regenerate) enables inpainting:
    the unmasked region is pinned to the original latents re-noised to
    each step's sigma and restored exactly afterwards."""
    key = jax.random.key(seed)
    return _img2img_jit(
        _Static(bundle),
        bundle.params,
        latents,
        context_pos,
        context_neg,
        key,
        steps,
        sampler,
        scheduler,
        float(cfg_scale),
        float(denoise),
        noise_mask=noise_mask,
        batch_fixed_noise=bool(batch_fixed_noise),
    )
