"""Text nodes: a language model in the graph.

`TextGenerate` takes the CLIP output of a `CheckpointLoaderSimple` that
loaded a language-model bundle (models/pipeline.load_language_model),
runs one prefill of the whole text and `max_new_tokens` decode steps on
the device, and gives the generated text as a `STRING` that any text
input (`CLIPTextEncode.text`) can be linked to. The graph leaves the
device here: the ids are read back, turned into a string on the host,
and the next node tokenises that string for its own encoder.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .nodes_core import annotate_attention, resolve_seed
from .registry import register_node


def generate_tokens(bundle, ids, seed: int, steps: int, temperature: float,
                    collect: bool = False, draft_tokens: int = 0):
    """The served path's two programs, dispatched and not waited for:
    the prefill of `ids` and the decode of `steps` ids drawn from `seed`
    (one a step of its loop, or with `draft_tokens` one or more).
    Returns what they return, device arrays: the model's `Prefill` and
    `Decode` (a model whose decode takes the cache by donation leaves
    `prefill.cache` deleted). `collect` (the parity check's) also keeps
    what the model compares with its reference, every step's logits
    among it, in programs of their own."""
    from ..telemetry import get_tracer

    tracer = get_tracer()
    lm, params = bundle.lm, bundle.params["lm"]
    with tracer.span("lm.prefill"):
        prefill = lm.prefill(params, jnp.asarray(ids, jnp.int32), len(ids) + steps, collect)
    # the logits, which no decode takes by donation (the cache it may)
    tracer.device_span("prefill", prefill.logits)
    with tracer.span("lm.decode"):
        decode = lm.decode(
            params, prefill.cache, prefill.logits, len(ids), jax.random.key(seed), steps,
            temperature, collect, draft_tokens,
        )
    tracer.device_span("decode", decode.ids)
    return prefill, decode


@register_node
class TextGenerate:
    """Generate text with a language model: one prefill of `text` and
    exactly `max_new_tokens` sampled tokens (no early stop, so a served
    shape never changes), drawn on the device from `seed`; the same seed
    gives the same text. An output node, so `/history` carries the text
    and the executor's node cache never answers it: every request
    computes its prefill again and keeps no prefix. Each prompt length
    is a program of its own.

    `draft_tokens` 0 decodes one token a step. 1 asks a model that has a
    draft module (K-EXAONE's and Ling-3.0-flash's multi-token-prediction
    modules) to decode by self-speculation: a step drafts a token,
    verifies it with the main model and emits one or two, still one
    program and exactly `max_new_tokens` ids; how a dropped draft is
    kept out of the model's state (written over, or left in a slot that
    does not stand) is the model's own. The text is distributed as without drafting,
    but for the same seed the ids differ: the draws are other draws. A
    model without a draft module refuses anything but 0."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "clip": ("CLIP",),
                "text": ("STRING", {"default": ""}),
                "seed": ("INT", {"default": 0}),
                "max_new_tokens": ("INT", {"default": 256}),
                "temperature": ("FLOAT", {"default": 1.0}),
            },
            "optional": {
                "draft_tokens": ("INT", {"default": 0}),
            },
        }

    RETURN_TYPES = ("STRING",)
    FUNCTION = "generate"
    OUTPUT_NODE = True

    def generate(self, clip, text, seed, max_new_tokens=256, temperature=1.0,
                 draft_tokens=0, context=None):
        from ..telemetry import get_tracer
        from ..telemetry.instruments import (
            lm_decode_steps_total,
            lm_layer_passes_total,
            lm_tokens_total,
        )

        lm = getattr(clip, "lm", None)
        if lm is None:
            raise ValueError(
                "TextGenerate needs the CLIP output of a checkpoint that holds a "
                f"language model; {clip.model_name!r} holds none"
            )
        tracer = get_tracer()
        steps, draft_tokens = int(max_new_tokens), int(draft_tokens)
        ids = clip.tokenizer.encode(str(text))
        with annotate_attention():
            prefill, decode = generate_tokens(
                clip, ids, resolve_seed(seed).effective_seed(), steps, float(temperature),
                draft_tokens=draft_tokens,
            )
        # the one read-back: the executor thread parks here until the
        # device has run both programs
        with tracer.device_wait() as wait:
            new_ids, *read = jax.device_get((decode.ids, *lm.read_back(prefill, decode)))
            wait.attrs["bytes"] = int(new_ids.nbytes + sum(a.nbytes for a in read))
        with tracer.span("lm.detokenize"):
            out = clip.tokenizer.decode(new_ids)
        report = lm.report(len(ids), steps, len(ids) + steps, *read)
        counted = lm.counted(report, len(ids), steps)
        tracer.annotate(**{
            "prompt_tokens": len(ids), "new_tokens": steps, "draft_tokens": draft_tokens,
            "decode_steps": counted["decode_steps"], **report})
        lm_decode_steps_total().inc(counted["decode_steps"])
        for phase, tokens in (("prefill", len(ids)), ("decode", steps)):
            lm_tokens_total().inc(tokens, phase=phase)
            lm_layer_passes_total().inc(counted[f"{phase}_layer_passes"], phase=phase)
        return (out, {"ui": {"text": [out]}})
