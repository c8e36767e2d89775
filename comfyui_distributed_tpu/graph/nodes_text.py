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
import numpy as np

from ..ops.attention import route_log as attention_route_log
from .nodes_core import resolve_seed
from .registry import register_node


def generate_tokens(bundle, ids, seed: int, steps: int, temperature: float,
                    collect: bool = False):
    """The served path's two programs, dispatched and not waited for:
    the prefill of `ids` and `steps` decode steps drawn from `seed`.
    Returns what they return, device arrays: the model's `Prefill` and
    `Decode`. `collect` (the parity check's) also keeps every step's
    logits and chosen experts, in programs of their own."""
    from ..telemetry import get_tracer

    tracer = get_tracer()
    lm, params = bundle.lm, bundle.params["lm"]
    with tracer.span("lm.prefill"):
        prefill = lm.prefill(params, jnp.asarray(ids, jnp.int32), len(ids) + steps, collect)
    with tracer.span("lm.decode"):
        decode = lm.decode(
            params, prefill.cache, prefill.logits, len(ids), jax.random.key(seed), steps,
            temperature, collect,
        )
    return prefill, decode


@register_node
class TextGenerate:
    """Generate text with a language model: one prefill of `text` and
    exactly `max_new_tokens` sampled tokens (no early stop, so a served
    shape never changes), drawn on the device from `seed`; the same seed
    gives the same text. An output node, so `/history` carries the text
    and the executor's node cache never answers it: every request
    computes its prefill again and keeps no prefix. Each prompt length
    is a program of its own."""

    @classmethod
    def INPUT_TYPES(cls):
        return {
            "required": {
                "clip": ("CLIP",),
                "text": ("STRING", {"default": ""}),
                "seed": ("INT", {"default": 0}),
                "max_new_tokens": ("INT", {"default": 256}),
                "temperature": ("FLOAT", {"default": 1.0}),
            }
        }

    RETURN_TYPES = ("STRING",)
    FUNCTION = "generate"
    OUTPUT_NODE = True

    def generate(self, clip, text, seed, max_new_tokens=256, temperature=1.0,
                 context=None):
        from ..telemetry import get_tracer
        from ..telemetry.instruments import lm_tokens_total

        if getattr(clip, "lm", None) is None:
            raise ValueError(
                "TextGenerate needs the CLIP output of a checkpoint that holds a "
                f"language model; {clip.model_name!r} holds none"
            )
        tracer = get_tracer()
        steps = int(max_new_tokens)
        ids = clip.tokenizer.encode(str(text))
        with attention_route_log() as routes:
            prefill, decode = generate_tokens(
                clip, ids, resolve_seed(seed).effective_seed(), steps, float(temperature)
            )
        # the one read-back: the executor thread parks here until the
        # device has run both programs
        with tracer.span("device.wait") as wait:
            new_ids, prefill_loads, decode_loads = jax.device_get(
                (decode.ids, prefill.loads, decode.loads)
            )
            wait.attrs["bytes"] = int(
                new_ids.nbytes + prefill_loads.nbytes + decode_loads.nbytes
            )
        with tracer.span("lm.detokenize"):
            out = clip.tokenizer.decode(new_ids)
        pairs_a_token = prefill_loads.shape[0] * clip.lm.cfg.num_experts_per_tok
        attrs = dict(
            prompt_tokens=len(ids), new_tokens=steps,
            **clip.lm.describe(len(ids) + steps, prefill.cache.dtype.itemsize),
        )
        for phase, tokens, loads in (
            ("prefill", len(ids), prefill_loads), ("decode", steps, decode_loads)
        ):
            attrs[f"{phase}_routed_pairs"] = tokens * pairs_a_token
            attrs[f"{phase}_routed_pairs_held"] = int(np.sum(loads))
            attrs[f"{phase}_expert_load_max"] = int(np.max(loads))
            lm_tokens_total().inc(tokens, phase=phase)
        if routes:
            # only the request that traced the programs gets here with
            # anything: which implementation the prefill's attention took
            attrs["attention"] = ", ".join(sorted(set(routes)))
        tracer.annotate(**attrs)
        return (out, {"ui": {"text": [out]}})
