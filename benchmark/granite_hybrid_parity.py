#!/usr/bin/env python3
"""granite-4.0-h-micro on the chip against its float32 reference, outside
any timed window: at the published sizes and the cell's lengths (the
bundle `load_pipeline` builds for the configuration's `registry_name`;
the committed workflow's 65,536-token prompt and 128 new tokens), the
served path's own two programs (`graph/nodes_text.generate_tokens`: the
prefill in 8 parts of 8,192, each of the 36 Mamba-2 layers handing its
float32 state and tail from part to part, and the 128-step decode
through the state tree) against the reference's forward pass over the
65,664 ids (the state-space recurrence token by token, attention under
the full mask in blocks of rows), teacher-forced on the ids the system
sampled.

    python3 benchmark/granite_hybrid_parity.py [--seeds 2]

The system's runs come first; then the weights leave the device and the
reference reads them from the host, a layer upcast at a time (12.8 GB of
float32 weights do not fit beside the bundle). Prints, per seed: the
relative L2 of the logits at the last prompt position and at each decoded
position (median and largest over the 129); of every Mamba-2 layer's
matrix state after the prefill (the largest is limited); of layer 0's
state, which nothing rounded precedes, after the prefill and after the
last token (the larger is limited); of the keys and values layer 5 wrote,
part by part (the largest part's is limited); and the same numbers for
five controls that have to fail: the reference on float8 e4m3 operands;
the reference whose second part starts from a zero state (every Mamba
layer forgets at position 8,192); the reference with attention scale
0.125 (64^-1/2) and with `residual_multiplier` 1; and **the system
carrying S in bfloat16** (the same two functions traced anew with the
matrix state rounded to bfloat16 after every part and every decode step:
its ids are its own, so its decode is held to layer 0's state alone,
against the reference's layer 0 over those ids). The limits (`parity` in
configs/granite-4.0-h-micro.json) have to pass the first and fail the
others. Also the seconds the prefill and a decode step took on this
script's own clock, beside what `granite_hybrid_counts` says the chip's
peaks allow. Exit 1 if a limit does not hold. Writes
chiprun_out/granite_hybrid_parity.json. One process: it holds the chip
itself.

`--rehearsal` checks this script on the CPU with the tiny preset; its
numbers mean nothing.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)


def whole_rel_l2(got, want) -> float:
    import numpy as np

    got, want = np.asarray(got, np.float64).ravel(), np.asarray(want, np.float64).ravel()
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def errors(mine: dict, want: dict, part: int) -> dict:
    """What `mine` has of `logits` [P, vocab], `states` [2, Mamba layers,
    H, P, N] (after the prefill, after the last token) and `kv` [2, T,
    key heads, d] (layer 5's, over the prompt) against the reference's."""
    import numpy as np

    from glm_dsa_parity import rel_l2  # row by row

    out = {}
    if "logits" in mine:
        rel = rel_l2(mine["logits"], want["logits"][: len(mine["logits"])])
        out.update(rel_l2_median=float(np.median(rel)), rel_l2_max=float(rel.max()),
                   rel_l2_prefill=float(rel[0]), positions=int(len(rel)))
    if "states" in mine:
        after = [whole_rel_l2(a, b) for a, b in zip(mine["states"][0], want["states"][0])]
        out.update(state_rel_l2=after, state_rel_l2_max=max(after))
        first = [after[0]]
        if len(mine["states"]) > 1:
            first.append(whole_rel_l2(mine["states"][1][0], want["states"][1][0]))
        out.update(first_state_rel_l2_each=first, first_state_rel_l2=max(first))
    if "kv" in mine:
        tokens = mine["kv"].shape[1]
        parts = [whole_rel_l2(mine["kv"][:, at:at + part], want["kv"][:, at:at + part])
                 for at in range(0, tokens, part)]
        out.update(kv_rel_l2_by_part=parts, kv_rel_l2=max(parts))
    return out


LIMITS = {"rel_l2_median": "tolerance_rel_l2_median", "rel_l2_max": "tolerance_rel_l2_max",
          "state_rel_l2_max": "tolerance_state_rel_l2",
          "first_state_rel_l2": "tolerance_first_state_rel_l2",
          "kv_rel_l2": "tolerance_kv_rel_l2"}


def within(numbers: dict, limits: dict) -> bool:
    """Every limit of the configuration's `parity` that `numbers` has a
    reading for holds."""
    return all(numbers[name] <= limits[limit] for name, limit in LIMITS.items()
               if name in numbers)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seeds", type=int, default=2)
    parser.add_argument("--rehearsal", action="store_true")
    args = parser.parse_args(argv)
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    import granite_hybrid_counts as counts
    from comfyui_distributed_tpu.graph.nodes_text import generate_tokens
    from comfyui_distributed_tpu.models import granite_hybrid, mamba2
    from comfyui_distributed_tpu.models import pipeline as pl
    from comfyui_distributed_tpu.parallel.sharding import params_byte_size
    from comfyui_distributed_tpu.workers.startup import configure_compile_cache

    config = counts.config()
    spec = importlib.util.spec_from_file_location(
        "granite_hybrid_reference", os.path.join(ROOT, config["reference"]))
    reference = importlib.util.module_from_spec(spec)
    sys.modules["granite_hybrid_reference"] = reference  # dataclasses looks the module up
    spec.loader.exec_module(reference)
    with open(os.path.join(HERE, "workflows", "longdoc-txt2img-granite-4.0-h-micro.json"),
              encoding="utf-8") as fh:
        (node,) = [n for n in json.load(fh).values() if n["class_type"] == "TextGenerate"]

    configure_compile_cache()
    device = jax.devices()[0]
    print(f"device: {device.platform} {device.device_kind} x{jax.device_count()}", flush=True)
    started = time.monotonic()
    bundle = pl.load_pipeline(
        "tiny-granite-hybrid" if args.rehearsal else config["registry_name"])
    jax.block_until_ready(bundle.params)
    lm, params = bundle.lm, bundle.params["lm"]
    cfg = lm.cfg
    print(f"bundle: {params_byte_size(params) / 1e9:.3f} GB in {time.monotonic() - started:.1f} s",
          flush=True)
    sizes = reference.Sizes.of(cfg)
    text = node["inputs"]["text"]
    ids = bundle.tokenizer.encode(text[:42] if args.rehearsal else text)
    steps = 8 if args.rehearsal else int(node["inputs"]["max_new_tokens"])
    temperature = float(node["inputs"]["temperature"])
    prompt, part = len(ids), cfg.prefill_part
    positions = np.arange(prompt - 1, prompt + steps)
    limits = config["parity"]
    # four heads' float32 scores of 1,024 rows over 65,664 positions: 1.08 GB
    blocks = {"head_chunk": 2, "row_block": 16} if args.rehearsal else {
        "head_chunk": 4, "row_block": 1024}
    report, ok = {"device": device.device_kind, "seeds": []}, True
    began = time.monotonic()
    jax.block_until_ready(generate_tokens(bundle, ids, 0, steps, temperature)[1].ids)  # builds both
    report["first_request_s"] = time.monotonic() - began
    print(f"first request (both programs built): {report['first_request_s']:.1f} s", flush=True)

    def matrix_states(cache):
        return np.stack([np.asarray(s) for s in cache["ssm"]])

    def collected(seed, prefill_fn, decode_fn):
        """The two functions once more, keeping every step's logits. The
        decode takes the prefill's state by donation, so what the prefill
        left is read before the decode is dispatched."""
        prefill = prefill_fn(cfg, params, jnp.asarray(ids, jnp.int32),
                             cache_len=prompt + steps, collect=True)
        after_prefill = matrix_states(prefill.cache)
        kv = np.asarray(prefill.cache["kv"][0][:, :, :prompt]).transpose(0, 2, 1, 3)
        decode = decode_fn(
            cfg, params, prefill.cache, prefill.logits, jnp.int32(prompt),
            jax.random.key(seed), jnp.float32(temperature), steps=steps, collect=True)
        full = np.concatenate([np.asarray(ids), np.asarray(decode.ids)])
        logits = np.concatenate([np.asarray(prefill.logits)[None], np.asarray(decode.logits)])
        states = np.stack([after_prefill, matrix_states(decode.cache)])
        return full, {"logits": logits, "states": states, "kv": kv}

    def bfloat16_state():
        """The system's two functions traced anew (new function objects:
        JAX would else hand back the cached trace) with `mamba2.mixer`
        handing on S in bfloat16: rounded after every part of the prompt
        and after every decode step."""
        mixer = mamba2.mixer
        # not a pair of casts: the compiler may keep the excess precision of those
        low = lambda s: jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)

        def rounding(*operands):
            out, tail, state = mixer(*operands)
            return out, tail, low(state)

        def patched(fn, *operands, **static):
            mamba2.mixer = rounding
            try:
                return fn.__wrapped__(*operands, **static)
            finally:
                mamba2.mixer = mixer

        def prefill(cfg, params, ids, *, cache_len, collect):
            return patched(granite_hybrid.prefill, cfg, params, ids, cache_len=cache_len,
                           collect=collect)

        def decode(cfg, params, cache, logits, start, key, temperature, *, steps, collect):
            return patched(granite_hybrid.decode, cfg, params, cache, logits, start, key,
                           temperature, steps=steps, collect=collect)

        return (jax.jit(prefill, static_argnames=("cfg", "cache_len", "collect")),
                jax.jit(decode, static_argnames=("cfg", "steps", "collect")))

    runs = []
    low_fns = bfloat16_state()
    for seed in range(1, args.seeds + 1):
        began = time.monotonic()
        prefill, decode = generate_tokens(bundle, ids, seed, steps, temperature)
        jax.block_until_ready(prefill.logits)
        prefill_s = time.monotonic() - began
        jax.block_until_ready(decode.ids)
        both_s = time.monotonic() - began
        served_ids = np.asarray(decode.ids)
        del prefill, decode
        full, mine = collected(seed, granite_hybrid.prefill, granite_hybrid.decode)
        full_low, low = collected(seed, *low_fns)
        entry = {
            "seed": seed, "prefill_s": prefill_s, "prefill_and_decode_s": both_s,
            "decode_step_s": (both_s - prefill_s) / steps,
            "served_ids_equal": bool(np.array_equal(served_ids, full[prompt:])),
            "bfloat16_state_ids_equal": bool(np.array_equal(full_low, full)),
        }
        if device.device_kind in counts.PEAKS:
            # this script's clock (dispatch and read-back in it), not a device trace
            peak = counts.peaks(device.device_kind)
            entry["prefill_least_s"] = max(
                counts.prefill_flops(config, prompt) / peak["flops_per_s"],
                counts.prefill_bytes(config, prompt) / peak["bytes_per_s"])
            entry["decode_step_least_s"] = counts.decode_step_bytes(
                config, prompt + steps // 2) / peak["bytes_per_s"]
        runs.append((entry, full, mine, full_low, low))
        print(json.dumps(entry), flush=True)
    report["peak_bytes_in_use"] = (device.memory_stats() or {}).get("peak_bytes_in_use")

    # the weights to the host: the reference's float32 working set has the chip to itself
    weights = jax.device_get(params)
    bundle.params.clear()
    for leaf in jax.tree_util.tree_leaves(params):
        leaf.delete()
    del params, low_fns

    def run(sizes, over, round_to=None):
        logits, states, _, kv = reference.forward(
            sizes, weights, over, round_to=round_to, positions=positions, state_at=prompt,
            **blocks)
        return {"logits": np.asarray(logits), "states": np.asarray(states),
                "kv": np.asarray(kv[0])[:, :prompt]}

    def first_layer(over):
        """The reference's layer 0 alone over `over`: its state after the
        prompt and after the last token."""
        with jax.default_matmul_precision("highest"):
            h = sizes.embedding_multiplier * jnp.asarray(weights["embed"], jnp.float32)[
                jnp.asarray(over)]
        _, kept = reference.layer(sizes, weights["layers"][0], h, state_at=prompt, **blocks)
        return np.stack([np.asarray(kept[0]), np.asarray(kept[1])])

    wrong = {
        "float8_reference": (sizes, jnp.float8_e4m3fn),
        "second_part_from_zero_state_reference": (
            dataclasses.replace(sizes, zero_state_at=part), None),
        "attention_scale_inverse_root_reference": (
            dataclasses.replace(sizes, attention_multiplier=cfg.head_dim ** -0.5), None),
        "residual_multiplier_one_reference": (
            dataclasses.replace(sizes, residual_multiplier=1.0), None),
    }
    for entry, full, mine, full_low, low in runs:
        began = time.monotonic()
        want = run(sizes, full)
        entry["reference_s"] = time.monotonic() - began
        entry["logit_abs_max"] = float(np.abs(want["logits"]).max())
        entry["state_abs_max"] = float(np.abs(want["states"]).max())
        entry["system"] = errors(mine, want, part)
        passes = entry["served_ids_equal"] and within(entry["system"], limits)
        entry["system_within_limits"] = passes
        ok = ok and passes
        print(json.dumps({"seed": entry["seed"], "system": entry["system"]}), flush=True)
        for name, (control_sizes, round_to) in wrong.items():
            # the control in the system's place, against the reference proper
            entry[name] = errors(run(control_sizes, full, round_to), want, part)
            entry[name + "_outside_limits"] = not within(entry[name], limits)
            ok = ok and entry[name + "_outside_limits"]
            print(json.dumps({"seed": entry["seed"], "control": name, **entry[name]}), flush=True)
        # the system's control: what the prompt alone decides against the same reference
        # (its logits at the last prompt position, its states and layer 5's keys and
        # values after the prefill); after its own decode, layer 0's state against the
        # reference's layer 0 over its own ids
        name = "bfloat16_state_system"
        theirs = dict(want, states=want["states"].copy())
        theirs["states"][1][0] = first_layer(full_low)[1]
        entry[name] = errors({**low, "logits": low["logits"][:1]}, theirs, part)
        entry[name + "_outside_limits"] = not within(entry[name], limits)
        ok = ok and entry[name + "_outside_limits"]
        print(json.dumps({"seed": entry["seed"], "control": name, **entry[name]}), flush=True)
        report["seeds"].append(entry)
    report["ok"] = ok
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "granite_hybrid_parity.json"), "w", encoding="utf-8") as fh:
        json.dump(report, fh, indent=1)
    print(json.dumps({"ok": ok, "peak_bytes_in_use": report["peak_bytes_in_use"], "limits": {
        k: v for k, v in limits.items() if k.startswith("tolerance")}}), flush=True)
    return 0 if ok or args.rehearsal else 1


if __name__ == "__main__":
    sys.exit(main())
