#!/usr/bin/env python3
"""Nemotron-3-Nano on the chip against its float32 reference, outside any
timed window: at the published widths and the cell's sizes (the bundle
`load_pipeline` builds for the configuration's `registry_name`; the
committed workflow's 8,192-token prompt and 512 new tokens), the served
path's own two programs (`graph/nodes_text.generate_tokens`: the prefill,
whose 23 Mamba-2 blocks scan 64 chunks each, and the 512-step decode
through the state tree) against the reference's forward pass over the
8,704 ids (the state-space recurrence token by token), teacher-forced on
the ids the system sampled.

    python3 benchmark/nemotron3_nano_parity.py [--seeds 2]

Prints, per seed: the relative L2 of the logits at the last prompt
position and at each decoded position (median and largest over the 513),
the share of (token, sparse block) pairs whose set of chosen experts
differs from the reference's, the largest relative L2 among the positions
whose own token chose the reference's experts in every block, the
relative L2 of each Mamba-2 block's matrix state after the prefill and
after the last decoded token (where a fault entered; the first block's,
which no router precedes, is arithmetic alone and has a limit of its
own: deeper states also carry what a flipped expert did to the residual
stream above them), and the same
numbers for five controls that have to fail: the reference computed a
precision below the configuration's (float8 e4m3 operands); the reference
with a wrong mechanism, three times (a plain ReLU in the experts; the
group norm before the gate; head h reading B and C of group h mod 8);
and **the system carrying S in bfloat16** (the same two functions traced
anew with the matrix state rounded to bfloat16 between the prefill's
chunks and between the decode's steps). The limits (`parity` in
configs/nemotron-3-nano-30b-a3b.json) have to pass the first and fail
the others. Also the seconds the prefill and a decode step took on this
script's own clock, beside what `nemotron3_nano_counts` says the chip's
peaks allow. Exit 1 if a limit does not hold. Writes
chiprun_out/nemotron3_nano_parity.json. One process: it holds the chip
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


def errors(logits, states, flips, want) -> dict:
    """`logits` [P, vocab] and `states` [2, M blocks, H, P, N] (after the
    prefill, after the last token) against the reference's (`want`);
    `flips` [E blocks, P] are the rows' own tokens of `flipped`.
    `rel_l2_max_unflipped` is the largest relative L2 among the positions
    whose token chose the reference's experts in every block (None where
    there is none)."""
    import numpy as np

    got, ref = np.asarray(logits, np.float64), np.asarray(want[0], np.float64)
    rel = np.linalg.norm(got - ref, axis=-1) / np.linalg.norm(ref, axis=-1)
    same = ~np.any(flips, axis=0)
    mine, theirs = np.asarray(states, np.float64), np.asarray(want[1], np.float64)

    def distance(when):
        return [float(np.linalg.norm(mine[when, i] - theirs[when, i])
                      / np.linalg.norm(theirs[when, i])) for i in range(mine.shape[1])]

    return {
        "rel_l2_median": float(np.median(rel)), "rel_l2_max": float(rel.max()),
        "rel_l2_prefill": float(rel[0]),
        "rel_l2_max_unflipped": float(rel[same].max()) if same.any() else None,
        "positions_unflipped": int(same.sum()),
        "state_rel_l2": distance(0), "final_state_rel_l2": distance(1),
        "first_state_rel_l2": max(distance(0)[0], distance(1)[0]),
    }


def within(numbers: dict, mismatch: float, limits: dict) -> bool:
    """Every limit of the configuration's `parity` holds."""
    worst = numbers["rel_l2_max_unflipped"]
    return (
        numbers["rel_l2_median"] <= limits["tolerance_rel_l2_median"]
        and mismatch <= limits["tolerance_expert_set_mismatch"]
        and worst is not None and worst <= limits["tolerance_rel_l2_max_unflipped"]
        and max(numbers["state_rel_l2"]) <= limits["tolerance_state_rel_l2"]
        and numbers["first_state_rel_l2"] <= limits["tolerance_first_state_rel_l2"]
    )


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

    import nemotron3_nano_counts as counts
    from deepseek_parity import flipped  # [layers, tokens]: another set than the reference chose
    from comfyui_distributed_tpu.graph.nodes_text import generate_tokens
    from comfyui_distributed_tpu.models import mamba2, nemotron_h
    from comfyui_distributed_tpu.models import pipeline as pl
    from comfyui_distributed_tpu.parallel.sharding import params_byte_size
    from comfyui_distributed_tpu.workers.startup import configure_compile_cache

    config = counts.config()
    spec = importlib.util.spec_from_file_location(
        "nemotron_h_reference", os.path.join(ROOT, config["reference"]))
    reference = importlib.util.module_from_spec(spec)
    sys.modules["nemotron_h_reference"] = reference  # dataclasses looks the module up
    spec.loader.exec_module(reference)
    with open(os.path.join(HERE, "workflows", "rewrite-txt2img-nemotron3-nano.json"),
              encoding="utf-8") as fh:
        (node,) = [n for n in json.load(fh).values() if n["class_type"] == "TextGenerate"]

    configure_compile_cache()
    device = jax.devices()[0]
    print(f"device: {device.platform} {device.device_kind} x{jax.device_count()}", flush=True)
    started = time.monotonic()
    bundle = pl.load_pipeline("tiny-nemotron3-nano" if args.rehearsal else config["registry_name"])
    jax.block_until_ready(bundle.params)
    lm, params = bundle.lm, bundle.params["lm"]
    cfg = lm.cfg
    print(f"bundle: {params_byte_size(params) / 1e9:.3f} GB in {time.monotonic() - started:.1f} s",
          flush=True)
    sizes, held = reference.Sizes.of(cfg), list(cfg.held_experts)
    blocks = nemotron_h.unstacked(cfg, params)
    ids = bundle.tokenizer.encode(node["inputs"]["text"])
    steps = 8 if args.rehearsal else int(node["inputs"]["max_new_tokens"])
    temperature = float(node["inputs"]["temperature"])
    positions = np.arange(len(ids) - 1, len(ids) + steps)
    limits = config["parity"]
    head_chunk = 8 if args.rehearsal else 2  # two heads' float32 scores over 8,704 tokens: 0.61 GB
    report, ok = {"device": device.device_kind, "seeds": []}, True
    began = time.monotonic()
    jax.block_until_ready(generate_tokens(bundle, ids, 0, steps, temperature)[1].ids)  # builds both
    report["first_request_s"] = time.monotonic() - began
    print(f"first request (both programs built): {report['first_request_s']:.1f} s", flush=True)

    def matrix_states(cache):
        """[M blocks, H, P, N] in published order, of a state tree."""
        return np.concatenate(
            [np.asarray(s).reshape(-1, *s.shape[-3:]) for s in cache["ssm"]])

    def collected(seed, prefill_fn, decode_fn):
        """The two functions once more, keeping every step's logits and
        chosen experts. The decode takes the prefill's state by donation,
        so what the prefill left is read before the decode is dispatched."""
        prefill = prefill_fn(cfg, params, jnp.asarray(ids, jnp.int32),
                             cache_len=len(ids) + steps, collect=True)
        after_prefill = matrix_states(prefill.cache)
        decode = decode_fn(
            cfg, params, prefill.cache, prefill.logits, jnp.int32(len(ids)),
            jax.random.key(seed), jnp.float32(temperature), steps=steps, collect=True)
        full = np.concatenate([np.asarray(ids), np.asarray(decode.ids)])
        logits = np.concatenate([np.asarray(prefill.logits)[None], np.asarray(decode.logits)])
        chosen = np.concatenate(
            [np.asarray(prefill.chosen), np.asarray(decode.chosen).transpose(1, 0, 2)], axis=1)
        states = np.stack([after_prefill, matrix_states(decode.cache)])
        return full, logits, chosen, states

    def bfloat16_state():
        """The system's two functions traced anew (new function objects:
        JAX would else hand back the cached trace) with `mamba2.mixer`
        carrying S in bfloat16: rounded between the prefill's chunks (the
        chunked form a chunk at a time under a scan) and after every
        decode step."""
        chunked, mixer = mamba2.ssd_chunked, mamba2.mixer
        # not a pair of casts: the compiler may keep the excess precision of those
        low = lambda s: jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)

        def by_chunk(u, b, c, step, a, state, chunk):
            count = u.shape[0] // chunk  # the cell's prompt is whole chunks

            def one(state, xs):
                y, state = chunked(*xs, a, state, chunk)
                return low(state), y

            split = lambda t: t[:count * chunk].reshape(count, chunk, *t.shape[1:])
            state, y = jax.lax.scan(one, low(state), tuple(map(split, (u, b, c, step))))
            if count * chunk < u.shape[0]:
                rest, state = chunked(*(t[count * chunk:] for t in (u, b, c, step)), a, state, chunk)
                return jnp.concatenate([y.reshape(-1, *y.shape[2:]), rest]), low(state)
            return y.reshape(-1, *y.shape[2:]), state

        def rounding(*operands):
            out, tail, state = mixer(*operands)
            return out, tail, low(state)

        def patched(fn, *operands, **static):
            mamba2.ssd_chunked, mamba2.mixer = by_chunk, rounding
            try:
                return fn.__wrapped__(*operands, **static)
            finally:
                mamba2.ssd_chunked, mamba2.mixer = chunked, mixer

        def prefill(cfg, params, ids, *, cache_len, collect):
            return patched(nemotron_h.prefill, cfg, params, ids, cache_len=cache_len,
                           collect=collect)

        def decode(cfg, params, cache, logits, start, key, temperature, *, steps, collect):
            return patched(nemotron_h.decode, cfg, params, cache, logits, start, key, temperature,
                           steps=steps, collect=collect)

        return (jax.jit(prefill, static_argnames=("cfg", "cache_len", "collect")),
                jax.jit(decode, static_argnames=("cfg", "steps", "collect")))

    for seed in range(1, args.seeds + 1):
        began = time.monotonic()
        prefill, decode = generate_tokens(bundle, ids, seed, steps, temperature)
        jax.block_until_ready(prefill.logits)
        prefill_s = time.monotonic() - began
        jax.block_until_ready(decode.ids)
        both_s = time.monotonic() - began
        served_ids = np.asarray(decode.ids)
        del prefill, decode
        full, logits, chosen, states = collected(seed, nemotron_h.prefill, nemotron_h.decode)

        def run(sizes, round_to=None, over=full):
            out, chosen_ref, states_ref = reference.forward(
                sizes, blocks, over, held, round_to=round_to, head_chunk=head_chunk,
                positions=positions, state_at=len(ids))
            return (np.asarray(out), np.asarray(states_ref)), np.asarray(chosen_ref)

        def against(want, chosen_ref, got, chosen):
            flips = flipped(chosen, chosen_ref)
            return errors(*got, flips[:, positions], want), float(np.mean(flips))

        want, chosen_ref = run(sizes)
        entry = {
            "seed": seed, "prefill_s": prefill_s, "prefill_and_decode_s": both_s,
            "decode_step_s": (both_s - prefill_s) / steps,
            "served_ids_equal": bool(np.array_equal(served_ids, full[len(ids):])),
            "logit_abs_max": float(np.abs(want[0]).max()),
            "state_abs_max": float(np.abs(want[1]).max()),
        }
        entry["system"], entry["expert_set_mismatch"] = against(
            want, chosen_ref, (logits, states), chosen)
        if device.device_kind in counts.PEAKS:
            # this script's clock (dispatch and read-back in it), not a
            # device trace: how far the reckoning is from the run
            peak = counts.peaks(device.device_kind)
            pairs = float(np.sum(chosen[:, : len(ids)] < len(held)))
            read = float(np.sum(chosen[:, len(ids):] < len(held))) / steps
            entry["prefill_least_s"] = max(
                counts.prefill_flops(config, len(ids), pairs) / peak["flops_per_s"],
                counts.prefill_bytes(config, len(ids)) / peak["bytes_per_s"])
            entry["decode_step_least_s"] = counts.decode_step_bytes(
                config, read, len(ids) + steps // 2) / peak["bytes_per_s"]
        passes = entry["served_ids_equal"] and within(
            entry["system"], entry["expert_set_mismatch"], limits)
        entry["system_within_limits"] = passes
        ok = ok and passes
        controls = {
            "float8_reference": (sizes, jnp.float8_e4m3fn),
            "plain_relu_reference": (dataclasses.replace(sizes, expert_square=False), None),
            "norm_before_gate_reference": (
                dataclasses.replace(sizes, gate_before_norm=False), None),
            "groups_strided_reference": (dataclasses.replace(sizes, groups_strided=True), None),
        }
        for name, (control_sizes, round_to) in controls.items():
            got, chosen_low = run(control_sizes, round_to)
            entry[name], entry[name + "_expert_set_mismatch"] = against(
                want, chosen_ref, got, chosen_low)
            fails = not within(entry[name], entry[name + "_expert_set_mismatch"], limits)
            entry[name + "_outside_limits"] = fails
            ok = ok and fails
        # the system's control: its own ids, so its own teacher-forced reference
        name = "bfloat16_state_system"
        full_low, logits_low, chosen_low, states_low = collected(seed, *bfloat16_state())
        want_low, chosen_ref_low = run(sizes, over=full_low)
        entry[name], entry[name + "_expert_set_mismatch"] = against(
            want_low, chosen_ref_low, (logits_low, states_low), chosen_low)
        fails = not within(entry[name], entry[name + "_expert_set_mismatch"], limits)
        entry[name + "_outside_limits"] = fails
        ok = ok and fails
        report["seeds"].append(entry)
        print(json.dumps(entry), flush=True)
    peaks = (device.memory_stats() or {}).get("peak_bytes_in_use")
    report["peak_bytes_in_use"] = peaks
    report["ok"] = ok
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "nemotron3_nano_parity.json"), "w", encoding="utf-8") as fh:
        json.dump(report, fh, indent=1)
    print(json.dumps({"ok": ok, "peak_bytes_in_use": peaks, "limits": {
        k: v for k, v in limits.items() if k.startswith("tolerance")}}), flush=True)
    return 0 if ok or args.rehearsal else 1


if __name__ == "__main__":
    sys.exit(main())
