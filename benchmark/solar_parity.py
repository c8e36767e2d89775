#!/usr/bin/env python3
"""Solar-Open2 on the chip against its float32 reference, outside any
timed window: at the published widths and the cell's sizes (the bundle
`load_pipeline` builds for the configuration's `registry_name`; the
committed workflow's 8,192-token prompt and 256 new tokens), the served
path's own two programs (`graph/nodes_text.generate_tokens`: the prefill,
whose KDA layers scan 128 chunks, and the 256-step decode through the
state tree) against the reference's forward pass over the 8,448 ids (the
delta rule as the per-token recurrence), teacher-forced on the ids the
system sampled.

    python3 benchmark/solar_parity.py [--seeds 2]

Prints, per seed: the relative L2 of the logits at the last prompt
position and at each decoded position (median and largest over the 257),
the share of (token, layer) pairs whose set of chosen experts differs
from the reference's, the largest relative L2 among the positions whose
own token chose the reference's experts in every layer, the relative L2
of each KDA layer's matrix state after the prefill (where a fault
entered), and the same numbers for two references that have to fail: one
computed a precision below the configuration's (float8 e4m3 operands),
and one with a wrong mechanism (beta = sigmoid, not 2 sigmoid: the delta
rule without its negative eigenvalue). The limits (`parity` in
configs/solar-open2-250b.json) have to pass the first and fail both
others. Also the seconds the prefill and a decode step took on this
script's own clock, beside what `solar_counts` says the chip's peaks
allow. Exit 1 if a limit does not hold. Writes
chiprun_out/solar_parity.json. One process: it holds the chip itself.

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
    """`logits` [P, vocab] and `states` [linear layers, H, d, d] against
    the reference's (`want`); `flips` [layers, P] are the rows' own tokens
    of `flipped`. `rel_l2_max_unflipped` is the largest relative L2 among
    the positions whose token chose the reference's experts in every
    layer (None where there is none): a flip moves a position by one
    expert's whole output, a fault anywhere else has to show here."""
    import numpy as np

    got, ref = np.asarray(logits, np.float64), np.asarray(want[0], np.float64)
    rel = np.linalg.norm(got - ref, axis=-1) / np.linalg.norm(ref, axis=-1)
    same = ~np.any(flips, axis=0)
    mine, theirs = np.asarray(states, np.float64), np.asarray(want[1], np.float64)
    layers = mine.shape[0]
    return {
        "rel_l2_median": float(np.median(rel)), "rel_l2_max": float(rel.max()),
        "rel_l2_prefill": float(rel[0]),
        "rel_l2_max_unflipped": float(rel[same].max()) if same.any() else None,
        "positions_unflipped": int(same.sum()),
        "state_rel_l2": [
            float(np.linalg.norm(mine[i] - theirs[i]) / np.linalg.norm(theirs[i]))
            for i in range(layers)],
    }


def within(numbers: dict, mismatch: float, limits: dict) -> bool:
    """Every limit of the configuration's `parity` holds."""
    worst = numbers["rel_l2_max_unflipped"]
    return (
        numbers["rel_l2_median"] <= limits["tolerance_rel_l2_median"]
        and mismatch <= limits["tolerance_expert_set_mismatch"]
        and worst is not None and worst <= limits["tolerance_rel_l2_max_unflipped"]
        and max(numbers["state_rel_l2"]) <= limits["tolerance_state_rel_l2"]
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

    import solar_counts
    from deepseek_parity import flipped  # [layers, tokens]: another set than the reference chose
    from comfyui_distributed_tpu.graph.nodes_text import generate_tokens
    from comfyui_distributed_tpu.models import pipeline as pl
    from comfyui_distributed_tpu.parallel.sharding import params_byte_size
    from comfyui_distributed_tpu.workers.startup import configure_compile_cache

    config = solar_counts.config()
    spec = importlib.util.spec_from_file_location(
        "solar_open2_reference", os.path.join(ROOT, config["reference"]))
    reference = importlib.util.module_from_spec(spec)
    sys.modules["solar_open2_reference"] = reference  # dataclasses looks the module up
    spec.loader.exec_module(reference)
    with open(os.path.join(HERE, "workflows", "rewrite-txt2img-solar-open2.json"),
              encoding="utf-8") as fh:
        (node,) = [n for n in json.load(fh).values() if n["class_type"] == "TextGenerate"]

    configure_compile_cache()
    device = jax.devices()[0]
    print(f"device: {device.platform} {device.device_kind} x{jax.device_count()}", flush=True)
    started = time.monotonic()
    bundle = pl.load_pipeline("tiny-solar-open2" if args.rehearsal else config["registry_name"])
    jax.block_until_ready(bundle.params)
    lm, params = bundle.lm, bundle.params["lm"]
    print(f"bundle: {params_byte_size(params) / 1e9:.3f} GB in {time.monotonic() - started:.1f} s",
          flush=True)
    sizes, held = reference.Sizes.of(lm.cfg), list(lm.cfg.held_experts)
    ids = bundle.tokenizer.encode(node["inputs"]["text"])
    steps = 8 if args.rehearsal else int(node["inputs"]["max_new_tokens"])
    temperature = float(node["inputs"]["temperature"])
    positions = np.arange(len(ids) - 1, len(ids) + steps)
    limits = config["parity"]
    head_chunk = 8 if args.rehearsal else 2  # two heads' float32 scores over 8,448 tokens: 0.57 GB
    report, ok = {"device": device.device_kind, "seeds": []}, True
    jax.block_until_ready(generate_tokens(bundle, ids, 0, steps, temperature)[1].ids)  # builds both

    def against(want, chosen_ref, got, chosen):
        flips = flipped(chosen, chosen_ref)
        return errors(*got, flips[:, positions], want), float(np.mean(flips))

    for seed in range(1, args.seeds + 1):
        began = time.monotonic()
        prefill, decode = generate_tokens(bundle, ids, seed, steps, temperature)
        jax.block_until_ready(prefill.logits)
        prefill_s = time.monotonic() - began
        jax.block_until_ready(decode.ids)
        both_s = time.monotonic() - began
        served_ids = np.asarray(decode.ids)
        del prefill, decode
        # the same two functions once more, keeping every step's logits
        # and chosen experts, which a served request does not pay for;
        # equal ids tie the served programs to what is compared below.
        # The decode takes the prefill's state by donation, so what the
        # prefill left is read before the decode is dispatched
        prefill = lm.prefill(params, jnp.asarray(ids, jnp.int32), len(ids) + steps, True)
        states = np.asarray(prefill.cache["state"])
        decode = lm.decode(
            params, prefill.cache, prefill.logits, len(ids), jax.random.key(seed), steps,
            temperature, True)
        full = np.concatenate([np.asarray(ids), np.asarray(decode.ids)])
        logits = jnp.concatenate([prefill.logits[None], decode.logits])
        chosen = np.concatenate(
            [np.asarray(prefill.chosen), np.asarray(decode.chosen).transpose(1, 0, 2)], axis=1)
        del prefill, decode

        def run(sizes, round_to=None):
            out, _, chosen_ref, states_ref = reference.forward(
                sizes, params, full, held, round_to=round_to, head_chunk=head_chunk,
                positions=positions, state_at=len(ids))
            return (np.asarray(out), np.asarray(states_ref)), np.asarray(chosen_ref)

        want, chosen_ref = run(sizes)
        entry = {
            "seed": seed, "prefill_s": prefill_s, "prefill_and_decode_s": both_s,
            "decode_step_s": (both_s - prefill_s) / steps,
            "served_ids_equal": bool(np.array_equal(served_ids, full[len(ids):])),
            "logit_abs_max": float(np.abs(want[0]).max()),
            "state_abs_max": [float(np.abs(s).max()) for s in want[1]],
        }
        entry["system"], entry["expert_set_mismatch"] = against(
            want, chosen_ref, (logits, states), chosen)
        if device.device_kind in solar_counts.PEAKS:
            # this script's clock (dispatch and read-back in it), not a
            # device trace: how far the reckoning is from the run
            peak = solar_counts.peaks(device.device_kind)
            pairs = float(np.sum(chosen[:, : len(ids)] < len(held)))
            entry["prefill_least_s"] = max(
                solar_counts.prefill_flops(config, len(ids), pairs) / peak["flops_per_s"],
                solar_counts.prefill_bytes(config, len(ids)) / peak["bytes_per_s"])
            entry["decode_step_least_s"] = solar_counts.decode_step_bytes(
                config, 1.0, len(ids) + steps // 2) / peak["bytes_per_s"]
        controls = {
            "float8_reference": (sizes, jnp.float8_e4m3fn),
            "beta_not_doubled_reference": (
                dataclasses.replace(sizes, kda_allow_neg_eigval=False), None),
        }
        passes = entry["served_ids_equal"] and within(
            entry["system"], entry["expert_set_mismatch"], limits)
        entry["system_within_limits"] = passes
        ok = ok and passes
        for name, (control_sizes, round_to) in controls.items():
            got, chosen_low = run(control_sizes, round_to)
            entry[name], entry[name + "_expert_set_mismatch"] = against(
                want, chosen_ref, got, chosen_low)
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
    with open(os.path.join(out_dir, "solar_parity.json"), "w", encoding="utf-8") as fh:
        json.dump(report, fh, indent=1)
    print(json.dumps({"ok": ok, "peak_bytes_in_use": peaks, "limits": {
        k: v for k, v in limits.items() if k.startswith("tolerance")}}), flush=True)
    return 0 if ok or args.rehearsal else 1


if __name__ == "__main__":
    sys.exit(main())
