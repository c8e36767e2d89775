#!/usr/bin/env python3
"""DeepSeek-V2 on the chip against its float32 reference, outside any
timed window: at the published widths and the cell's sizes (the bundle
`load_pipeline` builds for the configuration's `registry_name`; the
committed workflow's 2,048-token prompt and 256 new tokens), the served
path's own two programs (`graph/nodes_text.generate_tokens`: the prefill
and the 256-step decode through the latent cache) against the reference's
forward pass over the 2,304 ids, teacher-forced on the ids the system
sampled.

    python3 benchmark/deepseek_parity.py [--seeds 2]

Prints, per seed: the relative L2 and the largest absolute error of the
logits at the last prompt position and at each decoded position (median
and largest over the 257 positions), the share of (token, expert layer)
pairs whose set of chosen experts differs from the reference's, the largest
relative L2 among the positions whose own token chose the reference's
experts in every layer, the error of each layer when it is fed the reference's own input, and the same
logit numbers for the reference computed one precision below the
configuration's (float8 e4m3 operands). The limits (`parity` in
configs/deepseek-v2.json) have to pass the first and fail the second.
Exit 1 if either does not hold. Writes chiprun_out/deepseek_parity.json.
One process: it holds the chip itself.

`--rehearsal` checks this script on the CPU with the tiny preset; its
numbers mean nothing.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def flipped(mine, theirs):
    """[expert layers, tokens] bool: the chosen set differs from the
    reference's."""
    import numpy as np

    mine, theirs = np.sort(np.asarray(mine), -1), np.sort(np.asarray(theirs), -1)
    return np.any(mine != theirs, axis=-1)


def errors(got, want, flips) -> dict:
    """Per position (row) relative L2 and largest absolute difference;
    `flips` [expert layers, positions] are the rows' own tokens of
    `flipped`. `rel_l2_max_unflipped` is the largest relative L2 among
    the positions whose token chose the reference's experts in every
    layer (None where there is none): a flip moves a position by one
    expert's whole output, a fault anywhere else has to show here."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    rel = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    same = ~np.any(flips, axis=0)
    return {
        "rel_l2_median": float(np.median(rel)), "rel_l2_max": float(rel.max()),
        "rel_l2_prefill": float(rel[0]),
        "rel_l2_max_unflipped": float(rel[same].max()) if same.any() else None,
        "positions_unflipped": int(same.sum()),
        "max_abs": float(np.abs(got - want).max()),
    }


def within(numbers: dict, mismatch: float, limits: dict) -> bool:
    """Every limit of the configuration's `parity` holds: `numbers` of
    `errors`, `mismatch` the share of (token, expert layer) pairs that
    flipped."""
    worst = numbers["rel_l2_max_unflipped"]
    return (
        numbers["rel_l2_median"] <= limits["tolerance_rel_l2_median"]
        and mismatch <= limits["tolerance_expert_set_mismatch"]
        and worst is not None and worst <= limits["tolerance_rel_l2_max_unflipped"]
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

    from comfyui_distributed_tpu.graph.nodes_text import generate_tokens
    from comfyui_distributed_tpu.models import deepseek_v2 as system
    from comfyui_distributed_tpu.models import pipeline as pl
    from comfyui_distributed_tpu.parallel.sharding import params_byte_size
    from comfyui_distributed_tpu.workers.startup import configure_compile_cache

    with open(os.path.join(HERE, "configs", "deepseek-v2.json"), encoding="utf-8") as fh:
        config = json.load(fh)
    spec = importlib.util.spec_from_file_location(
        "deepseek_v2_reference", os.path.join(ROOT, config["reference"]))
    reference = importlib.util.module_from_spec(spec)
    sys.modules["deepseek_v2_reference"] = reference  # dataclasses looks the module up
    spec.loader.exec_module(reference)
    with open(os.path.join(HERE, "workflows", "rewrite-txt2img-deepseek-v2.json"),
              encoding="utf-8") as fh:
        (node,) = [n for n in json.load(fh).values() if n["class_type"] == "TextGenerate"]

    configure_compile_cache()
    device = jax.devices()[0]
    print(f"device: {device.platform} {device.device_kind} x{jax.device_count()}", flush=True)
    started = time.monotonic()
    bundle = pl.load_pipeline("tiny-deepseek-v2" if args.rehearsal else config["registry_name"])
    jax.block_until_ready(bundle.params)
    cfg, params = bundle.lm.cfg, bundle.params["lm"]
    print(f"bundle: {params_byte_size(params) / 1e9:.3f} GB in {time.monotonic() - started:.1f} s",
          flush=True)
    sizes = reference.Sizes.of(cfg)
    held = list(cfg.held_experts)
    ids = bundle.tokenizer.encode(node["inputs"]["text"])
    steps, temperature = int(node["inputs"]["max_new_tokens"]), float(node["inputs"]["temperature"])
    positions = np.arange(len(ids) - 1, len(ids) + steps)
    limits = config["parity"]
    report, ok = {"device": device.device_kind, "seeds": []}, True
    jax.block_until_ready(generate_tokens(bundle, ids, 0, steps, temperature))  # builds both
    for seed in range(1, args.seeds + 1):
        began = time.monotonic()
        prefill, decode = generate_tokens(bundle, ids, seed, steps, temperature)
        jax.block_until_ready(prefill.logits)
        prefill_s = time.monotonic() - began
        jax.block_until_ready(decode)
        both_s = time.monotonic() - began
        served_ids = np.asarray(decode.ids)
        # the same two functions once more, keeping every step's logits
        # and chosen experts, which a served request does not pay for;
        # equal ids tie the served programs to what is compared below
        prefill, decode = generate_tokens(bundle, ids, seed, steps, temperature, collect=True)
        full = np.concatenate([np.asarray(ids), np.asarray(decode.ids)])
        mine = jnp.concatenate([prefill.logits[None], decode.logits])
        chosen = np.concatenate(
            [np.asarray(prefill.chosen), np.asarray(decode.chosen).transpose(1, 0, 2)], axis=1)
        want, hidden, chosen_ref = reference.forward(sizes, params, full, held, positions=positions)
        flips = flipped(chosen, chosen_ref)
        entry = {
            "seed": seed, "prefill_s": prefill_s, "prefill_and_decode_s": both_s,
            "served_ids_equal": bool(np.array_equal(served_ids, full[len(ids):])),
            "system": errors(mine, want, flips[:, positions]),
            "expert_set_mismatch": float(np.mean(flips)),
            "logit_abs_max": float(np.abs(np.asarray(want)).max()),
        }
        # each layer alone, fed the reference's input: the layer's own error
        rope = system.rope_tables(cfg, jnp.arange(len(full)))
        per_layer = []
        for index, block in enumerate(params["layers"]):
            out = jax.jit(system.block_expanded, static_argnums=(0, 1))(
                cfg, index, block, hidden[index].astype(params["embed"].dtype), rope)[0]
            got, ref_out = np.asarray(out, np.float64), np.asarray(hidden[index + 1], np.float64)
            per_layer.append(float(np.linalg.norm(got - ref_out) / np.linalg.norm(ref_out)))
        entry["layer_rel_l2"] = per_layer
        del hidden
        low, _, chosen_low = reference.forward(
            sizes, params, full, held, positions=positions, round_to=jnp.float8_e4m3fn)
        flips_low = flipped(chosen_low, chosen_ref)
        entry["float8_reference"] = errors(low, want, flips_low[:, positions])
        entry["float8_expert_set_mismatch"] = float(np.mean(flips_low))
        passes = entry["served_ids_equal"] and within(
            entry["system"], entry["expert_set_mismatch"], limits)
        fails = not within(
            entry["float8_reference"], entry["float8_expert_set_mismatch"], limits)
        entry["system_within_limits"], entry["float8_outside_limits"] = passes, fails
        ok = ok and passes and fails
        report["seeds"].append(entry)
        print(json.dumps(entry), flush=True)
    peaks = (device.memory_stats() or {}).get("peak_bytes_in_use")
    report["peak_bytes_in_use"] = peaks
    report["ok"] = ok
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "deepseek_parity.json"), "w", encoding="utf-8") as fh:
        json.dump(report, fh, indent=1)
    print(json.dumps({"ok": ok, "peak_bytes_in_use": peaks, "limits": {
        k: v for k, v in limits.items() if k.startswith("tolerance")}}), flush=True)
    return 0 if ok or args.rehearsal else 1


if __name__ == "__main__":
    sys.exit(main())
