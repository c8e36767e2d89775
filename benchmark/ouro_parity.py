#!/usr/bin/env python3
"""Ouro-2.6B on the chip against its float32 reference, outside any timed
window: at the published sizes and the cell's lengths (the bundle
`load_pipeline` builds for the configuration's `registry_name`; the
committed workflow's 2,048-token prompt and 64 new tokens), the served
path's own two programs (`graph/nodes_text.generate_tokens`: the prefill
and the 64-step decode through the 192-slot cache) against the reference's
forward pass over the 2,112 ids, teacher-forced on the ids the system
sampled.

    python3 benchmark/ouro_parity.py [--seeds 2]

Prints, per seed: the relative L2 of the logits at the last prompt
position and at each decoded position (median and largest over the 65),
the same of every pass's h_t (which pass a fault entered at), the largest
absolute error of the exit distribution p(t), and the same numbers for two
references that have to fail: one computed a precision below the
configuration's (float8 e4m3 operands), and one in which every pass reads
the first pass's keys and values (a cache with one slot a layer). The
limits (`parity` in configs/ouro-2.6b.json: the logits' median and largest
position, every pass's h_t, p(t)) have to pass the first and fail both
others. Also the seconds the prefill and a decode step took on
this script's own clock, beside what `ouro_counts` says the chip's peaks
allow. Exit 1 if a limit does not hold. Writes
chiprun_out/ouro_parity.json. One process: it holds the chip itself.

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
sys.path.insert(0, HERE)


def rel_l2(got, want):
    """Per row."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


def errors(logits, hidden, exits, want) -> dict:
    """`logits` [P, vocab], `hidden` [T, P, hidden] and `exits` [T, P]
    against the reference's three (`want`)."""
    import numpy as np

    rel = rel_l2(logits, want[0])
    passes = rel_l2(hidden, want[1])                      # [T, P]
    return {
        "rel_l2_median": float(np.median(rel)), "rel_l2_max": float(rel.max()),
        "rel_l2_prefill": float(rel[0]),
        "hidden_rel_l2_median": [float(v) for v in np.median(passes, axis=1)],
        "hidden_rel_l2_max": [float(v) for v in passes.max(axis=1)],
        "exit_abs_max": float(np.abs(np.asarray(exits, np.float64)
                                     - np.asarray(want[2], np.float64)).max()),
    }


def within(numbers: dict, limits: dict) -> bool:
    """Every limit of the configuration's `parity` holds."""
    return (
        numbers["rel_l2_median"] <= limits["tolerance_rel_l2_median"]
        and numbers["rel_l2_max"] <= limits["tolerance_rel_l2_max"]
        and all(worst <= limit for worst, limit in zip(
            numbers["hidden_rel_l2_max"], limits["tolerance_hidden_rel_l2_max"], strict=True))
        and numbers["exit_abs_max"] <= limits["tolerance_exit_abs_max"]
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

    import ouro_counts
    from comfyui_distributed_tpu.graph.nodes_text import generate_tokens
    from comfyui_distributed_tpu.models import ouro as system
    from comfyui_distributed_tpu.models import pipeline as pl
    from comfyui_distributed_tpu.parallel.sharding import params_byte_size
    from comfyui_distributed_tpu.workers.startup import configure_compile_cache

    config = ouro_counts.config()
    spec = importlib.util.spec_from_file_location(
        "ouro_reference", os.path.join(ROOT, config["reference"]))
    reference = importlib.util.module_from_spec(spec)
    sys.modules["ouro_reference"] = reference  # dataclasses looks the module up
    spec.loader.exec_module(reference)
    with open(os.path.join(HERE, "workflows", "rewrite-txt2img-ouro-2.6b.json"),
              encoding="utf-8") as fh:
        (node,) = [n for n in json.load(fh).values() if n["class_type"] == "TextGenerate"]

    configure_compile_cache()
    device = jax.devices()[0]
    print(f"device: {device.platform} {device.device_kind} x{jax.device_count()}", flush=True)
    started = time.monotonic()
    bundle = pl.load_pipeline("tiny-ouro" if args.rehearsal else config["registry_name"])
    jax.block_until_ready(bundle.params)
    cfg, params = bundle.lm.cfg, bundle.params["lm"]
    print(f"bundle: {params_byte_size(params) / 1e9:.3f} GB in {time.monotonic() - started:.1f} s",
          flush=True)
    sizes, layers = reference.Sizes.of(cfg), system.unstacked(params)
    ids = bundle.tokenizer.encode(node["inputs"]["text"])
    steps = 8 if args.rehearsal else int(node["inputs"]["max_new_tokens"])
    temperature = float(node["inputs"]["temperature"])
    positions = np.arange(len(ids) - 1, len(ids) + steps)
    limits = config["parity"]
    report, ok = {"device": device.device_kind, "seeds": []}, True
    jax.block_until_ready(generate_tokens(bundle, ids, 0, steps, temperature)[1])  # builds both
    for seed in range(1, args.seeds + 1):
        began = time.monotonic()
        prefill, decode = generate_tokens(bundle, ids, seed, steps, temperature)
        jax.block_until_ready(prefill.logits)
        prefill_s = time.monotonic() - began
        jax.block_until_ready(decode.ids)
        both_s = time.monotonic() - began
        served_ids = np.asarray(decode.ids)
        del prefill, decode  # and the cache with them
        # the same two functions once more, keeping every step's logits,
        # h_t and p(t), which a served request does not pay for; equal ids
        # tie the served programs to what is compared below
        prefill, decode = generate_tokens(bundle, ids, seed, steps, temperature, collect=True)
        full = np.concatenate([np.asarray(ids), np.asarray(decode.ids)])
        mine = (
            jnp.concatenate([prefill.logits[None], decode.logits]),
            jnp.concatenate([prefill.hidden[None], decode.hidden]).transpose(1, 0, 2),
            jnp.concatenate([prefill.exits[None], decode.exits]).T,
        )
        del prefill, decode
        want = reference.forward(sizes, layers, full, positions=positions)
        entry = {
            "seed": seed, "prefill_s": prefill_s, "prefill_and_decode_s": both_s,
            "decode_step_s": (both_s - prefill_s) / steps,
            "served_ids_equal": bool(np.array_equal(served_ids, full[len(ids):])),
            "system": errors(*mine, want),
            "logit_abs_max": float(np.abs(np.asarray(want[0])).max()),
            "exit_mean": [float(v) for v in np.asarray(want[2]).mean(axis=1)],
        }
        if device.device_kind in ouro_counts.PEAKS:
            # this script's clock (dispatch and read-back in it), not a
            # device trace: how far the reckoning is from the run
            peak = ouro_counts.peaks(device.device_kind)
            entry["prefill_least_s"] = max(
                ouro_counts.prefill_flops(config, len(ids)) / peak["flops_per_s"],
                ouro_counts.prefill_bytes(config, len(ids)) / peak["bytes_per_s"])
            entry["decode_step_least_s"] = (
                ouro_counts.decode_step_bytes(config, len(ids) + steps) / peak["bytes_per_s"])
        low = reference.forward(
            sizes, layers, full, positions=positions, round_to=jnp.float8_e4m3fn)
        entry["float8_reference"] = errors(*low, want)
        del low
        shared = reference.forward(sizes, layers, full, positions=positions, shared_cache=True)
        entry["shared_cache_reference"] = errors(*shared, want)
        del shared, want
        entry["system_within_limits"] = entry["served_ids_equal"] and within(
            entry["system"], limits)
        entry["float8_outside_limits"] = not within(entry["float8_reference"], limits)
        entry["shared_cache_outside_limits"] = not within(
            entry["shared_cache_reference"], limits)
        ok = ok and entry["system_within_limits"] and entry["float8_outside_limits"] \
            and entry["shared_cache_outside_limits"]
        report["seeds"].append(entry)
        print(json.dumps(entry), flush=True)
    peaks = (device.memory_stats() or {}).get("peak_bytes_in_use")
    report["peak_bytes_in_use"] = peaks
    report["ok"] = ok
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "ouro_parity.json"), "w", encoding="utf-8") as fh:
        json.dump(report, fh, indent=1)
    print(json.dumps({"ok": ok, "peak_bytes_in_use": peaks, "limits": {
        k: v for k, v in limits.items() if k.startswith("tolerance")}}), flush=True)
    return 0 if ok or args.rehearsal else 1


if __name__ == "__main__":
    sys.exit(main())
