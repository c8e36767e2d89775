#!/usr/bin/env python3
"""Ling-3.0-flash on the chip against its float32 reference, outside any
timed window: at the published widths and the cell's sizes (the bundle
`load_pipeline` builds for the configuration's `registry_name`; the
committed workflow's 8,192-token prompt and 1,024 new tokens), the served
path's own two programs (`graph/nodes_text.generate_tokens`: the prefill
with its chunked delta rule and expanded latent attention, and the decode,
by self-speculation with `draft_tokens` 1 and again one token a step with
0) against the reference's forward passes over the 9,216 ids the run
emitted (the recurrence token by token, full masks, no cache, no slots; the
MTP module's pass over the whole sequence).

    python3 benchmark/ling_flash_parity.py [--seeds 2]

Prints, per seed and `draft_tokens`, what `k_exaone_parity.py` prints (its
`gathered`, `errors` and `within` are this script's too): the relative L2
of the main model's logits at the last prompt position and at every
position a step verified, median and largest, and the largest among the
positions whose own token chose the reference's experts in every layer;
the relative L2 of the draft logits (median); the share of (token, layer)
pairs whose set of chosen experts differs from the reference's; and the
same numbers for four controls that have to fail the limits (`parity` in
configs/ling-3.0-flash.json): the reference computed a precision below
the configuration's (float8 e4m3 operands); the reference with the decay
unbounded (g = -exp(A_log) softplus(.)); the reference's router without
groups; and **the system keeping the state after the draft whatever the
draft's fate** (`ling_flash.standing` replaced in a program of its own:
its own run, its own ids, its own reference). Also the steps a decode
took, the drafts it kept, and the seconds the prefill and the decode took
on this script's own clock, beside what `ling_flash_counts` says the
chip's peaks allow. Exit 1 if a limit does not hold. Writes
chiprun_out/ling_flash_parity.json. One process: it holds the chip itself.

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

    import ling_flash_counts
    from k_exaone_parity import errors, gathered, within
    from comfyui_distributed_tpu.graph.nodes_text import generate_tokens
    from comfyui_distributed_tpu.models import ling_flash
    from comfyui_distributed_tpu.models import pipeline as pl
    from comfyui_distributed_tpu.parallel.sharding import params_byte_size
    from comfyui_distributed_tpu.workers.startup import configure_compile_cache

    config = ling_flash_counts.config()
    spec = importlib.util.spec_from_file_location(
        "ling_flash_reference", os.path.join(ROOT, config["reference"]))
    reference = importlib.util.module_from_spec(spec)
    sys.modules["ling_flash_reference"] = reference  # dataclasses looks the module up
    spec.loader.exec_module(reference)
    with open(os.path.join(HERE, "workflows", "rewrite-txt2img-ling-flash.json"),
              encoding="utf-8") as fh:
        (node,) = [n for n in json.load(fh).values() if n["class_type"] == "TextGenerate"]

    configure_compile_cache()
    device = jax.devices()[0]
    print(f"device: {device.platform} {device.device_kind} x{jax.device_count()}", flush=True)
    started = time.monotonic()
    bundle = pl.load_pipeline("tiny-ling-flash" if args.rehearsal else config["registry_name"])
    jax.block_until_ready(bundle.params)
    lm, params = bundle.lm, bundle.params["lm"]
    print(f"bundle: {params_byte_size(params) / 1e9:.3f} GB in {time.monotonic() - started:.1f} s",
          flush=True)
    cfg = lm.cfg
    sizes, held = reference.Sizes.of(cfg), list(cfg.held_experts)
    ids = bundle.tokenizer.encode(node["inputs"]["text"])
    steps = 24 if args.rehearsal else int(node["inputs"]["max_new_tokens"])
    temperature = float(node["inputs"]["temperature"])
    assert int(node["inputs"]["draft_tokens"]) == 1
    limits = config["parity"]
    head_chunk = 8 if args.rehearsal else 2  # two heads' float32 scores over 9,216 tokens: 0.68 GB
    report, ok = {"device": device.device_kind, "seeds": []}, True

    def always_keeping(cfg, *operands, **options):
        """`ling_flash.decode` traced anew with the slot that holds the
        state after the draft standing whatever the draft's fate."""
        proper, ling_flash.standing = ling_flash.standing, lambda slot, kept: slot
        try:
            return ling_flash.decode.__wrapped__(cfg, *operands, **options)
        finally:
            ling_flash.standing = proper

    faulty = jax.jit(
        always_keeping, static_argnums=0, static_argnames=("steps", "collect", "draft_tokens"),
        donate_argnums=2)

    def collecting(decode, seed, draft_tokens):
        """The two functions once more, keeping what a served request
        does not pay for; `decode` may be the control's."""
        prefill = ling_flash.prefill(
            cfg, params, jnp.asarray(ids, jnp.int32), cache_len=len(ids) + steps, collect=True)
        kept = jax.tree_util.tree_map(np.asarray, prefill._replace(cache=None))
        decode = decode(
            cfg, params, prefill.cache, prefill.logits, jnp.int32(len(ids)),
            jax.random.key(seed), jnp.float32(temperature), steps=steps, collect=True,
            draft_tokens=draft_tokens)
        full = np.concatenate([np.asarray(ids), np.asarray(decode.ids)])
        return full, gathered(len(ids), kept, decode, draft_tokens)

    def run(full, mine, sizes, round_to=None):
        """The reference over `full` at what `mine` verified."""
        logits, h, chosen = reference.forward(
            sizes, params, full, held, round_to=round_to, head_chunk=head_chunk,
            positions=mine["positions"])
        drafts = None
        if "drawn" in mine:
            drafts, _ = reference.mtp_forward(
                sizes, params, h, full, held, round_to=round_to, head_chunk=head_chunk,
                positions=mine["drawn"])
            drafts = np.asarray(drafts)
        return np.asarray(logits), np.asarray(chosen), drafts

    for draft_tokens in (1, 0):  # builds the programs
        jax.block_until_ready(
            generate_tokens(bundle, ids, 0, steps, temperature, draft_tokens=draft_tokens)[1].ids)

    for seed in range(1, args.seeds + 1):
        entry = {"seed": seed}
        for draft_tokens in (1, 0):
            began = time.monotonic()
            prefill, decode = generate_tokens(
                bundle, ids, seed, steps, temperature, draft_tokens=draft_tokens)
            jax.block_until_ready(prefill.logits)
            prefill_s = time.monotonic() - began
            jax.block_until_ready(decode.ids)
            both_s = time.monotonic() - began
            served_ids, counts = np.asarray(decode.ids), np.asarray(decode.counts).tolist()
            del prefill, decode
            # equal ids tie the served programs to what is compared below
            full, mine = collecting(ling_flash.decode, seed, draft_tokens)
            want = run(full, mine, sizes)
            numbers = errors(mine, want[0], want[1], want[2])
            numbers.update({
                "prefill_s": prefill_s, "decode_s": both_s - prefill_s,
                "decode_steps": counts[0], "mtp_drafted": counts[1], "mtp_accepted": counts[2],
                "decode_experts_read": counts[3],
                "decode_step_s": (both_s - prefill_s) / counts[0],
                "served_ids_equal": bool(np.array_equal(served_ids, full[len(ids):])),
                "logit_abs_max": float(np.abs(want[0]).max()),
            })
            if device.device_kind in ling_flash_counts.PEAKS:
                # this script's clock (dispatch and read-back in it), not a
                # device trace: how far the reckoning is from the run
                peak = ling_flash_counts.peaks(device.device_kind)
                numbers["decode_step_least_s"] = ling_flash_counts.decode_step_bytes(
                    config, counts[3] / counts[0], len(ids) + steps // 2,
                    drafting=bool(draft_tokens)) / peak["bytes_per_s"]
            passes = numbers["served_ids_equal"] and within(numbers, limits)
            numbers["within_limits"] = passes
            ok = ok and passes
            entry[f"draft_tokens_{draft_tokens}"] = numbers
            if not draft_tokens:
                continue
            controls = {
                "float8_reference": (sizes, jnp.float8_e4m3fn),
                "unbounded_decay_reference": (
                    dataclasses.replace(sizes, bounded_decay=False), None),
                "ungrouped_router_reference": (dataclasses.replace(sizes, grouped=False), None),
            }
            for name, (control_sizes, round_to) in controls.items():
                low = run(full, mine, control_sizes, round_to)
                # the control in the system's place, against the reference proper
                stand_in = dict(mine, logits=low[0], chosen=low[1][:, mine["positions"]],
                                draft_logits=low[2])
                entry[name] = errors(stand_in, want[0], want[1], want[2])
                entry[name]["outside_limits"] = not within(entry[name], limits)
                ok = ok and entry[name]["outside_limits"]
            # every draft left in the recurrent state: its own run, its own ids, its own reference
            full, mine = collecting(faulty, seed, 1)
            want = run(full, mine, sizes)
            entry["every_draft_kept_in_the_state"] = errors(mine, want[0], want[1], want[2])
            entry["every_draft_kept_in_the_state"]["drafts_dropped"] = int(
                mine["steps"] - mine["accepted"])
            entry["every_draft_kept_in_the_state"]["outside_limits"] = not within(
                entry["every_draft_kept_in_the_state"], limits)
            ok = ok and entry["every_draft_kept_in_the_state"]["outside_limits"]
        report["seeds"].append(entry)
        print(json.dumps(entry), flush=True)
    peaks = (device.memory_stats() or {}).get("peak_bytes_in_use")
    report["peak_bytes_in_use"] = peaks
    report["ok"] = ok
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "ling_flash_parity.json"), "w", encoding="utf-8") as fh:
        json.dump(report, fh, indent=1)
    print(json.dumps({"ok": ok, "peak_bytes_in_use": peaks, "limits": {
        k: v for k, v in limits.items() if k.startswith("tolerance")}}), flush=True)
    return 0 if ok or args.rehearsal else 1


if __name__ == "__main__":
    sys.exit(main())
