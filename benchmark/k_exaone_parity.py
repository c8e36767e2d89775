#!/usr/bin/env python3
"""K-EXAONE on the chip against its float32 reference, outside any timed
window: at the published widths and the cell's sizes (the bundle
`load_pipeline` builds for the configuration's `registry_name`; the
committed workflow's 8,192-token prompt and 384 new tokens), the served
path's own two programs (`graph/nodes_text.generate_tokens`: the prefill
with its banded window layers, and the decode, by self-speculation with
`draft_tokens` 1 and again one token a step with 0) against the
reference's forward passes over the 8,576 ids the run emitted (full
masks, no ring, no cache; the MTP module's pass over the whole sequence).

    python3 benchmark/k_exaone_parity.py [--seeds 2]

Prints, per seed and `draft_tokens`: the relative L2 of the main model's
logits at the last prompt position and at every position a step verified
(row 0 of every step, row 1 where the draft was kept; every decoded
position without drafting): median and largest, and the largest among
the positions whose own token chose the reference's experts in every
layer; the relative L2 of the draft logits at every position a draft was
drawn from (median); the share of (token, layer) pairs whose set of
chosen experts differs from the reference's; and the same numbers for
three controls that have to fail the limits (`parity` in
configs/k-exaone-236b-a23b.json): the reference computed a precision
below the configuration's (float8 e4m3 operands), the reference with its
window layers seeing the whole prefix (the window ignored), and the
system with a ring of exactly `sliding_window` entries under drafting
(the clobbered key). Also the steps a decode took, the drafts it kept,
and the seconds the prefill and the decode took on this script's own
clock, beside what `k_exaone_counts` says the chip's peaks allow. Exit 1
if a limit does not hold. Writes chiprun_out/k_exaone_parity.json. One
process: it holds the chip itself.

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


def gathered(prompt: int, prefill, decode, draft_tokens: int) -> dict:
    """What a collecting run verified, by position: `positions` (the last
    of the prompt, then every decoded position the main model ran on a
    confirmed token), the main model's `logits` there, the experts
    `chosen` [sparse layers, positions, k] by the tokens at those
    positions, and when drafting the positions drafts were `drawn` from
    with their `draft_logits`."""
    import numpy as np

    kept = {k: np.asarray(v) for k, v in decode.kept.items()}
    steps = int(np.asarray(decode.counts)[0])
    if not draft_tokens:
        positions = prompt + np.arange(steps)
        rows, chosen = kept["logits"], kept["chosen"].transpose(1, 0, 2)
        extra = {}
    else:
        at, accepted = kept["position"][:steps], kept["accepted"][:steps]
        first, second = np.arange(steps), np.flatnonzero(accepted)
        positions = np.concatenate([at[first], at[second] + 1])
        rows = np.concatenate([kept["logits"][first, 0], kept["logits"][second, 1]])
        chosen = np.concatenate(
            [kept["chosen"][first, :, 0], kept["chosen"][second, :, 1]]).transpose(1, 0, 2)
        order = np.argsort(positions)
        positions, rows, chosen = positions[order], rows[order], chosen[:, order]
        extra = {"drawn": at - 1, "draft_logits": kept["draft_logits"][:steps],
                 "accepted": int(accepted.sum())}
    last = np.asarray(prefill.chosen)[:, prompt - 1:prompt]
    return {
        "positions": np.concatenate([[prompt - 1], positions]),
        "logits": np.concatenate([np.asarray(prefill.logits)[None], rows]),
        "chosen": np.concatenate([last, chosen], axis=1), "steps": steps, **extra,
    }


def rel_l2(got, want):
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


def errors(mine: dict, logits, chosen_ref, draft_logits=None) -> dict:
    """`mine` of `gathered` against the reference's `logits` at its
    positions, the reference's chosen experts [sparse layers, T, k] and,
    when drafting, its draft logits at the positions drawn from."""
    import numpy as np

    from deepseek_parity import flipped  # [layers, tokens]: another set than the reference chose

    rel = rel_l2(mine["logits"], logits)
    flips = flipped(mine["chosen"], chosen_ref[:, mine["positions"]])
    same = ~np.any(flips, axis=0)
    out = {
        "rel_l2_median": float(np.median(rel)), "rel_l2_max": float(rel.max()),
        "rel_l2_prefill": float(rel[0]),
        "rel_l2_max_unflipped": float(rel[same].max()) if same.any() else None,
        "positions": int(len(rel)), "positions_unflipped": int(same.sum()),
        "expert_set_mismatch": float(np.mean(flips)),
    }
    if draft_logits is not None:
        out["draft_rel_l2_median"] = float(np.median(rel_l2(mine["draft_logits"], draft_logits)))
    return out


def within(numbers: dict, limits: dict) -> bool:
    """Every limit of the configuration's `parity` holds."""
    worst, draft = numbers["rel_l2_max_unflipped"], numbers.get("draft_rel_l2_median")
    return (
        numbers["rel_l2_median"] <= limits["tolerance_rel_l2_median"]
        and numbers["expert_set_mismatch"] <= limits["tolerance_expert_set_mismatch"]
        and worst is not None and worst <= limits["tolerance_rel_l2_max_unflipped"]
        and (draft is None or draft <= limits["tolerance_draft_rel_l2_median"])
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

    import k_exaone_counts
    from comfyui_distributed_tpu.graph.nodes_text import generate_tokens
    from comfyui_distributed_tpu.models import k_exaone
    from comfyui_distributed_tpu.models import pipeline as pl
    from comfyui_distributed_tpu.parallel.sharding import params_byte_size
    from comfyui_distributed_tpu.workers.startup import configure_compile_cache

    config = k_exaone_counts.config()
    spec = importlib.util.spec_from_file_location(
        "k_exaone_reference", os.path.join(ROOT, config["reference"]))
    reference = importlib.util.module_from_spec(spec)
    sys.modules["k_exaone_reference"] = reference  # dataclasses looks the module up
    spec.loader.exec_module(reference)
    with open(os.path.join(HERE, "workflows", "rewrite-txt2img-k-exaone.json"),
              encoding="utf-8") as fh:
        (node,) = [n for n in json.load(fh).values() if n["class_type"] == "TextGenerate"]

    configure_compile_cache()
    device = jax.devices()[0]
    print(f"device: {device.platform} {device.device_kind} x{jax.device_count()}", flush=True)
    started = time.monotonic()
    bundle = pl.load_pipeline("tiny-k-exaone" if args.rehearsal else config["registry_name"])
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
    head_chunk = 8 if args.rehearsal else 2  # two heads' float32 scores over 8,576 tokens: 0.59 GB
    report, ok = {"device": device.device_kind, "seeds": []}, True

    def collecting(cfg, seed, draft_tokens):
        """The two functions once more, keeping what a served request
        does not pay for; `cfg` may be the control's."""
        prefill = k_exaone.prefill(
            cfg, params, jnp.asarray(ids, jnp.int32), cache_len=len(ids) + steps, collect=True)
        kept = jax.tree_util.tree_map(np.asarray, prefill._replace(cache=None))
        decode = k_exaone.decode(
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
            full, mine = collecting(cfg, seed, draft_tokens)
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
            if device.device_kind in k_exaone_counts.PEAKS:
                # this script's clock (dispatch and read-back in it), not a
                # device trace: how far the reckoning is from the run
                peak = k_exaone_counts.peaks(device.device_kind)
                numbers["decode_step_least_s"] = k_exaone_counts.decode_step_bytes(
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
                "window_ignored_reference": (dataclasses.replace(sizes, windowed=False), None),
            }
            for name, (control_sizes, round_to) in controls.items():
                low = run(full, mine, control_sizes, round_to)
                # the control in the system's place, against the reference proper
                stand_in = dict(mine, logits=low[0], chosen=low[1][:, mine["positions"]],
                                draft_logits=low[2])
                entry[name] = errors(stand_in, want[0], want[1], want[2])
                entry[name]["outside_limits"] = not within(entry[name], limits)
                ok = ok and entry[name]["outside_limits"]
            # a ring one entry short: its own run, its own ids, its own reference
            short = dataclasses.replace(cfg, ring=cfg.sliding_window)
            full, mine = collecting(short, seed, 1)
            want = run(full, mine, sizes)
            entry["ring_of_the_window_alone"] = errors(mine, want[0], want[1], want[2])
            entry["ring_of_the_window_alone"]["outside_limits"] = not within(
                entry["ring_of_the_window_alone"], limits)
            ok = ok and entry["ring_of_the_window_alone"]["outside_limits"]
        report["seeds"].append(entry)
        print(json.dumps(entry), flush=True)
    peaks = (device.memory_stats() or {}).get("peak_bytes_in_use")
    report["peak_bytes_in_use"] = peaks
    report["ok"] = ok
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "k_exaone_parity.json"), "w", encoding="utf-8") as fh:
        json.dump(report, fh, indent=1)
    print(json.dumps({"ok": ok, "peak_bytes_in_use": peaks, "limits": {
        k: v for k, v in limits.items() if k.startswith("tolerance")}}), flush=True)
    return 0 if ok or args.rehearsal else 1


if __name__ == "__main__":
    sys.exit(main())
