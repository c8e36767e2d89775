#!/usr/bin/env python3
"""dots3-note-prev on the chip against its float32 reference, outside any
timed window: at the published widths and the cell's sizes (the bundle
`load_pipeline` builds for the configuration's `registry_name`; the
committed workflow's 32,768-token prompt and 256 new tokens), the served
path's own two programs (`graph/nodes_text.generate_tokens`: the prefill
in its four parts, the decode one token a step) against the reference's
one forward pass over the 33,024 ids the run emitted (the whole sequence
at once, a `[T, T]` mask a layer: the band, or the selection by a full
stable sort; every key and value expanded).

    python3 benchmark/dots3_parity.py [--seeds 2]

The system runs first, every seed, and what it produced is kept on the
host; then the weights leave the device and the reference reads them from
the host, a weight at a time, so that its float32 working set has the
chip to itself.

Prints, per seed: the relative L2 of the logits at the last prompt
position and at every decoded position: median and largest, and the
largest among the positions whose own token chose the reference's experts
in every layer; the share of (token, layer) pairs whose set of chosen
experts differs from the reference's; of the two full layers' selections,
over `SAMPLED` prompt positions past `index_topk` and every decoded
position: the share that differ from the reference's at all, and the mean
share of a selection's positions that are not the reference's; of the
three sliding layers, the relative L2 of each ring's rows after the decode
against the reference's latents at the positions the ring should hold;
and how the served programs are tied to the collecting ones whose logits
are compared (`glm_dsa_parity.tied`). With `--precision-reading` also,
for seed 1, the reference on bfloat16 operands in the system's place (no
control and no limit: what the stated precision alone moves). Then the same numbers for eight
controls that have to fail the limits (`parity` in
configs/dots3-note-prev.json) on every seed, each the reference with one
thing wrong, in the system's place against the reference proper: float8
e4m3 operands; sliding layers without a window; a window of 257; the gate
left out; the rescale left out; the index without its ReLU; `index_topk`
1,024; a part's sliding queries blind to the tail before it. Also the
seconds the prefill and a decode step took on this script's own clock
beside what `dots3_counts` says the chip's peaks allow, and the
milliseconds of the sliding layers' band call on both routes
(`ops/attention.causal_attention`, the kernel and XLA's blocks). Exit 1
if a limit does not hold. Writes chiprun_out/dots3_parity.json. One
process: it holds the chip itself.

`--rehearsal` checks this script on the CPU with the tiny preset; its
numbers mean nothing.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

# Prompt positions whose selections are compared, drawn past `index_topk`.
SAMPLED = 192


def errors(mine: dict, want: dict) -> dict:
    """What a collecting run verified (`positions`, `logits`, `chosen`,
    the selections' `masks`, the `rings`' rows) against what the
    reference gave at the same places."""
    import numpy as np

    from deepseek_parity import flipped  # [layers, tokens]: another set than the reference chose
    from glm_dsa_parity import rel_l2, selection_errors

    rel = rel_l2(mine["logits"], want["logits"])
    flips = flipped(mine["chosen"], want["chosen"])
    same = ~np.any(flips, axis=0)
    differ, wrong = zip(*(selection_errors(a, b) for a, b in zip(mine["masks"], want["masks"])))
    rings = [float(np.linalg.norm(np.asarray(a, np.float64) - b) / np.linalg.norm(b))
             for a, b in zip(mine["rings"], (np.asarray(r, np.float64) for r in want["rings"]))]
    return {
        "rel_l2_median": float(np.median(rel)), "rel_l2_max": float(rel.max()),
        "rel_l2_prefill": float(rel[0]),
        "rel_l2_max_unflipped": float(rel[same].max()) if same.any() else None,
        "positions": int(len(rel)), "positions_unflipped": int(same.sum()),
        "expert_set_mismatch": float(np.mean(flips)),
        "selections_differ": float(np.mean(differ)),
        "selection_mismatch": float(np.mean(wrong)),
        "selection_mismatch_by_layer": [float(w) for w in wrong],
        "ring_rel_l2": max(rings), "ring_rel_l2_by_layer": rings,
    }


def within(numbers: dict, limits: dict) -> bool:
    """Every limit of the configuration's `parity` holds. Where no
    position chose the reference's experts in every layer there is no
    largest among them to limit (under this model's seeded weights four
    positions in five flip an expert somewhere: see the configuration's
    `why_these_limits`), and the other limits decide."""
    worst = numbers["rel_l2_max_unflipped"]
    return (
        numbers["rel_l2_median"] <= limits["tolerance_rel_l2_median"]
        and numbers["expert_set_mismatch"] <= limits["tolerance_expert_set_mismatch"]
        and numbers["selection_mismatch"] <= limits["tolerance_selection_mismatch"]
        and numbers["ring_rel_l2"] <= limits["tolerance_ring_rel_l2"]
        and (worst is None or worst <= limits["tolerance_rel_l2_max_unflipped"])
    )


def band_routes_ms(kind, rows: int, before: int, window: int, interpret: bool) -> dict:
    """ms a call of the sliding layers' band on both routes: `rows`
    queries over `before` + `rows` keys, bfloat16, ten calls dispatched
    back to back."""
    import jax
    import jax.numpy as jnp

    from comfyui_distributed_tpu.ops import attention

    keys = jax.random.split(jax.random.key(61), 3)
    q, k, v = (
        jax.random.normal(key, (1, n, kind.heads, d)).astype(jnp.bfloat16)
        for key, n, d in zip(keys, (rows, rows + before, rows + before),
                             (kind.width, kind.width, kind.value)))
    out = {}
    for name, flash in (("flash", True), ("xla", False)):
        fn = jax.jit(functools.partial(
            attention.causal_attention, scale=kind.width ** -0.5, window=window,
            force_flash=flash, interpret=flash and interpret))
        with attention.route_log() as routes:
            jax.block_until_ready(fn(q, k, v))
        began = time.perf_counter()
        for _ in range(10):
            last = fn(q, k, v)
        jax.block_until_ready(last)
        out[name] = {"entry": routes[0], "ms": 1e2 * (time.perf_counter() - began)}
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seeds", type=int, default=2)
    parser.add_argument("--control-seeds", type=int, default=2,
                        help="seeds whose run the eight controls are computed for")
    parser.add_argument("--precision-reading", action="store_true",
                        help="also report, for seed 1, the reference on bfloat16 operands against "
                             "the reference proper: what the stated precision alone moves")
    parser.add_argument("--rehearsal", action="store_true")
    args = parser.parse_args(argv)
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    import dots3_counts
    from glm_dsa_parity import as_masks, tied

    from comfyui_distributed_tpu.graph.nodes_text import generate_tokens
    from comfyui_distributed_tpu.models import dots3
    from comfyui_distributed_tpu.models import pipeline as pl
    from comfyui_distributed_tpu.parallel.sharding import params_byte_size
    from comfyui_distributed_tpu.workers.startup import configure_compile_cache

    config = dots3_counts.config()
    spec = importlib.util.spec_from_file_location(
        "dots3_reference", os.path.join(ROOT, config["reference"]))
    reference = importlib.util.module_from_spec(spec)
    sys.modules["dots3_reference"] = reference  # dataclasses looks the module up
    spec.loader.exec_module(reference)
    with open(os.path.join(HERE, "workflows", "longdoc-txt2img-dots3-note.json"),
              encoding="utf-8") as fh:
        (node,) = [n for n in json.load(fh).values() if n["class_type"] == "TextGenerate"]

    configure_compile_cache()
    device = jax.devices()[0]
    print(f"device: {device.platform} {device.device_kind} x{jax.device_count()}", flush=True)
    started = time.monotonic()
    bundle = pl.load_pipeline("tiny-dots3" if args.rehearsal else config["registry_name"])
    jax.block_until_ready(bundle.params)
    lm, params = bundle.lm, bundle.params["lm"]
    print(f"bundle: {params_byte_size(params) / 1e9:.3f} GB in {time.monotonic() - started:.1f} s",
          flush=True)
    cfg = lm.cfg
    sizes, held = reference.Sizes.of(cfg), list(cfg.held_experts)
    text = node["inputs"]["text"]
    ids = bundle.tokenizer.encode(text[:255] if args.rehearsal else text)
    steps = 24 if args.rehearsal else int(node["inputs"]["max_new_tokens"])
    total = len(ids) + steps
    temperature = float(node["inputs"]["temperature"])
    assert int(node["inputs"]["draft_tokens"]) == 0
    limits = config["parity"]
    # two heads' float32 scores of 1,024 queries over 33,024 positions: 0.27 GB
    blocks = {"head_chunk": 4, "row_block": 64} if args.rehearsal else {
        "head_chunk": 2, "row_block": 1024}
    sampled = np.sort(np.random.default_rng(61).choice(
        np.arange(cfg.index_topk, len(ids)), size=min(SAMPLED, len(ids) - cfg.index_topk),
        replace=False))
    # the positions a ring holds after the decode: the newest `ring_positions`
    ringed = np.arange(total - cfg.ring_positions, total)
    report, ok = {"device": device.device_kind, "seeds": []}, True

    def collecting(seed):
        """The two functions once more, keeping what a served request
        does not pay for: everything compared, on the host."""
        prefill = dots3.prefill(
            cfg, params, jnp.asarray(ids, jnp.int32), cache_len=total, collect=True)
        kept = dict(prefill.kept)
        kept["selections"] = [tuple(a[sampled] for a in layer) for layer in kept["selections"]]
        before = jax.device_get(prefill._replace(cache=None, kept=kept))
        decode = dots3.decode(
            cfg, params, prefill.cache, prefill.logits, jnp.int32(len(ids)),
            jax.random.key(seed), jnp.float32(temperature), steps=steps, collect=True)
        rings = [np.asarray(ring, np.float32)[ringed % cfg.ring_positions]
                 for ring in jax.device_get(decode.cache["ring"])]
        after = jax.device_get(decode._replace(cache=None))
        positions = len(ids) + np.arange(steps)
        mine = {
            "positions": np.concatenate([[len(ids) - 1], positions]),
            "logits": np.concatenate([before.logits[None], after.kept["logits"]]),
            "chosen": np.concatenate([
                before.kept["chosen"][:, len(ids) - 1:len(ids)],
                after.kept["chosen"].transpose(1, 0, 2)], axis=1),
            "queries": np.concatenate([sampled, positions]),
            "masks": [as_masks(tuple(np.concatenate([a, b]) for a, b in zip(early, late)), total)
                      for early, late in zip(before.kept["selections"], after.kept["selections"])],
            "rings": rings, "ids": after.ids, "step_logits": after.kept["logits"],
        }
        return np.concatenate([np.asarray(ids), after.ids]), mine

    def reference_at(weights, full, mine, sizes, round_to=None):
        """The reference over `full` at what `mine` verified."""
        logits, chosen, masks, rings = reference.forward(
            sizes, weights, full, held, round_to=round_to, positions=mine["positions"],
            queries=mine["queries"], keep_latents=ringed, **blocks)
        return {"logits": np.asarray(logits), "chosen": np.asarray(chosen)[:, mine["positions"]],
                "masks": [np.asarray(m) for m in masks], "rings": [np.asarray(r) for r in rings]}

    jax.block_until_ready(generate_tokens(bundle, ids, 0, steps, temperature)[1].ids)  # builds

    runs = []
    for seed in range(1, args.seeds + 1):
        began = time.monotonic()
        prefill, decode = generate_tokens(bundle, ids, seed, steps, temperature)
        jax.block_until_ready(prefill.logits)
        prefill_s = time.monotonic() - began
        jax.block_until_ready(decode.ids)
        both_s = time.monotonic() - began
        served = {"ids": np.asarray(decode.ids), "logits": np.asarray(prefill.logits)}
        said = lm.report(len(ids), steps, total, *jax.device_get(lm.read_back(prefill, decode)))
        del prefill, decode
        full, mine = collecting(seed)
        work = dots3_counts.prefill_flops(config, len(ids), said["prefill_routed_pairs_held"])
        step = dots3_counts.decode_step_bytes(
            config, said["decode_experts_read"] / steps, len(ids) + steps // 2)
        peak = dots3_counts.peaks(config["as_run"]["chip"])
        runs.append((seed, full, mine, {
            "prefill_s": prefill_s, "decode_s": both_s - prefill_s,
            "decode_step_s": (both_s - prefill_s) / steps,
            "prefill_least_s": work / peak["flops_per_s"],
            "decode_step_least_s": step / peak["bytes_per_s"],
            **{key: said[key] for key in (
                "decode_experts_read", "keys_visible", "keys_selected", "prefill_band_keys_seen",
                "prefill_band_keys_computed", "prefill_band_route",
                "prefill_routed_pairs_held")},
            **tied(served, mine, jax.random.key(seed), temperature, limits),
        }))
        print(json.dumps({"seed": seed, **runs[-1][3]}), flush=True)
    report["peak_bytes_in_use"] = (device.memory_stats() or {}).get("peak_bytes_in_use")

    # the weights to the host: the reference's float32 working set has the chip to itself
    weights = jax.device_get(params)
    bundle.params.clear()
    for leaf in jax.tree_util.tree_leaves(params):
        leaf.delete()
    del params
    rows = 64 if args.rehearsal else cfg.prefill_part
    report["band_routes"] = band_routes_ms(
        cfg.sliding, rows, min(rows, cfg.tail_positions), cfg.sliding_window_size, args.rehearsal)
    print(json.dumps({"band_routes": report["band_routes"]}), flush=True)
    wrong = {
        "float8_reference": (sizes, jnp.float8_e4m3fn),
        "sliding_layers_without_a_window": (dataclasses.replace(sizes, window=None), None),
        "window_halved": (
            dataclasses.replace(sizes, window=cfg.sliding_window_size // 2 + 1), None),
        "gate_left_out": (dataclasses.replace(sizes, gate=False), None),
        "rescale_left_out": (
            dataclasses.replace(sizes, rescale_q=False, rescale_kv=False), None),
        "index_without_relu": (dataclasses.replace(sizes, relu=False), None),
        "index_topk_halved": (
            dataclasses.replace(sizes, index_topk=cfg.index_topk // 2), None),
        "sliding_queries_blind_to_the_tail": (
            dataclasses.replace(sizes, blind_part=cfg.prefill_part), None),
    }
    entries, wanted = {}, {}
    for seed, full, mine, numbers in runs:  # the reference proper first, every seed
        began = time.monotonic()
        want = wanted[seed] = reference_at(weights, full, mine, sizes)
        numbers.update(errors(mine, want))
        numbers["reference_s"] = time.monotonic() - began
        numbers["logit_abs_max"] = float(np.abs(want["logits"]).max())
        passes = numbers["tied"] and within(numbers, limits)
        numbers["within_limits"] = passes
        ok = ok and passes
        entries[seed] = {"seed": seed, "served": numbers}
        print(json.dumps({"seed": seed, **numbers}), flush=True)
    if args.precision_reading:
        # no control and no limit: the reference in the configuration's own precision, in the
        # system's place; the system should read near it
        seed, full, mine, _ = runs[0]
        low = reference_at(weights, full, mine, sizes, jnp.bfloat16)
        entries[seed]["bfloat16_reference"] = errors({**mine, **low}, wanted[seed])
        print(json.dumps({"seed": seed, "reading": "bfloat16_reference",
                          **entries[seed]["bfloat16_reference"]}), flush=True)
    for seed, full, mine, _ in runs[:args.control_seeds]:
        for name, (control_sizes, round_to) in wrong.items():
            # the control in the system's place, against the reference proper
            low = reference_at(weights, full, mine, control_sizes, round_to)
            entry = entries[seed][name] = errors({**mine, **low}, wanted[seed])
            entry["outside_limits"] = not within(entry, limits)
            ok = ok and entry["outside_limits"]
            print(json.dumps({"seed": seed, "control": name, **entry}), flush=True)
    report["seeds"] = list(entries.values())
    report["ok"] = ok
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "dots3_parity.json"), "w", encoding="utf-8") as fh:
        json.dump(report, fh, indent=1)
    print(json.dumps({"ok": ok, "peak_bytes_in_use": report["peak_bytes_in_use"], "limits": {
        k: v for k, v in limits.items() if k.startswith("tolerance")}}), flush=True)
    return 0 if ok or args.rehearsal else 1


if __name__ == "__main__":
    sys.exit(main())
