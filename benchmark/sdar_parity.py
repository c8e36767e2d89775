#!/usr/bin/env python3
"""SDAR on the chip against its float32 reference, outside any timed
window: at the published widths and the cell's sizes (the bundle
`load_pipeline` builds for the configuration's `registry_name`; the
committed workflow's 2,048-token prompt and 512 new tokens), the served
path's own two programs (`graph/nodes_text.generate_tokens`: the prefill
under the block mask, and the decode by masked diffusion over blocks of
4) against the reference's forward passes, teacher-forced on the ids and
masks the run itself had.

    python3 benchmark/sdar_parity.py [--seeds 2]

The collecting decode keeps every pass's block as the pass saw it, what
was masked, drawn and kept, and the float32 logits of the denoising
passes of every eighth block. Under the block mask a position depends on
no later block, so the reference's `forward` over the run's final ids
with one block replaced by what a pass saw gives that pass's logits at
the block's rows; the sequence is always the whole 2,560 positions, one
shape. Prints, per seed: the relative L2 of the logits at the last
prefilled position and at every masked position of the kept passes
(median, largest, and largest among the positions that chose the
reference's experts in every layer); of layer 0's and the last layer's
keys and values over all positions after the decode against one
reference `forward` over the final ids; the share of (position, layer)
pairs whose set of chosen experts differs from the reference's; and the
share of kept passes whose transferred set is not the reference's own
choice given the system's draws (the rule on the reference's
confidences). Controls that have to fail the limits (`parity` in
configs/sdar-30b-a3b-chat.json), each over every fourth kept block: the
reference on float8 e4m3 operands; under a plain causal mask; rotating
before the norm (the heads' norm scales are drawn by this script: at the
seeded scale of one the two orders are one computation); with the chosen
weights not renormalised; and the system with its closing passes left out
(the last denoising pass's keys stand),
by its keys and values. Also the passes a decode took, the experts a pass
read, and the seconds of the prefill, of a denoising pass and of a
closing pass on this script's own clock beside what `sdar_counts` says
the chip's peaks allow. Exit 1 if a limit does not hold. Writes
chiprun_out/sdar_parity.json. One process: it holds the chip itself.

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
from functools import partial
from unittest import mock

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)


def rel_l2(got, want):
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


def kept_passes(kept: dict, stride: int, every: int = 1) -> list:
    """(block, pass, row of the kept logits) of the denoising passes a
    collecting decode kept logits of, every `every`-th such block."""
    out = []
    for b in range(0, kept["position"].shape[0], stride * every):
        out += [(b, s, b // stride) for s in range(kept["position"].shape[1])
                if kept["position"][b, s] >= 0]
    return out


def within(numbers: dict, limits: dict) -> bool:
    """Every limit of the configuration's `parity` that the numbers have a
    reading for holds."""
    pairs = (
        ("rel_l2_median", "tolerance_rel_l2_median"),
        ("rel_l2_max_unflipped", "tolerance_rel_l2_max_unflipped"),
        ("expert_set_mismatch", "tolerance_expert_set_mismatch"),
        ("transfer_mismatch", "tolerance_transfer_mismatch"),
        ("kv_rel_l2_first", "tolerance_kv_rel_l2_first"),
        ("kv_rel_l2_last", "tolerance_kv_rel_l2_last"),
    )
    return all(
        numbers[mine] is not None and numbers[mine] <= limits[limit]
        for mine, limit in pairs if mine in numbers)


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

    import sdar_counts
    from comfyui_distributed_tpu.graph.nodes_text import generate_tokens
    from comfyui_distributed_tpu.models import pipeline as pl
    from comfyui_distributed_tpu.models import sdar
    from comfyui_distributed_tpu.parallel.sharding import params_byte_size
    from comfyui_distributed_tpu.workers.startup import configure_compile_cache
    from deepseek_parity import flipped  # [layers, tokens]: another set than the reference chose

    config = sdar_counts.config()
    spec = importlib.util.spec_from_file_location(
        "sdar_reference", os.path.join(ROOT, config["reference"]))
    reference = importlib.util.module_from_spec(spec)
    sys.modules["sdar_reference"] = reference  # dataclasses looks the module up
    spec.loader.exec_module(reference)
    # a layer of the reference as one program a variant (`forward` looks `layer` up when it
    # is called): one by one its loop over 128 experts is some 6,000 dispatches a pass
    reference.layer = jax.jit(reference.layer, static_argnums=(0, 3, 4))
    with open(os.path.join(HERE, "workflows", "rewrite-txt2img-sdar-30b-a3b.json"),
              encoding="utf-8") as fh:
        (node,) = [n for n in json.load(fh).values() if n["class_type"] == "TextGenerate"]

    configure_compile_cache()
    device = jax.devices()[0]
    print(f"device: {device.platform} {device.device_kind} x{jax.device_count()}", flush=True)
    started = time.monotonic()
    bundle = pl.load_pipeline("tiny-sdar" if args.rehearsal else config["registry_name"])
    jax.block_until_ready(bundle.params)
    lm, params = bundle.lm, bundle.params["lm"]
    print(f"bundle: {params_byte_size(params) / 1e9:.3f} GB in {time.monotonic() - started:.1f} s",
          flush=True)
    cfg = lm.cfg
    sizes = reference.Sizes.of(cfg)
    # the seeded initialisation has every norm's scale at one, and under a scale of one a
    # head's norm commutes with its rotation: the control that rotates first could not
    # fail. The heads' two scales a layer are drawn here, for the system and the reference
    # alike (the bundle's tree is the one both read), so that the order shows
    for index, layer in enumerate(params["layers"]):
        for offset, name in enumerate(("q_norm", "k_norm")):
            key = jax.random.fold_in(jax.random.key(58), 2 * index + offset)
            layer["attn"][name] = jax.random.uniform(
                key, (cfg.head_dim,), minval=0.5, maxval=1.5).astype(layer["attn"][name].dtype)
    block, last_layer = cfg.block_length, cfg.num_hidden_layers - 1
    text = node["inputs"]["text"]
    ids = bundle.tokenizer.encode(text[:63] if args.rehearsal else text)
    steps = 24 if args.rehearsal else int(node["inputs"]["max_new_tokens"])
    temperature = float(node["inputs"]["temperature"])
    whole = len(ids) - len(ids) % block
    stride = sdar.collect_stride(cfg, steps)
    limits = config["parity"]
    head_chunk = 8
    report, ok = {"device": device.device_kind, "seeds": []}, True

    def collecting(decode_fn, seed):
        """The two functions once more, keeping what a served request
        does not pay for."""
        prefill = sdar.prefill(
            cfg, params, jnp.asarray(ids, jnp.int32), cache_len=len(ids) + steps, collect=True)
        first = jax.tree_util.tree_map(np.asarray, prefill._replace(cache=None))
        decode = decode_fn(
            cfg, params, prefill.cache, prefill.logits, jnp.int32(len(ids)),
            jax.random.key(seed), jnp.float32(temperature), steps=steps, collect=True)
        kv = {layer: np.asarray(decode.cache["kv"][layer], np.float32)
              for layer in (0, last_layer)}
        kept = {k: np.asarray(v) for k, v in decode.kept.items()}
        return first, np.asarray(decode.ids), np.asarray(decode.counts), kept, kv

    def final_sequence(kept):
        """The run's ids over whole blocks: the prompt's, then each
        block as its last denoising pass left it."""
        out = list(ids[:whole])
        for b in range(kept["position"].shape[0]):
            took = [s for s in range(kept["position"].shape[1]) if kept["position"][b, s] >= 0]
            if took:
                s = took[-1]
                out += np.where(kept["moved"][b, s], kept["drawn"][b, s],
                                kept["tokens"][b, s]).tolist()
        return np.asarray(out, np.int32)

    def kv_errors(kv, ref_kv):
        """The first and the last layer's standing keys and values
        against the reference's over the final ids."""
        out = {}
        for name, layer in (("first", 0), ("last", last_layer)):
            want = np.asarray(ref_kv[layer], np.float64)
            mine = kv[layer][:, :, :want.shape[2]]
            out[f"kv_rel_l2_{name}"] = float(np.linalg.norm(mine - want) / np.linalg.norm(want))
        return out

    def against(first, kept, kv, final, sizes_, round_to=None, every=1, whole_sequence=True):
        """The system's kept passes against the reference `sizes_` /
        `round_to` computes; `every`: of every how many kept blocks."""
        rels, flips, moved_other, passes = [], [], 0, kept_passes(kept, stride, every)
        for b, s, row in passes:
            at = int(kept["position"][b, s])
            seen = final.copy()
            seen[at:at + block] = kept["tokens"][b, s]
            logits, chosen, _ = reference.forward(
                sizes_, params, seen, round_to=round_to, head_chunk=head_chunk,
                positions=np.arange(at, at + block))
            logits, masked = np.asarray(logits), kept["masked"][b, s]
            rel = rel_l2(kept["logits"][row, s], logits)
            flip = flipped(kept["chosen"][row, s], np.asarray(chosen)[:, at:at + block])
            rels.append(rel[masked])
            flips.append(flip[:, masked])
            theirs = reference.transferred(
                sizes_, reference.confidence(logits, jnp.asarray(kept["drawn"][b, s]), temperature),
                masked, s)
            moved_other += int(not np.array_equal(theirs, kept["moved"][b, s]))
        numbers = {}
        if whole_sequence:
            logits, chosen, ref_kv = reference.forward(
                sizes_, params, final, round_to=round_to, head_chunk=head_chunk,
                positions=np.asarray([whole - 1]))
            rels.append(rel_l2(first.logits[None], np.asarray(logits)))
            flips.append(flipped(
                first.chosen[:, whole - 1:whole], np.asarray(chosen)[:, whole - 1:whole]))
            numbers.update(kv_errors(kv, ref_kv), rel_l2_prefill=float(rels[-1][0]))
        rel, flip = np.concatenate(rels), np.concatenate(flips, axis=1)
        same = ~np.any(flip, axis=0)
        numbers.update({
            "rel_l2_median": float(np.median(rel)), "rel_l2_max": float(rel.max()),
            "rel_l2_max_unflipped": float(rel[same].max()) if same.any() else None,
            "positions": int(len(rel)), "positions_unflipped": int(same.sum()),
            "expert_set_mismatch": float(np.mean(flip)),
            "transfer_mismatch": moved_other / float(len(passes)), "passes_compared": len(passes),
        })
        return numbers

    def without_closing(cfg_, params_, cache, tokens, position, close):
        """`sdar.block_pass` whose closing pass does nothing: the keys and
        values of the last denoising pass stand."""
        if not close:
            return block_pass(cfg_, params_, cache, tokens, position, close)
        experts = (cfg_.num_hidden_layers, cfg_.num_experts)
        return None, cache, jnp.zeros(
            (cfg_.num_hidden_layers, block, cfg_.num_experts_per_tok), jnp.int32), jnp.zeros(
            experts, jnp.int32)

    block_pass = sdar.block_pass
    jax.block_until_ready(generate_tokens(bundle, ids, 0, steps, temperature)[1].ids)  # builds

    for seed in range(1, args.seeds + 1):
        began = time.monotonic()
        prefill, decode = generate_tokens(bundle, ids, seed, steps, temperature)
        jax.block_until_ready(prefill.logits)
        prefill_s = time.monotonic() - began
        jax.block_until_ready(decode.ids)
        both_s = time.monotonic() - began
        served_ids = np.asarray(decode.ids)
        del prefill, decode
        # equal ids tie the served programs to what is compared below
        first, new_ids, counts, kept, kv = collecting(sdar.decode, seed)
        final = final_sequence(kept)
        denoise, closing, by_threshold, by_floor, read = (int(n) for n in counts)
        numbers = against(first, kept, kv, final, sizes)
        numbers.update({
            "prefill_s": prefill_s, "decode_s": both_s - prefill_s,
            "denoise_passes": denoise, "closing_passes": closing,
            "transferred_by_threshold": by_threshold, "transferred_by_floor": by_floor,
            "experts_read_a_pass_and_layer": read / float(
                denoise * cfg.num_hidden_layers + closing * last_layer),
            "served_ids_equal": bool(np.array_equal(served_ids, new_ids)),
            "final_ids_are_the_ids": bool(np.array_equal(
                final[len(ids):len(ids) + steps], new_ids)),
        })
        passes = (numbers["served_ids_equal"] and numbers["final_ids_are_the_ids"]
                  and within(numbers, limits))
        numbers["within_limits"] = passes
        ok = ok and passes
        entry = {"seed": seed, "system": numbers}
        controls = {
            "float8_reference": (sizes, jnp.float8_e4m3fn),
            "causal_mask_reference": (dataclasses.replace(sizes, block_mask=False), None),
            "rotation_before_norm_reference": (
                dataclasses.replace(sizes, norm_then_rotate=False), None),
            "weights_not_renormalised_reference": (
                dataclasses.replace(sizes, norm_topk_prob=False), None),
        }
        for name, (control_sizes, round_to) in controls.items():
            entry[name] = against(
                first, kept, kv, final, control_sizes, round_to, every=4, whole_sequence=False)
            entry[name]["outside_limits"] = not within(entry[name], limits)
            ok = ok and entry[name]["outside_limits"]
        # the system without its closing passes: its own run, ids and reference
        with mock.patch.object(sdar, "block_pass", without_closing):
            # a new function object, or JAX hands back the served program's trace
            fresh = jax.jit(
                partial(sdar.decode.__wrapped__), static_argnames=("cfg", "steps", "collect"),
                donate_argnames=("cache",))
            first_c, _, _, kept_c, kv_c = collecting(fresh, seed)
        final_c = final_sequence(kept_c)
        _, _, ref_kv = reference.forward(
            sizes, params, final_c, head_chunk=head_chunk, positions=np.asarray([whole - 1]))
        control = kv_errors(kv_c, ref_kv)
        control["outside_limits"] = not within(control, limits)
        ok = ok and control["outside_limits"]
        entry["closing_passes_left_out"] = control
        report["seeds"].append(entry)
        print(json.dumps(entry), flush=True)

    # a pass of either kind alone, on this script's clock: 32 calls in a row over one cache
    cache = sdar.prefill(
        cfg, params, jnp.asarray(ids, jnp.int32), cache_len=len(ids) + steps).cache
    tokens, at = jnp.asarray(ids[:block], jnp.int32), jnp.int32(len(ids) + steps // 2)
    timing = {}
    for close in (False, True):
        one = jax.jit(  # the weights an argument: closed over, they would be constants
            lambda params, cache, tokens, at, close=close: block_pass(
                cfg, params, cache, tokens, at, close)[:2], donate_argnums=(1,))
        _, cache = one(params, cache, tokens, at)
        jax.block_until_ready(cache)
        began = time.monotonic()
        for _ in range(32):
            _, cache = one(params, cache, tokens, at)
        jax.block_until_ready(cache)
        timing["closing_pass_s" if close else "denoise_pass_s"] = (time.monotonic() - began) / 32
    if device.device_kind in sdar_counts.PEAKS:
        # this script's clock (dispatch in it), not a device trace: how far
        # the reckoning is from the run
        peak = sdar_counts.peaks(device.device_kind)
        each = sdar_counts.expected_experts_read(config)
        layers = config["num_hidden_layers"]
        timing["denoise_pass_least_s"] = sdar_counts.pass_bytes(
            config, each * layers, len(ids) + steps // 2, False) / peak["bytes_per_s"]
        timing["closing_pass_least_s"] = sdar_counts.pass_bytes(
            config, each * (layers - 1), len(ids) + steps // 2, True) / peak["bytes_per_s"]
        timing["prefill_least_s"] = sdar_counts.prefill_flops(
            config, whole, whole * config["num_experts_per_tok"] * layers) / peak["flops_per_s"]
    report["timing"] = timing
    print(json.dumps(timing), flush=True)
    peaks = (device.memory_stats() or {}).get("peak_bytes_in_use")
    report["peak_bytes_in_use"] = peaks
    report["ok"] = ok
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "sdar_parity.json"), "w", encoding="utf-8") as fh:
        json.dump(report, fh, indent=1)
    print(json.dumps({"ok": ok, "peak_bytes_in_use": peaks, "limits": {
        k: v for k, v in limits.items() if k.startswith("tolerance")}}), flush=True)
    return 0 if ok or args.rehearsal else 1


if __name__ == "__main__":
    sys.exit(main())
