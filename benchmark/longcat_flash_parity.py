#!/usr/bin/env python3
"""LongCat-Flash-Chat on the chip against its float32 reference, outside
any timed window: at the published widths and the cell's sizes (the
bundle `load_pipeline` builds for the configuration's `registry_name`;
the committed workflow's 32,768-token prompt and 128 new tokens), the
served path's own two programs (`graph/nodes_text.generate_tokens`: the
prefill in its four parts, the decode one token a step) against the
reference's one forward pass over the ids the runs emitted (the whole
sequence at once, one `[T, T]` mask, every key and value expanded, a
loop over the held experts).

    python3 benchmark/longcat_flash_parity.py [--seeds 2]

The system runs first, every seed, and what it produced is kept on the
host; then the weights leave the device and the reference reads them from
the host, a weight at a time, so that its float32 working set has the
chip to itself. The seeds share their prompt, so the reference reads them
in **one** pass: the prompt, then each seed's new ids, every continuation
at the positions after the prompt and blind to the others (the
reference's `positions` and `seen`); a pass costs what one seed's would.

Prints, per seed: the relative L2 of the logits at the last prompt
position and at every decoded position: median and largest, and the
largest among the positions whose own token chose the reference's ids in
every layer; the share of (token, layer) pairs whose set of chosen ids
differs from the reference's; of the eight caches, the relative L2 of
`SAMPLED` prompt rows and every decoded row against the reference's
latents (the largest of the eight is limited); and how the served
programs are tied to the collecting ones whose logits are compared
(`glm_dsa_parity.tied`). Then the same numbers for ten controls that
have to fail the limits (`parity` in configs/longcat-flash-chat.json) on
every seed: the reference with one thing wrong, in the system's place
against the reference proper (float8 e4m3 operands; no rescale of the
query; none of the latent; renormalised weights; factor 1; identities
dropped; the branch read from x; the branch added after the first
feed-forward; rotation by halves), and the system with one thing wrong
(a layer's second attention over the first's cache, traced anew with
`longcat_flash.walk` replaced). Also the seconds the prefill and a
decode step took on this script's own clock beside what
`longcat_flash_counts` says the chip's peaks allow, `memory_stats()`
beside each program, and the milliseconds of a part's causal call (the
heads of one call) at the four counts of keys on both routes
(`ops/attention.causal_attention`, the kernel and XLA's blocks). Exit 1
if a limit does not hold. Writes chiprun_out/longcat_flash_parity.json.
One process: it holds the chip itself.

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

# Prompt positions whose cache rows are compared, beside every decoded one.
SAMPLED = 192


def errors(mine: dict, want: dict) -> dict:
    """What a collecting run verified (`logits`, `chosen`, the caches'
    `rows`) against what the reference gave at the same places."""
    import numpy as np

    from deepseek_parity import flipped  # [layers, tokens]: another set than the reference chose
    from glm_dsa_parity import rel_l2

    rel = rel_l2(mine["logits"], want["logits"])
    flips = flipped(mine["chosen"], want["chosen"])
    same = ~np.any(flips, axis=0)
    caches = [float(np.linalg.norm(np.asarray(a, np.float64) - b) / np.linalg.norm(b))
              for a, b in zip(mine["rows"], (np.asarray(r, np.float64) for r in want["rows"]))]
    return {
        "rel_l2_median": float(np.median(rel)), "rel_l2_max": float(rel.max()),
        "rel_l2_prefill": float(rel[0]),
        "rel_l2_max_unflipped": float(rel[same].max()) if same.any() else None,
        "positions": int(len(rel)), "positions_unflipped": int(same.sum()),
        "expert_set_mismatch": float(np.mean(flips)),
        "cache_rel_l2": max(caches), "cache_rel_l2_by_attention": caches,
    }


def within(numbers: dict, limits: dict) -> bool:
    """Every limit of the configuration's `parity` holds. Where no
    position chose the reference's ids in every layer there is no
    largest among them to limit, and the other limits decide."""
    worst = numbers["rel_l2_max_unflipped"]
    return (
        numbers["rel_l2_median"] <= limits["tolerance_rel_l2_median"]
        and numbers["expert_set_mismatch"] <= limits["tolerance_expert_set_mismatch"]
        and numbers["cache_rel_l2"] <= limits["tolerance_cache_rel_l2"]
        and (worst is None or worst <= limits["tolerance_rel_l2_max_unflipped"])
    )


def causal_routes_ms(cfg, rows: int, counts: tuple, interpret: bool) -> dict:
    """ms a call of a part's causal attention on both routes: `rows`
    queries of the heads one call takes over each of `counts` keys,
    bfloat16, five calls dispatched back to back."""
    import jax
    import jax.numpy as jnp

    from comfyui_distributed_tpu.ops import attention

    heads, out = cfg.attention_heads_a_call, {}
    for keys_n in counts:
        keys = jax.random.split(jax.random.key(63), 3)
        q, k, v = (
            jax.random.normal(key, (1, n, heads, d)).astype(jnp.bfloat16)
            for key, n, d in zip(keys, (rows, keys_n, keys_n),
                                 (cfg.qk_head_dim, cfg.qk_head_dim, cfg.v_head_dim)))
        for name, flash in (("flash", True), ("xla", False)):
            fn = jax.jit(functools.partial(
                attention.causal_attention, scale=cfg.qk_head_dim ** -0.5,
                force_flash=flash, interpret=flash and interpret))
            with attention.route_log() as routes:
                jax.block_until_ready(fn(q, k, v))
            began = time.perf_counter()
            for _ in range(5):
                last = fn(q, k, v)
            jax.block_until_ready(last)
            out[f"{name} {rows}x{keys_n}"] = {
                "entry": routes[0], "ms": 2e2 * (time.perf_counter() - began)}
    return out


def second_attention_over_the_first_cache(walk):
    """`longcat_flash.walk` with a wrong mechanism: a layer's second
    attention writes into and reads the first's cache."""
    def shared(cfg, params, caches, h, attend):
        held = []

        def through(p, x, cache):
            out, rows = attend(p, x, held[-1] if len(held) % 2 else cache)
            held.append(rows)
            return out, rows

        return walk(cfg, params, caches, h, through)

    return shared


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

    import longcat_flash_counts
    from glm_dsa_parity import tied

    from comfyui_distributed_tpu.graph.nodes_text import generate_tokens
    from comfyui_distributed_tpu.models import longcat_flash
    from comfyui_distributed_tpu.models import pipeline as pl
    from comfyui_distributed_tpu.parallel.sharding import params_byte_size
    from comfyui_distributed_tpu.workers.startup import configure_compile_cache

    config = longcat_flash_counts.config()
    spec = importlib.util.spec_from_file_location(
        "longcat_flash_reference", os.path.join(ROOT, config["reference"]))
    reference = importlib.util.module_from_spec(spec)
    sys.modules["longcat_flash_reference"] = reference  # dataclasses looks the module up
    spec.loader.exec_module(reference)
    with open(os.path.join(HERE, "workflows", "longdoc-txt2img-longcat-flash.json"),
              encoding="utf-8") as fh:
        (node,) = [n for n in json.load(fh).values() if n["class_type"] == "TextGenerate"]

    configure_compile_cache()
    device = jax.devices()[0]
    print(f"device: {device.platform} {device.device_kind} x{jax.device_count()}", flush=True)

    def memory(tag: str) -> dict:
        stats = device.memory_stats() or {}
        said = {"at": tag, **{k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use")}}
        print(json.dumps({"memory": said}), flush=True)
        return said

    started = time.monotonic()
    bundle = pl.load_pipeline(
        "tiny-longcat-flash" if args.rehearsal else config["registry_name"])
    jax.block_until_ready(bundle.params)
    lm, params = bundle.lm, bundle.params["lm"]
    print(f"bundle: {params_byte_size(params) / 1e9:.3f} GB in {time.monotonic() - started:.1f} s",
          flush=True)
    cfg = lm.cfg
    sizes, held = reference.Sizes.of(cfg), list(cfg.held_experts)
    text = node["inputs"]["text"]
    ids = bundle.tokenizer.encode(text[:255] if args.rehearsal else text)
    steps = 24 if args.rehearsal else int(node["inputs"]["max_new_tokens"])
    prompt, total = len(ids), len(ids) + steps
    temperature = float(node["inputs"]["temperature"])
    assert int(node["inputs"]["draft_tokens"]) == 0
    limits = config["parity"]
    # two heads' float32 scores of 1,024 queries over 33,024 rows: 0.27 GB
    blocks = {"head_chunk": 4, "row_block": 64} if args.rehearsal else {
        "head_chunk": 2, "row_block": 1024}
    sampled = np.sort(np.random.default_rng(63).choice(
        np.arange(prompt - 1), size=min(SAMPLED, prompt - 1), replace=False))
    report = {"device": device.device_kind, "seeds": [], "memory": [memory("weights loaded")]}
    ok = True

    def collecting(seed, prefill_fn=longcat_flash.prefill, decode_fn=longcat_flash.decode):
        """The two functions once more, keeping what a served request
        does not pay for: everything compared, on the host."""
        prefill = prefill_fn(
            cfg, params, jnp.asarray(ids, jnp.int32), cache_len=total, collect=True)
        before = jax.device_get(prefill._replace(cache=None))
        decode = decode_fn(
            cfg, params, prefill.cache, prefill.logits, jnp.int32(prompt),
            jax.random.key(seed), jnp.float32(temperature), steps=steps, collect=True)
        kept = np.concatenate([sampled, np.arange(prompt - 1, total)])
        rows = [np.asarray(cache[kept], np.float32) for cache in decode.cache["latents"]]
        after = jax.device_get(decode._replace(cache=None))
        return {
            "logits": np.concatenate([before.logits[None], after.kept["logits"]]),
            "chosen": np.concatenate([
                before.chosen[:, prompt - 1:prompt], after.kept["chosen"].transpose(1, 0, 2)],
                axis=1),
            "rows": rows, "ids": after.ids, "step_logits": after.kept["logits"],
        }

    jax.block_until_ready(generate_tokens(bundle, ids, 0, steps, temperature)[1].ids)  # builds
    report["memory"].append(memory("both programs built and run once"))

    runs = []
    for seed in range(1, args.seeds + 1):
        began = time.monotonic()
        prefill, decode = generate_tokens(bundle, ids, seed, steps, temperature)
        jax.block_until_ready(prefill.logits)
        prefill_s = time.monotonic() - began
        jax.block_until_ready(decode.ids)
        both_s = time.monotonic() - began
        served = {"ids": np.asarray(decode.ids), "logits": np.asarray(prefill.logits)}
        said = lm.report(prompt, steps, total, *jax.device_get(lm.read_back(prefill, decode)))
        del prefill, decode
        mine = collecting(seed)
        work = longcat_flash_counts.prefill_flops(
            config, prompt, said["prefill_routed_pairs_held"])
        step = longcat_flash_counts.decode_step_bytes(
            config, said["decode_experts_read"] / steps, prompt + steps // 2)
        peak = longcat_flash_counts.peaks(config["as_run"]["chip"])
        runs.append((seed, mine, {
            "prefill_s": prefill_s, "decode_s": both_s - prefill_s,
            "decode_step_s": (both_s - prefill_s) / steps,
            "prefill_least_s": work / peak["flops_per_s"],
            "decode_step_least_s": step / peak["bytes_per_s"],
            **{key: said[key] for key in (
                "decode_experts_read", "prefill_routed_pairs_held", "prefill_zero_pairs",
                "decode_zero_pairs", "prefill_routed_pairs", "decode_routed_pairs",
                "prefill_expert_rows", "decode_expert_route", "real_experts_per_token_mean",
                "real_experts_per_token_min", "real_experts_per_token_max")},
            **tied(served, mine, jax.random.key(seed), temperature, limits),
        }))
        print(json.dumps({"seed": seed, **runs[-1][2]}), flush=True)
    report["memory"].append(memory("the served and the collecting runs"))

    # the system with a wrong mechanism, traced anew: under new function objects, or JAX
    # hands back the trace it cached for the programs above
    shared = {}

    def prefill_again(cfg, params, ids, *, cache_len, collect=False):
        return longcat_flash.prefill.__wrapped__(
            cfg, params, ids, cache_len=cache_len, collect=collect)

    def decode_again(cfg, params, cache, logits, start, key, temperature, *, steps,
                     collect=False):
        return longcat_flash.decode.__wrapped__(
            cfg, params, cache, logits, start, key, temperature, steps=steps, collect=collect)

    walk = longcat_flash.walk
    longcat_flash.walk = second_attention_over_the_first_cache(walk)
    try:
        again = (
            jax.jit(prefill_again, static_argnames=("cfg", "cache_len", "collect")),
            jax.jit(decode_again, static_argnames=("cfg", "steps", "collect")))
        for seed, _, _ in runs:
            shared[seed] = collecting(seed, *again)
    finally:
        longcat_flash.walk = walk

    # the weights to the host: the reference's float32 working set has the chip to itself
    weights = jax.device_get(params)
    bundle.params.clear()
    for leaf in jax.tree_util.tree_leaves(params):
        leaf.delete()
    del params
    rows = 64 if args.rehearsal else cfg.prefill_part
    report["causal_routes"] = causal_routes_ms(
        cfg, rows, tuple(rows * (i + 1) for i in range(4)), args.rehearsal)
    print(json.dumps({"causal_routes": report["causal_routes"]}), flush=True)

    # every seed's continuation behind the one prompt, each blind to the others
    full = np.concatenate([np.asarray(ids)] + [mine["ids"] for _, mine, _ in runs])
    positions = np.concatenate(
        [np.arange(prompt)] + [prompt + np.arange(steps)] * len(runs))
    run_of = np.concatenate([np.zeros(prompt, int)] + [
        np.full(steps, n + 1) for n in range(len(runs))])
    rows_all = np.arange(len(full))
    seen = (rows_all[None, :] <= rows_all[:, None]) & (
        (run_of[None, :] == 0) | (run_of[None, :] == run_of[:, None]))
    keep = np.concatenate([sampled, np.arange(prompt - 1, len(full))])
    n_sampled = len(sampled)

    def reference_pass(sizes, round_to=None) -> dict:
        """One pass over every seed's ids; what each seed compares."""
        logits, chosen, caches = reference.forward(
            sizes, weights, full, held, round_to=round_to, keep=keep, positions=positions,
            seen=jnp.asarray(seen), **blocks)
        logits, chosen = np.asarray(logits), np.asarray(chosen)
        caches = [np.asarray(c) for c in caches]
        out = {}
        for n, (seed, _, _) in enumerate(runs):
            # of `keep`: the sampled rows, the last prompt row, this seed's rows
            mine = np.concatenate([
                np.arange(n_sampled + 1), n_sampled + 1 + n * steps + np.arange(steps)])
            at = np.concatenate([[prompt - 1], prompt + n * steps + np.arange(steps)])
            out[seed] = {"logits": logits[mine[n_sampled:]], "chosen": chosen[:, at],
                         "rows": [c[mine] for c in caches]}
        return out

    began = time.monotonic()
    wanted = reference_pass(sizes)
    reference_s = time.monotonic() - began
    entries = {}
    for seed, mine, numbers in runs:
        numbers.update(errors(mine, wanted[seed]))
        numbers["reference_s"] = reference_s
        numbers["logit_abs_max"] = float(np.abs(wanted[seed]["logits"]).max())
        passes = numbers["tied"] and within(numbers, limits)
        numbers["within_limits"] = passes
        ok = ok and passes
        entries[seed] = {"seed": seed, "served": numbers}
        print(json.dumps({"seed": seed, **numbers}), flush=True)
    report["memory"].append(memory("the reference's pass"))

    def control(name: str, low: dict) -> None:
        nonlocal ok
        for seed, mine, _ in runs:
            # the control in the system's place, against the reference proper
            entry = entries[seed][name] = errors({**mine, **low[seed]}, wanted[seed])
            entry["outside_limits"] = not within(entry, limits)
            ok = ok and entry["outside_limits"]
            print(json.dumps({"seed": seed, "control": name, **entry}), flush=True)

    control("second_attention_over_the_first_cache", shared)
    wrong = {
        "float8_reference": (sizes, jnp.float8_e4m3fn),
        "no_rescale_of_the_query": (dataclasses.replace(sizes, rescale_q=False), None),
        "no_rescale_of_the_latent": (dataclasses.replace(sizes, rescale_kv=False), None),
        "renormalised_weights": (dataclasses.replace(sizes, renormalise=True), None),
        "factor_1": (dataclasses.replace(sizes, routed_scaling_factor=1.0), None),
        "identities_dropped": (dataclasses.replace(sizes, identities=False), None),
        "branch_read_from_x": (dataclasses.replace(sizes, branch_from_x=True), None),
        "branch_added_after_the_first_feed_forward": (
            dataclasses.replace(sizes, branch_after_first=True), None),
        "rotation_by_halves": (dataclasses.replace(sizes, rotate_halves=True), None),
    }
    for name, (control_sizes, round_to) in wrong.items():
        control(name, reference_pass(control_sizes, round_to))
    report["seeds"] = list(entries.values())
    report["ok"] = ok
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "longcat_flash_parity.json"), "w", encoding="utf-8") as fh:
        json.dump(report, fh, indent=1)
    print(json.dumps({"ok": ok, "limits": {
        k: v for k, v in limits.items() if k.startswith("tolerance")}}), flush=True)
    return 0 if ok or args.rehearsal else 1


if __name__ == "__main__":
    sys.exit(main())
