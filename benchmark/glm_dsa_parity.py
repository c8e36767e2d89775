#!/usr/bin/env python3
"""GLM-5.2 on the chip against its float32 reference, outside any timed
window: at the published widths and the cell's sizes (the bundle
`load_pipeline` builds for the configuration's `registry_name`; the
committed workflow's 32,768-token prompt and 128 new tokens), the served
path's own two programs (`graph/nodes_text.generate_tokens`: the prefill
in its four parts, and the decode, by self-speculation with
`draft_tokens` 1 and again one token a step with 0) against the
reference's forward passes over the 32,896 ids the run emitted (the whole
sequence at once, full sorts, attention under a mask of the selection;
the MTP module's pass over the whole sequence).

    python3 benchmark/glm_dsa_parity.py [--seeds 2]

The system runs first, every seed and `draft_tokens`, and what it
produced is kept on the host; then the weights leave the device and the
reference reads them from the host, a weight at a time, so that its
float32 working set has the chip to itself.

Prints, per seed and `draft_tokens`: the relative L2 of the main model's
logits at the last prompt position and at every position a step verified
(row 0 of every step, row 1 where the draft was kept; every decoded
position without drafting): median and largest, and the largest among
the positions whose own token chose the reference's experts in every
layer; the relative L2 of the draft logits at every position a draft was
drawn from (median); the share of (token, layer) pairs whose set of
chosen experts differs from the reference's; how the served programs are
tied to the collecting ones whose logits are compared (`tied`, below);
and of the selections, over
`SAMPLED` prompt positions past `index_topk` and every verified decoded
position, a layer that has an indexer (the module's at the positions
drafts were drawn from): the share of selections that differ from the
reference's at all, and the mean share of a selection's positions that
are not the reference's. Then the same numbers for six controls that
have to fail the limits (`parity` in configs/glm-5.2.json), each the
reference with one thing wrong, in the system's place against the
reference proper: float8 e4m3 operands; the indexer without its ReLU;
`index_topk` halved; a `shared` layer attending by the selection of the
`full` layer above it; the indexer's rotation over halves; a part's
queries blind to the parts before it. Also the steps a decode took, the
drafts it kept, and the seconds the prefill and the decode took on this
script's own clock. Exit 1 if a limit does not hold. Writes
chiprun_out/glm_dsa_parity.json. One process: it holds the chip itself.

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

# Prompt positions whose selections are compared, drawn past `index_topk`.
SAMPLED = 192


def gathered(prompt: int, sampled, prefill, decode, draft_tokens: int) -> dict:
    """What a collecting run verified, by position: `positions` (the last
    of the prompt, then every decoded position the main model ran on a
    confirmed token), the main model's `logits` there, the experts
    `chosen` [sparse layers, positions, k] by the tokens at those
    positions, `queries` (the `sampled` prompt positions, then the
    decoded ones of `positions`) with each `full` layer's `selections`
    there as (positions, which count), and when drafting the positions
    drafts were `drawn` from with their `draft_logits` and the module's
    `draft_selection`."""
    import numpy as np

    kept = decode.kept
    steps = int(np.asarray(decode.counts)[0])
    if not draft_tokens:
        positions = prompt + np.arange(steps)
        rows, chosen = kept["logits"], kept["chosen"].transpose(1, 0, 2)
        selections = kept["selections"]
        extra = {}
    else:
        at, accepted = kept["position"][:steps], kept["accepted"][:steps]
        first, second = np.arange(steps), np.flatnonzero(accepted)
        positions = np.concatenate([at[first], at[second] + 1])
        order = np.argsort(positions)
        both = lambda a: np.concatenate([a[first, 0], a[second, 1]])[order]  # noqa: E731
        rows = both(kept["logits"])
        chosen = both(kept["chosen"].transpose(0, 2, 1, 3)).transpose(1, 0, 2)
        selections = [tuple(both(a) for a in layer) for layer in kept["selections"]]
        positions = positions[order]
        extra = {"drawn": at - 1, "draft_logits": kept["draft_logits"][:steps],
                 "draft_selection": tuple(a[:steps] for a in kept["draft_selection"]),
                 "accepted": int(accepted.sum())}
    before = prefill.kept
    return {
        "positions": np.concatenate([[prompt - 1], positions]),
        "logits": np.concatenate([prefill.logits[None], rows]),
        "chosen": np.concatenate([before["chosen"][:, prompt - 1:prompt], chosen], axis=1),
        "queries": np.concatenate([sampled, positions]),
        "selections": [
            tuple(np.concatenate([a, b]) for a, b in zip(early, late))
            for early, late in zip(before["selections"], selections)],
        "steps": steps, **extra,
    }


def rel_l2(got, want):
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


def as_masks(selection, size: int):
    """(positions [n, k], which count [n, k]) as [n, size] bools."""
    import numpy as np

    chosen, counts = selection
    masks = np.zeros((chosen.shape[0], size), bool)
    rows = np.broadcast_to(np.arange(chosen.shape[0])[:, None], chosen.shape)
    masks[rows[counts], chosen[counts]] = True
    return masks


def selection_errors(mine, theirs) -> tuple[float, float]:
    """Of the selections `mine` [n, T] against the reference's: the share
    that differ at all, and the mean share of a selection's positions
    that the reference did not choose."""
    import numpy as np

    wrong = (mine & ~theirs).sum(axis=1)
    differ = (mine != theirs).any(axis=1)
    return float(differ.mean()), float(np.mean(wrong / np.maximum(mine.sum(axis=1), 1)))


def errors(mine: dict, want: dict) -> dict:
    """`mine` of `gathered` (its selections as masks) against what the
    reference gave at the same positions (`reference_at`)."""
    import numpy as np

    from deepseek_parity import flipped  # [layers, tokens]: another set than the reference chose

    rel = rel_l2(mine["logits"], want["logits"])
    flips = flipped(mine["chosen"], want["chosen"])
    same = ~np.any(flips, axis=0)
    pairs = list(zip(mine["masks"], want["masks"]))
    if "draft_masks" in mine:
        pairs.append((mine["draft_masks"], want["draft_masks"]))
    differ, wrong = zip(*(selection_errors(a, b) for a, b in pairs))
    out = {
        "rel_l2_median": float(np.median(rel)), "rel_l2_max": float(rel.max()),
        "rel_l2_prefill": float(rel[0]),
        "rel_l2_max_unflipped": float(rel[same].max()) if same.any() else None,
        "positions": int(len(rel)), "positions_unflipped": int(same.sum()),
        "expert_set_mismatch": float(np.mean(flips)),
        "selections_differ": float(np.mean(differ)),
        "selection_mismatch": float(np.mean(wrong)),
        "selection_mismatch_by_layer": [float(w) for w in wrong],
    }
    if "draft_logits" in mine:
        out["draft_rel_l2_median"] = float(
            np.median(rel_l2(mine["draft_logits"], want["draft_logits"])))
    return out


def tied(served: dict, mine: dict, key, temperature: float, limits: dict) -> dict:
    """How the served programs (which keep no logits) are tied to the
    collecting ones, which are programs of their own and round
    differently: the relative L2 between the two prefills' logits at the
    last prompt position, under the limit of the logits themselves; and
    the ids, equal up to `ids_equal_until`. Where they part without
    drafting, the parting is shown to be a near-tie: id i is the largest
    of logits / temperature + the Gumbel noise of the key folded by i,
    and the served program's id has to lie within `tolerance_tie_margin`
    of the collecting program's largest under the collecting program's
    own logits. With drafting a step's ids come of three draws and a
    comparison, and only the index is reported."""
    import jax
    import numpy as np

    out = {"programs_rel_l2_prefill": float(rel_l2(served["logits"], mine["logits"][0]))}
    differ = np.flatnonzero(served["ids"] != mine["ids"])
    out["served_ids_equal"] = not differ.size
    out["ids_equal_until"] = int(differ[0]) if differ.size else int(len(mine["ids"]))
    ok = out["programs_rel_l2_prefill"] <= limits["tolerance_rel_l2_median"]
    if differ.size and "step_logits" in mine:
        at = int(differ[0])
        logits = mine["logits"][0] if at == 0 else mine["step_logits"][at - 1]
        noise = jax.random.gumbel(jax.random.fold_in(key, at), logits.shape, np.float32)
        drawn = np.asarray(logits) / temperature + np.asarray(noise)
        assert int(np.argmax(drawn)) == int(mine["ids"][at])  # the draw, as `sample` makes it
        out["tie_margin"] = float(drawn.max() - drawn[int(served["ids"][at])])
        ok = ok and out["tie_margin"] <= limits["tolerance_tie_margin"]
    out["tied"] = bool(ok)
    return out


def within(numbers: dict, limits: dict) -> bool:
    """Every limit of the configuration's `parity` holds."""
    worst, draft = numbers["rel_l2_max_unflipped"], numbers.get("draft_rel_l2_median")
    return (
        numbers["rel_l2_median"] <= limits["tolerance_rel_l2_median"]
        and numbers["expert_set_mismatch"] <= limits["tolerance_expert_set_mismatch"]
        and numbers["selection_mismatch"] <= limits["tolerance_selection_mismatch"]
        and worst is not None and worst <= limits["tolerance_rel_l2_max_unflipped"]
        and (draft is None or draft <= limits["tolerance_draft_rel_l2_median"])
    )


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seeds", type=int, default=2)
    parser.add_argument("--control-seeds", type=int, default=1,
                        help="seeds whose drafting run the six controls are computed for")
    parser.add_argument("--rehearsal", action="store_true")
    args = parser.parse_args(argv)
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    import glm_dsa_counts
    from comfyui_distributed_tpu.graph.nodes_text import generate_tokens
    from comfyui_distributed_tpu.models import glm_dsa
    from comfyui_distributed_tpu.models import pipeline as pl
    from comfyui_distributed_tpu.parallel.sharding import params_byte_size
    from comfyui_distributed_tpu.workers.startup import configure_compile_cache

    config = glm_dsa_counts.config()
    spec = importlib.util.spec_from_file_location(
        "glm_dsa_reference", os.path.join(ROOT, config["reference"]))
    reference = importlib.util.module_from_spec(spec)
    sys.modules["glm_dsa_reference"] = reference  # dataclasses looks the module up
    spec.loader.exec_module(reference)
    with open(os.path.join(HERE, "workflows", "longdoc-txt2img-glm-5.2.json"),
              encoding="utf-8") as fh:
        (node,) = [n for n in json.load(fh).values() if n["class_type"] == "TextGenerate"]

    configure_compile_cache()
    device = jax.devices()[0]
    print(f"device: {device.platform} {device.device_kind} x{jax.device_count()}", flush=True)
    started = time.monotonic()
    bundle = pl.load_pipeline("tiny-glm-dsa" if args.rehearsal else config["registry_name"])
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
    assert int(node["inputs"]["draft_tokens"]) == 1
    limits = config["parity"]
    # two heads' float32 scores of 1,024 queries over 32,896 positions: 0.27 GB
    blocks = {"head_chunk": 4, "row_block": 64} if args.rehearsal else {
        "head_chunk": 2, "row_block": 1024}
    sampled = np.sort(np.random.default_rng(52).choice(
        np.arange(cfg.index_topk, len(ids)), size=min(SAMPLED, len(ids) - cfg.index_topk),
        replace=False))
    report, ok = {"device": device.device_kind, "seeds": []}, True

    def collecting(seed, draft_tokens):
        """The two functions once more, keeping what a served request
        does not pay for: everything compared, on the host."""
        prefill = glm_dsa.prefill(
            cfg, params, jnp.asarray(ids, jnp.int32), cache_len=total, collect=True)
        kept = dict(prefill.kept)
        kept["selections"] = [tuple(a[sampled] for a in layer) for layer in kept["selections"]]
        before = jax.device_get(prefill._replace(cache=None, kept=kept))
        decode = glm_dsa.decode(
            cfg, params, prefill.cache, prefill.logits, jnp.int32(len(ids)),
            jax.random.key(seed), jnp.float32(temperature), steps=steps, collect=True,
            draft_tokens=draft_tokens)
        after = jax.device_get(decode._replace(cache=None))
        mine = gathered(len(ids), sampled, before, after, draft_tokens)
        mine["ids"] = after.ids
        if not draft_tokens:
            mine["step_logits"] = after.kept["logits"]
        mine["masks"] = [as_masks(layer, total) for layer in mine.pop("selections")]
        if draft_tokens:
            mine["draft_masks"] = as_masks(mine.pop("draft_selection"), total)[:, :total - 1]
        return np.concatenate([np.asarray(ids), after.ids]), mine

    def reference_at(weights, full, mine, sizes, round_to=None):
        """The reference over `full` at what `mine` verified."""
        logits, h, chosen, masks = reference.forward(
            sizes, weights, full, held, round_to=round_to, positions=mine["positions"],
            queries=mine["queries"], **blocks)
        want = {"logits": np.asarray(logits), "chosen": np.asarray(chosen)[:, mine["positions"]],
                "masks": [np.asarray(m) for m in masks]}
        if "drawn" in mine:
            drafts, _, draft_masks = reference.mtp_forward(
                sizes, weights, h, full, held, round_to=round_to, positions=mine["drawn"],
                **blocks)
            want.update(draft_logits=np.asarray(drafts), draft_masks=np.asarray(draft_masks))
        return want

    for draft_tokens in (1, 0):  # builds the programs
        jax.block_until_ready(
            generate_tokens(bundle, ids, 0, steps, temperature, draft_tokens=draft_tokens)[1].ids)

    runs = []
    for seed in range(1, args.seeds + 1):
        for draft_tokens in (1, 0):
            began = time.monotonic()
            prefill, decode = generate_tokens(
                bundle, ids, seed, steps, temperature, draft_tokens=draft_tokens)
            jax.block_until_ready(prefill.logits)
            prefill_s = time.monotonic() - began
            jax.block_until_ready(decode.ids)
            both_s = time.monotonic() - began
            served = {"ids": np.asarray(decode.ids), "logits": np.asarray(prefill.logits)}
            counts = np.asarray(decode.counts).tolist()
            keys = lm.report(len(ids), steps, total, *jax.device_get(lm.read_back(prefill, decode)))
            del prefill, decode
            full, mine = collecting(seed, draft_tokens)
            runs.append((seed, draft_tokens, full, mine, {
                "prefill_s": prefill_s, "decode_s": both_s - prefill_s,
                "decode_steps": counts[0], "mtp_drafted": counts[1], "mtp_accepted": counts[2],
                "decode_experts_read": counts[3],
                "decode_step_s": (both_s - prefill_s) / counts[0],
                "keys_visible": keys["keys_visible"], "keys_selected": keys["keys_selected"],
                **tied(served, mine, jax.random.key(seed), temperature, limits),
            }))
            print(json.dumps({"seed": seed, "draft_tokens": draft_tokens, **runs[-1][4]}),
                  flush=True)
    report["peak_bytes_in_use"] = (device.memory_stats() or {}).get("peak_bytes_in_use")

    # the weights to the host: the reference's float32 working set has the chip to itself
    weights = jax.device_get(params)
    bundle.params.clear()
    for leaf in jax.tree_util.tree_leaves(params):
        leaf.delete()
    del params
    wrong = {
        "float8_reference": (sizes, jnp.float8_e4m3fn),
        "indexer_without_relu": (dataclasses.replace(sizes, relu=False), None),
        "index_topk_halved": (
            dataclasses.replace(sizes, index_topk=cfg.index_topk // 2), None),
        "selection_shared_from_above": (dataclasses.replace(sizes, share_above=True), None),
        "indexer_rotated_in_halves": (dataclasses.replace(sizes, index_halves=True), None),
        "part_blind_to_the_parts_before": (
            dataclasses.replace(sizes, blind_part=cfg.prefill_part), None),
    }
    entries: dict[int, dict] = {}
    for seed, draft_tokens, full, mine, numbers in runs:
        entry = entries.setdefault(seed, {"seed": seed})
        began = time.monotonic()
        want = reference_at(weights, full, mine, sizes)
        numbers.update(errors(mine, want))
        numbers["reference_s"] = time.monotonic() - began
        numbers["logit_abs_max"] = float(np.abs(want["logits"]).max())
        passes = numbers["tied"] and within(numbers, limits)
        numbers["within_limits"] = passes
        ok = ok and passes
        entry[f"draft_tokens_{draft_tokens}"] = numbers
        print(json.dumps({"seed": seed, "draft_tokens": draft_tokens, **numbers}), flush=True)
        if not draft_tokens or seed > args.control_seeds:
            continue
        for name, (control_sizes, round_to) in wrong.items():
            # the control in the system's place, against the reference proper
            low = reference_at(weights, full, mine, control_sizes, round_to)
            entry[name] = errors({**mine, **low}, want)
            entry[name]["outside_limits"] = not within(entry[name], limits)
            ok = ok and entry[name]["outside_limits"]
            print(json.dumps({"seed": seed, "control": name, **entry[name]}), flush=True)
    report["seeds"] = list(entries.values())
    report["ok"] = ok
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "glm_dsa_parity.json"), "w", encoding="utf-8") as fh:
        json.dump(report, fh, indent=1)
    print(json.dumps({"ok": ok, "peak_bytes_in_use": report["peak_bytes_in_use"], "limits": {
        k: v for k, v in limits.items() if k.startswith("tolerance")}}), flush=True)
    return 0 if ok or args.rehearsal else 1


if __name__ == "__main__":
    sys.exit(main())
