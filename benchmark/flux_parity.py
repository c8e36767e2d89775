#!/usr/bin/env python3
"""FLUX.1-dev on the chip against its float32 reference, outside any
timed window: at the published widths and the cell's shapes (the bundle
`load_pipeline` builds for the configuration's `registry_name`, 512 text
+ 4,096 image tokens), one evaluation of the denoiser through the served
model function and the 20-step latent through `KSampler`'s own path.

    python3 benchmark/flux_parity.py [--seeds 3]

Prints, per seed, the relative L2 and the largest absolute error of the
system against the reference, and the same for the reference computed
one precision below the configuration's (float8 e4m3 operands): the
limit (`parity.tolerance_rel_l2` in configs/flux.1-dev.json) has to pass
the first and fail the second. Exit 1 if either does not hold. Writes
chiprun_out/flux_parity.json. One process: it holds the chip itself.

`--rehearsal` checks this script on the CPU with tiny-flux at 64 px; its
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
PROMPT = "a photograph of a mountain lake at dawn, mist over the water, sharp focus"


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seeds", type=int, default=3)
    parser.add_argument("--rehearsal", action="store_true")
    args = parser.parse_args(argv)
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from comfyui_distributed_tpu.graph.nodes_core import KSampler
    from comfyui_distributed_tpu.models import get_config
    from comfyui_distributed_tpu.models import pipeline as pl
    from comfyui_distributed_tpu.workers.startup import configure_compile_cache

    with open(os.path.join(HERE, "configs", "flux.1-dev.json"), encoding="utf-8") as fh:
        config = json.load(fh)
    spec = importlib.util.spec_from_file_location(
        "flux_reference", os.path.join(ROOT, config["reference"]))
    reference = importlib.util.module_from_spec(spec)
    sys.modules["flux_reference"] = reference  # dataclasses looks the module up
    spec.loader.exec_module(reference)

    device = jax.devices()[0]
    if device.platform != ("cpu" if args.rehearsal else "tpu"):
        print(f"flux_parity: needs a TPU, found {device.platform}", file=sys.stderr)
        return 2
    configure_compile_cache()
    name, px, steps = config["registry_name"], 1024, 20
    if args.rehearsal:
        name, px, steps = "tiny-flux", 64, 4
        print("REHEARSAL on the CPU at a toy size: this checks the script, not the chip")
    tolerance = float(config["parity"]["tolerance_rel_l2"])

    started = time.monotonic()
    bundle = pl.load_pipeline(name)
    cfg = get_config(name)
    shift = pl.model_schedule_info(bundle)[1]
    sizes = reference.Sizes(heads=cfg.heads, axes_dim=cfg.axes_dim, patch=cfg.patch_size,
                            theta=cfg.theta, freq_dim=cfg.freq_dim)
    positive = pl.encode_text_pooled(bundle, [PROMPT])
    positive.guidance = 3.5
    negative = pl.encode_text_pooled(bundle, [""])
    jax.block_until_ready((positive.context, negative.context))
    side = px // bundle.latent_scale
    shape = (1, side, side, bundle.latent_channels)
    tokens = positive.context.shape[1] + (side // cfg.patch_size) ** 2
    stats = device.memory_stats() or {}
    print(f"{name} on {device.device_kind}: loaded and encoded in "
          f"{time.monotonic() - started:.1f}s, {tokens} tokens, latent {shape}, "
          f"bytes in use {stats.get('bytes_in_use')}, tolerance {tolerance} (relative L2)")

    served = jax.jit(lambda params, x, sigma, cond: pl._make_model_fn(bundle, params)(
        x, sigma, cond))
    guidance = jnp.array([3.5], jnp.float32)

    def errors(got, want):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        diff = np.abs(got - want)
        return {"rel_l2": float(np.linalg.norm(diff) / np.linalg.norm(want)),
                "max_abs": float(diff.max()), "max_ref": float(np.abs(want).max())}

    rows, ok = [], True
    for seed in range(args.seeds):
        keys = jax.random.split(jax.random.key(1000 + seed), 2)
        x = jax.random.normal(keys[0], shape)
        sigma = jax.random.uniform(keys[1], (1,), minval=0.2, maxval=1.0)
        row = {"seed": seed, "sigma": float(sigma[0])}
        want = reference.velocity(bundle.params["unet"], sizes, x, sigma, positive.context,
                                  positive.pooled, guidance)
        row["evaluation"] = errors(served(bundle.params, x, sigma, positive), want)
        row["evaluation_float8_reference"] = errors(
            reference.velocity(bundle.params["unet"], sizes, x, sigma, positive.context,
                               positive.pooled, guidance, round_to=jnp.float8_e4m3fn), want)

        (out,) = KSampler().sample(bundle, seed, steps, 1.0, "euler", "simple", positive,
                                   negative, {"samples": jnp.zeros(shape)}, denoise=1.0)
        noise_key, _ = jax.random.split(jax.random.key(seed))
        noise = pl._batch_noise(noise_key, shape, False)
        latent = reference.sample_euler(
            bundle.params["unet"], sizes, noise, positive.context, positive.pooled, guidance,
            steps=steps, shift=shift)
        row["latent"] = errors(out["samples"], latent)
        row["latent_float8_reference"] = errors(
            reference.sample_euler(
                bundle.params["unet"], sizes, noise, positive.context, positive.pooled,
                guidance, steps=steps, shift=shift, round_to=jnp.float8_e4m3fn), latent)
        rows.append(row)
        for what in ("evaluation", "latent"):
            mine, below = row[what], row[what + "_float8_reference"]
            passed = mine["rel_l2"] < tolerance < below["rel_l2"]
            ok = ok and passed
            print(f"seed {seed} {what:10s} system rel_l2 {mine['rel_l2']:.3e} max_abs "
                  f"{mine['max_abs']:.3e} (largest reference value {mine['max_ref']:.3f}) | "
                  f"float8 reference rel_l2 {below['rel_l2']:.3e} max_abs "
                  f"{below['max_abs']:.3e} | tolerance {tolerance} "
                  f"{'ok' if passed else 'NOT MET'}", flush=True)

    result = {"ok": ok, "model": name, "tokens": tokens, "steps": steps, "shift": shift,
              "tolerance_rel_l2": tolerance, "rehearsal": args.rehearsal, "rows": rows,
              "device": {"platform": device.platform, "kind": device.device_kind},
              "seconds": time.monotonic() - started}
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "flux_parity.json"), "w", encoding="utf-8") as fh:
        json.dump(result, fh, indent=1)
    print(json.dumps({k: result[k] for k in ("ok", "model", "tokens", "device", "seconds")}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
