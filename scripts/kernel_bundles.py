#!/usr/bin/env python3
"""VLIW bundles and slot totals of a Pallas kernel's body, compiled for a
described TPU v5e with libtpu's own dump. By hand, on the CPU, no chip:

    python scripts/kernel_bundles.py                  # every case below
    python scripts/kernel_bundles.py "flux joint 4608" --keep /root/scratch/llo

A case is a label, the kernel's name in the compiled program, and a
function that lowers one call of it at a served shape. libtpu writes one
file a compiler pass and kernel under `--xla_jf_dump_to`; the file
`*-<kernel>*-final_hlo-static-per-bundle-utilization.txt` has one line a
bundle and the slots it fills. The process that loaded libtpu with those
flags aborts once its dumps are written, so every case compiles in a child
of its own (`--child`) and the parent, which never imports JAX, reads the
files. How to read the numbers: docs/performance.md, "Reading a kernel's
bundles". Nothing here runs or times anything: a time comes from the chip.
"""

from __future__ import annotations

import argparse
import glob
import os
import re
import shutil
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from scripts.described_v5e import one_chip, run_child  # noqa: E402

UTILIZATION = "final_hlo-static-per-bundle-utilization.txt"


def _attention(q_shape, kv_heads=None, v_width=None, window=None, causal=False):
    """Lower one `ops/attention` call on the kernel: q [B, N, H, D] over
    as many keys, bf16 as every served path."""
    def lower(place):
        import functools

        from comfyui_distributed_tpu.ops import attention as attn

        b, n, h, d = q_shape
        q = place(q_shape)
        k = place((b, n, kv_heads or h, d))
        v = place((b, n, kv_heads or h, v_width or d))
        if causal:
            fn = functools.partial(attn.causal_attention, window=window, force_flash=True)
        else:
            fn = functools.partial(attn.dot_product_attention, force_flash=True)
        return fn, (q, k, v)
    return lower


def _short(q_shape, keys, tiles=None):
    """Lower one `ops/short_attention` call: q [B, N, H, 64] over `keys`
    keys, all of them one block, `tiles` lane tiles a grid step (the
    plan's where None), bf16."""
    def lower(place):
        import functools

        from comfyui_distributed_tpu.ops import short_attention

        b, _, h, d = q_shape
        return functools.partial(short_attention.short_attention, tiles=tiles), (
            place(q_shape), place((b, keys, h, d)), place((b, keys, h, d)))
    return lower


def _dsa_attend(queries, rows, k, heads, rank, rope):
    """Lower one `ops/dsa_attend` call: a block of a part's queries over
    the chosen rows of a latent cache, bf16."""
    def lower(place):
        import jax
        import jax.numpy as jnp

        from comfyui_distributed_tpu.ops import dsa_attend

        def like(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=place(shape).sharding)

        def fn(q_lat, q_rope, cache, chosen, counts):
            return dsa_attend.dsa_attend(
                q_lat, q_rope, dsa_attend.table(cache, rank), chosen, counts, scale=1.0)

        return fn, (place((queries, heads, rank)), place((queries, heads, rope)),
                    place((rows, rank + rope)),
                    like((queries, k), jnp.int32), like((queries, k), jnp.bool_))
    return lower


def _dsa_select(queries, positions, k):
    """Lower one `ops/dsa_select` call: a block of a part's queries'
    float32 scores over a rung of the cache, the `k` best as positions."""
    def lower(place):
        import functools

        import jax
        import jax.numpy as jnp

        from comfyui_distributed_tpu.ops import dsa_select

        scores = jax.ShapeDtypeStruct(
            (queries, positions), jnp.float32, sharding=place((queries, positions)).sharding)
        return functools.partial(dsa_select.dsa_select, k=k), (scores,)
    return lower


def _ssd_chunk(tokens, heads, width, groups, n, chunk):
    """Lower one `ops/ssd_chunk` call: a Mamba-2 layer's chunked scan
    over a part of a prompt, bf16 operands and float32 steps and state."""
    def lower(place):
        import functools

        import jax
        import jax.numpy as jnp

        from comfyui_distributed_tpu.ops import ssd_chunk

        def f32(shape):
            return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=place(shape).sharding)

        return functools.partial(ssd_chunk.ssd_chunk, chunk=chunk), (
            place((tokens, heads, width)), place((tokens, groups, n)), place((tokens, groups, n)),
            f32((tokens, heads)), f32((heads,)), f32((heads, width, n)))
    return lower


def _grouped_matmul(rows, k, n, groups):
    """Lower one `ops/grouped_matmul` call: a rung's rows against the
    held experts' stacked weights [groups, k, n]."""
    def lower(place):
        import jax
        import jax.numpy as jnp

        from comfyui_distributed_tpu.ops import grouped_matmul

        sizes = jax.ShapeDtypeStruct((groups,), jnp.int32, sharding=place((groups,)).sharding)
        return grouped_matmul.grouped_matmul, (place((rows, k)), place((groups, k, n)), sizes)
    return lower


# label -> (kernel's name in the compiled program, lowering). The labels
# are `chip_smoke.SERVED_SHAPES` / `CAUSAL_SHAPES`', so a bundle count and
# the chip's time of `chip_smoke.py --legs attention` read side by side.
CASES = {
    "sd15 self 64x64": ("flash_attention", _attention((2, 4096, 8, 40))),
    "flux joint 4608": ("flash_attention", _attention((1, 4608, 24, 128))),
    "sdxl tile self 36x36": ("flash_attention", _attention((16, 1296, 10, 64))),
    "sdxl tile self 18x18": ("flash_attention", _attention((16, 324, 20, 64))),
    "sdxl tile self 18x18 short": ("short_attention", _short((16, 324, 20, 64), 324)),
    "sdxl tile self 18x18 short, one lane tile a step": (
        "short_attention", _short((16, 324, 20, 64), 324, tiles=1)),
    "sdxl tile cross 18x18 short": ("short_attention", _short((16, 324, 20, 64), 77)),
    "sdxl tile cross 36x36 short": ("short_attention", _short((16, 1296, 10, 64), 77)),
    "sdxl tile self 36x36 short": ("short_attention", _short((16, 1296, 10, 64), 1296)),
    "solar / k-exaone full 8192": (
        "flash_attention_causal", _attention((1, 8192, 64, 128), kv_heads=8, causal=True)),
    "k-exaone window 8192": (
        "flash_attention_causal",
        _attention((1, 8192, 64, 128), kv_heads=8, window=128, causal=True)),
    "deepseek-v2 mla 2048": (
        "flash_attention_causal", _attention((1, 2048, 128, 192), v_width=128, causal=True)),
    "glm-5.2 dsa": ("dsa_attend", _dsa_attend(2048, 32896, 2048, 64, 512, 64)),
    "glm-5.2 dsa select": ("dsa_select", _dsa_select(128, 32768, 2048)),
    "granite-4.0-h-micro mamba-2": ("ssd_chunk", _ssd_chunk(8192, 64, 64, 1, 128, 256)),
    "nemotron3-nano mamba-2": ("ssd_chunk", _ssd_chunk(8192, 64, 64, 8, 128, 128)),
    "dots3-note-prev part, gate-up": ("grouped_matmul", _grouped_matmul(8192, 5120, 3072, 32)),
    "dots3-note-prev part, down": ("grouped_matmul", _grouped_matmul(8192, 1536, 5120, 32)),
}
DEFAULT = ("sd15 self 64x64", "flux joint 4608", "solar / k-exaone full 8192")


def child(label: str) -> None:
    """Compile the case for a described v5e; libtpu dumps as it goes."""
    chip = one_chip()
    import jax
    import jax.numpy as jnp

    fn, args = CASES[label][1](
        lambda shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=chip))
    jax.jit(fn).lower(*args).compile()


def read_bundles(dump: str, kernel: str) -> dict:
    """Bundles and the sum of every slot's column over the kernel's file."""
    pattern = re.compile(rf"^\d+-{re.escape(kernel)}(\.\d+)?-\d+-{re.escape(UTILIZATION)}$")
    found = [p for p in glob.glob(os.path.join(dump, f"*{UTILIZATION}"))
             if pattern.match(os.path.basename(p))]
    if len(found) != 1:
        raise SystemExit(f"{len(found)} utilization files of {kernel} under {dump}")
    lines = open(found[0]).read().splitlines()
    at = lines.index("== UTILIZATION:")
    slots = [s.strip() for s in lines[at - 2].split(",")]
    capacity = [int(x) for x in lines[at - 1].split()]
    rows = [[int(x) for x in line.split()] for line in lines[at + 1:] if line.strip()]
    totals = [sum(col) for col in zip(*rows)]
    return {"bundles": len(rows), "slots": dict(zip(slots, zip(totals, capacity)))}


def measure(label: str, keep: str | None) -> dict:
    kernel = CASES[label][0]
    dump = tempfile.mkdtemp(prefix="kernel_bundles_", dir=keep)
    done = run_child(
        __file__, label,
        LIBTPU_INIT_ARGS=f"--xla_jf_dump_to={dump} --xla_jf_dump_llo_text=true")
    try:
        return read_bundles(dump, kernel)
    except SystemExit:
        sys.stderr.write(done.stderr[-4000:])  # a compile that failed says why here
        raise
    finally:
        if keep is None:
            shutil.rmtree(dump, ignore_errors=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("labels", nargs="*", help=f"cases (default: {', '.join(DEFAULT)})")
    ap.add_argument("--all", action="store_true", help="every case")
    ap.add_argument("--keep", help="keep the dumps under this directory (~70 MB a case)")
    ap.add_argument("--child", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        return child(args.child)
    labels = list(CASES) if args.all else args.labels or DEFAULT
    if args.keep:
        os.makedirs(args.keep, exist_ok=True)
    columns = ("MXU", "XLU", "VALU", "EUP", "VLOAD", "VLOAD:FILL", "VSTORE", "VSTORE:SPILL")
    print("| case | kernel | bundles | us at 1.5 GHz | "
          + " | ".join(columns) + " | MXU floor |")
    print("| --- " * (len(columns) + 5) + "|")
    for label in labels:
        got = measure(label, args.keep)
        slots = got["slots"]
        cells = [f"{slots[c][0]} ({slots[c][1]})" for c in columns]
        floor = -(-slots["MXU"][0] // slots["MXU"][1])
        print(f"| {label} | {CASES[label][0]} | {got['bundles']} | "
              f"{got['bundles'] / 1500:.2f} | " + " | ".join(cells) + f" | {floor} |",
              flush=True)


if __name__ == "__main__":
    main()
