#!/usr/bin/env python3
"""What the innermost loop body of a model's `decode` holds once the TPU's
compiler is done with it: the instructions in program order, each with its
result, the tail of its `op_name` and libtpu's `estimated_cycles`, and the
count of those that carry an estimate. By hand, on the CPU, no chip:

    python scripts/loop_body_ops.py            # every case below
    python scripts/loop_body_ops.py ouro --text scratch/ouro.hlo

A case is a label and a function that lowers the model's `decode` at a
cell's sizes for a described `v5e:2x2`, with `jax.default_backend` patched
to `"tpu"` so that the model takes its TPU routes (as
`tests/test_flash_kernel_v5e.py` steers them). The child (`--child`)
compiles and writes the compiled text; the parent, which never imports
JAX, finds the `while` whose body holds no further `while` and lists it
(the longest, where a program has several: `body_rows` is what the tests
call on a text they compiled themselves).

What the columns mean. A decode step at batch 1 is one dependent chain: no
instruction of the body starts before the one it reads has ended, and
nothing prefetches the next product's weights meanwhile. So every
instruction with an estimate is a launch the chain waits for, and the count
is a budget. `cycles` is the compiler's cost model, not a time: it reads a
memory-bound matrix-vector product about three times too long (Ouro's four
read 722,000 cycles a layer pass, 481 us at 1.5 GHz, where the chip takes
141), and is good only for telling a launch-sized operation (1,800 to
2,700) from a product. Scalar arithmetic in scalar memory (`S(6)`), bitcasts,
the in-place `dynamic-update-slice` and a kernel's custom call carry no
estimate and are listed without one. A time comes from the chip:
docs/performance.md, "Listing a decode's loop body".
"""

from __future__ import annotations

import argparse
import os
import re
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from scripts.described_v5e import one_chip, run_child  # noqa: E402

# not worth a row: they compile to nothing
SILENT = ("parameter", "get-tuple-element", "constant", "tuple", "bitcast")


def _ouro(place):
    """Ouro-2.6B's decode at its cell's sizes: 64 steps over a cache of
    2,112 positions, bfloat16."""
    import jax
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models import ouro

    cfg = ouro.OuroConfig()
    params = jax.tree.map(
        lambda s: place(s.shape, jnp.bfloat16),
        jax.eval_shape(lambda: ouro.init_params(cfg, jax.random.key(0), jnp.bfloat16)))
    key = jax.eval_shape(lambda: jax.random.key(0))
    return ouro.decode.lower(
        cfg, params, place(cfg.cache_shape(2112), jnp.bfloat16),
        place((cfg.vocab_size,), jnp.float32), place((), jnp.int32),
        place(key.shape, key.dtype), place((), jnp.float32), steps=64)


# label -> lowering of the model's decode, given `place(shape, dtype)`
CASES = {
    "ouro": _ouro,
}


def child(label: str, out: str) -> None:
    """Compile the case for a described v5e and write the compiled text."""
    chip = one_chip()
    import jax

    jax.default_backend = lambda: "tpu"  # this process compiles and runs nothing
    compiled = CASES[label](
        lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=chip)).compile()
    with open(out, "w") as fh:
        fh.write(compiled.as_text())


_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{$")
# a result is one shape with its tiling, or a tuple of them in parentheses
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\(")


def computations(text: str) -> dict:
    """{a computation's name: its instruction lines, in program order}."""
    found, name = {}, None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            name = head.group(1)
            found[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None and line.startswith("  "):
            found[name].append(line)
    return found


def innermost_body(text: str) -> list:
    """The instruction lines of the `while` body that holds no `while`
    itself; the longest, where several do."""
    comps = computations(text)
    bodies = {m for lines in comps.values() for line in lines
              for m in re.findall(r" while\(.*body=%([\w.\-]+)", line)}
    leaves = [comps[b] for b in bodies
              if b in comps and not any(" while(" in line for line in comps[b])]
    if not leaves:
        raise ValueError("no while loop in the compiled text")
    return max(leaves, key=len)


def _untiled(result: str) -> str:
    """`bf16[16,1,64]{2,0,1:T(8,128)(2,1)S(1)}` -> `bf16[16,1,64]`, in a
    tuple too."""
    return re.sub(r"(\w+\[[\d,]*\])\{[^}]*\}", r"\1", re.sub(r"/\*index=\d+\*/", "", result))


def body_rows(text: str) -> list:
    """[{name, op, result, from, cycles}] of the innermost loop body's
    instructions in program order, `cycles` None where the compiler
    gives the instruction no estimate."""
    rows = []
    for line in innermost_body(text):
        got = _INSTRUCTION.match(line)
        if not got or got.group(3) in SILENT:
            continue
        name, result, op = got.groups()
        if op == "custom-call":
            target = re.search(r'custom_call_target="([^"]+)"', line)
            op = f"custom-call {target.group(1)}" if target else op
        op_name = re.search(r'op_name="([^"]*)"', line)
        cycles = re.search(r'"estimated_cycles":"(\d+)"', line)
        rows.append({
            "name": name, "op": op,
            "result": _untiled(result),
            "from": "/".join(op_name.group(1).split("/")[-3:]) if op_name else "",
            "cycles": int(cycles.group(1)) if cycles else None,
        })
    return rows


def costed(rows: list) -> list:
    """The rows that carry an estimate: what the chain launches."""
    return [row for row in rows if row["cycles"] is not None]


def measure(label: str, keep: str | None) -> list:
    out = keep
    if out is None:
        handle, out = tempfile.mkstemp(prefix="loop_body_", suffix=".hlo")
        os.close(handle)
    done = run_child(__file__, label, "--text", out)
    try:
        with open(out) as fh:
            return body_rows(fh.read())
    except (OSError, ValueError):
        sys.stderr.write(done.stderr[-4000:])  # a compile that failed says why here
        raise
    finally:
        if keep is None and os.path.exists(out):
            os.remove(out)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("labels", nargs="*", help=f"cases (default: all of {', '.join(CASES)})")
    ap.add_argument("--text", help="keep the compiled text in this file (one case)")
    ap.add_argument("--child", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        return child(args.child, args.text)
    labels = args.labels or list(CASES)
    if args.text and len(labels) != 1:
        ap.error("--text keeps one case's text")
    for label in labels:
        rows = measure(label, args.text)
        print(f"\n{label}: {len(costed(rows))} instructions with a cost estimate "
              f"in the innermost loop body, {len(rows)} listed\n")
        print("| # | instruction | op | result | cycles | from |")
        print("| --- | --- | --- | --- | --- | --- |")
        for at, row in enumerate(rows, start=1):
            cycles = "–" if row["cycles"] is None else f"{row['cycles']:,}"
            print(f"| {at} | `{row['name']}` | {row['op']} | `{row['result']}` | {cycles} | "
                  f"{row['from']} |", flush=True)


if __name__ == "__main__":
    main()
