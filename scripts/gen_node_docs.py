#!/usr/bin/env python
"""Generate docs/nodes.md from the live node registry.

The reference's node surface is documented by ComfyUI itself; here the
reference doc is generated from the registry so it can never drift —
tests/test_node_docs.py regenerates and compares (fast tier).

Run after adding/changing nodes:  python scripts/gen_node_docs.py
"""

from __future__ import annotations

import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def render() -> str:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_platforms", "cpu")
    import comfyui_distributed_tpu.graph  # noqa: F401  (populates registry)
    from comfyui_distributed_tpu.graph.registry import NODE_REGISTRY

    by_module: dict[str, list[str]] = {}
    for name, cls in NODE_REGISTRY.items():
        mod = cls.__module__.rsplit(".", 1)[-1]
        by_module.setdefault(mod, []).append(name)

    titles = {
        "nodes_core": "Core (checkpoint → encode → sample → decode → save)",
        "nodes_custom_sampling": "Custom sampling (NOISE / GUIDER / SAMPLER / SIGMAS)",
        "nodes_loaders": "Separate-file loaders + model-sampling patches",
        "nodes_controlnet": "ControlNet + conditioning",
        "nodes_distributed": "Distributed (the reference's own node set)",
        "nodes_upscale": "Distributed upscale",
        "nodes_mask": "Masks",
        "nodes_transform": "Image/latent transforms (flip, rotate, crop, blend)",
        "nodes_video": "Video (WAN class)",
        "nodes_audio": "Audio",
        "nodes_text": "Text (a language model in the graph; STRING links)",
    }

    lines = [
        "# Node reference",
        "",
        "GENERATED by `scripts/gen_node_docs.py` from the live registry —",
        "edit node docstrings, not this file. Every class follows the",
        "ComfyUI node contract (`INPUT_TYPES` / `RETURN_TYPES` /",
        "`FUNCTION`), so reference workflows load directly.",
        "",
        f"{len(NODE_REGISTRY)} registered node classes.",
        "",
    ]
    for mod in titles:
        names = sorted(by_module.pop(mod, []))
        if not names:
            continue
        lines += [f"## {titles[mod]}", "", "| Node | Signature | Summary |",
                  "|---|---|---|"]
        for name in names:
            cls = NODE_REGISTRY[name]
            schema = cls.INPUT_TYPES()
            ins = list(schema.get("required", {})) + [
                f"{k}?" for k in schema.get("optional", {})
            ]
            outs = getattr(cls, "RETURN_TYPES", ())
            sig = f"({', '.join(ins)}) → {', '.join(outs) or '—'}"
            doc = " ".join(
                (cls.__doc__ or "").strip().split("\n\n")[0].split()
            )
            if len(doc) > 160:
                doc = doc[:157] + "..."
            # literal pipes would split the markdown table row
            sig = sig.replace("|", "\\|")
            doc = doc.replace("|", "\\|")
            lines.append(f"| `{name}` | `{sig}` | {doc} |")
        lines.append("")
    for mod in sorted(by_module):  # any module not in the title map
        lines += [f"## {mod}", ""]
        for name in sorted(by_module[mod]):
            lines.append(f"- `{name}`")
        lines.append("")
    return "\n".join(lines).rstrip() + "\n"


def main() -> int:
    dest = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "docs", "nodes.md",
    )
    content = render()
    if "--check" in sys.argv[1:]:
        with open(dest) as fh:
            if fh.read() != content:
                print("docs/nodes.md is stale; run scripts/gen_node_docs.py")
                return 1
        print("docs/nodes.md is current")
        return 0
    with open(dest, "w") as fh:
        fh.write(content)
    print(f"wrote {dest}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
