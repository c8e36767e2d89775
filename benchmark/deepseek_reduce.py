"""What the DeepSeek-V2 cell's readers share: one request's
`node.TextGenerate` span, its attributes, and the `device.wait` under it
(the one read-back of the ids, in which the executor thread sits out the
prefill and the decode). A program without the node makes these
return None or nothing, and the readers leave their metrics out."""

from __future__ import annotations

NODE = "node.TextGenerate"


def node_of(request: list):
    for span in request:
        if span["name"] == NODE and span.get("duration") is not None:
            return span
    return None


def attrs_of(request: list) -> dict:
    node = node_of(request)
    return (node.get("attrs") or {}) if node else {}


def wait_seconds(request: list):
    """The `device.wait` directly under the node."""
    node = node_of(request)
    if node is None:
        return None
    for span in request:
        if (span["name"] == "device.wait" and span.get("parent_id") == node["span_id"]
                and span.get("duration") is not None):
            return float(span["duration"])
    return None
