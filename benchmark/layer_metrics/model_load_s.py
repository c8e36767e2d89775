"""Seconds the checkpoint loader took in the run's first request
(/history timings; loading is host work, so the node's own clock holds)."""


def read(material):
    timings = material["first"].get("timings") or {}
    seconds = [
        timings[node] for node, spec in material["prompt"].items()
        if spec["class_type"] == "CheckpointLoaderSimple" and node in timings
    ]
    return float(sum(seconds)) if seconds else None
