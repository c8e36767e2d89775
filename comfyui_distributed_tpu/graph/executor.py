"""Graph validation and execution.

Standalone replacement for the ComfyUI executor the reference rides on
(reference utils/async_helpers.py:108-140 validates via ComfyUI's
execution.validate_prompt then enqueues into its prompt queue). Here:
`validate_prompt` gives the same node-error summarization contract and
`GraphExecutor.execute` runs the graph topologically with per-run
result caching on a compute thread.
"""

from __future__ import annotations

import dataclasses
import inspect
import threading
from typing import Any, Optional

from ..utils.exceptions import PromptValidationError
from .prompt import Prompt, is_link
from .registry import NODE_REGISTRY, get_node_class


@dataclasses.dataclass
class ExecutionContext:
    """Everything a node can reach at run time."""

    mesh: Any = None                     # jax.sharding.Mesh or None
    participant: Any = None              # graph.prompt.ParticipantInfo
    config: dict[str, Any] | None = None
    server: Any = None                   # api server state (elastic tier)
    interrupt_event: threading.Event = dataclasses.field(
        default_factory=threading.Event
    )
    # caches shared across nodes in one process
    pipelines: dict[str, Any] = dataclasses.field(default_factory=dict)
    extras: dict[str, Any] = dataclasses.field(default_factory=dict)
    # defer(work): a served prompt's hand-off of work that may end after
    # the graph walk (SaveImage's read-back, encode and write); the
    # server runs `work(overlapped, landed)` on its saver thread and the
    # prompt is done when it has. None: the node does the work itself.
    defer: Any = None

    def check_interrupted(self) -> None:
        if self.interrupt_event.is_set():
            raise InterruptedError("execution interrupted")


def validate_prompt(prompt: Prompt) -> None:
    """Validate a prompt graph; raises PromptValidationError carrying
    per-node error summaries (parity with the reference's
    PromptValidationError surface)."""
    node_errors: dict[str, list[str]] = {}
    if not isinstance(prompt, dict) or not prompt:
        raise PromptValidationError("prompt must be a non-empty dict", {})

    for node_id, node in prompt.items():
        errors: list[str] = []
        if not isinstance(node, dict) or "class_type" not in node:
            node_errors[str(node_id)] = ["node must be a dict with class_type"]
            continue
        class_type = node["class_type"]
        if class_type not in NODE_REGISTRY:
            errors.append(f"unknown class_type {class_type!r}")
            node_errors[str(node_id)] = errors
            continue
        schema = get_node_class(class_type).INPUT_TYPES()
        inputs = node.get("inputs", {})
        for name, spec in schema.get("required", {}).items():
            if name not in inputs:
                if _spec_default(spec) is None:
                    errors.append(f"missing required input {name!r}")
        for name, value in inputs.items():
            if is_link(value):
                if value[0] not in prompt:
                    errors.append(f"input {name!r} links to missing node {value[0]!r}")
                else:
                    src = prompt[value[0]]
                    src_cls = (
                        NODE_REGISTRY.get(src.get("class_type", ""))
                        if isinstance(src, dict)
                        else None
                    )
                    if src_cls is not None:
                        n_outputs = len(getattr(src_cls, "RETURN_TYPES", ()))
                        if value[1] >= n_outputs:
                            errors.append(
                                f"input {name!r} links to output {value[1]} of "
                                f"node {value[0]!r} which has {n_outputs} output(s)"
                            )
        if errors:
            node_errors[str(node_id)] = errors

    if node_errors:
        summary = "; ".join(
            f"node {nid}: {', '.join(errs)}" for nid, errs in sorted(node_errors.items())
        )
        raise PromptValidationError(f"invalid prompt: {summary}", node_errors)

    _toposort(prompt)  # raises on cycles


def _spec_default(spec: Any) -> Any:
    if isinstance(spec, (tuple, list)) and len(spec) > 1 and isinstance(spec[1], dict):
        return spec[1].get("default")
    return None


def _toposort(prompt: Prompt) -> list[str]:
    order: list[str] = []
    state: dict[str, int] = {}  # 0=unvisited 1=visiting 2=done

    def visit(node_id: str, chain: list[str]) -> None:
        s = state.get(node_id, 0)
        if s == 2:
            return
        if s == 1:
            cycle = " -> ".join(chain + [node_id])
            raise PromptValidationError(f"cycle in prompt graph: {cycle}", {})
        state[node_id] = 1
        for value in prompt[node_id].get("inputs", {}).values():
            if is_link(value) and value[0] in prompt:
                visit(value[0], chain + [node_id])
        state[node_id] = 2
        order.append(node_id)

    for node_id in sorted(prompt):
        visit(node_id, [])
    return order


# telemetry/runtime.program_work() key -> the node span attribute its delta goes by
_TALLY_ATTRS = {
    "compiles": "compiles",
    "compile_time_s": "compile_s",
    "cache_hits": "cache_hits",
    "cache_misses": "cache_misses",
    "trace_s": "trace_s",
    "lower_s": "lower_s",
    "cache_fetch_s": "cache_fetch_s",
}


def _program_work(before: dict[str, Any], after: dict[str, Any]) -> dict[str, Any]:
    """Between two `runtime.program_work()` snapshots: four tallies of the
    process, and the wall-clock sums over the `program.build` spans closed
    under the node (the last three); what did not move is left out."""
    return {
        attr: after[key] - before[key]
        for key, attr in _TALLY_ATTRS.items()
        if after[key] != before[key]
    }


class GraphExecutor:
    """Execute a validated prompt graph."""

    def __init__(self, context: Optional[ExecutionContext] = None):
        self.context = context or ExecutionContext()
        # per-node wall times of the last execution (observability the
        # reference lacks — SURVEY §5 "no timing/profiler integration")
        self.last_timings: dict[str, float] = {}
        # of the last execution: nodes whose function ran, and nodes
        # answered from the cache (a node that ran can round to 0.0 s)
        self.nodes_run = 0
        self.nodes_cached = 0

    def execute(self, prompt: Prompt) -> dict[str, Any]:
        """Run the graph; returns {node_id: output} for OUTPUT_NODE nodes.

        Nodes re-execute only when their literal inputs or any upstream
        node changed since the previous run on this context (ComfyUI's
        incremental-execution behavior). Distributed/gather nodes and
        output sinks always re-run — the reference forces the same via
        IS_CHANGED = nan on its distributed nodes.
        """
        import json
        import time

        from ..telemetry import get_tracer
        from ..telemetry import runtime

        tracer = get_tracer()
        validate_prompt(prompt)
        order = _toposort(prompt)
        results: dict[str, tuple] = {}
        outputs: dict[str, Any] = {}
        self.last_timings = {}
        self.nodes_run = self.nodes_cached = 0
        cache: dict[str, tuple[str, tuple]] = self.context.extras.setdefault(
            "node_cache", {}
        )
        content_keys: dict[str, str] = {}

        for node_id in order:
            self.context.check_interrupted()
            node_def = prompt[node_id]
            cls = get_node_class(node_def["class_type"])
            instance = cls()
            schema = cls.INPUT_TYPES()
            kwargs: dict[str, Any] = {}

            # content key: class + literal inputs + upstream keys
            literals = {
                k: v for k, v in node_def.get("inputs", {}).items()
                if not is_link(v)
            }
            upstream_keys = sorted(
                content_keys.get(v[0], "?")
                for v in node_def.get("inputs", {}).values()
                if is_link(v)
            )
            content_keys[node_id] = json.dumps(
                [node_def["class_type"], literals, upstream_keys],
                sort_keys=True, default=str,
            )
            cacheable = not getattr(cls, "OUTPUT_NODE", False) and not getattr(
                cls, "NEVER_CACHE", False
            )
            cached = cache.get(node_id) if cacheable else None
            if cached is not None and cached[0] == content_keys[node_id]:
                results[node_id] = cached[1]
                self.last_timings[node_id] = 0.0
                self.nodes_cached += 1
                continue

            # defaults first, then literal/link inputs
            for section in ("required", "optional"):
                for name, spec in schema.get(section, {}).items():
                    default = _spec_default(spec)
                    if default is not None:
                        kwargs[name] = default
            for name, value in node_def.get("inputs", {}).items():
                if is_link(value):
                    src_id, out_idx = value
                    kwargs[name] = results[src_id][out_idx]
                else:
                    kwargs[name] = value

            fn = getattr(instance, cls.FUNCTION)
            if "context" in inspect.signature(fn).parameters:
                kwargs["context"] = self.context
            # host time in the node: dispatch is asynchronous, so the
            # device's time shows where the thread waits (device.wait)
            with tracer.span(
                f"node.{node_def['class_type']}", node_id=node_id
            ) as span:
                before = runtime.program_work()
                started = time.perf_counter()
                result = fn(**kwargs)
                self.last_timings[node_id] = round(
                    time.perf_counter() - started, 4
                )
                span.attrs.update(_program_work(before, runtime.program_work()))
            self.nodes_run += 1
            if result is None:
                result = ()
            if not isinstance(result, tuple):
                result = (result,)
            results[node_id] = result
            if cacheable:
                cache[node_id] = (content_keys[node_id], result)
            if getattr(cls, "OUTPUT_NODE", False):
                outputs[node_id] = result
        # evict cache entries for node ids absent from this prompt:
        # without this a long-lived server accumulates stale results
        # (large tensors) for every node id any past prompt ever used
        for stale_id in set(cache) - set(prompt):
            del cache[stale_id]
        return outputs
