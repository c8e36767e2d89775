"""Span tracing: nesting, cross-thread trace joining, parenting to the
trace root, bounded retention, JSONL export, deterministic fake clock."""

import json
import threading

from comfyui_distributed_tpu.telemetry import Tracer, get_tracer, reset_tracer, tracing
from comfyui_distributed_tpu.resilience.chaos import FakeClock


def test_span_nesting_builds_parent_chain():
    tracer = Tracer()
    with tracer.span("root", trace_id="t1") as root:
        with tracer.span("child") as child:
            with tracer.span("grandchild") as grandchild:
                pass
    assert child.trace_id == "t1"
    assert child.parent_id == root.span_id
    assert grandchild.parent_id == child.span_id
    tree = tracer.tree("t1")
    assert len(tree) == 1
    assert tree[0]["name"] == "root"
    assert tree[0]["children"][0]["children"][0]["name"] == "grandchild"


def test_orphan_spans_parent_to_trace_root():
    """A span created with only a trace id (e.g. a server-side RPC span
    built from the propagated header) connects to the existing root."""
    tracer = Tracer()
    with tracer.span("root", trace_id="t1") as root:
        pass
    with tracer.span("rpc", trace_id="t1") as rpc:
        pass
    assert rpc.parent_id == root.span_id
    assert len(tracer.tree("t1")) == 1


def test_activate_joins_trace_across_threads():
    tracer = Tracer()
    with tracer.span("root", trace_id="t1") as root:
        done = threading.Event()

        def worker():
            token = tracer.activate("t1")
            try:
                with tracer.span("thread_work"):
                    pass
            finally:
                tracer.deactivate(token)
                done.set()

        t = threading.Thread(target=worker)
        t.start()
        t.join()
        assert done.is_set()
    spans = tracer.spans("t1")
    thread_span = next(s for s in spans if s["name"] == "thread_work")
    assert thread_span["parent_id"] == root.span_id


def test_error_status_and_duration():
    clock = FakeClock(step=1.0)
    tracer = Tracer(clock=clock)
    try:
        with tracer.span("boom", trace_id="t1"):
            raise ValueError("nope")
    except ValueError:
        pass
    (span,) = tracer.spans("t1")
    assert span["status"] == "error"
    assert span["attrs"]["error"].startswith("ValueError")
    assert span["duration"] == 1.0  # fake clock: start→end is one step


def test_events_attach_to_active_span():
    tracer = Tracer()
    with tracer.span("root", trace_id="t1"):
        tracer.event("log", message="hello")
    (span,) = tracer.spans("t1")
    assert span["events"][0]["name"] == "log"
    assert span["events"][0]["attrs"]["message"] == "hello"


def test_annotate_sets_attributes_on_the_innermost_active_span():
    tracer = Tracer()
    tracer.annotate(programs=1)  # outside a trace: nothing to set, nothing raised
    with tracer.span("outer", trace_id="t1", node_id="7"):
        with tracer.span("inner"):
            tracer.annotate(bytes=3)
        tracer.annotate(programs=1)
    attrs = {s["name"]: s["attrs"] for s in tracer.spans("t1")}
    assert attrs == {"outer": {"node_id": "7", "programs": 1}, "inner": {"bytes": 3}}


def test_trace_eviction_bound():
    tracer = Tracer(max_traces=3)
    for i in range(5):
        with tracer.span("s", trace_id=f"t{i}"):
            pass
    assert tracer.trace_ids() == ["t2", "t3", "t4"]
    assert tracer.spans("t0") == []


def test_eviction_is_lru_not_insertion_order():
    """An in-flight execution that keeps producing spans must survive a
    burst of short traces (or hostile trace-id headers) — eviction
    drops the least-recently-USED trace, not the oldest-created."""
    tracer = Tracer(max_traces=3)
    with tracer.span("root", trace_id="active"):
        pass
    for i in range(10):
        with tracer.span("s", trace_id=f"burst{i}"):
            pass
        # the active trace keeps appending spans between bursts
        with tracer.span("tile", trace_id="active"):
            pass
    assert "active" in tracer.trace_ids()
    active = tracer.spans("active")
    assert len(active) == 11  # nothing lost to eviction
    # and the root survived, so the tree stays singly-rooted
    assert len(tracer.tree("active")) == 1


def test_the_startup_trace_outlives_257_traces():
    """The oldest of `max_traces` goes first, but never the process's
    own start: a server that has answered 300 prompts still serves it."""
    tracer = Tracer(clock=FakeClock(step=1.0))
    root = tracer.start_span("process.start", trace_id=tracing.STARTUP_TRACE)
    with tracer.span("startup.backend", trace_id=tracing.STARTUP_TRACE):
        pass
    tracer.end_span(root)
    for i in range(257):
        with tracer.span("execute_prompt", trace_id=f"exec_{i}"):
            pass
    ids = tracer.trace_ids()
    assert len(ids) == tracer.max_traces == 256
    assert tracing.STARTUP_TRACE in ids and "exec_0" not in ids and "exec_1" not in ids
    assert [s["name"] for s in tracer.spans(tracing.STARTUP_TRACE)] == [
        "process.start", "startup.backend"]
    assert tracer.root_span_id(tracing.STARTUP_TRACE) == root.span_id


def test_record_span_parents_and_orders_as_a_context_managed_span_does():
    """A span whose start and end someone else read: under the active
    span, else under the trace's root, between its siblings by start,
    and never the active span itself."""
    tracer = Tracer(clock=FakeClock(step=1.0))
    with tracer.span("node.KSampler", trace_id="t") as node:
        with tracer.span("device.wait"):
            pass
        built = tracer.record_span(
            "program.build", node.start + 0.25, node.start + 0.5, attrs={"program": "jit(f)"})
        assert tracer.current_span_id() == node.span_id
        with tracer.span("png.encode") as later:
            pass
    assert (built.parent_id, built.trace_id, built.status) == (node.span_id, "t", "ok")
    assert built.duration == 0.25 and built.attrs == {"program": "jit(f)"}
    (tree,) = tracer.tree("t")
    assert [c["name"] for c in tree["children"]] == ["program.build", "device.wait", "png.encode"]
    assert later.parent_id == node.span_id
    # outside any span of the trace: the root's child
    token = tracer.activate("t")
    try:
        orphan = tracer.record_span("program.build", 0.0, 1.0)
    finally:
        tracer.deactivate(token)
    assert orphan.parent_id == node.span_id  # node is the trace's root
    # an explicit trace and parent, as start_span takes them
    other = tracer.record_span("program.build", 1.0, 2.0, trace_id="u")
    assert (other.trace_id, other.parent_id) == ("u", None)


def test_record_span_reaches_the_span_listener_open_then_close():
    seen, installed = [], tracing._span_listener  # the event bus's, once it is up
    tracing.set_span_listener(lambda phase, span: seen.append((phase, span.name, span.end)))
    try:
        Tracer().record_span("program.build", 1.0, 3.0, trace_id="t")
    finally:
        tracing.set_span_listener(installed)
    assert seen == [("open", "program.build", None), ("close", "program.build", 3.0)]


def test_jsonl_export_takes_several_traces_or_all(tmp_path):
    tracer = Tracer(clock=FakeClock(step=1.0))
    for trace_id in ("a", "b", "c"):
        with tracer.span("execute_prompt", trace_id=trace_id):
            with tracer.span("node.SaveImage"):
                pass
    path = tmp_path / "some.jsonl"
    assert tracer.write_jsonl(["c", "a", "nowhere"], str(path)) == 4
    lines = [json.loads(line) for line in path.read_text().splitlines()]
    assert [l["trace_id"] for l in lines] == ["c", "c", "a", "a"]
    assert tracer.write_jsonl(None, str(path)) == 6
    lines = [json.loads(line) for line in path.read_text().splitlines()]
    assert [l["trace_id"] for l in lines] == ["a", "a", "b", "b", "c", "c"]
    assert lines[0] == tracer.spans("a")[0]


def test_jsonl_export_round_trip(tmp_path):
    tracer = Tracer(clock=FakeClock())
    with tracer.span("root", trace_id="t1", kind="test"):
        with tracer.span("child"):
            pass
    path = tmp_path / "trace.jsonl"
    written = tracer.write_jsonl("t1", str(path))
    assert written == 2
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert {l["name"] for l in lines} == {"root", "child"}
    assert all(l["trace_id"] == "t1" for l in lines)
    assert all(l["end"] is not None for l in lines)


def test_fake_clock_spans_are_deterministic():
    def run():
        tracer = Tracer(clock=FakeClock())
        with tracer.span("a", trace_id="t"):
            with tracer.span("b"):
                pass
            with tracer.span("c"):
                pass
        return [
            (s["name"], s["start"], s["end"]) for s in tracer.spans("t")
        ]

    assert run() == run()


def test_global_tracer_reset():
    t1 = get_tracer()
    assert get_tracer() is t1
    reset_tracer()
    assert get_tracer() is not t1


def test_trace_logger_mirrors_into_spans():
    """trace_info attaches its message as an event on the trace's span
    tree (the subsumption contract of utils/trace_logger.py)."""
    from comfyui_distributed_tpu.utils.trace_logger import trace_info

    tracer = get_tracer()
    with tracer.span("root", trace_id="exec_test_1"):
        pass
    trace_info("exec_test_1", "dispatched")
    (span,) = tracer.spans("exec_test_1")
    assert any(
        e["attrs"].get("message") == "dispatched" for e in span["events"]
    )
    # a trace with no spans stays log-only (no crash, nothing recorded)
    trace_info("exec_never_spanned", "message")
    assert tracer.spans("exec_never_spanned") == []
