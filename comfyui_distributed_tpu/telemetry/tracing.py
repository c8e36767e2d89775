"""Span-based tracing keyed by the existing ``exec_*`` trace ids.

Subsumes the grep-oriented `utils/trace_logger.py`: instead of log
lines, one execution produces a TREE of spans that
`/distributed/trace/{trace_id}` serves as JSON. On the served path a
request's tree is `sched.wait`, `queue_orchestration`,
`prompt_queue.wait`, `execute_prompt` and under it one
`node.<class_type>` per node that ran, with `device.wait`,
`png.encode` and `file.write` where the executor thread blocks and the
saver thread saves, and one `device.run` per program a node launched,
ended by the watcher thread when the device has finished it; the
elastic tile tier adds `dispatch` and `tile.<stage>`
(docs/observability.md has the whole vocabulary).

Design:

- a span is {trace_id, span_id, parent_id, name, start, end, attrs,
  events, status}; times come from an injectable monotonic clock so
  tier-1 tests (and the chaos harness) are deterministic on CPU;
- the CURRENT span lives in a contextvar. Contexts are per-thread, so
  a compute thread joins a trace by calling `tracer.activate(trace_id)`
  (the server's executor thread does this with the PromptJob's trace
  id; chaos worker threads do it explicitly);
- master→worker propagation is one HTTP header, `X-CDT-Trace-Id`,
  carried by /prompt dispatch and by every tile-pull/submit RPC
  (graph/usdu_elastic.HTTPWorkClient); the receiving route re-attaches
  its spans to the propagated id so the whole distributed execution is
  ONE connected tree;
- a span created with no explicit parent and no active span parents to
  the trace's root span (if any) — server-side RPC spans connect to
  the orchestration root without shipping span ids over the wire;
- storage is bounded: at most `max_traces` traces (oldest evicted) of
  at most `max_spans_per_trace` spans each. The trace `startup`
  (`STARTUP_TRACE`: the process's own start, opened by `__main__`) is
  never evicted;
- `record_span` stores a span whose start and end are already known
  (a program JAX built, telemetry/runtime.py), parented like any other;
- `write_jsonl` exports one span per line, of one trace or of several,
  for offline analysis;
- while a profiler capture is open (telemetry/profiling.py installs
  `set_span_annotator`), every context-managed span is mirrored into
  the capture on the thread that runs it, so the program's spans and
  the device's lines share the profiler's clock. This module imports
  no jax; with no capture open a span pays one global read;
- `device_span` records what the device did without a wait on the
  thread that launches: the launch puts (span, one output array) on a
  FIFO, and one thread, `cdt-device-watch`, waits for each output in
  launch order. The device runs what it is given in order, so the
  moment one program's output is ready is the moment the next starts.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import json
import queue
import threading
import time
import uuid
from typing import Any, Callable, Iterator, Optional

TRACE_HEADER = "X-CDT-Trace-Id"
WATCH_THREAD = "cdt-device-watch"
# the process's own start: `process.start` and its children, and the
# programs built outside any request; kept for the life of the process
STARTUP_TRACE = "startup"

# (trace_id, span_id) of the active span; span_id None = trace joined
# via activate() but no span open yet.
_current: contextvars.ContextVar[Optional[tuple[str, Optional[str]]]] = (
    contextvars.ContextVar("cdt_current_span", default=None)
)

# Span lifecycle listener: the live event bus (telemetry/events.py)
# installs one callback that forwards span open/close as stream events.
_span_listener: Optional[Callable[[str, "Span"], None]] = None


def set_span_listener(fn: Optional[Callable[[str, "Span"], None]]) -> None:
    """Install the (phase, span) lifecycle callback (phase is "open" or
    "close"); None uninstalls. Errors are swallowed."""
    global _span_listener
    _span_listener = fn


# Span mirror: telemetry/profiling.ProfilerCapture installs one while a
# capture is open. Given a span just opened by `Tracer.span`, it returns
# a context manager to hold for the span's life on the same thread (a
# jax.profiler.TraceAnnotation), or None.
_span_annotator: Optional[Callable[["Span"], Any]] = None


def set_span_annotator(fn: Optional[Callable[["Span"], Any]]) -> None:
    """Install the mirror for context-managed spans; None uninstalls.
    Manual start_span/end_span pairs belong to no thread and are never
    mirrored."""
    global _span_annotator
    _span_annotator = fn


def _open_mirror(span: "Span") -> Any:
    """The entered mirror of a span just opened, or None."""
    annotator = _span_annotator
    if annotator is None:
        return None
    try:
        mirror = annotator(span)
        mirror.__enter__()
        return mirror
    except Exception:  # noqa: BLE001 - telemetry must not break tracing
        return None


def _notify_span(phase: str, span: "Span") -> None:
    listener = _span_listener
    if listener is not None:
        try:
            listener(phase, span)
        except Exception:  # noqa: BLE001 - telemetry must not break tracing
            pass


class Span:
    __slots__ = (
        "trace_id", "span_id", "parent_id", "name",
        "start", "end", "attrs", "events", "status",
    )

    def __init__(
        self,
        trace_id: str,
        span_id: str,
        parent_id: Optional[str],
        name: str,
        start: float,
        attrs: Optional[dict[str, Any]] = None,
    ):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.start = start
        self.end: Optional[float] = None
        self.attrs: dict[str, Any] = dict(attrs or {})
        self.events: list[dict[str, Any]] = []
        self.status = "ok"

    @property
    def duration(self) -> Optional[float]:
        return None if self.end is None else self.end - self.start

    def to_dict(self) -> dict[str, Any]:
        # attrs/events are COPIED: callers serialize outside the tracer
        # lock while instrumented code may still be annotating the span
        # (e.g. pull_span.attrs["tile_idx"] = ... after the span ended).
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "start": self.start,
            "end": self.end,
            "duration": self.duration,
            "attrs": dict(self.attrs),
            "events": [dict(e) for e in self.events],
            "status": self.status,
        }


class _Launch:
    """One launched program: its `device.run` span and the output the
    watcher waits for, dropped once it is ready."""

    __slots__ = ("span", "ready")

    def __init__(self, span: Span, ready: Any):
        self.span = span
        self.ready = ready


def _is_ready(ready: Any) -> bool:
    """Whether a launch's output is there already, without waiting."""
    try:
        return bool(ready.is_ready())
    except Exception:  # noqa: BLE001 - a deleted array cannot say
        return False


class Tracer:
    """Thread-safe bounded span store + context management."""

    def __init__(
        self,
        clock: Callable[[], float] = time.monotonic,
        max_traces: int = 256,
        max_spans_per_trace: int = 20000,
    ) -> None:
        self._clock = clock
        self.max_traces = max_traces
        self.max_spans_per_trace = max_spans_per_trace
        self._lock = threading.Lock()
        self._traces: "collections.OrderedDict[str, list[Span]]" = (
            collections.OrderedDict()
        )
        self._roots: dict[str, str] = {}  # trace_id -> root span_id
        # span_id -> Span per trace: O(1) event attachment (trace_info
        # fires per log line; scanning 20k spans under the lock won't do)
        self._by_id: dict[str, dict[str, Span]] = {}
        # device_span: the watcher thread, the FIFO it reads (one a
        # thread, so a stop and a restart never share a sentinel), and
        # the last launches, which device_wait looks through
        self._watch_lock = threading.Lock()
        self._watch_thread: Optional[threading.Thread] = None
        self._launches: "queue.SimpleQueue[Optional[_Launch]]" = queue.SimpleQueue()
        self._recent: "collections.deque[_Launch]" = collections.deque(maxlen=32)

    # --- bookkeeping ------------------------------------------------------

    def _store(self, span: Span) -> None:
        with self._lock:
            spans = self._traces.get(span.trace_id)
            if spans is None:
                spans = []
                self._traces[span.trace_id] = spans
                self._by_id[span.trace_id] = {}
                self._roots.setdefault(span.trace_id, span.span_id)
                while len(self._traces) > self.max_traces:
                    evicted = next(
                        (t for t in self._traces if t != STARTUP_TRACE), None
                    )
                    if evicted is None:
                        break
                    del self._traces[evicted]
                    self._roots.pop(evicted, None)
                    self._by_id.pop(evicted, None)
            else:
                # LRU, not insertion order: a long execution keeps
                # appending spans, so it stays most-recent and a burst
                # of short traces (or hostile trace-id headers on the
                # open RPC surface) evicts idle history instead of the
                # in-flight tree.
                self._traces.move_to_end(span.trace_id)
            if len(spans) < self.max_spans_per_trace:
                spans.append(span)
                self._by_id[span.trace_id][span.span_id] = span

    def root_span_id(self, trace_id: str) -> Optional[str]:
        with self._lock:
            return self._roots.get(trace_id)

    def now(self) -> float:
        """The clock every span of this tracer is stamped with."""
        return self._clock()

    # --- context ----------------------------------------------------------

    def activate(
        self, trace_id: str, span_id: Optional[str] = None
    ) -> contextvars.Token:
        """Join `trace_id` in the current context (thread); new spans
        with no active parent attach to `span_id` (a span another
        thread opened, or one started with `start_span`), else to the
        trace's root. Returns a token for `deactivate`."""
        return _current.set((trace_id, span_id))

    def deactivate(self, token: contextvars.Token) -> None:
        _current.reset(token)

    def current_trace_id(self) -> Optional[str]:
        state = _current.get()
        return state[0] if state else None

    def current_span_id(self) -> Optional[str]:
        state = _current.get()
        return state[1] if state else None

    # --- span lifecycle ---------------------------------------------------

    def start_span(
        self,
        name: str,
        trace_id: Optional[str] = None,
        parent_id: Optional[str] = None,
        attrs: Optional[dict[str, Any]] = None,
        start: Optional[float] = None,
    ) -> Span:
        """Manual span start (no context mutation); pair with
        `end_span`. Parent resolution: explicit parent_id → active span
        (same trace) → the trace's root span. `start` is an earlier
        reading of `now()`, for a span whose trace was not known when
        it began."""
        state = _current.get()
        if trace_id is None:
            if state is None:
                trace_id = f"trace_{uuid.uuid4().hex[:12]}"
            else:
                trace_id = state[0]
        if parent_id is None:
            if state is not None and state[0] == trace_id and state[1] is not None:
                parent_id = state[1]
            else:
                root = self.root_span_id(trace_id)
                parent_id = root  # None for the first span of a trace
        span = Span(
            trace_id=trace_id,
            span_id=uuid.uuid4().hex[:16],
            parent_id=parent_id,
            name=name,
            start=self._clock() if start is None else start,
            attrs=attrs,
        )
        self._store(span)
        _notify_span("open", span)
        return span

    def end_span(
        self, span: Span, status: str = "ok", end: Optional[float] = None
    ) -> None:
        """`end` is an earlier reading of `now()`, for a span that is
        stamped with what happened up to its end before it is closed."""
        if span.end is None:
            span.end = self._clock() if end is None else end
            # preserve a status the body set explicitly (e.g. a span
            # whose failure is swallowed by a best-effort except arm)
            if span.status == "ok":
                span.status = status
            _notify_span("close", span)

    def record_span(
        self,
        name: str,
        start: float,
        end: float,
        trace_id: Optional[str] = None,
        parent_id: Optional[str] = None,
        attrs: Optional[dict[str, Any]] = None,
    ) -> Span:
        """Store a span that is over: `start` and `end` are readings of
        `now()` taken by whoever watched the work. Parented as
        `start_span` parents (explicit parent, else the active span of
        the calling thread, else the trace's root); never the active
        span itself, and mirrored into no capture."""
        span = self.start_span(name, trace_id, parent_id, attrs, start=start)
        span.end = end
        _notify_span("close", span)
        return span

    @contextlib.contextmanager
    def span(
        self,
        name: str,
        trace_id: Optional[str] = None,
        parent_id: Optional[str] = None,
        **attrs: Any,
    ) -> Iterator[Span]:
        """Context-managed span that becomes the active span for
        nesting; exceptions mark the span status 'error' and re-raise."""
        span = self.start_span(name, trace_id, parent_id, attrs)
        token = _current.set((span.trace_id, span.span_id))
        mirror = _open_mirror(span)
        try:
            yield span
        except BaseException as exc:
            span.attrs.setdefault("error", f"{type(exc).__name__}: {exc}")
            self.end_span(span, status="error")
            raise
        else:
            self.end_span(span)
        finally:
            if mirror is not None:
                mirror.__exit__(None, None, None)
            _current.reset(token)

    # --- what the device did ----------------------------------------------

    def device_span(self, program: str, ready: Any, **attrs: Any) -> Optional[Span]:
        """Call on the thread that launched `program`, right after the
        launch returned, with one output array of it as `ready` (never
        one a later program takes by donation). Opens `device.run`
        under the active span and hands it to the watcher thread, which
        ends it when `ready` is: `begin` (when the device could start
        it: the later of this call and the previous launch's end),
        `queued_s` (how long it stood behind that launch),
        `idle_before_s` (how long the device had had nothing when this
        call came; one of the two is 0), `busy_s`. Waits for nothing
        here; outside a trace it does nothing."""
        if _current.get() is None:
            return None
        span = self.start_span("device.run", attrs={"program": program, **attrs})
        launch = _Launch(span, ready)
        with self._watch_lock:
            if self._watch_thread is None:
                self._launches = queue.SimpleQueue()
                self._watch_thread = threading.Thread(
                    target=self._watch, args=(self._launches,), name=WATCH_THREAD,
                    daemon=True,
                )
                self._watch_thread.start()
            self._recent.append(launch)
            self._launches.put(launch)
        return span

    def _watch(self, launches: "queue.SimpleQueue[Optional[_Launch]]") -> None:
        """The watcher thread: each launch's output in launch order."""
        from .instruments import device_busy_seconds_total

        last_end: Optional[float] = None
        while True:
            launch = launches.get()
            if launch is None:
                return
            span, error = launch.span, None
            program = span.attrs.get("program")
            # only while a capture mirrors it: its annotation on this
            # thread's line is what ties a `device.run` to the program
            # on the device's own line
            watch = self.span(
                "device.watch", trace_id=span.trace_id, parent_id=span.span_id,
                program=program,
            ) if _span_annotator is not None else contextlib.nullcontext()
            with watch:
                try:
                    launch.ready.block_until_ready()
                except Exception as exc:  # noqa: BLE001 - deleted, or the program failed
                    error = f"{type(exc).__name__}: {exc}"
                span.end = self._clock()
                launch.ready = None
            if error is None:
                begin = span.start if last_end is None else max(span.start, last_end)
                idle_before_s = 0.0 if last_end is None else begin - last_end
                last_end = span.end
                busy_s = span.end - begin
                span.attrs.update(
                    begin=begin, queued_s=begin - span.start,
                    idle_before_s=idle_before_s, busy_s=busy_s,
                )
                device_busy_seconds_total().inc(max(0.0, busy_s), program=str(program))
            else:
                # says nothing of when the device was free: the next
                # launch keeps the last good end
                span.status = "error"
                span.attrs["error"] = error
            _notify_span("close", span)

    def stop_device_watch(self, timeout: Optional[float] = None) -> None:
        """End the watcher thread once it has seen every launch so far
        to its end; the next `device_span` starts another."""
        with self._watch_lock:
            thread, self._watch_thread = self._watch_thread, None
            if thread is not None:
                self._launches.put(None)
        if thread is not None:
            thread.join(timeout)

    @contextlib.contextmanager
    def device_wait(self, **attrs: Any) -> Iterator[Span]:
        """The `device.wait` span around a read-back. On the way out it
        gains `after_ready_s`: its end less the end of the last
        `device.run` of its trace that had ended by then, which is what
        the read-back itself cost once the device was done."""
        with self.span("device.wait", **attrs) as wait:
            yield wait
        # after the span has ended, so that it ends where it always did
        with self._watch_lock:
            mine = [l for l in self._recent if l.span.trace_id == wait.trace_id]
        for launch in reversed(mine):
            # in this order: the watcher stamps `end`, then drops `ready`
            ready, end = launch.ready, launch.span.end
            if launch.span.status == "error":
                continue
            if end is not None and end <= wait.end:
                wait.attrs["after_ready_s"] = wait.end - end
            elif end is not None or _is_ready(ready):
                # there, and the watcher had not stamped it by then
                wait.attrs["after_ready_s"] = 0.0
            else:
                continue  # still running: the wait was for an earlier one
            break

    def _active_span(self) -> Optional[Span]:
        """The active span, falling back to the active trace's root
        span; None outside a trace."""
        state = _current.get()
        if state is None:
            return None
        trace_id, span_id = state
        target = span_id or self.root_span_id(trace_id)
        if target is None:
            return None
        with self._lock:
            return self._by_id.get(trace_id, {}).get(target)

    def event(self, name: str, **attrs: Any) -> None:
        """Attach a point-in-time event to the active span, falling
        back to the active trace's root span; no-op outside a trace."""
        span = self._active_span()
        if span is not None and len(span.events) < 1000:
            span.events.append({"name": name, "ts": self._clock(), "attrs": attrs})

    def annotate(self, **attrs: Any) -> None:
        """Set attributes on the active span from code that did not
        open it (a node inside the executor's `node.*` span); no-op
        outside a trace."""
        span = self._active_span()
        if span is not None:
            span.attrs.update(attrs)

    # --- export -----------------------------------------------------------

    def trace_ids(self) -> list[str]:
        with self._lock:
            return list(self._traces)

    def spans(self, trace_id: str) -> list[dict[str, Any]]:
        with self._lock:
            return [s.to_dict() for s in self._traces.get(trace_id, [])]

    def tree(
        self,
        trace_id: str,
        spans: Optional[list[dict[str, Any]]] = None,
    ) -> list[dict[str, Any]]:
        """Span forest for one trace: each node is the span dict plus
        'children', ordered by start time. Spans whose parent is
        missing (evicted / foreign) surface as extra roots. Pass an
        already-fetched `spans` list to avoid re-copying a large trace
        under the lock (and to keep the tree consistent with it)."""
        if spans is None:
            spans = self.spans(trace_id)
        nodes = {s["span_id"]: {**s, "children": []} for s in spans}
        roots: list[dict[str, Any]] = []
        for node in nodes.values():
            parent = nodes.get(node["parent_id"]) if node["parent_id"] else None
            if parent is not None and parent is not node:
                parent["children"].append(node)
            else:
                roots.append(node)
        def sort_rec(items: list[dict[str, Any]]) -> None:
            items.sort(key=lambda n: (n["start"], n["span_id"]))
            for item in items:
                sort_rec(item["children"])
        sort_rec(roots)
        return roots

    def write_jsonl(
        self, trace_id: "str | list[str] | None", path: str
    ) -> int:
        """Export one span per line: of one trace, of the traces
        listed, or (None) of every trace held, in storage order.
        Returns the number written."""
        if trace_id is None:
            trace_id = self.trace_ids()
        wanted = [trace_id] if isinstance(trace_id, str) else list(trace_id)
        written = 0
        with open(path, "w", encoding="utf-8") as fh:
            for one in wanted:
                for span in self.spans(one):
                    fh.write(json.dumps(span, sort_keys=True, default=str) + "\n")
                    written += 1
        return written

    def reset(self) -> None:
        with self._lock:
            self._traces.clear()
            self._roots.clear()
            self._by_id.clear()
        with self._watch_lock:
            self._recent.clear()


# --- global tracer --------------------------------------------------------

_tracer: Tracer | None = None
_tracer_lock = threading.Lock()


def get_tracer() -> Tracer:
    global _tracer
    with _tracer_lock:
        if _tracer is None:
            _tracer = Tracer()
        return _tracer


def set_tracer(tracer: Optional[Tracer]) -> None:
    """Install a specific tracer (chaos harness: fake clock)."""
    global _tracer
    with _tracer_lock:
        _tracer = tracer


def reset_tracer() -> None:
    """Drop the global tracer (tests)."""
    set_tracer(None)


def current_trace_id() -> Optional[str]:
    """Module-level convenience for transport code building headers."""
    state = _current.get()
    return state[0] if state else None
