"""DistributedServer: the runtime hub of one master/worker process.

Owns what the reference borrows from ComfyUI's PromptServer (reference
SURVEY: queues/locks monkey-patched onto server.PromptServer.instance):

- the aiohttp application with /prompt + /distributed/* routes,
- the prompt queue, consumed by a dedicated executor thread running
  GraphExecutor (compute never blocks the loop),
- the JobStore (collector queues, tile jobs),
- role identity (master vs worker, from env or constructor).

The same server runs on master and workers; role is decided per-prompt
by the hidden inputs injected during prompt rewriting, exactly like
the reference (reference distributed.py:48, prompt_transform.py).
"""

from __future__ import annotations

import asyncio
import contextlib
import functools
import os
import queue as thread_queue
import threading
from typing import Any, Optional

from aiohttp import web

from ..graph import ExecutionContext, GraphExecutor
from ..jobs import JobStore
from ..utils import config as config_mod
from ..utils.async_helpers import set_server_loop
from ..utils.constants import DEFAULT_MASTER_PORT, WORKER_ENV_FLAG
from ..utils.exceptions import PromptValidationError
from ..utils.logging import debug_log, log


class PromptJob:
    def __init__(
        self,
        prompt_id: str,
        prompt: dict,
        extra: dict | None = None,
        trace_id: str | None = None,
    ):
        self.prompt_id = prompt_id
        self.prompt = prompt
        self.extra = extra or {}
        # Execution joins this trace (master queue / propagated via the
        # X-CDT-Trace-Id dispatch header); prompt_id is the fallback so
        # standalone executions still get a span tree.
        self.trace_id = trace_id or prompt_id
        self.done = threading.Event()
        self.outputs: dict[str, Any] | None = None
        self.error: str | None = None
        self.timings: dict[str, float] = {}
        # the prompt_queue.wait span: opened at enqueue, closed by the
        # executor thread when it takes the job
        self.queue_span: Any = None
        # the execute_prompt span: opened by the executor thread when it
        # takes the job, ended with `done` by whichever thread finishes
        # the job's last piece of work
        self.execute_span: Any = None
        # pieces of work `done` waits for: the graph walk, and each save
        # handed to the saver thread
        self.open_work = 0


class SaveThread:
    """Runs the work handed to it one piece at a time, in hand-off
    order, on a thread of its own: the read-back, PNG encode and file
    write of prompt N while the executor thread walks prompt N+1. At
    most one piece waits beside the one running; `submit` blocks beyond
    that, so the executor thread is at most two prompts ahead of the
    disk and a burst holds two images, not a queue of them."""

    def __init__(self) -> None:
        self._queue: "thread_queue.Queue[Any]" = thread_queue.Queue(maxsize=1)
        self._thread: Optional[threading.Thread] = None

    def submit(self, work: Any) -> None:
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, name="cdt-saver", daemon=True
            )
            self._thread.start()
        self._queue.put(work)

    def _loop(self) -> None:
        while True:
            work = self._queue.get()
            if work is None:
                return
            work()

    def join(self) -> None:
        """Run what was submitted to its end and stop the thread. For
        the thread that submits."""
        if self._thread is not None:
            self._queue.put(None)
            self._thread.join()
            self._thread = None


class DistributedServer:
    def __init__(
        self,
        port: int = DEFAULT_MASTER_PORT,
        is_worker: Optional[bool] = None,
        mesh: Any = None,
        config_path: str | None = None,
        host: str | None = None,
        standby_of: str | None = None,
    ):
        self.port = port
        # Default loopback: the /distributed/* surface carries
        # process-launch and config-write endpoints with no auth, so
        # LAN exposure (0.0.0.0) is an explicit opt-in via --host or
        # CDT_HOST (the reference inherits the same default from
        # ComfyUI's --listen behavior)
        self.host = host or os.environ.get("CDT_HOST") or "127.0.0.1"
        self.is_worker = (
            is_worker
            if is_worker is not None
            else os.environ.get(WORKER_ENV_FLAG) == "1"
        )
        self.mesh = mesh
        self.config_path = config_path
        # JobStore picks up the env fault plan (CDT_FAULT_PLAN) so chaos
        # runs can script store-level faults; None in normal operation.
        from ..resilience import bind_quarantine_requeue, get_fault_injector
        from ..resilience.health import get_health_registry

        self.job_store = JobStore(fault_injector=get_fault_injector())
        # Circuit breaker → job store: a quarantined worker's in-flight
        # tiles go straight back to the pending queue.
        self._unbind_health = bind_quarantine_requeue(
            get_health_registry(), self.job_store
        )
        # Straggler & stall watchdog: consumes the store's per-worker
        # pull→submit latencies, pushes stragglers into the breaker as
        # SUSPECT, and speculatively re-enqueues stalled in-flight
        # tiles. CDT_WATCHDOG=0 disables it COMPLETELY — no latency
        # sink, no thread, no final verdict pass on stop — so an
        # operator who opted out (e.g. a legitimately heterogeneous
        # fleet) never sees watchdog-driven suspect transitions. The
        # object always exists so routes/tests can inspect it.
        from ..telemetry import Watchdog

        self._watchdog_enabled = os.environ.get("CDT_WATCHDOG", "1") != "0"
        self.watchdog = Watchdog(
            store=self.job_store, health=get_health_registry()
        )
        # Scheduler control plane: admission lanes + fair share sit in
        # front of orchestration (job_routes.queue gates on it), and
        # the placement policy steers the job store's pull path —
        # speed-weighted batches, tail trimming. Both consume the
        # store's pull→submit latency stream, so the sink fans out.
        from ..scheduler import SchedulerControl

        self.scheduler = SchedulerControl(health=get_health_registry())
        self.job_store.placement = self.scheduler.placement
        # Step-level preemption coordinator (scheduler/preempt.py):
        # ranks jobs by the admission queue's lane order; a premium
        # arrival flags running lower-lane jobs for step-boundary
        # eviction, and brownout escalation can evict shed lanes'
        # running work (CDT_PREEMPT_BROWNOUT_LEVEL). All seams are
        # advisory: with CDT_PREEMPT=0 or single-lane traffic this is
        # inert.
        from ..scheduler.preempt import PreemptionCoordinator

        self.preempt = PreemptionCoordinator(
            self.scheduler.queue.lane_order, self.job_store
        )
        self.job_store.preempt_policy = self.preempt

        def _brownout_evict(level: int, shed_lanes: list) -> None:
            # evaluate() runs on the server loop (admission path);
            # schedule the eviction sweep without blocking admission
            import asyncio as _asyncio

            with contextlib.suppress(RuntimeError):
                _asyncio.get_running_loop().create_task(
                    self.preempt.on_brownout(level, shed_lanes)
                )

        self.scheduler.brownout.preempt_hook = _brownout_evict
        # Poison pardon: when a tile is quarantined after exhausting
        # its attempt budget, the workers whose crashes were charged to
        # it leave the circuit breaker — one bad payload must not
        # cascade worker quarantines across the fleet.
        def _poison_pardon(worker_ids: list) -> None:
            registry = get_health_registry()
            for wid in worker_ids:
                registry.pardon(str(wid))

        self.job_store.poison_pardon = _poison_pardon
        sinks = [self.scheduler.placement.record_latency]
        if self._watchdog_enabled:
            sinks.append(self.watchdog.record_latency)

        def _latency_fan_out(worker_id: str, seconds: float) -> None:
            for sink in sinks:
                sink(worker_id, seconds)

        self.job_store.latency_sink = _latency_fan_out
        # admission-gap accounting: every cache settle tells the DRR
        # scheduler how much admitted cost never burned chip time
        # (surfaced as cdt_cache_unsettled_admission_cost at scrape)
        self.job_store.settle_sink = self.scheduler.note_cache_settled
        # Fleet observability plane (telemetry/fleet.py + slo.py):
        # masters aggregate worker snapshots piggybacked on the
        # heartbeat/request_image RPCs, retain the load-bearing series,
        # and evaluate burn-rate SLO alerts. CDT_FLEET=0 disables the
        # whole plane (routes answer enabled=false).
        from ..telemetry import FleetMonitor, FleetRegistry, SLOEngine
        from ..utils.constants import FLEET_ENABLED

        self.fleet: Optional[FleetRegistry] = None
        self.slo: Optional[SLOEngine] = None
        self._fleet_monitor: Optional[FleetMonitor] = None
        if FLEET_ENABLED and not self.is_worker:
            self.slo = SLOEngine()
            self.fleet = FleetRegistry()
            self.fleet.bind_master(
                scheduler=self.scheduler,
                job_store=self.job_store,
                slo=self.slo,
            )
            self._fleet_monitor = FleetMonitor(self.fleet, slo=self.slo)
            # tile pull→submit latencies feed the latency SLO through
            # the same fan-out the watchdog and placement consume
            slo_engine = self.slo
            sinks.append(
                lambda _wid, sec: slo_engine.note_latency(
                    "tile_latency", sec
                )
            )
            # departed-worker eviction: when placement or the breaker
            # registry forgets a worker, its fleet series depart too
            self.scheduler.placement.on_forget = self.fleet.forget_worker
            get_health_registry().on_forget = self.fleet.forget_worker
            # measured-cost admission (CDT_USAGE_COST=1): DRR cost
            # multiplies by the tenant's metered chip-s-per-tile ratio
            if self.fleet.usage is not None:
                self.scheduler.usage_cost = self.fleet.usage.cost_ratio
        # Region control plane (scheduler/router.py + autoscale.py):
        # CDT_SHARDS gives this master the job→shard map the region
        # route serves (workers compute the same map from the same
        # spec — consistent hashing needs no coordination), and
        # CDT_AUTOSCALE=1 starts the usage-driven scale loop: SLO burn
        # alerts + metered chip-second demand in, managed-worker
        # launches / SIGTERM drains out, every decision recorded with
        # its measured chip-second cost/benefit.
        from ..scheduler.autoscale import (
            AutoscaleController,
            managed_worker_actuators,
        )
        from ..scheduler.router import ShardRouter
        from ..utils.constants import AUTOSCALE_ENABLED

        self.router: Optional[ShardRouter] = None
        self.autoscale: Optional[AutoscaleController] = None
        if not self.is_worker:
            self.router = ShardRouter.from_env()
            if AUTOSCALE_ENABLED:
                launcher, drainer, capacity_fn = managed_worker_actuators(
                    self.config_path
                )
                self.autoscale = AutoscaleController(
                    slo=self.slo,
                    usage=self.fleet.usage if self.fleet is not None else None,
                    launcher=launcher,
                    drainer=drainer,
                    capacity_fn=capacity_fn,
                )
        # Durable control plane (durability/): enabled by setting
        # CDT_JOURNAL_DIR on a master. Construction is cheap and
        # file-free; recovery + the write-ahead seam attach in start(),
        # BEFORE the HTTP listener and executor thread exist, so no
        # mutation can race the replay. Workers never journal — the
        # master's store is the single source of coordination truth.
        from ..durability import DurabilityManager, journal_dir_from_env

        self.durability: Optional[DurabilityManager] = None
        journal_dir = journal_dir_from_env()
        if journal_dir and not self.is_worker:
            self.durability = DurabilityManager(
                journal_dir, scheduler=self.scheduler
            )
            # journal-append latency is the brownout controller's
            # second overload signal (a saturated fsync path sheds
            # low-priority lanes before the master tips over) — and the
            # journal-latency SLO's sample stream when the fleet plane
            # is on
            journal_sinks = [self.scheduler.brownout.note_journal_append]
            if self.slo is not None:
                slo_engine = self.slo
                journal_sinks.append(
                    lambda sec: slo_engine.note_latency(
                        "journal_latency", sec
                    )
                )

            def _journal_latency_fan_out(seconds: float) -> None:
                for sink in journal_sinks:
                    sink(seconds)

            self.durability.append_latency_sink = _journal_latency_fan_out
        # Incident plane (telemetry/flight.py + telemetry/incidents.py):
        # the always-on flight recorder taps the process bus so the
        # last window of events/spans is in memory when something
        # breaks (CDT_FLIGHT=0 opts out); masters with CDT_INCIDENT_DIR
        # set get an IncidentManager that captures debug bundles on
        # alert_fired / poison quarantine / deadline expiry / failover
        # (and POST .../capture), debounced + rate-limited + retained
        # under bounded disk. Constructed AFTER the durability manager
        # so bind_server wires the durability status source (the
        # bundle's role/epoch/journal section on journaling masters).
        # Trigger tap + writer thread attach in start(), detach in
        # stop().
        from ..telemetry import IncidentManager, get_flight_recorder
        from ..utils.constants import incident_dir_from_env

        self.flight = get_flight_recorder()
        self.incidents: Optional[IncidentManager] = None
        incident_dir = incident_dir_from_env()
        if incident_dir and not self.is_worker:
            self.incidents = IncidentManager(incident_dir)
            self.incidents.bind_server(self)
        # Warm-standby mode (--standby / CDT_STANDBY_OF): this master
        # tails the active's journal stream instead of recovering from
        # disk, and promotes itself when the active's lease expires
        # (api/standby.py). Requires the journal dir — the lease file
        # is the takeover arbitration medium and the promoted standby
        # journals into the same directory.
        from .standby import StandbyController

        self.standby: Optional[StandbyController] = None
        standby_of = standby_of or os.environ.get("CDT_STANDBY_OF", "").strip()
        if standby_of and not self.is_worker:
            if self.durability is None:
                raise ValueError(
                    "standby mode requires CDT_JOURNAL_DIR (the lease "
                    "file and post-promotion journal live there)"
                )
            self.standby = StandbyController(
                self, standby_of, journal_dir
            )
        # Lease renewal task handle (active masters with journaling);
        # `deposed` flips when a standby takes the lease from under us
        # (status surfaces report it; the journal seam enforces it).
        self._lease_task: Optional[asyncio.Task] = None
        self.deposed = False
        # Open replication WebSockets (standbys tailing our journal):
        # closed explicitly in stop() so a parked stream can't hold the
        # runner's graceful shutdown for its full timeout.
        self.replication_sockets: set = set()
        # Live-state gauge collectors are bound in start() — a server
        # constructed but never started must not leave a collector
        # (holding a strong reference to it) in the global registry.
        self._unbind_telemetry: Any = lambda: None
        self.app = web.Application(client_max_size=256 * 1024 * 1024)
        self.loop: Optional[asyncio.AbstractEventLoop] = None
        self._runner: Optional[web.AppRunner] = None
        self._site: Optional[web.TCPSite] = None

        self._prompt_queue: "thread_queue.Queue[Optional[PromptJob]]" = (
            thread_queue.Queue()
        )
        # set while a prompt is taken and not done (walking its graph, or
        # its images not yet on disk)
        self._executing = threading.Event()
        self._executor_thread: Optional[threading.Thread] = None
        self._saver = SaveThread()
        # under _jobs_lock: prompts taken and not done; prompts ever
        # taken (a save is overlapped when this moves before it ends);
        # saves handed to the saver and not yet on disk; those of them
        # whose read-back has not ended (a walk that begins meanwhile
        # is ahead: the device may still run the earlier prompt)
        self._jobs_lock = threading.Lock()
        self._unfinished = 0
        self._taken = 0
        self.saves_pending = 0
        self._reading = 0
        self._history: dict[str, PromptJob] = {}
        self._interrupt = threading.Event()
        self.execution_context = ExecutionContext(mesh=mesh)

        self._register_routes()

    # --- config ----------------------------------------------------------

    @property
    def config(self) -> dict[str, Any]:
        return config_mod.load_config(self.config_path)

    @property
    def log_buffer(self) -> list[str]:
        from ..utils.logging import LOG_RING

        return list(LOG_RING)

    # --- routes ----------------------------------------------------------

    def _register_routes(self) -> None:
        from . import (
            config_routes,
            incident_routes,
            job_routes,
            profile_routes,
            region_routes,
            replication_routes,
            scheduler_routes,
            telemetry_routes,
            tunnel_routes,
            usdu_routes,
            web_routes,
            worker_routes,
        )

        self.app.router.add_get("/prompt", self.handle_get_prompt)
        self.app.router.add_post("/prompt", self.handle_post_prompt)
        self.app.router.add_post("/interrupt", self.handle_interrupt)
        self.app.router.add_get("/history/{prompt_id}", self.handle_history)
        job_routes.register(self.app, self)
        scheduler_routes.register(self.app, self)
        telemetry_routes.register(self.app, self)
        incident_routes.register(self.app, self)
        profile_routes.register(self.app, self)
        usdu_routes.register(self.app, self)
        config_routes.register(self.app, self)
        worker_routes.register(self.app, self)
        tunnel_routes.register(self.app, self)
        web_routes.register(self.app, self)
        replication_routes.register(self.app, self)
        region_routes.register(self.app, self)

    # --- prompt queue ----------------------------------------------------

    @property
    def queue_remaining(self) -> int:
        """Prompts queued, executing, or executed with an image not yet
        on disk."""
        return self._prompt_queue.qsize() + self._unfinished

    async def handle_get_prompt(self, request: web.Request) -> web.Response:
        # ComfyUI-compatible probe shape (reference utils/network.py:108-136
        # reads exec_info.queue_remaining as the busy-ness metric).
        return web.json_response(
            {"exec_info": {"queue_remaining": self.queue_remaining}}
        )

    async def handle_post_prompt(self, request: web.Request) -> web.Response:
        try:
            body = await request.json()
        except Exception:
            return web.json_response({"error": "invalid json"}, status=400)
        prompt = body.get("prompt")
        if not isinstance(prompt, dict):
            return web.json_response({"error": "missing prompt"}, status=400)
        prompt_id = body.get("prompt_id") or f"prompt_{len(self._history)}_{os.getpid()}"
        from ..telemetry import TRACE_HEADER

        trace_id = request.headers.get(TRACE_HEADER) or None
        try:
            job = self.queue_prompt(
                prompt, prompt_id, body.get("extra_data"), trace_id=trace_id
            )
        except PromptValidationError as exc:
            return web.json_response(
                {"error": str(exc), "node_errors": exc.node_errors}, status=400
            )
        return web.json_response({"prompt_id": job.prompt_id, "number": 0})

    async def handle_interrupt(self, request: web.Request) -> web.Response:
        self.interrupt()
        return web.json_response({"interrupted": True})

    async def handle_history(self, request: web.Request) -> web.Response:
        prompt_id = request.match_info["prompt_id"]
        job = self._history.get(prompt_id)
        if job is None:
            return web.json_response({}, status=404)
        return web.json_response(
            {
                "prompt_id": prompt_id,
                "done": job.done.is_set(),
                "error": job.error,
                "outputs": _jsonable_outputs(job.outputs),
                "timings": job.timings,
            }
        )

    def queue_prompt(
        self,
        prompt: dict,
        prompt_id: str,
        extra: dict | None = None,
        trace_id: str | None = None,
    ) -> PromptJob:
        """Validate then enqueue (reference utils/async_helpers.py
        queue_prompt_payload contract: validation errors surface to the
        caller, not the executor).

        Idempotent per prompt_id: a retried dispatch whose first
        delivery actually landed (connection died after the request
        arrived), or a WS delivery followed by the HTTP fallback, must
        not execute the same prompt twice."""
        existing = self._history.get(prompt_id)
        if existing is not None:
            debug_log(f"prompt {prompt_id} already queued; duplicate dropped")
            return existing
        from ..graph import validate_prompt

        validate_prompt(prompt)
        job = PromptJob(prompt_id, prompt, extra, trace_id=trace_id)
        self._history[prompt_id] = job
        from ..telemetry import get_tracer

        job.queue_span = get_tracer().start_span(
            "prompt_queue.wait",
            trace_id=job.trace_id,
            attrs={"depth": self._prompt_queue.qsize()},
        )
        self._prompt_queue.put(job)
        return job

    def interrupt(self) -> None:
        self._interrupt.set()
        self.execution_context.interrupt_event.set()

    # --- executor thread --------------------------------------------------

    def _executor_loop(self) -> None:
        """Walk one prompt's graph at a time on this thread. A job is
        done when its walk and every save it handed off have ended.

        The thread waits for the device only where a node has to see a
        value (`TextGenerate`'s ids): the image's read-back is the
        saver thread's, so this thread takes prompt N+1 as soon as N's
        last program is launched, and N+1's programs queue on the
        device behind N's. `SaveThread`'s bound (one piece running, one
        waiting) keeps it at most two prompts ahead of the disk.

        The walk stays in this function's frame, with no closure in
        it: the traced programs record the stack they were built
        under, and a frame more between here and `execute` cost every
        cell's loader 15-19 % (`lower_s` 15.3 -> 23.5 s on SD1.5;
        PERF.md, PR 31)."""
        from ..telemetry import get_tracer

        # the tracer's clock when this thread came back from a job
        came_back: Optional[float] = None
        while True:
            idle = self._prompt_queue.empty()
            job = self._prompt_queue.get()
            if job is None:
                # no save outlives the loop
                self._saver.join()
                return
            tracer = get_tracer()
            if came_back is not None:
                # what this thread did between two jobs, in the trace of
                # the one it picks up: with it every instant of the
                # thread between two walks lies in a named span
                tracer.end_span(tracer.start_span(
                    "executor.between_jobs",
                    trace_id=job.trace_id,
                    attrs={"idle": int(idle)},
                    start=came_back,
                ))
            tracer.end_span(job.queue_span)
            ahead = self._job_taken(job)
            self._interrupt.clear()
            ctx = ExecutionContext(
                mesh=self.mesh,
                config=self.config,
                server=self,
                interrupt_event=self._interrupt,
                pipelines=self.execution_context.pipelines,
                extras=self.execution_context.extras,  # node cache persists
                defer=functools.partial(self._defer, job),
            )
            debug_log(f"executing prompt {job.prompt_id}")
            # A manual pair: the saver thread may be the one to end it.
            # From pick-up to the job's last byte on disk.
            span = job.execute_span = tracer.start_span(
                "execute_prompt",
                trace_id=job.trace_id,
                attrs={
                    "prompt_id": job.prompt_id,
                    "role": "worker" if self.is_worker else "master",
                    "ahead": ahead,
                },
            )
            # The compute thread joins the prompt's trace under that
            # span, so every span opened during execution (nodes, tile
            # pulls, sampler stages) attaches to the execution's tree.
            token = tracer.activate(job.trace_id, span.span_id)
            try:
                executor = GraphExecutor(ctx)
                job.outputs = executor.execute(job.prompt)
                job.timings = executor.last_timings
                span.attrs["nodes_run"] = executor.nodes_run
                span.attrs["nodes_cached"] = executor.nodes_cached
            except Exception as exc:  # noqa: BLE001 - reported to client
                self._fail(job, exc)
            finally:
                tracer.deactivate(token)
                self._work_ended(job)
                came_back = tracer.now()

    def _job_taken(self, job: PromptJob) -> int:
        """Count the job in; 1 when its walk begins ahead of an earlier
        prompt's read-back, else 0."""
        from ..telemetry.instruments import walks_total

        with self._jobs_lock:
            job.open_work += 1
            self._unfinished += 1
            self._taken += 1
            self._executing.set()
            ahead = int(self._reading > 0)
        walks_total().inc(ahead=str(ahead))
        return ahead

    def _defer(self, job: PromptJob, work: Any) -> None:
        """`ExecutionContext.defer` of a served prompt, called on the
        executor thread from inside a node: run `work(overlapped,
        landed)` on the saver thread, in the job's trace under the span
        active here. `work` calls `landed()` when its read-back has
        ended. Blocks while a save runs and another waits."""
        from ..telemetry import get_tracer

        tracer = get_tracer()
        parent_id = tracer.current_span_id()
        with self._jobs_lock:
            job.open_work += 1
            self.saves_pending += 1
            self._reading += 1
            taken = self._taken

        @functools.cache  # once: by `work`, or below where its read-back raised
        def landed() -> None:
            with self._jobs_lock:
                self._reading -= 1

        def run() -> None:
            token = tracer.activate(job.trace_id, parent_id)
            try:
                work(lambda: self._taken > taken, landed)
            except Exception as exc:  # noqa: BLE001 - reported to client
                self._fail(job, exc)
            finally:
                tracer.deactivate(token)
                landed()
                with self._jobs_lock:
                    self.saves_pending -= 1
                self._work_ended(job)

        self._saver.submit(run)

    def _fail(self, job: PromptJob, exc: Exception) -> None:
        if job.error is None:
            job.error = f"{type(exc).__name__}: {exc}"
        log(f"prompt {job.prompt_id} failed: {type(exc).__name__}: {exc}")

    def _work_ended(self, job: PromptJob) -> None:
        """One piece of the job's work ended, on either thread; the last
        one stamps the job's record on `execute_prompt`, ends it and
        sets `done`."""
        from ..telemetry import get_tracer
        from ..telemetry.job_record import stamp_job

        with self._jobs_lock:
            job.open_work -= 1
            if job.open_work:
                return
        tracer, span = get_tracer(), job.execute_span
        if job.error is not None:
            span.attrs.setdefault("error", job.error)
        end = tracer.now()
        try:
            stamp_job(tracer, span, end)
        except Exception as exc:  # noqa: BLE001 - telemetry must not fail the job
            debug_log(f"job record for {job.prompt_id} failed: {exc}")
        tracer.end_span(span, status="ok" if job.error is None else "error", end=end)
        self._export_trace(job.trace_id)
        with self._jobs_lock:
            self._unfinished -= 1
            if not self._unfinished:
                self._executing.clear()
        job.done.set()

    def _export_trace(self, trace_id: str) -> None:
        """Write the trace's spans as JSONL when CDT_TRACE_EXPORT_DIR is
        set (one file per execution per process — a master and a
        co-hosted managed worker share the inherited dir, so the role
        and pid keep their exports from overwriting each other;
        `cat <trace>.*.jsonl | perf_report /dev/stdin` merges them)."""
        export_dir = os.environ.get("CDT_TRACE_EXPORT_DIR")
        if not export_dir:
            return
        from ..telemetry import get_tracer

        try:
            os.makedirs(export_dir, exist_ok=True)
            safe = "".join(c if c.isalnum() or c in "._-" else "_" for c in trace_id)
            role = "worker" if self.is_worker else "master"
            get_tracer().write_jsonl(
                trace_id,
                os.path.join(export_dir, f"{safe}.{role}-{os.getpid()}.jsonl"),
            )
        except Exception as exc:  # noqa: BLE001 - export is best effort
            debug_log(f"trace export for {trace_id} failed: {exc}")

    # --- lifecycle --------------------------------------------------------

    async def start(self) -> None:
        """Start HTTP listener + executor thread on the running loop."""
        self.loop = asyncio.get_running_loop()
        set_server_loop(self.loop)
        # Push-mode grants (CDT_PUSH_GRANTS): the placement policy
        # publishes grant_available events on every pending-queue
        # refill so workers parked on /distributed/events wake
        # immediately instead of pull-polling.
        from ..utils.constants import PUSH_GRANTS_ENABLED

        if PUSH_GRANTS_ENABLED and not self.is_worker:
            self.job_store.grant_notifier = self.scheduler.placement.notify_grants
        if self.standby is not None:
            # Warm standby: no disk recovery, no journal seam — follow
            # the active's replication stream and hold admission closed
            # until promotion (usdu routes answer 503 meanwhile).
            try:
                self.scheduler.pause()
            except Exception as exc:  # noqa: BLE001 - advisory
                log(f"standby: scheduler pause failed: {exc}")
            self.standby.start()
        elif self.durability is not None:
            # Active master: take the lease FIRST (epoch+1; the newest
            # claimant on the journal dir always wins — a deposed
            # holder is fenced by the epoch bump), then crash recovery:
            # replay snapshot + WAL tail into the job store (in-flight
            # tiles requeue, durable results restore), then attach the
            # write-ahead seam so every transition from here on is
            # journaled before it is acknowledged. Admission lanes come
            # back PAUSED when jobs were recovered and resume on the
            # first worker heartbeat (durability/recovery.py).
            # CDT_LEASE_PEERS swaps the arbitration medium: a quorum
            # of off-node peer registers instead of a flock'd file on
            # a shared filesystem — same interface, same epoch fencing,
            # same FencedOut seam downstream.
            from ..durability import Lease, quorum_lease_from_env

            owner = f"master:{self.host}:{self.port}:{os.getpid()}"
            lease = quorum_lease_from_env(owner) or Lease(
                self.durability.directory, owner=owner
            )
            epoch = await self.loop.run_in_executor(
                None, lambda: lease.acquire(force=True)
            )
            self.durability.lease = lease
            self.durability.recover(self.job_store, scheduler=self.scheduler)
            self.job_store.journal_sink = self.durability.record
            self.job_store.on_worker_seen = self.durability.note_worker_activity
            self.job_store.set_epoch(epoch)
            self._lease_task = self.loop.create_task(
                self._renew_lease_loop(), name="cdt-lease-renew"
            )
        # Live-state gauges (queue depths, breaker states) are filled
        # at /distributed/metrics scrape time from this server.
        from ..telemetry import bind_server_collectors

        self._unbind_telemetry = bind_server_collectors(self)
        if self.incidents is not None:
            # writer thread + trigger tap: alert_fired / quarantine /
            # deadline / failover events become automatic captures
            self.incidents.start()
        if self._watchdog_enabled:
            self.watchdog.start()
        if self._fleet_monitor is not None:
            self._fleet_monitor.start()
        if self.autoscale is not None:
            self.autoscale.start()
        self._executor_thread = threading.Thread(
            target=self._executor_loop, name="cdt-executor", daemon=True
        )
        self._executor_thread.start()
        self._runner = web.AppRunner(self.app)
        await self._runner.setup()
        self._site = web.TCPSite(self._runner, self.host, self.port)
        await self._site.start()
        role = "worker" if self.is_worker else "master"
        if self.standby is not None and not self.standby.promoted:
            role = "standby"
        log(f"{role} server listening on {self.host}:{self.port}")

    # --- lease renewal / promotion ----------------------------------------

    async def _renew_lease_loop(self) -> None:
        """Renew the master lease every ttl/3 (file writes off-loop). On
        ``LeaseLost`` — a standby took over — this master is DEPOSED:
        renewal stops, the flag flips, and the journal seam's
        ``FencedOut`` check guarantees no further mutation can be
        acknowledged (the fencing-token pattern's enforcement point)."""
        from ..durability.lease import LeaseLost

        loop = asyncio.get_running_loop()
        while True:
            manager = self.durability
            lease = manager.lease if manager is not None else None
            if lease is None:
                return
            await asyncio.sleep(max(0.1, lease.ttl / 3.0))
            try:
                await loop.run_in_executor(None, lease.renew)
            except LeaseLost as exc:
                self.deposed = True
                log(
                    f"master DEPOSED: {exc}; journal appends are fenced, "
                    "this process serves no further authoritative writes"
                )
                from ..telemetry.events import get_event_bus

                get_event_bus().publish(
                    "master_deposed", owner=lease.owner, port=self.port
                )
                return
            except Exception as exc:  # noqa: BLE001 - renewal retries
                debug_log(f"lease renewal failed (will retry): {exc}")

    def note_promoted(self, epoch: int) -> None:
        """Called by the StandbyController (on the server loop) right
        after it acquired the lease and adopted the replicated state:
        start renewing the lease like any active master, and release
        the standby-mode admission pause when promotion found nothing
        to hold it for (jobs recovered keep it held until the first
        worker heartbeat, exactly like disk recovery)."""
        if self.loop is not None:
            self._lease_task = self.loop.create_task(
                self._renew_lease_loop(), name="cdt-lease-renew"
            )
        manager = self.durability
        if manager is not None and not manager._admission_held():
            try:
                self.scheduler.resume()
            except Exception as exc:  # noqa: BLE001 - advisory
                log(f"promotion: scheduler resume failed: {exc}")
        log(f"server on {self.host}:{self.port} now ACTIVE (epoch {epoch})")

    async def stop(self) -> None:
        import contextlib

        if self.standby is not None:
            await self.standby.stop()
        for ws in list(self.replication_sockets):
            with contextlib.suppress(Exception):
                await ws.close()
        if self._lease_task is not None:
            self._lease_task.cancel()
            try:
                await self._lease_task
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass
            self._lease_task = None
        # Join the watchdog thread OFF the loop: a speculation pass in
        # flight blocks that thread on a coroutine scheduled on THIS
        # loop, so joining inline would deadlock until the join timeout
        # (the executor keeps the loop free to run the coroutine).
        if self._watchdog_enabled:
            await asyncio.get_running_loop().run_in_executor(
                None, self.watchdog.stop
            )
        if self._fleet_monitor is not None:
            # pure thread join: the monitor's step touches only the
            # series store and the bus (non-blocking), never this loop
            self._fleet_monitor.stop()
        if self.autoscale is not None:
            # off-loop: a step in flight may be mid-drain (stop_worker
            # blocks through the SIGTERM grace window)
            await asyncio.get_running_loop().run_in_executor(
                None, self.autoscale.stop
            )
        if self.incidents is not None:
            # off-loop: stop joins the writer thread, which may be
            # mid-fsync on a capture
            await asyncio.get_running_loop().run_in_executor(
                None, self.incidents.stop
            )
        if self.fleet is not None:
            # global-registry hooks must not outlive this server
            from ..resilience.health import get_health_registry as _ghr

            if _ghr().on_forget == self.fleet.forget_worker:
                _ghr().on_forget = None
        self._unbind_health()
        self._unbind_telemetry()
        self._prompt_queue.put(None)
        if self._runner is not None:
            await self._runner.cleanup()
        if self._executor_thread is not None:
            self._executor_thread.join(timeout=10)
        # after the executor and its saver: nothing launches any more
        from ..telemetry import get_tracer

        get_tracer().stop_device_watch(timeout=10)
        # Journal LAST — after the HTTP listener is down and the
        # executor has drained, so every transition acknowledged during
        # shutdown (late worker RPCs, the in-flight prompt's cleanup)
        # was journaled; detaching earlier would resurrect completed
        # jobs as ghosts on the next boot. Off the loop (close joins
        # the write-behind thread and may fsync) and non-fatal: a
        # deferred write error must not abort shutdown.
        if self.durability is not None:
            self.job_store.journal_sink = None
            self.job_store.on_worker_seen = None
            try:
                await asyncio.get_running_loop().run_in_executor(
                    None, self.durability.close
                )
            except Exception as exc:  # noqa: BLE001 - reported, not fatal
                log(f"durability close failed during shutdown: {exc}")
            # Clean shutdown expires our lease NOW (same epoch) so a
            # standby — or the next restart — takes over immediately
            # instead of waiting out the TTL. No-op if already deposed.
            lease = self.durability.lease
            if lease is not None:
                try:
                    await asyncio.get_running_loop().run_in_executor(
                        None, lease.release
                    )
                except Exception as exc:  # noqa: BLE001 - best effort
                    debug_log(f"lease release failed during shutdown: {exc}")
        if self.loop is not None:
            set_server_loop(None)


def _jsonable_outputs(outputs: dict | None) -> dict:
    if not outputs:
        return {}
    out: dict[str, Any] = {}
    for node_id, result in outputs.items():
        entry: dict[str, Any] = {}
        for item in result if isinstance(result, tuple) else (result,):
            if isinstance(item, dict) and "ui" in item:
                entry.update(item["ui"])
        out[node_id] = entry
    return out
