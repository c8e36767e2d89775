"""In-process chaos harness: the elastic USDU master/worker loop under
a scripted fault plan, CPU-only and hermetic (no sockets, no model).

The harness runs `run_master_elastic` against worker THREADS that pull
from the same JobStore — the production protocol shape (the reference's
fake-comms test pattern) — while a seeded `FaultInjector` kills
workers mid-tile, injects latency, or drops heartbeats on a scripted
schedule. The assertion chaos tests make is strong: the blended output
of a faulted run is BIT-IDENTICAL to the fault-free run.

Two properties make that possible:

1. determinism of the work itself — per-tile noise keys fold the
   global tile index, so a requeued tile reproduces exactly no matter
   which participant re-runs it. The harness stubs the diffusion
   processor with a cheap deterministic op whose outputs are exact
   multiples of 1/255, so the PNG uint8 envelope worker tiles travel
   in is lossless and master-local vs worker-computed tiles are
   bit-equal;
2. determinism of the blend — sequential feathered compositing is
   order-dependent where tiles overlap, and arrival order is a race.
   The harness enables CDT_DETERMINISTIC_BLEND (sorted-order deferred
   compositing, ops/tiles.DeterministicHostCanvas) so the canvas is
   insensitive to who finished first.

Fault-plan op names exposed by the harness (see faults.py grammar):

    chaos:<worker>:pull     before a worker's pull RPC
    chaos:<worker>:pulled   after a successful pull (crash here =
                            crash-after-pull: tile assigned, never
                            submitted — the requeue path must cover it)
    chaos:<worker>:submit   before a worker's submit RPC
    store:heartbeat:<id>    JobStore heartbeat recording (drop = the
                            master never sees the beat)
    store:pull:<id> / store:submit:<id>   JobStore RPC surfaces

`run_chaos_master_crash` extends the harness to the MASTER's own
death: phase 1 runs the elastic loop with the write-ahead journal
attached and a fault plan that kills the master mid-job (after a pull,
or after a partial submit — `crash@store:pull:master#k` /
`crash@store:submit:master#k`); phase 2 simulates the restarted
process — a fresh JobStore recovered from the journal directory — and
drains the job to completion. The acceptance assertion is the same
bit-identical canvas the worker-crash scenarios make.

Used by tests/test_chaos_usdu.py (tier-1, `-m chaos` selectable),
scripts/chaos_smoke.py, and scripts/durability_soak.py.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
import time
import types
from typing import Any, Optional, Sequence
from unittest import mock

import numpy as np

from ..telemetry import Tracer, get_tracer, set_tracer
from ..utils.logging import debug_log
from .faults import FaultAction, FaultInjected, FaultInjector


class FakeClock:
    """Deterministic monotonic clock for trace timestamps: every call
    advances by a fixed step, so span durations in a chaos trace are a
    pure function of the span SEQUENCE, not wall time."""

    def __init__(self, step: float = 0.001):
        self._step = step
        self._now = 0.0
        self._lock = threading.Lock()

    def __call__(self) -> float:
        with self._lock:
            self._now += self._step
            return self._now


@dataclasses.dataclass
class ChaosResult:
    """Output image + what the injector actually did (tests assert the
    scripted faults FIRED, so a passing run can't be vacuous)."""

    output: np.ndarray
    fired: list[FaultAction]
    crashed_workers: list[str]
    trace_id: str = ""
    # watchdog verdicts (populated when run_chaos_usdu(watchdog=...)):
    stragglers: list[str] = dataclasses.field(default_factory=list)
    stalls: list[str] = dataclasses.field(default_factory=list)
    speculated: dict[str, list[int]] = dataclasses.field(default_factory=dict)
    health: dict[str, dict] = dataclasses.field(default_factory=dict)
    # accepted (first-wins) submissions per participant, master included
    tiles_by_worker: dict[str, int] = dataclasses.field(default_factory=dict)
    # placement snapshot (populated when run_chaos_usdu(placement=...))
    placement: dict = dataclasses.field(default_factory=dict)
    # SLO alert transitions in order (populated when
    # run_chaos_usdu(slo=...)): each entry is the engine's transition
    # dict ({"type": "alert_fired"|"alert_resolved", "slo", "ts", ...})
    alerts: list[dict] = dataclasses.field(default_factory=list)
    # whether any alert was still open when the harness gave up waiting
    slo_active: bool = False
    # incident bundles captured during the run (populated when
    # run_chaos_usdu(incidents=...)): the manager's newest-first
    # listing, plus the directory for offline analysis
    incidents: list[dict] = dataclasses.field(default_factory=list)
    incident_dir: str = ""
    # debounce proof: the disposition of a simulated second identical
    # alert inside the debounce window ("debounced" when a capture
    # happened; "" when no alert fired)
    incident_retrigger: str = ""
    # chip-time attribution captured on a run-local UsageMeter:
    # {"rollup": per-tenant/lane/job view, "totals": exact ns identity}
    usage: dict = dataclasses.field(default_factory=dict)
    # tile-result-cache counters for the run (populated when
    # run_chaos_usdu(cache=...)): TileResultCache.stats() after the run
    cache: dict = dataclasses.field(default_factory=dict)

    def fired_kinds(self) -> set[str]:
        return {a.kind for a in self.fired}


def _stub_process(params, tile, key, pos, neg, yx):
    """Deterministic stand-in for the jitted VAE→sample→VAE tile
    processor: tile content + keyed noise, snapped to the uint8 grid
    (multiples of 1/255) so the PNG envelope is lossless and
    master-local results are bit-equal to worker results."""
    import jax
    import jax.numpy as jnp

    noisy = jnp.clip(tile + 0.05 * jax.random.normal(key, tile.shape), 0.0, 1.0)
    return jnp.round(noisy * 255.0) / 255.0


@contextlib.contextmanager
def _ensure_server_loop():
    """All JobStore asyncio state must live on ONE loop; start a
    control-plane loop thread if the process doesn't have one."""
    from ..utils.async_helpers import ServerLoopThread, get_server_loop

    existing = get_server_loop()
    if existing is not None and existing.is_running():
        yield
        return
    thread = ServerLoopThread(name="cdt-chaos-loop")
    thread.start()
    try:
        yield
    finally:
        thread.stop()


def run_chaos_usdu(
    seed: int = 0,
    fault_plan: Optional[str] = None,
    *,
    workers: Sequence[str] = ("w1", "w2"),
    image_hw: tuple[int, int] = (64, 64),
    tile: int = 64,
    padding: int = 16,
    upscale_by: float = 2.0,
    worker_timeout: float = 0.6,
    job_id: str = "chaos-job",
    trace_jsonl: Optional[str] = None,
    watchdog: Optional[dict] = None,
    placement: Optional[dict] = None,
    tile_batch: int = 1,
    pipeline: bool = True,
    prefetch: bool = False,
    journal_dir: Optional[str] = None,
    mesh_devices: int = 0,
    slo: Optional[dict] = None,
    incidents: Optional[dict] = None,
    cache=None,
    device_canvas: bool = False,
) -> ChaosResult:
    """One in-process elastic USDU run under `fault_plan`; returns the
    blended [B, H, W, C] image plus the faults that actually fired.
    `fault_plan=None` is the fault-free reference run.

    The whole run executes under a fake-clock tracer (one span tree,
    trace id `exec_chaos_<seed>`): master and worker tile stages are
    recorded deterministically. `trace_jsonl` exports the spans to
    that path for scripts/perf_report.py.

    Worker threads start BEFORE the master and park on the JobStore's
    creation signal (`wait_for_tile_job`), so they contend for tiles
    from the first instant of the job — plans that slow the master's
    pulls (`latency(..)@store:pull:master`) make worker participation
    deterministic instead of a race the master usually wins.

    `watchdog`: pass a dict of Watchdog overrides (may be empty) to run
    a live straggler/stall monitor over the harness store — fed by the
    store's latency sink, pushing stragglers into a PRIVATE
    HealthRegistry and speculating stalled in-flight tiles through the
    real requeue path. Verdicts land in ChaosResult.stragglers /
    .stalls / .speculated / .health. The harness defaults are tight
    (50 ms interval, 300 ms stall window, min_samples=1) so sub-second
    chaos plans trigger real detections.

    `placement`: pass a dict of PlacementPolicy overrides (may be
    empty) to run cost-aware weighted placement over the harness store
    — worker threads then pull speed-sized BATCHES through
    `JobStore.pull_tasks`, the policy's EWMA is fed by the same latency
    sink, and tail pulls from slow/suspect workers are trimmed. The
    harness defaults (min_samples=1, base_batch=2, max_batch=4,
    tail_tiles=1) make a sub-second run develop real weights. Accepted
    submissions per participant land in ChaosResult.tiles_by_worker and
    the policy snapshot in ChaosResult.placement — chaos tests assert a
    straggler receives measurably fewer tiles while the canvas stays
    bit-identical (placement must change WHO, never WHAT).

    `mesh_devices`: N > 1 runs master AND worker grant samplers on an
    N-participant local device mesh (parallel/mesh.build_mesh over the
    first N host devices — the tier-1 suite forces virtual CPU devices,
    conftest.py): batches shard across the data axis with NamedSharding
    and gather through host_collect, exactly the production multi-chip
    path. The mesh-parity acceptance asserts the canvas is
    bit-identical to the 1-device run, square and ragged grids alike.

    `slo`: pass a dict of overrides (may be empty) to run a live
    burn-rate SLO engine (telemetry/slo.py) over the harness store's
    latency stream — one `tile_latency` spec with harness-tight
    windows (threshold 0.15 s, one (1 s, 0.25 s) burn rule, objective
    0.9, resolve hold 50 ms) so a sub-second straggler plan fires a
    real alert. The engine steps on every latency sample; after the
    run the harness keeps stepping (bounded) until the alert resolves
    — no new bad samples arrive once the straggler is quarantined out
    of the tail, so the short window drains and the alert closes.
    Transitions land in ChaosResult.alerts (and the alert events ride
    the process bus like production). Keys: ``threshold_s``,
    ``objective``, ``long_s``, ``short_s``, ``burn_threshold``,
    ``resolve_hold_s``, ``min_events``.

    `incidents`: pass ``{"dir": <path>, ...overrides}`` to run a live
    `IncidentManager` (telemetry/incidents.py) over the run — the
    always-on flight recorder taps the bus, a harness `FleetRegistry`
    retains per-worker tile-rate series from the latency stream, and
    the manager's bus tap turns the SLO engine's `alert_fired` into an
    automatic debug-bundle capture (the production loop, end to end,
    in one process). Overrides beyond ``dir`` are IncidentManager
    kwargs (``debounce_s``, ``min_interval_s``, ``max_bundles``,
    ``max_bytes``); harness defaults: debounce 60 s, no global rate
    limit, 8 retained bundles. Captured bundles land newest-first in
    ChaosResult.incidents (+ .incident_dir) — the chaos acceptance
    asserts the bundle holds the firing evaluation AND the straggler's
    fleet series while the canvas stays bit-identical.

    `cache`: pass a TileResultCache to install it run-locally (the
    process global is swapped in and restored like the usage meter) —
    the master probes it at grant time and settles hits straight into
    the job, so a warm re-run with the same cache serves tiles without
    dispatching them to workers. Counters land in ChaosResult.cache
    (TileResultCache.stats() after the run); the cache acceptance
    asserts warm output is BIT-IDENTICAL to the cold reference, under
    faults included — a cache may only change WHO computes a tile
    (nobody), never WHAT lands on the canvas.

    `tile_batch`/`pipeline`/`prefetch`: the batched-pipelined data path
    (graph/tile_pipeline.py). Worker threads ALWAYS run the production
    TilePipeline (this harness is its chaos coverage); `pipeline=False`
    forces the synchronous staging fallback, `tile_batch>1` runs grants
    through the bucketed vmapped K-tile processor on master and workers
    alike (CDT_TILE_BATCH is patched for the master loop), and
    `prefetch=True` enables the one-grant-ahead pull stage. Defaults
    keep claim timing deterministic (no prefetch) so scripted fault
    schedules fire on the same tiles every run. All combinations must
    produce the bit-identical canvas — that is the point.

    `device_canvas`: route the master's blend through the on-device
    DeviceCanvas (CDT_DEVICE_CANVAS=1, the device-resident hot path's
    one-flush compositing) instead of the deterministic host canvas.
    DeviceCanvas ≡ DeterministicHostCanvas is a BIT-IDENTITY contract,
    so every scenario must match the host baseline exactly — under
    crashes, speculation, and batched grants included.
    """
    import jax
    import jax.numpy as jnp

    from ..graph import ExecutionContext
    from ..graph import usdu_elastic as elastic
    from ..jobs import JobStore
    from ..ops import upscale as upscale_ops
    from ..utils import config as config_mod
    from ..utils import image as img_utils
    from ..utils.async_helpers import run_async_in_server_loop
    from ..utils.exceptions import JobQueueError

    mesh = None
    if mesh_devices and int(mesh_devices) > 1:
        from ..parallel.mesh import DATA_AXIS, MODEL_AXIS, build_mesh

        local = jax.local_devices()
        if len(local) < int(mesh_devices):
            raise ValueError(
                f"mesh_devices={mesh_devices} but only {len(local)} local "
                "device(s); force more with "
                "XLA_FLAGS=--xla_force_host_platform_device_count=N"
            )
        mesh = build_mesh(
            {DATA_AXIS: int(mesh_devices), MODEL_AXIS: 1},
            devices=local[: int(mesh_devices)],
        )

    injector = FaultInjector(fault_plan) if fault_plan else None
    store = JobStore(fault_injector=injector)
    durability = None
    if journal_dir:
        # journaled runs (durability soak / overhead A/B): the standard
        # scenarios with the write-ahead seam attached
        from ..durability import DurabilityManager

        durability = DurabilityManager(journal_dir)
        store.journal_sink = durability.record
    wd = None
    wd_health = None
    latency_sinks = []
    if watchdog is not None:
        from ..telemetry.watchdog import Watchdog
        from .health import HealthRegistry

        wd_health = HealthRegistry()
        wd_kwargs = dict(
            interval=0.05, stall_seconds=0.3, min_samples=1,
            straggler_factor=4.0,
        )
        wd_kwargs.update(watchdog)
        wd = Watchdog(store=store, health=wd_health, **wd_kwargs)
        latency_sinks.append(wd.record_latency)
    slo_engine = None
    if slo is not None:
        from ..telemetry.slo import BurnRule, SLOEngine, SLOSpec
        from ..telemetry.timeseries import SeriesStore

        slo_kwargs = dict(
            threshold_s=0.15, objective=0.9, long_s=1.0, short_s=0.25,
            burn_threshold=1.0, resolve_hold_s=0.05, min_events=2,
        )
        slo_kwargs.update(slo)
        spec = SLOSpec(
            name="tile_latency",
            description="chaos-harness tile pull->submit latency",
            objective=slo_kwargs["objective"],
            kind="latency",
            threshold_s=slo_kwargs["threshold_s"],
            rules=(
                BurnRule(
                    long_s=slo_kwargs["long_s"],
                    short_s=slo_kwargs["short_s"],
                    burn_threshold=slo_kwargs["burn_threshold"],
                ),
            ),
            resolve_hold_s=slo_kwargs["resolve_hold_s"],
            min_events=slo_kwargs["min_events"],
        )
        # fine raw buckets so sub-second windows have real resolution
        slo_engine = SLOEngine(
            specs=(spec,),
            store=SeriesStore(raw_step=0.05, raw_points=4096),
        )

        def _slo_sink(_wid: str, seconds: float) -> None:
            slo_engine.note_latency("tile_latency", seconds)
            slo_engine.step()

        latency_sinks.append(_slo_sink)
    incident_manager = None
    incident_fleet = None
    if incidents is not None:
        from ..telemetry.fleet import S_WORKER_TILES_PER_S, FleetRegistry
        from ..telemetry.flight import get_flight_recorder
        from ..telemetry.incidents import IncidentManager

        if not incidents.get("dir"):
            raise ValueError("incidents requires a 'dir' key")
        get_flight_recorder()  # tap the bus before anything publishes
        incident_fleet = FleetRegistry()
        inc_kwargs = dict(debounce_s=60.0, min_interval_s=0.0, max_bundles=8)
        inc_kwargs.update(
            {k: v for k, v in incidents.items() if k != "dir"}
        )
        incident_manager = IncidentManager(str(incidents["dir"]), **inc_kwargs)
        incident_manager.sources["store"] = store.stats_unlocked
        if wd_health is not None:
            incident_manager.sources["health"] = wd_health.snapshot
        if slo_engine is not None:
            incident_manager.sources["slo"] = slo_engine.status
        incident_manager.sources["fleet"] = (
            lambda: incident_fleet.status(since_s=600.0)
        )

        def _fleet_sink(wid: str, seconds: float) -> None:
            # per-worker tile-rate series on the harness registry: the
            # straggler's slow rate is the evidence the bundle's fleet
            # window must carry
            incident_fleet.store.record(
                S_WORKER_TILES_PER_S,
                (1.0 / seconds) if seconds > 0 else 0.0,
                worker_id=wid,
            )

        # FIRST in the fan-out: the sample that makes the SLO engine
        # fire (and thus capture) must already be in the fleet series
        # when the writer thread reads them — sink order is the only
        # thing keeping that race deterministic
        latency_sinks.insert(0, _fleet_sink)
    policy = None
    if placement is not None:
        from ..scheduler.placement import PlacementPolicy

        pl_kwargs = dict(
            min_samples=1, base_batch=2, max_batch=4, tail_tiles=1,
            health=wd_health,
        )
        pl_kwargs.update(placement)
        policy = PlacementPolicy(**pl_kwargs)
        store.placement = policy
        latency_sinks.append(policy.record_latency)
    if latency_sinks:
        store.latency_sink = lambda wid, sec: [
            sink(wid, sec) for sink in latency_sinks
        ]
    server = types.SimpleNamespace(job_store=store)
    ctx = ExecutionContext(server=server, config={"workers": []})
    bundle = types.SimpleNamespace(params=None)
    crashed: list[str] = []
    trace_id = f"exec_chaos_{seed}"
    chaos_tracer = Tracer(clock=FakeClock())

    h, w = image_hw
    image = jnp.asarray(
        np.random.default_rng(seed).random((1, h, w, 3)), jnp.float32
    )
    pos = neg = jnp.zeros((1, 4, 8), jnp.float32)

    accepted_by_worker: dict[str, int] = {wid: 0 for wid in workers}

    def worker_body(wid: str) -> None:
        # Identical preprocessing to the master: per-tile determinism
        # means the only thing identity changes is WHO computed a tile.
        from ..graph.tile_pipeline import GrantSampler, TilePipeline, stage_span

        _, grid, extracted = upscale_ops.prepare_upscaled_tiles(
            image, upscale_by, tile, padding, "bicubic", None
        )
        key = jax.random.key(seed)
        job = run_async_in_server_loop(
            store.wait_for_tile_job(job_id, grace_seconds=20), timeout=30
        )
        if job is None:
            return
        # Worker threads join the run's trace so their tile stages land
        # in the same span tree the master's stages do.
        tracer = get_tracer()
        token = tracer.activate(trace_id)
        sampler = GrantSampler(
            _stub_process, None, extracted, key, grid.positions_array(),
            None, None, k_max=tile_batch, role="worker", mesh=mesh,
            job_id=job_id,
        )
        flush_pending: dict[int, list] = {}

        def pull():
            if injector is not None:
                injector.check_blocking(f"chaos:{wid}:pull")
            # pull_tasks = the production batch path: singleton batches
            # without a placement policy (byte-identical to the
            # historical pull), speed-sized grants with one.
            return run_async_in_server_loop(
                store.pull_tasks(job_id, wid, timeout=0.2), timeout=10
            ) or None

        def sample(chunk):
            if injector is not None:
                # per-tile crash point AFTER assignment, BEFORE compute
                # (crash here = crash-after-pull: tile assigned, never
                # submitted — the requeue path must cover it)
                for _t in chunk:
                    injector.check_blocking(f"chaos:{wid}:pulled")
            return sampler.sample(chunk)

        def emit(tile_idx, arr):
            flush_pending[int(tile_idx)] = [
                {
                    "batch_idx": i,
                    "image": img_utils.encode_image_data_url(arr[i]),
                }
                for i in range(arr.shape[0])
            ]

        def flush(is_final):
            if not flush_pending:
                return
            grouped = dict(flush_pending)
            flush_pending.clear()
            if injector is not None:
                for _t in sorted(grouped):
                    injector.check_blocking(f"chaos:{wid}:submit")
            with stage_span(
                "submit", "worker", sorted(grouped)[0],
                batch=sorted(grouped), worker_id=wid,
            ):
                accepted = run_async_in_server_loop(
                    store.submit_flush(job_id, wid, grouped), timeout=10
                )
            accepted_by_worker[wid] += accepted

        def heartbeat():
            try:
                run_async_in_server_loop(
                    store.heartbeat(job_id, wid), timeout=10
                )
            except Exception:  # noqa: BLE001 - liveness is best effort
                pass

        def release(idxs):
            run_async_in_server_loop(
                store.release_tasks(job_id, wid, idxs), timeout=10
            )

        try:
            TilePipeline(
                pull=pull,
                sample=sample,
                chunks=sampler.chunks,
                to_host=sampler.collect,
                emit=emit,
                flush=flush,
                heartbeat=heartbeat,
                release=release,
                role="worker",
                span_attrs={"worker_id": wid},
                threaded=pipeline,
                prefetch=prefetch,
            ).run()
        except FaultInjected as exc:
            # Simulated crash: the thread dies with a tile assigned and
            # unsubmitted; the master's requeue path must recover it.
            debug_log(f"chaos worker {wid} died: {exc}")
            crashed.append(wid)
        except JobQueueError:
            pass  # master cleaned the job up while we were pulling
        finally:
            tracer.deactivate(token)

    threads = [
        threading.Thread(target=worker_body, args=(wid,), daemon=True)
        for wid in workers
    ]

    previous_tracer = get_tracer()
    if incident_manager is not None:
        # writer thread + bus trigger tap (alert_fired -> capture) —
        # started HERE, immediately before the guarded try, so any
        # raise in the remaining setup or the run itself reaches the
        # except arm that stops it (no leaked tap/thread)
        incident_manager.start()
    set_tracer(chaos_tracer)
    from ..telemetry.usage import UsageMeter, set_usage_meter

    usage_meter = UsageMeter()
    try:
        with contextlib.ExitStack() as stack:
            stack.enter_context(_ensure_server_loop())
            # run-local chip-time attribution: master loop, worker
            # threads, and store waste notes all meter into this
            # swapped-in meter (restored on stack exit); the result's
            # usage block is exactly this run's burn
            stack.callback(set_usage_meter, set_usage_meter(usage_meter))
            if cache is not None:
                # run-local tile result cache, same swap/restore idiom:
                # explicit set wins over the CDT_CACHE gate, so the
                # master's grant-time probe sees exactly this instance
                from ..cache.store import set_tile_cache

                stack.callback(set_tile_cache, set_tile_cache(cache))
            if wd is not None:
                # start after the loop exists (speculation round-trips
                # through it); stop (LIFO) before the loop shuts down
                wd.start()
                stack.callback(wd.stop)
            stack.enter_context(
                mock.patch.object(
                    elastic, "_jit_tile_processor", lambda *a, **k: _stub_process
                )
            )
            stack.enter_context(
                mock.patch.object(
                    config_mod, "get_worker_timeout_seconds",
                    lambda path=None: worker_timeout,
                )
            )
            stack.enter_context(
                mock.patch.dict(
                    os.environ,
                    {
                        "CDT_DETERMINISTIC_BLEND": "1",
                        # master loop + any nested tile_scan_batch()
                        # read share the harness's batching knob
                        "CDT_TILE_BATCH": str(max(1, int(tile_batch))),
                        "CDT_DEVICE_CANVAS": "1" if device_canvas else "0",
                    },
                )
            )
            token = chaos_tracer.activate(trace_id)
            try:
                with chaos_tracer.span(
                    "chaos_usdu", trace_id=trace_id, seed=seed,
                    fault_plan=fault_plan or "",
                ):
                    for t in threads:
                        t.start()
                    out = elastic.run_master_elastic(
                        bundle, image, pos, neg,
                        job_id=job_id,
                        enabled_worker_ids=list(workers),
                        mesh=mesh,
                        upscale_by=upscale_by, tile=tile, padding=padding,
                        steps=1, sampler="euler", scheduler="karras",
                        cfg=1.0, denoise=0.3, seed=seed, context=ctx,
                    )
                    for t in threads:
                        t.join(timeout=30)
            finally:
                chaos_tracer.deactivate(token)
        if trace_jsonl:
            chaos_tracer.write_jsonl(trace_id, trace_jsonl)
    except BaseException:
        # a raising run must not leak the incident plane: the bus tap
        # would keep capturing for unrelated later activity and the
        # writer thread would park on its queue forever (stop is
        # idempotent — the happy path below stops it again harmlessly)
        if incident_manager is not None:
            incident_manager.stop()
        raise
    finally:
        set_tracer(previous_tracer)
        if durability is not None:
            durability.close()
    if slo_engine is not None and slo_engine.is_active("tile_latency"):
        # the straggler is quarantined and the job is done — no new bad
        # samples can arrive, so continued evaluation MUST resolve the
        # alert once the short window drains past the resolve hold.
        # Bounded wait: a stuck alert here is a real engine bug, and
        # the test asserts on slo_active instead of hanging.
        deadline = time.monotonic() + 5.0
        while (
            slo_engine.is_active("tile_latency")
            and time.monotonic() < deadline
        ):
            slo_engine.step()
            time.sleep(0.02)
    incident_list: list[dict] = []
    incident_retrigger = ""
    if incident_manager is not None:
        # barrier: every queued capture written before the listing (a
        # trigger that fired in the final submit must not race)
        incident_manager.flush(10.0)
        if slo_engine is not None:
            fired = [
                a for a in slo_engine.history if a["type"] == "alert_fired"
            ]
            if fired:
                # debounce proof: a second identical alert inside the
                # window must capture NOTHING
                incident_retrigger = incident_manager.trigger(
                    "alert_fired",
                    key=str(fired[0].get("slo", "")),
                    context={"resimulated": True},
                )
                incident_manager.flush(5.0)
        incident_list = incident_manager.list_bundles()
        incident_manager.stop()
    # every tile is accepted exactly once (first result wins), so the
    # master's share is the remainder (plan_grid: geometry only, no
    # second resize/extract pass)
    _, _, grid = upscale_ops.plan_grid(h, w, upscale_by, tile, padding, None)
    tiles_by_worker = dict(accepted_by_worker)
    tiles_by_worker["master"] = grid.num_tiles - sum(accepted_by_worker.values())
    return ChaosResult(
        output=np.asarray(out),
        fired=list(injector.fired) if injector is not None else [],
        crashed_workers=crashed,
        trace_id=trace_id,
        stragglers=list(wd.stragglers_flagged) if wd is not None else [],
        stalls=list(wd.stalls_detected) if wd is not None else [],
        speculated=dict(wd.speculated) if wd is not None else {},
        health=wd_health.snapshot() if wd_health is not None else {},
        tiles_by_worker=tiles_by_worker,
        placement=policy.snapshot() if policy is not None else {},
        alerts=list(slo_engine.history) if slo_engine is not None else [],
        slo_active=(
            slo_engine.is_active("tile_latency")
            if slo_engine is not None
            else False
        ),
        incidents=incident_list,
        incident_dir=str(incidents["dir"]) if incidents else "",
        incident_retrigger=incident_retrigger,
        usage={
            "rollup": usage_meter.rollup(),
            "totals": usage_meter.totals(),
        },
        cache=cache.stats() if cache is not None else {},
    )


# --------------------------------------------------------------------------
# kill-the-master scenarios (durable control plane acceptance)
# --------------------------------------------------------------------------


@dataclasses.dataclass
class MasterCrashResult:
    """Outcome of a two-phase kill-the-master run: the recovered
    canvas, what recovery found, and proof the crash actually fired."""

    output: np.ndarray
    report: dict
    crash_error: str
    fired: list[FaultAction]

    def fired_kinds(self) -> set[str]:
        return {a.kind for a in self.fired}


def run_chaos_master_crash(
    seed: int = 0,
    crash_plan: str = "crash@store:pull:master#3",
    *,
    journal_dir: str,
    workers: Sequence[str] = ("w1", "w2"),
    image_hw: tuple[int, int] = (64, 64),
    tile: int = 64,
    padding: int = 16,
    upscale_by: float = 2.0,
    worker_timeout: float = 0.6,
    job_id: str = "chaos-crash-job",
    snapshot_every: int = 4,
    fsync_every: int = 0,
) -> MasterCrashResult:
    """SIGKILL-the-master simulation, in process and deterministic.

    Phase 1 ("the process that dies"): the elastic USDU loop runs with
    the write-ahead journal attached (`journal_dir`) under a fault plan
    that raises out of one of the MASTER's own store RPCs
    (`crash@store:pull:master#k` = killed after k-1 successful pulls,
    `crash@store:submit:master#k` = killed after a partial submit). The
    abandoned JobStore — like the dead process's memory — is discarded;
    worker threads are orphaned mid-flight exactly as a real master
    SIGKILL orphans them, then drained out.

    Phase 2 ("the restarted process"): a FRESH JobStore is recovered
    from `journal_dir` (snapshot + WAL tail; in-flight and
    master-volatile tiles requeue, durable worker payloads restore to
    the results queue) and a fresh master loop drains the job to
    completion with no workers.

    Determinism: per-tile noise keys fold the global tile index, so
    whichever tiles phase 2 recomputes reproduce exactly; restored
    worker tiles travel the lossless PNG envelope; the deterministic
    blend makes compositing order irrelevant. The caller asserts the
    returned canvas is bit-identical to an uninterrupted run — journal
    CONTENT races (which worker submits landed before the crash) change
    the requeue/restore split, never the output.
    """
    import jax.numpy as jnp

    from ..durability import DurabilityManager
    from ..graph import ExecutionContext
    from ..graph import usdu_elastic as elastic
    from ..graph.tile_pipeline import GrantSampler, TilePipeline
    from ..jobs import JobStore
    from ..ops import upscale as upscale_ops
    from ..utils import config as config_mod
    from ..utils import image as img_utils
    from ..utils.async_helpers import run_async_in_server_loop
    from ..utils.exceptions import JobQueueError

    h, w = image_hw
    image = jnp.asarray(
        np.random.default_rng(seed).random((1, h, w, 3)), jnp.float32
    )
    pos = neg = jnp.zeros((1, 4, 8), jnp.float32)
    bundle = types.SimpleNamespace(params=None)

    def worker_body(store: Any, wid: str) -> None:
        _, grid, extracted = upscale_ops.prepare_upscaled_tiles(
            image, upscale_by, tile, padding, "bicubic", None
        )
        import jax as _jax

        key = _jax.random.key(seed)
        job = run_async_in_server_loop(
            store.wait_for_tile_job(job_id, grace_seconds=20), timeout=30
        )
        if job is None:
            return
        sampler = GrantSampler(
            _stub_process, None, extracted, key, grid.positions_array(),
            None, None, k_max=1, role="worker",
        )
        flush_pending: dict[int, list] = {}

        def pull():
            return run_async_in_server_loop(
                store.pull_tasks(job_id, wid, timeout=0.2), timeout=10
            ) or None

        def emit(tile_idx, arr):
            flush_pending[int(tile_idx)] = [
                {
                    "batch_idx": i,
                    "image": img_utils.encode_image_data_url(arr[i]),
                }
                for i in range(arr.shape[0])
            ]

        def flush(is_final):
            if not flush_pending:
                return
            grouped = dict(flush_pending)
            flush_pending.clear()
            run_async_in_server_loop(
                store.submit_flush(job_id, wid, grouped), timeout=10
            )

        def heartbeat():
            try:
                run_async_in_server_loop(store.heartbeat(job_id, wid), timeout=10)
            except Exception:  # noqa: BLE001 - liveness best effort
                pass

        try:
            TilePipeline(
                pull=pull, sample=sampler.sample, chunks=sampler.chunks,
                emit=emit, flush=flush, heartbeat=heartbeat,
                role="worker", span_attrs={"worker_id": wid}, threaded=False,
            ).run()
        except JobQueueError:
            pass  # the dead master's job was torn down under us

    def run_master(store: Any) -> Any:
        ctx = ExecutionContext(
            server=types.SimpleNamespace(job_store=store),
            config={"workers": []},
        )
        return elastic.run_master_elastic(
            bundle, image, pos, neg,
            job_id=job_id,
            enabled_worker_ids=[],
            upscale_by=upscale_by, tile=tile, padding=padding,
            steps=1, sampler="euler", scheduler="karras",
            cfg=1.0, denoise=0.3, seed=seed, context=ctx,
        )

    injector = FaultInjector(f"seed={seed};{crash_plan}")
    crash_error = ""
    with contextlib.ExitStack() as stack:
        stack.enter_context(_ensure_server_loop())
        stack.enter_context(
            mock.patch.object(
                elastic, "_jit_tile_processor", lambda *a, **k: _stub_process
            )
        )
        stack.enter_context(
            mock.patch.object(
                config_mod, "get_worker_timeout_seconds",
                lambda path=None: worker_timeout,
            )
        )
        stack.enter_context(
            mock.patch.dict(
                os.environ,
                {"CDT_DETERMINISTIC_BLEND": "1", "CDT_TILE_BATCH": "1"},
            )
        )

        # --- phase 1: the master that dies -------------------------------
        store1 = JobStore(fault_injector=injector)
        manager1 = DurabilityManager(
            journal_dir, snapshot_every=snapshot_every, fsync_every=fsync_every
        )
        store1.journal_sink = manager1.record
        threads = [
            threading.Thread(
                target=worker_body, args=(store1, wid), daemon=True
            )
            for wid in workers
        ]
        for t in threads:
            t.start()
        try:
            run_master(store1)
            raise RuntimeError(
                f"master crash plan {crash_plan!r} never fired; the "
                "scenario would be vacuous"
            )
        except FaultInjected as exc:
            crash_error = str(exc)
            debug_log(f"chaos master died: {exc}")
        # The dead process takes its journal seam with it; late worker
        # submissions against the abandoned store are lost exactly as
        # they would be against a closed socket (recovery requeues
        # them — bit-identical recompute either way).
        store1.journal_sink = None

        async def _teardown():
            async with store1.lock:
                store1.tile_jobs.pop(job_id, None)

        run_async_in_server_loop(_teardown(), timeout=10)
        for t in threads:
            t.join(timeout=30)
        manager1.close()

        # --- phase 2: the restarted master -------------------------------
        store2 = JobStore()
        manager2 = DurabilityManager(
            journal_dir, snapshot_every=snapshot_every, fsync_every=fsync_every
        )
        report = manager2.recover(store2)
        store2.journal_sink = manager2.record
        try:
            out = run_master(store2)
        finally:
            manager2.close()

    return MasterCrashResult(
        output=np.asarray(out),
        report=report.as_json(),
        crash_error=crash_error,
        fired=list(injector.fired),
    )


# --------------------------------------------------------------------------
# warm-standby failover scenarios (HA layer acceptance)
# --------------------------------------------------------------------------


@dataclasses.dataclass
class FailoverResult:
    """Outcome of a kill-the-active-master + standby-promotes run."""

    output: np.ndarray
    report: dict          # the promotion's recovery report
    crash_error: str
    fired: list[FaultAction]
    epochs: tuple[int, int]        # (active's epoch, promoted epoch)
    replica: dict                  # standby replica status at promotion
    zombie_fenced: bool            # ex-active journal append -> FencedOut
    stale_pull_rejected: bool      # epoch-1 pull on the new store -> StaleEpoch
    stale_submit_rejected: bool    # epoch-1 submit -> StaleEpoch
    zombie_journaled_records: int  # journal growth from fenced attempts (0!)
    repointed_workers: list[str]   # workers that pulled the PROMOTED store
    # tile the harness claimed against the dying master and never
    # submitted: its requeue-at-promotion is the non-vacuous proof the
    # prepare_for_restart path ran (None when the queue was already dry)
    orphan_tile: Optional[int] = None

    def fired_kinds(self) -> set[str]:
        return {a.kind for a in self.fired}


def run_chaos_failover(
    seed: int = 0,
    crash_plan: str = "crash@store:pull:master#2;crash@chaos:w1:pulled#2",
    *,
    journal_dir: str,
    workers: Sequence[str] = ("w1", "w2"),
    image_hw: tuple[int, int] = (64, 64),
    tile: int = 64,
    padding: int = 16,
    upscale_by: float = 2.0,
    worker_timeout: float = 0.6,
    job_id: str = "chaos-failover-job",
    snapshot_every: int = 4,
    lease_ttl: float = 0.3,
    push_grants: bool = False,
    quorum_peers: Optional[Sequence[Any]] = None,
    peer_crash: Optional[str] = None,
) -> FailoverResult:
    """Kill-the-active-master failover, in process and deterministic.

    The full HA protocol with the transports removed (the same halves
    api/replication_routes.py + api/standby.py put on a WebSocket):

    - **phase 1 (the active master that dies)**: the elastic USDU loop
      runs with the write-ahead journal attached, holding the
      epoch-numbered lease on `journal_dir`; a live standby replica
      tails the journal through a ``ReplicationSubscription`` (attach-
      consistent snapshot + record tee — the exact stream the WS route
      serves) on its own thread. `crash_plan` kills the master mid-job
      at a scripted store RPC (`crash@store:pull:master#k` = after a
      pull, `crash@store:submit:master#k` = after a partial submit;
      pass ``snapshot_every=1`` to land the crash inside the snapshot
      cadence). A worker-crash rule (`crash@chaos:<w>:pulled#k`)
      guarantees an in-flight orphan tile exists at takeover, so the
      promotion's requeue path is never vacuous.

    - **takeover**: surviving workers observe the dead master (their
      next pull parks, exactly as re-pointed HTTP clients park in their
      retry/rotation loop); the standby waits out the lease TTL, takes
      the lease (epoch+1), drains the final teed records, and promotes:
      ``DurabilityManager.adopt`` — `prepare_for_restart` semantics
      end to end (in-flight tiles requeued for bit-identical recompute,
      durable worker payloads restored), journal reopened at the
      replicated head, immediate snapshot.

    - **fencing probes** (the regression the acceptance demands): after
      takeover the ex-active's journal seam must raise ``FencedOut``
      and journal NOTHING; the promoted store must reject pre-takeover
      authority (pull and submit carrying the old epoch) with
      ``StaleEpoch`` BEFORE any mutation — both probed directly and
      reported in the result.

    - **phase 2 (the promoted master serves)**: workers re-point to the
      promoted store (carrying the new epoch) and a fresh master loop
      drains the job to completion — no process restart anywhere. The
      caller asserts the canvas is bit-identical to an uninterrupted
      run.

    `push_grants=True` wires the store's grant notifier through a
    PlacementPolicy (the production push publisher) on both stores —
    the pushed-grant path must survive the same failover the pull
    fallback does.

    `quorum_peers` swaps the arbitration medium: both claimants run a
    ``QuorumLease`` over the given shared peer registers instead of a
    flock'd file on `journal_dir` — region mode, where no shared
    filesystem arbitrates. The protocol downstream is identical (epoch
    fencing, ``FencedOut``, ``StaleEpoch``), which is exactly what the
    scenario proves. `peer_crash` ("before" / "after") additionally
    crashes one peer mid-way through the standby's acquire — the
    mid-acquire peer-crash case: a majority of the survivors still
    elects, epochs stay monotonic, and the canvas stays bit-identical.
    """
    import jax.numpy as jnp

    from ..durability import (
        DurabilityManager,
        FencedOut,
        Lease,
        LeaseHeld,
        QuorumLease,
        StandbyReplica,
    )
    from ..graph import ExecutionContext
    from ..graph import usdu_elastic as elastic
    from ..graph.tile_pipeline import GrantSampler, TilePipeline
    from ..jobs import JobStore
    from ..ops import upscale as upscale_ops
    from ..utils import config as config_mod
    from ..utils import image as img_utils
    from ..utils.async_helpers import run_async_in_server_loop
    from ..utils.exceptions import JobQueueError, StaleEpoch

    h, w = image_hw
    image = jnp.asarray(
        np.random.default_rng(seed).random((1, h, w, 3)), jnp.float32
    )
    pos = neg = jnp.zeros((1, 4, 8), jnp.float32)
    bundle = types.SimpleNamespace(params=None)

    # Shared failover state the worker threads re-point through: the
    # in-process analogue of HTTPWorkClient's address list + epoch.
    crashed = threading.Event()
    promoted = threading.Event()
    holder: dict[str, Any] = {"store": None, "epoch": 0}
    repointed: list[str] = []
    repointed_lock = threading.Lock()

    def worker_body(wid: str) -> None:
        _, grid, extracted = upscale_ops.prepare_upscaled_tiles(
            image, upscale_by, tile, padding, "bicubic", None
        )
        import jax as _jax

        key = _jax.random.key(seed)
        job = run_async_in_server_loop(
            holder["store"].wait_for_tile_job(job_id, grace_seconds=20),
            timeout=30,
        )
        if job is None:
            return
        sampler = GrantSampler(
            _stub_process, None, extracted, key, grid.positions_array(),
            None, None, k_max=1, role="worker",
        )
        flush_pending: dict[int, list] = {}
        seen_promoted = False

        def pull():
            nonlocal seen_promoted
            while True:
                if crashed.is_set() and not promoted.is_set():
                    # the master is dead: the re-pointing client parks
                    # in its rotation/retry loop until a standby
                    # promotes (or the run is abandoned)
                    if not promoted.wait(timeout=15):
                        return None
                store, epoch = holder["store"], holder["epoch"]
                if promoted.is_set() and not seen_promoted:
                    seen_promoted = True
                    with repointed_lock:
                        repointed.append(wid)
                if injector is not None:
                    injector.check_blocking(f"chaos:{wid}:pull")
                try:
                    return run_async_in_server_loop(
                        store.pull_tasks(
                            job_id, wid, timeout=0.2, epoch=epoch
                        ),
                        timeout=10,
                    ) or None
                except StaleEpoch:
                    continue  # takeover mid-RPC: refresh epoch and retry
                except (JobQueueError, FencedOut):
                    if promoted.is_set() and store is holder["store"]:
                        return None  # promoted store tore the job down: done
                    continue  # dead master's store; re-point and retry

        def sample(chunk):
            if injector is not None:
                for _t in chunk:
                    injector.check_blocking(f"chaos:{wid}:pulled")
            return sampler.sample(chunk)

        def emit(tile_idx, arr):
            flush_pending[int(tile_idx)] = [
                {
                    "batch_idx": i,
                    "image": img_utils.encode_image_data_url(arr[i]),
                }
                for i in range(arr.shape[0])
            ]

        def flush(is_final):
            if not flush_pending:
                return
            grouped = dict(flush_pending)
            flush_pending.clear()
            store, epoch = holder["store"], holder["epoch"]
            try:
                run_async_in_server_loop(
                    store.submit_flush(job_id, wid, grouped, epoch=epoch),
                    timeout=10,
                )
            except (StaleEpoch, FencedOut, JobQueueError):
                # pre-takeover authority / dead store: drop the flush —
                # the promotion requeued these tiles and their recompute
                # is bit-identical (the whole point of the invariant)
                pass

        def heartbeat():
            try:
                run_async_in_server_loop(
                    holder["store"].heartbeat(
                        job_id, wid, epoch=holder["epoch"]
                    ),
                    timeout=10,
                )
            except Exception:  # noqa: BLE001 - liveness best effort
                pass

        try:
            TilePipeline(
                pull=pull, sample=sample, chunks=sampler.chunks,
                emit=emit, flush=flush, heartbeat=heartbeat,
                role="worker", span_attrs={"worker_id": wid}, threaded=False,
            ).run()
        except FaultInjected as exc:
            debug_log(f"chaos worker {wid} died: {exc}")
        except JobQueueError:
            pass

    def run_master(store: Any) -> Any:
        ctx = ExecutionContext(
            server=types.SimpleNamespace(job_store=store),
            config={"workers": []},
        )
        return elastic.run_master_elastic(
            bundle, image, pos, neg,
            job_id=job_id,
            enabled_worker_ids=list(workers),
            upscale_by=upscale_by, tile=tile, padding=padding,
            steps=1, sampler="euler", scheduler="karras",
            cfg=1.0, denoise=0.3, seed=seed, context=ctx,
        )

    def wire_push(store: JobStore) -> None:
        if not push_grants:
            return
        from ..scheduler.placement import PlacementPolicy

        policy = PlacementPolicy(min_samples=1)
        store.placement = policy
        store.grant_notifier = policy.notify_grants

    injector = FaultInjector(f"seed={seed};{crash_plan}")
    crash_error = ""
    with contextlib.ExitStack() as stack:
        stack.enter_context(_ensure_server_loop())
        stack.enter_context(
            mock.patch.object(
                elastic, "_jit_tile_processor", lambda *a, **k: _stub_process
            )
        )
        stack.enter_context(
            mock.patch.object(
                config_mod, "get_worker_timeout_seconds",
                lambda path=None: worker_timeout,
            )
        )
        stack.enter_context(
            mock.patch.dict(
                os.environ,
                {"CDT_DETERMINISTIC_BLEND": "1", "CDT_TILE_BATCH": "1"},
            )
        )

        # --- phase 1: the active master, its lease, and a live standby ---
        store1 = JobStore(fault_injector=injector)
        manager1 = DurabilityManager(
            journal_dir, snapshot_every=snapshot_every, fsync_every=0
        )

        def make_lease(owner: str) -> Any:
            if quorum_peers is not None:
                return QuorumLease(
                    list(quorum_peers), owner=owner, ttl=lease_ttl
                )
            return Lease(journal_dir, owner=owner, ttl=lease_ttl)

        lease1 = make_lease("chaos-active")
        epoch1 = lease1.acquire(force=True)
        manager1.lease = lease1
        store1.journal_sink = manager1.record
        store1.set_epoch(epoch1)
        wire_push(store1)
        holder["store"], holder["epoch"] = store1, epoch1

        # the standby: attach-consistent subscription + replica tail
        # thread (the direct wiring of the WS stream's two halves)
        sub = manager1.subscribe_replica()
        replica = StandbyReplica()
        replica.reset(sub.snapshot_state, sub.head_lsn, sub.epoch)
        tail_stop = threading.Event()

        def tail_body() -> None:
            while not tail_stop.is_set():
                sub.wait(0.02)
                for record in sub.pop():
                    replica.apply(record)
                replica.note_head(manager1.head_lsn(), epoch1)

        tail = threading.Thread(target=tail_body, name="chaos-standby", daemon=True)
        tail.start()

        threads = [
            threading.Thread(target=worker_body, args=(wid,), daemon=True)
            for wid in workers
        ]
        for t in threads:
            t.start()
        try:
            run_master(store1)
            raise RuntimeError(
                f"failover crash plan {crash_plan!r} never fired; the "
                "scenario would be vacuous"
            )
        except FaultInjected as exc:
            crash_error = str(exc)
            debug_log(f"chaos active master died: {exc}")
        crashed.set()
        # Deterministic orphan: claim one tile against the dying master
        # and never submit it — the pull journals (and replicates), so
        # the promotion MUST requeue it. Models the grant the dead
        # process served in its last instant.
        orphan_tile = None
        try:
            orphan_tile = run_async_in_server_loop(
                store1.pull_task(job_id, "orphan", timeout=0.2, epoch=epoch1),
                timeout=10,
            )
        except Exception:  # noqa: BLE001 - queue already dry is legal
            orphan_tile = None

        # --- takeover: wait out the TTL, then promote the standby --------
        # NOT forced: the standby promotion gate — the acquire succeeds
        # only once the dead active's lease has expired. `peer_crash`
        # arms a one-shot peer crash on the quorum path so the election
        # itself runs through a mid-acquire failure.
        lease2 = make_lease("chaos-standby")
        if peer_crash is not None and quorum_peers is not None:
            quorum_peers[-1].crash_next_propose = peer_crash
        deadline = time.monotonic() + max(5.0, lease_ttl * 20)
        epoch2: Optional[int] = None
        while time.monotonic() < deadline:
            try:
                epoch2 = lease2.acquire()
                break
            except LeaseHeld:
                time.sleep(lease_ttl / 10)  # the dead active's TTL
            except OSError:
                time.sleep(lease_ttl / 10)  # indeterminate quorum read
        if epoch2 is None:
            raise RuntimeError("standby never won the lease")
        # final drain: post-takeover the ex-active is fenced, so no
        # record can land after this
        for record in sub.pop(max_items=100000):
            replica.apply(record)
        tail_stop.set()
        tail.join(timeout=10)
        replica_status = replica.status()

        store2 = JobStore()
        manager2 = DurabilityManager(
            journal_dir, snapshot_every=snapshot_every, fsync_every=0
        )
        report = manager2.adopt(store2, replica, lease=lease2)
        store2.journal_sink = manager2.record
        store2.set_epoch(epoch2)
        wire_push(store2)

        # --- fencing probes (regression: the zombie mutates nothing) -----
        head_before = manager2.head_lsn()
        zombie_fenced = False
        try:
            manager1.record({"type": "submit", "job": job_id, "worker": "zombie",
                             "task": 0, "payload": None})
        except FencedOut:
            zombie_fenced = True
        stale_pull_rejected = False
        try:
            run_async_in_server_loop(
                store2.pull_task(job_id, "zombie", timeout=0.01, epoch=epoch1),
                timeout=10,
            )
        except StaleEpoch:
            stale_pull_rejected = True
        stale_submit_rejected = False
        try:
            run_async_in_server_loop(
                store2.submit_result(
                    job_id, "zombie", 0, None, epoch=epoch1
                ),
                timeout=10,
            )
        except StaleEpoch:
            stale_submit_rejected = True
        zombie_journaled = manager2.head_lsn() - head_before

        # --- phase 2: the promoted master serves; workers re-point -------
        holder["store"], holder["epoch"] = store2, epoch2
        promoted.set()
        try:
            out = run_master(store2)
        finally:
            for t in threads:
                t.join(timeout=30)
            manager2.close()
            manager1.close()
            lease2.release()

    return FailoverResult(
        output=np.asarray(out),
        report=report.as_json(),
        crash_error=crash_error,
        fired=list(injector.fired),
        epochs=(epoch1, epoch2),
        replica=replica_status,
        zombie_fenced=zombie_fenced,
        stale_pull_rejected=stale_pull_rejected,
        stale_submit_rejected=stale_submit_rejected,
        zombie_journaled_records=zombie_journaled,
        repointed_workers=sorted(repointed),
        orphan_tile=orphan_tile,
    )


def run_chaos_quorum_failover(
    seed: int = 0,
    crash_plan: str = "crash@store:pull:master#2;crash@chaos:w1:pulled#2",
    *,
    journal_dir: str,
    n_peers: int = 3,
    peer_crash: Optional[str] = None,
    **kwargs: Any,
) -> FailoverResult:
    """Region-mode failover: the same kill-the-active scenario as
    ``run_chaos_failover``, arbitrated by a ``QuorumLease`` over
    ``n_peers`` in-memory peer registers instead of a shared-filesystem
    flock. `peer_crash` ("before"/"after") crashes one peer mid-way
    through the standby's acquire. The caller asserts the canvas is
    bit-identical to the fault-free run — the acceptance that quorum
    leasing changes the arbitration medium and nothing else."""
    from ..durability import MemoryLeasePeer

    peers = [MemoryLeasePeer(f"peer{i}") for i in range(n_peers)]
    return run_chaos_failover(
        seed,
        crash_plan,
        journal_dir=journal_dir,
        quorum_peers=peers,
        peer_crash=peer_crash,
        **kwargs,
    )


@dataclasses.dataclass
class RegionResult:
    """Outcome of a two-shard region run with one shard failing over."""

    placements: dict          # job id -> shard name (the ring's map)
    shard0: FailoverResult    # the shard that lost its master mid-job
    shard1_tiles_completed: int  # the untouched shard's job, tile-complete
    shard1_epoch: int          # must still be its own epoch 1
    shard1_journal_appends: int
    placement_drift: int       # ring placements changed by the failover (0!)
    autoscale_decisions: list  # the controller's ledger across the run


def run_chaos_region(
    seed: int = 0,
    *,
    journal_root: str,
    crash_plan: str = "crash@store:pull:master#2;crash@chaos:w1:pulled#2",
    peer_crash: Optional[str] = None,
    probe_jobs: int = 64,
) -> RegionResult:
    """Two master shards, one region: shard0's master is killed mid-job
    and fails over through the quorum lease while shard1's job — opened
    BEFORE the crash and completed after — never loses a tile.

    What it proves, in one deterministic in-process run:

    - **placement is coordination-free**: the consistent-hash ring maps
      every probe job to the same shard before and after the failover
      (membership never changed, so zero keys move);
    - **shard isolation**: shard1's journal, lease epoch, and job state
      are untouched by shard0's crash/promotion — separate WALs,
      separate leases, zero cross-shard job loss;
    - **the failed shard recovers bit-identically** (delegated to
      ``run_chaos_quorum_failover``: zombie fenced, stale submits
      journal nothing, canvas equals the fault-free run);
    - **the autoscaler observes the region**: its step ledger across
      the run records each decision with the chip-second demand /
      capacity window that justified it (a burn alert during the
      outage forces a scale-up whose cost is measured on the next
      evaluation).
    """
    from ..durability import DurabilityManager, Lease
    from ..jobs import JobStore
    from ..scheduler.autoscale import AutoscaleController
    from ..scheduler.router import ShardRouter
    from ..utils.async_helpers import run_async_in_server_loop

    router = ShardRouter(
        {"shard0": ["http://s0:8188"], "shard1": ["http://s1:8188"]},
        vnodes=32,
    )
    placements = {
        f"region-job-{i}": router.shard_for(f"region-job-{i}")
        for i in range(probe_jobs)
    }
    job1 = next(j for j, s in placements.items() if s == "shard1")

    # The autoscaler watching the region: a burn alert flips during the
    # outage window; demand is the chip-seconds the shards burn.
    burn: set = set()
    usage_counter = {"chip_s": 0.0}
    pool = {"workers": 2}
    slo = types.SimpleNamespace(is_active=lambda name: name in burn)
    usage = types.SimpleNamespace(
        rollup=lambda: {"totals": {"chip_s": usage_counter["chip_s"]}}
    )
    controller = AutoscaleController(
        slo=slo,
        usage=usage,
        launcher=lambda: (
            pool.__setitem__("workers", pool["workers"] + 1)
            or f"w{pool['workers']}"
        ),
        drainer=None,
        capacity_fn=lambda: (pool["workers"], float(pool["workers"])),
        interval=1.0,
        min_workers=1,
        max_workers=4,
        target_util=0.7,
        down_hold=3600.0,
    )
    controller.step()  # baseline window

    with contextlib.ExitStack() as stack:
        stack.enter_context(_ensure_server_loop())
        # --- shard1: open its job BEFORE shard0's crash ----------------
        shard1_dir = os.path.join(journal_root, "shard1")
        store_s1 = JobStore()
        manager_s1 = DurabilityManager(
            shard1_dir, snapshot_every=4, fsync_every=0
        )
        lease_s1 = Lease(shard1_dir, owner="shard1-master", ttl=30.0)
        epoch_s1 = lease_s1.acquire(force=True)
        manager_s1.lease = lease_s1
        store_s1.journal_sink = manager_s1.record
        store_s1.set_epoch(epoch_s1)
        tiles_s1 = list(range(4))
        run_async_in_server_loop(
            store_s1.init_tile_job(job1, tiles_s1), timeout=10
        )
        first = run_async_in_server_loop(
            store_s1.pull_task(job1, "s1-w1", timeout=0.2, epoch=epoch_s1),
            timeout=10,
        )
        in_flight = [first] if first is not None else []

        # --- shard0: the full quorum-lease failover mid-job ------------
        usage_counter["chip_s"] += 1.4   # the window's measured demand
        burn.add("availability")          # the outage fires the SLO
        controller.step()                 # decision: scale_up (burn)
        shard0_result = run_chaos_quorum_failover(
            seed,
            crash_plan,
            journal_dir=os.path.join(journal_root, "shard0"),
            peer_crash=peer_crash,
        )
        burn.clear()
        usage_counter["chip_s"] += 0.4
        controller.step()                 # settles the scale_up's cost

        # --- shard1 again: finish the job it held across the outage ----
        for t in in_flight:
            run_async_in_server_loop(
                store_s1.submit_result(
                    job1, "s1-w1", int(t), None, epoch=epoch_s1
                ),
                timeout=10,
            )
        while True:
            t = run_async_in_server_loop(
                store_s1.pull_task(
                    job1, "s1-w1", timeout=0.05, epoch=epoch_s1
                ),
                timeout=10,
            )
            if t is None:
                break
            run_async_in_server_loop(
                store_s1.submit_result(
                    job1, "s1-w1", int(t), None, epoch=epoch_s1
                ),
                timeout=10,
            )
        job_state = store_s1.tile_jobs[job1]
        completed = len(job_state.completed)
        shard1_appends = manager_s1.head_lsn()
        manager_s1.close()
        lease_s1.release()

    drift = sum(
        1
        for j, s in placements.items()
        if router.shard_for(j) != s
    )
    if completed != len(tiles_s1):
        raise RuntimeError(
            f"cross-shard job loss: shard1 completed {completed}/"
            f"{len(tiles_s1)} tiles across shard0's failover"
        )
    return RegionResult(
        placements=placements,
        shard0=shard0_result,
        shard1_tiles_completed=completed,
        shard1_epoch=epoch_s1,
        shard1_journal_appends=shard1_appends,
        placement_drift=drift,
        autoscale_decisions=list(controller.decisions),
    )


# --------------------------------------------------------------------------
# request-lifecycle scenarios (cancel / poison-tile acceptance)
# --------------------------------------------------------------------------


class _TrimMaster:
    """Placement stub that trims the MASTER out of the pull set (and
    keeps worker grants at one tile): lifecycle scenarios need the
    poison/cancel tiles to stay with worker threads instead of being
    instantly drained by the in-process master."""

    def may_pull(self, worker_id: str, pending: int) -> bool:
        return worker_id != "master"

    def batch_size(self, worker_id: str, pending: int) -> int:
        return 1


@dataclasses.dataclass
class CancelResult:
    """Outcome of a cancel-mid-job run: the refund accounting, the
    leak check, and the terminal-state parity evidence."""

    raised: str                    # exception type the master died with
    reason: str                    # cancel reason it carried
    accounting: dict               # cancel_job's refund accounting
    completed_before_cancel: int
    stats_after: dict              # store stats right after cancel
    state_after_cancel: dict       # manager shadow at cancel time
    journal_jobs_after: dict       # jobs left in the journal at the end
    replica_jobs_after: dict       # jobs left in the replica at the end
    replica_saw_cancel: bool       # the cancel record reached the standby
    idempotent_replay: bool
    cancel_latency_ms: float       # cancel call -> all tiles refunded


def run_chaos_cancel(
    seed: int = 0,
    *,
    journal_dir: str,
    workers: Sequence[str] = ("w1", "w2"),
    image_hw: tuple[int, int] = (96, 96),
    tile: int = 48,
    padding: int = 16,
    upscale_by: float = 2.0,
    worker_timeout: float = 5.0,
    job_id: str = "chaos-cancel-job",
    cancel_after: int = 2,
    tile_delay: float = 0.08,
    reason: str = "chaos",
) -> CancelResult:
    """Cancel-mid-job acceptance: the elastic USDU loop runs with the
    write-ahead journal attached and a live standby replica teed in;
    once ``cancel_after`` tiles have completed, a canceller thread
    fires ``JobStore.cancel_job`` — mid-flight, with tiles pending AND
    assigned. The scenario then proves the acceptance bundle:

    - the refund accounting balances (no leaked in-flight assignment
      survives the cancel — ``stats_after``);
    - the master loop settles with a terminal ``JobCancelled`` instead
      of blending a partial canvas; workers' later submissions drop;
    - the cancel round-trips the journal: the shadow state at cancel
      time shows the job terminally drained, the standby replica
      applied the same record, and replay is idempotent.

    Workers are slowed by ``tile_delay`` per tile (and the master is
    trimmed out of the pull set) so the cancel deterministically lands
    while work is still in flight.
    """
    import jax
    import jax.numpy as jnp

    from ..durability import DurabilityManager, StandbyReplica
    from ..durability import state as dstate
    from ..durability.recovery import recover_state, verify_idempotent_replay
    from ..graph import ExecutionContext
    from ..graph import usdu_elastic as elastic
    from ..graph.tile_pipeline import GrantSampler, TilePipeline
    from ..jobs import JobStore
    from ..ops import upscale as upscale_ops
    from ..utils import config as config_mod
    from ..utils import image as img_utils
    from ..utils.async_helpers import run_async_in_server_loop
    from ..utils.exceptions import JobCancelled, JobQueueError

    h, w = image_hw
    image = jnp.asarray(
        np.random.default_rng(seed).random((1, h, w, 3)), jnp.float32
    )
    pos = neg = jnp.zeros((1, 4, 8), jnp.float32)
    bundle = types.SimpleNamespace(params=None)

    store = JobStore()
    store.placement = _TrimMaster()
    manager = DurabilityManager(journal_dir, snapshot_every=64, fsync_every=0)
    store.journal_sink = manager.record

    # live standby: attach-consistent subscription + replica tail
    sub = manager.subscribe_replica()
    replica = StandbyReplica()
    replica.reset(sub.snapshot_state, sub.head_lsn, sub.epoch)
    tail_stop = threading.Event()

    def tail_body() -> None:
        while not tail_stop.is_set():
            sub.wait(0.02)
            for record in sub.pop():
                replica.apply(record)

    tail = threading.Thread(target=tail_body, name="chaos-cancel-standby", daemon=True)
    tail.start()

    def worker_body(wid: str) -> None:
        _, grid, extracted = upscale_ops.prepare_upscaled_tiles(
            image, upscale_by, tile, padding, "bicubic", None
        )
        key = jax.random.key(seed)
        job = run_async_in_server_loop(
            store.wait_for_tile_job(job_id, grace_seconds=20), timeout=30
        )
        if job is None:
            return
        sampler = GrantSampler(
            _stub_process, None, extracted, key, grid.positions_array(),
            None, None, k_max=1, role="worker",
        )
        flush_pending: dict[int, list] = {}

        def pull():
            try:
                return run_async_in_server_loop(
                    store.pull_tasks(job_id, wid, timeout=0.2), timeout=10
                ) or None
            except JobQueueError:
                return None

        def sample(chunk):
            time.sleep(tile_delay)  # keep work in flight at cancel time
            return sampler.sample(chunk)

        def emit(tile_idx, arr):
            flush_pending[int(tile_idx)] = [
                {
                    "batch_idx": i,
                    "image": img_utils.encode_image_data_url(arr[i]),
                }
                for i in range(arr.shape[0])
            ]

        def flush(is_final):
            if not flush_pending:
                return
            grouped = dict(flush_pending)
            flush_pending.clear()
            try:
                run_async_in_server_loop(
                    store.submit_flush(job_id, wid, grouped), timeout=10
                )
            except JobQueueError:
                pass  # cancelled + cleaned up under us

        try:
            TilePipeline(
                pull=pull, sample=sample, chunks=sampler.chunks,
                emit=emit, flush=flush, role="worker",
                span_attrs={"worker_id": wid}, threaded=False,
            ).run()
        except JobQueueError:
            pass

    cancel_outcome: dict[str, Any] = {}

    def canceller_body() -> None:
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            job = run_async_in_server_loop(
                store.get_tile_job(job_id), timeout=10
            )
            if job is not None and len(job.completed) >= cancel_after:
                started = time.monotonic()
                accounting = run_async_in_server_loop(
                    store.cancel_job(job_id, reason=reason), timeout=10
                )
                cancel_outcome["latency_ms"] = (
                    time.monotonic() - started
                ) * 1000.0
                cancel_outcome["accounting"] = accounting
                cancel_outcome["completed"] = len(job.completed)
                with manager._lock:
                    cancel_outcome["state"] = dstate.clone(manager._state)
                cancel_outcome["stats"] = store.stats_unlocked()
                return
            time.sleep(0.005)

    raised = ""
    got_reason = ""
    with contextlib.ExitStack() as stack:
        stack.enter_context(_ensure_server_loop())
        stack.enter_context(
            mock.patch.object(
                elastic, "_jit_tile_processor", lambda *a, **k: _stub_process
            )
        )
        stack.enter_context(
            mock.patch.object(
                config_mod, "get_worker_timeout_seconds",
                lambda path=None: worker_timeout,
            )
        )
        stack.enter_context(
            mock.patch.dict(
                os.environ,
                {"CDT_DETERMINISTIC_BLEND": "1", "CDT_TILE_BATCH": "1"},
            )
        )
        ctx = ExecutionContext(
            server=types.SimpleNamespace(job_store=store),
            config={"workers": []},
        )
        threads = [
            threading.Thread(target=worker_body, args=(wid,), daemon=True)
            for wid in workers
        ]
        canceller = threading.Thread(target=canceller_body, daemon=True)
        for t in threads:
            t.start()
        canceller.start()
        try:
            elastic.run_master_elastic(
                bundle, image, pos, neg,
                job_id=job_id,
                enabled_worker_ids=list(workers),
                upscale_by=upscale_by, tile=tile, padding=padding,
                steps=1, sampler="euler", scheduler="karras",
                cfg=1.0, denoise=0.3, seed=seed, context=ctx,
            )
        except JobCancelled as exc:
            raised = type(exc).__name__
            got_reason = exc.reason
        finally:
            for t in threads:
                t.join(timeout=30)
            canceller.join(timeout=30)
            # final drain of the replication tee, then stop the tail
            for record in sub.pop(max_items=100000):
                replica.apply(record)
            tail_stop.set()
            tail.join(timeout=10)
            manager.close()

    journal_state, _ = recover_state(journal_dir)
    replica_state = dstate.clone(replica._state)
    state_after_cancel = cancel_outcome.get("state", {})
    job_at_cancel = state_after_cancel.get("jobs", {}).get(job_id, {})
    return CancelResult(
        raised=raised,
        reason=got_reason,
        accounting=cancel_outcome.get("accounting") or {},
        completed_before_cancel=int(cancel_outcome.get("completed", 0)),
        stats_after=cancel_outcome.get("stats") or {},
        state_after_cancel=job_at_cancel,
        journal_jobs_after=dict(journal_state.get("jobs", {})),
        replica_jobs_after=dict(replica_state.get("jobs", {})),
        replica_saw_cancel=bool(job_at_cancel.get("cancelled", False)),
        idempotent_replay=verify_idempotent_replay(journal_dir),
        cancel_latency_ms=float(cancel_outcome.get("latency_ms", 0.0)),
    )


class _PoisonCrash(RuntimeError):
    """Simulated worker-process death on a poison payload."""


@dataclasses.dataclass
class PoisonResult:
    """Outcome of a poison-tile run: quarantine evidence, breaker
    states, and the degraded canvas."""

    output: np.ndarray
    poison_tile: int
    poison_rect: tuple[int, int, int, int]   # y, x, h, w in output coords
    crashed_workers: list[str]
    attempts: dict
    quarantined: list[int]
    pardons: list[str]
    health_after: dict
    charged_states: list[str]   # breaker states observed right after each crash
    journal_quarantined: list[int]


def run_chaos_poison(
    seed: int = 0,
    *,
    journal_dir: Optional[str] = None,
    workers: Sequence[str] = ("w1", "w2", "w3"),
    image_hw: tuple[int, int] = (96, 96),
    tile: int = 48,
    padding: int = 16,
    upscale_by: float = 2.0,
    worker_timeout: float = 0.4,
    job_id: str = "chaos-poison-job",
    poison_tile: int = 0,
    max_attempts: int = 3,
    poison_policy: str = "degrade",
) -> PoisonResult:
    """Poison-tile acceptance: tile ``poison_tile``'s payload crashes
    EVERY worker that samples it (each crash also charges the worker's
    circuit breaker with failure_threshold=1 — the harshest cascade
    setting). The store must quarantine the tile after ``max_attempts``
    crash-requeues, the job must complete DEGRADED (the quarantined
    region blended from the base image, every other tile bit-identical
    to a clean run), and the breaker pardon must leave NO worker
    quarantined on account of the poison.

    The master is trimmed out of the pull set (``_TrimMaster``) so the
    poison can only travel through workers; its deadline fallback
    covers whatever healthy tiles the dead fleet left behind —
    explicitly skipping the quarantined one."""
    import jax
    import jax.numpy as jnp

    from ..graph import ExecutionContext
    from ..graph import usdu_elastic as elastic
    from ..graph.tile_pipeline import GrantSampler, TilePipeline
    from ..jobs import JobStore
    from ..ops import upscale as upscale_ops
    from ..utils import config as config_mod
    from ..utils import image as img_utils
    from ..utils.async_helpers import run_async_in_server_loop
    from ..utils.exceptions import JobQueueError
    from .health import HealthRegistry

    h, w = image_hw
    image = jnp.asarray(
        np.random.default_rng(seed).random((1, h, w, 3)), jnp.float32
    )
    pos = neg = jnp.zeros((1, 4, 8), jnp.float32)
    bundle = types.SimpleNamespace(params=None)

    health = HealthRegistry(failure_threshold=1, suspect_threshold=1)
    pardons: list[str] = []
    charged_states: list[str] = []
    captured: dict[str, Any] = {}

    store = JobStore(max_attempts=max_attempts, poison_policy=poison_policy)
    store.placement = _TrimMaster()

    def pardon(worker_ids: list) -> None:
        # fires at quarantine time ON the server loop: snapshot the
        # job's final attempt/quarantine books here — the job may be
        # cleaned up before any poller can observe them
        job_obj = store.tile_jobs.get(job_id)
        if job_obj is not None:
            captured["attempts"] = {
                int(t): int(n) for t, n in dict(job_obj.attempts).items()
            }
            captured["quarantined"] = sorted(job_obj.quarantined_tiles)
        for wid in worker_ids:
            pardons.append(str(wid))
            health.pardon(str(wid))

    store.poison_pardon = pardon
    manager = None
    if journal_dir:
        from ..durability import DurabilityManager

        manager = DurabilityManager(journal_dir, snapshot_every=64, fsync_every=0)
        store.journal_sink = manager.record

    crashed: list[str] = []
    crashed_lock = threading.Lock()

    def worker_body(wid: str) -> None:
        _, grid, extracted = upscale_ops.prepare_upscaled_tiles(
            image, upscale_by, tile, padding, "bicubic", None
        )
        key = jax.random.key(seed)
        job = run_async_in_server_loop(
            store.wait_for_tile_job(job_id, grace_seconds=20), timeout=30
        )
        if job is None:
            return
        sampler = GrantSampler(
            _stub_process, None, extracted, key, grid.positions_array(),
            None, None, k_max=1, role="worker",
        )
        flush_pending: dict[int, list] = {}

        def pull():
            # persistent pull: park through empty windows so a
            # requeued poison tile finds a live victim (a real worker
            # process keeps polling exactly like this)
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                try:
                    job_obj = run_async_in_server_loop(
                        store.get_tile_job(job_id), timeout=10
                    )
                    if job_obj is None or job_obj.cancelled:
                        return None
                    done = (
                        len(job_obj.completed)
                        + len(job_obj.quarantined_tiles)
                    )
                    if done >= job_obj.total_tasks:
                        return None
                    grant = run_async_in_server_loop(
                        store.pull_tasks(job_id, wid, timeout=0.1), timeout=10
                    )
                except JobQueueError:
                    return None
                if grant:
                    return grant
            return None

        def sample(chunk):
            if int(poison_tile) in [int(t) for t in chunk]:
                # the poison payload kills the worker process; the
                # breaker observes the death as a transport failure
                charged_states.append(
                    health.record_failure(wid).value
                )
                raise _PoisonCrash(f"{wid} crashed sampling tile {poison_tile}")
            return sampler.sample(chunk)

        def emit(tile_idx, arr):
            flush_pending[int(tile_idx)] = [
                {
                    "batch_idx": i,
                    "image": img_utils.encode_image_data_url(arr[i]),
                }
                for i in range(arr.shape[0])
            ]

        def flush(is_final):
            if not flush_pending:
                return
            grouped = dict(flush_pending)
            flush_pending.clear()
            try:
                run_async_in_server_loop(
                    store.submit_flush(job_id, wid, grouped), timeout=10
                )
            except JobQueueError:
                pass

        try:
            TilePipeline(
                pull=pull, sample=sample, chunks=sampler.chunks,
                emit=emit, flush=flush, role="worker",
                span_attrs={"worker_id": wid}, threaded=False,
            ).run()
        except _PoisonCrash as exc:
            debug_log(f"chaos poison worker died: {exc}")
            with crashed_lock:
                crashed.append(wid)
        except JobQueueError:
            pass

    with contextlib.ExitStack() as stack:
        stack.enter_context(_ensure_server_loop())
        stack.enter_context(
            mock.patch.object(
                elastic, "_jit_tile_processor", lambda *a, **k: _stub_process
            )
        )
        stack.enter_context(
            mock.patch.object(
                config_mod, "get_worker_timeout_seconds",
                lambda path=None: worker_timeout,
            )
        )
        stack.enter_context(
            mock.patch.dict(
                os.environ,
                {"CDT_DETERMINISTIC_BLEND": "1", "CDT_TILE_BATCH": "1"},
            )
        )
        ctx = ExecutionContext(
            server=types.SimpleNamespace(job_store=store),
            config={"workers": []},
        )
        threads = [
            threading.Thread(target=worker_body, args=(wid,), daemon=True)
            for wid in workers
        ]
        # monitor: fallback snapshots of the live job's books while it
        # exists (the pardon hook takes the authoritative final one)
        monitor_stop = threading.Event()

        def monitor_body() -> None:
            while not monitor_stop.is_set():
                try:
                    job_obj = run_async_in_server_loop(
                        store.get_tile_job(job_id), timeout=10
                    )
                except Exception:  # noqa: BLE001 - loop shutting down
                    return
                if job_obj is not None:
                    if job_obj.attempts:
                        captured["attempts"] = {
                            int(t): int(n)
                            for t, n in dict(job_obj.attempts).items()
                        }
                    if job_obj.quarantined_tiles:
                        captured["quarantined"] = sorted(
                            job_obj.quarantined_tiles
                        )
                time.sleep(0.02)

        monitor = threading.Thread(target=monitor_body, daemon=True)
        monitor.start()
        for t in threads:
            t.start()
        # ghost ids pad the master's collection deadline (timeout x N)
        # so three crash->timeout->requeue cycles fit before its local
        # fallback would race the quarantine
        padded_ids = list(workers) + [f"ghost{i}" for i in range(9)]
        try:
            out = elastic.run_master_elastic(
                bundle, image, pos, neg,
                job_id=job_id,
                enabled_worker_ids=padded_ids,
                upscale_by=upscale_by, tile=tile, padding=padding,
                steps=1, sampler="euler", scheduler="karras",
                cfg=1.0, denoise=0.3, seed=seed, context=ctx,
            )
        finally:
            for t in threads:
                t.join(timeout=30)
            monitor_stop.set()
            monitor.join(timeout=10)
            if manager is not None:
                manager.close()

    journal_quarantined: list[int] = []
    if journal_dir:
        from ..durability.recovery import recover_state

        state, _ = recover_state(journal_dir)
        job_state = state.get("jobs", {}).get(job_id, {})
        journal_quarantined = [int(t) for t in job_state.get("quarantined", [])]

    _, _, grid = upscale_ops.plan_grid(h, w, upscale_by, tile, padding, None)
    y, x = grid.positions[int(poison_tile)]
    rect = (int(y), int(x), int(grid.padded_h), int(grid.padded_w))
    return PoisonResult(
        output=np.asarray(out),
        poison_tile=int(poison_tile),
        poison_rect=rect,
        crashed_workers=sorted(crashed),
        attempts=captured.get("attempts", {}),
        quarantined=captured.get("quarantined", []),
        pardons=list(pardons),
        health_after=health.snapshot(),
        charged_states=charged_states,
        journal_quarantined=journal_quarantined,
    )


# --------------------------------------------------------------------------
# cross-job continuous batching + step-level preemption scenarios
# --------------------------------------------------------------------------


def _stub_stepwise(n_steps: int, signature: tuple = ("chaos-stepwise",)):
    """Step-resumable stand-in for the jitted stepwise tile processor
    (ops/stepwise.py): each step adds keyed noise derived from
    (tile key, step index) — a pure function of per-item inputs, so
    mixed-batch / preempt-resume runs are bit-identical to solo runs —
    and finish snaps to the uint8 grid so the PNG envelope is
    lossless (exactly the `_stub_process` contract)."""
    import jax
    import jax.numpy as jnp

    def init(params, tile, key):
        return tile + 0.0

    def step(params, x, key, pos, neg, yx, i):
        ki = jax.random.fold_in(key, i)
        return jnp.clip(
            x + (0.05 / max(1, n_steps)) * jax.random.normal(ki, x.shape),
            0.0,
            1.0,
        )

    def finish(params, x):
        return jnp.round(jnp.clip(x, 0.0, 1.0) * 255.0) / 255.0

    return types.SimpleNamespace(
        init=init, step=step, finish=finish, n_steps=int(n_steps),
        signature=tuple(signature),
    )


class _WideBatches:
    """Placement stub for xjob scenarios: pulls claim whole grants (the
    executor shapes its own device batches), and the master id is
    unused — the executor is the only compute participant."""

    def __init__(self, size: int = 64):
        self.size = int(size)

    def may_pull(self, worker_id: str, pending: int) -> bool:
        return True

    def batch_size(self, worker_id: str, pending: int) -> int:
        return self.size


@dataclasses.dataclass
class XJobResult:
    """Outcome of one cross-job continuous-batching fleet run."""

    canvases: dict[str, np.ndarray]       # job id -> blended canvas
    stats: dict                           # executor summary stats
    fill_ratio: float
    completion_order: list                # (job_id, tile_idx) in finish order
    preempted_jobs: list                  # jobs flagged during the run
    evictions: int
    resumes_device: int
    resumes_checkpoint: int
    resumes_recompute: int
    leaks: dict                           # job id -> leak accounting
    tiles_by_job: dict                    # job id -> accepted tile count
    # chip-time attribution captured on a run-local UsageMeter:
    # {"rollup": per-tenant/lane/job view, "totals": exact ns identity}
    usage: dict = dataclasses.field(default_factory=dict)


def run_chaos_xjob(
    seed: int = 0,
    *,
    jobs: Optional[Sequence[dict]] = None,
    k_max: int = 8,
    bucket_multiple: int = 1,
    cross_job: bool = True,
    steps: int = 4,
    lanes: Sequence[str] = ("premium", "batch"),
    premium: Optional[dict] = None,
    drop_checkpoints: bool = False,
    tile: int = 64,
    padding: int = 16,
    upscale_by: float = 2.0,
    trace_jsonl: Optional[str] = None,
) -> XJobResult:
    """One in-process cross-job continuous-batching run: N small jobs
    (different tenants/images/seeds, same geometry family) drain
    through ONE CrossJobExecutor against a real JobStore wired to a
    real PreemptionCoordinator — the production protocol shape with
    the transports removed.

    `jobs`: per-job specs ``{"job_id", "seed", "tenant", "lane",
    "image_hw"}``; defaults to four 3-tile jobs across two tenants on
    the "batch" lane. Each job's tiles blend into its own
    DeterministicHostCanvas at final flush; the caller compares each
    canvas against a SOLO run of the same spec (``jobs=[spec]``) —
    bit-identity is the acceptance bar.

    `premium`: ``{"job_id", "seed", "tenant", "image_hw",
    "after_tiles": N}`` — injected ON THE EXECUTOR THREAD after the
    fleet completes N tiles (deterministic, no timing race): the store
    inits it on the top lane, the coordinator flags every running
    batch-lane job, the executor checkpoints + releases their
    in-flight tiles at the next step boundary, the premium job's
    tiles take the freed slots, and on settle the flags lift and the
    evicted work resumes from its checkpoints.

    `drop_checkpoints=True` withholds retained checkpoints at re-grant
    (the master-restart / worker-crash story: checkpoints are volatile
    by design) so resumed tiles recompute from step 0 — the canvas
    must STILL be bit-identical.

    `cross_job=False` restricts every device batch to a single job's
    items: the per-job baseline tests/test_chaos_xjob.py compares
    the fill ratio against.
    """
    import jax
    import jax.numpy as jnp

    from ..graph.batch_executor import CrossJobExecutor, XJobHandle
    from ..jobs import JobStore
    from ..ops import tiles as tile_ops
    from ..ops import upscale as upscale_ops
    from ..scheduler.preempt import PreemptionCoordinator
    from ..utils import image as img_utils
    from ..utils.async_helpers import run_async_in_server_loop

    if jobs is None:
        jobs = [
            {
                "job_id": f"xjob-{i}",
                "seed": seed + i,
                "tenant": "tenant-a" if i % 2 == 0 else "tenant-b",
                "lane": "batch",
                "image_hw": (32, 96),  # 3 tiles: ragged vs pow2 buckets
            }
            for i in range(4)
        ]
    proc = _stub_stepwise(steps)

    store = JobStore()
    store.placement = _WideBatches()
    coordinator = PreemptionCoordinator(list(lanes), store, enabled=True)
    store.preempt_policy = coordinator
    # run-local chip-time attribution: the executor meters into this
    # meter (and it is swapped in as the process global below so the
    # store's attrs/waste notes land in the same place), so the
    # result's usage block is exactly THIS run's burn
    from ..telemetry.usage import UsageMeter, set_usage_meter

    usage_meter = UsageMeter()
    executor = CrossJobExecutor(
        k_max=k_max,
        bucket_multiple=bucket_multiple,
        cross_job=cross_job,
        preempt_enabled=True,
        usage_meter=usage_meter,
    )

    canvases: dict[str, np.ndarray] = {}
    tiles_by_job: dict[str, int] = {}
    preempted_jobs: list[str] = []

    def make_handle(spec: dict, lane: str, worker_id: str) -> XJobHandle:
        job_id = str(spec["job_id"])
        job_seed = int(spec.get("seed", seed))
        h, w = spec.get("image_hw", (32, 96))
        image = jnp.asarray(
            np.random.default_rng(job_seed).random((1, h, w, 3)), jnp.float32
        )
        upscaled, grid, extracted = upscale_ops.prepare_upscaled_tiles(
            image, upscale_by, tile, padding, "bicubic", None
        )
        positions = grid.positions_array()
        from ..parallel.seeds import fold_job_key

        base_key = fold_job_key(jax.random.key(job_seed), job_id)
        canvas = tile_ops.DeterministicHostCanvas(upscaled, grid)
        flush_pending: dict[int, list] = {}

        def pull():
            async def pull_batch():
                tasks = await store.pull_tasks(job_id, worker_id, timeout=0.05)
                if not tasks:
                    return None
                checkpoints = {}
                if not drop_checkpoints:
                    checkpoints = await store.checkpoints_for(job_id, tasks)
                elif tasks:
                    # the crash story: retained checkpoints die with the
                    # volatile store; pop them so recompute is honest
                    await store.checkpoints_for(job_id, tasks)
                return {"tile_idxs": tasks, "checkpoints": checkpoints}

            return run_async_in_server_loop(pull_batch(), timeout=10)

        def emit(tile_idx: int, arr) -> None:
            flush_pending[int(tile_idx)] = [
                {
                    "batch_idx": i,
                    "image": img_utils.encode_image_data_url(arr[i]),
                }
                for i in range(arr.shape[0])
            ]
            maybe_inject_premium()

        def flush(is_final: bool) -> None:
            if flush_pending:
                grouped = dict(flush_pending)
                flush_pending.clear()
                accepted = run_async_in_server_loop(
                    store.submit_flush(job_id, worker_id, grouped), timeout=10
                )
                tiles_by_job[job_id] = tiles_by_job.get(job_id, 0) + accepted
            if is_final:
                finalize()

        def finalize() -> None:
            # drain THIS job's accepted results and blend its canvas
            # (sorted-order deferred compositing — arrival order is
            # irrelevant), then settle the job at the store so the
            # coordinator lifts any flags it raised
            async def drain():
                job = await store.get_tile_job(job_id)
                items = []
                while job is not None and not job.results.empty():
                    items.append(job.results.get_nowait())
                return items

            for tile_idx, payload in run_async_in_server_loop(
                drain(), timeout=10
            ):
                batch = [
                    img_utils.decode_image_data_url(e["image"])
                    for e in sorted(payload, key=lambda e: e["batch_idx"])
                ]
                y, x = grid.positions[tile_idx]
                canvas.blend(jnp.asarray(np.stack(batch, axis=0)), y, x)
            canvases[job_id] = np.asarray(canvas.result())
            run_async_in_server_loop(store.cleanup_tile_job(job_id), timeout=10)

        def release(idxs: list, checkpoints: dict) -> None:
            if job_id not in preempted_jobs:
                preempted_jobs.append(job_id)
            run_async_in_server_loop(
                store.release_tasks(
                    job_id, worker_id, idxs, checkpoints=checkpoints
                ),
                timeout=10,
            )

        def preempt_check() -> bool:
            async def read():
                job = await store.get_tile_job(job_id)
                return bool(job is not None and job.preempt_requested)

            return run_async_in_server_loop(read(), timeout=10)

        run_async_in_server_loop(
            store.init_tile_job(
                job_id, list(range(grid.num_tiles)), lane=lane,
                tenant=str(spec.get("tenant", "default")),
            ),
            timeout=10,
        )
        return XJobHandle(
            job_id=job_id,
            proc=proc,
            params=None,
            extracted=extracted,
            positions=positions,
            pos=jnp.zeros((1,), jnp.float32),
            neg=jnp.zeros((1,), jnp.float32),
            base_key=base_key,
            pull=pull,
            emit=emit,
            flush=flush,
            release=release,
            preempt_check=preempt_check,
            tenant=str(spec.get("tenant", "default")),
            lane=lane,
            priority=list(lanes).index(lane) if lane in lanes else len(lanes),
        )

    injected = {"done": premium is None}

    def inject_premium() -> None:
        injected["done"] = True
        spec = {
            "job_id": premium.get("job_id", "xjob-premium"),
            "seed": premium.get("seed", seed + 1000),
            "tenant": premium.get("tenant", "tenant-premium"),
            "image_hw": premium.get("image_hw", (32, 64)),
        }
        handle = make_handle(spec, lane=str(lanes[0]), worker_id="xworker")
        executor.register(handle)

    def maybe_inject_premium() -> None:
        """Runs on the executor thread (from a batch job's emit): once
        the fleet has finished `after_tiles` tiles, init + register the
        premium job — deterministically mid-flight."""
        if injected["done"] or "after_tiles" not in premium:
            return
        if executor.tiles_finished >= int(premium["after_tiles"]):
            inject_premium()

    if premium is not None and premium.get("after_dispatches"):
        # inject at a STEP boundary (after the Nth device dispatch),
        # while the batch jobs' tiles are mid-trajectory — the scenario
        # that forces checkpointed eviction rather than a clean handoff
        target = int(premium["after_dispatches"])
        orig_step_batch = executor._step_batch

        def hooked_step_batch(batch):
            orig_step_batch(batch)
            if not injected["done"] and executor.dispatches >= target:
                inject_premium()

        executor._step_batch = hooked_step_batch

    chaos_tracer = Tracer(clock=FakeClock())
    previous_tracer = get_tracer()
    trace_id = f"exec_chaos_xjob_{seed}"
    with contextlib.ExitStack() as stack:
        stack.enter_context(_ensure_server_loop())
        stack.enter_context(
            mock.patch.dict(os.environ, {"CDT_DETERMINISTIC_BLEND": "1"})
        )
        stack.callback(set_usage_meter, set_usage_meter(usage_meter))
        set_tracer(chaos_tracer)
        stack.callback(set_tracer, previous_tracer)
        token = chaos_tracer.activate(trace_id)
        stack.callback(chaos_tracer.deactivate, token)
        for spec in jobs:
            executor.register(
                make_handle(
                    spec, lane=str(spec.get("lane", lanes[-1])),
                    worker_id="xworker",
                )
            )
        with chaos_tracer.span(
            "chaos_xjob", trace_id=trace_id, seed=seed,
            cross_job=cross_job,
        ):
            stats = executor.run()
        if trace_jsonl:
            chaos_tracer.write_jsonl(trace_id, trace_jsonl)
        # leak accounting BEFORE teardown: every job must have settled
        # with nothing pending/assigned/checkpointed
        async def leak_check():
            out = {}
            async with store.lock:
                for job_id in sorted(store.tile_jobs):
                    job = store.tile_jobs[job_id]
                    out[job_id] = {
                        "pending": job.pending.qsize(),
                        "assigned": sum(
                            len(v) for v in job.assigned.values()
                        ),
                        "checkpoints": len(job.checkpoints),
                        "completed": len(job.completed),
                    }
            return out

        leaks = run_async_in_server_loop(leak_check(), timeout=10)

    return XJobResult(
        canvases=canvases,
        stats=stats,
        fill_ratio=executor.fill_ratio(),
        completion_order=list(executor.completion_order),
        preempted_jobs=list(preempted_jobs),
        evictions=executor.preempt_evictions,
        resumes_device=executor.resumes_device,
        resumes_checkpoint=executor.resumes_checkpoint,
        resumes_recompute=executor.resumes_recompute,
        leaks=leaks,
        tiles_by_job=dict(tiles_by_job),
        usage={
            "rollup": usage_meter.rollup(),
            "totals": usage_meter.totals(),
        },
    )
