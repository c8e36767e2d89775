"""Central timeouts, intervals, and env-var overrides.

Parity with reference utils/constants.py (its own environment settings
kept, names adapted to the TPU runtime). A value read through
`_env_int`/`_env_float`/`os.environ` is a deployment setting listed in
utils/knob_registry.py; a plain literal is the internal of one
mechanism and has one value.
"""

from __future__ import annotations

import os


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default


# --- roles ---------------------------------------------------------------
# Worker processes are launched with this env var set; it suppresses
# master-side startup behavior (auto-launch, signal cleanup).
# Reference: distributed.py:48 (COMFYUI_IS_WORKER).
WORKER_ENV_FLAG = "CDT_IS_WORKER"
MASTER_PID_ENV = "CDT_MASTER_PID"
# Chip pinning for process-per-chip compatibility mode (the TPU analog of
# CUDA_VISIBLE_DEVICES in workers/process/lifecycle.py:33).
TPU_VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"

# --- heartbeat / liveness ------------------------------------------------
# Reference utils/constants.py:43-47 (COMFYUI_HEARTBEAT_*).
HEARTBEAT_INTERVAL_SECONDS = _env_float("CDT_HEARTBEAT_INTERVAL", 5.0)
HEARTBEAT_TIMEOUT_SECONDS = _env_float("CDT_HEARTBEAT_TIMEOUT", 60.0)
# The collector waits in slices of timeout/20 so interrupts propagate fast.
COLLECTOR_WAIT_SLICES = 20

# --- payloads ------------------------------------------------------------
# Reference upscale/job_store.py:12 (COMFYUI_MAX_PAYLOAD_SIZE 50MB) and
# utils/constants.py:43 (MAX_BATCH=20 tiles per flush).
MAX_PAYLOAD_SIZE = _env_int("CDT_MAX_PAYLOAD_SIZE", 50 * 1024 * 1024)
PAYLOAD_HEADROOM = 1024 * 1024
MAX_TILE_BATCH = _env_int("CDT_MAX_BATCH", 20)
# Tiles diffused per scan step in the USDU compute core (batch-K UNet/
# VAE programs; MXU utilization knob). 1 = reference numerics
# (bit-identical to the committed goldens); >1 is allclose.
# CDT_TILE_BATCH overrides; unset defaults by platform at first use:
# CPU stays 1 (golden-exact), accelerators get 8 (batch-1 convs leave
# most of the MXU idle).
def tile_scan_batch() -> int:
    """Platform-aware CDT_TILE_BATCH resolution. The callers are
    compute paths, so the backend is already up; a backend that cannot
    answer raises here rather than quietly taking the CPU value."""
    explicit = _env_int("CDT_TILE_BATCH", 0)
    if explicit > 0:
        return explicit
    import jax

    return 1 if jax.default_backend() == "cpu" else 8
MAX_AUDIO_PAYLOAD_BYTES = _env_int("CDT_MAX_AUDIO_PAYLOAD_BYTES", 256 * 1024 * 1024)

# --- orchestration concurrency ------------------------------------------
# Reference api/queue_orchestration.py semaphores (probe=8/prep=4/media=2)
# and utils/constants.py COMFYUI_ORCHESTRATION_* env overrides.
PROBE_CONCURRENCY = _env_int("CDT_ORCHESTRATION_PROBE_CONCURRENCY", 8)
PREP_CONCURRENCY = _env_int("CDT_ORCHESTRATION_PREP_CONCURRENCY", 4)
MEDIA_SYNC_CONCURRENCY = _env_int("CDT_ORCHESTRATION_MEDIA_CONCURRENCY", 2)
MEDIA_SYNC_TIMEOUT_SECONDS = _env_float("CDT_MEDIA_SYNC_TIMEOUT", 120.0)

# --- probes / retries ----------------------------------------------------
PROBE_TIMEOUT_SECONDS = _env_float("CDT_PROBE_TIMEOUT", 5.0)
DISPATCH_TIMEOUT_SECONDS = _env_float("CDT_DISPATCH_TIMEOUT", 30.0)
REQUEST_RETRY_COUNT = 5
REQUEST_RETRY_BACKOFF = 0.5
WORK_PULL_RETRY_COUNT = 10
WORK_PULL_RETRY_CAP_SECONDS = 30.0

# --- circuit breaker (resilience/health.py) -------------------------------
# A worker becomes SUSPECT after this many consecutive transport
# failures, QUARANTINED (circuit open: no dispatch, tiles requeued)
# at the failure threshold, and is probed again (half-open) once the
# cooldown elapses.
CIRCUIT_SUSPECT_THRESHOLD = 2
CIRCUIT_FAILURE_THRESHOLD = 5
CIRCUIT_COOLDOWN_SECONDS = 30.0

# --- watchdog (telemetry/watchdog.py) -------------------------------------
# The straggler & stall detector: a worker whose rolling-median tile
# latency exceeds STRAGGLER_FACTOR x the global rolling median (with at
# least MIN_SAMPLES completions in its window) is flagged suspect; a
# job with no completion progress for STALL seconds gets its in-flight
# tail tiles speculatively re-enqueued. CDT_WATCHDOG=0 disables the
# server's background monitor thread entirely.
WATCHDOG_INTERVAL_SECONDS = 2.0
WATCHDOG_STRAGGLER_FACTOR = _env_float("CDT_WATCHDOG_STRAGGLER_FACTOR", 4.0)
WATCHDOG_MIN_SAMPLES = 3
WATCHDOG_STALL_SECONDS = _env_float("CDT_WATCHDOG_STALL_SECONDS", 30.0)
WATCHDOG_LATENCY_WINDOW = 64

# --- scheduler control plane (scheduler/) ---------------------------------
# Admission lanes in strict priority order as "name:depth" pairs; a
# request lands in a lane by its payload's `lane` field (default
# CDT_SCHED_DEFAULT_LANE). A full lane answers HTTP 429 + Retry-After.
SCHED_LANES = os.environ.get(
    "CDT_SCHED_LANES", "interactive:64,batch:256,background:1024"
)
SCHED_DEFAULT_LANE = os.environ.get("CDT_SCHED_DEFAULT_LANE", "interactive")
# Orchestrations allowed to run concurrently; queued requests wait in
# their lane (deficit-round-robin over tenants) for a grant slot.
SCHED_MAX_ACTIVE = _env_int("CDT_SCHED_MAX_ACTIVE", 4)
# DRR quantum in cost units added per tenant visit; a tenant's actual
# replenishment is quantum x its weight (CDT_SCHED_TENANT_WEIGHTS,
# "tenantA=3,tenantB=1"; unlisted tenants weigh 1).
SCHED_QUANTUM = 1.0
SCHED_TENANT_WEIGHTS = os.environ.get("CDT_SCHED_TENANT_WEIGHTS", "")
# How long the queue route parks a request awaiting its grant before
# answering 429 (the client should back off and retry).
SCHED_GRANT_TIMEOUT_SECONDS = 120.0
# Cost-aware placement (scheduler/placement.py): per-worker EWMA over
# pull->submit tile latencies; a worker's pull batch scales with its
# relative speed up to MAX_PULL_BATCH (BASE_PULL_BATCH at speed 1.0).
# Inside the last TAIL_TILES of a job, suspect/slow workers are denied
# pulls so the tail lands on fast healthy participants.
SCHED_EWMA_ALPHA = 0.25
SCHED_MIN_SAMPLES = 2
SCHED_BASE_PULL_BATCH = 2
SCHED_MAX_PULL_BATCH = 8
SCHED_TAIL_TILES = _env_int("CDT_SCHED_TAIL_TILES", 2)
# A worker slower than TRIM_RATIO x the fleet's mean speed is trimmed
# from the tail (it may still pull while the queue is deep).
SCHED_TRIM_RATIO = 0.5

# --- cross-job continuous batching + step-level preemption ----------------
# CDT_XJOB_BATCH=1 routes the elastic master/worker loops through the
# cross-job continuous-batching executor (graph/batch_executor.py) when
# the job's sampler supports step-resumable execution: tiles from
# different jobs/tenants share shape-bucketed device batches and
# premium-lane arrivals preempt running lower-lane work at step
# boundaries. 0 (default) keeps the per-job scan tier exactly.
def xjob_batch_enabled() -> bool:
    return _env_int("CDT_XJOB_BATCH", 0) == 1


# Step-level preemption master-side: 1 (default) lets the scheduler
# coordinator flag running lower-lane jobs for eviction when a
# higher-lane job arrives with outstanding work; executors checkpoint
# and release at the next step boundary. Inert while every job shares
# one lane (legacy single-lane deployments see no behavior change).
PREEMPT_ENABLED = _env_int("CDT_PREEMPT", 1)
# Brownout integration: at what shed level the brownout controller
# also EVICTS running work from shed lanes (not just rejects new
# admissions). 0 = never (default: brownout stays admission-only).
PREEMPT_BROWNOUT_LEVEL = _env_int("CDT_PREEMPT_BROWNOUT_LEVEL", 0)
# Per-job byte budget for retained preemption checkpoints on the
# master (they are volatile and never journaled); beyond it — or on
# any malformed payload — the tile recomputes from step 0, which is
# the bit-identity reference anyway.
PREEMPT_CHECKPOINT_MB = _env_int("CDT_PREEMPT_CHECKPOINT_MB", 64)


# --- device-resident hot path ---------------------------------------------
# All resolved at CALL time (tests monkeypatch the env).


def xjob_device_resident_enabled() -> bool:
    """1 (default) parks evicted batch latents on-device in the
    cross-job executor: the host checkpoint becomes a lazy spill and a
    re-grant whose payload step matches the parked latent skips the
    b64 decode + H2D re-upload entirely. 0 restores decode-from-host
    on every resume (the bit-identity reference path — the parked
    latent IS the array the checkpoint was encoded from, so both
    resume modes are byte-identical by construction)."""
    return _env_int("CDT_XJOB_DEVICE_RESIDENT", 1) == 1


def xjob_device_resident_budget_bytes() -> int:
    """Byte budget for parked device latents (CDT_XJOB_DEVICE_RESIDENT_MB,
    default 256). Past it the stash evicts oldest-first; an evicted
    entry just means that tile resumes from its host spill."""
    return _env_int("CDT_XJOB_DEVICE_RESIDENT_MB", 256) * 1024 * 1024


def device_canvas_enabled() -> bool:
    """CDT_DEVICE_CANVAS=1 routes master-local tiles through the
    on-device canvas (ops/tiles.DeviceCanvas): one composited d2h per
    flush instead of one readback per tile. Only engages when the tile
    result cache is off — cache population needs host tile bytes at
    blend time. 0 (default) keeps the host canvas paths exactly."""
    return _env_int("CDT_DEVICE_CANVAS", 0) == 1


def precision_for_lane(lane: str) -> str:
    """Precision lane for a scheduler lane: CDT_BF16_LANES is a
    comma-separated list of lane names whose jobs carry their latents
    in bfloat16 between steps ("*" = every lane). Precision joins the
    cross-job batch signature, so bf16 and f32 tiles never share a
    device batch. Default: empty (everything f32)."""
    raw = os.environ.get("CDT_BF16_LANES", "")
    lanes = {part.strip() for part in raw.split(",") if part.strip()}
    if "*" in lanes or (lane and lane in lanes):
        return "bf16"
    return "f32"

# --- request lifecycle armor (deadlines / cancel / poison / brownout) -----
# Failed delivery attempts (crash/timeout requeues) a single tile may
# accumulate before it is quarantined out of the pull set as poison —
# a payload that crashes every worker that touches it must not cascade
# quarantines across the fleet forever.
TILE_MAX_ATTEMPTS = _env_int("CDT_TILE_MAX_ATTEMPTS", 3)
# What a job does when tiles were poison-quarantined: "degrade"
# completes the job with the quarantined region blended from the base
# image; "fail" raises a terminal JobPoisoned error instead.
POISON_POLICY = os.environ.get("CDT_POISON_POLICY", "degrade")
# Default end-to-end job deadline in seconds applied when a request
# names none (0 = no default deadline), and the cap clamped onto any
# client-supplied deadline (0 = uncapped).
JOB_DEADLINE_DEFAULT_SECONDS = _env_float("CDT_JOB_DEADLINE_DEFAULT", 0.0)
JOB_DEADLINE_MAX_SECONDS = _env_float("CDT_JOB_DEADLINE_MAX", 0.0)
# Brownout load-shed controller (scheduler/brownout.py): when queue-wait
# p95 or journal-append p95 crosses its threshold, admission sheds one
# more lowest-priority lane (the top lane is never shed); levels step
# at most once per cooldown and step back down once both signals fall
# under half their thresholds.
SHED_WAIT_P95_SECONDS = _env_float("CDT_SHED_WAIT_P95", 20.0)
SHED_JOURNAL_P95_SECONDS = _env_float("CDT_SHED_JOURNAL_P95", 0.25)
SHED_WINDOW_SAMPLES = 64
SHED_COOLDOWN_SECONDS = _env_float("CDT_SHED_COOLDOWN", 5.0)

# --- elastic tile pipeline (graph/tile_pipeline.py) -----------------------
# The elastic USDU worker/master data path runs as a staged pipeline:
# pull prefetch -> device sampling -> host readback + PNG encode ->
# submit flush. CDT_PIPELINE=0 restores the serial per-tile loop.
PIPELINE_ENABLED = os.environ.get("CDT_PIPELINE", "1") != "0"
# In-flight device batches the sampler may run ahead of the I/O stage
# (queue bound). 1 keeps at most two batches materialized (one in
# readback, one dispatched) beside SDXL's weights on a 16 GB chip;
# raise only on chips with headroom.
PIPELINE_DEPTH = _env_int("CDT_PIPELINE_DEPTH", 1)
# Pull prefetch: claim the next grant while the device samples the
# current one (bounded to ONE grant ahead so a crash never orphans a
# deep claim). 0 pulls synchronously between batches.
PIPELINE_PREFETCH = os.environ.get("CDT_PIPELINE_PREFETCH", "1") != "0"
# Warm the tile-processor compile during the worker's ready-poll
# window so the first pull doesn't eat the first compile. With the persistent compilation cache hot this is a cache
# load, not a compile.
WARM_COMPILE = os.environ.get("CDT_WARM_COMPILE", "1") != "0"

# --- persistent XLA compilation cache -------------------------------------
# First compiles dominate a cold start; the persistent cache makes every
# process after the first skip them. JAX_COMPILATION_CACHE_DIR places
# the cache from outside (jax reads it itself — the program then sets
# no directory in code). Unset, the cache lives at ONE fixed path under
# the checkout, derived from the package location: the path is part of
# the cache key, so a directory that follows the working directory
# never hits.
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_compile_cache_dir() -> str:
    """The in-checkout cache location used when COMPILE_CACHE_ENV is
    unset: <checkout>/.cdt/compile_cache, independent of cwd."""
    package_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(package_dir), ".cdt", "compile_cache")


# --- high availability: lease, standby, failover, push grants -------------
# The active master holds an epoch-numbered lease file in the journal
# dir (durability/lease.py); a warm standby promotes itself when the
# lease has been expired this long. The TTL bounds failover time AND
# the zombie window: a fenced ex-master can keep serving at most one
# TTL after losing the lease before its next journal append raises.
LEASE_TTL_SECONDS = _env_float("CDT_LEASE_TTL", 10.0)
# Standby reconnect/lease-poll cadence while following the active
# master's replication stream (api/standby.py).
STANDBY_POLL_SECONDS = 1.0
# Per-standby replication buffer (records). Overflow marks the stream
# LOST (never drops interior records — a hole would silently desync the
# replica) and the standby re-syncs from a fresh snapshot frame.
STANDBY_BUFFER_RECORDS = 4096
# Consecutive transport/5xx failures against one master address before
# the worker client rotates to the next address in its list.
FAILOVER_AFTER_ERRORS = 2
# Push-mode grants: workers hold the /distributed/events WebSocket and
# wake on pushed grant_available frames instead of pull-polling; 0
# restores the pure pull-poll protocol (the chaos-suite fallback).
PUSH_GRANTS_ENABLED = os.environ.get("CDT_PUSH_GRANTS", "1") != "0"
# How long a push-mode worker parks on the grant signal after an empty
# pull before concluding the queue is drained (one extra wait vs the
# pull protocol's immediate exit).
PUSH_WAIT_SECONDS = 1.0

# --- region mode: quorum lease, sharded masters, autoscaler ---------------
# Quorum lease peers (durability/quorum.py): a comma-separated list of
# peer register directories (one per lease-holder node). Non-empty
# switches the master lease from the shared-filesystem flock sidecar
# to majority agreement across these registers — the standby then
# needs no shared filesystem at all. Empty keeps the file lease.
LEASE_PEERS = [
    p.strip() for p in os.environ.get("CDT_LEASE_PEERS", "").split(",")
    if p.strip()
]
# Shard map for region mode (scheduler/router.py): shards separated by
# ';', each shard a comma-separated master address list (active first,
# standbys after), e.g. "http://a:8188,http://a2:8188;http://b:8188".
# Empty = unsharded (single master, the pre-region topology).
SHARDS_SPEC = os.environ.get("CDT_SHARDS", "")
# Virtual nodes per shard on the consistent-hash ring: more vnodes =
# smoother job spread and smaller reshuffle when a shard joins/leaves.
SHARD_VNODES = 64
# Per-URL backoff for the worker client's master endpoints: after a
# failure burst an address sits out base*2^k seconds (capped) so a
# dead/lagging shard address can't throttle pulls against healthy
# ones; any response resets its schedule.
ROUTER_BACKOFF_BASE_SECONDS = 0.5
ROUTER_BACKOFF_CAP_SECONDS = 30.0
# Usage-driven autoscaler (scheduler/autoscale.py): 1 starts the
# control loop on masters — SLO burn alerts + measured chip-second
# demand drive launch/drain of managed local workers.
AUTOSCALE_ENABLED = _env_int("CDT_AUTOSCALE", 0) == 1
# Seconds between autoscaler evaluations (each evaluation emits one
# decision record with measured chip-second cost/benefit).
AUTOSCALE_INTERVAL_SECONDS = 15.0
# Managed-worker count bounds the controller may scale between.
AUTOSCALE_MIN_WORKERS = _env_int("CDT_AUTOSCALE_MIN", 1)
AUTOSCALE_MAX_WORKERS = _env_int("CDT_AUTOSCALE_MAX", 8)
# Demand/capacity ratio the controller steers toward: above it scale
# up, below half of it (sustained for the hold window) scale down.
AUTOSCALE_TARGET_UTILIZATION = _env_float("CDT_AUTOSCALE_TARGET_UTIL", 0.70)
# Low utilization must persist this long before a scale-down drains a
# worker — scale-up is immediate, scale-down is patient (thrash guard).
AUTOSCALE_DOWN_HOLD_SECONDS = _env_float("CDT_AUTOSCALE_DOWN_HOLD", 120.0)

# --- fleet observability plane (telemetry/fleet.py, telemetry/slo.py) -----
# Master toggle for the fleet plane: 0 disables the monitor thread,
# master-side sampling, and SLO evaluation entirely (the routes then
# answer with enabled=false).
FLEET_ENABLED = os.environ.get("CDT_FLEET", "1") != "0"
# Seconds between master-side sampling passes (fleet sweep + rollup +
# SLO burn-rate evaluation) — also the raw-tier resolution's natural
# cadence.
FLEET_INTERVAL_SECONDS = _env_float("CDT_FLEET_INTERVAL", 10.0)
# Minimum seconds between a worker's piggybacked telemetry snapshots
# (the snapshot rides heartbeat/request_image RPCs it already sends).
FLEET_SNAPSHOT_SECONDS = 10.0
# A worker that stops snapshotting for this long is evicted from the
# fleet view (all its per-worker series drop).
FLEET_TTL_SECONDS = _env_float("CDT_FLEET_TTL", 120.0)
# SLO latency targets: the tile pull->submit p95 objective and the
# journal-append objective the burn-rate alerts evaluate against.
SLO_TILE_P95_SECONDS = _env_float("CDT_SLO_TILE_P95", 5.0)
SLO_JOURNAL_P95_SECONDS = _env_float("CDT_SLO_JOURNAL_P95", 0.25)

# --- usage metering / chip-time attribution (telemetry/usage.py) ----------
# Master toggle for the attribution plane: 0 disables dispatch
# attribution records on both execution tiers and the master-side
# aggregation (the usage route then answers enabled=false).
USAGE_ENABLED = os.environ.get("CDT_USAGE", "1") != "0"
# Closing the loop into admission: 1 multiplies a request's DRR cost by
# the tenant's MEASURED chip-seconds-per-tile ratio (vs the fleet
# mean), so fair share meters what tenants actually burn instead of
# the client's estimated_tiles alone.
USAGE_COST_ENABLED = _env_int("CDT_USAGE_COST", 0) == 1
# Idle usage entries (jobs/tenants with no attribution activity for
# this long) fold into retired aggregates and their retained series
# evict — tenant-id churn must not grow master memory.
USAGE_TTL_SECONDS = 3600.0

# --- device-time profiling plane (telemetry/profiling.py) -----------------
# Master toggle for the transfer ledger: 0 disables the per-dispatch
# device/host split, transfer byte accounting, and the host-tax rollup
# (the profile route then answers ledger enabled=false).
PROFILING_ENABLED = os.environ.get("CDT_PROFILING", "1") != "0"
# On-demand jax.profiler capture cap: a start request asking for more
# than this many seconds is clamped (an unstopped capture auto-stops).
PROFILE_MAX_SECONDS = _env_float("CDT_PROFILE_MAX_SECONDS", 30.0)
# Capture retention under CDT_PROFILE_DIR: prune-oldest beyond this
# many trace dirs or this many MB (never the newest capture).
PROFILE_MAX_CAPTURES = _env_int("CDT_PROFILE_MAX", 8)
PROFILE_MAX_MB = _env_float("CDT_PROFILE_MAX_MB", 512.0)
# Auto-capture: 1 lets an incident trigger (deadline / alert / poison)
# grab a short device trace alongside the debug bundle; the capture
# lasts PROFILE_AUTO_SECONDS and rides the incident writer thread.
PROFILE_AUTO_ENABLED = _env_int("CDT_PROFILE_AUTO", 0) == 1
PROFILE_AUTO_SECONDS = 2.0


def profile_dir_from_env() -> str | None:
    """CDT_PROFILE_DIR resolved at call time (tests monkeypatch the
    env); empty/unset disables on-demand profiler capture — the
    incident-dir idiom."""
    raw = os.environ.get("CDT_PROFILE_DIR", "").strip()
    return raw or None


# --- content-addressed tile result cache (cache/) -------------------------
# CDT_CACHE=1 consults the master-side tile result cache at grant time
# (hits settle straight into the job — they never ship to a worker) and
# populates it at blend/submit on both execution tiers. 0 (default)
# keeps the cache entirely out of the data path; chaos suites that
# count worker dispatches rely on the default staying off.
def cache_enabled() -> bool:
    return _env_int("CDT_CACHE", 0) == 1


# Host-RAM LRU budget for decoded tile results, in MB. Eviction is
# strict LRU by bytes; an entry larger than the whole budget is never
# RAM-resident (it still lands on disk when the disk tier is on).
CACHE_RAM_MB = _env_float("CDT_CACHE_RAM_MB", 256.0)
# Disk tier byte budget (prune-oldest by mtime past it; 0 = unbounded).
CACHE_DISK_MB = _env_float("CDT_CACHE_DISK_MB", 1024.0)
# Disk tier location; "0"/"off"/"none"/empty disables the disk tier
# (RAM-only cache). Follows the compile-cache dir idiom: resolved at
# call time so tests can monkeypatch the env.
CACHE_DIR_DISABLED_VALUES = ("0", "off", "none")


def cache_dir() -> str | None:
    """Resolved disk-tier directory for the tile cache (None = RAM-only)."""
    raw = os.environ.get("CDT_CACHE_DIR", "").strip()
    if not raw or raw.lower() in CACHE_DIR_DISABLED_VALUES:
        return None
    return raw


def cache_cost_enabled() -> bool:
    """CDT_CACHE_COST=1 discounts a job's DRR admission cost by its
    tenant's measured cache-hit share: tiles the cache index says are
    likely hits never reach a device, so charging full freight for
    them double-bills the tenant (the settle path already refunds the
    admission gap — this closes it at admission time). 0 (default)
    keeps admission cost hit-blind."""
    return _env_int("CDT_CACHE_COST", 0) == 1


def cache_cost_floor() -> float:
    """Lower bound on the cache-hit admission discount multiplier
    (default 0.25): even a tenant whose recent tiles all settled from
    cache pays at least this fraction of full cost, so a cold-cache
    burst can never ride an unbounded discount into the queue."""
    floor = _env_float("CDT_CACHE_COST_FLOOR", 0.25)
    return min(1.0, max(0.0, floor))


# --- adapter plane (adapters/) --------------------------------------------
# All resolved at CALL time (tests monkeypatch the env). The rank
# bucket set itself lives in adapters/segmented.rank_buckets (it
# validates + sorts); these are the cache/cost readers.


def adapter_cache_mb() -> float:
    """Host-RAM byte budget (MB) for decoded adapter operands
    (adapters/cache.AdapterOperandCache); strict LRU past it."""
    return _env_float("CDT_ADAPTER_CACHE_MB", 256.0)


def adapter_cold_cost() -> float:
    """DRR admission cost multiplier charged when a job's adapter
    operands are NOT resident in the operand cache. 1.0 (default)
    disables the seam — admission cost is unchanged."""
    return _env_float("CDT_ADAPTER_COLD_COST", 1.0)


def budget_tenants() -> tuple[str, ...]:
    """Comma-separated tenant list routed to the cheap lane when their
    request names no explicit lane (models/gguf quantized tiers are
    the cheap lane's intended capacity)."""
    raw = os.environ.get("CDT_BUDGET_TENANTS", "")
    return tuple(sorted({t.strip() for t in raw.split(",") if t.strip()}))


def cheap_lane() -> str:
    """Lane name budget tenants route to (default: background)."""
    return os.environ.get("CDT_CHEAP_LANE", "background").strip() or "background"


# --- live event stream (telemetry/events.py) ------------------------------
# Per-subscriber bounded queue size for /distributed/events; a consumer
# slower than the event rate loses its OLDEST events (drop-oldest) and
# is told how many via the subscription's dropped count.
EVENT_QUEUE_SIZE = 512

# --- incident plane (telemetry/flight.py, telemetry/incidents.py) ---------
# Always-on flight recorder: a synchronous bus tap keeps the last N
# events and span closes in cheap drop-oldest ring buffers so an
# incident bundle captured AFTER a trigger still holds the evidence
# from BEFORE it. CDT_FLIGHT=0 disables the recorder entirely.
FLIGHT_ENABLED = os.environ.get("CDT_FLIGHT", "1") != "0"
FLIGHT_EVENT_CAPACITY = _env_int("CDT_FLIGHT_EVENTS", 2048)
FLIGHT_SPAN_CAPACITY = _env_int("CDT_FLIGHT_SPANS", 2048)
# Incident debug bundles: captured into CDT_INCIDENT_DIR (unset =
# incident manager disabled, the journal-dir idiom) on alert_fired /
# poison quarantine / deadline expiry / failover / manual POST.
INCIDENT_DEBOUNCE_SECONDS = _env_float("CDT_INCIDENT_DEBOUNCE", 300.0)
# Global floor between captures regardless of trigger key — an alert
# storm across MANY distinct keys still cannot melt the disk.
INCIDENT_MIN_INTERVAL_SECONDS = 10.0
# Retention: prune-oldest beyond this many bundles or this many MB.
INCIDENT_MAX_BUNDLES = _env_int("CDT_INCIDENT_MAX", 32)
INCIDENT_MAX_MB = _env_float("CDT_INCIDENT_MAX_MB", 64.0)
# Seconds of retained fleet history pulled into a bundle around the
# trigger (the FleetRegistry ?since= window).
INCIDENT_WINDOW_SECONDS = 600.0


def incident_dir_from_env() -> str | None:
    """CDT_INCIDENT_DIR resolved at call time (tests monkeypatch the
    env); empty/unset disables the incident manager."""
    raw = os.environ.get("CDT_INCIDENT_DIR", "").strip()
    return raw or None

# --- job init races ------------------------------------------------------
# Grace period a result-submission endpoint waits for the master-side queue
# to be created (reference api/job_routes.py:314-333), and the worker-side
# job-ready poll (reference upscale/modes/static.py:33-47).
JOB_INIT_GRACE_SECONDS = 10.0
JOB_READY_POLL_ATTEMPTS = 20
JOB_READY_POLL_INTERVAL = 1.0
QUEUE_POLL_INTERVAL_SECONDS = 0.1

# --- worker lifecycle ----------------------------------------------------
AUTO_LAUNCH_DELAY_SECONDS = 2.0
MONITOR_POLL_INTERVAL_SECONDS = 2.0
WORKER_LAUNCH_GRACE_SECONDS = 90.0
TUNNEL_START_TIMEOUT = 30.0

# --- network -------------------------------------------------------------
DEFAULT_MASTER_PORT = _env_int("CDT_MASTER_PORT", 8188)
FIRST_WORKER_PORT = _env_int("CDT_FIRST_WORKER_PORT", 8189)
CONNECTION_POOL_LIMIT = 100
CONNECTION_POOL_PER_HOST = 30

# --- debug ---------------------------------------------------------------
DEBUG_FLAG_TTL_SECONDS = 5.0
