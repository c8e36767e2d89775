"""Registry of every ``CDT_*`` environment knob the codebase reads.

This is the single source of truth that closes the loop between code,
docs, and lint:

- ``scripts/gen_config_docs.py`` renders it into ``docs/configuration.md``
  (one row per knob: name, default, subsystem, effect);
- cdt-lint checker **CDT005** statically cross-checks that every knob
  read anywhere in the package appears here, that every entry here
  appears in the generated doc, and that no entry is stale (declared
  but never read).

Keep entries alphabetical within their subsystem group; ``default`` is
the *rendered* default (what an operator sees with the env var unset),
as a string, matching the reading site's fallback.
"""

from __future__ import annotations

from typing import NamedTuple


class Knob(NamedTuple):
    name: str
    default: str
    subsystem: str
    effect: str


KNOBS: tuple[Knob, ...] = (
    # --- roles / process identity ---------------------------------------
    Knob("CDT_IS_WORKER", "unset", "roles",
         "Set on spawned worker processes; suppresses master-only startup "
         "(auto-launch, signal cleanup) and flips `python -m` into worker mode."),
    Knob("CDT_MASTER_PID", "unset", "roles",
         "Master PID a worker watches; the worker exits when that process dies."),
    Knob("CDT_HOST", "127.0.0.1", "roles",
         "Bind address for the HTTP server (pass 0.0.0.0 to serve the LAN)."),
    Knob("CDT_CLOUD", "unset", "roles",
         "Forces cloud-worker detection on hosts whose metadata probe is ambiguous."),
    # --- heartbeat / liveness -------------------------------------------
    Knob("CDT_HEARTBEAT_INTERVAL", "5.0", "liveness",
         "Seconds between worker heartbeats to the master job store."),
    Knob("CDT_HEARTBEAT_TIMEOUT", "60.0", "liveness",
         "Seconds without a heartbeat before a worker's tiles are requeued."),
    # --- payloads --------------------------------------------------------
    Knob("CDT_MAX_PAYLOAD_SIZE", "52428800", "payloads",
         "Maximum HTTP payload bytes accepted by the API (50 MB default)."),
    Knob("CDT_MAX_BATCH", "20", "payloads",
         "Maximum tiles per submit flush from a worker."),
    Knob("CDT_MAX_AUDIO_PAYLOAD_BYTES", "268435456", "payloads",
         "Maximum decoded audio payload bytes (256 MB default)."),
    Knob("CDT_TILE_BATCH", "platform-aware (CPU 1, accelerators 8)", "payloads",
         "Tiles diffused per scan step in the USDU compute core (MXU batch K); "
         "1 is golden-exact, >1 is allclose."),
    # --- orchestration ---------------------------------------------------
    Knob("CDT_ORCHESTRATION_PROBE_CONCURRENCY", "8", "orchestration",
         "Concurrent worker liveness probes during dispatch."),
    Knob("CDT_ORCHESTRATION_PREP_CONCURRENCY", "4", "orchestration",
         "Concurrent per-worker prompt preparations during dispatch."),
    Knob("CDT_ORCHESTRATION_MEDIA_CONCURRENCY", "2", "orchestration",
         "Concurrent media-sync uploads per dispatch."),
    Knob("CDT_MEDIA_SYNC_TIMEOUT", "120.0", "orchestration",
         "Per-file media sync upload timeout in seconds."),
    Knob("CDT_PROBE_TIMEOUT", "5.0", "orchestration",
         "Worker liveness probe timeout in seconds."),
    Knob("CDT_DISPATCH_TIMEOUT", "30.0", "orchestration",
         "Per-worker prompt dispatch timeout in seconds."),
    # --- resilience ------------------------------------------------------
    Knob("CDT_FAULT_PLAN", "unset", "resilience",
         "Seeded fault-injection plan (e.g. `seed=3;latency(0.2)@request_image%0.5`) "
         "wrapping HTTP transport and the job store; unset = no injection."),
    Knob("CDT_DETERMINISTIC_BLEND", "unset", "resilience",
         "`1` forces sorted-order deferred compositing so the blended canvas is "
         "bit-identical regardless of tile arrival order (chaos harness sets it)."),
    # --- request lifecycle (deadlines / cancel / poison / brownout) ------
    Knob("CDT_JOB_DEADLINE_DEFAULT", "0.0", "lifecycle",
         "Default end-to-end job deadline in seconds applied when a request "
         "names none; 0 = no default deadline."),
    Knob("CDT_JOB_DEADLINE_MAX", "0.0", "lifecycle",
         "Cap clamped onto any client-supplied `deadline_s`; 0 = uncapped."),
    Knob("CDT_POISON_POLICY", "degrade", "lifecycle",
         "`degrade` completes a job with poison-quarantined tiles blended "
         "from the base image; `fail` raises a terminal JobPoisoned error."),
    Knob("CDT_SHED_COOLDOWN", "5.0", "lifecycle",
         "Seconds between brownout level steps (hysteresis against flapping)."),
    Knob("CDT_SHED_JOURNAL_P95", "0.25", "lifecycle",
         "Journal-append p95 seconds above which the brownout controller "
         "sheds one more lowest-priority lane."),
    Knob("CDT_SHED_WAIT_P95", "20.0", "lifecycle",
         "Queue-wait p95 seconds above which the brownout controller sheds "
         "one more lowest-priority lane (the premium lane never sheds)."),
    Knob("CDT_TILE_MAX_ATTEMPTS", "3", "lifecycle",
         "Failed delivery attempts (crash/timeout requeues) a tile may "
         "accumulate before it is quarantined out of the pull set as poison."),
    # --- watchdog --------------------------------------------------------
    Knob("CDT_WATCHDOG", "1", "watchdog",
         "`0` disables the server's background straggler/stall monitor thread."),
    Knob("CDT_WATCHDOG_STRAGGLER_FACTOR", "4.0", "watchdog",
         "A worker whose rolling median tile latency exceeds this multiple of the "
         "global median is flagged suspect."),
    Knob("CDT_WATCHDOG_STALL_SECONDS", "30.0", "watchdog",
         "A job quiet this long with tiles in flight triggers speculative re-dispatch."),
    # --- scheduler -------------------------------------------------------
    Knob("CDT_SCHED_LANES", "interactive:64,batch:256,background:1024", "scheduler",
         "Admission lanes in strict priority order as name:depth pairs; a full "
         "lane answers HTTP 429 + Retry-After."),
    Knob("CDT_SCHED_DEFAULT_LANE", "interactive", "scheduler",
         "Lane used when a queue request names none."),
    Knob("CDT_SCHED_MAX_ACTIVE", "4", "scheduler",
         "Orchestrations allowed to run concurrently; the rest wait in lanes."),
    Knob("CDT_SCHED_TENANT_WEIGHTS", "empty", "scheduler",
         "Per-tenant DRR weights as `tenantA=3,tenantB=1`; unlisted tenants weigh 1."),
    Knob("CDT_SCHED_TAIL_TILES", "2", "scheduler",
         "Within this many remaining tiles, suspect/slow workers are denied pulls."),
    # --- cross-job batching + step-level preemption ----------------------
    Knob("CDT_PREEMPT", "1", "scheduler",
         "Step-level preemption: a premium-lane arrival flags running "
         "lower-lane jobs for step-boundary eviction (checkpoint + requeue). "
         "Inert while every job shares one lane; `0` disables entirely."),
    Knob("CDT_PREEMPT_BROWNOUT_LEVEL", "0", "scheduler",
         "Brownout shed level at/above which running work in shed lanes is "
         "also EVICTED (not just refused admission); `0` keeps brownout "
         "admission-only."),
    Knob("CDT_PREEMPT_CHECKPOINT_MB", "64", "scheduler",
         "Per-job byte budget for volatile preemption checkpoints retained "
         "on the master; beyond it evicted tiles recompute from step 0."),
    Knob("CDT_XJOB_BATCH", "0", "scheduler",
         "`1` routes elastic master/worker loops through the cross-job "
         "continuous-batching executor (tiles from different jobs/tenants "
         "share shape-bucketed device batches; step-resumable samplers "
         "only)."),
    Knob("CDT_XJOB_DEVICE_RESIDENT", "1", "scheduler",
         "`1` parks evicted batch latents on-device in the cross-job "
         "executor: the host checkpoint becomes a lazy spill and a "
         "matching re-grant resumes without the b64 decode + h2d "
         "re-upload. `0` decodes every resume from the host checkpoint "
         "(both modes are byte-identical by construction)."),
    Knob("CDT_XJOB_DEVICE_RESIDENT_MB", "256", "scheduler",
         "Byte budget (MB) for parked device latents; past it the stash "
         "evicts oldest-first and the evicted tile resumes from its "
         "host spill."),
    Knob("CDT_BF16_LANES", "empty", "scheduler",
         "Comma-separated scheduler lane names whose jobs carry latents "
         "in bfloat16 between steps (`*` = every lane): halves "
         "checkpoint/transfer bytes; step math stays in the model's "
         "param dtype. Precision joins the batch signature, so bf16 "
         "and f32 tiles never share a device batch."),
    # --- tile pipeline ---------------------------------------------------
    Knob("CDT_PIPELINE", "1", "pipeline",
         "`0` replaces the staged tile pipeline with the serial per-tile loop."),
    Knob("CDT_PIPELINE_DEPTH", "1", "pipeline",
         "In-flight device batches the sampler may run ahead of the I/O stage."),
    Knob("CDT_PIPELINE_PREFETCH", "1", "pipeline",
         "`0` disables claiming the next grant while the device samples the "
         "current one."),
    Knob("CDT_WARM_COMPILE", "1", "pipeline",
         "`0` skips AOT-compiling the steady-state tile bucket during the "
         "worker's ready-poll window."),
    # --- durability ------------------------------------------------------
    Knob("CDT_JOURNAL_DIR", "unset", "durability",
         "Directory for the control-plane write-ahead journal + snapshots; "
         "unset disables the durable control plane entirely (master-only)."),
    Knob("CDT_JOURNAL_FSYNC", "1", "durability",
         "Journal fsync policy: 1 syncs every append before acknowledging "
         "(power-cut safe), N>1 syncs every N appends, 0 is write-behind "
         "via a dedicated writer thread (the <5% overhead mode; a SIGKILL "
         "may lose the last in-flight records, which recovery then "
         "recomputes bit-identically)."),
    Knob("CDT_SNAPSHOT_EVERY", "256", "durability",
         "Journal appends between control-plane snapshots; each snapshot "
         "prunes the segments it supersedes."),
    # --- high availability (failover / push grants) ----------------------
    Knob("CDT_LEASE_TTL", "10.0", "ha",
         "Master lease TTL in seconds (durability/lease.py): the standby "
         "promotes itself once the lease has been expired this long; also "
         "bounds the zombie window before epoch fencing bites."),
    Knob("CDT_PUSH_GRANTS", "1", "ha",
         "`0` disables push-mode grants: workers then pull-poll instead of "
         "waking on pushed grant_available events over /distributed/events."),
    Knob("CDT_STANDBY_OF", "unset", "ha",
         "Comma-separated active-master URL list; set (or pass --standby) to "
         "run this master as a warm standby tailing the journal stream."),
    # --- region control plane (quorum lease / shards / autoscaler) -------
    Knob("CDT_AUTOSCALE", "0", "region",
         "`1` starts the usage-driven autoscaler loop on masters "
         "(scheduler/autoscale.py): SLO burn-rate alerts and measured "
         "chip-second demand drive launch/drain of managed local workers, "
         "each decision journaled with its chip-second cost/benefit."),
    Knob("CDT_AUTOSCALE_DOWN_HOLD", "120.0", "region",
         "Seconds utilization must stay below half the target before a "
         "scale-down drains a worker; scale-up is immediate, scale-down "
         "is patient (thrash guard)."),
    Knob("CDT_AUTOSCALE_MAX", "8", "region",
         "Upper bound on managed worker count; pressure at the bound "
         "holds with `reason=pressure at max_workers` instead of "
         "launching."),
    Knob("CDT_AUTOSCALE_MIN", "1", "region",
         "Lower bound on managed worker count; scale-down never drains "
         "below it."),
    Knob("CDT_AUTOSCALE_TARGET_UTIL", "0.70", "region",
         "Demand/capacity chip-second ratio the controller steers "
         "toward: above it scale up, below half of it (sustained for "
         "the hold window) scale down."),
    Knob("CDT_LEASE_PEERS", "empty", "region",
         "Comma-separated lease-peer register directories; non-empty "
         "switches the master lease from the shared-filesystem flock "
         "sidecar to majority agreement across these registers "
         "(durability/quorum.py) — epoch fencing and FencedOut "
         "semantics carry over unchanged."),
    Knob("CDT_SHARDS", "empty", "region",
         "Region shard map: shards separated by `;`, each a "
         "comma-separated master address list (active first, standbys "
         "after). Non-empty enables consistent-hash job routing "
         "(scheduler/router.py); empty keeps the single-master "
         "topology."),
    # --- telemetry -------------------------------------------------------
    Knob("CDT_METRIC_MAX_SERIES", "128", "telemetry",
         "Per-metric label-series cap; excess series collapse into `_overflow`."),
    Knob("CDT_TRACE_EXPORT_DIR", "unset", "telemetry",
         "When set, each execution's span tree is exported as JSONL here."),
    Knob("CDT_RUNTIME_DEVICE_STATS", "1", "telemetry",
         "`0` disables the HBM/host-RSS scrape gauges."),
    Knob("CDT_FLEET", "1", "telemetry",
         "`0` disables the fleet observability plane (monitor thread, "
         "master-side sampling, SLO evaluation; routes answer enabled=false)."),
    Knob("CDT_FLEET_INTERVAL", "10.0", "telemetry",
         "Seconds between master-side fleet sampling passes "
         "(sweep + rollup + SLO burn-rate evaluation)."),
    Knob("CDT_FLEET_TTL", "120.0", "telemetry",
         "Seconds without a snapshot before a worker is evicted from the "
         "fleet view (all its retained series drop)."),
    Knob("CDT_PROFILE_AUTO", "0", "telemetry",
         "`1` makes every incident bundle capture a short device trace "
         "(requires CDT_PROFILE_DIR; the bundle records the capture ids)."),
    Knob("CDT_PROFILE_DIR", "unset", "telemetry",
         "Directory retained jax.profiler traces are captured into; unset "
         "disables the /distributed/profile capture routes (the "
         "CDT_JOURNAL_DIR idiom). The transfer ledger works without it."),
    Knob("CDT_PROFILE_MAX", "8", "telemetry",
         "Retained trace capture count; oldest captures pruned beyond it."),
    Knob("CDT_PROFILE_MAX_MB", "512.0", "telemetry",
         "Total on-disk trace budget in MB; oldest captures pruned beyond it."),
    Knob("CDT_PROFILE_MAX_SECONDS", "30.0", "telemetry",
         "Ceiling clamped onto any requested capture duration; every "
         "capture auto-stops at this bound even if /profile/stop never "
         "arrives."),
    Knob("CDT_PROFILING", "1", "telemetry",
         "`0` disables the transfer ledger (device/host time split, "
         "host-tax ratio, h2d/d2h byte accounting) on both execution "
         "tiers and its fleet-snapshot piggyback."),
    Knob("CDT_SLO_TILE_P95", "5.0", "telemetry",
         "Tile pull-to-submit latency target the tile_latency SLO "
         "classifies samples against (seconds)."),
    Knob("CDT_SLO_JOURNAL_P95", "0.25", "telemetry",
         "Journal-append latency target the journal_latency SLO "
         "classifies samples against (seconds)."),
    Knob("CDT_USAGE", "1", "telemetry",
         "`0` disables chip-time attribution records on both execution "
         "tiers and the master-side usage aggregation "
         "(GET /distributed/usage answers enabled=false)."),
    Knob("CDT_USAGE_COST", "0", "telemetry",
         "`1` multiplies DRR admission cost by the tenant's measured "
         "chip-seconds-per-tile ratio vs the fleet mean (clamped to "
         "[0.1, 10]), replacing the static estimated_tiles-only cost."),
    # --- tile result cache -----------------------------------------------
    Knob("CDT_CACHE", "0", "cache",
         "`1` enables the master-side content-addressed tile result "
         "cache: hits settle into the job at grant time (journaled, "
         "never dispatched) and blend from cached pixels."),
    Knob("CDT_CACHE_DIR", "unset", "cache",
         "Directory for the CRC-checked disk tier; unset/`0`/`off`/"
         "`none` keeps the cache RAM-only (the CDT_JOURNAL_DIR idiom)."),
    Knob("CDT_CACHE_DISK_MB", "1024.0", "cache",
         "Disk-tier byte budget in MB (oldest entries pruned beyond it; "
         "0 = unbounded)."),
    Knob("CDT_CACHE_RAM_MB", "256.0", "cache",
         "Host-RAM LRU byte budget in MB; an entry larger than the "
         "whole budget is stored disk-only."),
    Knob("CDT_CACHE_COST", "0", "cache",
         "`1` discounts DRR admission cost by the tenant's measured "
         "cache-hit share (tiles that settle from cache never burn "
         "chip time); bounded below by CDT_CACHE_COST_FLOOR."),
    Knob("CDT_CACHE_COST_FLOOR", "0.25", "cache",
         "Lower bound on the cache-hit admission discount multiplier: "
         "even an all-hits tenant pays this fraction of full cost."),
    # --- adapter plane ---------------------------------------------------
    Knob("CDT_ADAPTER_CACHE_MB", "256.0", "adapters",
         "Host-RAM LRU byte budget in MB for decoded adapter operands "
         "(per-adapter rank-bucketed down/up pairs)."),
    Knob("CDT_ADAPTER_COLD_COST", "1.0", "adapters",
         "DRR admission cost multiplier for requests whose adapter plan "
         "is not resident in the operand cache; 1.0 disables the cold "
         "surcharge."),
    Knob("CDT_ADAPTER_RANK_BUCKETS", "4,8,16,32,64", "adapters",
         "Comma-separated rank-bucket set adapters zero-pad to; one "
         "compiled program exists per (batch signature, bucket), so the "
         "set bounds adapter-induced compile count."),
    Knob("CDT_BUDGET_TENANTS", "empty", "adapters",
         "Comma-separated tenant ids routed to the cheap lane at the "
         "queue route when their request names no explicit lane."),
    Knob("CDT_CHEAP_LANE", "background", "adapters",
         "The lane CDT_BUDGET_TENANTS route to (the lane GGUF-quantized "
         "checkpoints are registered to serve)."),
    # --- incident plane --------------------------------------------------
    Knob("CDT_FLIGHT", "1", "incidents",
         "`0` disables the always-on flight recorder (the bus tap that "
         "retains recent events + span closes for incident bundles)."),
    Knob("CDT_FLIGHT_EVENTS", "2048", "incidents",
         "Flight-recorder event ring capacity (drop-oldest; drops counted "
         "in cdt_flight_dropped_total)."),
    Knob("CDT_FLIGHT_SPANS", "2048", "incidents",
         "Flight-recorder span-close ring capacity (drop-oldest)."),
    Knob("CDT_INCIDENT_DIR", "unset", "incidents",
         "Directory incident debug bundles are captured into; unset "
         "disables the incident manager (the CDT_JOURNAL_DIR idiom)."),
    Knob("CDT_INCIDENT_DEBOUNCE", "300.0", "incidents",
         "Seconds a trigger key (e.g. one SLO's alert) is debounced after "
         "a capture — a re-firing alert inside the window captures nothing."),
    Knob("CDT_INCIDENT_MAX", "32", "incidents",
         "Retained bundle count; the oldest bundles are pruned beyond it."),
    Knob("CDT_INCIDENT_MAX_MB", "64.0", "incidents",
         "Total on-disk bundle budget in MB; oldest pruned beyond it."),
    # --- workers ---------------------------------------------------------
    Knob("CDT_LOG_DIR", "./logs/workers", "workers",
         "Directory for per-worker stdout/stderr log files."),
    # --- network ---------------------------------------------------------
    Knob("CDT_MASTER_PORT", "8188", "network",
         "Default master HTTP port."),
    Knob("CDT_FIRST_WORKER_PORT", "8189", "network",
         "First port assigned to auto-launched local workers."),
    Knob("CDT_CONFIG_PATH", "<package>/tpu_config.json", "network",
         "Overrides the JSON config file location."),
    # --- tunnel ----------------------------------------------------------
    Knob("CDT_CLOUDFLARED_PATH", "unset", "tunnel",
         "Path to the cloudflared binary for master tunnels."),
    Knob("CDT_TUNNEL_AUTODOWNLOAD", "unset", "tunnel",
         "`1` permits downloading cloudflared when no binary is found."),
    # --- models ----------------------------------------------------------
    Knob("CDT_CHECKPOINT_DIR", "unset", "models",
         "Root directory (or direct file path) for model checkpoints "
         "(`<name>.{safetensors,ckpt,gguf}`)."),
    Knob("CDT_CLIP_VOCAB", "bundled asset dir", "models",
         "Directory holding OpenAI CLIP vocab.json/merges.txt."),
    Knob("CDT_T5_SPM", "unset", "models",
         "Path to a sentencepiece model for real T5 tokenization; unset uses "
         "the committed fallback vocab."),
    Knob("CDT_LORA_DIR", "empty", "models",
         "Root directory for LoRA adapter files."),
    Knob("CDT_PARAMS_DTYPE", "platform-aware (CPU float32, accelerators bfloat16)", "models",
         "Storage dtype of floating-point weights (the models compute in "
         "bfloat16 either way); SDXL in float32 does not fit a 16 GB chip."),
    # --- ops -------------------------------------------------------------
    Knob("CDT_FLASH", "unset", "ops",
         "`0` force-disables the Pallas flash-attention kernel."),
    Knob("CDT_DEVICE_CANVAS", "0", "ops",
         "`1` composites master-local tiles on-device (ops/tiles."
         "DeviceCanvas): one composited d2h per flush instead of a "
         "readback per tile; bit-identical to the deterministic host "
         "canvas. Engages only while the tile cache is off; remote "
         "worker tiles keep the PNG path."),
    # --- parallel --------------------------------------------------------
    Knob("CDT_MESH_SHAPE", "unset", "parallel",
         "Local device mesh axis sizes as `data,model` (e.g. `4,1`, `-1,2`; "
         "-1 infers the remainder). Unset auto-builds a pure data mesh over "
         "all local chips on accelerator platforms; on CPU the mesh is "
         "opt-in via this knob (forced host devices are a test construction)."),
    Knob("CDT_MESH_HBM_GB", "0", "parallel",
         "Per-chip HBM budget in GiB for the auto-tensor-parallel rule: a "
         "checkpoint whose parameters exceed it shards along the model axis "
         "(smallest power-of-two TP that fits) instead of failing to load; "
         "0 disables."),
    Knob("CDT_TP_SIZE", "unset", "parallel",
         "Tensor-parallel (model-axis) mesh size; overrides the model entry "
         "of CDT_MESH_SHAPE. Parameters shard along this axis via "
         "parallel/sharding.shard_params (TP outputs are allclose, not "
         "bit-identical)."),
    Knob("CDT_MULTIHOST", "unset", "parallel",
         "`1` requires multihost initialization to succeed (hard error otherwise)."),
    Knob("CDT_COORDINATOR", "unset", "parallel",
         "host:port of process 0 for multihost JAX initialization."),
    Knob("CDT_NUM_PROCESSES", "unset", "parallel",
         "Total process count for multihost initialization."),
    Knob("CDT_PROCESS_ID", "unset", "parallel",
         "This process's index for multihost initialization."),
    # --- graph I/O -------------------------------------------------------
    Knob("CDT_DATA_DIR", "./data", "graph-io",
         "Root data directory (inputs/outputs default beneath it)."),
    Knob("CDT_INPUT_DIR", "<data>/input", "graph-io",
         "Input image directory."),
    Knob("CDT_OUTPUT_DIR", "<data>/output", "graph-io",
         "Output image directory."),
    Knob("CDT_WORKFLOW_DIR", "empty", "graph-io",
         "Extra directory searched for workflow JSON files."),
    # --- native ----------------------------------------------------------
    Knob("CDT_NATIVE_BUILD_DIR", "<package>/native/build", "native",
         "Build directory for the optional native extension."),
    # --- tools -----------------------------------------------------------
    Knob("CDT_DRYRUN_PLATFORM", "cpu", "tools",
         "JAX platform forced by the graft-entry dry run."),
    Knob("CDT_GOLDEN_ATOL", "0.001", "tools",
         "Absolute tolerance for golden regeneration comparisons."),
)


def knob_names() -> set[str]:
    return {knob.name for knob in KNOBS}


def by_subsystem() -> dict[str, list[Knob]]:
    grouped: dict[str, list[Knob]] = {}
    for knob in KNOBS:
        grouped.setdefault(knob.subsystem, []).append(knob)
    return {sub: sorted(entries) for sub, entries in sorted(grouped.items())}
