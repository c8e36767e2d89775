"""Cross-job continuous batching with step-level preemption.

PR 8 made the K x D-chip device batch the unit of work, but batching
stayed per-grant within ONE job: at many-small-concurrent-jobs traffic
the steady state is ragged grants that under-fill the device batch —
chips run wraparound padding while other jobs' tiles wait in other
queues. This module is the vLLM/Orca-style answer (iteration-level
scheduling) transplanted to tile diffusion:

- **one ready-queue, many jobs** — registered jobs feed
  ``(job, tile)`` work items into a shape-bucketed ready queue keyed
  by the job's ``StepwiseProcessor.signature`` (same geometry + model
  + sampler config = same compiled programs = batchable together).
  Each scheduling round composes ONE device batch from the
  most-urgent signature group's items — across jobs and tenants —
  padded to the bounded ``ops/upscale.grant_buckets`` set exactly like
  the per-job tier, so compile counts stay bounded and the padding is
  wraparound duplicates of real items.

- **iteration-level scheduling** — work advances ONE denoise step per
  dispatch (ops/stepwise.py): items at different step indices share a
  batch (the step index is a traced per-item input), finished items
  decode + leave, new items join at the next boundary. That is what
  lets a premium tile start next-step instead of next-grant.

- **step-level preemption** — when a job's client reports a
  preemption request (the master's scheduler/preempt.py coordinator
  raised it for a premium-lane arrival, or brownout eviction), the
  executor checkpoints that job's in-flight latents at the NEXT step
  boundary (``encode_checkpoint``: latents + step index; the fold key
  is recomputed from job key + tile index) and hands every claimed
  tile back through the job's ``release`` callback — the existing
  ``release_tasks``/``return_tiles`` requeue path, now carrying
  checkpoints. On re-grant the tile resumes from its checkpoint; a
  lost checkpoint (worker crash, master restart — checkpoints are
  volatile by design) falls back to recompute-from-step-0, which is
  the bit-identity reference.

Determinism contract (tests/graph/test_batch_executor.py +
tests/test_chaos_xjob.py): a tile's output is bit-identical whether it
is sampled alone, batched with its own job, or batched with another
tenant's tiles — per-item inputs are pure functions of (job key, tile
index, step index), vmap batching never mixes lanes of the batch, and
the per-job fold key gains the job id (parallel/seeds.fold_job_key) so
two jobs sharing a user seed still draw independent streams.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Callable, Optional, Sequence

from ..ops.stepwise import (
    CheckpointError,
    decode_checkpoint,
    encode_checkpoint,
    validate_checkpoint_meta,
)
from ..telemetry.instruments import (
    batch_fill_ratio,
    pipeline_batches_total,
    pipeline_padded_tiles_total,
    preempt_resume_total,
    tiles_processed_total,
)
from ..telemetry.profiling import (
    D2H,
    H2D,
    ledger_if_enabled,
    transfer_nbytes,
)
from ..telemetry.usage import (
    SLOT_PADDING,
    SLOT_REAL,
    SLOT_RECOMPUTE,
    get_usage_meter,
)
from ..utils.logging import debug_log
from .tile_pipeline import stage_span


class XJobHandle:
    """One registered job's data + client seam for the executor.

    ``proc`` carries (init, step, finish, n_steps, signature) — see
    ops/stepwise.StepwiseProcessor; chaos/test stubs pass plain
    callables with a hand-made signature. Jobs whose signatures are
    EQUAL may share device batches; the executor never mixes
    signatures in one dispatch.

    Client callbacks (all run on the executor thread):

      pull()            -> {"tile_idxs": [...], "checkpoints": {...}}
                           | None (nothing pullable now = drained)
      emit(idx, arr)    one finished tile (host [B, h, w, C])
      flush(final)      submit pending results (size thresholds inside)
      release(idxs, checkpoints)  hand claimed tiles back on preemption
      preempt_check()   -> bool: the master wants this job evicted
      heartbeat()       optional liveness ping
    """

    def __init__(
        self,
        *,
        job_id: str,
        proc: Any,
        params: Any,
        extracted: Any,
        positions: Any,
        pos: Any,
        neg: Any,
        base_key: Any,
        pull: Callable[[], Optional[dict]],
        emit: Callable[[int, Any], None],
        flush: Callable[[bool], None],
        release: Optional[Callable[[list[int], dict], None]] = None,
        preempt_check: Optional[Callable[[], bool]] = None,
        heartbeat: Optional[Callable[[], None]] = None,
        check_interrupted: Optional[Callable[[], None]] = None,
        tenant: str = "default",
        lane: str = "",
        priority: int = 0,
        adapter: Any = None,
        device_emit: bool = False,
    ) -> None:
        self.job_id = str(job_id)
        self.proc = proc
        # Adapter plane (adapters/segmented.SegmentOperands | None):
        # when set, this job's tiles sample with the per-slot low-rank
        # patch and batch under the EXTENDED signature — rank bucket +
        # target-path digest — so same-bucket jobs wearing *different*
        # adapters still share one compiled program, while adapter-less
        # jobs keep the unmodified signature (and bit-identity).
        self.adapter = adapter
        if adapter is not None:
            from ..adapters import adapter_signature

            self.sig = adapter_signature(proc.signature, adapter)
        else:
            self.sig = proc.signature
        self.params = params
        self.extracted = extracted
        self.positions = positions
        self.pos = pos
        self.neg = neg
        self.base_key = base_key
        self.pull = pull
        self.emit = emit
        self.flush = flush
        self.release = release
        self.preempt_check = preempt_check
        self.heartbeat = heartbeat
        self.check_interrupted = check_interrupted
        self.tenant = str(tenant)
        self.lane = str(lane)
        # device_emit: this job's emit() accepts DEVICE arrays — the
        # executor skips the per-tile host readback and the consumer
        # (a DeviceCanvas master) owns the single composited d2h
        self.device_emit = bool(device_emit)
        # lower = more urgent; ties broken by registration order so
        # scheduling is a pure function of the registered sequence
        self.priority = int(priority)
        self.seq = 0  # assigned at register()
        self.done = False
        self.error: Optional[BaseException] = None
        # set when the executor finishes (drain) or fails this job —
        # the blocking production entries park on it
        self.finished = threading.Event()
        self.preempted = False  # currently evicted by request
        self.tiles_done = 0
        # (executor-local) tiles this job has claimed from its master
        # and neither emitted nor released — the crash-release set
        self.claimed: set[int] = set()


class _Item:
    """One tile's position in the executor: job, index, step cursor,
    and (after init / checkpoint adoption) its latent state."""

    __slots__ = (
        "job", "tile_idx", "step", "x", "key", "seq", "resumed",
        "recompute_until",
    )

    def __init__(self, job: XJobHandle, tile_idx: int, seq: int):
        self.job = job
        self.tile_idx = int(tile_idx)
        self.step = 0
        self.x = None
        self.key = None
        self.seq = seq  # arrival order; ties in priority break on this
        self.resumed = False
        # steps below this index are RE-RUNS of work a preemption
        # eviction already paid for (checkpoint lost → recompute): the
        # usage meter charges them to waste{preempt_recompute}
        self.recompute_until = 0

    def order(self) -> tuple[int, int, int]:
        return (self.job.priority, self.job.seq, self.seq)


class CrossJobExecutor:
    """Drains registered jobs through shared, shape-bucketed,
    step-granular device batches. Single driver thread (``run``);
    ``register`` may be called from any thread — new jobs are picked
    up at the next scheduling round.

    ``k_max``: device batch width (callers pass
    ``tile_scan_batch() x D`` exactly like GrantSampler).
    ``bucket_multiple``: buckets round up to multiples of this (the
    mesh data-axis width D), so every participant holds an equal
    slice — same rule as the mesh-aware GrantSampler.
    ``cross_job=False`` restricts every batch to a single job's items
    (the per-job baseline tests/test_chaos_xjob.py compares fill against).
    """

    def __init__(
        self,
        *,
        k_max: int = 8,
        bucket_multiple: int = 1,
        mesh: Any = None,
        role: str = "worker",
        cross_job: bool = True,
        preempt_enabled: bool = True,
        idle_poll_seconds: float = 0.02,
        clock: Callable[[], float] = time.monotonic,
        usage_meter: Any = None,
    ) -> None:
        from ..ops.upscale import grant_buckets
        from ..utils.constants import USAGE_ENABLED

        self.k_max = max(1, int(k_max))
        self.mesh = mesh
        self.role = str(role)
        self.cross_job = bool(cross_job)
        self.preempt_enabled = bool(preempt_enabled)
        self.idle_poll_seconds = float(idle_poll_seconds)
        self.clock = clock
        dp = max(1, int(bucket_multiple))
        if mesh is not None:
            from ..parallel.mesh import data_axis_size

            dp = max(dp, data_axis_size(mesh))
        self.bucket_multiple = dp
        if dp > 1:
            self.k_max = max(self.k_max, dp)
            self.buckets = tuple(
                sorted({max(dp, -(-b // dp) * dp) for b in grant_buckets(self.k_max)})
            )
        else:
            self.buckets = grant_buckets(self.k_max)
        self._lock = threading.Lock()
        self._jobs: dict[str, XJobHandle] = {}
        self._job_seq = 0
        self._item_seq = 0
        # signature -> live items (ready or mid-trajectory). One flat
        # list per signature: scheduling sorts by (priority, seq) each
        # round, which is cheap at device-batch scale and keeps the
        # policy in one place.
        self._items: dict[tuple, list[_Item]] = {}
        self._sig_order: list[tuple] = []  # first-seen signature order
        self._vstep_cache: dict[tuple, Any] = {}
        self._shardings: dict[int, Any] = {}
        # (job_id, tile_idx) -> step reached when this executor evicted
        # the tile: a later arrival without a checkpoint is a
        # recompute-from-0 resume, and the usage meter charges its
        # re-run steps (below that mark) to waste{preempt_recompute}
        self._evicted: dict[tuple[str, int], int] = {}
        # Device-resident latent stash (CDT_XJOB_DEVICE_RESIDENT):
        # (job_id, tile_idx) -> (device latent, step) kept at eviction
        # so a re-grant on THIS executor resumes without re-uploading
        # the host checkpoint (the host copy becomes the lazy spill —
        # written at the preemption boundary, read only when the tile
        # lands elsewhere or the stash was evicted). Insertion-ordered
        # dict = deterministic FIFO eviction under the byte budget.
        self._device_stash: dict[tuple[str, int], tuple[Any, int]] = {}
        self._device_stash_bytes = 0
        # chip-time attribution (telemetry/usage.py); None = disabled
        self.usage = usage_meter if usage_meter is not None else (
            get_usage_meter() if USAGE_ENABLED else None
        )
        self._chips = 1
        if mesh is not None:
            from ..parallel.mesh import data_axis_size as _das

            self._chips = max(1, _das(mesh))
        self._stop = threading.Event()
        # --- accounting (read by chaos assertions) -----------------------
        self.dispatches = 0
        self.slots_real = 0
        self.slots_padded = 0
        self.steps_run = 0
        self.tiles_finished = 0
        self.preempt_evictions = 0
        self.resumes_checkpoint = 0
        self.resumes_recompute = 0
        self.resumes_device = 0
        # completion order for scheduling assertions: (job_id, tile_idx).
        # Bounded: the PROCESS-shared executor outlives jobs, so an
        # unbounded list would grow one entry per tile served forever.
        self.completion_order: list[tuple[str, int]] = []
        self._max_completion_order = 65536

    # --- registration -----------------------------------------------------

    def register(self, job: XJobHandle) -> XJobHandle:
        with self._lock:
            if job.job_id in self._jobs:
                raise ValueError(f"job {job.job_id!r} already registered")
            self._job_seq += 1
            job.seq = self._job_seq
            self._jobs[job.job_id] = job
            sig = job.sig
            if sig not in self._items:
                self._items[sig] = []
                self._sig_order.append(sig)
        if self.usage is not None:
            # advisory attrs (the store's init path lands the
            # authoritative tenant/lane on masters)
            self.usage.note_job_attrs(job.job_id, job.tenant, job.lane)
        return job

    def stop(self) -> None:
        self._stop.set()

    @property
    def active_jobs(self) -> int:
        with self._lock:
            return len(self._jobs)

    def fill_ratio(self) -> float:
        total = self.slots_real + self.slots_padded
        return (self.slots_real / total) if total else 1.0

    # --- device programs --------------------------------------------------

    def _vstep(
        self,
        sig: tuple,
        step_one: Callable,
        adapter_paths: Optional[tuple] = None,
    ) -> Callable:
        """The batched one-step program for a signature: vmapped over
        (x, key, pos, neg, yx, i) with params shared. Jitted only when
        the per-item step is itself compiled (production) — raw Python
        stubs stay eager so the chaos parity suite's bit-identity
        against the serial path survives XLA's batch-size-specific
        rewrites (the PR 5 jit-vs-eager ulp hazard).

        ``adapter_paths`` (adapter-extended signatures only) grows the
        arity by per-slot (downs, ups, scale) operands applied as a
        low-rank weight patch inside each lane: params broadcast, only
        the targeted leaves batch. The jit gate stays on the UNDERLYING
        step — the adapter wrapper is plain Python on top of it."""
        cached = self._vstep_cache.get(sig)
        if cached is not None:
            return cached
        import jax

        if adapter_paths is not None:
            from ..adapters import make_adapter_step

            wrapped = make_adapter_step(step_one, adapter_paths)
            vmapped = jax.vmap(
                wrapped, in_axes=(None, 0, 0, 0, 0, 0, 0, 0, 0, 0)
            )
        else:
            vmapped = jax.vmap(step_one, in_axes=(None, 0, 0, 0, 0, 0, 0))
        # donate the stacked latents (arg 1): XLA aliases the input
        # batch buffer into the output, so the per-step loop holds ONE
        # batch-of-latents allocation instead of two. Safe because
        # _step_batch stacks xs fresh per dispatch (the stack is a
        # copy; per-item latents are never themselves donated), and
        # nothing reads xs after the call — outputs scatter back to
        # item.x. Raw Python stubs stay eager AND undonated (donation
        # is a jit concept).
        fn = (
            jax.jit(vmapped, donate_argnums=(1,))
            if hasattr(step_one, "lower")
            else vmapped
        )
        self._vstep_cache[sig] = fn
        return fn

    def _place(self, batched: tuple) -> tuple:
        """Pin every batched input's leading axis across the mesh's
        data axis (NamedSharding), replicating trailing dims — the
        GrantSampler._place idiom generalized to pytrees. No-op
        without a data-parallel mesh."""
        if self.mesh is None:
            return batched
        from ..parallel.mesh import DATA_AXIS, data_axis_size

        if data_axis_size(self.mesh) <= 1:
            return batched
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        def shard_leaf(leaf):
            ndim = getattr(leaf, "ndim", 0)
            if ndim < 1:
                return leaf
            sharding = self._shardings.get(ndim)
            if sharding is None:
                sharding = NamedSharding(
                    self.mesh, P(DATA_AXIS, *([None] * (ndim - 1)))
                )
                self._shardings[ndim] = sharding
            return jax.device_put(leaf, sharding)

        started = time.monotonic()
        placed = tuple(
            jax.tree_util.tree_map(shard_leaf, part) for part in batched
        )
        ledger = ledger_if_enabled()
        if ledger is not None:
            nbytes = sum(
                transfer_nbytes(leaf)
                for part in batched
                for leaf in jax.tree_util.tree_leaves(part)
            )
            ledger.note_transfer(H2D, nbytes, time.monotonic() - started)
        return placed

    # --- grant intake -----------------------------------------------------

    def _tile_key(self, job: XJobHandle, tile_idx: int):
        import jax

        return jax.random.fold_in(job.base_key, int(tile_idx))

    # --- device-resident latent stash --------------------------------------

    def _stash_put(self, job_id: str, tile_idx: int, x: Any, step: int) -> None:
        """Park an evicted tile's device latent for re-grant on this
        executor. Bounded by CDT_XJOB_DEVICE_RESIDENT_MB with FIFO
        eviction (insertion order — deterministic); a latent larger
        than the whole budget is never stashed."""
        from ..utils.constants import (
            xjob_device_resident_budget_bytes,
            xjob_device_resident_enabled,
        )

        if not xjob_device_resident_enabled():
            return
        nbytes = int(getattr(x, "nbytes", 0))
        budget = xjob_device_resident_budget_bytes()
        if nbytes <= 0 or nbytes > budget:
            return
        stale = self._device_stash.pop((job_id, tile_idx), None)
        if stale is not None:
            self._device_stash_bytes -= int(getattr(stale[0], "nbytes", 0))
        self._device_stash[(job_id, tile_idx)] = (x, int(step))
        self._device_stash_bytes += nbytes
        while self._device_stash_bytes > budget and len(self._device_stash) > 1:
            mark = next(iter(self._device_stash))
            old, _ = self._device_stash.pop(mark)
            self._device_stash_bytes -= int(getattr(old, "nbytes", 0))

    def _stash_take(self, job_id: str, tile_idx: int, step: int) -> Any:
        """Pop the stashed latent iff its step matches the checkpoint's
        resume step (the checkpoint payload stays the authoritative
        resume instruction; the stash only elides its decode + H2D).
        Returns None on miss or step mismatch."""
        entry = self._device_stash.pop((job_id, tile_idx), None)
        if entry is None:
            return None
        self._device_stash_bytes -= int(getattr(entry[0], "nbytes", 0))
        if entry[1] != int(step):
            return None
        return entry[0]

    def _drop_job_stash(self, job_id: str) -> None:
        dead = [mark for mark in self._device_stash if mark[0] == job_id]
        for mark in dead:
            x, _ = self._device_stash.pop(mark)
            self._device_stash_bytes -= int(getattr(x, "nbytes", 0))

    def _adopt_grant(self, job: XJobHandle, grant: dict) -> int:
        """Turn one pull answer into ready items; returns item count.
        Checkpoints that fail to decode are dropped (recompute)."""
        idxs = [int(t) for t in (grant.get("tile_idxs") or [])]
        checkpoints = grant.get("checkpoints") or {}
        added = 0
        sig = job.sig
        for tile_idx in idxs:
            self._item_seq += 1
            item = _Item(job, tile_idx, self._item_seq)
            item.key = self._tile_key(job, tile_idx)
            payload = checkpoints.get(tile_idx, checkpoints.get(str(tile_idx)))
            evicted_step = self._evicted.get((job.job_id, tile_idx))
            evicted_here = evicted_step is not None
            if payload is not None:
                try:
                    import jax.numpy as jnp

                    # Device-resident fast path: this executor evicted
                    # the tile and still holds its latent on device.
                    # The checkpoint stays the authority on WHICH step
                    # to resume at (validated structurally, cheap); the
                    # stash elides the b64 decode + H2D re-upload.
                    # Byte-exact equivalence with the host decode is
                    # pinned by tests — the checkpoint was encoded FROM
                    # this very latent at eviction.
                    step_hint = None
                    if isinstance(payload, dict):
                        try:
                            step_hint = int(payload.get("step"))
                        except (TypeError, ValueError):
                            step_hint = None
                    stashed = None
                    if (
                        step_hint is not None
                        and 0 < step_hint < job.proc.n_steps
                    ):
                        validate_checkpoint_meta(payload)
                        stashed = self._stash_take(
                            job.job_id, tile_idx, step_hint
                        )
                    if stashed is not None:
                        item.x = stashed
                        item.step = step_hint
                        item.resumed = True
                        self.resumes_device += 1
                        preempt_resume_total().inc(mode="device")
                    else:
                        state, step = decode_checkpoint(payload)
                        if 0 < step < job.proc.n_steps:
                            item.x = jnp.asarray(state)
                            item.step = step
                            item.resumed = True
                            self.resumes_checkpoint += 1
                            preempt_resume_total().inc(mode="checkpoint")
                except CheckpointError as exc:
                    debug_log(
                        f"xjob {job.job_id}:{tile_idx} checkpoint rejected "
                        f"({exc}); recomputing from step 0"
                    )
            if not item.resumed and evicted_here:
                self.resumes_recompute += 1
                preempt_resume_total().inc(mode="recompute")
                # the steps it re-runs up to the eviction mark were
                # already paid for once: waste, not tenant time
                item.recompute_until = int(evicted_step)
            self._evicted.pop((job.job_id, tile_idx), None)
            job.claimed.add(tile_idx)
            self._items.setdefault(sig, []).append(item)
            added += 1
        return added

    def _refill(self, jobs: list[XJobHandle]) -> bool:
        """Pull grants for jobs that have no live items (priority
        order). A pull answering None marks the job drained-pending-
        final-flush; preempt-flagged jobs don't pull (their released
        tiles must go to the premium work first)."""
        progressed = False
        live_jobs = {
            it.job.job_id
            for items in self._items.values()
            for it in items
        }
        for job in jobs:
            if job.done or job.error is not None:
                continue
            self._sync_preempt(job)
            if job.preempted:
                continue
            if job.job_id in live_jobs:
                continue
            try:
                grant = job.pull()
            except BaseException as exc:  # noqa: BLE001 - isolated per job
                self._fail_job(job, exc)
                continue
            if grant and grant.get("tile_idxs"):
                if self._adopt_grant(job, grant) > 0:
                    progressed = True
            else:
                # an empty pull may itself have carried the preempt
                # flag (HTTP clients learn it from the drained-reading
                # response): re-check before concluding the job is
                # done, or a preempted job would be finished — and the
                # worker lost to it — instead of parked until the
                # premium settles
                self._sync_preempt(job)
                if job.preempted:
                    continue
                self._finish_job(job)
                progressed = True
        return progressed

    # --- preemption -------------------------------------------------------

    def _sync_preempt(self, job: XJobHandle) -> None:
        if not self.preempt_enabled or job.preempt_check is None:
            return
        try:
            flagged = bool(job.preempt_check())
        except Exception as exc:  # noqa: BLE001 - advisory signal
            debug_log(f"preempt check for {job.job_id} failed: {exc}")
            return
        if flagged and not job.preempted:
            self._evict_job(job)
        job.preempted = flagged

    def _evict_job(self, job: XJobHandle) -> None:
        """Checkpoint + release every live item of `job` at this step
        boundary: mid-trajectory latents serialize into checkpoints,
        uninitialized items release bare. The release callback routes
        through the master's requeue path, so the tiles are pullable
        by (or after) the premium work immediately."""
        sig = job.sig
        items = [it for it in self._items.get(sig, []) if it.job is job]
        if not items:
            return
        self._items[sig] = [it for it in self._items[sig] if it.job is not job]
        idxs: list[int] = []
        checkpoints: dict[int, Any] = {}
        for item in sorted(items, key=lambda it: it.tile_idx):
            idxs.append(item.tile_idx)
            self._evicted[(job.job_id, item.tile_idx)] = int(item.step)
            if item.x is not None and 0 < item.step < job.proc.n_steps:
                try:
                    checkpoints[item.tile_idx] = encode_checkpoint(
                        item.x, item.step
                    )
                except CheckpointError as exc:
                    debug_log(
                        f"xjob {job.job_id}:{item.tile_idx} checkpoint "
                        f"encode failed ({exc}); releasing bare"
                    )
                else:
                    # the encoded host copy is the SPILL; the live
                    # device latent stays parked for re-grant here
                    self._stash_put(
                        job.job_id, item.tile_idx, item.x, item.step
                    )
            job.claimed.discard(item.tile_idx)
        self.preempt_evictions += len(idxs)
        debug_log(
            f"xjob executor: preempted {len(idxs)} tile(s) of job "
            f"{job.job_id} at step boundary ({len(checkpoints)} "
            "checkpointed)"
        )
        if job.release is not None:
            try:
                job.release(idxs, checkpoints)
            except Exception as exc:  # noqa: BLE001 - master requeue covers
                debug_log(f"xjob release for {job.job_id} failed: {exc}")

    # --- completion / failure ---------------------------------------------

    def _drop_job_eviction_marks(self, job_id: str) -> None:
        """A departing job's eviction marks are dead weight on the
        process-shared executor — drop them so the set stays bounded
        by live in-flight work."""
        self._evicted = {
            mark: step for mark, step in self._evicted.items()
            if mark[0] != job_id
        }

    def _prune_signature(self, sig: tuple) -> None:
        """Drop a signature's queue/order/compiled-program entries once
        its LAST registered job departs: the process-shared executor
        outlives jobs, and a cached vstep closure pins the job's step
        function — bundle, sigmas, grid and (when jitted) executables —
        for the process lifetime otherwise. While any same-signature
        job remains, the cache stays (that sharing is what keeps
        same-config jobs compile-free). Check-and-prune is ATOMIC
        under the registration lock: a same-signature register()
        racing this must either see the entries intact or re-create
        them — never lose its _items list to a prune that decided
        before it registered."""
        with self._lock:
            alive = any(j.sig == sig for j in self._jobs.values())
            if alive or self._items.get(sig):
                return
            self._items.pop(sig, None)
            if sig in self._sig_order:
                self._sig_order.remove(sig)
            self._vstep_cache.pop(sig, None)

    def _finish_job(self, job: XJobHandle) -> None:
        if job.done:
            return
        job.done = True
        with contextlib.suppress(Exception):
            job.flush(True)
        with self._lock:
            self._jobs.pop(job.job_id, None)
        self._drop_job_eviction_marks(job.job_id)
        self._drop_job_stash(job.job_id)
        self._prune_signature(job.sig)
        job.finished.set()

    def _fail_job(self, job: XJobHandle, exc: BaseException) -> None:
        """Isolate one job's callback failure: release what it still
        claims (bare — its master's requeue path recomputes) and drop
        it from the executor; other jobs keep batching."""
        job.error = exc
        debug_log(f"xjob job {job.job_id} failed: {exc!r}")
        sig = job.sig
        items = [it for it in self._items.get(sig, []) if it.job is job]
        self._items[sig] = [it for it in self._items.get(sig, []) if it.job is not job]
        orphaned = sorted({it.tile_idx for it in items} | set(job.claimed))
        if orphaned and job.release is not None:
            with contextlib.suppress(Exception):
                job.release(orphaned, {})
        job.claimed.clear()
        with self._lock:
            self._jobs.pop(job.job_id, None)
        self._drop_job_eviction_marks(job.job_id)
        self._drop_job_stash(job.job_id)
        self._prune_signature(job.sig)
        job.finished.set()

    # --- the scheduling round ---------------------------------------------

    def _select_batch(self) -> list[_Item]:
        """Compose the next device batch: the signature group holding
        the most-urgent item, items sorted by (priority, arrival), up
        to k_max. ``cross_job=False`` further restricts the batch to
        the first item's job — the per-job baseline."""
        best_sig = None
        best_order = None
        for sig in self._sig_order:
            items = self._items.get(sig)
            if not items:
                continue
            head = min(it.order() for it in items)
            if best_order is None or head < best_order:
                best_order = head
                best_sig = sig
        if best_sig is None:
            return []
        items = sorted(self._items[best_sig], key=_Item.order)
        if not self.cross_job:
            owner = items[0].job
            items = [it for it in items if it.job is owner]
        batch = items[: self.k_max]
        remaining = [it for it in self._items[best_sig] if it not in batch]
        self._items[best_sig] = remaining
        return batch

    def _bucket_for(self, n: int) -> int:
        from ..ops.upscale import bucket_for

        return bucket_for(n, self.k_max, self.buckets)

    def _init_items(self, batch: list[_Item]) -> None:
        """Encode + noise items entering at step 0. Per-item single-
        tile programs (one compiled shape per signature): init and
        finish are one model call each, dwarfed by the per-step loop,
        so batching them would buy little and cost extra compiles."""
        for item in batch:
            if item.x is None:
                job = item.job
                item.x = job.proc.init(
                    job.params, job.extracted[item.tile_idx], item.key
                )

    def _step_batch(self, batch: list[_Item]) -> None:
        """ONE denoise step for the whole batch: pad to the bucket
        with wraparound duplicates of real items (their updated lanes
        are sliced off — numerics never depend on padding), stack
        per-item inputs, run the shared vmapped program, scatter the
        advanced latents back."""
        import jax.numpy as jnp
        import jax.tree_util as jtu

        sig = batch[0].job.sig
        n = len(batch)
        bucket = self._bucket_for(n)
        padded = [batch[i % n] for i in range(bucket)]
        params = batch[0].job.params
        xs = jnp.stack([it.x for it in padded], axis=0)
        keys = jnp.stack([it.key for it in padded], axis=0)
        poss = jtu.tree_map(
            lambda *leaves: jnp.stack(leaves, axis=0),
            *[it.job.pos for it in padded],
        )
        negs = jtu.tree_map(
            lambda *leaves: jnp.stack(leaves, axis=0),
            *[it.job.neg for it in padded],
        )
        yxs = jnp.stack(
            [jnp.asarray(it.job.positions[it.tile_idx]) for it in padded],
            axis=0,
        )
        steps = jnp.asarray([it.step for it in padded], jnp.int32)
        # Adapter plane: every batch-mate shares the extended signature
        # (same rank bucket + target-path set), so per-slot operands
        # stack into [B, r_b, I] / [B, O, r_b] stacks per targeted leaf
        # — each lane samples under ITS OWN job's low-rank patch while
        # the base params stay a single broadcast copy. Adapter-less
        # batches never reach this branch (their signature is the
        # unmodified stepwise tuple).
        adapter = batch[0].job.adapter
        if adapter is not None:
            downs = tuple(
                jnp.stack(
                    [it.job.adapter.downs[k] for it in padded], axis=0
                )
                for k in range(len(adapter.paths))
            )
            ups = tuple(
                jnp.stack([it.job.adapter.ups[k] for it in padded], axis=0)
                for k in range(len(adapter.paths))
            )
            scales = jnp.asarray(
                [it.job.adapter.scale for it in padded], jnp.float32
            )
            xs, keys, poss, negs, yxs, steps, downs, ups, scales = (
                self._place(
                    (xs, keys, poss, negs, yxs, steps, downs, ups, scales)
                )
            )
        else:
            xs, keys, poss, negs, yxs, steps = self._place(
                (xs, keys, poss, negs, yxs, steps)
            )
        fn = self._vstep(
            sig,
            batch[0].job.proc.step,
            adapter.paths if adapter is not None else None,
        )
        # slot-exact attribution: one entry per device slot of the
        # padded bucket, classified BEFORE the step advances — a real
        # item re-running steps below its eviction mark is recompute
        # waste, a wraparound duplicate is padding
        slots = [
            {
                "job_id": it.job.job_id,
                "kind": (
                    SLOT_RECOMPUTE
                    if it.step < it.recompute_until
                    else SLOT_REAL
                ),
            }
            for it in batch
        ] + [{"job_id": "", "kind": SLOT_PADDING}] * (bucket - n)
        slot_tenants: dict[str, int] = {}
        slot_jobs: dict[str, int] = {}
        for it in batch:
            slot_tenants[it.job.tenant] = slot_tenants.get(it.job.tenant, 0) + 1
            slot_jobs[it.job.job_id] = slot_jobs.get(it.job.job_id, 0) + 1
        # one span per DEVICE DISPATCH with its fill accounting —
        # perf_report's batch-fill column reconstructs the ratio from
        # exactly these attrs (real tiles vs bucket slots), and the
        # --usage column splits the span's wall across slot_jobs /
        # slot_tenants / padding the same way the meter does
        # compiled-vs-eager split for the transfer ledger (same rule as
        # _vstep's jit gate): only compiled programs count device time
        device = hasattr(batch[0].job.proc.step, "lower")
        ledger = ledger_if_enabled()
        started = time.monotonic()
        with stage_span(
            "dispatch", self.role, batch[0].tile_idx,
            real=n, bucket=int(bucket),
            jobs=len({it.job.job_id for it in batch}),
            slot_jobs=slot_jobs, slot_tenants=slot_tenants,
            device=device,
            recompute=sum(
                1 for s in slots if s["kind"] == SLOT_RECOMPUTE
            ),
            adapter=adapter is not None,
        ):
            if adapter is not None:
                out = fn(
                    params, xs, keys, poss, negs, yxs, steps,
                    downs, ups, scales,
                )
            else:
                out = fn(params, xs, keys, poss, negs, yxs, steps)
            if device and ledger is not None:
                # profiling wants honest device-execute wall: JAX
                # dispatch is async, so block inside the bracket
                import jax

                out = jax.block_until_ready(out)  # cdt: noqa[CDT007]
        elapsed = time.monotonic() - started
        if self.usage is not None:
            self.usage.note_dispatch(
                tier="xjob",
                role=self.role,
                elapsed_s=elapsed,
                chips=self._chips,
                slots=slots,
            )
        if ledger is not None:
            ledger.note_dispatch(
                elapsed, tier="xjob", role=self.role, device=device
            )
        self.dispatches += 1
        self.steps_run += n
        self.slots_real += n
        self.slots_padded += bucket - n
        batch_fill_ratio().set(n / bucket, role=self.role)
        if adapter is not None:
            from ..telemetry.instruments import adapter_slots_total

            adapter_slots_total().inc(n, role=self.role)
        pipeline_batches_total().inc(role=self.role, bucket=str(bucket))
        if bucket > n:
            pipeline_padded_tiles_total().inc(bucket - n, role=self.role)
        for i, item in enumerate(batch):
            item.x = out[i]
            item.step += 1

    def _retire(self, batch: list[_Item]) -> None:
        """Finish items whose trajectory completed: decode, emit to
        their OWNING job (the fan-back seam), count, flush. Unfinished
        items return to their signature queue for the next round."""
        for item in batch:
            job = item.job
            if job.error is not None:
                continue  # failed mid-retire: its master requeues
            if item.step >= job.proc.n_steps:
                with stage_span(
                    "sample", self.role, item.tile_idx, job_id=job.job_id
                ):
                    out = job.proc.finish(job.params, item.x)
                ledger = ledger_if_enabled()
                if job.device_emit:
                    # device-canvas consumer: the tile stays on device;
                    # the canvas flush pays ONE composited d2h instead
                    # of one per tile
                    host = out
                    if ledger is not None:
                        ledger.note_tiles(1)
                else:
                    readback_started = time.monotonic()
                    host = self._to_host(out)
                    if ledger is not None:
                        ledger.note_transfer(
                            D2H,
                            int(getattr(host, "nbytes", 0)),
                            time.monotonic() - readback_started,
                        )
                        ledger.note_tiles(1)
                try:
                    with stage_span(
                        "encode", self.role, item.tile_idx, job_id=job.job_id
                    ):
                        job.emit(item.tile_idx, host)
                    job.claimed.discard(item.tile_idx)
                    job.tiles_done += 1
                    self.tiles_finished += 1
                    if self.usage is not None:
                        self.usage.note_tiles(self.role, job.job_id, 1)
                    self.completion_order.append((job.job_id, item.tile_idx))
                    if len(self.completion_order) > self._max_completion_order:
                        del self.completion_order[
                            : -self._max_completion_order // 2
                        ]
                    tiles_processed_total().inc(role=self.role)
                    job.flush(False)
                except BaseException as exc:  # noqa: BLE001 - per-job isolation
                    self._fail_job(job, exc)
            else:
                self._items.setdefault(job.sig, []).append(item)

    @staticmethod
    def _to_host(result):
        from ..utils import image as img_utils

        # the _retire readback: ledger-bracketed (D2H note) at the one
        # call site, skipped entirely for device_emit jobs
        return img_utils.ensure_numpy(result)  # cdt: noqa[CDT007]

    # --- driver -----------------------------------------------------------

    def run(self) -> dict[str, Any]:
        """Drive scheduling rounds until every registered job drains
        (or ``stop()``). Returns summary stats; per-job errors are
        recorded on their handles and raised (first one) unless every
        job completed — callers that need partial progress inspect
        handles directly."""
        last_beat = self.clock()
        errors: list[BaseException] = []
        while not self._stop.is_set():
            with self._lock:
                jobs = sorted(
                    self._jobs.values(), key=lambda j: (j.priority, j.seq)
                )
            if not jobs:
                break
            # interrupt seam (the dispatched prompt's interrupt, a
            # cooperative cancel): checked at every step boundary; a
            # raising job releases its claims and leaves, like the
            # TilePipeline interrupt path
            for job in jobs:
                if job.done or job.error is not None:
                    continue
                if job.check_interrupted is not None:
                    try:
                        job.check_interrupted()
                    except BaseException as exc:  # noqa: BLE001
                        self._fail_job(job, exc)
            progressed = self._refill(jobs)
            # preemption flags may have flipped between refills; evict
            # at this boundary before composing the batch
            for job in jobs:
                if not job.done and job.error is None:
                    self._sync_preempt(job)
            batch = self._select_batch()
            if batch:
                try:
                    self._init_items(batch)
                    self._step_batch(batch)
                except BaseException as exc:  # noqa: BLE001
                    # a device-program failure poisons the whole batch:
                    # fail every owning job (their masters requeue)
                    for job in sorted(
                        {it.job for it in batch}, key=lambda j: j.seq
                    ):
                        self._fail_job(job, exc)
                    errors.append(exc)
                    continue
                self._retire(batch)
                progressed = True
            now = self.clock()
            if now - last_beat >= 1.0:
                # paced: an idle (preempt-parked / drained-waiting)
                # executor must not turn every 20 ms poll round into a
                # heartbeat RPC per job against the master
                last_beat = now
                for job in jobs:
                    if job.heartbeat is not None and not job.done:
                        with contextlib.suppress(Exception):
                            job.heartbeat()
            if not progressed:
                # nothing ready anywhere (all jobs preempt-parked or
                # their queues momentarily empty): idle briefly
                time.sleep(self.idle_poll_seconds)
        with self._lock:
            leftover = sorted(self._jobs.values(), key=lambda j: j.seq)
        for job in leftover:
            if job.error is not None:
                errors.append(job.error)
        stats = {
            "dispatches": self.dispatches,
            "steps_run": self.steps_run,
            "tiles": self.tiles_finished,
            "slots_real": self.slots_real,
            "slots_padded": self.slots_padded,
            "fill_ratio": self.fill_ratio(),
            "preempt_evictions": self.preempt_evictions,
            "resumes_checkpoint": self.resumes_checkpoint,
            "resumes_recompute": self.resumes_recompute,
            "resumes_device": self.resumes_device,
        }
        if errors:
            raise errors[0]
        return stats


# --------------------------------------------------------------------------
# production entries (CDT_XJOB_BATCH=1): elastic master/worker loops
# routed through one process-shared executor
# --------------------------------------------------------------------------


class SharedExecutor:
    """Process-global CrossJobExecutor + lazily-(re)started driver
    thread. Every concurrently-running elastic job in this process —
    dispatched worker prompts, the master's own participation —
    registers here, which is exactly what makes their tiles share
    device batches."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._executor: Optional[CrossJobExecutor] = None
        self._thread: Optional[threading.Thread] = None

    def executor(self, *, k_max: int, mesh: Any, role: str) -> CrossJobExecutor:
        from ..utils.constants import PREEMPT_ENABLED

        with self._lock:
            if self._executor is None:
                self._executor = CrossJobExecutor(
                    k_max=k_max,
                    mesh=mesh,
                    role=role,
                    preempt_enabled=PREEMPT_ENABLED == 1,
                )
            return self._executor

    def ensure_running(self) -> None:
        with self._lock:
            if self._executor is None:
                return
            if self._thread is not None and self._thread.is_alive():
                return

            executor = self._executor

            def drive() -> None:
                try:
                    executor.run()
                except BaseException as exc:  # noqa: BLE001 - per-job errors
                    # already delivered on each handle; the shared
                    # driver itself must not die loudly between jobs
                    debug_log(f"shared xjob executor driver exit: {exc!r}")

            self._thread = threading.Thread(
                target=drive, name="cdt-xjob-executor", daemon=True
            )
            self._thread.start()


_SHARED = SharedExecutor()


def get_shared_executor() -> SharedExecutor:
    return _SHARED


def _reset_shared_executor_for_tests() -> None:
    global _SHARED
    _SHARED = SharedExecutor()


def _prep_xjob(
    bundle, image, pos, neg, upscale_by, tile, padding, upscale_method,
    tile_h, mask_blur, uniform, steps, sampler, scheduler, cfg, denoise,
    tiled_decode, seed, job_id, precision=None, lane="",
):
    """Shared prep for the xjob master/worker entries: tile extraction,
    per-tile conditioning, the step-resumable processor, and the
    job-folded base key (parallel/seeds.fold_job_key — the key gains
    the job id so cross-tenant batch-mates can never correlate).

    ``precision`` (None = resolve from the lane via CDT_BF16_LANES)
    picks the latent-carry lane; it joins the processor signature, so
    f32 and bf16 jobs never share a device batch."""
    import jax

    from ..ops import upscale as upscale_ops
    from ..ops.stepwise import make_stepwise_tile_processor
    from ..parallel.seeds import fold_job_key
    from ..utils.constants import precision_for_lane

    if precision is None:
        precision = precision_for_lane(lane)
    upscaled, grid, extracted = upscale_ops.prepare_upscaled_tiles(
        image, upscale_by, tile, padding, upscale_method, tile_h,
        mask_blur=mask_blur, uniform=uniform,
    )
    pos = upscale_ops.prep_cond_for_tiles(pos, grid)
    neg = upscale_ops.prep_cond_for_tiles(neg, grid)
    proc = make_stepwise_tile_processor(
        bundle, grid, steps, sampler, scheduler, cfg, denoise, tiled_decode,
        precision=precision,
    )
    base_key = fold_job_key(jax.random.key(seed), job_id)
    return upscaled, grid, extracted, pos, neg, proc, base_key


def run_worker_xjob(
    bundle,
    image,
    pos,
    neg,
    job_id: str,
    worker_id: str,
    master_url: str,
    upscale_by: float,
    tile: int,
    padding: int,
    steps: int,
    sampler: str,
    scheduler: str,
    cfg: float,
    denoise: float,
    seed: int,
    upscale_method: str = "bicubic",
    mask_blur: int = 0,
    uniform: bool = True,
    tiled_decode: bool = False,
    tile_h: int | None = None,
    context=None,
    client: Any = None,
    mesh: Any = None,
    lane: str = "",
    precision: str | None = None,
) -> None:
    """CDT_XJOB_BATCH worker entry (same signature as
    ``run_worker_loop``): registers this job with the process-shared
    continuous-batching executor and parks until it drains. Raises
    ``ValueError`` from the stepwise factory for unsupported samplers —
    the delegating caller falls back to the scan tier."""
    from ..utils import image as img_utils
    from ..utils.constants import (
        MAX_TILE_BATCH,
        SCHED_MAX_PULL_BATCH,
        tile_scan_batch,
    )
    from ..utils.exceptions import WorkerError
    from ..utils.logging import log
    from ..parallel.mesh import (
        advertised_capacity,
        data_axis_size,
        note_serving_mesh,
        worker_mesh,
    )
    from ..parallel.sharding import maybe_shard_params, params_byte_size

    params = bundle.params
    if mesh is None:
        mesh = worker_mesh(params_bytes=params_byte_size(params))
    note_serving_mesh(mesh)
    capacity = advertised_capacity(mesh)
    _, grid, extracted, pos, neg, proc, base_key = _prep_xjob(
        bundle, image, pos, neg, upscale_by, tile, padding, upscale_method,
        tile_h, mask_blur, uniform, steps, sampler, scheduler, cfg, denoise,
        tiled_decode, seed, job_id, precision=precision, lane=lane,
    )
    from .usdu_elastic import HTTPWorkClient, _flush_threshold_bytes

    client = client or HTTPWorkClient(
        master_url, job_id, worker_id, devices=capacity
    )
    params = maybe_shard_params(params, mesh)
    if not client.poll_ready():
        raise WorkerError(f"job {job_id} never became ready", worker_id)

    # Adapter plane: the readiness poll carried the job's resolved wire
    # plan. Re-resolve against the LOCAL catalog — resolve() verifies
    # the master-stamped content hashes against local bytes, so a
    # divergent checkpoint fails loudly here instead of sampling wrong
    # pixels — then build the rank-bucketed per-slot operands (served
    # from the process adapter cache).
    adapter = None
    adapter_wire = getattr(client, "adapters", None) or []
    if adapter_wire:
        from ..adapters import (
            bundle_target_map,
            get_adapter_catalog,
            operands_for_plan,
            specs_from_wire,
        )
        from ..telemetry.instruments import adapter_jobs_total

        specs = get_adapter_catalog().resolve(specs_from_wire(adapter_wire))
        adapter = operands_for_plan(specs, bundle_target_map(bundle))
        adapter_jobs_total().inc(tier="xjob")

    pending: list[dict] = []
    pending_bytes = 0

    def emit(tile_idx: int, arr) -> None:
        nonlocal pending_bytes
        for batch_idx in range(arr.shape[0]):
            encoded = img_utils.encode_image_data_url(arr[batch_idx])
            y, x = grid.positions[tile_idx]
            pending.append(
                {
                    "tile_idx": tile_idx,
                    "batch_idx": batch_idx,
                    "global_idx": tile_idx * arr.shape[0] + batch_idx,
                    "x": int(x),
                    "y": int(y),
                    "extracted_w": grid.padded_w,
                    "extracted_h": grid.padded_h,
                    "image": encoded,
                }
            )
            pending_bytes += len(encoded)

    def flush(is_final: bool) -> None:
        nonlocal pending, pending_bytes
        if not is_final and (
            len(pending) < MAX_TILE_BATCH
            and pending_bytes < _flush_threshold_bytes()
        ):
            return
        if pending or is_final:
            with stage_span("submit", "worker", worker_id=worker_id):
                client.submit_tiles(pending, is_final)
        pending, pending_bytes = [], 0

    def pull() -> Optional[dict]:
        work = client.request_tile(batch_max=SCHED_MAX_PULL_BATCH * capacity)
        if work is None:
            return None
        idxs = work.get("tile_idxs") or (
            [work["tile_idx"]] if work.get("tile_idx") is not None else []
        )
        return {
            "tile_idxs": [int(t) for t in idxs],
            "checkpoints": work.get("checkpoints") or {},
        }

    def release(idxs: list[int], checkpoints: dict) -> None:
        client.return_tiles(idxs, checkpoints=checkpoints)

    def check_abort() -> None:
        if context is not None:
            context.check_interrupted()
        if getattr(client, "job_cancelled", False):
            raise InterruptedError(
                f"job {job_id} cancelled by master "
                f"({getattr(client, 'cancel_reason', '') or 'cancelled'})"
            )

    handle = XJobHandle(
        job_id=job_id,
        proc=proc,
        params=params,
        extracted=extracted,
        positions=grid.positions_array(),
        pos=pos,
        neg=neg,
        base_key=base_key,
        pull=pull,
        emit=emit,
        flush=flush,
        release=release,
        preempt_check=lambda: bool(getattr(client, "preempt_requested", False)),
        heartbeat=client.heartbeat,
        check_interrupted=check_abort,
        adapter=adapter,
    )
    shared = get_shared_executor()
    executor = shared.executor(
        k_max=tile_scan_batch() * max(1, data_axis_size(mesh) if mesh else 1),
        mesh=mesh,
        role="worker",
    )
    executor.register(handle)
    while True:
        shared.ensure_running()
        if handle.finished.wait(timeout=0.25):
            break
    if handle.error is not None:
        if isinstance(handle.error, InterruptedError) and getattr(
            client, "job_cancelled", False
        ):
            log(
                f"worker {worker_id}: job {job_id} cancelled; aborted cleanly"
            )
            return
        raise handle.error


def run_master_xjob(
    bundle,
    image,
    pos,
    neg,
    job_id: str,
    enabled_worker_ids: list,
    mesh=None,
    upscale_by: float = 2.0,
    tile: int = 512,
    padding: int = 32,
    steps: int = 20,
    sampler: str = "euler",
    scheduler: str = "karras",
    cfg: float = 7.0,
    denoise: float = 0.35,
    seed: int = 0,
    upscale_method: str = "bicubic",
    mask_blur: int = 0,
    uniform: bool = True,
    tiled_decode: bool = False,
    tile_h: int | None = None,
    context=None,
    lane: str = "",
    precision: str | None = None,
):
    """CDT_XJOB_BATCH master entry (same signature/contract as
    ``run_master_elastic``): the master participates through the shared
    continuous-batching executor — its own compute rides the same
    cross-job batches as any other registered job — while this thread
    runs the collection loop (worker-result drain, timeout requeue,
    deadline sweep, lifecycle settle)."""
    import time as _time

    import jax.numpy as jnp
    import numpy as np

    from ..ops import tiles as tile_ops
    from ..utils import image as img_utils
    from ..utils.async_helpers import run_async_in_server_loop
    from ..utils.config import get_worker_timeout_seconds
    from ..utils.constants import (
        QUEUE_POLL_INTERVAL_SECONDS,
        tile_scan_batch,
    )
    from ..utils.exceptions import JobCancelled, JobPoisoned
    from ..utils.logging import log
    from ..parallel.mesh import data_axis_size, note_serving_mesh

    import os as _os

    server = context.server
    store = server.job_store
    upscaled, grid, extracted, pos, neg, proc, base_key = _prep_xjob(
        bundle, image, pos, neg, upscale_by, tile, padding, upscale_method,
        tile_h, mask_blur, uniform, steps, sampler, scheduler, cfg, denoise,
        tiled_decode, seed, job_id, precision=precision, lane=lane,
    )
    note_serving_mesh(mesh)
    master_width = data_axis_size(mesh) if mesh is not None else 1

    async def _note_master_capacity() -> None:
        store.note_worker_capacity("master", master_width)

    run_async_in_server_loop(_note_master_capacity())
    done_tiles: set[int] = set()
    timeout = get_worker_timeout_seconds()

    # Adapter plane: the orchestration parked the resolved wire plan in
    # the store (note_job_adapters) — peek it (non-destructive; the
    # init below pops + journals it) and build this master's own
    # operands. The plan key joins the cache key: flipping ONLY the
    # adapter hash or strength must flip every tile key.
    adapter = None
    adapter_key = None
    adapter_wire = run_async_in_server_loop(
        store.peek_job_adapters(job_id), timeout=30
    )
    if adapter_wire:
        from ..adapters import (
            adapter_plan_key,
            bundle_target_map,
            get_adapter_catalog,
            operands_for_plan,
            specs_from_wire,
        )
        from ..telemetry.instruments import adapter_jobs_total

        adapter_specs = get_adapter_catalog().resolve(
            specs_from_wire(adapter_wire)
        )
        adapter_key = adapter_plan_key(adapter_specs)
        adapter = operands_for_plan(adapter_specs, bundle_target_map(bundle))
        adapter_jobs_total().inc(tier="xjob")

    # --- content-addressed tile cache (cache/), CDT_CACHE=1 ----------
    # The xjob tier keys on the JOB-FOLDED base key (_prep_xjob's
    # fold_job_key): its tile outputs depend on job_id, so entries can
    # only dedup a re-run of the SAME job (crash/requeue/retry) —
    # never across jobs. The per-tile key derivation is otherwise
    # identical to the elastic tier's. UNPATCHED params on purpose:
    # the adapter's identity enters through `adapter=` (the plan key),
    # so the params fingerprint stays one hash per checkpoint.
    from ..cache import bind_job_cache, job_key_context, tile_keys_for
    from ..utils.constants import USAGE_ENABLED

    cache_binding = bind_job_cache(
        lambda: tile_keys_for(
            job_key_context(
                bundle.params, pos, neg, base_key, grid,
                steps=steps, sampler=sampler, scheduler=scheduler,
                cfg=cfg, denoise=denoise, upscale_by=upscale_by,
                upscale_method=upscale_method, mask_blur=mask_blur,
                uniform=uniform, tiled_decode=tiled_decode,
                adapter=adapter_key,
            ),
            extracted, grid,
        )
    )

    # Canvas routing rule (see docs/performance.md): the on-device
    # canvas takes master-local tiles when CDT_DEVICE_CANVAS=1 AND the
    # tile result cache is off — cache population needs host tile
    # bytes, so with the cache on the per-tile materialization happens
    # regardless and the device canvas buys nothing. Remote workers
    # keep the PNG path either way (their tiles arrive host-side by
    # construction and are uploaded once into the device canvas).
    # Sorted compositing keeps the device canvas deterministic — and
    # bit-identical to DeterministicHostCanvas, a hard test gate.
    from ..utils.constants import device_canvas_enabled

    device_canvas = device_canvas_enabled() and cache_binding is None
    if device_canvas:
        canvas = tile_ops.DeviceCanvas(upscaled, grid)
    elif _os.environ.get("CDT_DETERMINISTIC_BLEND") == "1":
        canvas = tile_ops.DeterministicHostCanvas(upscaled, grid)
    else:
        canvas = tile_ops.HostIncrementalCanvas(upscaled, grid)

    def blend_local(tile_idx: int, result) -> None:
        with stage_span("blend", "master", tile_idx):
            y, x = grid.positions[tile_idx]
            if cache_binding is not None:
                # one host materialisation serves both the cache
                # write-back and the host canvas blend
                result = np.asarray(result)  # cdt: noqa[CDT007]
                cache_binding.populate(tile_idx, result)
            canvas.blend(result, y, x)
            done_tiles.add(tile_idx)

    # Probe BEFORE the job exists, settle ATOMICALLY with its creation
    # (init_tile_job's cache_settled): hits are journaled
    # (`cache_settle`) with the pending queue shrunken under the same
    # lock hold, so no puller or batch-mate ever burns a slot on them
    # and a warm run's settled count is deterministic. On a
    # pre-existing job (recovery re-entry) creation ignored the list —
    # fall back to the standalone op, which excludes tiles workers
    # already completed (those must not be re-blended).
    cached_hits: dict = {}
    if cache_binding is not None:
        with stage_span("cache.probe", "master") as probe_span:
            cached_hits = cache_binding.probe()
            probe_span.attrs["hits"] = len(cached_hits)
    job = run_async_in_server_loop(
        store.init_tile_job(
            job_id, list(range(grid.num_tiles)),
            cache_settled=sorted(cached_hits) if cached_hits else None,
        ),
        timeout=30,
    )
    if cached_hits:
        settled = [t for t in sorted(cached_hits) if t in job.cached_tiles]
        if not settled:
            settled = run_async_in_server_loop(
                store.settle_cached(job_id, sorted(cached_hits)), timeout=30
            )
        for tile_idx in settled:
            with stage_span("cache.hit", "master", tile_idx):
                y, x = grid.positions[tile_idx]
                canvas.blend(cached_hits[tile_idx], y, x)
                done_tiles.add(tile_idx)
        if settled:
            cache_binding.cache.note_settled(len(settled))
            if USAGE_ENABLED:
                get_usage_meter().note_cached(
                    "master", job_id, len(settled)
                )

    def drain_results() -> None:
        async def drain():
            job = await store.get_tile_job(job_id)
            items = []
            while job is not None and not job.results.empty():
                items.append(job.results.get_nowait())
            return items

        for tile_idx, payload in run_async_in_server_loop(drain(), timeout=30):
            if tile_idx in done_tiles or payload is None:
                continue
            with stage_span("decode", "master", tile_idx):
                batch = [
                    img_utils.decode_image_data_url(e["image"])
                    for e in sorted(payload, key=lambda e: e["batch_idx"])
                ]
            # remote PNG tiles are ALREADY host bytes — stacking them
            # pulls nothing off a device
            blend_local(tile_idx, jnp.asarray(np.stack(batch, axis=0)))  # cdt: noqa[CDT007]

    # --- master's own compute rides the shared executor ------------------
    def pull() -> Optional[dict]:
        async def pull_any():
            tasks = await store.pull_tasks(
                job_id, "master", timeout=QUEUE_POLL_INTERVAL_SECONDS
            )
            if not tasks:
                return None
            return {
                "tile_idxs": tasks,
                "checkpoints": await store.checkpoints_for(job_id, tasks),
            }

        return run_async_in_server_loop(pull_any(), timeout=30)

    def emit(tile_idx: int, arr) -> None:
        blend_local(int(tile_idx), jnp.asarray(arr))

    def flush(is_final: bool) -> None:
        pass  # blends are local; accounting rides emit->submit below

    def submit_done(tile_idx: int) -> None:
        run_async_in_server_loop(
            store.submit_flush(job_id, "master", {int(tile_idx): None}),
            timeout=30,
        )

    def emit_and_submit(tile_idx: int, arr) -> None:
        emit(tile_idx, arr)
        submit_done(tile_idx)

    def release(idxs: list[int], checkpoints: dict) -> None:
        run_async_in_server_loop(
            store.release_tasks(job_id, "master", idxs, checkpoints=checkpoints),
            timeout=30,
        )

    def preempt_check() -> bool:
        async def read():
            job = await store.get_tile_job(job_id)
            return bool(job is not None and job.preempt_requested)

        return run_async_in_server_loop(read(), timeout=30)

    def check_abort() -> None:
        if context is not None:
            context.check_interrupted()

    def make_master_handle() -> XJobHandle:
        return XJobHandle(
            job_id=job_id,
            proc=proc,
            params=bundle.params,
            extracted=extracted,
            positions=grid.positions_array(),
            pos=pos,
            neg=neg,
            base_key=base_key,
            pull=pull,
            emit=emit_and_submit,
            flush=flush,
            release=release,
            preempt_check=preempt_check,
            check_interrupted=check_abort,
            adapter=adapter,
            device_emit=device_canvas,
        )

    shared = get_shared_executor()
    executor = shared.executor(
        k_max=tile_scan_batch() * max(1, master_width), mesh=mesh,
        role="master",
    )
    handle = make_master_handle()
    executor.register(handle)

    def _lifecycle() -> dict:
        state = run_async_in_server_loop(store.job_lifecycle(job_id), timeout=30)
        return state or {
            "cancelled": False, "cancel_reason": "", "quarantined": [],
        }

    deadline = _time.monotonic() + timeout * max(1, len(enabled_worker_ids) + 1)
    while True:
        shared.ensure_running()
        lifecycle = _lifecycle()
        quarantined = set(lifecycle["quarantined"])
        if lifecycle["cancelled"] or (
            len(done_tiles | quarantined) >= grid.num_tiles
        ):
            break
        if context is not None:
            context.check_interrupted()
        run_async_in_server_loop(store.sweep_deadlines(), timeout=30)
        drain_results()
        run_async_in_server_loop(
            store.requeue_timed_out(job_id, timeout, None), timeout=60
        )
        if handle.error is not None:
            break
        if _time.monotonic() > deadline:
            log(f"USDU xjob: master deadline hit on job {job_id}")
            break
        if handle.finished.wait(timeout=QUEUE_POLL_INTERVAL_SECONDS):
            # the executor drained its view of the queue; keep draining
            # worker results until the job settles
            drain_results()
            if len(done_tiles | quarantined) >= grid.num_tiles:
                break
            pending_now = run_async_in_server_loop(
                store.remaining(job_id), timeout=30
            )
            if pending_now and not lifecycle["cancelled"]:
                # requeued tiles (a crashed/timed-out worker's claims,
                # watchdog speculation) landed AFTER the master's view
                # drained: re-enter the executor so the master can
                # re-run them locally — the run_master_elastic contract
                handle = make_master_handle()
                executor.register(handle)
                continue
            _time.sleep(QUEUE_POLL_INTERVAL_SECONDS)

    drain_results()
    lifecycle = _lifecycle()
    run_async_in_server_loop(store.cleanup_tile_job(job_id), timeout=30)
    if handle.error is not None and not isinstance(
        handle.error, InterruptedError
    ):
        raise handle.error
    if lifecycle["cancelled"]:
        raise JobCancelled(job_id, lifecycle["cancel_reason"] or "cancel")
    poisoned = sorted(set(lifecycle["quarantined"]) - done_tiles)
    if poisoned:
        policy = getattr(store, "poison_policy", "degrade")
        if policy == "fail":
            raise JobPoisoned(job_id, poisoned)
        log(
            f"USDU xjob: job {job_id} completes DEGRADED: tile(s) "
            f"{poisoned} quarantined"
        )
    if device_canvas:
        # ONE composited d2h per flush — the whole point. Ledger-noted
        # here so perf_report's d2h-bytes/tile column sees the canvas
        # transfer instead of per-tile readbacks.
        with stage_span("readback", "master", tiles=canvas.tile_count):
            started = _time.monotonic()
            composited = canvas.result()
            host = np.asarray(composited)  # cdt: noqa[CDT007]
            ledger = ledger_if_enabled()
            if ledger is not None:
                ledger.note_transfer(
                    D2H, int(host.nbytes), _time.monotonic() - started
                )
        return jnp.asarray(host)
    return canvas.result()
