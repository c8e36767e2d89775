"""Benchmark harness: one process that measures on the device it finds.

Prints one JSON line; the LAST line is the datum. It runs on a TPU and
exits non-zero on anything else — a CPU timing is not a device metric —
unless BENCH_CPU=1 asks for the CPU by name (the CI smoke and the tiny
A/B blocks below, which measure the control plane and counts, not the
chip). Every datum names the device it ran on (`topology`).

Primary metric: distributed tiled-upscale throughput in tiles/sec/chip
(the BASELINE.md headline: USDU 4K-upscale tiles/sec/chip), measured by
running the USDU compute core over all available chips. Its replacement
by a table of cells driven through the server is ROADMAP Queue 1 item 1.

What the numbers are:
- `vs_baseline` is a *measured* scaling factor (multi-chip/single-chip
  on the same hardware, labeled via `scaling_source`); null on one chip.
- `mfu` is whole-program FLOPs from XLA cost analysis vs the chip's peak
  bf16 FLOPs (peaks in one table keyed by device kind; a TPU that is not
  in it is an error). FLOPs are composed from scan-free per-tile
  components (VAE encode + N model evals + decode) because XLA counts a
  lax.scan body once — costing the whole nested-scan program
  undercounts by ~tiles*steps.

Env knobs: BENCH_TINY=1 (small model/shapes), BENCH_CPU=1 (run on the
CPU on purpose), BENCH_METRIC=usdu|txt2img|video, BENCH_MODEL /
BENCH_SRC / BENCH_TILE / BENCH_STEPS / BENCH_FRAMES (shape overrides),
BENCH_TILE_BATCH (USDU tile grouping; default 1 on CPU, 8 on
accelerators). The weight storage dtype and the persistent compile
cache are the program's own defaults (models/pipeline.py,
workers/startup.configure_compile_cache); bench.py sets neither.
"""

from __future__ import annotations

import json
import os
import sys
import time

# peak dense bf16 FLOPs/s per chip by device_kind substring
_PEAK_FLOPS = [
    ("v6", 918e12),
    ("trillium", 918e12),
    ("v5p", 459e12),
    ("v5 lite", 197e12),
    ("v5e", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
]


def _peak_flops(device) -> float | None:
    """Peak dense bf16 FLOP/s of one chip; None on the CPU (BENCH_CPU=1
    runs report no utilization). A TPU kind missing from the table is an
    error, not a default."""
    if device.platform != "tpu":
        return None
    kind = device.device_kind.lower()
    for sub, peak in _PEAK_FLOPS:
        if sub in kind:
            return peak
    raise RuntimeError(
        f"no peak FLOP/s on record for device kind {device.device_kind!r}; "
        "add it to _PEAK_FLOPS with its source"
    )


def _sync(jax, out) -> None:
    """Execution barrier for timed closures: dispatch is asynchronous,
    so a timing that does not wait measures the enqueue."""
    jax.block_until_ready(out)


def _profiling_stamp() -> dict | None:
    """The process transfer ledger's cumulative totals (None when the
    plane is off or nothing was recorded)."""
    try:
        from comfyui_distributed_tpu.telemetry.profiling import (
            peek_transfer_ledger,
        )

        ledger = peek_transfer_ledger()
        if ledger is None:
            return None
        totals = ledger.totals()
        if not (
            totals.get("device_ns")
            or totals.get("host_total_ns")
            or totals.get("tiles")
        ):
            return None
        return totals
    except Exception:  # noqa: BLE001 - forensics only
        return None


def _phase(name: str) -> None:
    """Phase marker on stderr, so a run killed mid-phase names it."""
    print(f"bench phase: {name}", file=sys.stderr, flush=True)


def _runtime_snapshot() -> dict:
    from comfyui_distributed_tpu.telemetry.runtime import runtime_snapshot

    return runtime_snapshot()


def _topology_stamp() -> dict:
    """The device this datum was measured on — platform, device kind,
    chip counts — and the mesh the worker tier would build from the
    CDT_MESH_* knobs. Datums from different fleet shapes compare on
    `value` (already normalized per chip) + this stamp."""
    from comfyui_distributed_tpu.parallel.mesh import (
        describe_topology,
        serving_mesh_summary,
    )

    topo = describe_topology()
    stamp = {
        k: topo[k]
        for k in (
            "platform",
            "device_kind",
            "device_count",
            "local_device_count",
            "process_count",
            "versions",
        )
    }
    stamp["mesh"] = serving_mesh_summary()
    return stamp


def _init_jax() -> tuple:
    """Returns (jax, environment_tag) with the backend up. The compile
    cache resolves the same way as for the server and chip_smoke.py."""
    import jax

    from comfyui_distributed_tpu.workers.startup import configure_compile_cache

    if os.environ.get("BENCH_CPU") == "1":
        jax.config.update("jax_platforms", "cpu")
    configure_compile_cache()
    platform = jax.devices()[0].platform
    if platform == "tpu":
        return jax, "accelerator"
    if os.environ.get("BENCH_CPU") == "1":
        return jax, "cpu"
    print(
        f"bench.py measures on a TPU and found platform {platform!r}; "
        "set BENCH_CPU=1 to run the CPU smoke on purpose",
        file=sys.stderr, flush=True,
    )
    sys.exit(1)


def _rate(fn, n_items: int, iters: int = 3) -> float:
    """items/sec of fn(seed) after one compile call."""
    _phase("compile")
    fn(0)
    _phase("time")
    t0 = time.perf_counter()
    for i in range(iters):
        fn(i + 1)
    return n_items * iters / (time.perf_counter() - t0)


def bench_usdu(jax, tiny: bool) -> dict:
    import jax.numpy as jnp

    from comfyui_distributed_tpu.models import pipeline as pl
    from comfyui_distributed_tpu.ops import upscale as up
    from comfyui_distributed_tpu.parallel import build_mesh

    n_dev = len(jax.devices())
    # 4K-class output in the real config: 1024 -> 2048 with 512px tiles.
    model = os.environ.get("BENCH_MODEL") or ("tiny-unet" if tiny else "sdxl")
    src = int(os.environ.get("BENCH_SRC") or (64 if tiny else 1024))
    tile = int(os.environ.get("BENCH_TILE") or (64 if tiny else 512))
    padding = 16 if tiny else 32
    steps = int(os.environ.get("BENCH_STEPS") or (2 if tiny else 20))

    _phase(f"load ({model})")
    bundle = pl.load_pipeline(model, seed=0)
    img = jnp.linspace(0, 1, src * src * 3).reshape(1, src, src, 3).astype(jnp.float32)
    pos = pl.encode_text(bundle, ["benchmark"])
    neg = pl.encode_text(bundle, [""])
    _, _, grid = up.plan_grid(src, src, 2.0, tile, padding)
    # batch-K tile grouping: the program's own platform default (K=1
    # on the CPU, golden-exact; K=8 on accelerators — batch-1 convs
    # leave most of the MXU idle) unless BENCH_TILE_BATCH names one
    tile_batch = int(os.environ.get("BENCH_TILE_BATCH") or 0)
    if tile_batch <= 0:
        from comfyui_distributed_tpu.utils.constants import tile_scan_batch

        tile_batch = tile_scan_batch()
    kwargs = dict(
        upscale_by=2.0, tile=tile, padding=padding, steps=steps,
        sampler="euler", scheduler="karras", cfg=7.0, denoise=0.35,
        tile_batch=tile_batch,
    )

    mesh = build_mesh({"data": n_dev}) if n_dev > 1 else None

    def run(seed):
        out = up.run_upscale(bundle, img, pos, neg, mesh=mesh, seed=seed, **kwargs)
        _sync(jax, out)

    rate = _rate(run, grid.num_tiles)
    rate_per_chip = rate / n_dev

    result = {
        "metric": (
            f"USDU tiles/sec/chip ({model}, {src}->{2*src}px, "
            f"{tile}px tiles, {steps} steps, {n_dev} chip(s)"
            + (f", tile_batch={tile_batch}" if tile_batch != 1 else "")
            + ")"
        ),
        "value": round(rate_per_chip, 4),
        "unit": "tiles/sec/chip",
        # the un-normalized aggregate + the divisor, explicit, so rounds
        # from different fleet shapes stay comparable at a glance
        "rate_total": round(rate, 4),
        "chips": n_dev,
        "vs_baseline": None,
        "scaling_source": None,
    }

    if n_dev > 1:
        # real multi-chip scaling vs a single-chip run of the same shape
        def run_single(seed):
            out = up.run_upscale(
                bundle, img, pos, neg, mesh=None, seed=seed, **kwargs
            )
            _sync(jax, out)

        single_rate = _rate(run_single, grid.num_tiles)
        result["vs_baseline"] = round(rate / max(single_rate, 1e-9), 3)
        result["scaling_source"] = f"measured_{n_dev}chip"

    # MFU numerator: analytic FLOPs composed from scan-free per-tile
    # components (XLA cost analysis can't see scan trip counts)
    peak = _peak_flops(jax.devices()[0])
    if peak is not None:
        from comfyui_distributed_tpu.ops.upscale import _jitted_for_flops

        _phase("mfu cost-analysis")
        flops = _jitted_for_flops(bundle, img, pos, neg, mesh, **kwargs)
        if flops:
            result["mfu"] = round(
                (flops * rate / grid.num_tiles) / (n_dev * peak), 4
            )
        else:
            result["mfu"] = None
    else:
        result["mfu"] = None
    return result


def bench_txt2img(jax, tiny: bool) -> dict:
    from comfyui_distributed_tpu.models import pipeline as pl
    from comfyui_distributed_tpu.parallel import build_mesh
    from comfyui_distributed_tpu.parallel.generation import txt2img_parallel

    n_dev = len(jax.devices())
    model = os.environ.get("BENCH_MODEL") or ("tiny-unet" if tiny else "sd15")
    size = int(os.environ.get("BENCH_SRC") or (64 if tiny else 512))
    steps = int(os.environ.get("BENCH_STEPS") or (2 if tiny else 20))
    _phase(f"load ({model})")
    bundle = pl.load_pipeline(model, seed=0)
    mesh = build_mesh({"data": n_dev})

    def run(seed):
        out = txt2img_parallel(
            bundle, mesh, "benchmark prompt", height=size, width=size,
            steps=steps, seed=seed,
        )
        _sync(jax, out)

    rate = _rate(run, n_dev)

    result = {
        "metric": f"txt2img imgs/sec ({model} {size}px {steps} steps, {n_dev} chip(s))",
        "value": round(rate, 4),
        "unit": "imgs/sec",
        "chips": n_dev,
        "vs_baseline": None,
        "scaling_source": None,
        "mfu": None,
    }
    if n_dev > 1:
        def run_single(seed):
            out = pl.txt2img(
                bundle, "benchmark prompt", height=size, width=size,
                steps=steps, seed=seed,
            )
            _sync(jax, out)

        single_rate = _rate(run_single, 1)
        result["vs_baseline"] = round(rate / max(single_rate, 1e-9), 3)
        result["scaling_source"] = f"measured_{n_dev}chip"

    peak = _peak_flops(jax.devices()[0])
    if peak is not None:
        _phase("mfu cost-analysis")
        flops = pl.txt2img_flops(bundle, height=size, width=size, steps=steps)
        if flops:
            # flops = one 1-image program; rate is imgs/sec pod-wide
            result["mfu"] = round((flops * rate) / (n_dev * peak), 4)
    return result


def bench_video(jax, tiny: bool) -> dict:
    """WAN-class t2v throughput in frames/sec/chip — the video rows of
    BASELINE.md's config matrix (8-chip ICI, parallel seeds)."""
    from comfyui_distributed_tpu.models import video_pipeline as vp
    from comfyui_distributed_tpu.parallel import build_mesh

    n_dev = len(jax.devices())
    model = os.environ.get("BENCH_MODEL") or ("tiny-dit" if tiny else "wan-1.3b")
    vae = "tiny-video-vae-3d" if tiny else "wan-vae"
    frames = int(os.environ.get("BENCH_FRAMES") or (5 if tiny else 33))
    size = int(os.environ.get("BENCH_SRC") or (32 if tiny else 256))
    steps = int(os.environ.get("BENCH_STEPS") or (2 if tiny else 20))
    _phase(f"load ({model})")
    bundle = vp.load_video_pipeline(model, vae_name=vae)

    if n_dev > 1:
        mesh = build_mesh({"data": n_dev})

        def run(seed):
            out = vp.t2v_parallel(
                bundle, mesh, "benchmark", frames=frames, height=size,
                width=size, steps=steps, seed=seed,
            )
            _sync(jax, out)

        rate = _rate(run, frames * n_dev)
    else:
        def run(seed):
            out = vp.t2v(
                bundle, "benchmark", frames=frames, height=size,
                width=size, steps=steps, seed=seed,
            )
            _sync(jax, out)

        rate = _rate(run, frames)

    result = {
        "metric": (
            f"WAN t2v frames/sec/chip ({model}, {frames}f {size}px "
            f"{steps} steps, {n_dev} chip(s))"
        ),
        "value": round(rate / n_dev, 4),
        "unit": "frames/sec/chip",
        "rate_total": round(rate, 4),
        "chips": n_dev,
        "vs_baseline": None,
        "scaling_source": None,
        "mfu": None,
    }
    if n_dev > 1:
        def run_single(seed):
            out = vp.t2v(
                bundle, "benchmark", frames=frames, height=size,
                width=size, steps=steps, seed=seed,
            )
            _sync(jax, out)

        single_rate = _rate(run_single, frames)
        result["vs_baseline"] = round(rate / max(single_rate, 1e-9), 3)
        result["scaling_source"] = f"measured_{n_dev}chip"

    peak = _peak_flops(jax.devices()[0])
    if peak is not None:
        _phase("mfu cost-analysis")
        flops = vp.t2v_flops(
            bundle, frames=frames, height=size, width=size, steps=steps
        )
        if flops:
            # per-frame FLOPs x pod-wide frames/sec
            result["mfu"] = round(
                ((flops / frames) * rate) / (n_dev * peak), 4
            )
    return result


def _measure_cancel_latency(jobs: int = 4, tiles: int = 64) -> dict | None:
    """Cancel reclaim speed (lifecycle-armor PR satellite): time from
    the cancel request to every pending + in-flight tile refunded, on
    an in-process JobStore with `tiles`-deep jobs and a few claimed
    grants — the accounting path POST /distributed/cancel/{job_id}
    drives, minus the HTTP envelope. Stamped into the bench datum as
    `lifecycle.cancel_latency_ms` (mean over `jobs` cancels) together
    with the process's shed counters; returns None (never raises) when
    the measurement can't run — losing the stamp must not cost the
    datum."""
    try:
        import asyncio
        import time as time_mod

        from comfyui_distributed_tpu.jobs import JobStore

        async def run_once(store: JobStore, job_id: str) -> float:
            await store.init_tile_job(job_id, list(range(tiles)))
            for wid in ("w1", "w2", "w3"):
                await store.pull_tasks(job_id, wid, timeout=0.01)
            started = time_mod.perf_counter()
            acct = await store.cancel_job(job_id, reason="bench")
            elapsed = (time_mod.perf_counter() - started) * 1000.0
            assert acct is not None
            assert (
                acct["pending_refunded"] + acct["in_flight_refunded"] == tiles
            ), acct
            stats = store.stats_unlocked()
            assert stats["in_flight"] == 0, stats
            return elapsed

        async def run_all() -> list[float]:
            store = JobStore()
            return [
                await run_once(store, f"bench-cancel-{i}") for i in range(jobs)
            ]

        samples = asyncio.run(run_all())
        shed_counts: dict[str, float] = {}
        try:
            from comfyui_distributed_tpu.telemetry.instruments import shed_total

            counter = shed_total()
            with counter._lock:
                items = dict(counter._values)
            for key, value in items.items():
                shed_counts[key[0] if key else ""] = value
        except Exception:
            shed_counts = {}
        return {
            "cancel_latency_ms": round(sum(samples) / len(samples), 3),
            "cancel_latency_ms_max": round(max(samples), 3),
            "cancel_jobs": jobs,
            "cancel_tiles_per_job": tiles,
            "shed_total": shed_counts,
        }
    except Exception as exc:  # noqa: BLE001 - the stamp is optional
        print(f"cancel-latency measurement failed: {exc}", file=sys.stderr)
        return None


def _measure_mixed_small_jobs(
    n_jobs: int = 4, steps: int = 4, k_max: int = 8
) -> dict | None:
    """Cross-job continuous-batching A/B (xjob-tier PR satellite):
    `n_jobs` concurrent small (3-tile) jobs across two tenants drain
    through the CrossJobExecutor twice — cross-job batches vs per-job
    batches — on the in-process chaos harness (real JobStore + real
    preemption coordinator, stub processor). Stamps the measured
    batch-fill ratios (real vs padded device slots per dispatch),
    tiles/sec/chip for each mode, and a bit-identity verdict (first
    job's canvas vs its solo run) into the datum as
    `mixed_small_jobs`, so the cross-job win lands as a measured A/B.
    Returns None (never raises) when the measurement can't run."""
    try:
        import time as time_mod

        from comfyui_distributed_tpu.resilience.chaos import run_chaos_xjob

        jobs = [
            {
                "job_id": f"bench-xjob-{i}",
                "seed": 100 + i,
                "tenant": "tenant-a" if i % 2 == 0 else "tenant-b",
                "lane": "batch",
                "image_hw": (32, 96),  # 3 tiles each: ragged vs buckets
            }
            for i in range(n_jobs)
        ]

        def one_mode(cross_job: bool):
            started = time_mod.perf_counter()
            result = run_chaos_xjob(
                seed=100, jobs=jobs, steps=steps, k_max=k_max,
                cross_job=cross_job,
            )
            elapsed = time_mod.perf_counter() - started
            tiles = result.stats["tiles"]
            rate = round(tiles / elapsed, 3) if elapsed > 0 else None
            # usage block (usage-metering PR satellite): the run-local
            # meter's per-tenant chip-seconds + waste shares, and the
            # fill-adjusted rate — tiles/sec/chip discounted by the
            # attributed share of measured dispatch time, so modes with
            # different padding burn compare on USEFUL chip throughput
            usage_roll = (result.usage or {}).get("rollup", {})
            totals = usage_roll.get("totals", {})
            chip_s = totals.get("chip_s", 0.0)
            waste_s = totals.get("waste_s", {})
            attributed_share = (
                totals.get("attributed_s", 0.0) / chip_s if chip_s else 1.0
            )
            usage_block = {
                "tenants": {
                    tenant: {
                        "chip_s": round(stats["chip_s"], 6),
                        "tiles": stats["tiles"],
                        "chip_share": stats.get("chip_share", 0.0),
                    }
                    for tenant, stats in sorted(
                        usage_roll.get("tenants", {}).items()
                    )
                },
                "chip_s": round(chip_s, 6),
                "waste_shares": {
                    r: round(s / chip_s, 6) if chip_s else 0.0
                    for r, s in sorted(waste_s.items())
                },
                "attributed_share": round(attributed_share, 6),
                "conserved": (result.usage or {})
                .get("totals", {})
                .get("conserved"),
                "tiles_per_sec_chip_effective": (
                    round(rate * attributed_share, 3)
                    if rate is not None
                    else None
                ),
            }
            return result, {
                "fill_ratio": round(result.fill_ratio, 4),
                "padded_slots": result.stats["slots_padded"],
                "real_slots": result.stats["slots_real"],
                "dispatches": result.stats["dispatches"],
                "tiles": tiles,
                "elapsed_s": round(elapsed, 4),
                # ONE host drives the harness executor, so per-chip ==
                # per-run here; real fleets scale by topology.chips
                "tiles_per_sec_chip": rate,
                "usage": usage_block,
            }

        # solo baseline FIRST: it doubles as the jax dispatch warmup so
        # neither timed mode pays first-call tracing overhead
        solo = run_chaos_xjob(seed=100, jobs=[dict(jobs[0])], steps=steps)
        mixed_result, mixed = one_mode(True)
        perjob_result, perjob = one_mode(False)
        import numpy as _np

        jid = jobs[0]["job_id"]
        bit_identical = bool(
            _np.array_equal(solo.canvases[jid], mixed_result.canvases[jid])
            and _np.array_equal(
                solo.canvases[jid], perjob_result.canvases[jid]
            )
        )
        return {
            "jobs": n_jobs,
            "tiles_per_job": 3,
            "tenants": 2,
            "steps": steps,
            "k_max": k_max,
            "cross_job": mixed,
            "per_job": perjob,
            "fill_ratio_gain": round(
                mixed["fill_ratio"] - perjob["fill_ratio"], 4
            ),
            "bit_identical": bit_identical,
        }
    except Exception as exc:  # noqa: BLE001 - the stamp is optional
        print(f"mixed-small-jobs measurement failed: {exc}", file=sys.stderr)
        return None


def _measure_cache_ab(seed: int = 17) -> dict | None:
    """Cold->warm tile-cache A/B (content-addressed-cache PR
    satellite): the same elastic USDU run twice against one run-local
    TileResultCache on the in-process chaos harness (real JobStore,
    stub processor). The cold run populates; the warm run's master
    probes at grant time, settles every tile straight from RAM, and
    dispatches nothing — so the warm wall-clock measures the cached
    serving floor. Stamps both measured rates, the warm probe hit
    rate, cache counters/bytes, an amortized effective rate
    (cold rate / miss share — what a fleet whose probe stream hits at
    this rate pays per tile), and the bit-identity verdict into the
    datum as `cache`. Returns None (never raises) when the measurement
    can't run — losing the stamp must not cost the datum."""
    try:
        import time as time_mod

        import numpy as _np

        from comfyui_distributed_tpu.cache.store import TileResultCache
        from comfyui_distributed_tpu.resilience.chaos import run_chaos_usdu

        cache = TileResultCache(ram_mb=128)

        def one_run():
            started = time_mod.perf_counter()
            result = run_chaos_usdu(seed=seed, cache=cache)
            return result, time_mod.perf_counter() - started

        cold, cold_s = one_run()
        warm, warm_s = one_run()
        tiles = cold.cache["puts"]
        if not tiles or cold_s <= 0 or warm_s <= 0:
            return None
        hits = warm.cache["hits"] - cold.cache["hits"]
        misses = warm.cache["misses"] - cold.cache["misses"]
        lookups = hits + misses
        miss_share = misses / tiles
        cold_rate = tiles / cold_s
        warm_rate = tiles / warm_s
        worker_tiles = sum(
            v for k, v in warm.tiles_by_worker.items() if k != "master"
        )
        return {
            "tiles": tiles,
            "bit_identical": bool(_np.array_equal(cold.output, warm.output)),
            "cold": {
                "elapsed_s": round(cold_s, 4),
                "tiles_per_sec_chip": round(cold_rate, 3),
            },
            "warm": {
                "elapsed_s": round(warm_s, 4),
                "tiles_per_sec_chip": round(warm_rate, 3),
                "hit_rate": round(hits / lookups, 4) if lookups else 0.0,
                "settled": warm.cache["settled"] - cold.cache["settled"],
                # dispatch-free proof: tiles any worker computed warm
                "worker_tiles": worker_tiles,
            },
            "speedup": round(warm_rate / cold_rate, 3),
            # amortized view: unbounded at miss share 0 (every tile
            # cached), so null there — the measured warm rate above is
            # the honest serving floor
            "tiles_per_sec_chip_effective": (
                round(cold_rate / miss_share, 3) if miss_share > 0 else None
            ),
            "hits": warm.cache["hits"],
            "misses": warm.cache["misses"],
            "puts": warm.cache["puts"],
            "evictions": warm.cache["evictions"],
            "ram_bytes": warm.cache["ram_bytes"],
        }
    except Exception as exc:  # noqa: BLE001 - the stamp is optional
        print(f"cache A/B measurement failed: {exc}", file=sys.stderr)
        return None


def _measure_canvas_ab(seed: int = 19) -> dict | None:
    """Host-canvas vs device-canvas A/B (device-resident hot path):
    the same elastic USDU run on the in-process chaos harness, once
    through the deterministic host canvas and once with
    CDT_DEVICE_CANVAS routing master-local tiles through the on-device
    DeviceCanvas (one composited d2h per flush instead of one readback
    per tile). Each run gets a fresh TransferLedger so the stamp
    carries measured d2h bytes/tile for both sides, the rate, the
    reduction ratio, and the bit-identity verdict (hard gate: the
    device canvas must not change the image). Returns None (never
    raises) when the measurement can't run."""
    try:
        import time as time_mod

        import numpy as _np

        from comfyui_distributed_tpu.resilience.chaos import run_chaos_usdu
        from comfyui_distributed_tpu.telemetry.profiling import (
            D2H,
            TransferLedger,
            set_transfer_ledger,
        )

        def one_run(device: bool):
            ledger = TransferLedger()
            prev = set_transfer_ledger(ledger)
            try:
                started = time_mod.perf_counter()
                # no remote workers: the device canvas targets the
                # MASTER-LOCAL readback seam (remote tiles keep the
                # PNG path by design), so the A/B isolates it
                result = run_chaos_usdu(
                    seed=seed, workers=(), device_canvas=device
                )
                elapsed = time_mod.perf_counter() - started
            finally:
                set_transfer_ledger(prev)
            snap = ledger.totals()
            tiles = sum(result.tiles_by_worker.values()) or 1
            d2h = snap["transfer"].get(D2H, {})
            return {
                "result": result,
                "elapsed_s": elapsed,
                "tiles": tiles,
                "d2h_bytes": int(d2h.get("bytes", 0)),
                "d2h_transfers": int(d2h.get("count", 0)),
            }

        # one untimed warmup so one-time costs (native blend kernel
        # compile, jit warming) don't bias whichever side runs first
        run_chaos_usdu(seed=seed, workers=())
        host = one_run(False)
        device = one_run(True)
        if host["elapsed_s"] <= 0 or device["elapsed_s"] <= 0:
            return None

        def side(run):
            return {
                "elapsed_s": round(run["elapsed_s"], 4),
                "tiles_per_sec": round(run["tiles"] / run["elapsed_s"], 3),
                "d2h_bytes_per_tile": round(run["d2h_bytes"] / run["tiles"]),
                "d2h_transfers": run["d2h_transfers"],
            }

        host_bpt = host["d2h_bytes"] / host["tiles"]
        device_bpt = device["d2h_bytes"] / device["tiles"]
        return {
            "tiles": host["tiles"],
            "bit_identical": bool(
                _np.array_equal(host["result"].output, device["result"].output)
            ),
            "host": side(host),
            "device": side(device),
            # the win condition: strictly fewer d2h bytes per tile
            "d2h_bytes_per_tile_ratio": (
                round(device_bpt / host_bpt, 4) if host_bpt > 0 else None
            ),
        }
    except Exception as exc:  # noqa: BLE001 - the stamp is optional
        print(f"canvas A/B measurement failed: {exc}", file=sys.stderr)
        return None


def _measure_precision_ab(
    steps: int = 16, shape: tuple = (4, 32, 32, 4)
) -> dict | None:
    """bf16-lane vs f32 A/B (device-resident hot path's budget tier):
    the production lane semantics exactly — step math upcast to f32,
    the latent CARRY quantized to bf16 between steps — on a jitted
    donated euler step over a toy score model. Stamps steps/sec for
    both lanes, the speedup, and PSNR of the bf16 trajectory vs the
    f32 reference (the quality cost a budget tenant buys into).
    Returns None (never raises) when the measurement can't run."""
    try:
        import time as time_mod

        import jax
        import jax.numpy as jnp
        import numpy as _np

        from comfyui_distributed_tpu.ops import samplers as smp
        from comfyui_distributed_tpu.ops.stepwise import euler_step

        sigmas = jnp.asarray(smp.get_sigmas("karras", steps))
        n = int(sigmas.shape[0]) - 1

        def model_fn(x, sigma, cond):
            # cheap non-linear surrogate so quantization error actually
            # propagates through the trajectory
            return 0.3 * x + 0.01 * jnp.tanh(x)

        def make_step(bf16: bool):
            def _step(x, i):
                if bf16:
                    x = x.astype(jnp.float32)
                out = euler_step(
                    model_fn, x, jnp.take(sigmas, i),
                    jnp.take(sigmas, i + 1), None,
                )
                return out.astype(jnp.bfloat16) if bf16 else out

            return jax.jit(_step, donate_argnums=(0,))

        x0 = jax.random.normal(jax.random.key(3), shape) * sigmas[0]

        def run(bf16: bool):
            step = make_step(bf16)

            def fresh():
                x = x0 + 0.0  # a copy: the step donates its operand
                return x.astype(jnp.bfloat16) if bf16 else x

            # warm the (single, step-index-traced) compile
            jax.block_until_ready(step(fresh(), jnp.int32(0)))
            x = fresh()
            started = time_mod.perf_counter()
            for i in range(n):
                x = step(x, jnp.int32(i))
            x = jax.block_until_ready(x)
            elapsed = time_mod.perf_counter() - started
            return _np.asarray(x.astype(jnp.float32)), elapsed

        ref, f32_s = run(False)
        quant, bf16_s = run(True)
        if f32_s <= 0 or bf16_s <= 0:
            return None
        mse = float(_np.mean((ref - quant) ** 2))
        peak = float(_np.max(_np.abs(ref))) or 1.0
        psnr = (
            round(10.0 * _np.log10(peak * peak / mse), 2)
            if mse > 0
            else None  # bit-identical: infinite PSNR
        )
        return {
            "steps": n,
            "shape": list(shape),
            "f32": {
                "elapsed_s": round(f32_s, 4),
                "steps_per_sec": round(n / f32_s, 3),
            },
            "bf16": {
                "elapsed_s": round(bf16_s, 4),
                "steps_per_sec": round(n / bf16_s, 3),
            },
            "speedup": round(f32_s / bf16_s, 3),
            "psnr_db_vs_f32": psnr,
        }
    except Exception as exc:  # noqa: BLE001 - the stamp is optional
        print(f"precision A/B measurement failed: {exc}", file=sys.stderr)
        return None


def _measure_adapter_churn(
    n_jobs: int = 6, steps: int = 4, k_max: int = 8
) -> dict | None:
    """Adapter-churn mixed-tenant scenario (adapter-plane PR
    satellite): `n_jobs` concurrent jobs across two tenants, each
    wearing a DIFFERENT LoRA adapter, plus one adapter-less job, drain
    through one CrossJobExecutor in two waves. The cold wave pays
    operand decode + the (single) extended-signature compile; the warm
    wave re-requests every adapter at a different strength and must
    serve all operands from the run-local LRU (strength is a traced
    scalar, not a cache key). Stamps per-wave fill/throughput, the
    compiled-program count (one adapter program serves all N distinct
    adapters + one base program — the plane's compile contract), the
    operand-cache hit/miss ledger, and two bit-identity verdicts (worn
    job and adapter-less job, wave vs solo) into the datum as
    `adapter_churn`. Returns None (never raises) when the measurement
    can't run — losing the stamp must not cost the datum."""
    try:
        import time as time_mod
        import types as types_mod

        import numpy as _np

        import jax
        import jax.numpy as jnp

        from comfyui_distributed_tpu.adapters import AdapterSpec
        from comfyui_distributed_tpu.adapters.cache import (
            AdapterOperandCache,
            operands_for_plan,
        )
        from comfyui_distributed_tpu.adapters.registry import AdapterCatalog
        from comfyui_distributed_tpu.graph.batch_executor import (
            CrossJobExecutor,
            XJobHandle,
        )
        from comfyui_distributed_tpu.parallel.seeds import fold_job_key

        dim = 3
        rank = 2
        target_map = {"lora_unet_dense": ("unet/dense/kernel", (dim, dim))}
        params = {
            "unet": {
                "dense": {"kernel": jnp.eye(dim, dtype=jnp.float32) * 0.9}
            }
        }

        # run-local catalog + operand cache: one distinct tiny kohya
        # adapter per job (distinct bytes → distinct content hashes)
        catalog = AdapterCatalog()
        for i in range(n_jobs):
            rng = _np.random.default_rng(1000 + i)
            catalog.register_memory(
                f"bench-style-{i}",
                {
                    "lora_unet_dense.lora_down.weight": (
                        0.1 * rng.normal(size=(rank, dim))
                    ).astype(_np.float32),
                    "lora_unet_dense.lora_up.weight": (
                        0.1 * rng.normal(size=(dim, rank))
                    ).astype(_np.float32),
                    "lora_unet_dense.alpha": _np.float32(rank),
                },
            )
        op_cache = AdapterOperandCache()

        trace_log: list[int] = []

        def step(p, x, key, pos, neg, yx, i):
            trace_log.append(1)
            w = p["unet"]["dense"]["kernel"]
            ki = jax.random.fold_in(key, i)
            return (
                jnp.einsum("hwc,cd->hwd", x, w)
                + 0.01 * jax.random.normal(ki, x.shape)
                + 0.001 * pos
            )

        proc = types_mod.SimpleNamespace(
            init=lambda p, tile, key: tile + 0.0,
            step=jax.jit(step),
            finish=lambda p, x: jnp.clip(x, -10.0, 10.0),
            n_steps=steps,
            signature=("bench-adapter-stub",),
        )

        class _Master:
            def __init__(self, n_tiles):
                self.pending = list(range(n_tiles))

            def pull(self):
                if not self.pending:
                    return None
                grant, self.pending = self.pending, []
                return {"tile_idxs": grant, "checkpoints": {}}

            def release(self, idxs, cks):
                self.pending = sorted(set(self.pending) | set(idxs))

        def make_job(job_id, n_tiles, seed, tenant, adapter):
            master = _Master(n_tiles)
            rng = _np.random.default_rng(seed)
            outs: dict[int, _np.ndarray] = {}
            handle = XJobHandle(
                job_id=job_id,
                proc=proc,
                params=params,
                extracted=jnp.asarray(
                    rng.random((n_tiles, 4, 4, dim)), jnp.float32
                ),
                positions=jnp.zeros((n_tiles, 2), jnp.int32),
                pos=jnp.float32(seed),
                neg=jnp.float32(0),
                base_key=fold_job_key(jax.random.key(seed), job_id),
                pull=master.pull,
                emit=lambda idx, arr, outs=outs: outs.__setitem__(
                    int(idx), _np.asarray(arr)
                ),
                flush=lambda final: None,
                release=master.release,
                tenant=tenant,
                adapter=adapter,
            )
            return handle, outs

        def ops_for(i, strength):
            (resolved,) = catalog.resolve(
                [AdapterSpec(f"bench-style-{i}", strength)]
            )
            return operands_for_plan(
                [resolved], target_map, catalog=catalog, cache=op_cache
            )

        def one_wave(strength):
            ex = CrossJobExecutor(k_max=k_max)
            canvases = {}
            sigs = set()
            traces_before = len(trace_log)
            started = time_mod.perf_counter()
            for i in range(n_jobs):
                handle, outs = make_job(
                    f"bench-adapter-{i}",
                    2,
                    100 + i,
                    "tenant-a" if i % 2 == 0 else "tenant-b",
                    ops_for(i, strength),
                )
                ex.register(handle)
                canvases[handle.job_id] = outs
                sigs.add(handle.sig)
            base_handle, base_outs = make_job(
                "bench-adapter-base", 2, 900, "tenant-a", None
            )
            ex.register(base_handle)
            canvases[base_handle.job_id] = base_outs
            sigs.add(base_handle.sig)
            stats = ex.run()
            elapsed = time_mod.perf_counter() - started
            tiles = stats["tiles"]
            return canvases, {
                "fill_ratio": round(stats["fill_ratio"], 4),
                "dispatches": stats["dispatches"],
                "tiles": tiles,
                "elapsed_s": round(elapsed, 4),
                # ONE host drives the harness executor, so per-chip ==
                # per-run here; real fleets scale by topology.chips
                "tiles_per_sec_chip": (
                    round(tiles / elapsed, 3) if elapsed > 0 else None
                ),
                # one device program per distinct signature; the
                # contract is 2 (one extended-sig program shared by
                # all N distinct adapters + one untouched base
                # program), never a function of n_jobs
                "device_programs": len(sigs),
                # step-BODY traces this wave (0 = everything served
                # from jit caches, e.g. the warm wave)
                "step_traces": len(trace_log) - traces_before,
            }

        cold_canvases, cold = one_wave(strength=1.0)
        # warm wave sweeps strength: operands must still all hit
        warm_canvases, warm = one_wave(strength=0.5)
        del warm_canvases

        # bit-identity: wave output == solo output, worn AND base
        def solo(job_id, n_tiles, seed, adapter):
            ex = CrossJobExecutor(k_max=k_max)
            handle, outs = make_job(job_id, n_tiles, seed, "tenant-a", adapter)
            ex.register(handle)
            ex.run()
            return outs

        worn_solo = solo("bench-adapter-0", 2, 100, ops_for(0, 1.0))
        base_solo = solo("bench-adapter-base", 2, 900, None)
        bit_identical = bool(
            all(
                _np.array_equal(worn_solo[i], cold_canvases["bench-adapter-0"][i])
                for i in range(2)
            )
        )
        base_bit_identical = bool(
            all(
                _np.array_equal(
                    base_solo[i], cold_canvases["bench-adapter-base"][i]
                )
                for i in range(2)
            )
        )
        return {
            "jobs": n_jobs + 1,
            "adapters": n_jobs,
            "tenants": 2,
            "steps": steps,
            "k_max": k_max,
            "cold": cold,
            "warm": warm,
            "operand_cache": op_cache.stats(),
            "bit_identical": bit_identical,
            "base_bit_identical": base_bit_identical,
        }
    except Exception as exc:  # noqa: BLE001 - the stamp is optional
        print(f"adapter-churn measurement failed: {exc}", file=sys.stderr)
        return None


def _measure_grant_ab(
    waves: int = 6,
    wave_tiles: int = 2,
    gap_s: float = 0.6,
    poll_s: float = 0.1,
) -> dict | None:
    """Push-vs-poll grant dispatch A/B over the REAL HTTP surface
    (CPU-OK; failover-PR satellite). One mode = one DistributedServer
    on a loopback port with a tile job whose grants are released in
    timed waves (the requeue/speculation shape that refills a pending
    queue mid-job):

    - **pull** — the classic protocol: the client re-polls
      request_image, each empty answer held QUEUE_POLL_INTERVAL
      server-side then paced poll_s client-side, so a wave landing
      between polls waits out the quantization;
    - **push** — the client parks on the /distributed/events WebSocket
      and pulls the instant a grant_available frame lands (push carries
      availability, never assignment — the pull RPC still transfers the
      grant, so placement sizing and fencing are identical).

    Grant RTT = release instant → client holds the tile. Idle polls =
    request_image answers that carried no work. Stamped into the bench
    datum as `grant_ab`; returns None (never raises) when the A/B
    can't run — losing the stamp must not cost the datum."""
    try:
        import asyncio
        import math
        import socket
        import statistics

        import aiohttp

        from comfyui_distributed_tpu.api.server import DistributedServer
    except Exception as exc:  # noqa: BLE001 - stamp is optional
        print(f"grant A/B unavailable: {exc}", file=sys.stderr)
        return None

    total = waves * wave_tiles
    job_id = "grant-ab"

    async def run_mode(push: bool) -> dict:
        with socket.socket() as sock:
            sock.bind(("127.0.0.1", 0))
            port = sock.getsockname()[1]
        server = DistributedServer(port=port, is_worker=False)
        await server.start()
        stats = {"rtts": [], "idle_polls": 0, "requests": 0}
        try:
            store = server.job_store
            # the A/B flips the push publisher directly (start() wires
            # it from CDT_PUSH_GRANTS; both arms must run in-process)
            store.grant_notifier = (
                server.scheduler.placement.notify_grants if push else None
            )
            await store.init_tile_job(job_id, list(range(total)))
            claimed = []
            for _ in range(total):
                tid = await store.pull_task(job_id, "holder", timeout=0.05)
                if tid is not None:
                    claimed.append(tid)
            release_at: dict[int, float] = {}

            async def producer():
                for wave in range(waves):
                    await asyncio.sleep(gap_s)
                    batch = claimed[wave * wave_tiles : (wave + 1) * wave_tiles]
                    now = time.perf_counter()
                    for tid in batch:
                        release_at[tid] = now
                    await store.release_tasks(job_id, "holder", batch)

            url = f"http://127.0.0.1:{port}/distributed/request_image"

            async def pull_once(session) -> int | None:
                async with session.post(
                    url, json={"job_id": job_id, "worker_id": "ab-worker"}
                ) as resp:
                    out = await resp.json()
                stats["requests"] += 1
                tid = out.get("tile_idx")
                if tid is None:
                    stats["idle_polls"] += 1
                    return None
                stats["rtts"].append(time.perf_counter() - release_at[int(tid)])
                return int(tid)

            async def pull_client(session):
                got = 0
                while got < total:
                    tid = await pull_once(session)
                    if tid is None:
                        await asyncio.sleep(poll_s)
                    else:
                        got += 1

            async def push_client(session):
                got = 0
                ws_url = (
                    f"http://127.0.0.1:{port}/distributed/events"
                    "?types=grant_available"
                )
                async with session.ws_connect(ws_url) as ws:
                    while got < total:
                        msg = await asyncio.wait_for(ws.receive(), timeout=15)
                        if msg.type != aiohttp.WSMsgType.TEXT:
                            break
                        if json.loads(msg.data).get("type") != "grant_available":
                            continue  # hello frame
                        # drain everything the push announced, then
                        # park on the socket again (ONE empty pull ends
                        # the drain — that is push mode's whole idle
                        # request budget)
                        while got < total:
                            tid = await pull_once(session)
                            if tid is None:
                                break
                            got += 1

            producer_task = asyncio.create_task(producer())
            async with aiohttp.ClientSession() as session:
                await asyncio.wait_for(
                    (push_client if push else pull_client)(session),
                    timeout=waves * gap_s + 30,
                )
            await producer_task
            await store.cleanup_tile_job(job_id)
        finally:
            await server.stop()
        rtts = stats["rtts"]
        return {
            "grant_rtt_ms_mean": round(1e3 * statistics.fmean(rtts), 2),
            "grant_rtt_ms_p95": round(
                1e3 * sorted(rtts)[max(0, math.ceil(len(rtts) * 0.95) - 1)], 2
            ),
            "grants": len(rtts),
            "idle_polls": stats["idle_polls"],
            "requests": stats["requests"],
        }

    async def run_both() -> dict:
        pull = await run_mode(push=False)
        push = await run_mode(push=True)
        return {
            "pull": pull,
            "push": push,
            "rtt_speedup": round(
                pull["grant_rtt_ms_mean"] / max(push["grant_rtt_ms_mean"], 1e-6),
                2,
            ),
            "idle_poll_ratio": round(
                pull["idle_polls"] / max(push["idle_polls"], 1), 2
            ),
            "waves": waves,
            "wave_tiles": wave_tiles,
            "gap_s": gap_s,
            "poll_s": poll_s,
        }

    previous_watchdog = os.environ.get("CDT_WATCHDOG")
    os.environ["CDT_WATCHDOG"] = "0"  # no speculation over the held grants
    try:
        return asyncio.run(run_both())
    except Exception as exc:  # noqa: BLE001 - stamp is optional
        print(f"grant A/B failed: {exc}", file=sys.stderr)
        return None
    finally:
        if previous_watchdog is None:
            os.environ.pop("CDT_WATCHDOG", None)
        else:
            os.environ["CDT_WATCHDOG"] = previous_watchdog


def _flash_compile_check(jax) -> dict | None:
    """Lower + compile the Pallas flash kernel for the TPU (None on the
    CPU, where nothing compiles it). A kernel that does not compile
    raises. Head dim 128: the serving dispatcher pads head dims to a
    multiple of 128 before calling flash_attention; chip_smoke.py
    covers the served shapes one by one."""
    if jax.devices()[0].platform != "tpu":
        return None
    import jax.numpy as jnp

    from comfyui_distributed_tpu.ops.attention import flash_attention

    q = jnp.zeros((1, 256, 4, 128), jnp.bfloat16)
    flash_attention.lower(q, q, q).compile()
    return {"flash_compiled": True}


def _usage_stamp() -> dict | None:
    """Chip-time attribution stamp for every datum (usage-metering PR
    satellite): this process's cumulative per-tenant chip-seconds,
    the waste breakdown with each bucket's share of measured dispatch
    time, and the conservation verdict — so BENCH_* rounds are
    cost-comparable across fleet shapes (a 4-chip round that burned
    30% padding is NOT cheaper than a 1-chip round at 2%). Zeroes on
    paths that bypass the metered samplers; never raises."""
    try:
        from comfyui_distributed_tpu.telemetry.usage import get_usage_meter

        rollup = get_usage_meter().rollup()
        totals = rollup["totals"]
        chip_s = totals["chip_s"]
        waste = totals["waste_s"]
        return {
            "tenants": {
                tenant: {
                    "chip_s": round(stats["chip_s"], 6),
                    "tiles": stats["tiles"],
                    "chip_share": stats.get("chip_share", 0.0),
                }
                for tenant, stats in sorted(rollup["tenants"].items())
            },
            "chip_s": round(chip_s, 6),
            "attributed_s": round(totals["attributed_s"], 6),
            "waste_s": {r: round(s, 6) for r, s in sorted(waste.items())},
            "waste_shares": {
                r: round(s / chip_s, 6) if chip_s else 0.0
                for r, s in sorted(waste.items())
            },
            "dispatches": totals["dispatches"],
            "conserved": totals["conserved"],
        }
    except Exception as exc:  # noqa: BLE001 - the stamp is optional
        print(f"usage stamp failed: {exc}", file=sys.stderr)
        return None


def _incident_stamp() -> dict | None:
    """Bundle-trail stamp: how many incident bundles CDT_INCIDENT_DIR
    holds and which triggers produced them (None when the incident
    plane is off)."""
    incident_dir = os.environ.get("CDT_INCIDENT_DIR", "").strip()
    if not incident_dir:
        return None
    from comfyui_distributed_tpu.telemetry.incidents import IncidentManager

    listing = IncidentManager(incident_dir).list_bundles()
    triggers: dict[str, int] = {}
    for entry in listing:
        triggers[entry["trigger"]] = triggers.get(entry["trigger"], 0) + 1
    return {"dir": incident_dir, "count": len(listing), "triggers": triggers}


def main() -> None:
    # Incident bundle trail (docs/observability.md §Incidents): the
    # bundle count/triggers ride every datum. Opt out by exporting
    # CDT_INCIDENT_DIR= (empty).
    os.environ.setdefault(
        "CDT_INCIDENT_DIR", os.path.join(".", ".cdt", "incidents")
    )
    jax, environment = _init_jax()
    tiny = os.environ.get("BENCH_TINY") == "1"
    which = os.environ.get("BENCH_METRIC", "usdu")
    bench = {
        "usdu": bench_usdu,
        "txt2img": bench_txt2img,
        "video": bench_video,
    }.get(which, bench_usdu)
    flash_info = _flash_compile_check(jax)
    result = bench(jax, tiny)

    result["environment"] = environment
    # JAX runtime profiling context (compiles, cache hits, HBM, RSS):
    # a throughput datum without it can't distinguish "slow kernel"
    # from "recompiled every iteration" after the fact.
    result["runtime"] = _runtime_snapshot()
    # which device and fleet shape produced this number
    result["topology"] = _topology_stamp()
    # push-vs-poll grant dispatch A/B (tiny/CPU child only: it measures
    # the CONTROL plane — wave-released grants over the real HTTP
    # surface — so accelerator time is never spent on it)
    if tiny and os.environ.get("BENCH_GRANT_AB", "1") != "0":
        grant_ab = _measure_grant_ab()
        if grant_ab is not None:
            result["grant_ab"] = grant_ab
    # lifecycle reclaim speed (cancel-request -> all tiles refunded) +
    # shed counters, so future rounds track the armor's overheads
    if tiny and os.environ.get("BENCH_LIFECYCLE", "1") != "0":
        lifecycle = _measure_cancel_latency()
        if lifecycle is not None:
            result["lifecycle"] = lifecycle
    # cross-job continuous-batching A/B: batch-fill ratio + tiles/sec/
    # chip for mixed small concurrent jobs vs per-job batching (the
    # xjob tier's utilization win as a measured datum)
    if tiny and os.environ.get("BENCH_MIXED_JOBS", "1") != "0":
        mixed_jobs = _measure_mixed_small_jobs()
        if mixed_jobs is not None:
            result["mixed_small_jobs"] = mixed_jobs
    # cold->warm tile-cache A/B: cached serving floor vs recompute +
    # bit-identity verdict (the content-addressed cache's win as a
    # measured datum)
    if tiny and os.environ.get("BENCH_CACHE", "1") != "0":
        cache_ab = _measure_cache_ab()
        if cache_ab is not None:
            result["cache"] = cache_ab
    # adapter-churn mixed-tenant scenario: N distinct same-rank LoRAs
    # + one base job sharing 2 compiled programs, cold->warm operand
    # cache, strength sweep, bit-identity (the adapter plane's
    # batching win as a measured datum)
    if tiny and os.environ.get("BENCH_ADAPTER", "1") != "0":
        adapter_churn = _measure_adapter_churn()
        if adapter_churn is not None:
            result["adapter_churn"] = adapter_churn
    # host-vs-device canvas A/B: tiles/sec + measured d2h bytes/tile
    # both ways + bit-identity (the device-resident hot path's canvas
    # win as a measured datum)
    if tiny and os.environ.get("BENCH_CANVAS_AB", "1") != "0":
        canvas_ab = _measure_canvas_ab()
        if canvas_ab is not None:
            result["canvas_ab"] = canvas_ab
    # bf16-vs-f32 lane A/B: steps/sec both lanes + PSNR of the bf16
    # trajectory against the f32 reference (the budget tier's
    # speed/quality trade as a measured datum)
    if tiny and os.environ.get("BENCH_PRECISION_AB", "1") != "0":
        precision_ab = _measure_precision_ab()
        if precision_ab is not None:
            result["precision_ab"] = precision_ab
    if flash_info:
        result.update(flash_info)
    incidents = _incident_stamp()
    if incidents is not None:
        result["incidents"] = incidents
    usage = _usage_stamp()
    if usage is not None:
        result["usage"] = usage
    # transfer-ledger stamp (telemetry/profiling.py): device/host ns
    # split + bytes moved + host-tax ratio
    profiling = _profiling_stamp()
    if profiling is not None:
        result["profiling"] = profiling
    print(json.dumps(result))


if __name__ == "__main__":
    main()
