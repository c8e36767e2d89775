"""CLI entry point: run a master or worker server.

    python -m comfyui_distributed_tpu --port 8188            # master
    python -m comfyui_distributed_tpu --port 8189 --worker   # worker

The same process serves both roles (role decided per-prompt by hidden
inputs, reference distributed.py pattern); --worker only suppresses
master-side startup behavior (auto-launch, signal-driven worker
cleanup) and enables the master-pid watchdog.

The start itself is the trace `startup` (`/distributed/trace/startup`):
`process.start`, from the operating system's creation of the process
to the bound socket, with a child for each step below.
"""

from __future__ import annotations

import argparse
import asyncio
import os
import sys
import time

from .telemetry.tracing import STARTUP_TRACE, get_tracer


def _seconds_since_creation() -> float:
    """How long ago the operating system created this process: its
    start time in /proc, in ticks since boot, against the boot clock.
    0.0 where that cannot be had."""
    try:
        with open("/proc/self/stat", encoding="ascii") as fh:
            # the 22nd field, counted after the parenthesised command
            ticks = int(fh.read().rsplit(")", 1)[1].split()[19])
        created = ticks / os.sysconf("SC_CLK_TCK")
        return max(0.0, time.clock_gettime(time.CLOCK_BOOTTIME) - created)
    except (OSError, ValueError, IndexError, AttributeError):
        return 0.0


def main(argv: list[str] | None = None) -> int:
    tracer = get_tracer()
    entered, python_s = tracer.now(), _seconds_since_creation()
    parser = argparse.ArgumentParser(prog="comfyui_distributed_tpu")
    parser.add_argument("--port", type=int, default=8188)
    parser.add_argument(
        "--host", type=str, default=None,
        help="bind address (default 127.0.0.1, or CDT_HOST; pass "
             "0.0.0.0 to accept LAN/remote masters and workers — the "
             "/distributed/* surface has no auth, so binding wide is "
             "an explicit opt-in)",
    )
    parser.add_argument("--worker", action="store_true")
    parser.add_argument(
        "--standby", type=str, default=None, metavar="URLS",
        help="run as a warm-standby master tailing the given active "
             "master URL(s) (comma-separated; or CDT_STANDBY_OF). "
             "Requires CDT_JOURNAL_DIR — the lease file there is the "
             "takeover arbitration medium. The standby serves 503 on "
             "work RPCs until the active's lease expires, then "
             "promotes itself in place (docs/durability.md §failover)",
    )
    parser.add_argument("--config", type=str, default=None)
    parser.add_argument(
        "--platform", type=str, default=None,
        help="force a jax platform. Without it the server refuses to "
             "serve on the CPU (pass cpu to do that on purpose)",
    )
    args = parser.parse_args(argv)

    if args.worker:
        os.environ.setdefault("CDT_IS_WORKER", "1")

    # the process's own start as one trace, kept for its life: the root
    # ends when the socket is bound, each child where its work happens
    root = tracer.start_span(
        "process.start", trace_id=STARTUP_TRACE, start=entered - python_s,
        attrs={"pid": os.getpid(), "role": "worker" if args.worker else "master",
               "python_s": python_s},
    )
    joined = tracer.activate(STARTUP_TRACE, root.span_id)

    from .utils.logging import log
    from .workers.startup import (
        apply_master_chips,
        configure_compile_cache,
        init_backend,
    )

    # Order matters: the chip set before anything can initialise a
    # backend (libtpu takes every chip it can see), the cache before
    # the first compile, the backend before the listener so no request
    # handler is ever the first to touch it.
    with tracer.span("startup.chips"):
        apply_master_chips(args.config)
    try:
        with tracer.span("startup.compile_cache") as span:
            span.attrs["dir"] = configure_compile_cache()
        with tracer.span("startup.backend") as span:
            devices = init_backend(args.platform)
            span.attrs.update(
                platform=devices[0].platform,
                device_kind=str(devices[0].device_kind), devices=len(devices),
            )
    except RuntimeError as exc:
        log(f"backend start-up failed: {exc}")
        return 1

    with tracer.span("startup.imports"):
        from . import native
        from .api.server import DistributedServer
        from .parallel.mesh import mesh_summary, note_serving_mesh, worker_mesh
        from .workers.monitor import start_master_watchdog
        from .workers.startup import (
            auto_populate_workers,
            delayed_auto_launch,
            register_signals,
            register_worker_drain,
        )

    # every local chip serves: the same mesh rule the elastic tile tier
    # uses (None on one chip, and on the CPU unless CDT_MESH_SHAPE opts
    # in), handed to every node through the execution context
    with tracer.span("startup.mesh") as span:
        mesh = worker_mesh()
        note_serving_mesh(mesh)
        # the first question to the data plane builds or loads it
        span.attrs["data_plane"] = native.backend()
    log(f"serving mesh {mesh_summary(mesh)}; data plane: {span.attrs['data_plane']}")

    # a manual pair: the constructor here, the listener on the loop
    serving = tracer.start_span("startup.server", attrs={"port": args.port})
    server = DistributedServer(
        port=args.port, is_worker=args.worker, mesh=mesh,
        config_path=args.config, host=args.host, standby_of=args.standby,
    )
    # before the loop copies this context: a request's handler joins
    # its own trace, never this one
    tracer.deactivate(joined)

    async def start():
        await server.start()
        tracer.end_span(serving)
        tracer.end_span(root)
        register_signals(asyncio.get_running_loop(), args.config)
        if not server.is_worker:
            auto_populate_workers(args.config)
            delayed_auto_launch(args.config)
        else:
            start_master_watchdog()
            # SIGTERM/SIGINT on a worker drains gracefully: finish the
            # in-flight batch, flush encoded tiles, hand the remainder
            # back via return_tiles, then deregister and stop
            register_worker_drain(asyncio.get_running_loop(), server)

    # The signal handlers end the process with loop.stop(), which
    # run_forever() returns from cleanly — so any exception that does
    # get out of here is a failure and exits non-zero as one.
    with asyncio.Runner() as runner:
        runner.run(start())
        try:
            runner.get_loop().run_forever()
        except KeyboardInterrupt:
            pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
