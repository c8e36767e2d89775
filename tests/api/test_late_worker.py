"""A worker that arrives after its job is over leaves at once.

On a chip a warm master finishes a 16-tile job in seconds while a
freshly launched worker is still loading its model; the worker's first
pull then meets "no such job". That 404 is the master's verdict (it is
given only after the master's own init grace), not an outage to sit
out: ten backed-off retries held the worker's prompt queue for minutes
while the next job went by without it."""

import asyncio
import socket
import time

import pytest

from comfyui_distributed_tpu.api import usdu_routes
from comfyui_distributed_tpu.api.server import DistributedServer
from comfyui_distributed_tpu.graph.usdu_elastic import HTTPWorkClient
from comfyui_distributed_tpu.utils.async_helpers import ServerLoopThread


@pytest.fixture()
def master(tmp_config_path, monkeypatch):
    monkeypatch.setattr(usdu_routes, "JOB_INIT_GRACE_SECONDS", 0.2)
    loop_thread = ServerLoopThread()
    loop_thread.start()
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]
    server = DistributedServer(port=port, is_worker=False)
    asyncio.run_coroutine_threadsafe(server.start(), loop_thread.loop).result(30)
    yield server, port, loop_thread
    asyncio.run_coroutine_threadsafe(server.stop(), loop_thread.loop).result(30)
    loop_thread.stop()


def test_pull_for_a_finished_job_is_not_retried(master):
    _server, port, _loop = master
    client = HTTPWorkClient(f"http://127.0.0.1:{port}", "job-long-gone", "w1")
    started = time.monotonic()
    assert client.request_tile(batch_max=8) is None
    # one answer (after the master's grace), not the pull policy's ten
    # attempts with backoff up to 30 s each
    assert time.monotonic() - started < 5.0


def test_pull_still_serves_a_live_job(master):
    server, port, loop_thread = master
    asyncio.run_coroutine_threadsafe(
        server.job_store.init_tile_job("live", [0, 1, 2]), loop_thread.loop
    ).result(10)
    client = HTTPWorkClient(f"http://127.0.0.1:{port}", "live", "w1")
    work = client.request_tile()
    assert work is not None and work["tile_idx"] in (0, 1, 2)
