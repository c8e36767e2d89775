"""/distributed/metrics and /distributed/trace/{id}: Prometheus text
validity (including per-tile stage histograms and breaker-state
gauges) and span-tree JSON served over real HTTP."""

import asyncio
import json
import socket
import urllib.error
import urllib.request

import pytest

from comfyui_distributed_tpu.api.server import DistributedServer
from comfyui_distributed_tpu.resilience.health import get_health_registry
from comfyui_distributed_tpu.telemetry import get_tracer
from comfyui_distributed_tpu.telemetry.instruments import tile_stage_seconds
from comfyui_distributed_tpu.utils.async_helpers import ServerLoopThread


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _get(url: str, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.headers, resp.read().decode()


@pytest.fixture()
def server(tmp_config_path):
    loop_thread = ServerLoopThread()
    loop_thread.start()
    port = _free_port()
    srv = DistributedServer(port=port, is_worker=False)
    asyncio.run_coroutine_threadsafe(srv.start(), loop_thread.loop).result(
        timeout=30
    )
    yield srv, port, loop_thread
    asyncio.run_coroutine_threadsafe(srv.stop(), loop_thread.loop).result(
        timeout=30
    )
    loop_thread.stop()


def test_metrics_endpoint_serves_prometheus_text(server):
    srv, port, loop_thread = server

    # Push activity through the instrumented layers: store ops, a tile
    # stage observation, and breaker transitions.
    async def touch_store():
        await srv.job_store.init_tile_job("job-m", [0, 1])
        await srv.job_store.pull_task("job-m", "w1", timeout=0.05)
        await srv.job_store.submit_result("job-m", "w1", 0, None)

    asyncio.run_coroutine_threadsafe(touch_store(), loop_thread.loop).result(
        timeout=10
    )
    tile_stage_seconds().observe(0.05, stage="sample", role="master")
    registry = get_health_registry()
    for _ in range(5):
        registry.record_failure("w1")  # → quarantined

    status, headers, body = _get(f"http://127.0.0.1:{port}/distributed/metrics")
    assert status == 200
    assert headers["Content-Type"].startswith("text/plain")

    # exposition-format sanity: every non-comment line is `name{...} value`
    for line in body.strip().splitlines():
        if line.startswith("#"):
            continue
        name_part, _, value = line.rpartition(" ")
        assert name_part and value not in ("",), line
        float(value)  # parses as a number

    assert "# TYPE cdt_store_pulls_total counter" in body
    assert 'cdt_store_pulls_total{worker_id="w1",outcome="task"} 1' in body
    assert 'cdt_store_submits_total{worker_id="w1",outcome="accepted"} 1' in body
    # per-tile stage histogram series
    assert "# TYPE cdt_tile_stage_seconds histogram" in body
    assert 'cdt_tile_stage_seconds_bucket{stage="sample",role="master",le="0.1"} 1' in body
    # per-worker breaker gauge, filled by the scrape-time collector
    assert "# TYPE cdt_worker_breaker_state gauge" in body
    assert 'cdt_worker_breaker_state{worker_id="w1"} 2' in body  # quarantined
    assert "cdt_worker_breaker_transitions_total" in body
    # live queue-depth gauges exist, labelled by server role:port so
    # co-hosted servers in one process don't clobber each other
    assert f'cdt_prompt_queue_depth{{server="master:{port}"}} 0' in body
    assert f'cdt_tile_jobs_active{{server="master:{port}"}} 1' in body
    # pulled tile was completed
    assert f'cdt_tiles_in_flight{{server="master:{port}"}} 0' in body
    # JAX runtime health rides the same scrape (telemetry/runtime.py):
    # compile/cache gauges always render; jax is initialized in this
    # process (conftest), so the compile counter is a real number
    assert "# TYPE cdt_jax_compiles gauge" in body
    assert "cdt_jax_cache_hits" in body
    assert "cdt_jax_cache_misses" in body
    assert "cdt_jax_compile_time_seconds" in body
    assert "cdt_host_rss_bytes" in body
    # per-worker pull→submit latency histogram (watchdog signal)
    assert 'cdt_worker_tile_seconds_count{worker_id="w1"} 1' in body
    # elastic tile-pipeline instruments are declared on the very first
    # scrape (a dashboard reads them before any tile job runs)
    assert "# TYPE cdt_pipeline_batches_total counter" in body
    assert "# TYPE cdt_pipeline_inflight gauge" in body
    assert "# TYPE cdt_pipeline_padded_tiles_total counter" in body


def test_trace_endpoint_serves_span_tree(server):
    _srv, port, _loop = server
    tracer = get_tracer()
    with tracer.span("queue_orchestration", trace_id="exec_rt_1"):
        with tracer.span("dispatch", worker_id="w1"):
            pass

    status, _headers, body = _get(
        f"http://127.0.0.1:{port}/distributed/trace/exec_rt_1"
    )
    assert status == 200
    data = json.loads(body)
    assert data["trace_id"] == "exec_rt_1"
    assert data["span_count"] == 2
    (root,) = data["tree"]
    assert root["name"] == "queue_orchestration"
    assert root["children"][0]["name"] == "dispatch"

    status, _headers, body = _get(
        f"http://127.0.0.1:{port}/distributed/traces"
    )
    assert "exec_rt_1" in json.loads(body)["traces"]


def test_trace_endpoint_404_for_unknown_trace(server):
    _srv, port, _loop = server
    try:
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}/distributed/trace/nope", timeout=10
        )
        raise AssertionError("expected 404")
    except urllib.error.HTTPError as err:
        assert err.code == 404


def test_status_endpoints_expose_live_state(server):
    """Satellite: queue depth / in-flight tiles / breaker states appear
    in system_info and queue_status without scraping Prometheus."""
    srv, port, loop_thread = server

    async def touch_store():
        await srv.job_store.init_tile_job("job-s", [0, 1, 2])
        await srv.job_store.pull_task("job-s", "w9", timeout=0.05)

    asyncio.run_coroutine_threadsafe(touch_store(), loop_thread.loop).result(
        timeout=10
    )
    get_health_registry().record_failure("w9")

    _status, _h, body = _get(f"http://127.0.0.1:{port}/distributed/system_info")
    info = json.loads(body)["status"]
    assert info["tile_jobs"] == 1
    assert info["tile_queue_depth"] == 2
    assert info["in_flight_tiles"] == 1
    assert info["breakers"]["w9"]["state"] == "healthy"
    assert info["queue_remaining"] == 0

    _status, _h, body = _get(
        f"http://127.0.0.1:{port}/distributed/queue_status/job-s"
    )
    data = json.loads(body)
    assert data["tile_job"]["pending"] == 2
    assert data["tile_job"]["in_flight"] == 1
    assert data["breakers"]["w9"]["consecutive_failures"] == 1
