"""Profile routes over real HTTP: ledger surface, capture start/stop
round-trip, the single-flight 409, the disabled hint without
CDT_PROFILE_DIR, and the system_info `probe` key.
"""

import asyncio
import json
import socket
import urllib.error
import urllib.request

import pytest

from comfyui_distributed_tpu.api.server import DistributedServer
from comfyui_distributed_tpu.telemetry.profiling import (
    _reset_profiler_capture_for_tests,
    _reset_transfer_ledger_for_tests,
    get_transfer_ledger,
)
from comfyui_distributed_tpu.utils.async_helpers import ServerLoopThread

pytestmark = pytest.mark.fast


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _get_json(url: str, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read().decode())


def _post_json(url: str, payload=None, timeout=10):
    data = json.dumps(payload).encode() if payload is not None else b""
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read().decode())


class FakeProfiler:
    def __init__(self):
        self.started = []
        self.stopped = 0

    def start_trace(self, path, profiler_options=None):
        self.started.append(path)

    def stop_trace(self):
        self.stopped += 1


@pytest.fixture()
def fake_profiler(monkeypatch):
    import jax

    fake = FakeProfiler()
    monkeypatch.setattr(jax.profiler, "start_trace", fake.start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", fake.stop_trace)
    return fake


@pytest.fixture()
def clean_profiling():
    _reset_profiler_capture_for_tests()
    _reset_transfer_ledger_for_tests()
    yield
    _reset_profiler_capture_for_tests()
    _reset_transfer_ledger_for_tests()


def _start_server(port: int):
    loop_thread = ServerLoopThread()
    loop_thread.start()
    srv = DistributedServer(port=port, is_worker=False)
    asyncio.run_coroutine_threadsafe(srv.start(), loop_thread.loop).result(
        timeout=30
    )
    return srv, loop_thread


def _stop_server(srv, loop_thread):
    asyncio.run_coroutine_threadsafe(srv.stop(), loop_thread.loop).result(
        timeout=30
    )
    loop_thread.stop()


@pytest.fixture()
def server(tmp_config_path, tmp_path, monkeypatch, clean_profiling):
    monkeypatch.setenv("CDT_PROFILE_DIR", str(tmp_path / "traces"))
    port = _free_port()
    srv, loop_thread = _start_server(port)
    yield srv, port
    _stop_server(srv, loop_thread)


def test_status_serves_ledger_and_capture_index(server, fake_profiler):
    srv, port = server
    ledger = get_transfer_ledger()
    ledger.note_dispatch(0.5, device=True)
    ledger.note_host("gather", 0.25)
    ledger.note_tiles(4)
    status, payload = _get_json(
        f"http://127.0.0.1:{port}/distributed/profile"
    )
    assert status == 200
    assert payload["enabled"] is True
    assert payload["ledger"]["tiles"] == 4
    assert payload["ledger"]["host_tax"] == pytest.approx(1.0 / 3.0)
    assert payload["ledger"]["host_total_ns"] == sum(
        payload["ledger"]["host_ns"].values()
    )
    assert payload["capture"]["active"] is None
    assert payload["captures"] == []


def test_start_stop_round_trip_and_busy_409(server, fake_profiler):
    srv, port = server
    base = f"http://127.0.0.1:{port}/distributed/profile"
    status, started = _post_json(
        f"{base}/start", {"duration_s": 5.0, "tag": "drill"}
    )
    assert status == 200 and started["started"] is True
    assert started["id"].endswith("-drill")

    with pytest.raises(urllib.error.HTTPError) as err:
        _post_json(f"{base}/start", {})
    assert err.value.code == 409
    assert json.loads(err.value.read().decode())["reason"] == "busy"

    status, info = _get_json(base)
    assert info["capture"]["active"]["id"] == started["id"]

    status, stopped = _post_json(f"{base}/stop")
    assert status == 200 and stopped["stopped"] is True
    assert stopped["id"] == started["id"]
    assert fake_profiler.stopped == 1

    # idempotent stop + the capture now in the retained index
    status, again = _post_json(f"{base}/stop")
    assert again["stopped"] is False
    status, info = _get_json(base)
    assert [c["id"] for c in info["captures"]] == [started["id"]]


def test_bad_duration_is_400(server, fake_profiler):
    srv, port = server
    with pytest.raises(urllib.error.HTTPError) as err:
        _post_json(
            f"http://127.0.0.1:{port}/distributed/profile/start",
            {"duration_s": "a lot"},
        )
    assert err.value.code == 400


def test_disabled_without_profile_dir(
    tmp_config_path, monkeypatch, clean_profiling
):
    monkeypatch.delenv("CDT_PROFILE_DIR", raising=False)
    port = _free_port()
    srv, loop_thread = _start_server(port)
    try:
        base = f"http://127.0.0.1:{port}/distributed/profile"
        status, payload = _get_json(base)
        assert status == 200
        assert payload["enabled"] is False
        assert "CDT_PROFILE_DIR" in payload["hint"]
        # the ledger half still serves (None until something metered)
        assert "ledger" in payload
        for suffix in ("start", "stop"):
            with pytest.raises(urllib.error.HTTPError) as err:
                _post_json(f"{base}/{suffix}", {})
            assert err.value.code == 400
    finally:
        _stop_server(srv, loop_thread)


def test_system_info_names_the_device_and_versions(
    tmp_config_path, clean_profiling
):
    """What chip_smoke.py and the panel read to know what the server
    runs on: platform, device kind, counts, package versions, the
    serving mesh and which data plane loaded."""
    port = _free_port()
    srv, loop_thread = _start_server(port)
    try:
        status, info = _get_json(
            f"http://127.0.0.1:{port}/distributed/system_info"
        )
        assert status == 200
        topology = info["topology"]
        assert topology["platform"] == "cpu"
        assert topology["device_kind"] == "cpu"
        assert topology["device_count"] == topology["local_device_count"] >= 1
        assert set(topology["versions"]) == {"jax", "jaxlib", "libtpu"}
        assert topology["versions"]["jax"]
        assert topology["mesh"]["devices"] >= 1
        assert info["data_plane"] in ("native", "numpy")
        assert "probe" not in info
    finally:
        _stop_server(srv, loop_thread)


def test_system_info_reports_a_dead_backend_as_an_error(
    tmp_config_path, monkeypatch, clean_profiling
):
    """A backend that cannot start is an `error` in the answer (which
    chip_smoke.py fails on), not an empty device list."""
    from comfyui_distributed_tpu.parallel import mesh as mesh_mod

    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(mesh_mod, "describe_topology", boom)
    port = _free_port()
    srv, loop_thread = _start_server(port)
    try:
        status, info = _get_json(
            f"http://127.0.0.1:{port}/distributed/system_info"
        )
        assert status == 200
        assert info["topology"] == {
            "error": "Unable to initialize backend 'tpu'"
        }
    finally:
        _stop_server(srv, loop_thread)
