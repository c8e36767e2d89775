"""`python -m comfyui_distributed_tpu` start-up: the platform it serves
on is stated and chosen on purpose, a backend that cannot start is a
non-zero exit, and the server hands every node the mesh the
worker_mesh rule builds."""

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

import pytest

from comfyui_distributed_tpu.workers import startup

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(tmp_path, **extra):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        CDT_CONFIG_PATH=str(tmp_path / "tpu_config.json"),
        CDT_DATA_DIR=str(tmp_path / "data"),
        CDT_LOG_DIR=str(tmp_path / "logs"),
        PYTHONPATH=REPO_ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    env.pop("CDT_IS_WORKER", None)
    env.pop("CDT_MESH_SHAPE", None)
    env.update(extra)
    return env


def _free_port() -> int:
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


def _cli(tmp_path, *args, **extra_env):
    return subprocess.run(
        [sys.executable, "-m", "comfyui_distributed_tpu", *args],
        cwd=str(tmp_path), env=_env(tmp_path, **extra_env),
        capture_output=True, text=True, timeout=120,
    )


def test_cli_refuses_the_cpu_unless_asked_by_name(tmp_path):
    """JAX_PLATFORMS=cpu (or a silent fallback when libtpu cannot take
    the chip) is not a request to serve on the CPU: exit non-zero and
    name the platform."""
    proc = _cli(tmp_path, "--port", str(_free_port()))
    out = proc.stdout + proc.stderr
    assert proc.returncode != 0, out
    assert "platform=cpu" in out
    assert "refusing to serve on platform 'cpu'" in out
    assert "--platform cpu" in out


def test_cli_exits_nonzero_when_the_backend_cannot_start(tmp_path):
    """jax's "Unable to initialize backend" is a RuntimeError; it used
    to be caught around asyncio.run and turned into exit 0."""
    proc = _cli(tmp_path, "--port", str(_free_port()), "--platform", "nosuchchip")
    out = proc.stdout + proc.stderr
    assert proc.returncode != 0, out
    assert "backend start-up failed" in out
    assert "nosuchchip" in out


def test_init_backend_states_and_checks_the_platform(capsys):
    with pytest.raises(RuntimeError, match="refusing to serve on platform 'cpu'"):
        startup.init_backend(None)
    devices = startup.init_backend("cpu")
    assert devices and devices[0].platform == "cpu"
    assert "serving on platform=cpu device_kind=cpu" in capsys.readouterr().out


def test_cli_server_builds_its_mesh_from_worker_mesh(tmp_path):
    """On a multi-chip host the CLI master must serve on every chip:
    the mesh comes from parallel/mesh.worker_mesh (on the CPU that is
    the opt-in shape over the virtual devices) and is what
    system_info reports — and SIGTERM ends the process with exit 0."""
    port = _free_port()
    proc = subprocess.Popen(
        [sys.executable, "-m", "comfyui_distributed_tpu", "--port", str(port),
         "--platform", "cpu"],
        cwd=str(tmp_path),
        env=_env(
            tmp_path,
            XLA_FLAGS="--xla_force_host_platform_device_count=8",
            CDT_MESH_SHAPE="4,1",
        ),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    try:
        info = None
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline and proc.poll() is None:
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/distributed/system_info", timeout=5
                ) as resp:
                    info = json.loads(resp.read())
                break
            except OSError:
                time.sleep(0.3)
        assert info is not None, "server never answered"
        topology = info["topology"]
        assert topology["platform"] == "cpu"
        assert topology["local_device_count"] == 8
        assert topology["mesh"] == {"data": 4, "model": 1, "devices": 4}
        # the start itself is a trace the running server serves
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/distributed/trace/startup", timeout=5
        ) as resp:
            (root,) = json.loads(resp.read())["tree"]
        assert root["name"] == "process.start" and root["end"] is not None
        assert root["attrs"]["pid"] == proc.pid and root["attrs"]["role"] == "master"
        assert 0.0 < root["attrs"]["python_s"] < root["duration"]
        children = [c for c in root["children"] if c["name"].startswith("startup.")]
        assert [c["name"] for c in children] == [
            "startup.chips", "startup.compile_cache", "startup.backend",
            "startup.imports", "startup.mesh", "startup.server"]
        assert all(root["start"] <= c["start"] <= c["end"] <= root["end"] for c in children)
        assert children[2]["attrs"] == {"platform": "cpu", "device_kind": "cpu", "devices": 8}
        assert children[1]["attrs"]["dir"] and children[5]["attrs"] == {"port": port}
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=60) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    log = proc.stdout.read()
    assert "serving mesh {'data': 4, 'model': 1, 'devices': 4}" in log
    assert "data plane: " in log


def test_cli_bad_mesh_knob_is_a_failed_start_not_one_participant(tmp_path):
    proc = _cli(
        tmp_path, "--port", str(_free_port()), "--platform", "cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        CDT_MESH_SHAPE="banana",
    )
    assert proc.returncode != 0
    assert "CDT_MESH_SHAPE" in proc.stdout + proc.stderr
