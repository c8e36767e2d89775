"""Worker lifecycle: launch command building, arg sanitization, PID
persistence, stale recovery, auto-populate, monitor helpers."""

import os
import subprocess
import sys
import time

import pytest

from comfyui_distributed_tpu.utils import config as cfg_mod
from comfyui_distributed_tpu.utils.exceptions import ProcessError
from comfyui_distributed_tpu.workers import detection
from comfyui_distributed_tpu.workers import process_manager as pm
from comfyui_distributed_tpu.workers import startup


def test_build_launch_command():
    manager = pm.WorkerProcessManager()
    cmd = manager.build_launch_command(
        {"id": "w1", "port": 8190, "extra_args": "--platform cpu"}
    )
    assert cmd[1:] == [
        "-m", "comfyui_distributed_tpu", "--port", "8190", "--worker",
        "--platform", "cpu",
    ]


def test_extra_args_sanitized():
    with pytest.raises(ProcessError):
        pm.sanitize_extra_args("--foo; rm -rf /")
    with pytest.raises(ProcessError):
        pm.sanitize_extra_args("$(evil)")
    assert pm.sanitize_extra_args('--a "b c"') == ["--a", "b c"]
    assert pm.sanitize_extra_args("") == []


def test_is_process_alive():
    assert pm.is_process_alive(os.getpid())
    proc = subprocess.Popen([sys.executable, "-c", "pass"])
    proc.wait()
    time.sleep(0.1)
    assert not pm.is_process_alive(999999)


def test_pid_persistence_and_stale_recovery(tmp_config_path):
    manager = pm.WorkerProcessManager()
    manager._persist("w1", 999999, None)  # dead pid
    assert "w1" in manager.managed_processes()
    stale = manager.clear_stale()
    assert stale == ["w1"]
    assert "w1" not in manager.managed_processes()


def test_concurrent_persist_does_not_lose_writers(tmp_config_path):
    """Config read-modify-write cycles run on executor threads; without
    the shared config lock, two concurrent _persist calls can load the
    same snapshot and the second save erases the first's entry."""
    import threading

    manager = pm.WorkerProcessManager()
    barrier = threading.Barrier(8)

    def persist(i):
        barrier.wait()
        manager._persist(f"w{i}", 100000 + i, None)
        manager.clear_launching(f"w{i}")

    threads = [threading.Thread(target=persist, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    managed = manager.managed_processes()
    assert sorted(managed) == [f"w{i}" for i in range(8)]
    assert all("launching" not in e for e in managed.values())


def test_launch_and_stop_real_process(tmp_config_path, tmp_path, monkeypatch):
    """Launch a real (sleep) process through the manager and tree-kill it."""
    monkeypatch.setenv("CDT_LOG_DIR", str(tmp_path / "logs"))
    manager = pm.WorkerProcessManager()
    monkeypatch.setattr(
        manager, "build_launch_command",
        lambda worker: [sys.executable, "-c", "import time; time.sleep(60)"],
    )
    info = manager.launch_worker({"id": "w2", "name": "w2", "port": 0})
    assert pm.is_process_alive(info["pid"])
    assert "w2" in manager.managed_processes()
    # duplicate launch refused while alive
    with pytest.raises(ProcessError):
        manager.launch_worker({"id": "w2", "name": "w2", "port": 0})
    assert manager.stop_worker("w2") is True
    time.sleep(0.2)
    assert not pm.is_process_alive(info["pid"])
    assert "w2" not in manager.managed_processes()


def test_clear_launching_marker(tmp_config_path):
    """_persist marks a fresh launch; clear_launching drops exactly
    that marker (reference /distributed/worker/clear_launching) and is
    idempotent."""
    manager = pm.WorkerProcessManager()
    manager._persist("w1", os.getpid(), None)
    assert manager.managed_processes()["w1"]["launching"] is True
    assert manager.clear_launching("w1") is True
    entry = manager.managed_processes()["w1"]
    assert "launching" not in entry
    assert entry["pid"] == os.getpid()  # rest of the record intact
    assert manager.clear_launching("w1") is False  # idempotent
    assert manager.clear_launching("missing") is False


def test_host_tpu_chips_counts_device_nodes_without_a_backend(tmp_path):
    """Chips are counted from the device nodes a process could open —
    numbered vfio groups (v5e on) or /dev/accelN — never through jax,
    whose enumeration takes the chips it lists."""
    v5e = tmp_path / "v5e"
    (v5e / "vfio").mkdir(parents=True)
    for name in ("0", "1", "2", "3", "vfio"):
        (v5e / "vfio" / name).touch()
    assert startup.host_tpu_chips(str(v5e)) == [0, 1, 2, 3]
    older = tmp_path / "older"
    older.mkdir()
    for name in ("accel0", "accel1", "accelerometer", "null"):
        (older / name).touch()
    assert startup.host_tpu_chips(str(older)) == [0, 1]
    assert startup.host_tpu_chips(str(tmp_path / "missing")) == []


def test_auto_populate_once(tmp_config_path, monkeypatch):
    monkeypatch.setattr(startup, "host_tpu_chips", lambda: list(range(8)))
    # master unpinned (the default): its mesh drives every chip, there
    # is nothing to populate and the first-run flag stays unspent
    assert startup.auto_populate_workers() == []
    assert "has_auto_populated_workers" not in cfg_mod.load_config()["settings"]
    # process-per-chip mode: one disabled entry per chip the master left
    cfg = cfg_mod.load_config()
    cfg["master"]["tpu_chips"] = [0]
    cfg_mod.save_config(cfg)
    created = startup.auto_populate_workers()
    assert [w["tpu_chips"] for w in created] == [[c] for c in range(1, 8)]
    assert all(not w["enabled"] for w in created)
    cfg = cfg_mod.load_config()
    assert len(cfg["workers"]) == 7
    assert cfg["settings"]["has_auto_populated_workers"] is True
    # second call is a no-op
    assert startup.auto_populate_workers() == []
    assert len(cfg_mod.load_config()["workers"]) == 7


def test_chip_environment_is_a_complete_sub_host_process():
    """TPU_VISIBLE_CHIPS alone leaves libtpu assuming the host's whole
    topology and its host-wide lock; a sub-host process also needs its
    bounds, a controller port of its own and the multi-load permit."""
    assert pm.chip_environment([]) == {}
    env = pm.chip_environment([1])
    assert env == {
        "TPU_VISIBLE_CHIPS": "1",
        "TPU_VISIBLE_DEVICES": "1",
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_CHIPS_PER_HOST_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_HOST_BOUNDS": "1,1,1",
        "TPU_PROCESS_ADDRESSES": "localhost:8477",
        "TPU_PROCESS_PORT": "8477",
        "TPU_MESH_CONTROLLER_ADDRESS": "localhost:8477",
        "TPU_MESH_CONTROLLER_PORT": "8477",
        "TPU_RUNTIME_METRICS_PORTS": "8432",
        "CLOUD_TPU_TASK_ID": "0",
        "TPU_WORKER_ID": "0",
        "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
    }
    # two processes on one host never share a controller port
    assert pm.chip_environment([0])["TPU_PROCESS_PORT"] == "8476"
    assert pm.chip_environment([2, 3])["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,2,1"
    with pytest.raises(ProcessError):
        pm.chip_environment([0, 1, 2])


def _capture_launch(manager, monkeypatch):
    launched = {}

    class _Proc:
        pid = 424242

    def fake_popen(cmd, **kwargs):
        launched["cmd"] = cmd
        launched["env"] = kwargs["env"]
        return _Proc()

    monkeypatch.setattr(pm.subprocess, "Popen", fake_popen)
    return launched


def test_master_on_chip_0_launches_worker_on_chip_1(
    tmp_config_path, tmp_path, monkeypatch
):
    """Process per chip: the master pins ITSELF (before any backend
    starts) and hands the worker a complete environment for its own
    chip — not the master's, and not visibility alone."""
    monkeypatch.setenv("CDT_LOG_DIR", str(tmp_path / "logs"))
    for key in pm.chip_environment([0]):
        monkeypatch.delenv(key, raising=False)
    monkeypatch.delenv("CDT_IS_WORKER", raising=False)
    cfg = cfg_mod.load_config()
    cfg["master"]["tpu_chips"] = [0]
    cfg_mod.save_config(cfg)

    assert startup.apply_master_chips() == [0]
    for key, value in pm.chip_environment([0]).items():
        assert os.environ[key] == value
        monkeypatch.setenv(key, value)  # restored after the test

    manager = pm.WorkerProcessManager()
    launched = _capture_launch(manager, monkeypatch)
    manager.launch_worker(
        {"id": "w1", "name": "w1", "port": 8190, "tpu_chips": [1]}
    )
    env = launched["env"]
    for key, value in pm.chip_environment([1]).items():
        assert env[key] == value
    assert env["CDT_IS_WORKER"] == "1"
    assert env["CDT_MASTER_PID"] == str(os.getpid())
    # the chip the master holds is refused, loudly, at launch
    with pytest.raises(ProcessError, match="holds 0"):
        manager.launch_worker(
            {"id": "w0", "name": "w0", "port": 8191, "tpu_chips": [0]}
        )


def test_unpinned_master_refuses_a_chip_pinned_worker(
    tmp_config_path, tmp_path, monkeypatch
):
    """The default master takes every local chip; a worker that needs
    one would die in its own log, so the launch says so instead."""
    monkeypatch.setenv("CDT_LOG_DIR", str(tmp_path / "logs"))
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    monkeypatch.delenv("CDT_IS_WORKER", raising=False)
    assert startup.apply_master_chips() == []
    assert "TPU_VISIBLE_CHIPS" not in os.environ
    manager = pm.WorkerProcessManager()
    _capture_launch(manager, monkeypatch)
    with pytest.raises(ProcessError, match="master.tpu_chips"):
        manager.launch_worker(
            {"id": "w1", "name": "w1", "port": 8190, "tpu_chips": [1]}
        )
    # a worker with no chip set (CPU hosts, remote-style local workers)
    # launches as before
    manager.launch_worker({"id": "w2", "name": "w2", "port": 8191})


def test_worker_process_never_repins_itself(tmp_config_path, monkeypatch):
    monkeypatch.setenv("CDT_IS_WORKER", "1")
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    cfg = cfg_mod.load_config()
    cfg["master"]["tpu_chips"] = [0]
    cfg_mod.save_config(cfg)
    assert startup.apply_master_chips() == []
    assert "TPU_VISIBLE_CHIPS" not in os.environ


def test_detection_helpers():
    assert len(detection.get_machine_id()) == 12
    assert detection.is_local_worker({"type": "local"})
    assert detection.is_local_worker({"type": "remote", "host": "127.0.0.1"})
    assert not detection.is_local_worker({"type": "remote", "host": "10.1.2.3"})
