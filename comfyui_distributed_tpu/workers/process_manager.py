"""Worker process lifecycle management.

The reference's WorkerProcessManager subsystem (reference
workers/process_manager.py + workers/process/*): build a launch
command, spawn with per-worker env (chip pinning, role flag, master
pid), log to per-worker files, persist PIDs into config
managed_processes for restore-on-restart, and stop via process-tree
kill. TPU adaptations: chip pinning via the libtpu sub-host process
environment (chip_environment) instead of CUDA_VISIBLE_DEVICES; workers
run `python -m comfyui_distributed_tpu --port N --worker`.
"""

from __future__ import annotations

import datetime
import os
import shlex
import subprocess
import sys
import threading
import time
from typing import Any, Optional

import psutil

from ..utils import config as config_mod
from ..utils.constants import MASTER_PID_ENV, TPU_VISIBLE_CHIPS_ENV, WORKER_ENV_FLAG
from ..utils.exceptions import ProcessError
from ..utils.logging import debug_log, log

FORBIDDEN_ARG_CHARS = set(";&|`$<>\n\r")


def logs_dir() -> str:
    return os.environ.get(
        "CDT_LOG_DIR", os.path.join(os.getcwd(), "logs", "workers")
    )


def worker_log_path(name: str) -> str:
    date = datetime.date.today().isoformat()
    safe = "".join(c for c in name if c.isalnum() or c in "-_") or "worker"
    return os.path.join(logs_dir(), f"{safe}_{date}.log")


def get_python_executable() -> str:
    return sys.executable or "python3"


def is_process_alive(pid: int) -> bool:
    try:
        proc = psutil.Process(pid)
        return proc.is_running() and proc.status() != psutil.STATUS_ZOMBIE
    except (psutil.NoSuchProcess, ValueError):
        return False


def sanitize_extra_args(extra: str) -> list[str]:
    """Split user-provided extra CLI args, refusing shell metacharacters
    (reference workers/process/launch_builder.py sanitization)."""
    if not extra:
        return []
    if any(c in FORBIDDEN_ARG_CHARS for c in extra):
        raise ProcessError(f"forbidden characters in extra_args: {extra!r}")
    return shlex.split(extra)


def chip_environment(chips: list[int]) -> dict[str, str]:
    """The environment libtpu needs to run as a sub-host process on
    `chips`: visibility alone leaves it assuming the whole host's
    topology (and the host-wide lock), so the process bounds, a private
    controller port, a metrics port of its own and the multi-load
    permit travel with it. Each chip set is its own one-process slice —
    the processes talk HTTP, not ICI. A TPU host image exports the
    whole-host values under libtpu's older names
    (TPU_CHIPS_PER_HOST_BOUNDS=2,2,1 ...), so both generations of each
    name are set: whichever libtpu reads, it reads this process's.
    Empty = the whole host, which needs nothing."""
    if not chips:
        return {}
    bounds = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}.get(len(chips))
    if bounds is None:
        raise ProcessError(
            f"tpu_chips {chips}: a process takes 1, 2, 4 or 8 chips"
        )
    visible = ",".join(str(c) for c in chips)
    port = str(8476 + min(chips))
    return {
        TPU_VISIBLE_CHIPS_ENV: visible,
        "TPU_VISIBLE_DEVICES": visible,
        "TPU_CHIPS_PER_PROCESS_BOUNDS": bounds,
        "TPU_CHIPS_PER_HOST_BOUNDS": bounds,
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_HOST_BOUNDS": "1,1,1",
        "TPU_PROCESS_ADDRESSES": f"localhost:{port}",
        "TPU_PROCESS_PORT": port,
        "TPU_MESH_CONTROLLER_ADDRESS": f"localhost:{port}",
        "TPU_MESH_CONTROLLER_PORT": port,
        "TPU_RUNTIME_METRICS_PORTS": ",".join(str(8431 + c) for c in chips),
        "CLOUD_TPU_TASK_ID": "0",
        "TPU_WORKER_ID": "0",
        "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
    }


class WorkerProcessManager:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._procs: dict[str, subprocess.Popen] = {}

    # --- launch -----------------------------------------------------------

    def build_launch_command(self, worker: dict[str, Any]) -> list[str]:
        cmd = [
            get_python_executable(),
            "-m",
            "comfyui_distributed_tpu",
            "--port",
            str(worker.get("port") or 8189),
            "--worker",
        ]
        cmd += sanitize_extra_args(str(worker.get("extra_args", "") or ""))
        return cmd

    def launch_worker(
        self, worker: dict[str, Any], config_path: str | None = None
    ) -> dict[str, Any]:
        worker_id = str(worker.get("id") or worker.get("name") or "worker")
        with self._lock:
            managed = self.managed_processes(config_path)
            existing = managed.get(worker_id)
            if existing and is_process_alive(int(existing.get("pid", -1))):
                raise ProcessError(
                    f"worker {worker_id} already running (pid {existing['pid']})"
                )

            env = dict(os.environ)
            env[WORKER_ENV_FLAG] = "1"
            env[MASTER_PID_ENV] = str(os.getpid())
            chips = [int(c) for c in worker.get("tpu_chips") or []]
            held = os.environ.get(TPU_VISIBLE_CHIPS_ENV)
            if chips and (
                not held or set(chips) & {int(c) for c in held.split(",")}
            ):
                # libtpu gives a chip to one process; a launch that
                # cannot get its chip would die in its log, not here
                raise ProcessError(
                    f"worker {worker_id} wants chips {chips} but this "
                    f"master holds {held or 'every local chip'}: set "
                    "master.tpu_chips to the master's own chips and "
                    "restart it (process-per-chip mode)"
                )
            env.update(chip_environment(chips))
            cmd = self.build_launch_command(worker)

            os.makedirs(logs_dir(), exist_ok=True)
            log_path = worker_log_path(worker.get("name") or worker_id)
            log_file = open(log_path, "ab")
            log(f"launching worker {worker_id}: {' '.join(cmd)} (log: {log_path})")
            proc = subprocess.Popen(
                cmd,
                stdout=log_file,
                stderr=subprocess.STDOUT,
                env=env,
                start_new_session=True,
            )
            log_file.close()
            self._procs[worker_id] = proc
            self._persist(worker_id, proc.pid, config_path)
            return {"worker_id": worker_id, "pid": proc.pid, "log": log_path}

    # --- stop -------------------------------------------------------------

    def stop_worker(
        self, worker_id: str, config_path: str | None = None
    ) -> bool:
        managed = self.managed_processes(config_path)
        entry = managed.get(worker_id)
        pid = entry.get("pid") if entry else None
        stopped = False
        if pid is not None:
            stopped = self._kill_tree(int(pid))
        with self._lock:
            self._procs.pop(worker_id, None)
        self._unpersist(worker_id, config_path)
        return stopped

    def stop_all(self, config_path: str | None = None) -> int:
        count = 0
        for worker_id in list(self.managed_processes(config_path)):
            if self.stop_worker(worker_id, config_path):
                count += 1
        return count

    @staticmethod
    def _kill_tree(pid: int) -> bool:
        """Terminate a process and its children: TERM, grace, KILL
        (reference workers/process/lifecycle.py tree-kill)."""
        try:
            root = psutil.Process(pid)
        except psutil.NoSuchProcess:
            return False
        procs = [root] + root.children(recursive=True)
        for p in procs:
            try:
                p.terminate()
            except psutil.NoSuchProcess:
                pass
        _, alive = psutil.wait_procs(procs, timeout=5)
        for p in alive:
            try:
                p.kill()
            except psutil.NoSuchProcess:
                pass
        debug_log(f"killed process tree of pid {pid}")
        return True

    # --- persistence -------------------------------------------------------

    def managed_processes(self, config_path: str | None = None) -> dict[str, Any]:
        return dict(
            config_mod.load_config(config_path).get("managed_processes", {})
        )

    # Persistence writes go through config_mod.locked_config — the
    # SAME mutex as the async config_transaction used by the config
    # routes, so a launch's _persist cannot interleave with a panel
    # settings save and lose either write.

    def _persist(self, worker_id: str, pid: int, config_path: str | None) -> None:
        with config_mod.locked_config(config_path) as config:
            config.setdefault("managed_processes", {})[worker_id] = {
                "pid": pid,
                "started_at": time.time(),
                # cleared via clear_launching once the worker is
                # confirmed up; a crashed launch otherwise leaves the
                # flag for the panel's grace-window logic to expire
                "launching": True,
            }

    def clear_launching(
        self, worker_id: str, config_path: str | None = None
    ) -> bool:
        """Drop the 'launching' marker once the worker is confirmed
        running (reference api/worker_routes.py clear_launching_state);
        returns whether a marker was cleared."""
        with config_mod.locked_config(config_path) as config:
            entry = config.get("managed_processes", {}).get(worker_id)
            if entry is None or "launching" not in entry:
                return False
            del entry["launching"]
            return True

    def _unpersist(self, worker_id: str, config_path: str | None) -> None:
        with config_mod.locked_config(config_path) as config:
            config.get("managed_processes", {}).pop(worker_id, None)

    def clear_stale(self, config_path: str | None = None) -> list[str]:
        """Drop managed entries whose PIDs are dead (master restart
        recovery, reference workers/process/persistence.py)."""
        stale = []
        with config_mod.locked_config(config_path) as config:
            managed = config.get("managed_processes", {})
            for worker_id, entry in list(managed.items()):
                if not is_process_alive(int(entry.get("pid", -1))):
                    stale.append(worker_id)
                    del managed[worker_id]
        return stale


_manager: Optional[WorkerProcessManager] = None
_manager_lock = threading.Lock()


def get_worker_manager() -> WorkerProcessManager:
    global _manager
    with _manager_lock:
        if _manager is None:
            _manager = WorkerProcessManager()
        return _manager
