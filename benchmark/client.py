"""The server child and its HTTP face, as a client sees them.

Copied from chip_smoke.py (`Server`, `http`, `read_metrics`, the PNG
checks) so that the yardstick does not move when the smoke does. This
process never starts a JAX backend: the chip belongs to the child.
"""

from __future__ import annotations

import http.client
import io
import json
import os
import signal
import socket
import subprocess
import sys
import time

PACKAGE = "comfyui_distributed_tpu"


class Failure(Exception):
    """The run cannot report a result."""


class Conn:
    """One keep-alive connection; one per thread."""

    def __init__(self, port: int, timeout: float = 300.0):
        self.port, self.timeout = port, timeout
        self._conn: http.client.HTTPConnection | None = None

    def call(self, method: str, path: str, body=None):
        """(status, parsed JSON or text). Reconnects once if the kept
        connection has gone stale."""
        data = None if body is None else json.dumps(body).encode()
        headers = {"Content-Type": "application/json"} if data else {}
        for attempt in (0, 1):
            if self._conn is None:
                self._conn = http.client.HTTPConnection(
                    "127.0.0.1", self.port, timeout=self.timeout
                )
            try:
                self._conn.request(method, path, body=data, headers=headers)
                response = self._conn.getresponse()
                raw = response.read()
                break
            except (http.client.HTTPException, OSError):
                self.close()
                if attempt:
                    raise
        text = raw.decode(errors="replace")
        try:
            return response.status, json.loads(text)
        except json.JSONDecodeError:
            return response.status, text

    def ok(self, method: str, path: str, body=None):
        status, answer = self.call(method, path, body)
        if status != 200:
            raise Failure(f"{method} {path} -> HTTP {status}: {str(answer)[:500]}")
        return answer

    def close(self) -> None:
        if self._conn is not None:
            self._conn.close()
            self._conn = None


def port_is_dead(port: int) -> bool:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as sock:
        sock.settimeout(1.0)
        return sock.connect_ex(("127.0.0.1", port)) != 0


def tail(path: str, lines: int = 30) -> str:
    try:
        with open(path, "r", encoding="utf-8", errors="replace") as fh:
            return "".join(fh.readlines()[-lines:])
    except OSError as exc:
        return f"<no log: {exc}>"


class Server:
    """One `python -m comfyui_distributed_tpu --port P` child. Config,
    data, logs and the native build live under `out`; the compile cache
    stays where the program puts it (JAX_COMPILATION_CACHE_DIR, else its
    fixed in-checkout path)."""

    def __init__(self, root: str, out: str, port: int, *, extra_args=(), extra_env=None):
        self.root, self.out, self.port = root, out, port
        self.extra_args = list(extra_args)
        self.extra_env = dict(extra_env or {})
        self.log_path = os.path.join(out, "server.log")
        self.proc: subprocess.Popen | None = None

    def start(self) -> dict:
        if not port_is_dead(self.port):
            raise Failure(f"port {self.port} is already taken")
        env = dict(os.environ)
        env.update(
            CDT_CONFIG_PATH=os.path.join(self.out, "tpu_config.json"),
            CDT_DATA_DIR=os.path.join(self.out, "data"),
            CDT_LOG_DIR=os.path.join(self.out, "logs"),
            CDT_NATIVE_BUILD_DIR=os.path.join(self.out, "native_build"),
            PYTHONPATH=self.root + os.pathsep + env.get("PYTHONPATH", ""),
            PYTHONUNBUFFERED="1",
        )
        env.update(self.extra_env)
        cmd = [sys.executable, "-m", PACKAGE, "--port", str(self.port), *self.extra_args]
        with open(self.log_path, "ab") as log:
            self.proc = subprocess.Popen(
                cmd, cwd=self.root, env=env, stdout=log, stderr=subprocess.STDOUT,
                start_new_session=True,
            )
        conn = Conn(self.port, timeout=10)
        deadline = time.monotonic() + 600
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise Failure(
                    f"server exited with code {self.proc.returncode} during "
                    f"start-up; end of its log:\n{tail(self.log_path)}"
                )
            try:
                status, info = conn.call("GET", "/distributed/system_info")
                if status == 200:
                    conn.close()
                    return info
            except (http.client.HTTPException, OSError):
                pass
            time.sleep(0.25)
        raise Failure("server gave no answer within 600s")

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    def stop(self, grace_s: float = 60) -> None:
        """SIGTERM, SIGKILL after `grace_s`; returns once the child has
        ended and its port is dead."""
        proc, self.proc = self.proc, None
        if proc is None:
            return
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=grace_s)
            except subprocess.TimeoutExpired:
                pass
        # whatever the child started shares its session
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        proc.wait()
        deadline = time.monotonic() + 15
        while not port_is_dead(self.port):
            if time.monotonic() > deadline:
                raise Failure(f"server ended but port {self.port} still answers")
            time.sleep(0.1)


def device_of(info: dict) -> dict:
    """The device as the server (JAX) reports it in system_info."""
    topology = info.get("topology") or {}
    for holder in (topology, topology.get("mesh") or {}):
        if "error" in holder:
            raise Failure(f"system_info: {holder['error']}")
    return {
        "platform": topology.get("platform"),
        "kind": topology.get("device_kind"),
        "count": topology.get("device_count"),
    }


_PLAIN = {
    "cdt_jax_compiles": "compiles",
    "cdt_jax_compile_time_seconds": "compile_s",
    "cdt_jax_cache_hits": "cache_hits",
    "cdt_jax_cache_misses": "cache_misses",
}


def parse_metrics(text: str) -> dict:
    """The runtime gauges of /distributed/metrics (telemetry/runtime.py)."""
    out = {name: 0.0 for name in _PLAIN.values()}
    out["peak_bytes_in_use"], out["bytes_in_use"] = {}, {}
    for line in text.splitlines():
        if line.startswith("#") or not line.strip():
            continue
        head, _, value = line.rpartition(" ")
        name, _, labels = head.partition("{")
        if name in _PLAIN:
            out[_PLAIN[name]] = float(value)
        elif name == "cdt_device_memory_bytes":
            pairs = dict(
                part.split("=", 1) for part in labels.rstrip("}").split(",") if part
            )
            pairs = {k: v.strip('"') for k, v in pairs.items()}
            if pairs.get("stat") in ("peak_bytes_in_use", "bytes_in_use"):
                out[pairs["stat"]][pairs.get("device", "?")] = int(float(value))
    return out


def scrape(conn: Conn) -> dict:
    return parse_metrics(conn.ok("GET", "/distributed/metrics"))


# --- images ------------------------------------------------------------------


def write_input_image(path: str, px: int, seed: int) -> None:
    """A LoadImage input from a seed: smooth colour fields plus fine
    noise, so every tile has content and none is constant."""
    import numpy as np
    from PIL import Image

    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:px, 0:px].astype(np.float32) / px
    phase = rng.uniform(0, 2 * np.pi, size=(3, 2))
    planes = [
        0.5 + 0.35 * np.sin(2 * np.pi * (3 + c) * xx + phase[c, 0])
        * np.cos(2 * np.pi * (2 + c) * yy + phase[c, 1])
        for c in range(3)
    ]
    image = np.stack(planes, axis=-1) + rng.normal(0, 0.04, size=(px, px, 3))
    pixels = (np.clip(image, 0, 1) * 255 + 0.5).astype(np.uint8)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    Image.fromarray(pixels).save(path, compress_level=1)


def image_fault(raw: bytes, size: int, block: int) -> str | None:
    """None if the PNG has the right shape, is finite and has no
    constant block (a NaN tile leaves the encoder as one flat colour);
    else what is wrong with it."""
    import numpy as np
    from PIL import Image

    try:
        pixels = np.asarray(Image.open(io.BytesIO(raw)).convert("RGB"))
    except Exception as exc:  # noqa: BLE001 - any decode error is the fault
        return f"not a readable image: {exc}"
    if pixels.shape != (size, size, 3):
        return f"shape {pixels.shape}, expected {(size, size, 3)}"
    as_float = pixels.astype(np.float32)
    if not np.isfinite(as_float).all():
        return "non-finite pixels"
    for y in range(0, size, block):
        for x in range(0, size, block):
            if as_float[y:y + block, x:x + block].std() == 0.0:
                return f"constant {block}px block at ({y},{x})"
    return None
