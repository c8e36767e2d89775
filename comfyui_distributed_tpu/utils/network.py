"""HTTP transport utilities for the elastic (cross-host / DCN) tier.

Inside a pod slice, participants communicate via ICI collectives (see
parallel/); this module is the control plane and the transport for
remote participants. Behavior parity with reference utils/network.py:
one shared pooled ClientSession, host normalization, scheme-aware
worker/master URL builders (cloud hosts get https), and a `/prompt`
probe whose `queue_remaining` doubles as the busy-ness metric.
"""

from __future__ import annotations

import asyncio
import ipaddress
import threading
from typing import Any

import aiohttp

from .constants import (
    CONNECTION_POOL_LIMIT,
    CONNECTION_POOL_PER_HOST,
    DEFAULT_MASTER_PORT,
    PROBE_TIMEOUT_SECONDS,
)
from .logging import debug_log

def parse_master_urls(raw) -> list[str]:
    """One URL or a comma-separated failover list ('active,standby').
    Shared by the worker client (rotates on consecutive failures,
    FAILOVER_AFTER_ERRORS) and the standby controller (rotates its
    replication stream) so both sides agree on list semantics."""
    if isinstance(raw, str):
        urls = [u.strip().rstrip("/") for u in raw.split(",")]
    else:
        urls = [str(u).strip().rstrip("/") for u in raw]
    return [u for u in urls if u]


# One pooled session per event loop (the server loop keeps one long-lived
# session; transient asyncio.run loops get their own and must close it
# via close_client_session before the loop dies).
_sessions: dict[asyncio.AbstractEventLoop, aiohttp.ClientSession] = {}
_sessions_lock = threading.Lock()


async def get_client_session() -> aiohttp.ClientSession:
    """Shared pooled session for the current event loop.

    Under an active fault plan (CDT_FAULT_PLAN / an installed
    injector) the session is wrapped so chaos tests can inject
    connection errors, 5xx responses, and latency spikes at the
    transport without touching call sites."""
    loop = asyncio.get_running_loop()
    with _sessions_lock:
        session = _sessions.get(loop)
        if session is None or session.closed:
            connector = aiohttp.TCPConnector(
                limit=CONNECTION_POOL_LIMIT, limit_per_host=CONNECTION_POOL_PER_HOST
            )
            session = aiohttp.ClientSession(connector=connector)
            _sessions[loop] = session
            # Drop map entries for loops that are gone so the dict stays
            # bounded; run_async_in_server_loop's fallback closes transient
            # loops' sessions before their loop exits.
            for stale in [l for l in _sessions if l.is_closed()]:
                _sessions.pop(stale)
    from ..resilience.faults import get_fault_injector

    injector = get_fault_injector()
    if injector is not None:
        return FaultingClientSession(session, injector)
    return session


# --- fault-injecting transport wrapper ------------------------------------

class _InjectedResponse:
    """Minimal stand-in for an aiohttp response (injected http500/drop)."""

    def __init__(self, status: int, url: str):
        self.status = status
        self.url = url

    async def json(self) -> dict:
        return {}

    async def text(self) -> str:
        return f"injected fault response ({self.status}) for {self.url}"

    def release(self) -> None:
        pass


class _FaultingRequestContext:
    """Async context manager around one request; consults the injector
    with op `http:<METHOD>:<path>` before touching the network."""

    def __init__(self, session, injector, method: str, url: str, kwargs: dict):
        self._session = session
        self._injector = injector
        self._method = method
        self._url = url
        self._kwargs = kwargs
        self._ctx = None

    async def __aenter__(self):
        from urllib.parse import urlsplit

        path = urlsplit(str(self._url)).path or "/"
        action = self._injector.hit(f"http:{self._method}:{path}")
        if action is not None:
            if action.kind == "latency":
                await asyncio.sleep(action.arg or 0.0)
            elif action.kind in ("connect_error", "crash"):
                raise aiohttp.ClientConnectionError(
                    f"injected {action.kind} at {path}"
                )
            elif action.kind == "http500":
                return _InjectedResponse(500, str(self._url))
            elif action.kind == "drop":
                # Swallowed server-side: caller sees a generic OK with
                # an empty body; the operation never happens.
                return _InjectedResponse(200, str(self._url))
        self._ctx = getattr(self._session, self._method.lower())(
            self._url, **self._kwargs
        )
        return await self._ctx.__aenter__()

    async def __aexit__(self, *exc_info):
        if self._ctx is not None:
            return await self._ctx.__aexit__(*exc_info)
        return False


class FaultingClientSession:
    """Transparent proxy over the pooled ClientSession; GET/POST go
    through the fault injector, everything else delegates."""

    def __init__(self, session: aiohttp.ClientSession, injector):
        self._session = session
        self._injector = injector

    def get(self, url, **kwargs):
        return _FaultingRequestContext(
            self._session, self._injector, "GET", url, kwargs
        )

    def post(self, url, **kwargs):
        return _FaultingRequestContext(
            self._session, self._injector, "POST", url, kwargs
        )

    def __getattr__(self, name):
        return getattr(self._session, name)


async def close_client_session() -> None:
    """Close the current loop's session (call before a transient loop exits)."""
    loop = asyncio.get_running_loop()
    with _sessions_lock:
        session = _sessions.pop(loop, None)
    if session is not None and not session.closed:
        await session.close()


def handle_api_error(context: str, exc: Exception) -> str:
    message = f"{context}: {type(exc).__name__}: {exc}"
    debug_log(message)
    return message


# --- host / URL handling -------------------------------------------------

def normalize_host(host: str) -> str:
    """Strip scheme/trailing slash; keep bare host[:port] or hostname."""
    host = (host or "").strip()
    for scheme in ("https://", "http://"):
        if host.startswith(scheme):
            host = host[len(scheme):]
    return host.rstrip("/")


def split_host_port(host: str, default_port: int | None = None) -> tuple[str, int | None]:
    host = normalize_host(host)
    if host.startswith("["):  # [ipv6]:port
        bracket_end = host.find("]")
        if bracket_end != -1:
            addr = host[1:bracket_end]
            rest = host[bracket_end + 1:]
            if rest.startswith(":"):
                try:
                    return addr, int(rest[1:])
                except ValueError:
                    return addr, default_port
            return addr, default_port
    if host.count(":") == 1:
        name, _, port_s = host.partition(":")
        try:
            return name, int(port_s)
        except ValueError:
            return name, default_port
    return host, default_port


def is_private_host(host: str) -> bool:
    name, _ = split_host_port(host)
    if name in ("localhost", ""):
        return True
    try:
        return ipaddress.ip_address(name).is_private
    except ValueError:
        return False


_LOOPBACK_HOSTS = {"", "localhost", "127.0.0.1", "::1", "0.0.0.0"}


def is_loopback_host(host: str) -> bool:
    """True only for this-machine addresses (NOT arbitrary private LAN
    IPs — a 192.168.x worker is a different box and must call back to
    the master's real address)."""
    name, _ = split_host_port(host)
    return name in _LOOPBACK_HOSTS


def _fmt_host(name: str) -> str:
    """Re-bracket bare IPv6 addresses for URL assembly."""
    return f"[{name}]" if ":" in name and not name.startswith("[") else name


def _wants_https(host: str, port: int | None, worker_type: str) -> bool:
    if worker_type in ("cloud", "remote_https"):
        return True
    if port == 443:
        return True
    name, _ = split_host_port(host)
    if name.endswith(".trycloudflare.com") or ".proxy.runpod.net" in name:
        return True
    return False


def build_worker_url(worker: dict[str, Any], path: str = "") -> str:
    """URL for reaching a worker described by a config entry.

    https for cloud/tunnel/port-443 hosts, http otherwise
    (reference utils/network.py:88-105).
    """
    host = normalize_host(str(worker.get("host") or "localhost"))
    worker_type = str(worker.get("type", "local"))
    name, embedded_port = split_host_port(host)
    explicit_port = embedded_port or worker.get("port") or 0
    https = _wants_https(host, explicit_port or None, worker_type)
    scheme = "https" if https else "http"
    if https and explicit_port in (443, 0):
        base = f"{scheme}://{_fmt_host(name)}"
    else:
        base = f"{scheme}://{_fmt_host(name)}:{explicit_port or DEFAULT_MASTER_PORT}"
    return f"{base}{path}" if path.startswith("/") or not path else f"{base}/{path}"


def build_master_url(master_host: str, master_port: int, path: str = "") -> str:
    host = normalize_host(master_host) or "127.0.0.1"
    name, embedded_port = split_host_port(host)
    port = embedded_port or master_port
    https = _wants_https(host, port, "remote")
    scheme = "https" if https else "http"
    if https and port in (443, 0):
        base = f"{scheme}://{_fmt_host(name)}"
    else:
        base = f"{scheme}://{_fmt_host(name)}:{port}"
    return f"{base}{path}"


def build_master_callback_url(
    worker: dict[str, Any], master_host: str, master_port: int, path: str = ""
) -> str:
    """URL a worker should use to call back to the master.

    Same-machine workers (type local/mesh, or loopback hosts) always
    call back over loopback regardless of the advertised master host
    (reference utils/network.py:139-201) — the advertised host may be
    a tunnel or external IP unreachable from the same box. Workers on
    other machines (including private LAN IPs) get the real master URL.
    """
    if worker.get("type") in ("local", "mesh") or is_loopback_host(
        str(worker.get("host", ""))
    ):
        return f"http://127.0.0.1:{master_port}{path}"
    return build_master_url(master_host, master_port, path)


# --- probing -------------------------------------------------------------

async def probe_worker(
    url_base: str, timeout: float = PROBE_TIMEOUT_SECONDS
) -> dict[str, Any]:
    """GET {worker}/prompt; returns {"online", "queue_remaining"}.

    `queue_remaining` doubles as the busy-ness metric for least-busy
    selection and busy-probe grace on timeouts.
    """
    session = await get_client_session()
    try:
        async with session.get(
            f"{url_base}/prompt", timeout=aiohttp.ClientTimeout(total=timeout)
        ) as resp:
            if resp.status != 200:
                return {"online": False, "queue_remaining": None}
            data = await resp.json()
            remaining = (
                data.get("exec_info", {}).get("queue_remaining")
                if isinstance(data, dict)
                else None
            )
            if remaining is None:
                return {"online": False, "queue_remaining": None}
            return {"online": True, "queue_remaining": int(remaining)}
    except (aiohttp.ClientError, asyncio.TimeoutError, OSError, ValueError) as exc:
        handle_api_error(f"probe {url_base}", exc)
        return {"online": False, "queue_remaining": None}
