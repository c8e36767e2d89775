"""Lease-aware shard router: jobs → masters, addresses → health.

Region mode runs M master shards (each with its own WAL + standby pair
and its own lease), and this module is the thin layer that decides,
for every job and every RPC, which address to talk to:

- ``ShardRing`` — consistent hashing with virtual nodes: a job id maps
  to one shard, the mapping is stable across processes (md5, not
  Python's salted ``hash``), and adding/removing a shard reshuffles
  only ~1/M of the keys;
- ``EndpointRotation`` — per-URL failure backoff + epoch tracking for
  one shard's address list (active first, standbys after). This
  replaces the worker client's old single rotation cursor: a dead or
  lagging address sits out an exponential backoff window while pulls
  continue against healthy addresses, and re-pointing prefers the
  address that last reported the highest fencing epoch (the promoted
  master, not a random next-in-list);
- ``ShardRouter`` — the map from job ids to shards plus the per-shard
  health/epoch view the ``/distributed/region`` route serves.

One shard's failover or brownout never stalls the others: rotation
state is per shard per address, and the ring never consults health —
placement of a job on a shard is a pure function of its id, so every
participant (workers, the soak harness, a restarted master) computes
the same answer without coordination.
"""

from __future__ import annotations

import hashlib
import time
from bisect import bisect_right
from typing import Any, Callable, Optional

from ..utils.constants import (
    ROUTER_BACKOFF_BASE_SECONDS,
    ROUTER_BACKOFF_CAP_SECONDS,
    SHARD_VNODES,
)
from ..utils.logging import log


class EndpointState:
    """One master address's health ledger."""

    __slots__ = ("url", "fails", "bursts", "backoff_until", "epoch", "last_ok")

    def __init__(self, url: str) -> None:
        self.url = url
        self.fails = 0          # consecutive failures while current
        self.bursts = 0         # threshold crossings (backoff exponent)
        self.backoff_until = 0.0
        self.epoch: Optional[int] = None  # highest epoch it reported
        self.last_ok = 0.0

    def as_dict(self, now: float) -> dict[str, Any]:
        return {
            "url": self.url,
            "fails": self.fails,
            "backoff_remaining_s": round(max(0.0, self.backoff_until - now), 3),
            "epoch": self.epoch,
        }


class EndpointRotation:
    """Per-URL backoff + epoch tracking over one address list.

    The contract the old global cursor provided is preserved —
    ``FAILOVER_AFTER_ERRORS`` consecutive failures against the current
    address re-point to another — but failure history is now per
    address: a re-pointed-away-from address carries an exponential
    backoff window (``ROUTER_BACKOFF_BASE_SECONDS`` · 2^bursts, capped at
    ``ROUTER_BACKOFF_CAP_SECONDS``: 0.5 s and 30 s) so rotation never lands back on a
    known-dead address while a healthy one exists, and any successful
    response resets that address's schedule. Selection prefers
    non-backed-off addresses reporting the highest fencing epoch (the
    freshest master); when everything is backing off it takes the
    address whose window expires soonest.
    """

    def __init__(
        self,
        urls: list[str],
        threshold: Optional[int] = None,
        backoff_base: Optional[float] = None,
        backoff_cap: Optional[float] = None,
        clock: Callable[[], float] = time.monotonic,
    ) -> None:
        self.urls = [str(u) for u in urls] or ["http://127.0.0.1:8188"]
        self._threshold = threshold
        self.backoff_base = (
            backoff_base if backoff_base is not None
            else ROUTER_BACKOFF_BASE_SECONDS
        )
        self.backoff_cap = (
            backoff_cap if backoff_cap is not None
            else ROUTER_BACKOFF_CAP_SECONDS
        )
        self.clock = clock
        self._states = {u: EndpointState(u) for u in self.urls}
        self._idx = 0

    @property
    def threshold(self) -> int:
        # resolved per call so tests can monkeypatch the constants module
        if self._threshold is not None:
            return max(1, self._threshold)
        from ..utils import constants

        return max(1, constants.FAILOVER_AFTER_ERRORS)

    @property
    def current(self) -> str:
        return self.urls[self._idx % len(self.urls)]

    @property
    def current_state(self) -> EndpointState:
        return self._states[self.current]

    def note_success(self) -> None:
        state = self.current_state
        state.fails = 0
        state.bursts = 0
        state.backoff_until = 0.0
        state.last_ok = self.clock()

    def learn_epoch(self, epoch: int) -> None:
        state = self.current_state
        if state.epoch is None or epoch > state.epoch:
            state.epoch = epoch

    def note_failure(self) -> bool:
        """One failure against the current address. Returns True when
        the threshold tripped and the rotation re-pointed (the caller
        logs/meters the failover); always False with one address."""
        state = self.current_state
        state.fails += 1
        if len(self.urls) < 2 or state.fails < self.threshold:
            return False
        now = self.clock()
        window = min(
            self.backoff_cap, self.backoff_base * (2.0 ** state.bursts)
        )
        state.bursts += 1
        state.fails = 0
        state.backoff_until = now + window
        self._idx = self.urls.index(self._select_next(now))
        return True

    def _select_next(self, now: float) -> str:
        """The re-point target: rotation order from the current
        address, healthy (not backing off) first, highest known epoch
        among the healthy; all-backing-off falls back to the earliest
        window expiry — never a hard stall."""
        start = self._idx % len(self.urls)
        order = [
            self.urls[(start + offset) % len(self.urls)]
            for offset in range(1, len(self.urls) + 1)
        ][:-1]  # every address except the current one
        healthy = [u for u in order if self._states[u].backoff_until <= now]
        if healthy:
            best = max(self._states[u].epoch or 0 for u in healthy)
            for url in healthy:
                if (self._states[url].epoch or 0) == best:
                    return url
        return min(order, key=lambda u: self._states[u].backoff_until)

    def snapshot(self) -> list[dict[str, Any]]:
        now = self.clock()
        out = []
        for url in self.urls:
            entry = self._states[url].as_dict(now)
            entry["current"] = url == self.current
            out.append(entry)
        return out


class ShardRing:
    """Consistent-hash ring: stable job→shard placement with bounded
    reshuffle on membership change. md5 keeps the mapping identical
    across processes and restarts (Python's ``hash`` is salted)."""

    def __init__(
        self, shards: list[str], vnodes: Optional[int] = None
    ) -> None:
        self.vnodes = max(1, vnodes if vnodes is not None else SHARD_VNODES)
        self._points: list[tuple[int, str]] = []
        self.shards: list[str] = []
        for shard in shards:
            self.add(shard)

    @staticmethod
    def _hash(key: str) -> int:
        return int.from_bytes(
            hashlib.md5(key.encode("utf-8")).digest()[:8], "big"
        )

    def add(self, shard: str) -> None:
        if shard in self.shards:
            return
        self.shards.append(shard)
        for v in range(self.vnodes):
            self._points.append((self._hash(f"{shard}#{v}"), shard))
        self._points.sort()

    def remove(self, shard: str) -> None:
        if shard not in self.shards:
            return
        self.shards.remove(shard)
        self._points = [(h, s) for h, s in self._points if s != shard]

    def shard_for(self, key: str) -> str:
        if not self._points:
            raise ValueError("shard ring is empty")
        h = self._hash(str(key))
        idx = bisect_right([p[0] for p in self._points], h)
        return self._points[idx % len(self._points)][1]


class ShardInfo:
    """One shard's addresses + rotation + lease view."""

    def __init__(self, name: str, urls: list[str]) -> None:
        self.name = name
        self.urls = list(urls)
        self.rotation = EndpointRotation(self.urls)
        self.epoch: Optional[int] = None  # highest fencing epoch seen

    def note_epoch(self, epoch) -> None:
        try:
            value = int(epoch)
        except (TypeError, ValueError):
            return
        if value > 0 and (self.epoch is None or value > self.epoch):
            self.epoch = value
            self.rotation.learn_epoch(value)

    def as_dict(self) -> dict[str, Any]:
        return {
            "name": self.name,
            "urls": list(self.urls),
            "epoch": self.epoch,
            "endpoints": self.rotation.snapshot(),
        }


class ShardRouter:
    """job id → shard → address list, with the per-shard epoch/health
    view the region routes serve. Construction from the CDT_SHARDS
    spec (shards ';'-separated, each a comma list) or an explicit
    ``{name: [urls]}`` map; an empty spec is the unsharded topology
    (``enabled`` False, every job routes to the single master)."""

    def __init__(
        self,
        shard_map: Optional[dict[str, list[str]]] = None,
        vnodes: Optional[int] = None,
    ) -> None:
        self.shards: dict[str, ShardInfo] = {
            name: ShardInfo(name, urls)
            for name, urls in (shard_map or {}).items()
        }
        self.ring = ShardRing(sorted(self.shards), vnodes=vnodes)

    @classmethod
    def from_spec(
        cls, spec: str, vnodes: Optional[int] = None
    ) -> "ShardRouter":
        from ..utils.network import parse_master_urls

        shard_map: dict[str, list[str]] = {}
        for i, group in enumerate(g for g in spec.split(";") if g.strip()):
            urls = parse_master_urls(group)
            if urls:
                shard_map[f"shard{i}"] = urls
        return cls(shard_map, vnodes=vnodes)

    @classmethod
    def from_env(cls) -> "ShardRouter":
        # resolved per call so tests (and workers spawned with a
        # different CDT_SHARDS) see the current knob, not import-time
        from ..utils import constants

        return cls.from_spec(constants.SHARDS_SPEC)

    @property
    def enabled(self) -> bool:
        return bool(self.shards)

    def shard_for(self, job_id: str) -> str:
        return self.ring.shard_for(job_id)

    def route(self, job_id: str) -> ShardInfo:
        return self.shards[self.shard_for(job_id)]

    def addresses_for(self, job_id: str) -> str:
        """The comma list the worker client consumes for this job —
        the multiplexing seam: each of a worker's jobs pulls from its
        own shard's addresses, so one shard's outage backs off only
        that shard's endpoints."""
        return ",".join(self.route(job_id).urls)

    def client_for(self, job_id: str, worker_id: str, devices: int = 1):
        """An HTTPWorkClient bound to the job's shard."""
        from ..graph.usdu_elastic import HTTPWorkClient

        return HTTPWorkClient(
            self.addresses_for(job_id), job_id, worker_id, devices=devices
        )

    def note_epoch(self, shard_name: str, epoch) -> None:
        info = self.shards.get(shard_name)
        if info is not None:
            info.note_epoch(epoch)

    def rebalance(self, name: str, urls: Optional[list[str]]) -> None:
        """Add (urls given) or remove (None) one shard. Logged: a
        membership change reshuffles ~1/M of the job space."""
        if urls is None:
            self.shards.pop(name, None)
            self.ring.remove(name)
            log(f"shard router: removed shard {name}")
            return
        self.shards[name] = ShardInfo(name, urls)
        self.ring.add(name)
        log(f"shard router: added shard {name} -> {urls}")

    def status(self) -> dict[str, Any]:
        return {
            "enabled": self.enabled,
            "vnodes": self.ring.vnodes,
            "shards": {
                name: info.as_dict()
                for name, info in sorted(self.shards.items())
            },
        }


__all__ = [
    "EndpointRotation",
    "EndpointState",
    "ShardInfo",
    "ShardRing",
    "ShardRouter",
]
