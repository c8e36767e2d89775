"""Unified retry/backoff policy for every cross-host RPC.

One `RetryPolicy` (exponential backoff + jitter + an overall deadline)
and one `retry_async` helper replace the hand-rolled retry loops that
used to live in `graph/usdu_elastic.py` (job-ready poll, work pull),
`api/orchestration/dispatch.py`, and `api/orchestration/media_sync.py`.

Design points:

- policies are values (frozen dataclasses) so call sites can derive
  variants with `dataclasses.replace` / `with_deadline`;
- the deadline is a wall-clock budget for the WHOLE retry sequence —
  a retry whose backoff would overshoot the budget is not attempted,
  so caller-level timeouts compose instead of stacking;
- jitter is multiplicative (+-`jitter` fraction) and draws from an
  injectable `random.Random`, which keeps fault-injection runs
  deterministic under a fixed seed;
- `retry_async` re-raises the LAST failure on exhaustion, so callers
  keep their existing exception taxonomy (`WorkerError`,
  `aiohttp.ClientError`, ...) instead of learning a new wrapper type.

The default attempt counts / backoff bases are the constants of
utils/constants.py (`REQUEST_RETRY_COUNT`, `REQUEST_RETRY_BACKOFF`,
`WORK_PULL_RETRY_COUNT`, `WORK_PULL_RETRY_CAP_SECONDS`,
`JOB_READY_POLL_ATTEMPTS`, `JOB_READY_POLL_INTERVAL`).
"""

from __future__ import annotations

import asyncio
import dataclasses
import random
import time
from typing import Any, Awaitable, Callable, Optional, Tuple, Type

from ..utils import constants
from ..utils.logging import debug_log

# Shared jitter source for call sites that don't inject their own.
_default_rng = random.Random()


def transport_errors() -> Tuple[Type[BaseException], ...]:
    """Failures where the request may never have arrived — the only
    class worth retrying for non-idempotent sends and the only class
    the circuit breaker counts. One definition so dispatch and media
    sync can't drift apart."""
    import aiohttp

    return (aiohttp.ClientConnectionError, asyncio.TimeoutError, OSError)


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Exponential backoff schedule with jitter and an overall deadline."""

    max_attempts: int = 5
    base_delay: float = 0.5
    multiplier: float = 2.0
    max_delay: float = 30.0
    jitter: float = 0.1          # +- fraction of the computed delay
    deadline: Optional[float] = None  # wall-clock budget for all attempts

    def delay_for(self, attempt: int, rng: random.Random | None = None) -> float:
        """Backoff to sleep after failed attempt `attempt` (0-based)."""
        raw = min(self.base_delay * (self.multiplier ** attempt), self.max_delay)
        if self.jitter > 0:
            raw *= 1.0 + (rng or _default_rng).uniform(-self.jitter, self.jitter)
        return max(0.0, raw)

    def with_deadline(self, deadline: float | None) -> "RetryPolicy":
        return dataclasses.replace(self, deadline=deadline)


async def retry_async(
    fn: Callable[[], Awaitable[Any]],
    policy: RetryPolicy,
    *,
    retryable: Tuple[Type[BaseException], ...] = (Exception,),
    label: str = "",
    on_retry: Callable[[int, BaseException, float], None] | None = None,
    sleep: Callable[[float], Awaitable[None]] = asyncio.sleep,
    rng: random.Random | None = None,
    clock: Callable[[], float] = time.monotonic,
) -> Any:
    """Await `fn()` under `policy`; re-raise the last failure when the
    attempt budget or the deadline is exhausted.

    Exceptions not matching `retryable` propagate immediately — use it
    to separate transport failures (retry) from semantic rejections
    (don't re-send a prompt a worker refused).
    """
    start = clock()
    last: BaseException | None = None
    attempts = max(1, policy.max_attempts)
    for attempt in range(attempts):
        try:
            return await fn()
        except retryable as exc:  # noqa: PERF203 - retry loop by design
            last = exc
            if attempt + 1 >= attempts:
                break
            delay = policy.delay_for(attempt, rng)
            if (
                policy.deadline is not None
                and clock() - start + delay > policy.deadline
            ):
                debug_log(
                    f"retry[{label}]: deadline {policy.deadline}s exhausted "
                    f"after {attempt + 1} attempt(s)"
                )
                break
            # Retry visibility: one counter labelled by the operation
            # part of the label ("dispatch:w1" → op="dispatch"), so
            # dashboards see retry pressure without per-target series.
            from ..telemetry import instruments

            instruments.retries_total().inc(
                op=label.split(":", 1)[0] if label else "unlabeled"
            )
            if on_retry is not None:
                on_retry(attempt, exc, delay)
            debug_log(
                f"retry[{label}]: attempt {attempt + 1}/{attempts} failed "
                f"({type(exc).__name__}: {exc}); backing off {delay:.2f}s"
            )
            await sleep(delay)
    assert last is not None
    raise last


# --- canonical policies ---------------------------------------------------
# Factories (not module constants) so tests can monkeypatch
# utils.constants and get fresh values, matching the old loops which
# read the constants at call time.

def http_policy(deadline: float | None = None) -> RetryPolicy:
    """General request retry: short exponential backoff, 30 s cap."""
    return RetryPolicy(
        max_attempts=constants.REQUEST_RETRY_COUNT,
        base_delay=constants.REQUEST_RETRY_BACKOFF,
        multiplier=2.0,
        max_delay=30.0,
        jitter=0.1,
        deadline=deadline,
    )


def work_pull_policy() -> RetryPolicy:
    """Worker->master tile pull: patient (x10, capped) — losing the
    pull loop strands the whole worker for the job."""
    return RetryPolicy(
        max_attempts=constants.WORK_PULL_RETRY_COUNT,
        base_delay=constants.REQUEST_RETRY_BACKOFF,
        multiplier=2.0,
        max_delay=constants.WORK_PULL_RETRY_CAP_SECONDS,
        jitter=0.1,
    )


def poll_ready_policy() -> RetryPolicy:
    """Job-ready poll: fixed interval (multiplier 1, no jitter), the
    reference's N x 1 s readiness probe."""
    return RetryPolicy(
        max_attempts=constants.JOB_READY_POLL_ATTEMPTS,
        base_delay=constants.JOB_READY_POLL_INTERVAL,
        multiplier=1.0,
        max_delay=constants.JOB_READY_POLL_INTERVAL,
        jitter=0.0,
    )
