"""The one traffic generator. A mix is a data file under traffic/:

closed loop   {"loop": "closed", "clients": N}
              N clients, each sending its next request when its last
              one is done; none sends after `seconds`.
open loop     {"loop": "open", "schedule_seed": S, "rate_per_s": R,
               "burst_mean": M, "burst_cap": C, "burst_gap_s": G}
              bursts start as a Poisson process, sizes are geometric
              with mean M capped at C, requests of a burst are G apart,
              and the burst rate is chosen so that requests arrive at R
              a second. Drawn from `schedule_seed` alone: every run of
              the cell sends at the same times, and `--seed` changes
              only what is asked for. Requests due after `seconds` are
              not sent.

`send(index, due)` is the caller's: it posts one request, waits until
it is done and returns its record. It is called from a thread of its
own, because the queue route answers only once the scheduler grants.
"""

from __future__ import annotations

import threading
import time

import numpy as np


def capped_geometric_mean(mean: float, cap: int) -> float:
    """E[min(G, cap)] for G geometric on 1, 2, ... with mean `mean`."""
    q = 1.0 - 1.0 / mean
    return sum(q ** k for k in range(cap))


def schedule(mix: dict, seconds: float) -> list[float]:
    """Due times, in seconds from the window's start, of an open loop."""
    rng = np.random.default_rng(int(mix["schedule_seed"]))
    mean, cap = float(mix["burst_mean"]), int(mix["burst_cap"])
    burst_rate = float(mix["rate_per_s"]) / capped_geometric_mean(mean, cap)
    due, at = [], 0.0
    while True:
        at += float(rng.exponential(1.0 / burst_rate))
        size = min(int(rng.geometric(1.0 / mean)), cap)
        if at >= seconds:
            break
        due += [at + i * float(mix["burst_gap_s"]) for i in range(size)]
    return sorted(t for t in due if t < seconds)


def run(mix: dict, seconds: float, send) -> dict:
    """Offer the mix for `seconds`, wait for every request sent, and
    return {"start": monotonic, "records": [...], "worst_lateness_s"}."""
    records: list[dict] = []
    lock = threading.Lock()
    errors: list[BaseException] = []
    worst_lateness = 0.0

    def one(index: int, due: float) -> None:
        try:
            record = send(index, due)
        except BaseException as exc:  # noqa: BLE001 - re-raised by run()
            with lock:
                errors.append(exc)
            return
        with lock:
            records.append(record)

    start = time.monotonic()
    threads: list[threading.Thread] = []
    if mix["loop"] == "closed":
        counter = iter(range(10 ** 9))

        def client() -> None:
            while time.monotonic() - start < seconds and not errors:
                with lock:
                    index = next(counter)
                one(index, time.monotonic())

        threads = [threading.Thread(target=client) for _ in range(int(mix["clients"]))]
        for thread in threads:
            thread.start()
    elif mix["loop"] == "open":
        for index, offset in enumerate(schedule(mix, seconds)):
            due = start + offset
            wait = due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            worst_lateness = max(worst_lateness, time.monotonic() - due)
            thread = threading.Thread(target=one, args=(index, due))
            thread.start()
            threads.append(thread)
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    for thread in threads:
        thread.join()
    if errors:
        raise errors[0]
    records.sort(key=lambda r: r["index"])
    return {"start": start, "records": records, "worst_lateness_s": worst_lateness}
