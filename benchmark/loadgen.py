"""One general load generator, driven by a traffic file's parameters.

``loop: "closed"`` runs ``clients`` threads; each sends its next request
only after its previous one completed, and takes ``rows_per_request``
query rows, in order, from the seeded pool.  Every seed sends the same
pool in its own order, so runs differ in order and not in load.

Each request is timed from its send to when its result reached the
client; a request the server refused or failed is marked failed.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Outcome:
    """One request: its pool rows, when it was sent and done (host
    seconds), and its answer, or the error that failed it."""

    pool_idx: np.ndarray
    t_sent: float = 0.0
    t_done: float = 0.0
    dist: Optional[np.ndarray] = None
    ids: Optional[np.ndarray] = None
    error: Optional[str] = None

    @property
    def rows(self) -> int:
        return int(len(self.pool_idx))

    @property
    def done(self) -> bool:
        return self.t_done > 0.0


@dataclasses.dataclass
class Window:
    """What one measured window produced."""

    t0: float
    t1: float
    outcomes: List[Outcome]


def _resolve(out: Outcome, fut) -> None:
    out.t_done = time.monotonic()
    exc = fut.exception()
    if exc is not None:
        out.error = type(exc).__name__
    else:
        out.dist, out.ids = fut.result()


def _submit(server, pool, out: Outcome, deadline_ms: float):
    out.t_sent = time.monotonic()
    try:
        return server.submit(pool[out.pool_idx], deadline_ms=deadline_ms)
    except Exception as exc:  # noqa: BLE001 — a refusal is a failed request
        out.t_done = out.t_sent
        out.error = type(exc).__name__
        return None


def pool_order(n_pool: int, seed: int) -> np.ndarray:
    """The seeded order in which requests take rows from the pool."""
    return np.random.default_rng(seed).permutation(n_pool)


def closed_loop(server, pool, order, traffic: dict, seconds: float,
                settle_s: float = 60.0) -> Window:
    rows = int(traffic["rows_per_request"])
    deadline_ms = float(traffic["deadline_ms"])
    lock = threading.Lock()
    cursor = [0]
    outcomes: List[Outcome] = []

    def take():
        with lock:
            lo = cursor[0]
            cursor[0] += rows
        return order[np.arange(lo, lo + rows) % len(order)]

    def client():
        while True:
            now = time.monotonic()
            if now >= t1:
                return
            out = Outcome(take())
            with lock:
                outcomes.append(out)
            fut = _submit(server, pool, out, deadline_ms)
            if fut is None:
                continue
            try:
                fut.result(timeout=max(t1 - now, 0.0) + settle_s)
            except Exception:  # noqa: BLE001 — recorded by _resolve
                pass
            if fut.done():
                _resolve(out, fut)
            else:
                return

    threads = [threading.Thread(target=client, name=f"bench-client-{c}")
               for c in range(int(traffic["clients"]))]
    t0 = time.monotonic()
    t1 = t0 + seconds
    for t in threads:
        t.start()
    for t in threads:
        t.join(seconds + settle_s + 5.0)
    return Window(t0, t1, outcomes)
