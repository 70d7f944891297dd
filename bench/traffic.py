"""The one traffic generator: arrival schedules and the client loops.

A mix (``traffic/<mix>.json``) is data: ``loop`` is ``open`` (independent
users on a schedule) or ``closed`` (``clients`` callers that each wait for
their answer), ``batch`` queries per request drawn from a ``pool`` of
seeded queries, and for an open loop the arrival law and its fixed rate
(``rate_per_s``, about 0.8x the knee that ``sweep.py`` finds for the
configuration it serves: a configuration with another knee takes a mix of
its own).

Every request is timed on the client's side with the host clock. An open
loop times a request from when it was due, so a stall also charges the
requests queued behind it, and records how late the generator ran. The
client threads mark their calls into the program with profiler spans
(``bench.submit``, ``bench.await``, ``bench.sleep``), so a trace can say
what the host was doing in a gap of the device.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, List, Optional

import numpy as np


@dataclasses.dataclass
class Request:
    """One request and what became of it (host perf_counter seconds)."""

    queries: np.ndarray  # [batch, dim] float32, the payload submitted
    due: float = 0.0  # when it was due (open loop) or sent (closed)
    sent: float = 0.0  # when submit was called
    done: Optional[float] = None  # when the client held the answer
    scores: Any = None
    ids: Any = None
    error: Optional[BaseException] = None

    @property
    def latency(self) -> float:
        return self.done - self.due


def open_schedule(mix: dict, rate: float, seconds: float):
    """Due times in [0, seconds) of an open loop: one fixed draw of the
    mix's arrival law (``gap_seed``), scaled to mean 1 / rate.

    Every seed offers the same arrivals, and the run's seed decides which
    queries arrive when. Near the knee the queueing of an arrival trace
    depends on the order of its gaps more than on anything the system
    does: at 40 requests/s on web-flat, seeds that only reordered one set
    of gaps read p95 latencies from 112 to 232 ms.
    """
    n = int(round(rate * seconds))
    if mix["arrival"] == "poisson":
        gaps = np.random.default_rng(mix["gap_seed"]).exponential(size=n)
    elif mix["arrival"] == "uniform":
        gaps = np.ones(n)
    else:
        raise ValueError(f"unknown arrival law {mix['arrival']!r}")
    gaps = gaps * (seconds / gaps.sum())
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def payload_rows(seed: int, n: int, batch: int, pool: int) -> np.ndarray:
    """[n, batch] pool rows of the requests a run sends, from the seed."""
    return np.random.default_rng([seed, 7]).integers(0, pool, (n, batch))


def _span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def _submit(router, req: Request):
    from repro.launch.serving import SearchRequest

    with _span("bench.submit"):
        req.sent = time.perf_counter()
        return router.submit(SearchRequest(queries=req.queries))


def _await(ticket, req: Request, timeout: float):
    try:
        with _span("bench.await"):
            res = ticket.search_result(timeout=timeout)
        req.done = time.perf_counter()
        req.scores, req.ids = res.scores, res.ids
    except Exception as e:  # a failed request, counted as such
        req.error = e


def run_open(router, pool: np.ndarray, mix: dict, rate: float,
             seconds: float, seed: int, *, grace: float = 60.0):
    """Offer the schedule; returns (requests, how late the generator ran
    in seconds at its worst, t0)."""
    due = open_schedule(mix, rate, seconds)
    rows = payload_rows(seed, len(due), mix["batch"], pool.shape[0])
    reqs = [Request(queries=pool[r]) for r in rows]
    tickets: List[Any] = [None] * len(reqs)
    sent = threading.Semaphore(0)
    late = [0.0]
    t0 = time.perf_counter() + 0.05

    def generate():
        for i, (req, d) in enumerate(zip(reqs, due)):
            req.due = t0 + d
            wait = req.due - time.perf_counter()
            if wait > 0:
                with _span("bench.sleep"):
                    time.sleep(wait)
            try:
                tickets[i] = _submit(router, req)
            except Exception as e:
                req.error = e
            late[0] = max(late[0], req.sent - req.due)
            sent.release()

    gen = threading.Thread(target=generate, name="bench-generate")
    gen.start()
    deadline = t0 + seconds + grace
    for i, req in enumerate(reqs):
        sent.acquire()
        if tickets[i] is not None:
            _await(tickets[i], req, max(0.0, deadline - time.perf_counter()))
    gen.join()
    return reqs, late[0], t0


def run_closed(router, pool: np.ndarray, mix: dict, seconds: float,
               seed: int, *, grace: float = 60.0):
    """``clients`` callers, each sending its next request when its last
    one is answered, until the window closes; returns (requests, 0, t0)."""
    clients = mix["clients"]
    per_client = [[] for _ in range(clients)]
    t0 = time.perf_counter() + 0.05
    t_end = t0 + seconds

    def client(c: int):
        rng = np.random.default_rng([seed, 11, c])
        while True:
            now = time.perf_counter()
            if now < t0:
                time.sleep(t0 - now)
            elif now >= t_end:
                return
            r = rng.integers(0, pool.shape[0], mix["batch"])
            req = Request(queries=pool[r])
            per_client[c].append(req)
            try:
                ticket = _submit(router, req)
            except Exception as e:
                req.error = e
                return
            req.due = req.sent
            _await(ticket, req, t_end + grace - time.perf_counter())

    threads = [threading.Thread(target=client, args=(c,),
                                name=f"bench-client-{c}")
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return [r for rs in per_client for r in rs], 0.0, t0
