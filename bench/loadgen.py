"""Load for the serving front: one generator loop on the calling thread,
one waiter thread per replica.

Open loop: requests are due on a schedule fixed before the window and are
submitted when due, whatever the system does; each is timed from its due
time, so a stall is charged to every request it delays.  Closed loop: a
fixed number of requests is outstanding; each answer releases the next
submission.

The waiters stamp each answer as its ticket resolves.  A replica serves its
queue earliest-deadline-first and every request carries the same SLO, so a
replica answers in submission order and its waiter can wait on its tickets
in that order without stamping one late.
"""
from __future__ import annotations

import queue
import threading
import time
from collections import deque
from dataclasses import dataclass

import numpy as np

import jax

_BASE_SEED = 0  # the one stream every run's arrival gaps are drawn from


def percentiles_ms(latencies_s) -> dict:
    """p50/p95 (ms) over per-request latencies (s): linear interpolation
    between order statistics, as `repro.router.metrics.percentiles_ms`
    computes them, without its rounding."""
    a = np.asarray(list(latencies_s), np.float64) * 1e3
    if a.size == 0:
        return {"count": 0, "p50_ms": None, "p95_ms": None}
    return {"count": int(a.size), "p50_ms": float(np.percentile(a, 50)),
            "p95_ms": float(np.percentile(a, 95))}


def arrival_offsets(traffic: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times (s from the window's start) of an open-loop window:
    Poisson arrivals at `rate_qps`.  The exponential gaps are drawn from one
    fixed stream, as `benchmarks/fig14_serving.py:_poisson_schedule` draws
    them, and the run's seed only shuffles them: every seed offers the same
    number of requests, in another order."""
    rate = float(traffic["rate_qps"])
    base = np.random.default_rng(_BASE_SEED)
    t = np.cumsum(base.exponential(1.0 / rate,
                                   size=int(rate * seconds * 2 + 64)))
    gaps = np.diff(t[: np.searchsorted(t, seconds)], prepend=0.0)
    return np.cumsum(np.random.default_rng(seed).permutation(gaps))


def query_picks(pool: int, count: int, seed: int) -> np.ndarray:
    """Pool rows for the run's requests, uniform over the pool."""
    return np.random.default_rng(seed).integers(0, pool, size=count)


@dataclass
class Answer:
    qi: int                 # pool row of the query
    t_due: float            # perf_counter seconds
    t_submit: float
    t_done: float = float("nan")
    ids: np.ndarray | None = None
    dists: np.ndarray | None = None
    error: str | None = None


class Client:
    """Submits requests to a `Router` and collects every answer."""

    def __init__(self, router, vectors: np.ndarray, picks: np.ndarray, *,
                 wait_s: float = 60.0):
        self.router = router
        self.vectors = vectors
        self.picks = picks
        self.wait_s = wait_s
        self.answers: list[Answer] = []
        self.done: queue.Queue = queue.Queue()  # answers, for the closed loop
        self._next = 0
        self._lanes: dict[str, tuple[deque, threading.Condition]] = {}
        self._threads: list[threading.Thread] = []
        self._closed = False
        self._deadline: float | None = None  # set by finish()
        for rep in router.replicas:
            lane = (deque(), threading.Condition())
            self._lanes[rep.name] = lane
            t = threading.Thread(target=self._wait, args=lane,
                                 name=f"bench-wait-{rep.name}", daemon=True)
            t.start()
            self._threads.append(t)

    def submit(self, t_due: float) -> None:
        qi = int(self.picks[self._next % len(self.picks)])
        self._next += 1
        with jax.profiler.TraceAnnotation("bench.submit"):
            t_submit = time.perf_counter()
            ans = Answer(qi, t_due, t_submit)
            self.answers.append(ans)
            try:
                ticket = self.router.submit(self.vectors[qi])
            except Exception as exc:  # refused (QueueFull) or shut down
                ans.t_done, ans.error = time.perf_counter(), repr(exc)
                self.done.put(ans)
                return
        lane, cv = self._lanes[ticket.replica]
        with cv:
            lane.append((ans, ticket))
            cv.notify()

    def _wait(self, lane: deque, cv: threading.Condition) -> None:
        while True:
            with cv:
                while not lane and not self._closed:
                    cv.wait()
                if not lane:
                    return
                ans, ticket = lane.popleft()
            dl = self._deadline
            timeout = (self.wait_s if dl is None
                       else max(dl - time.perf_counter(), 0.0))
            try:
                ids, dists = ticket.result(timeout=timeout)
                ans.t_done = time.perf_counter()
                ans.ids, ans.dists = np.asarray(ids), np.asarray(dists)
            except Exception as exc:  # a serving failure, or never answered
                ans.t_done, ans.error = time.perf_counter(), repr(exc)
            self.done.put(ans)

    def run_open(self, t0: float, offsets: np.ndarray) -> None:
        for off in offsets:
            t_due = t0 + float(off)
            delay = t_due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            self.submit(t_due)

    def run_closed(self, t0: float, t_end: float, clients: int) -> None:
        for _ in range(clients):
            self.submit(t0)
        while True:
            left = t_end - time.perf_counter()
            if left <= 0:
                return
            try:
                self.done.get(timeout=left)
            except queue.Empty:
                return
            now = time.perf_counter()
            if now < t_end:
                self.submit(now)

    def finish(self) -> None:
        """Wait for every outstanding answer, for at most `wait_s` from
        now, then stop the waiters."""
        self._deadline = time.perf_counter() + self.wait_s
        for _, cv in self._lanes.values():
            with cv:
                self._closed = True
                cv.notify_all()
        for t in self._threads:
            t.join()
