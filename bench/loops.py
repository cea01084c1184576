"""Load generation: the closed and the open loop, and their arithmetic.

Every time is in seconds from the window's start, on
``time.perf_counter``.  A request's latency runs from when it was due to
when ``result()`` handed its answer to the client thread; in the closed
loop a request is due when its client sends it.
"""

from __future__ import annotations

import dataclasses
import math
import queue
import threading
import time
from typing import Callable, Optional

import numpy as np

OK, REFUSED, TIMEOUT, ERROR = "ok", "refused", "timeout", "error"


@dataclasses.dataclass
class Outcome:
    item: object               # what the deployment submitted
    due_s: float
    sent_s: float = math.nan   # when the client called the submit entry
    done_s: float = math.nan   # when result() returned
    status: str = TIMEOUT
    output: Optional[np.ndarray] = None

    @property
    def latency_s(self) -> float:
        return self.done_s - self.due_s


def poisson_schedule(rate_per_s: float, window_s: float,
                     r: np.random.Generator) -> list:
    """Arrival times in [0, window_s) with seeded exponential gaps.

    Copied from ``_poisson_schedule`` in
    ``benchmarks/serving_throughput.py`` (arrivals only).
    """
    times, t = [], 0.0
    while True:
        t += float(r.exponential(1.0 / rate_per_s))
        if t >= window_s:
            return times
        times.append(t)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of
    the values at or below it."""
    data = sorted(values)
    if not data:
        raise ValueError("percentile of no values")
    rank = max(1, math.ceil(q / 100.0 * len(data)))
    return float(data[rank - 1])


def latencies_s(outcomes, give_up_s: float) -> list:
    """Latency of every request: a request never answered counts as
    waiting until the run gave up on it."""
    return [o.latency_s if o.status == OK else give_up_s - o.due_s
            for o in outcomes]


def lags_s(outcomes) -> list:
    """How late the generator sent each request it sent."""
    return [o.sent_s - o.due_s for o in outcomes if not math.isnan(o.sent_s)]


def _collect(server, outcome: Outcome, rid, clock, give_up_s: float) -> None:
    try:
        out = server.result(rid, timeout=max(give_up_s - clock(), 1e-3))
    except TimeoutError:
        outcome.status = TIMEOUT
        return
    except (KeyError, RuntimeError):
        outcome.status = ERROR
        return
    outcome.done_s = clock()
    outcome.output = out
    outcome.status = OK


def closed_loop(server, submit: Callable, items: list, clients: int,
                window_s: float, drain_s: float, t0: float) -> list:
    """``clients`` threads, each sending its next request when its last
    answer came back, until the window closes."""
    clock = lambda: time.perf_counter() - t0  # noqa: E731
    outcomes: list = []
    lock = threading.Lock()
    give_up = window_s + drain_s

    def client():
        while True:
            with lock:
                now = clock()
                if now >= window_s:
                    return
                o = Outcome(item=items[len(outcomes) % len(items)], due_s=now)
                outcomes.append(o)
            o.sent_s = o.due_s
            rid = submit(o.item)
            if rid is None:
                o.status = REFUSED
                continue
            _collect(server, o, rid, clock, give_up)

    _run_threads([threading.Thread(target=client, name=f"bench-client-{i}")
                  for i in range(clients)])
    return outcomes


def open_loop(server, submit: Callable, schedule: list, clients: int,
              drain_s: float, window_s: float, t0: float) -> list:
    """Send each request of ``schedule`` ([(due_s, item)]) when it is due,
    from ``clients`` threads in turn, whatever the server's state; as many
    threads again wait for the answers."""
    clock = lambda: time.perf_counter() - t0  # noqa: E731
    outcomes = [Outcome(item=item, due_s=due) for due, item in schedule]
    pending: "queue.Queue" = queue.Queue()
    give_up = window_s + drain_s

    def sender(k: int):
        for o in outcomes[k::clients]:
            wait = o.due_s - clock()
            if wait > 0:
                time.sleep(wait)
            o.sent_s = clock()
            rid = submit(o.item)
            if rid is None:
                o.status = REFUSED
            else:
                pending.put((o, rid))

    def collector():
        while True:
            job = pending.get()
            if job is None:
                return
            _collect(server, job[0], job[1], clock, give_up)

    senders = [threading.Thread(target=sender, args=(k,),
                                name=f"bench-send-{k}")
               for k in range(clients)]
    collectors = [threading.Thread(target=collector, name=f"bench-wait-{k}")
                  for k in range(clients)]
    for t in collectors:
        t.start()
    _run_threads(senders)
    for _ in collectors:
        pending.put(None)
    for t in collectors:
        t.join()
    return outcomes


def _run_threads(threads: list) -> None:
    for t in threads:
        t.start()
    for t in threads:
        t.join()
