"""The load generator: a closed or an open loop through a served engine.

It drives only the server's public surface: ``submit`` -> ticket,
``poll`` between arrivals, ``n_pending`` and ``ticket.result(timeout)``.
The thread that runs a loop is the server's admission thread. A ticket is
waited on only once it has been flushed (it is no longer pending), so a
waiting client never forces a flush.

Closed loop (``"loop": "closed"``): ``clients`` clients each send their next
query as soon as their previous one returns. Open loop (``"loop": "open"``):
queries are due on a Poisson schedule at ``rate_qps``; every gap of the
schedule is an exponential quantile, in an order drawn from the seed, so
every seed offers the same arrivals. Latency runs from the due time to the
moment a client sees the result; in the open loop a collector thread waits
on flushed tickets in order.

Queries come from a pool of ``RangeQuery`` objects, cycled in order.
"""
import collections
import contextlib
import dataclasses
import queue
import threading
import time

import numpy as np

_NULL = contextlib.nullcontext()


def no_span(_name):
    return _NULL


@dataclasses.dataclass
class Log:
    """One record per submitted query, in submission order."""

    pool_idx: list = dataclasses.field(default_factory=list)
    due: list = dataclasses.field(default_factory=list)
    t_submit: list = dataclasses.field(default_factory=list)
    t_done: list = dataclasses.field(default_factory=list)
    result: list = dataclasses.field(default_factory=list)
    error: list = dataclasses.field(default_factory=list)

    def add(self, idx, due, t_submit) -> int:
        self.pool_idx.append(idx)
        self.due.append(due)
        self.t_submit.append(t_submit)
        self.t_done.append(None)
        self.result.append(None)
        self.error.append(None)
        return len(self.pool_idx) - 1

    def __len__(self):
        return len(self.pool_idx)


def poisson_gaps(n: int, rate: float, rng: np.random.Generator) -> np.ndarray:
    """``n`` inter-arrival gaps at ``rate``: exponential quantiles, shuffled."""
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate
    rng.shuffle(gaps)
    return gaps


class Driver:
    """Submits pool queries to ``srv`` and records what comes back."""

    def __init__(self, srv, make_query, pool_size: int, span=no_span):
        self.srv = srv
        self.make_query = make_query   # pool index -> RangeQuery
        self.pool_size = pool_size
        self.span = span
        self.log = Log()
        self._pending = collections.deque()   # (ticket, k), not yet flushed
        self.flushed = collections.deque()    # (ticket, k), in flight
        self.submitted = 0

    def submit(self, due: float) -> None:
        i = self.submitted % self.pool_size
        self.submitted += 1
        q = self.make_query(i)
        k = self.log.add(i, due, time.perf_counter())
        with self.span("bench.submit"):
            t = self.srv.submit(q)
        if t.shed:
            self.log.error[k] = "shed"
            self.log.t_done[k] = time.perf_counter()
        else:
            self._pending.append((t, k))
        self.move_flushed()

    def poll(self) -> None:
        with self.span("bench.poll"):
            self.srv.poll()
        self.move_flushed()

    def move_flushed(self) -> None:
        while len(self._pending) > self.srv.n_pending:
            self.flushed.append(self._pending.popleft())

    def collect(self, ticket, k: int, timeout=None) -> None:
        """Wait for a flushed ticket; raises TimeoutError if not ready."""
        with self.span("bench.wait"):
            try:
                res = ticket.result(timeout=timeout)
            except TimeoutError:
                raise
            except Exception as e:
                self.log.error[k] = e
                res = None
        self.log.t_done[k] = time.perf_counter()
        self.log.result[k] = res

    def finish(self, timeout_s: float) -> None:
        """Flush what is pending and wait, up to ``timeout_s``, for it all."""
        with self.span("bench.flush"):
            self.srv.flush()
        self.move_flushed()
        deadline = time.perf_counter() + timeout_s
        while self.flushed:
            t, k = self.flushed.popleft()
            try:
                self.collect(t, k, timeout=max(deadline - time.perf_counter(),
                                               1e-3))
            except TimeoutError:
                self.log.error[k] = "never returned"


def run_closed(drv: Driver, clients: int, seconds: float, wait_s: float,
               max_submits: float = np.inf):
    """Closed loop for ``seconds``, or until ``max_submits`` queries have
    gone out; returns (t_start, t_end) of the window. What is still out at
    the end is left for ``Driver.finish``."""
    t0 = time.perf_counter()
    end = t0 + seconds
    stop = drv.submitted + max_submits
    for _ in range(min(clients, max_submits)):
        drv.submit(time.perf_counter())
    while drv.submitted < stop:
        now = time.perf_counter()
        if now >= end:
            break
        if not drv.flushed:
            drv.poll()
            if not drv.flushed:
                time.sleep(wait_s / 4)
            continue
        t, k = drv.flushed[0]
        try:
            drv.collect(t, k, timeout=wait_s)
        except TimeoutError:
            drv.poll()
            continue
        drv.flushed.popleft()
        if drv.log.t_done[k] < end:
            drv.submit(time.perf_counter())
    return t0, min(end, time.perf_counter())


def run_open(drv: Driver, gaps: np.ndarray, wait_s: float, grace_s: float):
    """Open loop over the schedule ``gaps``; returns (t_start, t_end).

    The window ends at the last due time. A collector thread waits on
    flushed tickets in order, so a result's time is when it became ready,
    not when the generator next looked. Results still missing ``grace_s``
    after the window are recorded as never returned.
    """
    handoff: "queue.Queue" = queue.Queue()
    deadline = [None]

    def collector():
        while True:
            item = handoff.get()
            if item is None:
                return
            t, k = item
            limit = deadline[0]
            timeout = 3600.0 if limit is None else max(
                limit - time.perf_counter(), 1e-3)
            try:
                drv.collect(t, k, timeout=timeout)
            except TimeoutError:
                drv.log.error[k] = "never returned"

    th = threading.Thread(target=collector, name="bench-collector",
                          daemon=True)
    th.start()
    try:
        t0 = time.perf_counter()
        dues = t0 + np.cumsum(gaps)
        i = 0
        while i < len(dues):
            now = time.perf_counter()
            while i < len(dues) and dues[i] <= now:
                drv.submit(float(dues[i]))
                i += 1
            drv.poll()
            while drv.flushed:
                handoff.put(drv.flushed.popleft())
            if i < len(dues):
                gap = dues[i] - time.perf_counter()
                if gap > 0:
                    with drv.span("bench.sleep"):
                        time.sleep(min(gap, wait_s / 4))
        t_end = float(dues[-1]) if len(dues) else t0
        deadline[0] = time.perf_counter() + grace_s
        with drv.span("bench.flush"):
            drv.srv.flush()
        drv.move_flushed()
        while drv.flushed:
            handoff.put(drv.flushed.popleft())
    finally:
        handoff.put(None)
        th.join()
    return t0, t_end
