"""AOT-warmed, double-buffered MDRQ serving pipeline (DESIGN.md §13).

``MDRQServer`` is deliberately synchronous: every flush pays plan + launch +
host sync + host finalize back-to-back on one thread, so the device idles
while Python runs ``np.nonzero`` and the admission loop idles while the
device scans. ``PipelinedMDRQServer`` splits the flush along the seam the
core layer now exposes (``MDRQEngine.launch_batch`` -> ``PendingBatch``):

  * **device stage** (admission thread): plan the window and issue every
    bucket's fused launch — jax dispatch is async, so this returns while the
    device still computes. The in-flight ``PendingBatch`` crosses to the
    finalizer through a *bounded* backlog queue (the double buffer: batch
    k+1 launches while batch k executes/finalizes).
  * **finalize stage** (dedicated thread): the one counted
    ``ops.device_get`` per bucket + the spec's host finalizers + ticket
    resolution. Per-batch launch/host-sync budgets are identical to the
    synchronous path — the stages are the same work, relocated.

**AOT warmup**: at construction (and after every ``compact``) the server
pre-compiles the executables the hot path will need — every pow2 query
bucket up to ``max_batch``, for every warm path, under the server's spec,
through ``ops.aot_capture()`` — so steady-state serving *provably* never
retraces (``ops.trace_log()`` stays empty; data-shape-dependent visit
buckets on tree/VA paths are the documented residual and fall back to jit).

**Admission control**: ``submit`` sheds with a typed ``Overloaded`` ticket
once ``(backlog depth + 1) x EWMA batch seconds`` exceeds
``latency_budget_s`` — the server degrades by refusing work it cannot serve
in time instead of growing an unbounded queue. Sheds are visible in
``ServerStats.shed_counts`` and ``mdrq_server_shed_total``.

**Spans** (``obs.tracing``; on the profiler's clock while
``obs.to_profiler(True)`` is on): the admission thread opens ``flush``
(``window``, ``reason``, ``n_queries``) around the launch and
``backlog_put`` around the blocking hand-off; the finalizer thread opens
``finalize`` (``window``, ``n_queries``) around the payload syncs, the host
finalizers and the window's stats. ``window`` is a per-server sequence
number that links one window's two stages across the threads.

Threading contract (enforced by mdrqlint's ``thread-boundary`` rule):
device values cross threads only *inside* a ``PendingBatch`` riding the
backlog queue; ``ops.device_get`` runs only on the finalizer thread; stage
membership is declared with the ``@device_stage`` / ``@finalizer_stage``
decorators. The two threads share no locks — each ``ServerStats`` field has
exactly one writer thread (admission: ``shed_counts``/``flush_reasons``;
finalizer: everything else), and the queue provides the ordering.

The synchronous ``MDRQServer`` remains the default and the deterministic
test surface; ``serve_pipelined(engine)`` is the opt-in factory.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Optional

import numpy as np

from repro import numerics, obs
from repro.obs import tracing as obs_tracing
from repro.core import MDRQEngine, RangeQuery
from repro.core import types as T
from repro.core.engine import PendingBatch
from repro.kernels import ops
from repro.serve.mdrq_server import MDRQServer, Ticket


def device_stage(fn):
    """Mark a function as device-stage: runs on the admission thread, may
    launch device work, must NOT sync it (no ``ops.device_get``) and must
    not park device values on ``self`` — in-flight payloads cross to the
    finalizer only through the backlog queue (mdrqlint: thread-boundary)."""
    fn.__mdrq_stage__ = "device"
    return fn


def finalizer_stage(fn):
    """Mark a function as finalize-stage: runs on the finalizer thread and
    owns the counted ``ops.device_get`` syncs (mdrqlint: thread-boundary)."""
    fn.__mdrq_stage__ = "finalize"
    return fn


class Overloaded(RuntimeError):
    """The server shed this query at admission: the backlog's estimated
    drain time exceeded the latency budget. Retry later or elsewhere."""


@dataclasses.dataclass
class PipelineTicket(Ticket):
    """Event-backed ticket for pipelined serving.

    ``result()`` raises ``Overloaded`` for shed queries, re-raises the
    window's failure if its finalize raised, and otherwise blocks until the
    finalizer thread resolves the window this ticket flushed with.
    """

    _event: threading.Event = dataclasses.field(
        default_factory=threading.Event)
    _inflight: bool = False
    _shed: bool = False
    _error: Optional[BaseException] = None

    @property
    def shed(self) -> bool:
        return self._shed

    def result(self, timeout: Optional[float] = None):
        if self._shed:
            raise Overloaded(
                "query shed at admission: backlog exceeds the latency "
                "budget (see ServerStats.shed_counts)")
        if not self._done and not self._inflight:
            self._server.flush()
        if not self._event.wait(timeout):
            raise TimeoutError(f"pipelined result not ready in {timeout}s")
        if self._error is not None:
            raise self._error
        return self._result


@dataclasses.dataclass
class _Window:
    """One flushed window in flight between the stages."""

    pending: list    # [(RangeQuery, PipelineTicket, t_submit)], flush order
    reason: str
    batch: PendingBatch
    t_flush: float         # device-stage start (queue latency anchor)
    launch_seconds: float  # device-stage wall (plan + dispatch)
    window: int            # per-server sequence number: links its two
    #                        stages' spans (``flush`` and ``finalize``)


@dataclasses.dataclass(frozen=True)
class WarmupReport:
    """What one AOT warmup pass advertised and compiled.

    ``keys`` is exactly the set of ``ops`` AOT-cache keys this pass added —
    the advertised executable set tests assert against; ``n_compiled`` can
    be smaller than ``n_runs`` when shapes coincide across paths."""

    paths: tuple[str, ...]
    bucket_sizes: tuple[int, ...]
    spec_kind: str
    n_runs: int
    n_compiled: int
    seconds: float
    keys: tuple


def _warm_batch(n_q: int, m: int) -> T.QueryBatch:
    """A (n_q, m) warmup batch constraining every dim.

    Constrained dims carry the widest *finite* f32 bounds (finite so they
    count as constrained; widest so tree/VA warmups traverse their largest
    visit bucket). Shapes — the only thing an AOT executable is specialized
    on — match real traffic exactly.
    """
    lo = np.full((n_q, m), numerics.finite_min(np.float32), np.float32)
    up = np.full((n_q, m), numerics.finite_max(np.float32), np.float32)
    return T.QueryBatch(lo, up)


class PipelinedMDRQServer(MDRQServer):
    """Double-buffered MDRQ server: overlapped device/finalize stages, AOT
    warmup, bounded backlog, and admission-control shedding.

    Drop-in for ``MDRQServer`` (same submit/poll/flush/ingest surface) with
    extras: ``warmup()``, ``drain()``, ``close()`` (or use it as a context
    manager), ``latency_budget_s``. Ticket ``result()`` calls block on the
    finalizer thread instead of running the batch inline.
    """

    ticket_cls = PipelineTicket

    def __init__(
        self,
        engine: MDRQEngine,
        max_batch: int = 128,
        max_wait_s: float = 2e-3,
        method: str = "auto",
        spec=None,
        mode: Optional[str] = None,
        query_log_capacity: int = 512,
        *,
        backlog: int = 4,
        latency_budget_s: float = 0.25,
        warmup: bool = True,
    ):
        super().__init__(engine, max_batch=max_batch, max_wait_s=max_wait_s,
                         method=method, spec=spec, mode=mode,
                         query_log_capacity=query_log_capacity)
        if backlog < 1:
            raise ValueError(f"backlog must be >= 1, got {backlog}")
        self.latency_budget_s = latency_budget_s
        # The double buffer: in-flight windows between the stages. ``put``
        # blocks when full — backpressure on the admission thread, so device
        # work can never run unboundedly ahead of host finalization.
        self._backlog: "queue.Queue[Optional[_Window]]" = \
            queue.Queue(maxsize=backlog)
        self._ewma_batch_s = 0.0   # finalizer-thread-only writer
        self._window_seq = 0       # admission-thread-only writer
        self._wall_t0: Optional[float] = None
        self._closed = False
        self._warmup_enabled = bool(warmup)
        self.last_warmup: Optional[WarmupReport] = None
        self._finalizer = threading.Thread(
            target=self._finalize_loop, name="mdrq-finalizer", daemon=True)
        self._finalizer.start()
        if warmup:
            self.warmup()

    # -- lifecycle -----------------------------------------------------------
    def __enter__(self) -> "PipelinedMDRQServer":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()

    def drain(self) -> None:
        """Flush the pending window and block until every in-flight window
        has finalized (the backlog is empty and all tickets resolved)."""
        self.flush()
        self._backlog.join()

    def close(self) -> None:
        """Drain, then stop the finalizer thread. Idempotent."""
        if self._closed:
            return
        self.drain()
        self._closed = True
        self._backlog.put(None)   # stop sentinel
        self._finalizer.join()

    def reset_stats(self) -> None:
        """Fresh stats AND a fresh wall-clock anchor: ``wall_seconds`` must
        measure the next pass only, not everything since construction. Call
        only between passes (after ``drain()``), never with windows in
        flight — the finalizer thread writes stats concurrently otherwise."""
        super().reset_stats()
        self._wall_t0 = None

    # -- AOT warmup ----------------------------------------------------------
    def warmup(self) -> WarmupReport:
        """Pre-compile the hot path's executables -> ``WarmupReport``.

        Sweeps every pow2 bucket size up to ``max_batch`` for every warm
        path (all plannable paths under ``method="auto"``, else the explicit
        path), under the server's spec and the engine's *current* delta
        snapshot, inside ``ops.aot_capture()`` — each jitted op a run hits
        is lowered + compiled once and cached by (op, shapes, statics).
        Steady-state traffic whose
        shapes were advertised here dispatches straight to compiled
        executables: zero retraces, counter-asserted via ``ops.trace_log``.
        Re-run automatically after ``compact`` (new data shapes).
        """
        t0 = time.perf_counter()
        engine = self.engine
        paths = engine.paths
        m = engine.dataset.m
        dview = engine.delta.snapshot()
        delta_arg = None if dview.is_empty else dview
        if self.method == "auto":
            names = tuple(n for n, p in paths.items()
                          if getattr(p, "plannable", True))
        else:
            names = (self.method,)
        sizes, b = [], 1
        top = T.next_pow2(self.max_batch)
        while b <= top:
            sizes.append(b)
            b *= 2
        before = set(ops.aot_cache_keys())
        n_runs = 0
        with obs_tracing.span("warmup", paths=len(names)):
            with ops.aot_capture():
                for name in names:
                    for bsz in sizes:
                        engine._path_query_batch(
                            paths[name], _warm_batch(bsz, m), self.spec,
                            delta=delta_arg)
                        n_runs += 1
        keys = tuple(k for k in ops.aot_cache_keys() if k not in before)
        self.last_warmup = WarmupReport(
            paths=names, bucket_sizes=tuple(sizes),
            spec_kind=self.spec.kind, n_runs=n_runs, n_compiled=len(keys),
            seconds=time.perf_counter() - t0, keys=keys)
        return self.last_warmup

    def compact(self):
        """Compact the engine, then re-warm: the swapped-in version's device
        arrays have new shapes, so the old executables no longer apply."""
        out = super().compact()
        if self._warmup_enabled:
            self.warmup()
        return out

    # -- admission control ---------------------------------------------------
    def _should_shed(self) -> bool:
        # (windows not yet finalized + the one this query would join) x the
        # EWMA batch cost ~= time until this query's result; shed when that
        # exceeds the budget. EWMA 0.0 until the first window completes —
        # cold start never sheds.
        if self._ewma_batch_s <= 0.0:
            return False
        est = (self._backlog.unfinished_tasks + 1) * self._ewma_batch_s
        return est > self.latency_budget_s

    @device_stage
    def submit(self, q: RangeQuery) -> Ticket:
        """Admission: shed with an ``Overloaded`` ticket when the backlog's
        estimated drain time exceeds the budget, else enqueue as usual."""
        if self._closed:
            raise RuntimeError("server is closed")
        if self._wall_t0 is None:
            self._wall_t0 = time.perf_counter()
        if self._should_shed():
            ticket = self.ticket_cls(self, spec=self.spec)
            ticket._shed = True
            self.stats.shed_counts["overloaded"] = \
                self.stats.shed_counts.get("overloaded", 0) + 1
            obs.registry().counter(
                "mdrq_server_shed_total",
                help="queries shed at admission, by reason",
                reason="overloaded").inc()
            return ticket
        return super().submit(q)

    # -- the device stage ----------------------------------------------------
    @device_stage
    def flush(self, reason: str = "forced") -> int:
        """Device stage of a flush: plan + launch the window, hand the
        in-flight ``PendingBatch`` to the finalizer via the backlog.

        On a launch failure the window is re-queued in order with its
        deadline clock re-anchored — tickets stay resolvable by a later
        flush, exactly like the synchronous server's exception path.
        """
        if not self._pending:
            return 0
        pending, self._pending = self._pending, []
        queries = [q for q, _, _ in pending]
        self._window_seq += 1
        window = self._window_seq
        t0 = time.perf_counter()
        try:
            with obs_tracing.span("flush", window=window, reason=reason,
                                  n_queries=len(pending)):
                pb = self.engine.launch_batch(queries, method=self.method,
                                              spec=self.spec)
        except Exception:
            self._pending = pending + self._pending
            self._oldest_t = pending[0][2]
            raise
        launch_s = time.perf_counter() - t0
        for _, ticket, _ in pending:
            ticket._inflight = True
        win = _Window(pending=pending, reason=reason, batch=pb,
                      t_flush=t0, launch_seconds=launch_s, window=window)
        with obs_tracing.span("backlog_put", window=window):
            self._backlog.put(win)   # blocks when full: backpressure
        self.stats.flush_reasons[reason] = \
            self.stats.flush_reasons.get(reason, 0) + 1
        obs.registry().counter(
            "mdrq_server_flushes_total",
            help="server batch flushes, by trigger", reason=reason).inc()
        return len(pending)

    # -- the finalize stage --------------------------------------------------
    @finalizer_stage
    def _finalize_loop(self) -> None:
        """Finalizer thread: drain windows, sync + finalize + resolve.

        A window whose finalize raises poisons only its own tickets (the
        exception re-raises from each ``result()``); later windows keep
        serving — per-window fault isolation.
        """
        while True:
            win = self._backlog.get()
            if win is None:   # stop sentinel from close()
                self._backlog.task_done()
                return
            t0 = time.perf_counter()
            try:
                with obs_tracing.span("finalize", window=win.window,
                                      n_queries=len(win.pending)):
                    results = win.batch.finalize()
                    for (_, ticket, _), res in zip(win.pending, results):
                        ticket._result = res
                        ticket._done = True
                    self._record_window(win, results,
                                        time.perf_counter() - t0)
            except Exception as e:
                for _, ticket, _ in win.pending:
                    ticket._error = e
            finally:
                for _, ticket, _ in win.pending:
                    ticket._event.set()
                self._backlog.task_done()

    @finalizer_stage
    def _record_window(self, win: _Window, results: list,
                       fin_s: float) -> None:
        """Stats + query log for one finalized window (finalizer thread is
        the sole writer of every field it touches here)."""
        stats = self.stats
        bs = win.batch.stats
        kind = self.spec.kind
        methods = win.batch.methods or [self.method] * len(win.pending)
        for (q, _, t_submit), res, meth in zip(win.pending, results, methods):
            queue_s = win.t_flush - t_submit
            # execute latency is the *device-stage* wall — under overlap the
            # whole-flush wall of the sync server would double-count the
            # finalize time of the previous window
            stats.observe_latency(kind, queue_s, win.launch_seconds)
            self.query_log.offer(obs.QueryLogEntry(
                lower=q.lower, upper=q.upper, spec_kind=kind, method=meth,
                result_size=self.spec.result_size(res),
                queue_seconds=queue_s, execute_seconds=win.launch_seconds,
                flush_reason=win.reason, batch_size=len(win.pending)))
        stats.n_queries += len(win.pending)
        stats.spec_counts[kind] = \
            stats.spec_counts.get(kind, 0) + len(win.pending)
        stats.n_batches += 1
        stats.busy_seconds += win.launch_seconds + fin_s
        stats.plan_seconds += bs.plan_seconds
        stats.finalize_seconds += fin_s
        stats.n_results += bs.n_results
        for meth, c in win.batch.method_counts.items():
            stats.method_counts[meth] = stats.method_counts.get(meth, 0) + c
        # wall anchor: first submit -> this finalize; qps divides by this
        if self._wall_t0 is not None:
            stats.wall_seconds = time.perf_counter() - self._wall_t0
        # EWMA of one window's full pipeline cost, for admission control
        total = win.launch_seconds + fin_s
        self._ewma_batch_s = (total if self._ewma_batch_s <= 0.0
                              else 0.8 * self._ewma_batch_s + 0.2 * total)


def serve_pipelined(engine: MDRQEngine, **kwargs) -> PipelinedMDRQServer:
    """Factory: an AOT-warmed, double-buffered server over ``engine``.

    ``with serve_pipelined(engine) as srv: ...`` warms up at construction
    and drains + stops the finalizer thread on exit. Keyword arguments are
    ``PipelinedMDRQServer``'s (``max_batch``, ``backlog``,
    ``latency_budget_s``, ``spec``, ``warmup=False`` to skip warmup, ...).
    """
    return PipelinedMDRQServer(engine, **kwargs)
