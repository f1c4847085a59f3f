"""MDRQEngine — a registry of access paths behind one query interface.

Ingests a columnar dataset, builds the requested structures (scan is always
available; kd-tree / R*-tree / VA-file optional), wraps each in its
``core.paths.AccessPath`` adapter, and answers range queries either with an
explicitly named path or through the planner ("auto"). This is the paper's
experimental matrix (§7.1.3) as a composable component — and the extension
seam (DESIGN.md §6): all routing is one lookup in the ``paths`` registry,
so a new access path is ``register_path`` away from planning and execution,
with no engine changes.

Batched execution: ``query_batch`` takes a whole stream of queries at once —
the inter-query-parallelism counterpart of the paper's intra-query parallel
scans (§5). The planner's vectorized fixpoint (``Planner.plan_batch``)
assigns every query an access path under *realized-bucket* cost
amortization, each bucket executes through one fused multi-query launch
(``kernels.multi_scan``), and results come back per query. ``query`` is a
batch of one through the same bucket loop. ``BatchStats`` splits ``plan_seconds`` from execution so
the planning cost is visible to ``benchmarks.bench_throughput``;
``serve.mdrq_server`` wraps the whole thing into a throughput front end.

Result shapes: every entry point takes a ``types.ResultSpec`` — ``Ids()``
(default, the paper's §2.1 id sets), ``Count()``, ``Mask()``,
``TopK(k, dim)``, ``Agg(op, dim)`` — pairing an on-device reducer with a
host finalizer, so reduced shapes ship only their payload across the
device->host boundary (the filter-then-aggregate fast path of analytical
workloads). The legacy ``mode="ids"|"count"`` strings keep working through
``types.validate_mode`` with a DeprecationWarning. A new result shape is a
``register_result_spec`` subclass away — specs extend like access paths, not
via another if/elif sweep.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Optional, Sequence, Union

import numpy as np

from repro.obs import metrics as obs_metrics
from repro.obs import tracing as obs_tracing
from repro.kernels import ops
from repro.core import types as T
from repro.core import delta as delta_mod
from repro.core import scan as scan_mod
from repro.core import paths as paths_mod
from repro.core import distributed
from repro.core.distributed import DistributedScan
from repro.core.kdtree import build_kdtree
from repro.core.rstar import build_rstar
from repro.core.vafile import build_vafile
from repro.core.planner import CostModel, Histograms, Planner

# The built-in access paths (every name ``structures``/``mesh`` can put in
# the registry). The registry itself — ``MDRQEngine.paths`` — is the
# authoritative routing table; this tuple is the build vocabulary.
ALL_METHODS = ("scan", "scan_vertical", "kdtree", "rstar", "vafile")
RESULT_MODES = T.RESULT_MODES


@dataclasses.dataclass
class QueryStats:
    method: str
    seconds: float
    n_results: int
    est_selectivity: float


@dataclasses.dataclass
class BatchStats:
    """Aggregate statistics of one ``query_batch`` execution.

    ``seconds`` is the whole wall time (planning + execution);
    ``plan_seconds`` is the planning share of it, so the vectorized batch
    planner's cost is measurable separately from kernel time.
    """

    n_queries: int
    seconds: float
    method_counts: dict[str, int]
    n_results: int
    plan_seconds: float = 0.0
    # per-query chosen path, positionally aligned with the input batch — the
    # server's query log records how each query was served without paying for
    # full tracing
    methods: Optional[list[str]] = None

    @property
    def qps(self) -> float:
        # 0.0 on an empty/zero-time batch (mirrors ServerStats.qps — a rate
        # with nothing measured is reported as zero, not infinity).
        return self.n_queries / self.seconds if self.seconds > 0 else 0.0


def _n_results(spec: T.ResultSpec, results: Sequence) -> int:
    """Total result magnitude across per-query results, typed by the spec."""
    return int(sum(spec.result_size(r) for r in results))


@dataclasses.dataclass
class PendingBatch:
    """An in-flight batch: device work launched, host finalization deferred.

    Produced by ``MDRQEngine.launch_batch`` (the device stage of a split
    ``query_batch``); ``finalize()`` — run later, possibly on another thread
    — performs each bucket's single counted ``ops.device_get`` and the spec's
    host finalizers, returning the per-query results positionally aligned
    with the input. Everything the finalize needs was captured at launch time
    (the state version, the delta snapshot inside each finalize closure), so
    a concurrent ingest or compaction swap cannot mix versions mid-batch.

    ``stats`` is filled by ``finalize()`` but deliberately NOT written to
    ``engine.last_batch_stats``: with several batches in flight the engine-
    level "last" slot would interleave nondeterministically; the pipelined
    server aggregates per-window stats itself.
    """

    n_queries: int
    spec: T.ResultSpec
    methods: list[str]
    method_counts: dict[str, int]
    plan_seconds: float
    launch_seconds: float
    version: int
    # per-bucket (path, input positions, in-flight device payload | None,
    # finalize)
    _parts: list = dataclasses.field(default_factory=list)
    stats: Optional[BatchStats] = None

    def finalize(self) -> list:
        """Host stage: sync each bucket's payload, run the host finalizers,
        scatter per-query results back to input order. Idempotent only in
        the sense that ``stats`` records the *last* call; call once."""
        t0 = time.perf_counter()
        results: list = [None] * self.n_queries
        for meth, idxs, payload, fin in self._parts:
            host = (ops.device_get(payload, stage="finalize", path=meth)
                    if payload is not None else None)
            out = fin(host)
            for k, res in zip(idxs, out):
                results[k] = res
        dt = time.perf_counter() - t0
        self.stats = BatchStats(
            n_queries=self.n_queries,
            seconds=self.plan_seconds + self.launch_seconds + dt,
            method_counts=dict(self.method_counts),
            n_results=_n_results(self.spec, results),
            plan_seconds=self.plan_seconds,
            methods=list(self.methods),
        )
        return results


def bucket_order(idxs: np.ndarray, dims_mask: np.ndarray) -> np.ndarray:
    """A bucket's input positions ``idxs`` in the order its launch takes
    them: stably sorted by how many dimensions each query bounds, then by
    which (the integer whose bit d is dimension d of ``dims_mask``, (Q, m)
    bool). Queries of one signature sit together and the widest fill the
    last chunks, so a scan kernel's chunk of ``INT8_SUBLANES`` queries
    compares only the rows its own queries bound. A bucket of one signature
    keeps its order."""
    mask = dims_mask[idxs]
    packed = np.packbits(mask, axis=1, bitorder="little")
    # lexsort's last key is the primary one: the count, then the bytes of
    # the highest dimensions first
    return idxs[np.lexsort((*packed.T, mask.sum(axis=1)))]


def _buckets(methods: Sequence[str],
             dims_mask: np.ndarray) -> dict[str, np.ndarray]:
    """Path -> the input positions of its queries, in ``bucket_order``.
    Results scatter back by position, so the order costs nothing else;
    ``mdrq_bucket_reordered_total{path}`` counts the buckets it changed."""
    buckets: dict[str, list[int]] = {}
    for k, meth in enumerate(methods):
        buckets.setdefault(meth, []).append(k)
    out = {}
    for meth, idxs in buckets.items():
        idxs = np.asarray(idxs)
        out[meth] = bucket_order(idxs, dims_mask)
        if not np.array_equal(out[meth], idxs):
            obs_metrics.registry().counter(
                "mdrq_bucket_reordered_total",
                help="buckets whose launch order bucket_order changed",
                path=meth).inc()
    return out


def _lookup_path(paths: dict, method: str) -> paths_mod.AccessPath:
    path = paths.get(method)
    if path is None:
        raise ValueError(f"unknown method {method!r}; "
                         f"options: {tuple(paths)} or 'auto'")
    return path


class _EngineState:
    """One immutable *version* of the engine: frozen structures built from a
    dataset snapshot, their access-path registry + planner, and the mutable
    delta segment layered on top (DESIGN.md §11).

    Queries read ``MDRQEngine._state`` exactly once and work off the captured
    object, so the compactor's atomic swap — a single attribute assignment —
    can never mix structures from two versions inside one batch; in-flight
    batches simply finish on the version they captured.
    """

    def __init__(self, dataset: T.Dataset, structures: tuple[str, ...],
                 tile_n: int, mesh, version: int = 0):
        self.dataset = dataset
        self.tile_n = tile_n
        self.version = version
        # With a mesh, "scan" executes as the cross-device batched scan: data
        # sharded over the 'data' axis, one collective launch per batch
        # (horizontal partitioning, §3.1). Other paths stay single-device —
        # and the single-device columnar copy is then built lazily, so a
        # meshed engine that never routes through them doesn't hold the
        # dataset on device twice.
        self.dist = (DistributedScan(dataset, mesh=mesh, tile_n=tile_n)
                     if mesh is not None else None)
        self._columnar = (None if mesh is not None
                          else scan_mod.build_columnar_scan(dataset, tile_n=tile_n))
        self.kdtree = build_kdtree(dataset, tile_n=tile_n) if "kdtree" in structures else None
        self.rstar = build_rstar(dataset, tile_n=tile_n) if "rstar" in structures else None
        self.vafile = build_vafile(dataset, tile_n=tile_n) if "vafile" in structures else None
        self.hist = Histograms.build(dataset)
        # The mutable plane over this frozen version: appended rows +
        # tombstones, scanned by every batch launch alongside the structures.
        self.delta = delta_mod.MutableDelta(dataset)

        # -- the access-path registry (build-from-spec) --------------------
        # Every built structure registers as a plannable path, or "auto"
        # silently never chooses it (the seed omitted rstar — a structure
        # paid for at build time that could not win a single query). On a
        # meshed engine the vertical scan is *not* plannable: it executes on
        # the single-device columnar copy, so an "auto" choice of it would
        # lazily re-place the full dataset on one device — the exact
        # duplication sharding exists to avoid. Explicit
        # ``method="scan_vertical"`` remains an opt-in.
        self.paths: dict[str, paths_mod.AccessPath] = {}
        if self.dist is not None:
            self.add_path(paths_mod.DistributedScanPath(self.dist))
            self.add_path(
                paths_mod.VerticalScanPath(lambda: self.columnar,
                                           plannable=False))
        else:
            self.add_path(paths_mod.ColumnarScanPath(self._columnar))
            self.add_path(paths_mod.VerticalScanPath(lambda: self.columnar))
        for index in (self.kdtree, self.rstar):
            if index is not None:
                self.add_path(paths_mod.BlockedIndexPath(index))
        if self.vafile is not None:
            self.add_path(paths_mod.VAFilePath(self.vafile, self.hist))

        # The planner shares the registry dict: paths registered later are
        # planned without rebuilding anything.
        self.planner = Planner(
            self.hist, CostModel(n=dataset.n, m=dataset.m, tile_n=tile_n,
                                 n_devices=(self.dist.n_devices
                                            if self.dist is not None else 1)),
            paths=self.paths,
        )

    @property
    def columnar(self) -> scan_mod.ColumnarScan:
        if self._columnar is None:
            self._columnar = scan_mod.build_columnar_scan(self.dataset,
                                                          tile_n=self.tile_n)
        return self._columnar

    def add_path(self, path: paths_mod.AccessPath) -> None:
        for attr in ("name", "plannable", "owns_storage", "nbytes_index",
                     "query_batch", "cost", "cost_batch"):
            if not hasattr(path, attr):
                raise TypeError(f"access path lacks {attr!r} "
                                f"(see core.paths.AccessPath)")
        self.paths[path.name] = path


class MDRQEngine:
    """Build-once, query-many MDRQ engine (analytical workloads, §1) — now
    with a mutable plane: ``append``/``delete`` land in a versioned delta
    segment and ``compact`` folds it back into freshly built structures."""

    def __init__(
        self,
        dataset: T.Dataset,
        structures: tuple[str, ...] = ("scan", "kdtree", "rstar", "vafile"),
        tile_n: int = 1024,
        mesh=None,
    ):
        # Build parameters persist so ``compact`` can rebuild the same
        # structure set over the merged dataset.
        self._structures = tuple(structures)
        self.tile_n = tile_n
        self._mesh = mesh
        # Serializes the write side (append/delete/compact-commit); the read
        # side is lock-free — queries capture ``self._state`` once.
        self._ingest_lock = threading.Lock()
        self._state = self._build_state(dataset, version=0)
        self.last_stats: Optional[QueryStats] = None
        self.last_batch_stats: Optional[BatchStats] = None
        self.last_trace: Optional[obs_tracing.BatchTrace] = None

    def _build_state(self, dataset: T.Dataset, version: int = 0) -> _EngineState:
        mesh = self._mesh
        if mesh is None:
            # A table one device cannot hold is sharded over every local
            # device (DESIGN.md §5); only the scan shards.
            mesh = distributed.placement_mesh(dataset.m, dataset.n,
                                              self.tile_n)
            unsharded = set(self._structures) & {"kdtree", "rstar", "vafile"}
            if mesh is not None and unsharded:
                raise ValueError(
                    f"a ({dataset.m}, {dataset.n}) table is sharded over "
                    f"{mesh.shape['data']} devices; {sorted(unsharded)} "
                    f"would place it whole on one")
        return _EngineState(dataset, self._structures, self.tile_n, mesh,
                            version=version)

    # -- versioned-state views ---------------------------------------------
    # Pre-versioning callers read these as plain attributes; each delegates
    # to the *current* version. Code that must be swap-consistent (query,
    # query_batch, the Compactor) captures ``self._state`` once instead.
    @property
    def dataset(self) -> T.Dataset:
        return self._state.dataset

    @property
    def dist(self):
        return self._state.dist

    @property
    def kdtree(self):
        return self._state.kdtree

    @property
    def rstar(self):
        return self._state.rstar

    @property
    def vafile(self):
        return self._state.vafile

    @property
    def hist(self) -> Histograms:
        return self._state.hist

    @property
    def paths(self) -> dict[str, paths_mod.AccessPath]:
        return self._state.paths

    @property
    def planner(self) -> Planner:
        return self._state.planner

    @property
    def columnar(self) -> scan_mod.ColumnarScan:
        return self._state.columnar

    @property
    def _columnar(self):
        # introspection compat: None until the lazy columnar copy is built
        return self._state._columnar

    @property
    def delta(self) -> delta_mod.MutableDelta:
        return self._state.delta

    @property
    def version(self) -> int:
        """Monotone dataset version: bumps on every compaction swap."""
        return self._state.version

    # -- the mutable plane (append / delete / compact) ----------------------
    def append(self, rows) -> np.ndarray:
        """Append rows ((k, m) array-like) -> their assigned int64 ids.

        Rows land in the current version's delta segment and are visible to
        every subsequent query: the fused batch launches scan the delta
        block alongside the frozen structures (same launch, same host sync).
        """
        with self._ingest_lock:
            return self._state.delta.append(rows)

    def delete(self, ids) -> int:
        """Tombstone ids (base or delta rows) -> count of newly deleted."""
        with self._ingest_lock:
            return self._state.delta.delete(ids)

    def compact(self) -> np.ndarray:
        """Merge delta rows + tombstones into freshly built main structures
        and atomically swap the engine to the new version.

        Returns the id map (old id -> new id, -1 for deleted rows). The
        build runs outside the ingest lock — serving and ingest continue on
        the old version — and the commit re-folds anything ingested during
        the build into the new version's delta before swapping ``_state`` in
        a single assignment.
        """
        with obs_tracing.span("compact", version=self._state.version):
            comp = delta_mod.Compactor(self)
            comp.build()
            return comp.commit()

    # -- the registry ------------------------------------------------------
    def register_path(self, path: paths_mod.AccessPath) -> None:
        """Register (or replace) an access path under ``path.name``.

        The planner sees it immediately (shared registry dict): a plannable
        path is costed by ``explain``/``plan_batch`` and can win "auto"
        queries; any registered path is addressable as ``method=name``.
        Registration binds to the *current* version — a later ``compact``
        rebuilds the registry from the engine's build spec, so external
        paths must re-register after a swap.
        """
        self._state.add_path(path)

    def _path(self, method: str) -> paths_mod.AccessPath:
        return _lookup_path(self._state.paths, method)

    def memory_report(self) -> dict[str, int]:
        """Bytes of auxiliary structures per path (paper §7.2 comparison),
        plus the mutable plane ("delta": segment rows + both tombstone sets).

        Storage-owning paths only: views over another path's arrays (the
        vertical scan) would double-count.
        """
        state = self._state
        rep = {"data": state.dataset.nbytes, "delta": state.delta.nbytes}
        for name, path in state.paths.items():
            if path.owns_storage:
                rep[name] = path.nbytes_index
        return rep

    @staticmethod
    def _path_query_batch(path, sub: T.QueryBatch, spec: T.ResultSpec,
                          delta=None) -> list:
        """Run one bucket through a path under ``spec`` (and ``delta``).

        Paths registered against the pre-ResultSpec protocol (a
        ``query_batch(batch, mode)`` taking mode strings) still serve the
        two legacy shapes; reduced shapes on such a path get the canonical
        error instead of silently wrong results. A non-empty delta likewise
        only goes to paths that declare the parameter — anything else would
        silently drop appended rows.
        """
        if delta is not None:
            if not paths_mod.takes_delta(path.query_batch):
                raise ValueError(
                    f"access path {path.name!r} is not delta-aware; "
                    f"call compact() first")
            return path.query_batch(sub, spec=spec, delta=delta)
        if paths_mod.takes_spec(path.query_batch):
            return path.query_batch(sub, spec=spec)
        if spec.kind in T.RESULT_MODES:
            import warnings
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", DeprecationWarning)
                return path.query_batch(sub, spec.kind)
        raise ValueError(f"path {path.name!r} predates the ResultSpec "
                         f"protocol and cannot serve spec {spec.kind!r}")

    @staticmethod
    def _path_supports_launch(path, delta) -> bool:
        """Whether this bucket can use the split launch/finalize protocol.

        Registered paths without ``launch_batch`` (or whose ``launch_batch``
        predates the spec/delta parameters) fall back to synchronous
        execution inside the device stage — correct, just not overlapped.
        """
        if not paths_mod.supports_launch(path):
            return False
        lb = path.launch_batch
        if not paths_mod.takes_spec(lb):
            return False
        if delta is not None and not paths_mod.takes_delta(lb):
            return False
        return True

    def launch_batch(
        self,
        queries: Union[T.QueryBatch, Sequence[T.RangeQuery]],
        method: str = "auto",
        spec: Optional[T.ResultSpec] = None,
        mode: Optional[str] = None,
    ) -> PendingBatch:
        """Device stage of a split ``query_batch`` -> a ``PendingBatch``.

        Plans the batch and issues every bucket's fused launch without
        synchronizing; the returned ``PendingBatch.finalize()`` performs the
        deferred host syncs + spec finalizers (one counted ``device_get`` per
        bucket — the same budget as the synchronous path) and may run on
        another thread. State and delta snapshot are captured here, once:
        in-flight batches finalize on the version they launched against, so
        ingest/compaction stays atomic while a batch is in flight
        (DESIGN.md §13). Buckets whose path lacks the split protocol execute
        synchronously inside this call (their results ride a pre-finalized
        part). ``finalize()`` fills ``PendingBatch.stats`` but never touches
        ``engine.last_batch_stats``.
        """
        state = self._state
        spec = T.resolve_spec(spec, mode)
        if isinstance(queries, T.QueryBatch):
            batch = queries
        else:
            queries = list(queries)
            batch = T.QueryBatch.from_queries(queries) if queries else None
        if batch is None or len(batch) == 0:
            return PendingBatch(0, spec, [], {}, 0.0, 0.0, state.version)
        if batch.m != state.dataset.m:
            raise ValueError(f"batch dims {batch.m} != dataset dims "
                             f"{state.dataset.m}")
        spec.validate(state.dataset.m)
        dview = state.delta.snapshot()
        delta_arg = None if dview.is_empty else dview

        t0 = time.perf_counter()
        with obs_tracing.span("plan", n_queries=len(batch)):
            state.planner.model.delta_n = dview.d
            if method == "auto":
                bp = state.planner.plan_batch(batch, spec=spec)
                methods = bp.methods
            else:
                _lookup_path(state.paths, method)  # raise before work
                methods = [method] * len(batch)
        t1 = time.perf_counter()

        buckets = _buckets(methods, batch.dims_mask)

        pending = PendingBatch(
            n_queries=len(batch), spec=spec, methods=list(methods),
            method_counts={m: len(ix) for m, ix in buckets.items()},
            plan_seconds=t1 - t0, launch_seconds=0.0, version=state.version)
        for meth, idxs in buckets.items():
            sub = T.QueryBatch(batch.lower[idxs], batch.upper[idxs])
            path = _lookup_path(state.paths, meth)
            with obs_tracing.span("execute", path=meth, bucket=len(idxs),
                                  stage="launch",
                                  n_devices=getattr(path, "n_devices", None)):
                if self._path_supports_launch(path, delta_arg):
                    payload, fin = path.launch_batch(sub, spec=spec,
                                                     delta=delta_arg)
                else:
                    out = self._path_query_batch(path, sub, spec,
                                                 delta=delta_arg)
                    payload, fin = None, (lambda _h, _out=out: _out)
            pending._parts.append((meth, idxs, payload, fin))
        pending.launch_seconds = time.perf_counter() - t1

        reg = obs_metrics.registry()
        reg.counter("mdrq_query_batches_total",
                    help="query_batch executions").inc()
        for meth, idxs in buckets.items():
            reg.counter("mdrq_queries_total",
                        help="queries served, by access path",
                        path=meth).inc(len(idxs))
        return pending

    def query(self, q: T.RangeQuery, method: str = "auto",
              spec: Optional[T.ResultSpec] = None,
              mode: Optional[str] = None):
        """Execute q under a ResultSpec -> sorted ids (default ``Ids()``),
        an int count, a bool mask, top-k ids, or an aggregate; records
        QueryStats. ``mode="ids"|"count"`` is the deprecated string alias.

        A batch of one: ``planner.explain`` picks the path ("auto"), then q
        runs through the bucket loop of ``query_batch``, with the same
        launches and host syncs as ``query_batch([q], method=<that path>)``.
        """
        state = self._state
        if q.m != state.dataset.m:
            raise ValueError(f"query dims {q.m} != dataset dims {state.dataset.m}")
        spec = T.resolve_spec(spec, mode).validate(state.dataset.m)
        dview = state.delta.snapshot()
        state.planner.model.delta_n = dview.d
        if method == "auto":
            plan = state.planner.explain(q, spec=spec)
            method, est = plan.method, plan.est_selectivity
        else:
            est = state.planner.hist.selectivity(q)
        batch = T.QueryBatch.from_queries([q])
        t0 = time.perf_counter()
        res = self._execute(state, batch, _buckets([method], batch.dims_mask),
                            spec, None if dview.is_empty else dview)[0]
        dt = time.perf_counter() - t0
        self.last_stats = QueryStats(method=method, seconds=dt,
                                     n_results=spec.result_size(res),
                                     est_selectivity=est)
        return res

    def _execute(self, state: _EngineState, batch: T.QueryBatch,
                 buckets: dict[str, np.ndarray], spec: T.ResultSpec,
                 delta) -> list:
        """Run each bucket through its path, synchronously -> per-query
        results, positionally aligned with ``batch``."""
        results: list = [None] * len(batch)
        for meth, idxs in buckets.items():
            sub = T.QueryBatch(batch.lower[idxs], batch.upper[idxs])
            path = _lookup_path(state.paths, meth)
            with obs_tracing.span(
                    "execute", path=meth, bucket=len(idxs),
                    n_devices=getattr(path, "n_devices", None)) as sp:
                out = self._path_query_batch(path, sub, spec, delta=delta)
                sp.block_on(out)
            for k, res in zip(idxs, out):
                results[k] = res
        return results

    def query_batch(
        self,
        queries: Union[T.QueryBatch, Sequence[T.RangeQuery]],
        method: str = "auto",
        spec: Optional[T.ResultSpec] = None,
        mode: Optional[str] = None,
        trace: bool = False,
    ) -> list:
        """Execute a batch of queries under a ResultSpec -> per-query typed
        results (sorted id arrays by default).

        Queries are bucketed by access path (the planner's vectorized
        fixpoint under realized-bucket, spec-aware cost amortization when
        ``method="auto"``, or the explicit method for all) and each bucket
        runs through a single fused multi-query launch carrying the spec's
        on-device reducer. Results are positionally aligned with the input
        and do not depend on the other queries of the batch; aggregate
        ``BatchStats``
        land in ``last_batch_stats`` with the planning share in
        ``plan_seconds``.

        ``trace=True`` installs an ``obs.Tracer`` for the duration and leaves
        a ``BatchTrace`` in ``last_trace``: one ``QueryTrace`` per query
        (chosen path, bucket, estimated vs realized selectivity and cost,
        amortized launches/host-syncs) plus the span tree. With
        ``trace=False`` the span calls short-circuit to ``obs.NULL_SPAN`` —
        nothing is allocated on the hot path.
        """
        state = self._state
        spec = T.resolve_spec(spec, mode)
        if isinstance(queries, T.QueryBatch):
            batch = queries
        else:
            queries = list(queries)
            batch = T.QueryBatch.from_queries(queries) if queries else None
        if batch is None or len(batch) == 0:
            self.last_batch_stats = BatchStats(0, 0.0, {}, 0, methods=[])
            return []
        if batch.m != state.dataset.m:
            raise ValueError(f"batch dims {batch.m} != dataset dims {state.dataset.m}")
        spec.validate(state.dataset.m)
        # One snapshot serves the whole batch: concurrent appends/deletes
        # become visible at the next batch, never mid-batch.
        dview = state.delta.snapshot()
        delta_arg = None if dview.is_empty else dview

        tracer = obs_tracing.Tracer() if trace else None
        if tracer is not None:
            tracer.__enter__()
        bp = None
        try:
            t0 = time.perf_counter()
            with obs_tracing.span("plan", n_queries=len(batch)):
                # The delta's size is a per-version cost axis: every path
                # pays an extra delta scan per batch, amortized over its
                # realized bucket — which can flip index picks to the scan
                # as the delta grows.
                state.planner.model.delta_n = dview.d
                if method == "auto":
                    bp = state.planner.plan_batch(batch, spec=spec)
                    methods = bp.methods
                else:
                    _lookup_path(state.paths, method)  # raise before work
                    methods = [method] * len(batch)
            plan_dt = time.perf_counter() - t0

            buckets = _buckets(methods, batch.dims_mask)
            results = self._execute(state, batch, buckets, spec, delta_arg)
            dt = time.perf_counter() - t0
        finally:
            if tracer is not None:
                tracer.__exit__(None, None, None)

        reg = obs_metrics.registry()
        reg.counter("mdrq_query_batches_total",
                    help="query_batch executions").inc()
        for meth, idxs in buckets.items():
            reg.counter("mdrq_queries_total",
                        help="queries served, by access path",
                        path=meth).inc(len(idxs))

        self.last_batch_stats = BatchStats(
            n_queries=len(batch),
            seconds=dt,
            method_counts={m: len(ix) for m, ix in buckets.items()},
            n_results=_n_results(spec, results),
            plan_seconds=plan_dt,
            methods=list(methods),
        )
        if tracer is not None:
            self.last_trace = self._build_trace(
                state, tracer, batch, spec, bp, methods, buckets, results,
                plan_dt, dt)
        return results

    @staticmethod
    def _build_trace(state, tracer, batch, spec, bp, methods, buckets,
                     results, plan_dt, dt) -> obs_tracing.BatchTrace:
        """Assemble per-query ``QueryTrace`` records from the span tree and
        the batch plan (estimates come from ``bp`` when the planner chose;
        explicit-method runs get histogram selectivities and NaN cost)."""
        n = state.dataset.n
        mq = batch.dims_mask.sum(axis=1)
        if bp is not None:
            sels = bp.est_selectivity
            path_row = {name: j for j, name in enumerate(bp.path_names)}
        else:
            sels = state.planner.plan_inputs(batch).sels
            path_row = {}
        # one execute span per bucket, keyed by its path attr
        bucket_spans = {s.attrs.get("path"): s for s in tracer.find("execute")}
        records = []
        for k, meth in enumerate(methods):
            bsize = len(buckets[meth])
            sp = bucket_spans.get(meth)
            res_size = spec.result_size(results[k])
            obs_sel = (res_size / n if spec.kind in ("ids", "count", "mask")
                       else None)
            est_cost = (float(bp.costs[path_row[meth], k]) if bp is not None
                        else float("nan"))
            records.append(obs_tracing.QueryTrace(
                index=k,
                method=meth,
                bucket_size=bsize,
                est_selectivity=float(sels[k]),
                est_cost=est_cost,
                spec_kind=spec.kind,
                mq=int(mq[k]),
                result_size=res_size,
                obs_selectivity=obs_sel,
                seconds=(sp.seconds / bsize if sp is not None else 0.0),
                launches=(sp.launches / bsize if sp is not None else 0.0),
                host_syncs=(sp.host_syncs / bsize if sp is not None else 0.0),
            ))
        return obs_tracing.BatchTrace(
            n=n, n_queries=len(batch), spec_kind=spec.kind,
            plan_seconds=plan_dt, seconds=dt, queries=records,
            spans=tracer.spans)
