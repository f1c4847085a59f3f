"""The access-path layer: one protocol behind every MDRQ execution engine.

The paper's experimental matrix (§7.1.3) — scans, tree MDIS, VA-file — is a
set of interchangeable access paths behind one query interface. This module
makes that matrix explicit (DESIGN.md §6): ``AccessPath`` is the protocol
every path speaks, the ``*Path`` adapters put the concrete structures
(``ColumnarScan``, ``DistributedScan``, ``BlockedIndex``, ``VAFile``) behind
it, and ``MDRQEngine`` becomes a name -> path registry —
adding a path (grid file, learned layout, ...) means registering one object,
not editing three dispatch chains.

Planning rides the same protocol: each path prices itself, scalar
(``cost``, the single-query ``Planner.explain`` hook) and vectorized
(``cost_batch``, the (paths x Q) matrix ``Planner.plan_batch`` builds from
one ``PlanInputs`` pass). The cost mixins delegate to ``CostModel`` so the
built-in paths and the planner's structure-free planning stubs share one set
of formulas; a registered third-party path brings its own.

Conventions:

  * ``cost``/``cost_batch`` return ``inf`` where the path is not applicable
    (e.g. the vertical scan on a complete-match query) — the planner skips
    non-finite entries.
  * ``plannable=False`` paths execute only when named explicitly (a
    ``PerQueryPath`` by default; the vertical scan on a meshed engine, where
    an "auto" choice would lazily re-place the dataset on one device).
  * ``owns_storage=False`` marks views over another path's arrays so
    ``memory_report`` never double-counts (the vertical scan shares the
    columnar scan's data).
"""
from __future__ import annotations

import dataclasses
import functools
import inspect
from typing import Any, Callable, Protocol, Union, runtime_checkable

import numpy as np

from repro.core import types as T


def supports_launch(path) -> bool:
    """Whether a path offers the split-execution protocol:
    ``launch_batch(batch, spec, delta) -> (payload, finalize)`` where the
    caller owns the single ``ops.device_get(payload)`` (skipped when payload
    is None) and ``finalize(host_payload)`` types the per-query results.
    Paths without it still serve pipelined traffic — their buckets execute
    synchronously in the device stage."""
    return callable(getattr(path, "launch_batch", None))


@functools.lru_cache(maxsize=None)
def _fn_takes_spec(fn) -> bool:
    try:
        params = inspect.signature(fn).parameters
    except (TypeError, ValueError):  # builtins / C callables
        return False
    return "spec" in params or any(p.kind == p.VAR_KEYWORD
                                   for p in params.values())


def takes_spec(method) -> bool:
    """Whether a path hook (``query_batch``/``cost``/``cost_batch``) accepts
    the ``spec`` argument of the ResultSpec protocol.

    Paths registered against the pre-spec protocol keep working — the engine
    serves them the two legacy shapes and the planner prices them as Ids.
    The signature probe is cached on the underlying function object (a
    path's signature cannot change after registration), so the execution
    and planning hot paths never re-run ``inspect``.
    """
    return _fn_takes_spec(getattr(method, "__func__", method))


@functools.lru_cache(maxsize=None)
def _fn_takes_delta(fn) -> bool:
    try:
        params = inspect.signature(fn).parameters
    except (TypeError, ValueError):  # builtins / C callables
        return False
    return "delta" in params or any(p.kind == p.VAR_KEYWORD
                                    for p in params.values())


def takes_delta(method) -> bool:
    """Whether a path's ``query_batch`` accepts the ``delta`` argument of the
    versioned-dataset protocol (a ``core.delta.DeltaView``).

    The engine only hands a non-empty delta to paths that declare the
    parameter; registered paths that predate the mutable plane raise a
    "compact() first" error instead of silently serving stale results. Cached
    like ``takes_spec``.
    """
    return _fn_takes_delta(getattr(method, "__func__", method))

# Per-query results under some ResultSpec: id arrays (Ids/TopK), ints
# (Count), bool masks (Mask), or floats (Agg).
Results = Union["list[np.ndarray]", "list[int]", "list[float]"]


@dataclasses.dataclass(frozen=True)
class PlanInputs:
    """Per-query planning statistics for one batch, computed in one pass.

    ``Planner.plan_batch`` builds this once from the (Q, 2, m) bounds
    (``Histograms.dim_selectivity_batch`` / ``selectivity_batch``) and hands
    it to every path's ``cost_batch`` — no per-query Python loop anywhere in
    batch planning.
    """

    lower: np.ndarray      # (Q, m) float32 query lower bounds
    upper: np.ndarray      # (Q, m) float32 query upper bounds
    dims_mask: np.ndarray  # (Q, m) bool — True where a dim is constrained
    mq: np.ndarray         # (Q,) int — number of constrained dims
    dim_sels: np.ndarray   # (Q, m) per-dim selectivity (1.0 if unconstrained)
    sels: np.ndarray       # (Q,) independence-assumption query selectivity

    def __len__(self) -> int:
        return self.lower.shape[0]

    @property
    def is_complete(self) -> np.ndarray:
        """(Q,) bool — queries constraining every dimension."""
        return self.dims_mask.all(axis=1)


@runtime_checkable
class AccessPath(Protocol):
    """What the engine registry and the planner require of a path.

    Execution surface: ``query_batch(batch, spec)`` (one fused launch per
    bucket; ``spec`` is a ``types.ResultSpec`` — ids, count, mask, top-k,
    aggregate — whose on-device reducer the path's launch carries); a single
    query is a batch of one. Planning surface: ``cost`` (scalar) and
    ``cost_batch`` (vectorized over a ``PlanInputs``), both taking the spec
    so reduced result shapes price their smaller host payload.
    ``PerQueryPath`` adapts anything that only has per-query
    ``query``/``count`` functions.
    """

    name: str
    plannable: bool
    owns_storage: bool

    @property
    def nbytes_index(self) -> int: ...

    def query_batch(self, batch: T.QueryBatch,
                    spec: T.ResultSpec = T.IDS) -> Results: ...

    def cost(self, q: T.RangeQuery, sel: float, batch: int, model,
             spec: T.ResultSpec = T.IDS) -> float: ...

    def cost_batch(self, pi: PlanInputs, bucket: np.ndarray, model,
                   spec: T.ResultSpec = T.IDS) -> np.ndarray: ...


# -- cost mixins --------------------------------------------------------------
# One mixin per cost shape, delegating to the CostModel formulas so the real
# paths here and the planner's structure-free stubs cannot drift apart.
# ``bucket`` is the (Q,) per-query amortization size the planner's fixpoint
# converged on (realized bucket sizes, not the whole batch). ``spec`` threads
# into the CostModel so each path's result-payload/host-sync bytes are priced
# per result shape (reduced specs read back O(k) instead of a mask).

class ScanCost:
    """Full fused scan: cost is query-independent except for amortization."""

    def cost(self, q: T.RangeQuery, sel: float, batch: int, model,
             spec: T.ResultSpec = T.IDS) -> float:
        return model.cost_scan(q, batch=batch, spec=spec)

    def cost_batch(self, pi: PlanInputs, bucket: np.ndarray, model,
                   spec: T.ResultSpec = T.IDS) -> np.ndarray:
        return model.cost_scan_batch(len(pi), bucket, spec=spec)


class VerticalScanCost:
    """Partial-match scan: touches only constrained columns; inapplicable
    (inf) to complete-match queries, where it degenerates to the full scan."""

    def cost(self, q: T.RangeQuery, sel: float, batch: int, model,
             spec: T.ResultSpec = T.IDS) -> float:
        if q.is_complete_match:
            return float("inf")
        return model.cost_scan_vertical(q, batch=batch, spec=spec)

    def cost_batch(self, pi: PlanInputs, bucket: np.ndarray, model,
                   spec: T.ResultSpec = T.IDS) -> np.ndarray:
        return np.where(pi.is_complete, np.inf,
                        model.cost_scan_vertical_batch(pi.mq, bucket,
                                                       spec=spec))


class TreeCost:
    """Blocked tree MDIS (kd-tree / R*-tree): prune + visit two-phase cost."""

    def cost(self, q: T.RangeQuery, sel: float, batch: int, model,
             spec: T.ResultSpec = T.IDS) -> float:
        return model.cost_tree(q, sel, batch=batch, spec=spec)

    def cost_batch(self, pi: PlanInputs, bucket: np.ndarray, model,
                   spec: T.ResultSpec = T.IDS) -> np.ndarray:
        return model.cost_tree_batch(pi.sels, pi.mq, bucket, spec=spec)


class VAFileCost:
    """VA-file: packed approximation stream + candidate-block refinement."""

    hist: Any  # Histograms — the scalar candidate-fraction estimate needs it

    def cost(self, q: T.RangeQuery, sel: float, batch: int, model,
             spec: T.ResultSpec = T.IDS) -> float:
        return model.cost_vafile(q, self.hist, batch=batch, spec=spec)

    def cost_batch(self, pi: PlanInputs, bucket: np.ndarray, model,
                   spec: T.ResultSpec = T.IDS) -> np.ndarray:
        return model.cost_vafile_batch(pi.dim_sels, pi.dims_mask, bucket,
                                       spec=spec)


# -- adapters over the concrete structures ------------------------------------

class ColumnarScanPath(ScanCost):
    """``ColumnarScan`` as the "scan" path (single-device full fused scan)."""

    name = "scan"
    plannable = True
    owns_storage = True

    def __init__(self, scan):
        self._scan = scan

    @property
    def nbytes_index(self) -> int:
        return self._scan.nbytes_index

    def query_batch(self, batch: T.QueryBatch,
                    spec: T.ResultSpec = T.IDS, delta=None) -> Results:
        return self._scan.query_batch(batch, spec=spec, delta=delta)

    def launch_batch(self, batch: T.QueryBatch,
                     spec: T.ResultSpec = T.IDS, delta=None) -> tuple:
        return self._scan.launch_batch(batch, spec=spec, delta=delta)


class DistributedScanPath(ScanCost):
    """``DistributedScan`` as the "scan" path — one collective launch per
    batch, data sharded over the mesh (horizontal partitioning, §3.1)."""

    name = "scan"
    plannable = True
    owns_storage = True

    def __init__(self, dist):
        self._dist = dist
        self.n_devices = dist.n_devices

    @property
    def nbytes_index(self) -> int:
        return self._dist.nbytes_index

    def query_batch(self, batch: T.QueryBatch,
                    spec: T.ResultSpec = T.IDS, delta=None) -> Results:
        return self._dist.query_batch(batch, spec=spec, delta=delta)

    def launch_batch(self, batch: T.QueryBatch,
                     spec: T.ResultSpec = T.IDS, delta=None) -> tuple:
        return self._dist.launch_batch(batch, spec=spec, delta=delta)


class VerticalScanPath(VerticalScanCost):
    """The partial-match vertical scan (§5.5) as its own path.

    A *view* over the columnar scan's storage (``owns_storage=False``),
    built lazily through ``scan_ref`` so a meshed engine — where this path is
    ``plannable=False`` and only runs on explicit request — doesn't place a
    second full copy of the dataset on one device just by existing.
    """

    name = "scan_vertical"
    owns_storage = False

    def __init__(self, scan_ref: Callable[[], Any], plannable: bool = True):
        self._scan_ref = scan_ref
        self.plannable = plannable

    @property
    def nbytes_index(self) -> int:
        return 0

    def query_batch(self, batch: T.QueryBatch,
                    spec: T.ResultSpec = T.IDS, delta=None) -> Results:
        return self._scan_ref().query_batch(batch, partial=True, spec=spec,
                                            delta=delta)

    def launch_batch(self, batch: T.QueryBatch,
                     spec: T.ResultSpec = T.IDS, delta=None) -> tuple:
        return self._scan_ref().launch_batch(batch, partial=True,
                                             spec=spec, delta=delta)


class BlockedIndexPath(TreeCost):
    """A ``BlockedIndex`` (kd-tree or packed STR R*-tree) as a path."""

    plannable = True
    owns_storage = True

    def __init__(self, index):
        self._index = index
        self.name = index.name

    @property
    def nbytes_index(self) -> int:
        return self._index.nbytes_index

    def query_batch(self, batch: T.QueryBatch,
                    spec: T.ResultSpec = T.IDS, delta=None) -> Results:
        return self._index.query_batch(batch, spec=spec, delta=delta)

    def launch_batch(self, batch: T.QueryBatch,
                     spec: T.ResultSpec = T.IDS, delta=None) -> tuple:
        return self._index.launch_batch(batch, spec=spec, delta=delta)


class VAFilePath(VAFileCost):
    """A ``VAFile`` as a path (two-phase approximation scan)."""

    name = "vafile"
    plannable = True
    owns_storage = True

    def __init__(self, vafile, hist):
        self._vafile = vafile
        self.hist = hist

    @property
    def nbytes_index(self) -> int:
        return self._vafile.nbytes_index

    def query_batch(self, batch: T.QueryBatch,
                    spec: T.ResultSpec = T.IDS, delta=None) -> Results:
        return self._vafile.query_batch(batch, spec=spec, delta=delta)

    def launch_batch(self, batch: T.QueryBatch,
                     spec: T.ResultSpec = T.IDS, delta=None) -> tuple:
        return self._vafile.launch_batch(batch, spec=spec, delta=delta)


class PerQueryPath:
    """Generic adapter: any object with per-query ``query``/``count``
    functions becomes a full ``AccessPath`` whose batch execution is a
    per-query loop over them.

    This is the extension rung of the layer — registered structures without
    a fused batch kernel (prototypes, test doubles) still ride the registry,
    paying Q calls instead of one launch. Reduced result shapes ride the spec's
    *host* fallback: ids materialize per query and ``ResultSpec.from_ids``
    finalizes against the host columns (pass ``cols`` to enable — specs that
    read attribute values need it). Not plannable by default: a path whose
    batch cost is Q times its single cost should stay an explicit opt-in
    until it prices itself (subclass and override ``cost``/``cost_batch``,
    then pass ``plannable=True``).
    """

    owns_storage = True

    def __init__(self, name: str, impl, plannable: bool = False,
                 cols: np.ndarray | None = None):
        self.name = name
        self._impl = impl
        self.plannable = plannable
        self._cols = cols

    @property
    def nbytes_index(self) -> int:
        return int(getattr(self._impl, "nbytes_index", 0))

    def query_batch(self, batch: T.QueryBatch,
                    spec: T.ResultSpec = T.IDS, delta=None) -> Results:
        spec = T.validate_mode(spec)
        if delta is not None and not delta.is_empty:
            return self._query_batch_delta(batch, spec, delta)
        if spec.kind == "ids":
            return [self._impl.query(batch[k]) for k in range(len(batch))]
        if spec.kind == "count":
            # the impl's own count (device-reduced where it has one)
            return [self._impl.count(batch[k]) for k in range(len(batch))]
        if self._cols is None:
            raise ValueError(
                f"path {self.name!r} has no host columns for result spec "
                f"{spec.kind!r}; construct PerQueryPath(..., cols=...)")
        return [spec.from_ids(self._impl.query(batch[k]), self._cols)
                for k in range(len(batch))]

    def _query_batch_delta(self, batch: T.QueryBatch, spec: T.ResultSpec,
                           delta) -> Results:
        # Host-side delta merge: the wrapped singles see only the frozen
        # base, so per query drop base tombstones, append the delta's host
        # match, and re-finalize every spec from ids against the combined
        # columns (this rung already pays Q host round trips — one numpy
        # filter more does not change its cost class).
        cols = delta.combined_cols()
        out = []
        for k in range(len(batch)):
            q = batch[k]
            ids = np.asarray(self._impl.query(q), np.int64)
            if delta.has_base_tombs:
                ids = ids[~delta.base_tomb[ids]]
            ids = np.concatenate([ids, delta.match_delta_ids(q)])
            out.append(ids if spec.kind == "ids" else spec.from_ids(ids, cols))
        return out

    # A plannable=False path is never priced; keep the protocol total anyway.
    def cost(self, q: T.RangeQuery, sel: float, batch: int, model,
             spec: T.ResultSpec = T.IDS) -> float:
        return float("inf")

    def cost_batch(self, pi: PlanInputs, bucket: np.ndarray, model,
                   spec: T.ResultSpec = T.IDS) -> np.ndarray:
        # host-side planner cost, not a device sentinel: f64 inf is exact
        return np.full((len(pi),), np.inf, np.float64)
