"""Core datatypes for multidimensional range queries (MDRQ).

Mirrors the paper's problem definition (§2.1):

  * a dataset ``D`` of ``n`` objects with ``m`` float attributes,
  * a (partial- or complete-match) range query ``q`` with per-dimension
    predicates ``[lb_j, ub_j]``; un-queried dimensions use ``[-inf, +inf]``,
  * a result = the set of identifiers of matching objects.

The canonical device layout is **dimension-major (columnar)**, shape ``(m, n)``
— the TPU-native realization of the paper's vertical partitioning (§3.2): the
last (lane) dimension runs over objects so one VREG holds 128 objects of one
attribute, and the AND-merge across dimensions happens in-register.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, ClassVar, Optional, Sequence

import numpy as np
import jax.numpy as jnp

from repro import numerics

NEG_INF = np.float32(-np.inf)
POS_INF = np.float32(np.inf)

# Legacy result-mode strings (pre-ResultSpec protocol). Kept only for the
# ``mode="ids"|"count"`` back-compat shim in ``validate_mode``.
RESULT_MODES = ("ids", "count")


# =============================================================================
# ResultSpec — the first-class result protocol (DESIGN.md §9)
# =============================================================================
# The paper defines an MDRQ result as the materialized id set (§2.1), but the
# analytics workloads that motivate its scan-vs-index question mostly consume
# that set through a *reduction* — counts, extremes, top-k by an attribute.
# A ``ResultSpec`` names the shape a caller wants back and pairs
#
#   * an **on-device reducer** — applied to the (Q, n) match masks (or the
#     (V, tile_n) two-phase visit masks) inside the same jit as the kernel
#     that produced them, so only the reduced payload ever crosses the
#     device->host boundary, and
#   * a **host finalizer** — turning the fetched payload into one typed
#     result per query,
#
# plus the planner's output-bytes estimate and the per-query host fallback
# (``from_ids``) the generic ``PerQueryPath`` rung uses. Each access-path
# shape calls a fixed protocol method — there is no per-kind if/elif sweep
# anywhere in the engine — so a new result shape is one subclass plus
# ``register_result_spec``, exactly like registering a new access path.
#
# Specs are frozen (hashable) dataclasses: they ride jax.jit static args, so
# the reduction specializes at trace time per spec instance.

RESULT_SPEC_KINDS: dict[str, type] = {}


def register_result_spec(cls):
    """Register a ResultSpec subclass under ``cls.kind`` (decorator).

    Registration makes the kind addressable by name (``ServerStats``
    bucketing, benchmark ``--spec`` flags) — the result-shape analogue of
    ``MDRQEngine.register_path``.
    """
    RESULT_SPEC_KINDS[cls.kind] = cls
    return cls


@dataclasses.dataclass(frozen=True)
class ResultSpec:
    """Base of the result protocol: what a query should return, and how.

    Subclasses override the device reducers for the three execution shapes
    (full masks, two-phase visit masks, sharded masks) and the matching host
    finalizers. The base class implements the identity reduction (payload =
    the masks themselves) so mask-shaped specs (``Ids``, ``Mask``) need no
    device code at all.
    """

    kind: ClassVar[str] = "abstract"
    # True when the device payload stays sharded over the object axis under
    # shard_map (Ids/Mask); False when the reducer merges to a replicated
    # payload through collectives (Count/TopK/Agg).
    sharded_payload: ClassVar[bool] = False
    # True when ``reduce_visits`` consumes the host-built (Q, M) visit-index
    # table (TopK's gather); everyone else gets a (1, 1) placeholder so the
    # two-phase paths skip the build + transfer.
    needs_visit_index: ClassVar[bool] = False
    # True when the visit reducer drops every visit row whose ``valid`` is 0
    # and the payload finalizes through ``finalize`` alone, with no host
    # visit list: the tree paths then build the visit list on the device,
    # from the prune mask, and sync once per batch (``combine_visits`` merges
    # the payloads of the chunks they visit in). Ids/Mask/TopK read the host
    # list and keep the host route.
    visits_on_device: ClassVar[bool] = False

    @property
    def value_dim(self) -> Optional[int]:
        """Attribute dimension whose values the reducer reads (None = none)."""
        return None

    def validate(self, m: int) -> "ResultSpec":
        """Check the spec against an m-dim dataset (canonical error site)."""
        d = self.value_dim
        if d is not None and not (0 <= d < m):
            raise ValueError(f"{self.kind} dim {d} out of range for m={m}")
        return self

    # -- on-device reducers (called inside the fused-kernel jits) ----------
    def device_reduce(self, masks, data_cm, *, tile_n: int, interpret: bool):
        """(q_pad, n_pad) match masks -> device payload (identity here)."""
        return masks

    def reduce_visits(self, masks, data_cm, qids, bids, valid, visit_index,
                      *, tile_n: int, n_queries: int, interpret: bool):
        """(V_pad, tile_n) two-phase visit masks -> device payload."""
        return masks

    def distributed_reduce(self, mask_local, data_local, axis: str):
        """Per-shard masks -> payload, inside shard_map (collectives OK)."""
        return mask_local

    def combine_visits(self, a, b):
        """The payload of two disjoint chunks of visits from theirs (device
        route only; see ``visits_on_device``)."""
        raise NotImplementedError

    # -- host finalizers ----------------------------------------------------
    def finalize(self, payload, q_n: int, n: int) -> list:
        """Host payload from the mask-shaped routes -> one result/query."""
        raise NotImplementedError

    def finalize_visits(self, payload, vctx: "VisitHostCtx") -> list:
        """Host payload from the visit-shaped route -> one result/query.

        Defaults to ``finalize`` — correct whenever the visit reducer already
        produced the same payload shape as the mask reducer (Count/TopK/Agg).
        """
        return self.finalize(payload, vctx.n_queries, vctx.n)

    def from_ids(self, ids: np.ndarray, cols: np.ndarray):
        """Host fallback from a materialized id set (``PerQueryPath`` rung)."""
        raise NotImplementedError

    # -- planner surface ----------------------------------------------------
    def host_bytes(self, touched, n: int):
        """Estimated device->host payload + host-materialization bytes per
        query. ``touched`` is the mask bytes the path would read back in the
        identity reduction (n for full scans, visited-fraction * n for the
        two-phase paths); scalar or (Q,) — the return broadcasts with it.
        """
        raise NotImplementedError

    # -- delta merge (mutable data plane, DESIGN.md §11) --------------------
    def merge_delta(self, base_results: list, delta_results: list,
                    dctx: "DeltaHostCtx") -> list:
        """Fold per-query delta results into the base results.

        Under a non-empty delta segment the fused jits evaluate base and
        delta in one launch and return two payloads; both finalize with the
        spec's ordinary host finalizer (the delta side in *local* delta
        coordinates, objects ``[0, d)``), and this hook combines them into
        one answer per query. Specs that don't implement it can't serve a
        mutated engine — ``compact()`` first.
        """
        raise NotImplementedError(
            f"result spec {self.kind!r} does not implement merge_delta; "
            f"compact() the engine before querying with it")

    # -- misc ---------------------------------------------------------------
    def empty_result(self, n: int):
        """The result of a query with an empty candidate set."""
        raise NotImplementedError

    def result_size(self, res) -> int:
        """Result magnitude for QueryStats/BatchStats ``n_results``."""
        raise NotImplementedError


@register_result_spec
@dataclasses.dataclass(frozen=True)
class Ids(ResultSpec):
    """Sorted matching identifiers — the paper's §2.1 result definition."""

    kind: ClassVar[str] = "ids"
    sharded_payload: ClassVar[bool] = True

    def finalize(self, payload, q_n, n):
        return [np.nonzero(payload[k, :n])[0].astype(np.int64)
                for k in range(q_n)]

    def finalize_visits(self, payload, vctx):
        from repro.core import blockindex  # runtime: no import cycle
        return blockindex.scatter_visit_results(
            payload[: vctx.qids.size], vctx.qids, vctx.bids, vctx.n_queries,
            vctx.tile_n, vctx.n, vctx.perm)

    def from_ids(self, ids, cols):
        return ids

    def merge_delta(self, base_results, delta_results, dctx):
        # Delta ids are all >= n (append order), so concatenation keeps the
        # per-query id arrays sorted.
        return [np.concatenate(
            [b, dctx.delta_ids[np.asarray(d, np.int64)]])
            for b, d in zip(base_results, delta_results)]

    def host_bytes(self, touched, n):
        # the mask readback plus the host-side nonzero sweep over it; the
        # materialized id arrays themselves are selectivity-proportional and
        # path-independent, so they never move a ranking
        return 2.0 * touched

    def empty_result(self, n):
        return np.empty((0,), np.int64)

    def result_size(self, res):
        return int(res.size)


@register_result_spec
@dataclasses.dataclass(frozen=True)
class Mask(ResultSpec):
    """The raw (n,) bool match mask per query (no id materialization)."""

    kind: ClassVar[str] = "mask"
    sharded_payload: ClassVar[bool] = True

    def finalize(self, payload, q_n, n):
        return [np.asarray(payload[k, :n]) > 0 for k in range(q_n)]

    def finalize_visits(self, payload, vctx):
        from repro.core import blockindex
        out = []
        for ids in blockindex.scatter_visit_results(
                payload[: vctx.qids.size], vctx.qids, vctx.bids,
                vctx.n_queries, vctx.tile_n, vctx.n, vctx.perm):
            m = np.zeros((vctx.n,), bool)
            m[ids] = True
            out.append(m)
        return out

    def from_ids(self, ids, cols):
        m = np.zeros((cols.shape[1],), bool)
        m[ids] = True
        return m

    def merge_delta(self, base_results, delta_results, dctx):
        # The merged mask covers the combined id space [0, n + d).
        out = []
        for b, d in zip(base_results, delta_results):
            m = np.zeros((dctx.n + dctx.delta_ids.size,), bool)
            m[: dctx.n] = b
            m[dctx.n:] = d
            out.append(m)
        return out

    def host_bytes(self, touched, n):
        return touched + float(n)

    def empty_result(self, n):
        return np.zeros((n,), bool)

    def result_size(self, res):
        return int(res.sum())


@register_result_spec
@dataclasses.dataclass(frozen=True)
class Count(ResultSpec):
    """Per-query match counts reduced on device (COUNT(*) fast path)."""

    kind: ClassVar[str] = "count"
    visits_on_device: ClassVar[bool] = True

    def device_reduce(self, masks, data_cm, *, tile_n, interpret):
        return jnp.sum(masks != 0, axis=-1).astype(jnp.int32)

    def reduce_visits(self, masks, data_cm, qids, bids, valid, visit_index,
                      *, tile_n, n_queries, interpret):
        from repro.kernels import reducers
        return reducers.visit_mask_counts(masks, qids, valid, n_queries)

    def combine_visits(self, a, b):
        return a + b

    def distributed_reduce(self, mask_local, data_local, axis):
        import jax
        return jax.lax.psum(
            jnp.sum(mask_local != 0, axis=-1).astype(jnp.int32), axis)

    def finalize(self, payload, q_n, n):
        return [int(c) for c in np.asarray(payload)[:q_n]]

    def from_ids(self, ids, cols):
        return int(ids.size)

    def merge_delta(self, base_results, delta_results, dctx):
        return [int(b) + int(d)
                for b, d in zip(base_results, delta_results)]

    def host_bytes(self, touched, n):
        return 4.0 * np.ones_like(np.asarray(touched, np.float64))

    def empty_result(self, n):
        return 0

    def result_size(self, res):
        return int(res)


@register_result_spec
@dataclasses.dataclass(frozen=True)
class TopK(ResultSpec):
    """Top-k matching ids ordered by attribute ``dim`` (k-largest/smallest).

    The reducer fills non-matching lanes with the identity, runs a device
    ``top_k`` over the filled values, and ships only (k values, k positions,
    1 count) per query; the finalizer maps positions to original ids
    (through the structure's permutation where one exists) and truncates to
    the true match count. Ties order by ascending id (XLA top_k and the
    numpy fallback agree).
    """

    kind: ClassVar[str] = "topk"
    needs_visit_index: ClassVar[bool] = True
    k: int = 1
    dim: int = 0
    largest: bool = True

    def __post_init__(self):
        if self.k < 1:
            raise ValueError(f"TopK k must be >= 1, got {self.k}")

    @property
    def value_dim(self):
        return self.dim

    @property
    def _fill(self) -> float:
        return -np.inf if self.largest else np.inf

    def device_reduce(self, masks, data_cm, *, tile_n, interpret):
        from repro.kernels import reducers
        return reducers.masked_topk(masks, data_cm[self.dim], self.k,
                                    self.largest, tile_n=tile_n,
                                    interpret=interpret)

    def reduce_visits(self, masks, data_cm, qids, bids, valid, visit_index,
                      *, tile_n, n_queries, interpret):
        from repro.kernels import reducers
        vblocks = reducers.gather_visit_values(data_cm, self.dim, bids, tile_n)
        vals, pos = reducers.visit_topk(masks, vblocks, bids, valid,
                                        visit_index, self.k, self.largest,
                                        tile_n)
        counts = reducers.visit_mask_counts(masks, qids, valid, n_queries)
        return vals, pos, counts

    def distributed_reduce(self, mask_local, data_local, axis):
        import jax
        lax = jax.lax
        vals = data_local[self.dim].astype(jnp.float32)
        filled = jnp.where(mask_local != 0, vals, self._fill)
        key = filled if self.largest else -filled
        kk = min(self.k, key.shape[-1])
        v, i = lax.top_k(key, kk)  # shard-local partials, key space
        gidx = i.astype(jnp.int32) \
            + lax.axis_index(axis).astype(jnp.int32) * data_local.shape[-1]
        counts = lax.psum(jnp.sum(mask_local != 0, axis=-1).astype(jnp.int32),
                          axis)
        vg = lax.all_gather(v, axis)      # (D, Q, kk) — the small collective
        ig = lax.all_gather(gidx, axis)
        d = vg.shape[0]
        q_n = v.shape[0]
        key_all = jnp.transpose(vg, (1, 0, 2)).reshape(q_n, d * kk)
        idx_all = jnp.transpose(ig, (1, 0, 2)).reshape(q_n, d * kk)
        v2, j = lax.top_k(key_all, min(self.k, d * kk))
        idx = jnp.take_along_axis(idx_all, j, axis=1)
        return (v2 if self.largest else -v2), idx, counts

    def finalize(self, payload, q_n, n):
        _, idx, counts = payload
        out = []
        for k in range(q_n):
            c = min(int(counts[k]), idx.shape[1], self.k)
            out.append(np.asarray(idx[k, :c]).astype(np.int64))
        return out

    def finalize_visits(self, payload, vctx):
        vals, pos, counts = payload
        out = []
        for k in range(vctx.n_queries):
            c = min(int(counts[k]), pos.shape[1], self.k)
            p = np.asarray(pos[k, :c]).astype(np.int64)
            out.append(vctx.perm[p] if vctx.perm is not None else p)
        return out

    def from_ids(self, ids, cols):
        vals = cols[self.dim, ids]
        order = np.argsort(-vals if self.largest else vals, kind="stable")
        return ids[order[: self.k]].astype(np.int64)

    def merge_delta(self, base_results, delta_results, dctx):
        # Exact: top-k of (base ∪ delta) ⊆ (top-k of base) ∪ (top-k of
        # delta), so re-ranking the ≤2k candidates by a host value gather
        # reproduces the frozen-dataset answer. Ties keep the ascending-id
        # order the device top_k produces.
        out = []
        for b, d in zip(base_results, delta_results):
            cand = np.concatenate(
                [np.asarray(b, np.int64),
                 dctx.delta_ids[np.asarray(d, np.int64)]])
            if cand.size == 0:
                out.append(cand)
                continue
            vals = np.where(
                cand < dctx.n,
                dctx.base_cols[self.dim, np.minimum(cand, dctx.n - 1)],
                dctx.delta_rows[np.maximum(cand - dctx.n, 0), self.dim])
            order = np.lexsort((cand, -vals if self.largest else vals))
            out.append(cand[order[: self.k]].astype(np.int64))
        return out

    def host_bytes(self, touched, n):
        return (12.0 * self.k + 4.0) \
            * np.ones_like(np.asarray(touched, np.float64))

    def empty_result(self, n):
        return np.empty((0,), np.int64)

    def result_size(self, res):
        return int(res.size)


@register_result_spec
@dataclasses.dataclass(frozen=True)
class Agg(ResultSpec):
    """A per-query aggregate (min | max | sum) of attribute ``dim`` over the
    matching set. Empty matches finalize to 0.0 (sum) or NaN (min/max)."""

    kind: ClassVar[str] = "agg"
    visits_on_device: ClassVar[bool] = True
    op: str = "sum"
    dim: int = 0

    OPS: ClassVar[tuple[str, ...]] = ("min", "max", "sum")

    def __post_init__(self):
        if self.op not in self.OPS:
            raise ValueError(f"unknown agg op {self.op!r}; options: {self.OPS}")

    @property
    def value_dim(self):
        return self.dim

    @property
    def _fill(self) -> float:
        return {"sum": 0.0, "min": np.inf, "max": -np.inf}[self.op]

    def device_reduce(self, masks, data_cm, *, tile_n, interpret):
        from repro.kernels import reducers
        return reducers.masked_agg(masks, data_cm[self.dim], self.op,
                                   tile_n=tile_n, interpret=interpret)

    def reduce_visits(self, masks, data_cm, qids, bids, valid, visit_index,
                      *, tile_n, n_queries, interpret):
        from repro.kernels import reducers
        vblocks = reducers.gather_visit_values(data_cm, self.dim, bids, tile_n)
        agg = reducers.visit_agg(masks, vblocks, qids, valid, self.op,
                                 n_queries)
        counts = reducers.visit_mask_counts(masks, qids, valid, n_queries)
        return agg, counts

    def combine_visits(self, a, b):
        merge = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}
        return merge[self.op](a[0], b[0]), a[1] + b[1]

    def distributed_reduce(self, mask_local, data_local, axis):
        import jax
        lax = jax.lax
        vals = data_local[self.dim].astype(jnp.float32)
        filled = jnp.where(mask_local != 0, vals, self._fill)
        local = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}[self.op](
            filled, axis=-1)
        merge = {"sum": lax.psum, "min": lax.pmin, "max": lax.pmax}[self.op]
        counts = lax.psum(jnp.sum(mask_local != 0, axis=-1).astype(jnp.int32),
                          axis)
        return merge(local, axis), counts

    def finalize(self, payload, q_n, n):
        agg, counts = payload
        out = []
        for k in range(q_n):
            if int(counts[k]) == 0:
                out.append(self.empty_result(n))
            else:
                out.append(float(agg[k]))
        return out

    def from_ids(self, ids, cols):
        if ids.size == 0:
            return self.empty_result(cols.shape[1])
        vals = cols[self.dim, ids]
        if self.op == "sum":
            # float32 accumulation, matching the device reducer's dtype
            return float(np.sum(vals, dtype=np.float32))
        return float({"min": np.min, "max": np.max}[self.op](vals))

    def merge_delta(self, base_results, delta_results, dctx):
        # NaN marks an empty match set on min/max (the finalizer's empty
        # sentinel), so the combine is NaN-aware; sums add directly (empty
        # sides contribute the 0.0 identity).
        out = []
        for b, d in zip(base_results, delta_results):
            if self.op == "sum":
                out.append(float(b) + float(d))
            elif np.isnan(b):
                out.append(float(d))
            elif np.isnan(d):
                out.append(float(b))
            else:
                out.append(float({"min": min, "max": max}[self.op](b, d)))
        return out

    def host_bytes(self, touched, n):
        return 12.0 * np.ones_like(np.asarray(touched, np.float64))

    def empty_result(self, n):
        return 0.0 if self.op == "sum" else float("nan")

    def result_size(self, res):
        return 1


# Shared default instances (hash-stable jit static args; use these instead of
# constructing fresh specs in hot paths).
IDS = Ids()
COUNT = Count()

# Legacy mode-string vocabulary of the pre-spec protocol.
_MODE_SPECS: dict[str, ResultSpec] = {"ids": IDS, "count": COUNT}


@dataclasses.dataclass(frozen=True)
class VisitHostCtx:
    """Host-side context ``finalize_visits`` needs to map a visit-shaped
    payload back to per-query results (two-phase paths only)."""

    qids: np.ndarray            # (V,) int32 query id per real visit
    bids: np.ndarray            # (V,) int32 block id per real visit
    tile_n: int
    n: int                      # logical object count
    n_queries: int
    perm: Optional[np.ndarray]  # position -> original id (None = identity)


@dataclasses.dataclass(frozen=True)
class DeltaHostCtx:
    """Host-side context ``ResultSpec.merge_delta`` needs to fold per-query
    delta results (local delta coordinates) into base results (original ids).

    Built by ``core.delta.DeltaView.host_ctx``; the value arrays back the
    TopK re-rank's host gather.
    """

    n: int                      # base object count — delta ids start here
    delta_ids: np.ndarray       # (d,) int64 global ids of the delta rows
    base_cols: np.ndarray       # (m, n) base columns
    delta_rows: np.ndarray      # (d, m) delta rows


def validate_mode(mode) -> ResultSpec:
    """Canonicalize a result spec; the one place unknown specs are rejected.

    ``ResultSpec`` instances pass through untouched. The legacy string
    spellings ``"ids"`` / ``"count"`` map to ``Ids()`` / ``Count()`` with a
    single ``DeprecationWarning`` (every layer hands the resolved spec
    object down, so the warning fires once per user call, at the boundary).
    Anything else gets the canonical error.
    """
    if isinstance(mode, ResultSpec):
        return mode
    if isinstance(mode, str) and mode in _MODE_SPECS:
        warnings.warn(
            f"mode={mode!r} strings are deprecated; pass a ResultSpec "
            f"(types.{_MODE_SPECS[mode].kind.capitalize()}()) instead",
            DeprecationWarning, stacklevel=3)
        return _MODE_SPECS[mode]
    raise ValueError(f"unknown mode {mode!r}; options: {RESULT_MODES} "
                     f"or a types.ResultSpec")


def resolve_spec(spec=None, mode=None) -> ResultSpec:
    """Resolve the (spec=..., mode=...) kwarg pair of the public entry points.

    ``spec`` is the typed protocol; ``mode`` is the deprecated string alias.
    Both default to ``Ids()``; passing both is an error (ambiguous intent).
    """
    if spec is not None and mode is not None:
        raise ValueError("pass spec= or the deprecated mode=, not both")
    if spec is None and mode is None:
        return IDS
    return validate_mode(spec if spec is not None else mode)


@dataclasses.dataclass(frozen=True)
class RangeQuery:
    """A multidimensional range query (complete- or partial-match).

    ``lower``/``upper`` always have length ``m``; dimensions not mentioned in
    the query carry ``[-inf, +inf]`` (paper §2.1). ``dims_mask`` records which
    dimensions are actually constrained — engines use it to skip un-queried
    columns (the vertical-partitioning partial-match advantage, §3.2/§5.5).
    """

    lower: np.ndarray  # (m,) float32
    upper: np.ndarray  # (m,) float32

    def __post_init__(self):
        lo = np.asarray(self.lower, dtype=np.float32)
        up = np.asarray(self.upper, dtype=np.float32)
        if lo.shape != up.shape or lo.ndim != 1:
            raise ValueError(f"bad query bounds: {lo.shape} vs {up.shape}")
        object.__setattr__(self, "lower", lo)
        object.__setattr__(self, "upper", up)

    @property
    def m(self) -> int:
        return self.lower.shape[0]

    @property
    def dims_mask(self) -> np.ndarray:
        """(m,) bool — True where the dimension is actually constrained."""
        return ~(np.isneginf(self.lower) & np.isposinf(self.upper))

    @property
    def n_queried_dims(self) -> int:
        return int(self.dims_mask.sum())

    @property
    def is_complete_match(self) -> bool:
        return bool(self.dims_mask.all())

    @staticmethod
    def complete(lower: Sequence[float], upper: Sequence[float]) -> "RangeQuery":
        return RangeQuery(np.asarray(lower, np.float32), np.asarray(upper, np.float32))

    @staticmethod
    def partial(m: int, predicates: dict[int, tuple[float, float]]) -> "RangeQuery":
        """Partial-match query: ``{dim: (lb, ub)}`` over an m-dim space."""
        lo = np.full((m,), NEG_INF, np.float32)
        up = np.full((m,), POS_INF, np.float32)
        for j, (a, b) in predicates.items():
            lo[j], up[j] = np.float32(a), np.float32(b)
        return RangeQuery(lo, up)

    def reorder(self, order: np.ndarray) -> "RangeQuery":
        """Query with dimensions permuted by ``order`` (selectivity ordering)."""
        return RangeQuery(self.lower[order], self.upper[order])


@dataclasses.dataclass(frozen=True)
class QueryBatch:
    """An ordered batch of range queries over the same m-dim space.

    Batched execution: analytical workloads are streams of queries, and the
    fused multi-query kernels (``kernels.multi_scan``) evaluate a whole batch
    per launch. ``QueryBatch`` is the host-side carrier: bounds are stacked
    (Q, m) so the kernels' query-minor (m_pad, Q) layout and the per-query
    constrained-dim lists derive without touching each query again.
    """

    lower: np.ndarray  # (Q, m) float32
    upper: np.ndarray  # (Q, m) float32

    def __post_init__(self):
        lo = np.asarray(self.lower, dtype=np.float32)
        up = np.asarray(self.upper, dtype=np.float32)
        if lo.shape != up.shape or lo.ndim != 2:
            raise ValueError(f"bad batch bounds: {lo.shape} vs {up.shape}")
        object.__setattr__(self, "lower", lo)
        object.__setattr__(self, "upper", up)

    @staticmethod
    def from_queries(queries: Sequence["RangeQuery"]) -> "QueryBatch":
        if not queries:
            raise ValueError("empty query batch")
        m = queries[0].m
        for q in queries:
            if q.m != m:
                raise ValueError(f"mixed dims in batch: {q.m} != {m}")
        return QueryBatch(np.stack([q.lower for q in queries]),
                          np.stack([q.upper for q in queries]))

    def __len__(self) -> int:
        return self.lower.shape[0]

    def __getitem__(self, k: int) -> "RangeQuery":
        return RangeQuery(self.lower[k], self.upper[k])

    @property
    def m(self) -> int:
        return self.lower.shape[1]

    @property
    def queries(self) -> list["RangeQuery"]:
        return [self[k] for k in range(len(self))]

    @property
    def dims_mask(self) -> np.ndarray:
        """(Q, m) bool — True where a dimension is actually constrained."""
        return ~(np.isneginf(self.lower) & np.isposinf(self.upper))

    def bounds_columnar(self, m_pad: int, q_pad: int | None = None,
                        dtype=np.float32) -> tuple[np.ndarray, np.ndarray]:
        """Query-minor (m_pad, q_pad or Q) finite bounds for the fused kernels.

        Padding dims (and unconstrained dims) carry the extrema of ``dtype``
        (the dtype the device comparison runs in), i.e. match-all against any
        finite value; padding *queries* (columns beyond Q, used to round the
        batch to a pow2 jit bucket) are match-all too — callers drop their
        output rows.
        """
        q_n = q_pad or len(self)
        lo = np.full((m_pad, q_n), NEG_INF, np.float32)
        up = np.full((m_pad, q_n), POS_INF, np.float32)
        lo[: self.m, : len(self)] = self.lower.T
        up[: self.m, : len(self)] = self.upper.T
        return finite_query_bounds(lo, up, dtype=dtype)


@dataclasses.dataclass
class Dataset:
    """A columnar in-memory dataset: ``cols[j, i]`` = attribute j of object i.

    ``row(i)`` and ``rows()`` give the row-major view (the paper's horizontal
    layout) when needed.
    """

    cols: np.ndarray  # (m, n) float32

    def __post_init__(self):
        c = np.asarray(self.cols)
        if c.ndim != 2:
            raise ValueError(f"cols must be (m, n), got {c.shape}")
        self.cols = np.ascontiguousarray(c, dtype=np.float32)

    @property
    def m(self) -> int:
        return self.cols.shape[0]

    @property
    def n(self) -> int:
        return self.cols.shape[1]

    @property
    def nbytes(self) -> int:
        return self.cols.nbytes

    def rows(self) -> np.ndarray:
        return np.ascontiguousarray(self.cols.T)

    @staticmethod
    def from_rows(rows: np.ndarray) -> "Dataset":
        rows = np.asarray(rows, np.float32)
        return Dataset(np.ascontiguousarray(rows.T))

    def selectivity(self, q: RangeQuery) -> float:
        """Exact selectivity of ``q`` on this dataset (fraction in [0, 1])."""
        return float(match_mask_np(self.cols, q).mean())


def match_mask_np(cols: np.ndarray, q: RangeQuery) -> np.ndarray:
    """Numpy oracle: (n,) bool mask of objects matching q. O(n·m)."""
    lo = q.lower[:, None]
    up = q.upper[:, None]
    return np.logical_and(cols >= lo, cols <= up).all(axis=0)


def match_ids_np(cols: np.ndarray, q: RangeQuery) -> np.ndarray:
    """Numpy oracle: sorted identifiers of matching objects."""
    return np.nonzero(match_mask_np(cols, q))[0].astype(np.int64)


def mask_to_ids(mask) -> np.ndarray:
    """Device/host mask -> sorted id array (host-side, dynamic shape)."""
    return np.nonzero(np.asarray(mask))[0].astype(np.int64)


def next_pow2(x: int) -> int:
    """Smallest power of two >= x (pow2 buckets bound jit retraces)."""
    return 1 if x <= 1 else 1 << (x - 1).bit_length()


def pad_axis(x: np.ndarray, axis: int, multiple: int, value) -> np.ndarray:
    """Pad ``axis`` of x up to the next multiple of ``multiple`` with value."""
    size = x.shape[axis]
    target = -(-size // multiple) * multiple
    if target == size:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, target - size)
    return np.pad(x, widths, constant_values=value)


def finite_query_bounds(lo: np.ndarray, up: np.ndarray, dtype=np.float32):
    """Replace +-inf with the *target device dtype's* finite extrema.

    ``dtype`` must be the dtype the comparison actually runs in: substituting
    float32 extrema under a bfloat16 cast rounds ``finfo(f32).max`` back to
    ``+inf``, so the +inf object-padding sentinels *match* and every
    padded-axis reduction (scan-mask counts, visit segment counts, psum counts)
    overcounts. ``jnp.finfo`` understands bfloat16 (ml_dtypes); extrema are
    additionally clamped into float32's finite range because these carrier
    arrays are float32 — for a wider dtype (f64 under jax x64) the f32
    extrema are what survive the round trip finite, and all dataset values
    are f32-representable (``Dataset`` stores float32).
    """
    neg = max(numerics.finite_min(dtype), numerics.finite_min(np.float32))
    pos = min(numerics.finite_max(dtype), numerics.finite_max(np.float32))
    lo = np.where(np.isneginf(lo), neg, lo).astype(np.float32)
    up = np.where(np.isposinf(up), pos, up).astype(np.float32)
    return lo, up
