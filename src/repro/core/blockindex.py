"""Shared machinery for blocked (two-phase) MDIS on TPU.

Both tree MDIS in this framework — the blocked kd-tree and the packed STR
R*-tree — reduce at query time to the same TPU-native two-phase plan
(DESIGN.md §2):

  phase 1 (prune):  vectorized MBR-overlap tests over a small hierarchy of
                    per-block bounding boxes (device, one jit call for the
                    whole batch);
  phase 2 (refine): the ``multi_scan_visit`` Pallas kernel scans *only* the
                    surviving (query, leaf block) pairs (grid size =
                    #survivors, so pruned blocks cost nothing — the TPU
                    analogue of subtree pruning). Under Count and Agg the
                    survivors are compacted into the visit list on the
                    device; the other specs build it on the host.

A single query is a batch of one.

What distinguishes the structures is the *build*: how objects are permuted
into leaf blocks (median splits vs sort-tile-recursive vs storage order).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import types as T
from repro.kernels import ops


def build_hierarchy(
    leaf_lo: np.ndarray, leaf_hi: np.ndarray, fanout: int = 64
) -> list[tuple[np.ndarray, np.ndarray]]:
    """Build MBR levels bottom-up from leaf MBRs.

    Args:
      leaf_lo, leaf_hi: (m, n_leaves) per-leaf bounding boxes (columnar).
      fanout: children per inner node.

    Returns:
      Levels from root to leaves: [(lo, hi), ...] each (m, n_nodes_level).
    """
    levels = [(leaf_lo, leaf_hi)]
    lo, hi = leaf_lo, leaf_hi
    while lo.shape[1] > 1:
        n_nodes = lo.shape[1]
        n_up = -(-n_nodes // fanout)
        pad = n_up * fanout - n_nodes
        lo_p = np.pad(lo, ((0, 0), (0, pad)), constant_values=np.inf)
        hi_p = np.pad(hi, ((0, 0), (0, pad)), constant_values=-np.inf)
        lo = lo_p.reshape(lo.shape[0], n_up, fanout).min(axis=2)
        hi = hi_p.reshape(hi.shape[0], n_up, fanout).max(axis=2)
        levels.append((lo, hi))
        if n_up == 1:
            break
    return levels[::-1]  # root first


@functools.partial(jax.jit, static_argnames=("fanout",))
def _prune_hierarchy_batch_jit(
    levels_lo: tuple[jax.Array, ...],
    levels_hi: tuple[jax.Array, ...],
    qlo: jax.Array,
    qhi: jax.Array,
    fanout: int,
) -> jax.Array:
    """Batched top-down MBR pruning: all queries of a batch in one jit call.

    Args:
      levels_lo/hi: root-first tuples of (m, n_nodes) MBR bounds.
      qlo, qhi: (m, Q) query bounds, one column per query.

    Returns:
      (Q, n_leaves) bool — per-query leaf survivors.
    """
    ops.note_trace("prune_hierarchy_batch")
    active = None
    for lo, hi in zip(levels_lo, levels_hi):
        overlap = jnp.all(
            jnp.logical_and(hi[:, None, :] >= qlo[:, :, None],
                            lo[:, None, :] <= qhi[:, :, None]),
            axis=0,
        )  # (Q, n_nodes)
        if active is None:
            active = overlap
        else:
            parents = jnp.repeat(active, fanout, axis=1)[:, : overlap.shape[1]]
            active = jnp.logical_and(parents, overlap)
    return active


prune_hierarchy_batch = ops.counted(
    "prune_hierarchy_batch",
    "Batched phase-1 MBR hierarchy prune: every query of a batch in one "
    "vectorized launch -> the (Q, n_leaves) survivor mask, which the visit "
    "launch takes on the device or the host syncs (see "
    "``BlockedIndex.launch_batch``).",
)(_prune_hierarchy_batch_jit)


_next_pow2 = T.next_pow2


def _pad_visit_list(
    query_ids: np.ndarray, block_ids: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """Pad a flattened (query, block) visit list to a pow2 jit bucket.

    Padding rows carry query 0 / block -1 — the visit kernel clamps negative
    block ids to 0, so callers must drop (ids mode) or zero out (count mode)
    the padding rows' output.
    """
    n_visit = _next_pow2(query_ids.size)
    qids_p = np.zeros((n_visit,), np.int32)
    bids_p = np.full((n_visit,), -1, np.int32)
    qids_p[: query_ids.size] = query_ids
    bids_p[: block_ids.size] = block_ids
    return qids_p, bids_p


def _build_visit_index(query_ids: np.ndarray, n_queries: int,
                       n_visit_pad: int) -> np.ndarray:
    """(n_queries, M) table of padded-visit row indices per query.

    M is the pow2-padded maximum visit count of any query (bounds jit
    retraces); empty slots point at row ``n_visit_pad`` — the sentinel fill
    row the top-k visit reducer appends. One argsort pass, no Python loop
    over queries.
    """
    counts = np.bincount(query_ids, minlength=n_queries)
    m_vis = _next_pow2(max(int(counts.max(initial=0)), 1))
    index = np.full((n_queries, m_vis), n_visit_pad, np.int32)
    order = np.argsort(query_ids, kind="stable")
    starts = np.zeros(n_queries + 1, np.int64)
    np.cumsum(counts, out=starts[1:])
    slots = np.arange(query_ids.size) - starts[query_ids[order]]
    index[query_ids[order], slots] = order.astype(np.int32)
    return index


def _delta_launch(delta, data_dev: jax.Array, tile_n: int,
                  perm: np.ndarray | None) -> tuple:
    """What a visit launch needs of a delta -> ``(dcm, tomb, merge)``.

    ``dcm`` is the delta rows' device block (None when there are none),
    ``tomb`` the base tombstones in storage order (None when there are
    none), and ``merge(spec, base_results, delta_host, q_n)`` folds the
    delta's payload into the base results, with the delta's host context
    captured here, at launch. All three are None for an empty delta.
    """
    dview = delta if delta is not None and not delta.is_empty else None
    if dview is None:
        return None, None, None
    key = None if perm is None else ("perm", id(perm), int(data_dev.shape[1]))
    tomb = dview.base_tomb_dev(data_dev.shape[1], perm=perm, key=key)
    d_n, host_ctx = dview.d, dview.host_ctx()

    def merge(spec, base, delta_host, q_n):
        return spec.merge_delta(base, spec.finalize(delta_host, q_n, d_n),
                                host_ctx)
    return dview.device_cm(tile_n), tomb, merge


def launch_visits_batch(
    data_dev: jax.Array,
    query_ids: np.ndarray,
    block_ids: np.ndarray,
    batch: T.QueryBatch,
    tile_n: int,
    n_queries: int,
    spec: T.ResultSpec,
    n: int,
    perm: np.ndarray | None = None,
    delta=None,
) -> tuple:
    """Phase 2 of the host route, under any ResultSpec: one launch over a
    host-built visit list, no host sync.

    Returns ``(payload, finalize)``; the caller owns the single counted
    ``ops.device_get(payload)`` and hands its host value to ``finalize`` —
    which is what lets the pipelined server run the sync + host finalizers on
    a different thread from the launch. ``payload`` is ``None`` (and the
    host value ignored) when nothing pruned through on a frozen dataset —
    that corner has no device work at all.
    """
    dcm, tomb, merge = _delta_launch(delta, data_dev, tile_n, perm)
    if query_ids.size == 0:
        # Nothing pruned through — but a non-empty delta still has to scan.
        # This corner pays one delta-only launch (vs zero on a frozen
        # dataset); the normal non-empty-visit case stays at one launch.
        base = [spec.empty_result(n) for _ in range(n_queries)]
        if dcm is None:
            return None, lambda _host: base
        lo_d, up_d = ops.batch_bounds_device(batch, dcm.shape[0], dcm.dtype,
                                             q_pad=_next_pow2(len(batch)))
        payload = ops.multi_scan_reduce(dcm, lo_d, up_d, spec=spec,
                                        tile_n=tile_n)
        return payload, lambda host: merge(spec, base, host, n_queries)
    qids_p, bids_p = _pad_visit_list(query_ids, block_ids)
    q_bucket = _next_pow2(max(n_queries, 1))  # pow2 bounds jit retraces
    # The per-query visit-index table only feeds TopK's gather; every other
    # spec ignores it, so it is built (and shipped) on demand — a (1, 1)
    # placeholder keeps the jit signature stable for the rest.
    if spec.needs_visit_index:
        visit_index = _build_visit_index(query_ids.astype(np.int64), q_bucket,
                                         qids_p.size)
    else:
        visit_index = np.zeros((1, 1), np.int32)
    lo_d, up_d = ops.batch_bounds_device(batch, data_dev.shape[0],
                                         data_dev.dtype,
                                         q_pad=_next_pow2(len(batch)))
    payload = ops.multi_visit_reduce(
        data_dev, (jnp.asarray(qids_p), jnp.asarray(bids_p)),
        jnp.asarray(visit_index), lo_d, up_d, dcm, tomb,
        spec=spec, tile_n=tile_n, n_queries=q_bucket,
    )
    vctx = T.VisitHostCtx(
        qids=query_ids.astype(np.int32), bids=block_ids.astype(np.int32),
        tile_n=tile_n, n=n, n_queries=n_queries, perm=perm)
    if dcm is None:
        def finalize(host_payload):
            return spec.finalize_visits(host_payload, vctx)
        return payload, finalize

    def finalize_delta(host_payload):
        base_host, delta_host = host_payload
        return merge(spec, spec.finalize_visits(base_host, vctx), delta_host,
                     n_queries)
    return payload, finalize_delta


def scatter_visit_results(
    masks: np.ndarray,
    query_ids: np.ndarray,
    block_ids: np.ndarray,
    n_queries: int,
    tile_n: int,
    n: int,
    perm: np.ndarray | None = None,
) -> list[np.ndarray]:
    """Turn fused (V, tile_n) visit masks back into per-query sorted id arrays.

    Shared tail of every batched two-phase path (tree and VA-file): each visit
    row holds the match mask of one (query, block) pair; positions map through
    ``perm`` (when the structure permuted objects) and object padding drops.
    Visit rows are grouped by query with one argsort + searchsorted pass
    (O(V log V)) instead of rescanning the whole visit list per query (O(Q·V)).
    """
    results: list[np.ndarray] = [np.empty((0,), np.int64) for _ in range(n_queries)]
    offsets = np.arange(tile_n)
    order = np.argsort(query_ids, kind="stable")
    qids_sorted = query_ids[order]
    bounds = np.searchsorted(qids_sorted, np.arange(n_queries + 1))
    for k in range(n_queries):
        rows = order[bounds[k]: bounds[k + 1]]
        if rows.size == 0:
            continue
        pos = block_ids[rows][:, None] * tile_n + offsets[None, :]
        pos = pos[masks[rows] > 0]
        pos = pos[pos < n]
        if perm is not None:
            pos = perm[pos]
        results[k] = np.sort(pos).astype(np.int64)
    return results


@dataclasses.dataclass
class BlockedIndex:
    """A built blocked MDIS instance (query-side shared by kd-tree / R-tree).

    Attributes:
      name: structure name ("kdtree" | "rstar").
      data_dev: (m_pad, n_pad) permuted columnar data on device.
      perm: (n,) original object id of each permuted position.
      levels: root-first MBR hierarchy, device arrays.
      tile_n: leaf block size (objects per leaf).
      m, n: logical sizes.
    """

    name: str
    data_dev: jax.Array
    perm: np.ndarray
    levels_lo: tuple[jax.Array, ...]
    levels_hi: tuple[jax.Array, ...]
    fanout: int
    tile_n: int
    m: int
    n: int

    # (query, block) pairs the last batch visited (benchmarks, calibration)
    last_visited_blocks: int = 0

    @property
    def n_leaves(self) -> int:
        return self.data_dev.shape[1] // self.tile_n

    @property
    def nbytes_index(self) -> int:
        """Extra memory vs a plain scan (MBR hierarchy; paper §7.2 metric)."""
        return sum(int(np.prod(l.shape)) * 4 * 2 for l in self.levels_lo)

    def launch_batch(self, batch: T.QueryBatch, spec: T.ResultSpec = T.IDS,
                     delta=None) -> tuple:
        """Device half of the batched two-phase query -> (payload, finalize).

        Two routes, chosen by the spec alone (``visits_on_device``):

        * device route (Count, Agg): the prune's (Q, n_leaves) survivor mask
          goes straight from the prune launch to the visit launch, which
          compacts it into the visit list on the device and visits as many
          pairs as survived (``ops.multi_visit_reduce``). Nothing syncs
          here; the payload carries the pair count, and the returned
          ``finalize`` is the batch's one sync.
        * host route (Ids, Mask, TopK, whose finalizers read the visit list
          on the host): the survivor mask syncs here, mid-stage, and the
          host builds the pow2-padded visit list the visit launch takes;
          ``finalize`` then syncs the payload.

        Padding queries of the pow2 query bucket carry NaN bounds, which
        overlap no block. On the host route ``payload`` is None (host value
        ignored) when nothing pruned through on a frozen dataset.
        """
        spec = T.validate_mode(spec).validate(self.m)
        q_n = len(batch)
        q_pad = _next_pow2(q_n)  # pow2 query bucket bounds jit retraces
        qlo, qhi = batch.bounds_columnar(self.m, q_pad)
        qlo[:, q_n:] = qhi[:, q_n:] = np.nan
        leaf_mask = prune_hierarchy_batch(
            self.levels_lo, self.levels_hi,
            jnp.asarray(qlo), jnp.asarray(qhi), fanout=self.fanout,
        )  # (q_pad, n_leaves)
        if spec.visits_on_device:
            return self._launch_survivors(leaf_mask, batch, spec, delta)
        qids, bids = np.nonzero(ops.device_get(leaf_mask, stage="launch",
                                               path=self.name))
        self.last_visited_blocks = int(qids.size)
        ops.count_visit_pairs(self.name, "host", qids.size)
        return launch_visits_batch(
            self.data_dev, qids.astype(np.int32), bids.astype(np.int32),
            batch, self.tile_n, q_n, spec, self.n, perm=self.perm,
            delta=delta,
        )

    def _launch_survivors(self, leaf_mask: jax.Array, batch: T.QueryBatch,
                          spec: T.ResultSpec, delta) -> tuple:
        """The device route of ``launch_batch``: one visit launch over the
        prune mask's survivors, no sync."""
        q_n = len(batch)
        dcm, tomb, merge = _delta_launch(delta, self.data_dev, self.tile_n,
                                         self.perm)
        lo_d, up_d = ops.batch_bounds_device(batch, self.data_dev.shape[0],
                                             self.data_dev.dtype,
                                             q_pad=leaf_mask.shape[0])
        payload = ops.multi_visit_reduce(
            self.data_dev, leaf_mask, None, lo_d, up_d, dcm, tomb,
            spec=spec, tile_n=self.tile_n, n_queries=leaf_mask.shape[0],
        )

        def finalize(host_payload):
            (visits, n_pairs), delta_host = (
                host_payload if dcm is not None else (host_payload, None))
            self.last_visited_blocks = int(n_pairs)
            ops.count_visit_pairs(self.name, "device", int(n_pairs))
            res = spec.finalize(visits, q_n, self.n)
            return res if dcm is None else merge(spec, res, delta_host, q_n)
        return payload, finalize

    def query_batch(self, batch: T.QueryBatch, spec: T.ResultSpec = T.IDS,
                    delta=None) -> list:
        """Batched two-phase query: one counted prune launch + one fused
        visit launch + the payload sync (and, on the host route, the
        survivor-mask sync between the launches; see ``launch_batch``).

        Phase 1 prunes all Q queries' hierarchies in a single vectorized
        call; phase 2 flattens the surviving (query, block) pairs into one
        ``multi_visit_reduce`` launch that carries the ResultSpec's
        on-device reducer, so per-query dispatch and host-sync taxes are
        paid once per batch and reduced shapes (count, top-k, aggregate)
        ship only their payload. Both phases are visible to the launch /
        host-sync counters (mdrqlint's host-sync rule keeps it that way).
        Positions map through ``perm`` in the spec's finalizer (counts and
        aggregates are permutation-invariant).
        """
        payload, fin = self.launch_batch(batch, spec=spec, delta=delta)
        return fin(ops.device_get(payload) if payload is not None else None)


def finish_build(
    name: str,
    cols_perm: np.ndarray,
    perm: np.ndarray,
    tile_n: int,
    fanout: int,
    dtype=jnp.float32,
) -> BlockedIndex:
    """Common tail of every build: pad, compute leaf MBRs, build hierarchy.

    Args:
      cols_perm: (m, n) columnar data already permuted into leaf order.
      perm: (n,) original id per permuted position.
    """
    m, n = cols_perm.shape
    padded, _, _ = ops.prepare_columnar(cols_perm, tile_n=tile_n)
    n_leaves = padded.shape[1] // tile_n
    blocks = padded[:m].reshape(m, n_leaves, tile_n)
    # +inf object padding poisons MBR lows/highs of the last block; mask it.
    leaf_lo = np.where(np.isposinf(blocks), np.inf, blocks).min(axis=2)
    leaf_hi = np.where(np.isposinf(blocks), -np.inf, blocks).max(axis=2)
    levels = build_hierarchy(leaf_lo, leaf_hi, fanout=fanout)
    return BlockedIndex(
        name=name,
        data_dev=jnp.asarray(padded, dtype=dtype),
        perm=np.asarray(perm),
        levels_lo=tuple(jnp.asarray(lo) for lo, _ in levels),
        levels_hi=tuple(jnp.asarray(hi) for _, hi in levels),
        fanout=fanout,
        tile_n=tile_n,
        m=m,
        n=n,
    )
