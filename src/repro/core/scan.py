"""Parallel scans (TPU adaptation of the paper's §3 / §5.4 / §5.5).

Three scan flavors, mirroring the paper's contestants:

  * ``ColumnarScan.query``          — complete-match scan over the columnar
    layout via the ``range_scan`` Pallas kernel (vectorized, all dims fused).
  * ``ColumnarScan.query_partial``  — partial-match scan via the
    ``range_scan_vertical`` kernel: compares only the queried dimensions
    (the paper's vertical-partitioning advantage, §5.5).
  * ``RowScan.query``               — row-major layout scan (the paper's
    horizontal partitioning, §5.4) — kept for the layout ablation.

The paper's multi-threading dimension (horizontal partitioning over t threads)
maps to sharding over devices and lives in ``core.distributed``.

Batched execution: ``mask_batch`` / ``mask_batch_partial`` evaluate a whole
``QueryBatch`` through the fused multi-query kernels (``kernels.multi_scan``)
— one launch per batch instead of one per query, with the query axis padded
to a pow2 bucket so arbitrary batch sizes hit a bounded set of jit traces.

Result shapes: ``query_batch(batch, spec=...)`` takes any ``types.ResultSpec``
— the fused kernel and the spec's on-device reducer run as one launch
(``ops.multi_scan_reduce`` / ``multi_scan_vertical_reduce``), so counts,
top-k, and aggregates ship only their payload across the device->host
boundary and the per-query host-side ``nonzero`` — the dominant cost for
large result sets — never runs. The single-query ``count`` /
``count_partial`` / ``count_batch`` fast paths reduce via ``ops.mask_counts``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import types as T
from repro.kernels import ops


def bucketed_batch_bounds(batch: T.QueryBatch, m_pad: int, dtype
                          ) -> tuple[int, jax.Array, jax.Array]:
    """(q_pad, lo, up): pow2-bucketed device bounds for one fused batch launch.

    The query axis rounds up to the next power of two so arbitrary batch sizes
    hit a bounded set of jit traces; padding columns are match-all and their
    output rows are dropped by the caller. Shared by ``ColumnarScan`` and
    ``DistributedScan`` so both batch paths bucket identically.
    """
    q_pad = T.next_pow2(len(batch))
    lo, up = ops.batch_bounds_device(batch, m_pad, dtype, q_pad=q_pad)
    return q_pad, lo, up


@dataclasses.dataclass
class ColumnarScan:
    """Full-scan engine over dimension-major data."""

    data_dev: jax.Array  # (m_pad, n_pad)
    m: int
    n: int
    tile_n: int = 1024

    @property
    def nbytes_index(self) -> int:
        return 0  # a scan needs no auxiliary structures (paper §8)

    def mask(self, q: T.RangeQuery) -> np.ndarray:
        """(n,) bool match mask (complete or partial match)."""
        qlo, qhi = ops.query_bounds_device(q, self.data_dev.shape[0], self.data_dev.dtype)
        out = ops.range_scan(self.data_dev, qlo, qhi, tile_n=self.tile_n)
        return ops.device_get(out)[: self.n] > 0

    def mask_partial(self, q: T.RangeQuery) -> np.ndarray:
        """(n,) bool mask touching only the queried dimensions."""
        if not q.dims_mask.any():
            return np.ones((self.n,), bool)
        qlo, qhi = ops.query_bounds_device(q, self.data_dev.shape[0], self.data_dev.dtype)
        out = ops.range_scan_vertical(self.data_dev, qlo, qhi,
                                      tile_n=self.tile_n)
        return ops.device_get(out)[: self.n] > 0

    def query(self, q: T.RangeQuery) -> np.ndarray:
        return np.nonzero(self.mask(q))[0].astype(np.int64)

    def query_partial(self, q: T.RangeQuery) -> np.ndarray:
        return np.nonzero(self.mask_partial(q))[0].astype(np.int64)

    # -- count-only results (device-side reduction, no id materialization) --
    def count(self, q: T.RangeQuery) -> int:
        """Match count from one scan launch + one scalar transfer."""
        qlo, qhi = ops.query_bounds_device(q, self.data_dev.shape[0], self.data_dev.dtype)
        out = ops.range_scan(self.data_dev, qlo, qhi, tile_n=self.tile_n)
        return int(ops.device_get(ops.mask_counts(out)))

    def count_partial(self, q: T.RangeQuery) -> int:
        """Match count touching only the queried dimensions' columns."""
        if not q.dims_mask.any():
            return self.n
        qlo, qhi = ops.query_bounds_device(q, self.data_dev.shape[0], self.data_dev.dtype)
        out = ops.range_scan_vertical(self.data_dev, qlo, qhi,
                                      tile_n=self.tile_n)
        return int(ops.device_get(ops.mask_counts(out)))

    # -- batched execution (fused multi-query kernels) ---------------------
    # The query axis pads to a pow2 bucket (match-all padding columns, rows
    # dropped here) so arbitrary batch sizes hit a bounded set of jit traces.
    def mask_batch(self, batch: T.QueryBatch) -> np.ndarray:
        """(Q, n) bool match masks from one fused full-scan launch."""
        out = self._mask_batch_device(batch, partial=False)
        return ops.device_get(out)[: len(batch), : self.n] > 0

    def mask_batch_partial(self, batch: T.QueryBatch) -> np.ndarray:
        """(Q, n) bool masks touching only each query's constrained dims."""
        out = self._mask_batch_device(batch, partial=True)
        return ops.device_get(out)[: len(batch), : self.n] > 0

    def _mask_batch_device(self, batch: T.QueryBatch, partial: bool) -> jax.Array:
        """(q_pad, n_pad) device masks from one fused launch (rows >= Q and
        columns >= n are padding; object padding never matches)."""
        q_pad, lo, up = self._launch_bounds(batch, partial)
        if partial:
            return ops.multi_range_scan_vertical(self.data_dev, lo, up,
                                                 tile_n=self.tile_n)
        return ops.multi_range_scan(self.data_dev, lo, up, tile_n=self.tile_n)

    def _launch_bounds(self, batch: T.QueryBatch, partial: bool
                       ) -> tuple[int, jax.Array, jax.Array]:
        """``bucketed_batch_bounds`` of one scan launch, whose compared and
        skipped (chunk, row) pairs are counted here on the host."""
        m_pad = self.data_dev.shape[0]
        q_pad, lo, up = bucketed_batch_bounds(batch, m_pad,
                                              self.data_dev.dtype)
        ops.count_scan_rows("vertical" if partial else "full",
                            batch.dims_mask, q_pad, m_pad)
        return q_pad, lo, up

    def count_batch(self, batch: T.QueryBatch, partial: bool = False
                    ) -> list[int]:
        """Per-query match counts: one fused launch, one O(Q) host transfer."""
        out = self._mask_batch_device(batch, partial)
        counts = ops.device_get(ops.mask_counts(out))[: len(batch)]
        return [int(c) for c in counts]

    def query_batch(self, batch: T.QueryBatch, partial: bool = False,
                    spec: T.ResultSpec = T.IDS, delta=None) -> list:
        """Batched execution under any ResultSpec: the fused multi-query
        kernel and the spec's on-device reducer run as one launch, the
        payload crosses in one host sync, and the spec's host finalizer
        types the per-query results (ids / counts / masks / top-k ids /
        aggregates).

        ``delta`` (a ``core.delta.DeltaView``) folds the mutable data plane
        into the same launch: base tombstones AND into the masks on device,
        the delta block scans with the same bounds, and the spec merges the
        two finalized halves — still one launch + one host sync.
        """
        payload, fin = self.launch_batch(batch, partial=partial, spec=spec,
                                         delta=delta)
        return fin(ops.device_get(payload))

    def launch_batch(self, batch: T.QueryBatch, partial: bool = False,
                     spec: T.ResultSpec = T.IDS, delta=None):
        """Device half of ``query_batch``: issue the one fused launch and
        return ``(payload, finalize)`` without synchronizing.

        ``payload`` is the in-flight device value; ``finalize(host_payload)``
        — where ``host_payload`` is the caller's single counted
        ``ops.device_get(payload)`` — runs the spec's host finalizer (and the
        delta merge) and types the per-query results. The split is what the
        pipelined server overlaps: batch k+1 launches while batch k's
        finalize runs on another thread; composing the halves back-to-back is
        exactly the synchronous path with an unchanged launch/sync budget.
        """
        spec = T.validate_mode(spec).validate(self.m)
        q_pad, lo, up = self._launch_bounds(batch, partial)
        dcm = tomb = None
        if delta is not None and not delta.is_empty:
            dcm = delta.device_cm(self.tile_n)
            tomb = delta.base_tomb_dev(self.data_dev.shape[1])
        if partial:
            payload = ops.multi_scan_vertical_reduce(
                self.data_dev, lo, up, dcm, tomb, spec=spec,
                tile_n=self.tile_n)
        else:
            payload = ops.multi_scan_reduce(self.data_dev, lo, up, dcm, tomb,
                                            spec=spec, tile_n=self.tile_n)
        n_q, n, d_n = len(batch), self.n, delta.d if dcm is not None else 0
        if dcm is None:
            def finalize(host_payload):
                return spec.finalize(host_payload, n_q, n)
        else:
            host_ctx = delta.host_ctx()

            def finalize(host_payload):
                base_host, delta_host = host_payload
                base = spec.finalize(base_host, n_q, n)
                dres = spec.finalize(delta_host, n_q, d_n)
                return spec.merge_delta(base, dres, host_ctx)
        return payload, finalize


def build_columnar_scan(dataset: T.Dataset, tile_n: int = 1024) -> ColumnarScan:
    padded, m, n = ops.prepare_columnar(dataset.cols, tile_n=tile_n)
    return ColumnarScan(data_dev=jnp.asarray(padded), m=m, n=n, tile_n=tile_n)


@dataclasses.dataclass
class RowScan:
    """Row-major layout scan (horizontal partitioning analogue)."""

    data_dev: jax.Array  # (n_pad, m_pad)
    m: int
    n: int
    tile_rows: int = 512

    @property
    def nbytes_index(self) -> int:
        return 0

    def _mask_device(self, q: T.RangeQuery) -> jax.Array:
        qlo, qhi = ops.query_bounds_device(q, self.data_dev.shape[1], self.data_dev.dtype)
        return ops.range_scan_rows(
            self.data_dev, qlo.T, qhi.T, tile_rows=self.tile_rows
        )

    def mask(self, q: T.RangeQuery) -> np.ndarray:
        return ops.device_get(self._mask_device(q))[: self.n] > 0

    def query(self, q: T.RangeQuery) -> np.ndarray:
        return np.nonzero(self.mask(q))[0].astype(np.int64)

    def count(self, q: T.RangeQuery) -> int:
        """Match count summed on device (+inf padding rows never match)."""
        return int(ops.device_get(ops.mask_counts(self._mask_device(q))))


def build_row_scan(dataset: T.Dataset, tile_rows: int = 512) -> RowScan:
    rows = dataset.rows()  # (n, m)
    rows = T.pad_axis(rows, 1, 8, 0.0)       # dim padding: match-all bounds
    rows = T.pad_axis(rows, 0, tile_rows, np.inf)  # object padding: never match
    return RowScan(data_dev=jnp.asarray(rows), m=dataset.m, n=dataset.n,
                   tile_rows=tile_rows)


@jax.jit
def _xla_scan_mask_jit(data_cm: jax.Array, qlo: jax.Array,
                       qhi: jax.Array) -> jax.Array:
    ops.note_trace("xla_scan_mask")
    ok = jnp.logical_and(data_cm >= qlo, data_cm <= qhi)
    return jnp.all(ok, axis=0)


xla_scan_mask = ops.counted(
    "xla_scan_mask",
    "Plain-XLA (non-Pallas) columnar scan — the 'unoptimized baseline' the "
    "Pallas kernel is benchmarked against (paper's scalar-vs-SIMD axis).",
)(_xla_scan_mask_jit)


def numpy_scan_ids(cols: np.ndarray, q: T.RangeQuery) -> np.ndarray:
    """Single-core numpy scan — the host-side baseline."""
    return T.match_ids_np(cols, q)
