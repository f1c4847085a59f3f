"""Parallel scans (TPU adaptation of the paper's §3 / §5.4 / §5.5).

``ColumnarScan`` answers a ``QueryBatch`` (a single query is a batch of one)
through the fused multi-query scan kernel (``kernels.multi_scan``) in one of
two flavors, mirroring the paper's contestants:

  * ``query_batch(batch)``               — the full scan over the columnar
    layout (vectorized, all dims fused);
  * ``query_batch(batch, partial=True)`` — the partial-match (vertical)
    scan (§5.5), the same kernel counted and named apart.

The query axis pads to a pow2 bucket so arbitrary batch sizes hit a bounded
set of jit traces. The paper's multi-threading dimension (horizontal
partitioning over t threads) maps to sharding over devices and lives in
``core.distributed``.

Result shapes: ``query_batch(batch, spec=...)`` takes any
``types.ResultSpec`` — the fused kernel and the spec's on-device reducer run
as one launch (``ops.multi_scan_reduce`` / ``multi_scan_vertical_reduce``),
so counts, top-k, and aggregates ship only their payload across the
device->host boundary and the per-query host-side ``nonzero`` — the dominant
cost for large result sets — never runs.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

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
