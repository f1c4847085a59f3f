"""Distributed MDRQ execution — horizontal partitioning over devices.

The paper's horizontal partitioning (§3.1) assigns n/t objects to each of t
threads, runs the same search per partition, and concatenates partial results.
The TPU mapping (DESIGN.md §2): the object axis of the columnar array shards
over the ``data`` mesh axis via ``shard_map``; every device runs the identical
Pallas scan on its local (m_pad, n_pad/p) shard. The paper's "concatenate
partial result sets" becomes a no-op — the output mask inherits the input
sharding — and the only collective in the system is an optional ``psum`` for
global match counts. Load balancing is inherited from random object placement,
exactly as in the paper.

Batched execution (cross-device × multi-query): ``distributed_multi_mask`` /
``distributed_multi_counts`` wrap the fused multi-query kernels
(``kernels.multi_scan``) in the same shard_map — data sharded ``P(None,
"data")``, the (m_pad, Q) query bounds replicated — so one collective launch
answers a whole batch on every device at once. In count mode the per-device
(Q,) partial counts reduce through a single ``psum`` and only O(Q) ints ever
cross the collective *and* the host boundary. ``DistributedScan.query_batch``
buckets the query axis to pow2 exactly like ``ColumnarScan`` so both engines
share jit traces per batch-size bucket.

Instrumentation: every entry point here is registered through
``kernels.ops.counted`` and every device->host read goes through
``ops.device_get`` — the distributed path pays the same launch/host-sync
accounting the single-device ops do, so counter-based budget tests see it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import types as T
from repro.kernels import ops
from repro.kernels import multi_scan as _ms
from repro.kernels import range_scan as _rs


def shard_map_compat(f, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` with the vma check off: pallas_call outputs carry no
    varying-manual-axes metadata. The one place the repo builds a shard_map
    (mdrqlint's ``raw-shard-map`` rule points here)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


def make_data_mesh(n_devices: int | None = None) -> Mesh:
    """1-D mesh over all (or the first k) local devices: axis 'data'."""
    devs = jax.devices()
    k = n_devices or len(devs)
    return Mesh(np.asarray(devs[:k]), ("data",))


def shard_columnar(mesh: Mesh, padded_cols: np.ndarray, tile_n: int = 1024) -> jax.Array:
    """Place (m_pad, n_pad) columnar data sharded over objects.

    n_pad must divide by (#devices * tile_n) — callers pad with +inf sentinels
    via ``ops.prepare_columnar`` using tile_n * axis_size.
    """
    n_dev = mesh.shape["data"]
    m_pad, n_pad = padded_cols.shape
    assert n_pad % (n_dev * tile_n) == 0, (n_pad, n_dev, tile_n)
    # Straight from host numpy into the sharding: each device receives only
    # its own slice (a jnp.asarray first would land the whole array on one).
    return jax.device_put(padded_cols, NamedSharding(mesh, P(None, "data")))


def _local_scan(data_local, lo, up, *, tile_n: int, interpret: bool):
    """One device's full scan of its object shard (backend-dispatched)."""
    if ops.use_xla():
        from repro.kernels import ref as _ref
        return _ref.range_scan_ref(data_local, lo, up)
    return _rs.range_scan_tiles(data_local, lo, up, tile_n=tile_n,
                                interpret=interpret)


def _local_multi_scan(data_local, lo, up, *, tile_n: int, interpret: bool):
    """One device's fused multi-query scan of its shard -> (Q, n_local)."""
    if ops.use_xla():
        from repro.kernels import ref as _ref
        return _ref.multi_scan_ref(data_local, lo, up)
    return _ms.multi_scan_tiles(data_local, lo, up, tile_n=tile_n,
                                interpret=interpret)


@functools.partial(jax.jit, static_argnames=("mesh", "tile_n", "interpret"))
def _distributed_mask_jit(
    mesh: Mesh,
    data_sharded: jax.Array,
    qlo: jax.Array,
    qhi: jax.Array,
    *,
    tile_n: int = 1024,
    interpret: bool | None = None,
) -> jax.Array:
    ops.note_trace("distributed_mask")
    if interpret is None:
        interpret = ops.default_interpret()

    def local_scan(data_local, lo, up):
        return _local_scan(data_local, lo, up, tile_n=tile_n,
                           interpret=interpret)

    fn = shard_map_compat(
        local_scan,
        mesh=mesh,
        in_specs=(P(None, "data"), P(), P()),
        out_specs=P("data"),
    )
    return fn(data_sharded, qlo, qhi)


distributed_mask = ops.counted(
    "distributed_mask",
    "Sharded single-query match mask: each device scans its own object shard "
    "-> (n_pad,) int8, output sharded over 'data'.",
)(_distributed_mask_jit)


@functools.partial(jax.jit, static_argnames=("mesh", "tile_n", "interpret"))
def _distributed_count_jit(
    mesh: Mesh,
    data_sharded: jax.Array,
    qlo: jax.Array,
    qhi: jax.Array,
    *,
    tile_n: int = 1024,
    interpret: bool | None = None,
) -> jax.Array:
    ops.note_trace("distributed_count")
    if interpret is None:
        interpret = ops.default_interpret()

    def local_count(data_local, lo, up):
        mask = _local_scan(data_local, lo, up, tile_n=tile_n,
                           interpret=interpret)
        return jax.lax.psum(mask.astype(jnp.int32).sum(), "data")

    fn = shard_map_compat(
        local_count,
        mesh=mesh,
        in_specs=(P(None, "data"), P(), P()),
        out_specs=P(),
    )
    return fn(data_sharded, qlo, qhi)


distributed_count = ops.counted(
    "distributed_count",
    "Global single-query match count — one psum over the data axis (the "
    "paper's result concatenation reduced to its cheapest sufficient "
    "collective).",
)(_distributed_count_jit)


@functools.partial(jax.jit, static_argnames=("mesh", "tile_n", "interpret"))
def _distributed_multi_mask_jit(
    mesh: Mesh,
    data_sharded: jax.Array,
    lower: jax.Array,
    upper: jax.Array,
    *,
    tile_n: int = 1024,
    interpret: bool | None = None,
) -> jax.Array:
    ops.note_trace("distributed_multi_mask")
    if interpret is None:
        interpret = ops.default_interpret()

    def local_multi(data_local, lo, up):
        return _local_multi_scan(data_local, lo, up, tile_n=tile_n,
                                 interpret=interpret)

    fn = shard_map_compat(
        local_multi,
        mesh=mesh,
        in_specs=(P(None, "data"), P(), P()),
        out_specs=P(None, "data"),
    )
    return fn(data_sharded, lower, upper)


distributed_multi_mask = ops.counted(
    "distributed_multi_mask",
    "Cross-device fused batch scan: every device evaluates the whole (m_pad, "
    "Q) replicated query batch against its own object shard in one "
    "collective launch -> (Q, n_pad) int8 masks sharded over objects.",
)(_distributed_multi_mask_jit)


@functools.partial(jax.jit, static_argnames=("mesh", "tile_n", "interpret"))
def _distributed_multi_counts_jit(
    mesh: Mesh,
    data_sharded: jax.Array,
    lower: jax.Array,
    upper: jax.Array,
    *,
    tile_n: int = 1024,
    interpret: bool | None = None,
) -> jax.Array:
    ops.note_trace("distributed_multi_counts")
    if interpret is None:
        interpret = ops.default_interpret()

    def local_multi_counts(data_local, lo, up):
        mask = _local_multi_scan(data_local, lo, up, tile_n=tile_n,
                                 interpret=interpret)
        # (Q,) partial counts per device; one psum concatenates the paper's
        # partial result sets — only O(Q) ints cross the collective.
        return jax.lax.psum(jnp.sum(mask != 0, axis=-1).astype(jnp.int32),
                            "data")

    fn = shard_map_compat(
        local_multi_counts,
        mesh=mesh,
        in_specs=(P(None, "data"), P(), P()),
        out_specs=P(),
    )
    return fn(data_sharded, lower, upper)


distributed_multi_counts = ops.counted(
    "distributed_multi_counts",
    "Cross-device fused batch count: per-device (Q,) partial counts reduced "
    "via one psum -> (Q,) int32 global match counts, replicated.",
)(_distributed_multi_counts_jit)


@functools.partial(jax.jit, static_argnames=("mesh", "spec", "tile_n",
                                             "interpret"))
def _distributed_multi_reduce_jit(
    mesh: Mesh,
    data_sharded: jax.Array,
    lower: jax.Array,
    upper: jax.Array,
    delta_cm: jax.Array | None = None,
    base_tomb: jax.Array | None = None,
    *,
    spec,
    tile_n: int = 1024,
    interpret: bool | None = None,
):
    ops.note_trace("distributed_multi_reduce")
    if interpret is None:
        interpret = ops.default_interpret()

    # Ids/Mask payloads stay sharded over objects (the paper's "partial
    # result sets", never concatenated); reduced payloads replicate.
    out_specs = P(None, "data") if spec.sharded_payload else P()

    if base_tomb is None:
        def local_reduce(data_local, lo, up):
            mask = _local_multi_scan(data_local, lo, up, tile_n=tile_n,
                                     interpret=interpret)
            # Shard-local partials + the spec's collective merge (psum
            # counts, pmin/pmax/psum aggregates, all_gather'd (Q, k) top-k
            # partials) — mirroring the count psum: only the reduced payload
            # crosses the collective. Identity specs return the shard-local
            # mask.
            return spec.distributed_reduce(mask, data_local, "data")

        fn = shard_map_compat(
            local_reduce,
            mesh=mesh,
            in_specs=(P(None, "data"), P(), P()),
            out_specs=out_specs,
        )
        base = fn(data_sharded, lower, upper)
    else:
        def local_reduce_tomb(data_local, lo, up, tomb_local):
            from repro.kernels import reducers as _red
            mask = _local_multi_scan(data_local, lo, up, tile_n=tile_n,
                                     interpret=interpret)
            # The tombstone vector shards with the data axis, so the fold is
            # shard-local — no extra collective.
            mask = _red.fold_tombstones(mask, tomb_local)
            return spec.distributed_reduce(mask, data_local, "data")

        fn = shard_map_compat(
            local_reduce_tomb,
            mesh=mesh,
            in_specs=(P(None, "data"), P(), P(), P("data")),
            out_specs=out_specs,
        )
        base = fn(data_sharded, lower, upper, base_tomb)
    if delta_cm is None:
        return base
    # The delta block is tiny and replicated: scan + reduce it outside the
    # shard_map (every device computes the same payload, no collective).
    return base, ops._delta_payload(delta_cm, lower, upper, spec=spec,
                                    tile_n=tile_n, interpret=interpret)


distributed_multi_reduce = ops.counted(
    "distributed_multi_reduce",
    "Cross-device fused batch scan + the ResultSpec's shard-local reducer "
    "and one small collective merge in a single launch -> the spec payload "
    "(sharded masks for Ids/Mask; replicated counts/top-k/aggregates).",
)(_distributed_multi_reduce_jit)


class DistributedScan:
    """Horizontally partitioned scan over a device mesh (build-once facade).

    Single-query (``mask`` / ``query`` / ``count``) and batched
    (``mask_batch`` / ``query_batch`` / ``count_batch``) entry points mirror
    ``ColumnarScan`` — batched calls are one collective launch and one host
    sync per batch, with the same pow2 query-axis bucketing.
    """

    def __init__(self, dataset: T.Dataset, mesh: Mesh | None = None, tile_n: int = 1024):
        self.mesh = mesh or make_data_mesh()
        self.tile_n = tile_n
        self.n_devices = self.mesh.shape["data"]
        padded, self.m, self.n = ops.prepare_columnar(
            dataset.cols, tile_n=tile_n * self.n_devices
        )
        self.m_pad = padded.shape[0]
        self.data = shard_columnar(self.mesh, padded, tile_n=tile_n)

    @property
    def nbytes_index(self) -> int:
        return 0  # a scan needs no auxiliary structures (paper §8)

    # -- single query ------------------------------------------------------
    def mask(self, q: T.RangeQuery) -> np.ndarray:
        qlo, qhi = ops.query_bounds_device(q, self.m_pad, self.data.dtype)
        out = distributed_mask(self.mesh, self.data, qlo, qhi, tile_n=self.tile_n)
        return ops.device_get(out)[: self.n] > 0

    def query(self, q: T.RangeQuery) -> np.ndarray:
        return np.nonzero(self.mask(q))[0].astype(np.int64)

    def count(self, q: T.RangeQuery) -> int:
        qlo, qhi = ops.query_bounds_device(q, self.m_pad, self.data.dtype)
        total = distributed_count(self.mesh, self.data, qlo, qhi, tile_n=self.tile_n)
        # subtract sentinel padding matches (there are none: +inf never matches)
        return int(ops.device_get(total))

    # -- batched execution (one collective launch per batch) ---------------
    def _as_batch(self, batch) -> T.QueryBatch:
        if not isinstance(batch, T.QueryBatch):
            batch = T.QueryBatch.from_queries(list(batch))
        return batch

    def mask_batch(self, batch) -> np.ndarray:
        """(Q, n) bool match masks from one cross-device fused launch."""
        from repro.core.scan import bucketed_batch_bounds
        batch = self._as_batch(batch)
        _, lo, up = bucketed_batch_bounds(batch, self.m_pad, self.data.dtype)
        out = distributed_multi_mask(self.mesh, self.data, lo, up,
                                     tile_n=self.tile_n)
        return ops.device_get(out)[: len(batch), : self.n] > 0

    def count_batch(self, batch) -> list[int]:
        """Per-query global counts: one collective launch + one psum, so the
        host (and the collective) only ever see (Q,) ints."""
        from repro.core.scan import bucketed_batch_bounds
        batch = self._as_batch(batch)
        _, lo, up = bucketed_batch_bounds(batch, self.m_pad, self.data.dtype)
        counts = distributed_multi_counts(self.mesh, self.data, lo, up,
                                          tile_n=self.tile_n)
        return [int(c) for c in ops.device_get(counts)[: len(batch)]]

    def query_batch(self, batch, spec=T.IDS, delta=None) -> list:
        """Batched execution under any ResultSpec: one collective launch
        (scan + the spec's shard-local reduce + its collective merge, all in
        the same shard_map jit) and one host sync for the payload.

        ``delta`` folds the mutable plane into the same launch: the base
        tombstone vector shards with the data axis and ANDs in shard-locally;
        the small delta block replicates and scans outside the shard_map.
        """
        payload, fin = self.launch_batch(batch, spec=spec, delta=delta)
        return fin(ops.device_get(payload))

    def launch_batch(self, batch, spec=T.IDS, delta=None) -> tuple:
        """Device half of ``query_batch`` -> (payload, finalize): the one
        collective launch without its host sync, for the pipelined server
        (the counted ``device_get`` + host finalizers run via ``finalize``
        on the caller's thread)."""
        spec = T.validate_mode(spec).validate(self.m)
        from repro.core.scan import bucketed_batch_bounds
        batch = self._as_batch(batch)
        _, lo, up = bucketed_batch_bounds(batch, self.m_pad, self.data.dtype)
        dcm = tomb = None
        if delta is not None and not delta.is_empty:
            dcm = delta.device_cm(self.tile_n)
            tomb = delta.base_tomb_dev(
                self.data.shape[1], key=("dist", int(self.data.shape[1])),
                put=lambda h: jax.device_put(
                    h, NamedSharding(self.mesh, P("data"))))
        payload = distributed_multi_reduce(self.mesh, self.data, lo, up,
                                           dcm, tomb,
                                           spec=spec, tile_n=self.tile_n)
        n_q, n = len(batch), self.n
        if dcm is None:
            def finalize(host_payload):
                return spec.finalize(host_payload, n_q, n)
            return payload, finalize
        d_n, host_ctx = delta.d, delta.host_ctx()

        def finalize_delta(host_payload):
            base_host, delta_host = host_payload
            base = spec.finalize(base_host, n_q, n)
            dres = spec.finalize(delta_host, n_q, d_n)
            return spec.merge_delta(base, dres, host_ctx)
        return payload, finalize_delta
