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
from repro.obs import tracing as obs_tracing
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


# -- placement: one device, or sharded when one device cannot hold the table --
# A device scanning its table also holds the (Q, n) int8 mask of the window
# it scans; WINDOW_Q is the servers' default window (``max_batch``).
WINDOW_Q = 128


def device_bytes_limit() -> int | None:
    """The first device's memory limit in bytes; None where the backend
    reports none (the CPU)."""
    return (jax.devices()[0].memory_stats() or {}).get("bytes_limit")


def padded_shape(m: int, n: int, n_devices: int, tile_n: int
                 ) -> tuple[int, int]:
    """(m_pad, n_pad) of an (m, n) table padded to sublane groups and to
    whole tiles on each of ``n_devices`` devices."""
    return (-(-m // _rs.SUBLANES) * _rs.SUBLANES,
            -(-n // (n_devices * tile_n)) * n_devices * tile_n)


def scan_bytes_per_device(m: int, n: int, n_devices: int,
                          tile_n: int) -> int:
    """Bytes each of ``n_devices`` devices holds to scan its share of an
    (m, n) float32 table: its padded slice and a full window's mask."""
    m_pad, n_pad = padded_shape(m, n, n_devices, tile_n)
    return n_pad // n_devices * (m_pad * 4 + WINDOW_Q)


def placement_mesh(m: int, n: int, tile_n: int) -> Mesh | None:
    """None where one device holds an (m, n) table beside a window's mask,
    else a data mesh over every local device (DESIGN.md §5).

    Raises ValueError where even the local devices together cannot hold it.
    """
    limit = device_bytes_limit()
    if limit is None or scan_bytes_per_device(m, n, 1, tile_n) <= limit:
        return None
    k = len(jax.devices())
    need = scan_bytes_per_device(m, n, k, tile_n)
    if need > limit:
        raise ValueError(
            f"a ({m}, {n}) float32 table needs {need} B a device on {k} "
            f"device(s) (its padded slice and a {WINDOW_Q}-query int8 mask); "
            f"a device holds {limit} B")
    return make_data_mesh(k)


def shard_columnar(mesh: Mesh, cols: np.ndarray, tile_n: int = 1024) -> jax.Array:
    """Place (m, n) columnar data sharded over objects, padded as
    ``ops.prepare_columnar`` pads it (dim rows 0.0, object columns +inf) to
    whole tiles on every device.

    Straight from host numpy into the sharding: each device's padded slice
    is cut from ``cols`` on its own, so the host never holds more than one
    padded copy, and no device ever holds more than its slice.
    """
    n_dev = mesh.shape["data"]
    m, n = cols.shape
    shape = padded_shape(m, n, n_dev, tile_n)

    def piece(index) -> np.ndarray:
        a, b, _ = index[1].indices(shape[1])
        k = max(0, min(b, n) - a)
        out = np.full((shape[0], b - a), np.inf, np.float32)
        out[:m, :k] = cols[:, a:a + k]
        out[m:, :k] = 0.0
        return out

    with obs_tracing.span("place", n_devices=n_dev,
                          bytes_per_device=shape[0] * shape[1] // n_dev * 4):
        return jax.make_array_from_callback(
            shape, NamedSharding(mesh, P(None, "data")), piece)


def _local_scan(data_local, lo, up, *, tile_n: int, interpret: bool):
    """One device's full scan of its object shard (backend-dispatched)."""
    if ops.use_xla():
        from repro.kernels import ref as _ref
        return _ref.range_scan_ref(data_local, lo, up)
    return _rs.range_scan_tiles(data_local, lo, up, tile_n=tile_n,
                                interpret=interpret)


def _local_multi_scan(data_local, lo, up, *, tile_n: int, interpret: bool):
    """One device's fused multi-query scan of its shard -> (Q, n_local).

    The scope names the kernel's device operation ``sharded_scan.<k>`` in a
    profiler trace (``mdrqbench/layers/shard_scan_roofline.count.py``)."""
    if ops.use_xla():
        from repro.kernels import ref as _ref
        return _ref.multi_scan_ref(data_local, lo, up)
    with jax.named_scope("sharded_scan"):
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
        self.m, self.n = dataset.m, dataset.n
        self.data = shard_columnar(self.mesh, dataset.cols, tile_n=tile_n)
        self.m_pad = self.data.shape[0]

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

    def _launch_bounds(self, batch: T.QueryBatch) -> tuple:
        """Device bounds of one batch launch (``bucketed_batch_bounds``),
        whose compared and skipped (chunk, row) pairs are counted here on
        the host, once per launch, under ``kernel="sharded"``."""
        from repro.core.scan import bucketed_batch_bounds
        q_pad, lo, up = bucketed_batch_bounds(batch, self.m_pad,
                                              self.data.dtype)
        ops.count_scan_rows("sharded", batch.dims_mask, q_pad, self.m_pad)
        return lo, up

    def mask_batch(self, batch) -> np.ndarray:
        """(Q, n) bool match masks from one cross-device fused launch."""
        batch = self._as_batch(batch)
        lo, up = self._launch_bounds(batch)
        out = distributed_multi_mask(self.mesh, self.data, lo, up,
                                     tile_n=self.tile_n)
        return ops.device_get(out)[: len(batch), : self.n] > 0

    def count_batch(self, batch) -> list[int]:
        """Per-query global counts: one collective launch + one psum, so the
        host (and the collective) only ever see (Q,) ints."""
        batch = self._as_batch(batch)
        lo, up = self._launch_bounds(batch)
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
        batch = self._as_batch(batch)
        lo, up = self._launch_bounds(batch)
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
