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

Batched execution (cross-device × multi-query): ``distributed_multi_reduce``
wraps the fused multi-query kernel (``kernels.multi_scan``) and the
ResultSpec's reducer in the shard_map — data sharded ``P(None, "data")``, the
(m_pad, Q) query bounds replicated — so one collective launch answers a whole
batch on every device at once; a single query is a batch of one. Under Count
the per-device (Q,) partial counts reduce through a single ``psum`` and only
O(Q) ints ever cross the collective *and* the host boundary.
``DistributedScan.query_batch`` buckets the query axis to pow2 exactly like
``ColumnarScan`` so both engines share jit traces per batch-size bucket.

Instrumentation: every entry point here is registered through
``kernels.ops.counted`` and every device->host read goes through
``ops.device_get`` — the distributed path pays the same launch/host-sync
accounting the single-device ops do, so counter-based budget tests see it.
"""
from __future__ import annotations

import functools

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import types as T
from repro.obs import tracing as obs_tracing
from repro.kernels import ops
from repro.kernels import multi_scan as _ms


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
    return (-(-m // _ms.SUBLANES) * _ms.SUBLANES,
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

    ``query_batch`` / ``launch_batch`` mirror ``ColumnarScan``'s: one
    collective launch and one host sync per batch, with the same pow2
    query-axis bucketing.
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
