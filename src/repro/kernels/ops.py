"""Jit'd public wrappers around the MDRQ Pallas kernels.

Handles layout/padding policy (pad m to sublanes with match-all bounds, n to
the tile size with +inf sentinel objects that never match), dtype casting of
the bounds, and interpret-mode selection (interpret=True on CPU so the kernel
body executes as the oracle-checked reference path; compiled Mosaic on TPU).

Batched execution: the ``multi_*`` ops drive the fused multi-query kernels
(``kernels.multi_scan``) — (m_pad, Q) query-minor bounds, one launch for a
whole query batch — and ``multi_va_filter`` does the same for the VA-file's
packed approximation phase. A single query is a batch of one: there are no
single-query ops. On the XLA backend they route to the
per-dimension-accumulating refs in ``ref.py``, which are also the honest CPU
throughput proxy for ``benchmarks/bench_throughput.py``.

Instrumentation: every public op is built by ``_counted`` — a plain-Python
wrapper that bumps a named launch counter before delegating to the jitted
implementation — and ``device_get`` is the counted device->host transfer
point. Tests use the counters to assert launch/sync budgets (e.g. "one
phase-1 launch and one host sync per VA-file batch") that wall-clock
measurements on CPU cannot see.

AOT serving cache: inside ``aot_capture()`` every counted call additionally
``jit_fn.lower(...).compile()``s its executable and stores it keyed by
(op, arg shapes/dtypes, statics); afterwards calls whose key is cached
dispatch straight to the compiled executable — no jit argument hashing, and
*provably* no retrace (the ``note_trace`` probe sits first in every jitted
body, so a retrace is observable as a log entry rather than inferred from
timing). ``serve.pipeline`` warms this cache at server construction; the
counters still see every call because the bump happens before the lookup.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import multi_scan as _ms
from repro.kernels import ref as _ref
from repro.kernels import va_filter as _va

import os

# Kernel execution backend:
#   auto      — Mosaic on TPU, interpret-mode Pallas on CPU (correctness path)
#   interpret — force interpret-mode Pallas
#   xla       — execute the ref.py jnp implementations (identical semantics).
#               Benchmarks use this on CPU: interpret-mode runs the grid as a
#               Python loop, so its wall-time says nothing about the kernel;
#               the XLA path is the honest CPU proxy for throughput numbers.
_BACKEND = os.environ.get("REPRO_KERNEL_BACKEND", "auto")


def use_xla() -> bool:
    return _BACKEND == "xla"


def set_backend(name: str) -> str:
    """Switch the kernel backend mid-process; returns the previous backend.

    The backend is read at *trace* time inside the jitted ops, and jit caches
    key on shapes/statics only — an executable traced under the old backend
    would be silently reused for any already-seen shape, so a switch must
    drop the compilation caches to actually take effect.
    """
    global _BACKEND
    prev = _BACKEND
    if name != prev:
        _BACKEND = name
        jax.clear_caches()
        # AOT executables bake the backend at trace time exactly like the jit
        # caches do — a stale one would silently serve the old backend.
        clear_aot_cache()
    return prev


def default_interpret() -> bool:
    if _BACKEND == "interpret":
        return True
    return jax.default_backend() != "tpu"


# -- launch / transfer instrumentation ---------------------------------------
# Counters live outside jit (wrappers bump them per call, not per trace), so a
# count of 1 really means one kernel launch / one device->host round trip.
#
# The store is the obs metrics registry (family "mdrq_launches_total",
# labeled by op) rather than a module-private dict: spans attribute their
# launch/sync budgets from the same counters tests assert on, and the
# exporters ship them without a second accounting path. The historical
# ``counter``/``counters``/``reset_counters`` API is preserved on top —
# launch-budget tests run unchanged against the new backend.

from repro.obs import metrics as _obs_metrics
from repro.obs import tracing as _tracing

_LAUNCH_FAMILY = "mdrq_launches_total"
_LAUNCH_HELP = ("Kernel launches (and device->host transfers, op=host_sync) "
                "counted per public op wrapper call")
# op name -> its registry Counter. Cached so the per-launch cost is one dict
# lookup + one float add; registry reset() keeps these objects live.
_COUNTERS: dict[str, _obs_metrics.Counter] = {}


def _launch_counter(name: str) -> _obs_metrics.Counter:
    c = _COUNTERS.get(name)
    if c is None:
        c = _obs_metrics.registry().counter(_LAUNCH_FAMILY, help=_LAUNCH_HELP,
                                            op=name)
        _COUNTERS[name] = c
    return c


def _bump(name: str) -> None:
    _launch_counter(name).inc()


def counter(name: str) -> int:
    """Launches of op ``name`` (or ``"host_sync"`` transfers) since reset."""
    c = _COUNTERS.get(name)
    return int(c.value) if c is not None else 0


def counters() -> dict[str, int]:
    """Nonzero per-op launch counts since the last reset. AOT cache events
    ride the same store (for registry-reset liveness) but report through
    ``aot_counters`` — launch-budget equality assertions stay exact."""
    return {name: int(c.value) for name, c in _COUNTERS.items()
            if c.value and not name.startswith("aot:")}


def reset_counters() -> None:
    for c in _COUNTERS.values():
        c.reset()


# (chunk, row) pairs of the scan kernels: each chunk of ``INT8_SUBLANES``
# queries compares a dimension row only where ``multi_scan.chunk_flags`` flags
# it. Counted on the host where ``ColumnarScan`` or ``DistributedScan`` builds
# a batch launch, once per launch (a sharded launch once, not per device), in
# families of their own so that ``counters()`` and the launch budgets do not
# see them.
_ROWS_HELP = "(query chunk, dimension row) pairs the scan kernels {}"


def count_scan_rows(kernel: str, dims_mask: np.ndarray, q_pad: int,
                    m_pad: int) -> None:
    """Count one scan launch's compared and skipped (chunk, row) pairs,
    labelled ``kernel`` (``"vertical"``, ``"full"`` or ``"sharded"``), from
    the batch's (Q, m) ``dims_mask`` padded to the launch's (q_pad, m_pad)."""
    bound = np.zeros((q_pad, m_pad), bool)
    bound[: dims_mask.shape[0], : dims_mask.shape[1]] = dims_mask
    flags = _ms.chunk_flags(bound, xp=np)
    compared = int(flags.sum())
    for outcome, n in (("compared", compared),
                       ("skipped", flags.size - compared)):
        _obs_metrics.registry().counter(
            f"mdrq_scan_rows_{outcome}_total",
            help=_ROWS_HELP.format(outcome), kernel=kernel).inc(n)


def count_visit_pairs(path: str, route: str, n: int) -> None:
    """Count the (query, block) pairs a two-phase batch visited, labelled by
    ``path`` and ``route``: ``"host"`` where the visit list was built on the
    host, ``"device"`` where the prune mask was compacted on the device and
    the count came back in the payload."""
    _obs_metrics.registry().counter(
        "mdrq_visit_pairs_total",
        help="(query, block) pairs the two-phase paths visited",
        path=path, route=route).inc(n)


def device_get(x, *, stage=None, path=None):
    """Counted device->host transfer — the host-sync tax the cost model prices.

    Accepts a single array or a payload pytree (tuple/list — the ResultSpec
    reducers return e.g. ``(values, indices, counts)``); either way it is one
    logical synchronization, counted once.

    Where a span would record (``obs.tracing.active()``), the transfer runs
    under a ``sync`` span carrying the payload's device ``bytes`` and the
    caller's ``stage`` (``"launch"`` for a mid-launch survivor sync,
    ``"finalize"`` for a payload sync) and ``path``; otherwise no span opens
    and no byte count is taken.
    """
    _bump("host_sync")
    if not _tracing.active():
        return _fetch(x)
    with _tracing.span("sync", bytes=_payload_nbytes(x), stage=stage,
                       path=path):
        return _fetch(x)


def _fetch(x):
    if isinstance(x, (tuple, list)):
        return jax.device_get(x)
    return np.asarray(x)


def _payload_nbytes(x) -> int:
    """Device bytes of a payload: the sum of its leaves' ``nbytes``."""
    return int(sum(getattr(leaf, "nbytes", 0)
                   for leaf in jax.tree_util.tree_leaves(x)))


# -- retrace observability ----------------------------------------------------
# ``note_trace(op)`` is the first statement of every jitted implementation
# body: it runs when (and only when) jax traces the function — never per
# execution — so ``trace_log()`` is a direct record of (re)compilations. The
# serving pipeline's "zero retraces after warmup" guarantee is asserted on
# this log, not inferred from wall time.

_TRACE_LOG: list[str] = []


def note_trace(name: str) -> None:
    """Trace-time probe (call first inside a jitted body)."""
    _TRACE_LOG.append(name)


def trace_log() -> tuple[str, ...]:
    """Op names in (re)trace order since the last ``reset_trace_log``."""
    return tuple(_TRACE_LOG)


def reset_trace_log() -> None:
    _TRACE_LOG.clear()


# -- AOT executable cache -----------------------------------------------------
# (op name, per-arg (shape, dtype) abstraction, statics) -> the compiled
# executable from ``jit_fn.lower(...).compile()``. A hit bypasses the jit
# dispatch entirely (``exe(*args)`` — statics are baked in), so a warmed
# serving path cannot retrace no matter what jax's own caches do. Population
# only happens inside ``aot_capture()`` (the server warmup pass); outside it
# the cache is read-only, and the lookup itself costs one tuple build + one
# dict get per call. Reads are GIL-safe from any thread; capture is expected
# single-threaded (one warmup pass).

_AOT_CACHE: dict = {}
_AOT_CAPTURE: bool = False
_AOT_FAMILY = "mdrq_aot_total"
_AOT_HELP = ("AOT executable cache events: compile (warmup capture), hit "
             "(dispatched to a compiled executable), miss (warmed process "
             "fell back to jit dispatch)")


def _aot_bump(event: str) -> None:
    key = "aot:" + event
    c = _COUNTERS.get(key)
    if c is None:
        c = _obs_metrics.registry().counter(_AOT_FAMILY, help=_AOT_HELP,
                                            event=event)
        _COUNTERS[key] = c
    c.inc()


def _abstract(x):
    """Hashable cache-key atom for one call argument: arrays collapse to
    (shape, dtype) — exactly what decides a retrace — statics stay as-is."""
    if x is None:
        return None
    if isinstance(x, (tuple, list)):
        return ("seq", type(x).__name__, tuple(_abstract(e) for e in x))
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return ("arr", tuple(shape), str(dtype))
    return ("static", x)


def _aot_key(name: str, args: tuple, kwargs: dict):
    return (name, tuple(_abstract(a) for a in args),
            tuple(sorted((k, _abstract(v)) for k, v in kwargs.items())))


@contextlib.contextmanager
def aot_capture():
    """Within this context every counted call lower+compiles (and caches) its
    executable on a key miss. The call still executes and returns normally —
    warmup doubles as a correctness-visible dry run."""
    global _AOT_CAPTURE
    prev = _AOT_CAPTURE
    _AOT_CAPTURE = True
    try:
        yield
    finally:
        _AOT_CAPTURE = prev


def aot_cache_size() -> int:
    return len(_AOT_CACHE)


def aot_cache_keys() -> tuple:
    return tuple(_AOT_CACHE)


def clear_aot_cache() -> None:
    _AOT_CACHE.clear()


def aot_counters() -> dict[str, int]:
    """Nonzero AOT cache event counts ("compile" / "hit" / "miss")."""
    out = {}
    for key, c in _COUNTERS.items():
        if key.startswith("aot:") and c.value:
            out[key[4:]] = int(c.value)
    return out


def counted(name: str, doc: str):
    """Build a public op: bump the named launch counter, consult the AOT
    executable cache, and otherwise delegate to the jitted implementation.
    One definition keeps every op in the accounting — a hand-written wrapper
    that forgets the bump silently escapes it. Other modules that own jitted
    entry points (e.g. ``core.distributed``) register them through this same
    hook so no launch path escapes the counters — and so every op is AOT
    warmable for free."""
    def deco(jit_fn):
        def wrapper(*args, **kwargs):
            _bump(name)
            try:
                key = _aot_key(name, args, kwargs)
                exe = _AOT_CACHE.get(key)
            except TypeError:  # unhashable static — not AOT-cacheable
                return jit_fn(*args, **kwargs)
            if exe is None:
                if not _AOT_CAPTURE:
                    if _AOT_CACHE:
                        # a warmed process fell off the compiled set — the
                        # "zero retraces" budget tests watch this counter
                        _aot_bump("miss")
                    return jit_fn(*args, **kwargs)
                exe = jit_fn.lower(*args, **kwargs).compile()
                try:
                    # convention check before caching: executables take the
                    # dynamic args positionally with statics baked in, so a
                    # call site passing a static *positionally* produces an
                    # executable we cannot redispatch to — skip it (the op
                    # still works through jit; fix the call site to pass
                    # statics as keywords to make it AOT-cacheable)
                    out = exe(*args)
                except TypeError:
                    return jit_fn(*args, **kwargs)
                _AOT_CACHE[key] = exe
                _aot_bump("compile")
                return out
            else:
                _aot_bump("hit")
            return exe(*args)
        wrapper.__name__ = wrapper.__qualname__ = name
        wrapper.__doc__ = doc
        wrapper.__wrapped__ = jit_fn
        return wrapper
    return deco


_counted = counted  # historical spelling used by the in-module registrations


def prepare_columnar(
    cols: np.ndarray, tile_n: int = _ms.DEFAULT_TILE_N, dtype=jnp.float32
) -> tuple[np.ndarray, int, int]:
    """Pad (m, n) columnar data for the kernel.

    Dim padding rows are 0.0 (queried with match-all bounds); object padding
    columns are +inf (never match any finite upper bound).

    Returns (padded array, m, n) with original sizes.
    """
    from repro.core import types as T  # deferred: breaks ops<->core cycle
    m, n = cols.shape
    x = T.pad_axis(cols, 0, _ms.SUBLANES, 0.0)
    x = T.pad_axis(x, 1, tile_n, np.inf)
    return np.asarray(x, dtype=np.float32 if dtype == jnp.float32 else x.dtype), m, n


def batch_bounds_device(batch, m_pad: int, dtype,
                        q_pad: int | None = None) -> tuple[jax.Array, jax.Array]:
    """(m_pad, q_pad or Q) finite device bounds for a QueryBatch.

    Pad rows — and padding query columns beyond Q when ``q_pad`` rounds the
    batch to a jit bucket — are match-all in ``dtype``'s finite extrema;
    callers drop their output rows.
    """
    from repro.core import types as T  # deferred: breaks ops<->core cycle
    if not isinstance(batch, T.QueryBatch):
        batch = T.QueryBatch.from_queries(list(batch))
    lo, up = batch.bounds_columnar(m_pad, q_pad, dtype=dtype)
    return jnp.asarray(lo, dtype=dtype), jnp.asarray(up, dtype=dtype)


@functools.partial(jax.jit, static_argnames=("m", "tile_n", "block_n", "interpret"))
def _multi_va_filter_jit(
    packed: jax.Array,
    cell_lo: jax.Array,
    cell_hi: jax.Array,
    m: int,
    *,
    tile_n: int = _va.DEFAULT_TILE_N,
    block_n: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    note_trace("multi_va_filter")
    if use_xla():
        out = _ref.multi_va_filter_packed_ref(packed, cell_lo, cell_hi, m)
    else:
        if interpret is None:
            interpret = default_interpret()
        out = _va.multi_va_filter_packed(
            packed, cell_lo, cell_hi, m, tile_n=tile_n, interpret=interpret
        )
    if block_n is not None:
        q_n, n_pad = out.shape
        # Reduce to per-(query, block) survivor bits *on device*: only the
        # small (Q, n_blocks) array ever crosses to the host.
        out = jnp.any((out != 0).reshape(q_n, n_pad // block_n, block_n), axis=2)
    return out


multi_va_filter = _counted(
    "multi_va_filter",
    "Batched packed VA filter, one launch per query batch: (Q, n_pad) int8 "
    "candidate masks, or — with ``block_n`` — the on-device reduction to "
    "(Q, n_pad // block_n) bool per-block survivor bits (the phase-2 visit "
    "list seed; the reduction rides in the same jit).",
)(_multi_va_filter_jit)


# -- fused spec-reduce launches (the ResultSpec layer's device half) ----------
# Each op composes a mask-producing kernel with the spec's on-device reducer
# in ONE jit (the spec is a frozen dataclass and rides as a static argument),
# so a reduced result shape — count, top-k, aggregate — is exactly one device
# launch and, with the single ``device_get`` of the payload, one host sync
# per batch. The identity specs (Ids/Mask) flow through unchanged: their
# "payload" is the mask itself.
#
# Mutable data plane (DESIGN.md §11): each op takes two optional extras that
# ride in the SAME jit, so a non-empty delta costs zero additional launches —
#   * ``base_tomb`` — (n_pad,) int8 tombstone flags in the data's storage
#     order, ANDed into the base match masks before the reducer sees them;
#   * ``delta_cm``  — the delta segment as a (m_pad, d_pad) columnar block
#     (same padding contract as ``data_cm``; tombstoned delta rows are +inf
#     poisoned at build time). When present the op scans it with the same
#     bounds, reduces it with the same spec, and returns a (base_payload,
#     delta_payload) pair — one ``device_get`` of the pair is still one host
#     sync, and the spec's ``merge_delta`` folds the halves on the host.


def _multi_scan_masks(data_cm, lower, upper, *, tile_n, interpret):
    """Backend-dispatched fused multi-query mask kernel (trace-time helper)."""
    if use_xla():
        return _ref.multi_scan_ref(data_cm, lower, upper)
    return _ms.multi_scan_tiles(data_cm, lower, upper, tile_n=tile_n,
                                interpret=interpret)


def _scan_reduce(data_cm, lower, upper, delta_cm, base_tomb, *, spec, tile_n,
                 interpret):
    """The fused scan, the base tombstones and the spec's reducer, plus the
    delta's payload where there is a delta (trace-time helper)."""
    if interpret is None:
        interpret = default_interpret()
    mask = _multi_scan_masks(data_cm, lower, upper, tile_n=tile_n,
                             interpret=interpret)
    if base_tomb is not None:
        from repro.kernels import reducers as _red
        mask = _red.fold_tombstones(mask, base_tomb)
    base = spec.device_reduce(mask, data_cm, tile_n=tile_n,
                              interpret=interpret)
    if delta_cm is None:
        return base
    return base, _delta_payload(delta_cm, lower, upper, spec=spec,
                                tile_n=tile_n, interpret=interpret)


def _delta_payload(delta_cm, lower, upper, *, spec, tile_n, interpret):
    """Scan + reduce the delta block with the batch's bounds (same jit)."""
    dmask = _multi_scan_masks(delta_cm, lower, upper, tile_n=tile_n,
                              interpret=interpret)
    return spec.device_reduce(dmask, delta_cm, tile_n=tile_n,
                              interpret=interpret)


@functools.partial(jax.jit, static_argnames=("spec", "tile_n", "interpret"))
def _multi_scan_reduce_jit(
    data_cm: jax.Array,
    lower: jax.Array,
    upper: jax.Array,
    delta_cm: jax.Array | None = None,
    base_tomb: jax.Array | None = None,
    *,
    spec,
    tile_n: int = _ms.DEFAULT_TILE_N,
    interpret: bool | None = None,
):
    note_trace("multi_scan_reduce")
    return _scan_reduce(data_cm, lower, upper, delta_cm, base_tomb,
                        spec=spec, tile_n=tile_n, interpret=interpret)


multi_scan_reduce = _counted(
    "multi_scan_reduce",
    "Fused full scan of a query batch + the ResultSpec's on-device reducer "
    "in one launch -> the spec's payload (masks for Ids/Mask, (Q,) counts, "
    "(Q, k) top-k values/positions, (Q,) aggregates).",
)(_multi_scan_reduce_jit)


@functools.partial(jax.jit, static_argnames=("spec", "tile_n", "interpret"))
def _multi_scan_vertical_reduce_jit(
    data_cm: jax.Array,
    lower: jax.Array,
    upper: jax.Array,
    delta_cm: jax.Array | None = None,
    base_tomb: jax.Array | None = None,
    *,
    spec,
    tile_n: int = _ms.DEFAULT_TILE_N,
    interpret: bool | None = None,
):
    note_trace("multi_scan_vertical_reduce")
    return _scan_reduce(data_cm, lower, upper, delta_cm, base_tomb,
                        spec=spec, tile_n=tile_n, interpret=interpret)


multi_scan_vertical_reduce = _counted(
    "multi_scan_vertical_reduce",
    "Batched partial-match scan + ResultSpec reducer in one launch: "
    "multi_scan_reduce, counted and named apart.",
)(_multi_scan_vertical_reduce_jit)


def _visit_masks(data_cm, query_ids, block_ids, lower, upper, *, tile_n,
                 interpret, n_visit=None):
    """Backend-dispatched visit kernel over a (query, block) list
    (trace-time helper)."""
    if use_xla():
        m_pad, n_pad = data_cm.shape
        blocks = data_cm.reshape(m_pad, n_pad // tile_n, tile_n).transpose(1, 0, 2)
        return _ref.multi_scan_blocks_ref(blocks, query_ids, block_ids, lower,
                                          upper)
    return _ms.multi_scan_visit(data_cm, query_ids, block_ids, lower, upper,
                                tile_n=tile_n, interpret=interpret,
                                n_visit=n_visit)


def _reduce_visit_list(data_cm, query_ids, block_ids, valid, visit_index,
                       lower, upper, base_tomb, *, spec, tile_n, n_queries,
                       interpret, n_visit=None):
    """A visit list's masks, folded with the base tombstones, through the
    spec's visit reducer, which drops the rows whose ``valid`` is 0
    (trace-time helper)."""
    masks = _visit_masks(data_cm, query_ids, block_ids, lower, upper,
                         tile_n=tile_n, interpret=interpret, n_visit=n_visit)
    if base_tomb is not None:
        from repro.kernels import reducers as _red
        masks = _red.fold_tombstones(
            masks, _red.gather_tomb_blocks(base_tomb, block_ids, tile_n))
    return spec.reduce_visits(masks, data_cm, query_ids, block_ids, valid,
                              visit_index, tile_n=tile_n,
                              n_queries=n_queries, interpret=interpret)


def _reduce_survivors(data_cm, leaf_mask, lower, upper, base_tomb, *, spec,
                      tile_n, n_queries, interpret):
    """The device route: the (Q, n_leaves) prune mask's survivors visited
    in chunks of ``VISIT_CHUNK`` pairs, as many as the device count needs,
    each chunk's masks reduced into the spec's payload -> (payload, int32
    number of visited pairs). Slots past the count are dropped: the kernel
    leaves them unwritten (trace-time helper)."""
    n_leaves = leaf_mask.shape[1]
    chunk = min(_ms.VISIT_CHUNK,
                -(-leaf_mask.size // _ms.INT8_SUBLANES) * _ms.INT8_SUBLANES)
    prefix = _ms.survivor_prefix(leaf_mask)
    n_pairs = prefix[2][-1]

    def reduce_chunk(c):
        slots = c * chunk + jnp.arange(chunk, dtype=jnp.int32)
        valid = slots < n_pairs
        flat = _ms.nth_survivor(prefix, slots)
        return _reduce_visit_list(
            data_cm, jnp.where(valid, flat // n_leaves, 0),
            jnp.where(valid, flat % n_leaves, 0), valid.astype(jnp.int32),
            None, lower, upper, base_tomb, spec=spec, tile_n=tile_n,
            n_queries=n_queries, interpret=interpret,
            n_visit=n_pairs - c * chunk)

    n_chunks = (n_pairs + chunk - 1) // chunk
    payload = jax.lax.fori_loop(
        1, n_chunks,
        lambda c, acc: spec.combine_visits(acc, reduce_chunk(c)),
        reduce_chunk(0))
    return payload, n_pairs


@functools.partial(jax.jit,
                   static_argnames=("spec", "tile_n", "n_queries", "interpret"))
def _multi_visit_reduce_jit(
    data_cm: jax.Array,
    visits,
    visit_index: jax.Array | None,
    lower: jax.Array,
    upper: jax.Array,
    delta_cm: jax.Array | None = None,
    base_tomb: jax.Array | None = None,
    *,
    spec,
    tile_n: int = _ms.DEFAULT_TILE_N,
    n_queries: int = 1,
    interpret: bool | None = None,
):
    """``visits`` is the host route's padded ``(query_ids, block_ids)`` list
    or the device route's (Q, n_leaves) bool prune mask; the device route's
    base payload is ``(payload, n_pairs)``."""
    note_trace("multi_visit_reduce")
    if interpret is None:
        interpret = default_interpret()
    if isinstance(visits, tuple):
        query_ids, block_ids = visits  # block -1 pads the pow2 bucket
        base = _reduce_visit_list(data_cm, query_ids, block_ids,
                                  (block_ids >= 0).astype(jnp.int32),
                                  visit_index, lower, upper, base_tomb,
                                  spec=spec, tile_n=tile_n,
                                  n_queries=n_queries, interpret=interpret)
    else:
        base = _reduce_survivors(data_cm, visits, lower, upper, base_tomb,
                                 spec=spec, tile_n=tile_n,
                                 n_queries=n_queries, interpret=interpret)
    if delta_cm is None:
        return base
    # The (m_pad, q_pad) bounds already cover the whole batch, so the delta
    # scans once for every query regardless of which blocks it visited.
    return base, _delta_payload(delta_cm, lower, upper, spec=spec,
                                tile_n=tile_n, interpret=interpret)


multi_visit_reduce = _counted(
    "multi_visit_reduce",
    "Batched two-phase refinement + the ResultSpec's on-device visit "
    "reducer in one launch (shared by the tree MDIS and the VA-file phase "
    "2): over a host-built (query, block) visit list, or over the survivors "
    "of a device prune mask, compacted on the device.",
)(_multi_visit_reduce_jit)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kv_visit_attention_jit(
    q: jax.Array,
    k_blocks: jax.Array,
    v_blocks: jax.Array,
    block_ids: jax.Array,
    pos: jax.Array,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    note_trace("kv_visit_attention")
    from repro.kernels import kv_visit as _kvv
    if use_xla():
        return _ref.kv_visit_attention_ref(q, k_blocks, v_blocks, block_ids, pos)
    if interpret is None:
        interpret = default_interpret()
    return _kvv.kv_visit_attention(q, k_blocks, v_blocks, block_ids, pos,
                                   interpret=interpret)


kv_visit_attention = _counted(
    "kv_visit_attention",
    "Block-visit decode attention (zone-map-pruned KV) -> (B, KV, G, hd).",
)(_kv_visit_attention_jit)
