"""Pallas TPU kernels: fused multi-query (batched) range scans.

Batched execution — the inter-query-parallelism counterpart of the paper's
intra-query parallel scans (§5): analytical MDRQ workloads are *streams* of
queries (GMRQB issues eight templates concurrently, §6), and a single-query
launch pays the full dispatch + host-sync tax per query. These kernels
evaluate a (Q, m) batch of query boxes against the (m, n) columnar dataset in
one launch, so the fixed overheads amortize over Q and each VMEM data tile is
fetched from HBM *once* and compared against all Q queries while resident.
They are the engine's only scan kernels: a single query is a batch of one.

The layout is the TPU adaptation of the paper's Listing 2 (DESIGN.md §2):
data is dimension-major ``(m_pad, n_pad)``, so the lane axis runs over
objects and one VPU op compares 128 objects of one attribute against one
bound; the AND across dimensions happens in vector registers. m pads to a
multiple of ``SUBLANES``, tiles to a multiple of ``LANES``.

Two kernels:

  * ``multi_scan_tiles`` — fused scan, complete- or partial-match: grid
    ``(n_tiles,)``, one (m_pad, tile_n) data tile and one (Q, tile_n) int8
    mask block per step. The scan is bound by VPU compares, not by bytes, so
    each chunk of ``INT8_SUBLANES`` queries compares only the dimension rows
    some query of it bounds (``chunk_flags``, listed in SMEM); the other rows
    carry match-all bounds for the whole chunk and would decide nothing. The
    batched vertical (partial-match) scan is this kernel too: fetching only
    the 8-dim sublane groups a batch needs saved bytes the kernel was not
    short of, and its extra grid steps cost more than they saved.
  * ``multi_scan_visit`` — batched two-phase refinement: a flattened
    (query_id, block_id) visit list drives scattered tile scans for *all*
    queries of a batch in one launch (kd-tree / R*-tree / VA-file phase 2).

Mosaic block rule: the last two dims of every block are (8, 128)-aligned or
span the whole array. So the query axis is never split into (·, 1) blocks:
bounds enter as one query-major (Q, m_pad) block (Q is a pow2 jit bucket) and
masks leave as (Q, tile_n) blocks, written in chunks of ``INT8_SUBLANES``
query rows (int8's native (32, 128) tile). Callers keep passing bounds
query-minor, ``(m_pad, Q)``; the wrappers transpose the tiny arrays.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
# int8's native tile is (32, 128): batched kernels write their (Q, tile_n)
# int8 masks in chunks of this many query rows.
INT8_SUBLANES = 32
DEFAULT_TILE_N = 1024


# A chunk that flags every row compares them in straight-line code, as the
# chunks of a tile where all are dense do together. Any other chunk
# compares the first HEAD entries of its row list in straight-line code,
# then the rest TAIL at a time in a loop: a loop step costs about as much as
# two row compares on a v5e (PERF.md).
HEAD, TAIL = SUBLANES, 2


def chunk_flags(bound, xp=jnp):
    """(n_chunks, m_pad) int32: which rows each chunk of ``INT8_SUBLANES``
    queries compares.

    ``bound`` is (Q, m_pad) bool, True where a query bounds a row. A row is
    flagged where any query of its chunk bounds it. A chunk that bounds
    nothing compares row 0, so the object padding (+inf on every row) is
    still rejected there. A chunk whose loop would run over more than half
    the rows past its head compares them all, as does every chunk of a
    tile no taller than the head: a loop step costs about two row compares,
    so such a loop costs more than the rows it skips (PERF.md). ``xp`` is
    ``jnp`` inside a jit and ``np`` on the host, so the kernel and the
    host's row counters agree.
    """
    q_n, m_pad = bound.shape
    rows = min(INT8_SUBLANES, q_n)
    n_chunks = -(-q_n // rows)
    bound = xp.pad(bound, ((0, n_chunks * rows - q_n), (0, 0)))
    flags = bound.reshape(n_chunks, rows, m_pad).any(axis=1)
    row0 = xp.arange(m_pad) == 0
    flags = flags | (row0 & ~flags.any(axis=1, keepdims=True))
    loop = flags.sum(axis=1, keepdims=True) - HEAD
    flags = flags | (2 * loop > m_pad - HEAD) | (m_pad <= HEAD)
    return flags.astype(xp.int32)


def _bound_rows(lo_t, up_t):
    """(Q, m_pad) bool from query-major bounds in the data's dtype: False only
    on a match-all row (the dtype's finite extrema, which every finite value
    satisfies), so skipping it decides nothing."""
    info = jnp.finfo(lo_t.dtype)
    return jnp.logical_or(lo_t != info.min, up_t != info.max)


def _chunks(q_n):
    """(chunk, q0, q1) of the int8-tile query-row chunks of a (q_n, ·) block."""
    rows = min(INT8_SUBLANES, q_n)
    return [(c, q0, min(q0 + rows, q_n))
            for c, q0 in enumerate(range(0, q_n, rows))]


def _row_list(flags):
    """Each chunk's flagged rows: (n_chunks, m_pad) int32 row numbers, flagged
    ones first and ascending, the tail repeating the last flagged row (AND is
    idempotent); and (n_chunks,) int32 counts."""
    m_pad = flags.shape[1]
    order = jnp.argsort(1 - flags, axis=-1, stable=True)
    counts = flags.sum(axis=-1)
    pos = jnp.minimum(jnp.arange(m_pad), counts[:, None] - 1)
    rows = jnp.take_along_axis(order, pos, axis=-1)
    return rows.astype(jnp.int32), counts.astype(jnp.int32)


def _listed(b, rows):
    """(Q, m_pad) bounds whose column k holds each query's bound on entry k
    of its chunk's row list; a chunk that flags every row keeps its order."""
    q_n = b.shape[0]
    per_query = jnp.repeat(rows, min(INT8_SUBLANES, q_n), axis=0)[:q_n]
    return jnp.take_along_axis(b, per_query, axis=1)


def _column(ref, q0, q1, k):
    """(q1 - q0, 1) column ``k`` of a (Q, m_pad) bounds block: a static lane
    slice, or for a traced ``k`` a select and a lane reduce."""
    if isinstance(k, int):
        return ref[q0:q1, k:k + 1]
    b = ref[q0:q1, :]
    lanes = jax.lax.broadcasted_iota(jnp.int32, b.shape, 1)
    return jnp.max(jnp.where(lanes == k, b, -jnp.inf), axis=1, keepdims=True)


def _multi_scan_kernel(rows_ref, counts_ref, lower_ref, upper_ref, data_ref,
                       out_ref):
    """Compare one (m_pad, TN) data tile against every query's bounds, on the
    rows each query chunk flags. ``rows_ref`` holds each chunk's row list,
    and column k of the (Q, m_pad) bounds blocks the bound on its entry k."""
    m_pad = data_ref.shape[0]

    def all_of(pairs, q0, q1):
        """(q1 - q0, TN) bool: AND of the compares of each (data row, bounds
        column k) pair. A row broadcasts over the query sublanes, each
        query's bound over the lanes; the compares stay in the data's
        dtype."""
        ok = None
        for row, k in pairs:
            hit = jnp.logical_and(row >= _column(lower_ref, q0, q1, k),
                                  row <= _column(upper_ref, q0, q1, k))
            ok = hit if ok is None else jnp.logical_and(ok, hit)
        return ok

    def every_row(chunks):
        for _, q0, q1 in chunks:
            ok = all_of([(data_ref[d:d + 1, :], d) for d in range(m_pad)],
                        q0, q1)
            out_ref[q0:q1, :] = ok.astype(jnp.int8)

    def listed_rows(c, q0, q1):
        listed = c * m_pad

        def rows(k0, n):
            return [(data_ref[pl.ds(rows_ref[listed + k0 + u], 1), :], k0 + u)
                    for u in range(n)]
        # int32 accumulator: Mosaic cannot narrow an int8 vector to bool
        acc = all_of(rows(0, HEAD), q0, q1).astype(jnp.int32)

        def step(t, acc):
            return jnp.where(all_of(rows(HEAD + t * TAIL, TAIL), q0, q1),
                             acc, 0)
        steps = (counts_ref[c] - HEAD + TAIL - 1) // TAIL
        acc = jax.lax.fori_loop(0, steps, step, acc)
        out_ref[q0:q1, :] = acc.astype(jnp.int8)

    chunks = _chunks(out_ref.shape[0])
    if m_pad <= HEAD:  # every chunk flags every row (``chunk_flags``)
        every_row(chunks)
        return
    dense = [counts_ref[c] == m_pad for c, _, _ in chunks]
    all_dense = functools.reduce(jnp.logical_and, dense)
    pl.when(all_dense)(functools.partial(every_row, chunks))

    @pl.when(jnp.logical_not(all_dense))
    def _():
        for chunk in chunks:
            if len(chunks) == 1:
                listed_rows(*chunk)
                continue
            pl.when(dense[chunk[0]])(functools.partial(every_row, [chunk]))
            pl.when(jnp.logical_not(dense[chunk[0]]))(
                functools.partial(listed_rows, *chunk))


def multi_scan_tiles(
    data_cm: jax.Array,
    lower: jax.Array,
    upper: jax.Array,
    *,
    tile_n: int = DEFAULT_TILE_N,
    interpret: bool = False,
) -> jax.Array:
    """Fused scan of a query batch, complete- or partial-match.

    Each chunk of ``INT8_SUBLANES`` queries compares only the rows some query
    of it bounds (``chunk_flags``, listed in SMEM); the rest carry match-all
    bounds for the whole chunk and would decide nothing.

    Args:
      data_cm: (m_pad, n_pad) columnar data; m_pad % 8 == 0, n_pad % tile_n == 0.
      lower, upper: (m_pad, Q) finite bounds, one column per query, match-all
        on the rows a query leaves unconstrained.

    Returns:
      (Q, n_pad) int8 match masks, row q = query q.
    """
    m_pad, n_pad = data_cm.shape
    q_n = lower.shape[1]
    assert m_pad % SUBLANES == 0, m_pad
    assert n_pad % tile_n == 0 and tile_n % LANES == 0, (n_pad, tile_n)
    assert lower.shape == (m_pad, q_n) and upper.shape == (m_pad, q_n)
    lo_t, up_t = lower.T.astype(data_cm.dtype), upper.T.astype(data_cm.dtype)
    rows, counts = _row_list(chunk_flags(_bound_rows(lo_t, up_t)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_pad // tile_n,),
        in_specs=[
            pl.BlockSpec((q_n, m_pad), lambda i, r, c: (0, 0)),
            pl.BlockSpec((q_n, m_pad), lambda i, r, c: (0, 0)),
            pl.BlockSpec((m_pad, tile_n), lambda i, r, c: (0, i)),
        ],
        out_specs=pl.BlockSpec((q_n, tile_n), lambda i, r, c: (0, i)),
    )
    return pl.pallas_call(
        _multi_scan_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((q_n, n_pad), jnp.int8),
        interpret=interpret,
    )(rows.reshape(-1), counts, _listed(lo_t, rows), _listed(up_t, rows),
      data_cm)


# The visit lists are scalar-prefetched into SMEM (1 MiB on v5e). One kernel
# takes at most this many (query, block) pairs (2 x 128 KiB of int32); a
# longer list loops over chunks of it.
VISIT_CHUNK = 32768


def _multi_visit_kernel(qids_ref, bids_ref, lower_ref, upper_ref, data_ref,
                        out_ref):
    """Scan the tile selected by the flattened (query, block) visit list into
    row ``i % rows`` of the resident (rows, TN) output block."""
    x = data_ref[...]
    lo = lower_ref[...]  # (m_pad, 1) — the visiting query's bounds column
    up = upper_ref[...]
    ok = jnp.all(jnp.logical_and(x >= lo, x <= up), axis=0, keepdims=True)
    row = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
    hit = row == pl.program_id(0) % out_ref.shape[0]
    # select in int32: Mosaic cannot narrow an int8 vector to a bool one
    prev = out_ref[...].astype(jnp.int32)
    out_ref[...] = jnp.where(hit, ok.astype(jnp.int32), prev).astype(jnp.int8)


def multi_scan_visit(
    data_cm: jax.Array,
    query_ids: jax.Array,
    block_ids: jax.Array,
    lower: jax.Array,
    upper: jax.Array,
    *,
    tile_n: int = DEFAULT_TILE_N,
    interpret: bool = False,
    n_visit: jax.Array | None = None,
) -> jax.Array:
    """Batched two-phase refinement: visit each (query, block) pair once.

    Each grid step fills one row of a (rows, tile_n) output block that stays
    resident for ``rows`` consecutive visits (rows = ``INT8_SUBLANES``, or V
    when the list is shorter), so the int8 output keeps its native tile.
    Lists longer than ``VISIT_CHUNK`` run as a loop of kernels over chunks.

    With ``n_visit``, a traced int32 count, the list is one chunk (V at most
    ``VISIT_CHUNK``, a multiple of ``INT8_SUBLANES``) whose first ``n_visit``
    entries are real (a count past V means all of them): the grid stops at
    the count rounded up to whole output blocks, and the rows past that are
    left unwritten, for the caller to drop.

    Args:
      data_cm: (m_pad, n_pad) columnar data, n_pad % tile_n == 0.
      query_ids: (V,) int32 — which query's bounds each visit uses.
      block_ids: (V,) int32 tile indices; padding entries are negative
        (clamped to 0; callers drop their output rows).
      lower, upper: (m_pad, Q) finite bounds, one column per query.

    Returns:
      (V, tile_n) int8 per-visit masks.
    """
    m_pad, n_pad = data_cm.shape
    v_n = block_ids.shape[0]
    q_n = lower.shape[1]
    assert query_ids.shape == (v_n,)
    assert m_pad % SUBLANES == 0 and n_pad % tile_n == 0

    def columns(b):  # (m_pad, Q) -> (Q, m_pad, 1): one bounds block per query
        return b.T.astype(data_cm.dtype).reshape(q_n, m_pad, 1)

    lo, up = columns(lower), columns(upper)
    if n_visit is not None:
        assert v_n <= VISIT_CHUNK and v_n % INT8_SUBLANES == 0, v_n
        rows = INT8_SUBLANES
        steps = (jnp.clip(n_visit, 1, v_n) + rows - 1) // rows * rows
        return _visit_call(data_cm, lo, up, v_n, rows, steps, tile_n,
                           interpret)(query_ids, block_ids, lo, up, data_cm)
    rows = min(INT8_SUBLANES, v_n)
    chunk = min(VISIT_CHUNK, -(-v_n // rows) * rows)
    v_pad = -(-v_n // chunk) * chunk
    qids = jnp.pad(query_ids.astype(jnp.int32), (0, v_pad - v_n))
    bids = jnp.pad(block_ids.astype(jnp.int32), (0, v_pad - v_n),
                   constant_values=-1)
    call = _visit_call(data_cm, lo, up, chunk, rows, chunk, tile_n,
                       interpret)
    if v_pad == chunk:
        out = call(qids, bids, lo, up, data_cm)
    else:  # one launch still: the chunks loop inside this jit
        out = jax.lax.map(
            lambda qb: call(qb[0], qb[1], lo, up, data_cm),
            (qids.reshape(-1, chunk), bids.reshape(-1, chunk)),
        ).reshape(v_pad, tile_n)
    return out if v_pad == v_n else out[:v_n]


def _visit_call(data_cm, lo, up, chunk, rows, steps, tile_n, interpret):
    """The visit kernel over a ``chunk``-long list, of which the grid runs
    the first ``steps`` entries (an int, or a traced count: Mosaic's dynamic
    grid bound)."""
    m_pad = data_cm.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((None, m_pad, 1), lambda i, q, b: (q[i], 0, 0)),
            pl.BlockSpec((None, m_pad, 1), lambda i, q, b: (q[i], 0, 0)),
            pl.BlockSpec((m_pad, tile_n),
                         lambda i, q, b: (0, jnp.maximum(b[i], 0))),
        ],
        out_specs=pl.BlockSpec((rows, tile_n), lambda i, q, b: (i // rows, 0)),
    )
    return pl.pallas_call(
        _multi_visit_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((chunk, tile_n), jnp.int8),
        interpret=interpret,
    )


# -- the visit list on the device ----------------------------------------------
# A (Q, n_leaves) prune mask becomes the visit list without leaving the
# device: survivor number j, in the query-major, leaf-ascending order of
# ``np.nonzero``, is found by three searches over prefix counts, each a
# compare over at most ``LANES`` entries (plus one over the few groups of
# ``LANES * LANES`` mask bits). No scatter and no sort: each slot of a chunk
# computes its own pair.


def survivor_prefix(leaf_mask: jax.Array) -> tuple:
    """Prefix counts of a (Q, n_leaves) bool prune mask, for ``nth_survivor``.

    Returns ``(bits, ends, group_ends)``: the mask's bits as (R, LANES)
    int32 rows of the flattened mask (zero-padded to whole groups of LANES
    rows), each row's inclusive running survivor count as (R // LANES,
    LANES), and each group's as (R // LANES,). ``group_ends[-1]`` is the
    number of survivors.
    """
    flat = leaf_mask.reshape(-1)
    group = LANES * LANES
    flat = jnp.pad(flat, (0, -flat.size % group))
    bits = flat.reshape(-1, LANES).astype(jnp.int32)
    ends = jnp.cumsum(bits.sum(axis=1)).astype(jnp.int32).reshape(-1, LANES)
    return bits, ends, ends[:, -1]


def nth_survivor(prefix: tuple, slots: jax.Array) -> jax.Array:
    """(C,) int32 flat mask index of survivor number ``slots`` (0-based, in
    flat order). Slots at or past the survivor count give an index the
    caller must drop."""
    bits, ends, group_ends = prefix
    j = slots[:, None]
    before = lambda e: jnp.max(jnp.where(e <= j, e, 0), axis=1)
    g = jnp.minimum(jnp.sum(group_ends[None, :] <= j, axis=1),
                    group_ends.size - 1)
    g_ends = ends[g]                                         # (C, LANES)
    r = g * LANES + jnp.minimum(jnp.sum(g_ends <= j, axis=1), LANES - 1)
    # survivors in the rows before row r, then the rank of j inside it
    k = slots - jnp.maximum(before(g_ends), before(group_ends[None, :]))
    seen = jnp.cumsum(bits[r], axis=1)                       # (C, LANES)
    lane = jnp.minimum(jnp.sum(seen <= k[:, None], axis=1), LANES - 1)
    return r * LANES + lane
