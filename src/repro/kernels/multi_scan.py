"""Pallas TPU kernels: fused multi-query (batched) range scans.

Batched execution — the inter-query-parallelism counterpart of the paper's
intra-query parallel scans (§5): analytical MDRQ workloads are *streams* of
queries (GMRQB issues eight templates concurrently, §6), and a single-query
launch pays the full dispatch + host-sync tax per query. These kernels
evaluate a (Q, m) batch of query boxes against the (m, n) columnar dataset in
one launch, so the fixed overheads amortize over Q and each VMEM data tile is
fetched from HBM *once* and compared against all Q queries while resident.

Three variants, mirroring the single-query entry points in ``range_scan``:

  * ``multi_scan_tiles``    — fused full scan: grid ``(n_tiles,)``, one
    (m_pad, tile_n) data tile and one (Q, tile_n) int8 mask block per step.
  * ``multi_scan_vertical`` — batched partial-match scan: grid
    ``(n_tiles, n_groups)`` fetching only the 8-dim sublane groups that some
    query of the batch constrains (the paper's vertical partitioning at the
    TPU's sublane granularity).
  * ``multi_scan_visit``    — batched two-phase refinement: a flattened
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

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.range_scan import (DEFAULT_TILE_N, INT8_SUBLANES, LANES,
                                      SUBLANES)


def _match_rows(x, lo, up):
    """(Q, TN) bool: AND over the rows of an (r, TN) data tile against
    (Q, r) query-major bounds. Each data row broadcasts over the query
    sublanes and each query's bound over the lanes."""
    acc = None
    for d in range(x.shape[0]):
        row = x[d:d + 1, :]
        ok = jnp.logical_and(row >= lo[:, d:d + 1], row <= up[:, d:d + 1])
        acc = ok if acc is None else jnp.logical_and(acc, ok)
    return acc


def _store_matches(out_ref, x, lower_ref, upper_ref, *, merge=None):
    """Write (Q, TN) int8 matches of tile ``x`` in int8-tile row chunks;
    with ``merge`` (a traced bool) AND them into what ``out_ref`` holds."""
    q_n = out_ref.shape[0]
    for q0 in range(0, q_n, INT8_SUBLANES):
        q1 = min(q0 + INT8_SUBLANES, q_n)
        ok = _match_rows(x, lower_ref[q0:q1, :], upper_ref[q0:q1, :])
        if merge is not None:
            prev = out_ref[q0:q1, :] != 0
            ok = jnp.logical_and(ok, jnp.logical_or(prev, jnp.logical_not(merge)))
        out_ref[q0:q1, :] = ok.astype(jnp.int8)


def _multi_scan_kernel(lower_ref, upper_ref, data_ref, out_ref):
    """Compare one (m_pad, TN) data tile against every query's bounds."""
    _store_matches(out_ref, data_ref[...], lower_ref, upper_ref)


def multi_scan_tiles(
    data_cm: jax.Array,
    lower: jax.Array,
    upper: jax.Array,
    *,
    tile_n: int = DEFAULT_TILE_N,
    interpret: bool = False,
) -> jax.Array:
    """Fused full scan of a query batch.

    Args:
      data_cm: (m_pad, n_pad) columnar data; m_pad % 8 == 0, n_pad % tile_n == 0.
      lower, upper: (m_pad, Q) finite bounds, one column per query.

    Returns:
      (Q, n_pad) int8 match masks, row q = query q.
    """
    m_pad, n_pad = data_cm.shape
    q_n = lower.shape[1]
    assert m_pad % SUBLANES == 0, m_pad
    assert n_pad % tile_n == 0 and tile_n % LANES == 0, (n_pad, tile_n)
    assert lower.shape == (m_pad, q_n) and upper.shape == (m_pad, q_n)

    grid = (n_pad // tile_n,)
    return pl.pallas_call(
        _multi_scan_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((q_n, m_pad), lambda i: (0, 0)),
            pl.BlockSpec((q_n, m_pad), lambda i: (0, 0)),
            pl.BlockSpec((m_pad, tile_n), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((q_n, tile_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((q_n, n_pad), jnp.int8),
        interpret=interpret,
    )(lower.T.astype(data_cm.dtype), upper.T.astype(data_cm.dtype), data_cm)


def _multi_vertical_kernel(group_ids_ref, lower_ref, upper_ref, data_ref,
                           out_ref):
    """One grid step = (tile, dim group); AND-merge in place over groups."""
    j = pl.program_id(1)
    _store_matches(out_ref, data_ref[...], lower_ref, upper_ref, merge=j > 0)


def _needed_groups(dim_ids: jax.Array, n_groups: int) -> jax.Array:
    """(n_groups,) int32 ascending ids of the sublane groups any query of the
    batch constrains, padded by repeating the last one. A repeated block
    index is not fetched again, and AND is idempotent."""
    need = jnp.zeros((n_groups,), jnp.bool_).at[
        dim_ids.reshape(-1) // SUBLANES].set(True)
    order = jnp.sort(jnp.where(need, jnp.arange(n_groups), n_groups))
    last = order[jnp.sum(need) - 1]
    return jnp.where(order < n_groups, order, last).astype(jnp.int32)


def multi_scan_vertical(
    data_cm: jax.Array,
    dim_ids: jax.Array,
    lower: jax.Array,
    upper: jax.Array,
    *,
    tile_n: int = DEFAULT_TILE_N,
    interpret: bool = False,
) -> jax.Array:
    """Batched partial-match vertical scan.

    Only the (8, tile_n) sublane groups holding a dim that some query
    constrains are read from HBM; every query is compared on every dim of
    those groups, which is exact because unconstrained dims carry match-all
    bounds.

    Args:
      data_cm: (m_pad, n_pad) columnar data.
      dim_ids: (Q, D_max) int32 per-query constrained-dimension ids (padding
        repeats one of the query's own dims; a match-all query uses dim 0).
      lower, upper: (m_pad, Q) finite bounds, match-all on unconstrained dims.

    Returns:
      (Q, n_pad) int8 match masks over each query's constrained dims.
    """
    m_pad, n_pad = data_cm.shape
    q_n = dim_ids.shape[0]
    assert m_pad % SUBLANES == 0, m_pad
    assert n_pad % tile_n == 0 and tile_n % LANES == 0, (n_pad, tile_n)
    assert lower.shape == (m_pad, q_n) and upper.shape == (m_pad, q_n)
    n_groups = m_pad // SUBLANES

    def grouped(b):  # (m_pad, Q) -> (n_groups, Q, 8): one block per group
        return b.T.astype(data_cm.dtype).reshape(
            q_n, n_groups, SUBLANES).transpose(1, 0, 2)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_pad // tile_n, n_groups),
        in_specs=[
            pl.BlockSpec((None, q_n, SUBLANES), lambda i, j, g: (g[j], 0, 0)),
            pl.BlockSpec((None, q_n, SUBLANES), lambda i, j, g: (g[j], 0, 0)),
            pl.BlockSpec((SUBLANES, tile_n), lambda i, j, g: (g[j], i)),
        ],
        out_specs=pl.BlockSpec((q_n, tile_n), lambda i, j, g: (0, i)),
    )
    return pl.pallas_call(
        _multi_vertical_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((q_n, n_pad), jnp.int8),
        interpret=interpret,
    )(_needed_groups(dim_ids, n_groups), grouped(lower), grouped(upper),
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
) -> jax.Array:
    """Batched two-phase refinement: visit each (query, block) pair once.

    Each grid step fills one row of a (rows, tile_n) output block that stays
    resident for ``rows`` consecutive visits (rows = ``INT8_SUBLANES``, or V
    when the list is shorter), so the int8 output keeps its native tile.
    Lists longer than ``VISIT_CHUNK`` run as a loop of kernels over chunks.

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
    n_visit = block_ids.shape[0]
    q_n = lower.shape[1]
    assert query_ids.shape == (n_visit,)
    assert m_pad % SUBLANES == 0 and n_pad % tile_n == 0
    rows = min(INT8_SUBLANES, n_visit)
    chunk = min(VISIT_CHUNK, -(-n_visit // rows) * rows)
    v_pad = -(-n_visit // chunk) * chunk
    qids = jnp.pad(query_ids.astype(jnp.int32), (0, v_pad - n_visit))
    bids = jnp.pad(block_ids.astype(jnp.int32), (0, v_pad - n_visit),
                   constant_values=-1)

    def columns(b):  # (m_pad, Q) -> (Q, m_pad, 1): one bounds block per query
        return b.T.astype(data_cm.dtype).reshape(q_n, m_pad, 1)

    lo, up = columns(lower), columns(upper)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(chunk,),
        in_specs=[
            pl.BlockSpec((None, m_pad, 1), lambda i, q, b: (q[i], 0, 0)),
            pl.BlockSpec((None, m_pad, 1), lambda i, q, b: (q[i], 0, 0)),
            pl.BlockSpec((m_pad, tile_n),
                         lambda i, q, b: (0, jnp.maximum(b[i], 0))),
        ],
        out_specs=pl.BlockSpec((rows, tile_n), lambda i, q, b: (i // rows, 0)),
    )
    call = pl.pallas_call(
        _multi_visit_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((chunk, tile_n), jnp.int8),
        interpret=interpret,
    )
    if v_pad == chunk:
        out = call(qids, bids, lo, up, data_cm)
    else:  # one launch still: the chunks loop inside this jit
        out = jax.lax.map(
            lambda qb: call(qb[0], qb[1], lo, up, data_cm),
            (qids.reshape(-1, chunk), bids.reshape(-1, chunk)),
        ).reshape(v_pad, tile_n)
    return out if v_pad == n_visit else out[:n_visit]
