"""Pallas TPU kernels: batched masked reducers over (Q, n) match masks.

The ResultSpec layer (``core.types``, DESIGN.md §9) pushes result reduction
onto the device: instead of shipping a (Q, n) match mask to the host and
materializing ids there, a spec's reducer turns the mask into its payload —
top-k values/positions, an aggregate, a count — *inside the same jit* as the
kernel that produced the mask, so only O(Q·k) / O(Q) bytes ever cross the
device->host boundary.

Two Pallas kernels, both on the fused-batch grid ``(n_tiles,)`` the
multi-query scans use, with (Q, tile_n) mask blocks (so the streamed values
tile is fetched from HBM once per batch):

  * ``masked_fill_tiles`` — elementwise select: matching lanes keep the
    attribute value, non-matching lanes take the reduction identity. The
    filled (Q, n_pad) array feeds ``jax.lax.top_k`` in the same jit — the
    TPU-native way to run a batched masked top-k (sorting networks inside a
    Mosaic kernel are not a win over XLA's top_k).
  * ``masked_agg_tiles`` — lane-parallel accumulation: every tile step
    revisits one (Q, tile_n) accumulator block (init at tile 0, combine
    after — an in-place merge over the grid), leaving a
    (Q, tile_n) lane partial whose final cross-lane reduce rides in the
    wrapping jit.

The jnp ``visit_*`` reducers cover the two-phase paths' (V, tile_n) visit
masks (segment reductions by query id). XLA oracles live in ``ref.py``;
the counted public entry points (``multi_scan_reduce`` & co.) in ``ops.py``
compose mask kernel + reducer into one launch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.multi_scan import DEFAULT_TILE_N, LANES

# Reduction identities, keyed by agg op.
AGG_FILL = {"sum": 0.0, "min": float("inf"), "max": float("-inf")}
_AGG_COMBINE = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}
_AGG_FINAL = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}


def _hits(mask_ref):
    """(Q, TN) bool from an int8 mask block, compared in int32: Mosaic cannot
    broadcast a value against a bool vector laid out from int8."""
    return mask_ref[...].astype(jnp.int32) != 0


def _masked_fill_kernel(mask_ref, val_ref, out_ref, *, fill):
    """Matching lanes keep the value; the rest take the identity ``fill``."""
    out_ref[...] = jnp.where(_hits(mask_ref), val_ref[...], jnp.float32(fill))


def masked_fill_tiles(
    masks: jax.Array,
    values: jax.Array,
    fill: float,
    *,
    tile_n: int = DEFAULT_TILE_N,
    interpret: bool = False,
) -> jax.Array:
    """Batched masked fill (the top-k front half).

    Args:
      masks: (Q, n_pad) int8 match masks, n_pad % tile_n == 0.
      values: (n_pad,) attribute values (one dataset row, storage order).
      fill: value for non-matching lanes (the reduction identity).

    Returns:
      (Q, n_pad) float32 filled values.
    """
    q_n, n_pad = masks.shape
    assert n_pad % tile_n == 0 and tile_n % LANES == 0, (n_pad, tile_n)
    assert values.shape == (n_pad,), values.shape

    grid = (n_pad // tile_n,)
    return pl.pallas_call(
        functools.partial(_masked_fill_kernel, fill=float(fill)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((q_n, tile_n), lambda i: (0, i)),
            pl.BlockSpec((1, tile_n), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((q_n, tile_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((q_n, n_pad), jnp.float32),
        interpret=interpret,
    )(masks, values.astype(jnp.float32).reshape(1, n_pad))


def _masked_agg_kernel(mask_ref, val_ref, out_ref, *, op, fill):
    """Accumulate one masked tile into every query's (1, tile_n) lane
    partial (rows of the resident (Q, tile_n) output block)."""
    i = pl.program_id(0)
    part = jnp.where(_hits(mask_ref), val_ref[...], jnp.float32(fill))

    @pl.when(i == 0)
    def _init():
        out_ref[...] = part

    @pl.when(i > 0)
    def _combine():
        out_ref[...] = _AGG_COMBINE[op](out_ref[...], part)


def masked_agg_tiles(
    masks: jax.Array,
    values: jax.Array,
    op: str,
    *,
    tile_n: int = DEFAULT_TILE_N,
    interpret: bool = False,
) -> jax.Array:
    """Batched masked aggregate, reduced to per-query lane partials.

    Args:
      masks: (Q, n_pad) int8 match masks.
      values: (n_pad,) attribute values.
      op: "sum" | "min" | "max".

    Returns:
      (Q, tile_n) float32 lane partials — the caller's final cross-lane
      ``sum/min/max(axis=-1)`` produces the (Q,) aggregates.
    """
    q_n, n_pad = masks.shape
    assert n_pad % tile_n == 0 and tile_n % LANES == 0, (n_pad, tile_n)
    assert values.shape == (n_pad,), values.shape
    fill = AGG_FILL[op]

    # One (Q, tile_n) accumulator block, revisited by every tile step and
    # merged in place; it flushes once.
    grid = (n_pad // tile_n,)
    return pl.pallas_call(
        functools.partial(_masked_agg_kernel, op=op, fill=fill),
        grid=grid,
        in_specs=[
            pl.BlockSpec((q_n, tile_n), lambda i: (0, i)),
            pl.BlockSpec((1, tile_n), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((q_n, tile_n), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((q_n, tile_n), jnp.float32),
        interpret=interpret,
    )(masks, values.astype(jnp.float32).reshape(1, n_pad))


# -- backend-dispatched reducers (called inside the counted ops' jits) --------

def masked_topk(masks, values, k: int, largest: bool, *, tile_n: int,
                interpret: bool):
    """(Q, n_pad) masks + (n_pad,) values -> ((Q,k) vals, (Q,k) idx, (Q,) counts).

    Matching lanes keep their value (Pallas fill kernel or the XLA ref, per
    backend), a device ``top_k`` selects the k extremes, and the per-query
    match count rides along so the host finalizer can truncate queries with
    fewer than k matches. Positions are storage-order column indices (the
    caller maps them through a permutation where one exists). Ties order by
    ascending position — XLA top_k semantics.
    """
    from repro.kernels import ops as _ops
    from repro.kernels import ref as _ref

    fill = -jnp.inf if largest else jnp.inf
    if _ops.use_xla():
        filled = _ref.masked_fill_ref(masks, values, fill)
    else:
        filled = masked_fill_tiles(masks, values, float(fill), tile_n=tile_n,
                                   interpret=interpret)
    key = filled if largest else -filled
    kk = min(int(k), key.shape[-1])
    v, idx = jax.lax.top_k(key, kk)
    counts = jnp.sum(masks != 0, axis=-1).astype(jnp.int32)
    return (v if largest else -v), idx.astype(jnp.int32), counts


def masked_agg(masks, values, op: str, *, tile_n: int, interpret: bool):
    """(Q, n_pad) masks + (n_pad,) values -> ((Q,) aggregates, (Q,) counts).

    Empty matches produce the reduction identity; the host finalizer turns
    them into 0.0 (sum) / NaN (min, max) using the count.
    """
    from repro.kernels import ops as _ops
    from repro.kernels import ref as _ref

    if _ops.use_xla():
        agg = _ref.masked_agg_ref(masks, values, op)
    else:
        lanes = masked_agg_tiles(masks, values, op, tile_n=tile_n,
                                 interpret=interpret)
        agg = _AGG_FINAL[op](lanes, axis=-1)
    counts = jnp.sum(masks != 0, axis=-1).astype(jnp.int32)
    return agg, counts


# -- tombstone folds (mutable data plane, DESIGN.md §11) ----------------------

def fold_tombstones(masks, tomb):
    """AND tombstone flags into match masks: a tombstoned object never matches.

    ``tomb`` is int8 (1 = dead) and broadcasts against ``masks`` — (n_pad,)
    against the (Q, n_pad) scan masks, or a pre-gathered (V, tile_n) block
    against the visit masks. Runs inside the fused reduce jits, before the
    spec's reducer, so every payload shape (counts, top-k, aggregates) sees
    tombstones folded at zero extra launches.
    """
    return masks * (tomb == 0).astype(masks.dtype)


def gather_tomb_blocks(tomb, bids, tile_n: int):
    """(V, tile_n) tombstone flags of the visited blocks (padding visits ->
    block 0; harmless — downstream reducers mask them via ``valid``)."""
    return tomb.reshape(-1, tile_n)[jnp.maximum(bids, 0)]


# -- visit-shaped reducers (two-phase paths; plain jnp segment reductions) ----

def gather_visit_values(data_cm, dim: int, bids, tile_n: int):
    """(V, tile_n) attribute values of the visited blocks (padding -> block 0,
    masked out downstream via ``valid``)."""
    n_blocks = data_cm.shape[1] // tile_n
    blocks = data_cm[dim].reshape(n_blocks, tile_n)
    return blocks[jnp.maximum(bids, 0)]


def visit_mask_counts(masks, qids, valid, n_queries: int):
    """(V, tile_n) visit masks -> (n_queries,) per-query match counts."""
    per_visit = jnp.sum(masks != 0, axis=-1).astype(jnp.int32) * valid
    return jnp.zeros((n_queries,), jnp.int32).at[qids].add(per_visit)


def visit_agg(masks, vblocks, qids, valid, op: str, n_queries: int):
    """Segment-aggregate visit masks by query id -> (n_queries,) float32."""
    fill = jnp.float32(AGG_FILL[op])
    live = jnp.logical_and(masks != 0, valid[:, None] > 0)
    filled = jnp.where(live, vblocks.astype(jnp.float32), fill)
    per_visit = _AGG_FINAL[op](filled, axis=-1)  # (V,)
    init = jnp.full((n_queries,), fill, jnp.float32)
    if op == "sum":
        return init.at[qids].add(per_visit)
    if op == "min":
        return init.at[qids].min(per_visit)
    return init.at[qids].max(per_visit)


def visit_topk(masks, vblocks, bids, valid, visit_index, k: int,
               largest: bool, tile_n: int):
    """Per-query top-k over scattered visit masks, in two stages.

    Stage 1 reduces each (1, tile_n) visit row to its own top-k' partial
    (k' = min(k, tile_n)) plus the matching storage positions. Stage 2
    gathers the partials through ``visit_index`` — the host-built
    (n_queries, M) table of padded-visit row indices per query (M =
    pow2-padded max visits of any query; empty slots point one past the
    last row) — into (Q, M·k') and re-selects the global top-k per query.
    The per-visit pre-reduction keeps the dense gather at Q·M·k' elements
    (vs Q·M·tile_n for a direct gather), so one broad query visiting every
    block costs ~k/tile_n of the naive memory, not a device OOM.

    Returns ((Q, k'') values, (Q, k'') int32 positions), k'' = min(k, M·k').
    """
    fill = jnp.float32(-jnp.inf if largest else jnp.inf)
    live = jnp.logical_and(masks != 0, valid[:, None] > 0)
    key = jnp.where(live, vblocks.astype(jnp.float32), fill)     # (V, t)
    if not largest:
        key = -key
    k1 = min(int(k), tile_n)
    v1, off1 = jax.lax.top_k(key, k1)                            # (V, k1)
    pos1 = jnp.maximum(bids, 0)[:, None] * tile_n + off1         # (V, k1)
    pad_v = jnp.full((1, k1), -jnp.inf, jnp.float32)             # key space
    pad_p = jnp.zeros((1, k1), pos1.dtype)
    g_v = jnp.concatenate([v1, pad_v], axis=0)[visit_index]      # (Q, M, k1)
    g_p = jnp.concatenate([pos1, pad_p], axis=0)[visit_index]
    q_n, m_vis, _ = g_v.shape
    k2 = min(int(k), m_vis * k1)
    v2, j = jax.lax.top_k(g_v.reshape(q_n, m_vis * k1), k2)      # (Q, k2)
    pos = jnp.take_along_axis(g_p.reshape(q_n, m_vis * k1), j, axis=1)
    return (v2 if largest else -v2), pos.astype(jnp.int32)
