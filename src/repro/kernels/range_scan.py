"""Pallas TPU kernel: vectorized multidimensional range scan.

TPU-native adaptation of the paper's Listing 2 (AVX compare of a query object
against data objects). Differences forced by the hardware (see DESIGN.md §2):

  * layout is **dimension-major** ``(m, n)`` — the lane axis runs over objects,
    so one VPU op compares 128 objects of one attribute against one bound;
  * there is no per-lane early break; the AND across dimensions happens in
    vector registers (the paper's vertical-partitioning bitmask-merge, §3.2,
    collapsed into a single in-register reduction);
  * blocks are (m_pad, TN) VMEM tiles: m is padded to a multiple of 8
    (sublanes), TN is a multiple of 128 (lanes).

Entry points:

  * ``range_scan_tiles``     — full scan: grid over all n/TN tiles.
  * ``range_scan_rows``      — the row-major layout, for the layout ablation.
  * ``range_scan_visit``     — two-phase scan: a scalar-prefetched list of
    block ids selects which tiles are visited (kd-tree / R-tree / VA-file
    refinement). Grid size = number of visited blocks, so pruned blocks cost
    *nothing* — the TPU analogue of "skip subtrees".

``range_scan_visit`` is the batched visit kernel of ``multi_scan`` at Q=1; the
single-query partial-match scan is ``multi_scan_tiles`` at Q=1 (``ops``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
SUBLANES = 8
# int8's native tile is (32, 128): batched kernels write their (Q, tile_n)
# int8 masks in chunks of this many query rows.
INT8_SUBLANES = 32
DEFAULT_TILE_N = 1024


def _scan_kernel(lower_ref, upper_ref, data_ref, out_ref):
    """Compare one (m_pad, TN) columnar tile against the query bounds."""
    x = data_ref[...]
    lo = lower_ref[...]  # (m_pad, 1), broadcasts over lanes
    up = upper_ref[...]
    ok = jnp.logical_and(x >= lo, x <= up)
    out_ref[...] = jnp.all(ok, axis=0, keepdims=True).astype(jnp.int8)


def range_scan_tiles(
    data_cm: jax.Array,
    lower: jax.Array,
    upper: jax.Array,
    *,
    tile_n: int = DEFAULT_TILE_N,
    interpret: bool = False,
) -> jax.Array:
    """Full columnar range scan.

    Args:
      data_cm: (m_pad, n_pad) columnar data; m_pad % 8 == 0, n_pad % tile_n == 0.
        Padding dims must carry match-all bounds; padding objects are dropped by
        the caller.
      lower, upper: (m_pad, 1) bounds in data dtype (finite — caller replaces
        +-inf with dtype extrema).

    Returns:
      (n_pad,) int8 match mask.
    """
    m_pad, n_pad = data_cm.shape
    assert m_pad % SUBLANES == 0, m_pad
    assert n_pad % tile_n == 0 and tile_n % LANES == 0, (n_pad, tile_n)
    assert lower.shape == (m_pad, 1) and upper.shape == (m_pad, 1)

    grid = (n_pad // tile_n,)
    out = pl.pallas_call(
        _scan_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((m_pad, 1), lambda i: (0, 0)),
            pl.BlockSpec((m_pad, 1), lambda i: (0, 0)),
            pl.BlockSpec((m_pad, tile_n), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, tile_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.int8),
        interpret=interpret,
    )(lower.astype(data_cm.dtype), upper.astype(data_cm.dtype), data_cm)
    return out[0]


def _rows_kernel(lower_ref, upper_ref, data_ref, out_ref):
    """Row-major (horizontal-layout) tile: lanes run over dimensions."""
    x = data_ref[...]  # (TR, m_pad)
    lo = lower_ref[...]  # (1, m_pad)
    up = upper_ref[...]
    ok = jnp.logical_and(x >= lo, x <= up)
    out_ref[...] = jnp.all(ok, axis=1, keepdims=True).astype(jnp.int8)


def range_scan_rows(
    data_rm: jax.Array,
    lower: jax.Array,
    upper: jax.Array,
    *,
    tile_rows: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Row-major scan (the paper's horizontal layout, §3.1/§5.4).

    Exists for the layout ablation (Fig. 4): lane-axis = dimensions wastes
    128-m lanes for small m and forces a cross-lane reduction, which is why
    the columnar layout is the TPU-canonical one.

    Args:
      data_rm: (n_pad, m_pad) row-major data, n_pad % tile_rows == 0.
      lower, upper: (1, m_pad) finite bounds.

    Returns:
      (n_pad,) int8 match mask.
    """
    n_pad, m_pad = data_rm.shape
    assert n_pad % tile_rows == 0

    grid = (n_pad // tile_rows,)
    out = pl.pallas_call(
        _rows_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, m_pad), lambda i: (0, 0)),
            pl.BlockSpec((1, m_pad), lambda i: (0, 0)),
            pl.BlockSpec((tile_rows, m_pad), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tile_rows, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, 1), jnp.int8),
        interpret=interpret,
    )(lower.astype(data_rm.dtype), upper.astype(data_rm.dtype), data_rm)
    return out[:, 0]


def range_scan_visit(
    data_cm: jax.Array,
    block_ids: jax.Array,
    lower: jax.Array,
    upper: jax.Array,
    *,
    tile_n: int = DEFAULT_TILE_N,
    interpret: bool = False,
) -> jax.Array:
    """Two-phase scan: visit only the listed (m_pad, tile_n) blocks.

    The batched visit kernel with every visit on query 0.

    Args:
      data_cm: (m_pad, n_pad) columnar data, n_pad % tile_n == 0.
      block_ids: (n_visit,) int32 tile indices into [0, n_pad / tile_n); padding
        entries are negative (clamped to 0; callers drop their output rows).
      lower, upper: (m_pad, 1) finite bounds.

    Returns:
      (n_visit, tile_n) int8 per-visit masks.
    """
    from repro.kernels import multi_scan as _ms  # deferred: _ms imports us
    qids = jnp.zeros(block_ids.shape, jnp.int32)
    return _ms.multi_scan_visit(data_cm, qids, block_ids, lower, upper,
                                tile_n=tile_n, interpret=interpret)
