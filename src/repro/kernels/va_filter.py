"""Pallas TPU kernel: VA-file approximation filter on packed 2-bit codes.

The paper's VA-file (§2.2.3, §5.3) quantizes every dimension to 2 bits and
scans the *approximations* first; only buckets whose approximation intersects
the approximated query are refined against the exact data. On TPU this is the
most natural of the three MDIS: the approximation scan is a branch-free packed
integer compare that is 16x denser than the float scan (16 dims per int32
word), converting the first phase from HBM-bandwidth-bound to nearly free.

Packing: word ``w`` of object ``i`` holds dims ``[16w, 16w+16)`` — dim
``16w + k`` occupies bits ``[2k, 2k+2)``. The kernel unpacks with static
shift/mask ops (VPU int32 lanes) and AND-reduces across dims in registers.

``multi_va_filter_packed`` filters a whole query batch in one launch (a
single query is a batch of one): grid ``(n_tiles,)`` with a (Q, tile_n) mask
block per step, so each (w, tile_n) packed-word tile streams from HBM once
per *batch* — the same fusion ``multi_scan`` applies to the exact scans, here
applied to the approximation phase.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.multi_scan import INT8_SUBLANES, LANES
DEFAULT_TILE_N = 2048
# The paper's static cell resolution (b_j = 2, §2.2.3). Everything downstream
# — word packing density, the planner's candidate-fraction slack and
# approximation byte count, ``vafile.CELLS`` — derives from this one constant
# so a resolution change cannot silently skew one layer against another.
BITS_PER_DIM = 2
CODE_MASK = (1 << BITS_PER_DIM) - 1
DIMS_PER_WORD = 32 // BITS_PER_DIM


def pack_codes(codes: np.ndarray) -> np.ndarray:
    """Pack (m, n) uint8 cell codes into (ceil(m/DIMS_PER_WORD), n) int32."""
    m, n = codes.shape
    w = -(-m // DIMS_PER_WORD)
    out = np.zeros((w, n), dtype=np.int32)
    for d in range(m):
        wi, k = divmod(d, DIMS_PER_WORD)
        out[wi] |= codes[d].astype(np.int32) << (BITS_PER_DIM * k)
    return out


def _multi_va_kernel(qlo_ref, qhi_ref, packed_ref, out_ref, *, m: int):
    """Unpack-compare one (w, TN) word tile against every query's cell
    bounds; (Q, TN) int8 out, written in int8-tile row chunks."""
    words = packed_ref[...]  # (w, tn) int32
    q_n = out_ref.shape[0]
    for q0 in range(0, q_n, INT8_SUBLANES):
        q1 = min(q0 + INT8_SUBLANES, q_n)
        lo = qlo_ref[q0:q1, :]  # (qc, m_s), query-major
        hi = qhi_ref[q0:q1, :]
        acc = None
        for d in range(m):
            wi, k = divmod(d, DIMS_PER_WORD)
            field = jnp.bitwise_and(
                jnp.right_shift(words[wi:wi + 1, :], BITS_PER_DIM * k),
                CODE_MASK)  # (1, tn)
            ok = jnp.logical_and(field >= lo[:, d:d + 1],
                                 field <= hi[:, d:d + 1])
            acc = ok if acc is None else jnp.logical_and(acc, ok)
        out_ref[q0:q1, :] = acc.astype(jnp.int8)


def multi_va_filter_packed(
    packed: jax.Array,
    cell_lo: jax.Array,
    cell_hi: jax.Array,
    m: int,
    *,
    tile_n: int = DEFAULT_TILE_N,
    interpret: bool = False,
) -> jax.Array:
    """Candidate masks for a whole query batch from one launch.

    Grid ``(n_tiles,)``: each (w, tile_n) packed-word tile is fetched from
    HBM once per batch and compared against every query's cell bounds while
    resident in VMEM. Bounds enter as one query-major (Q, m_s) block and the
    masks leave as (Q, tile_n) blocks (the ``multi_scan`` layout).

    Args:
      packed: (w, n_pad) int32 packed codes, n_pad % tile_n == 0.
      cell_lo, cell_hi: (m_s, Q) int32 per-query cell bounds, query-minor
        (one column per query, like the ``multi_scan`` bounds layout); padded
        rows carry [0, 3] match-all bounds.
      m: true dimensionality.

    Returns:
      (Q, n_pad) int8 candidate masks, row q = query q.
    """
    w, n_pad = packed.shape
    assert n_pad % tile_n == 0 and tile_n % LANES == 0
    m_s, q_n = cell_lo.shape
    assert m_s >= m and cell_lo.shape == cell_hi.shape == (m_s, q_n)

    grid = (n_pad // tile_n,)
    return pl.pallas_call(
        functools.partial(_multi_va_kernel, m=m),
        grid=grid,
        in_specs=[
            pl.BlockSpec((q_n, m_s), lambda i: (0, 0)),
            pl.BlockSpec((q_n, m_s), lambda i: (0, 0)),
            pl.BlockSpec((w, tile_n), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((q_n, tile_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((q_n, n_pad), jnp.int8),
        interpret=interpret,
    )(cell_lo.T.astype(jnp.int32), cell_hi.T.astype(jnp.int32), packed)
