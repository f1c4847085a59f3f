"""Pallas TPU kernel: block-visit decode attention (zone-map-pruned KV).

§Perf cell 3 showed that XLA cannot keep a pruned-KV gather shard-local: the
`take_along_axis` over the block axis lowers to cross-device all-gathers and
the gather's HLO cost counts the full cache operand. This kernel is the
TPU-native fix — the same scalar-prefetch visit-list idiom as
``multi_scan_visit`` (the MDRQ engine's two-phase refine), applied to
attention:

  * the host (or a tiny jnp prune pass over the zone maps) produces a per
    (batch, kv-head) list of key-block ids to visit;
  * the grid is (B, KV, n_visit) — each step DMAs exactly ONE (bs, hd) key
    block and value block selected by the prefetched id; unselected blocks
    are never touched;
  * softmax is streamed across visits (running max / denominator / weighted
    accumulator in VMEM scratch), so no (S,) score row ever materializes.

Cache layout is block-major ``(B, KV, nb, bs, hd)`` — the layout a pruned
production cache would use natively.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import numerics

F32 = jnp.float32
NEG = numerics.mask_fill(jnp.bfloat16)  # finite under every score dtype


def _kernel(ids_ref, pos_ref, q_ref, k_ref, v_ref, out_ref,
            m_ref, l_ref, acc_ref, *, bs: int, scale: float):
    b = pl.program_id(0)
    h = pl.program_id(1)
    j = pl.program_id(2)
    n_visit = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(F32)            # (G, hd)
    k = k_ref[0, 0, 0].astype(F32)         # (bs, hd)
    v = v_ref[0, 0, 0].astype(F32)

    blk = ids_ref[b, h, j]
    slots = blk * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    # padding entries are -1 (index_map clamps the DMA to block 0; the mask
    # kills the contribution so nothing is double-counted)
    valid = (slots <= pos_ref[b]) & (blk >= 0)  # (1, bs)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (G, bs)
    s = jnp.where(valid, s, NEG)

    m_prev = m_ref[...]                    # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                 # (G, bs)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))    # (G, hd)
    m_ref[...] = m_new

    @pl.when(j == n_visit - 1)
    def _emit():
        denom = jnp.maximum(l_ref[...], 1e-30)
        out_ref[0, 0] = (acc_ref[...] / denom).astype(out_ref.dtype)


def kv_visit_attention(
    q: jax.Array,           # (B, KV, G, hd) grouped query for one token
    k_blocks: jax.Array,    # (B, KV, nb, bs, hd)
    v_blocks: jax.Array,    # (B, KV, nb, bs, hd)
    block_ids: jax.Array,   # (B, KV, n_visit) int32 (may repeat; host-dedup)
    pos: jax.Array,         # (B,) int32 current decode positions
    *,
    interpret: bool = False,
) -> jax.Array:
    """Decode attention over only the listed key blocks -> (B, KV, G, hd)."""
    b, kv, g, hd = q.shape
    nb, bs = k_blocks.shape[2], k_blocks.shape[3]
    n_visit = block_ids.shape[-1]
    scale = hd ** -0.5

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kv, n_visit),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda bi, hi, j, ids, pos: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, 1, bs, hd),
                         lambda bi, hi, j, ids, pos: (bi, hi, jnp.maximum(ids[bi, hi, j], 0), 0, 0)),
            pl.BlockSpec((1, 1, 1, bs, hd),
                         lambda bi, hi, j, ids, pos: (bi, hi, jnp.maximum(ids[bi, hi, j], 0), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd),
                               lambda bi, hi, j, ids, pos: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), F32),
            pltpu.VMEM((g, 1), F32),
            pltpu.VMEM((g, hd), F32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, bs=bs, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, g, hd), q.dtype),
        interpret=interpret,
    )(block_ids.astype(jnp.int32), pos.astype(jnp.int32), q, k_blocks, v_blocks)
