"""VA-file (TPU adaptation of the paper's §2.2.3 / §5.3).

Kept nearly literal — the VA-file is already a branch-free two-phase scan and
therefore the most TPU-friendly of the paper's MDIS:

  * build: quantize every dimension to 2 bits (4 cells, paper's static
    ``b_j = 2``), boundaries either equal-width over the observed domain (the
    paper's choice) or equal-frequency (exposed as an option, which the paper
    lists as an obvious improvement direction, §8);
  * phase 1: the ``multi_va_filter`` Pallas kernel compares packed
    approximations (16 dims / int32 word) against the approximated queries —
    ints instead of floats, 16x less HBM traffic than the exact scan;
  * phase 2: leaf blocks containing at least one candidate are refined with
    the exact ``multi_scan_visit`` kernel. Blocks with zero candidates are
    never touched — the paper's "buckets whose approximation intersects".

Unlike the tree MDIS, data stays in storage order (no permutation): the
VA-file is a *scan accelerator*, not a clustering structure.

Batched execution runs *both* phases fused: phase 1 is one
``multi_va_filter`` launch per batch (grid ``(n_tiles,)``, packed words
fetched from HBM once per batch) whose candidate masks reduce to per-
(query, block) survivor bits on device — a single small (Q, n_blocks) bool
readback replaces Q per-query mask transfers — and phase 2 flattens the
surviving pairs into one ``multi_visit_reduce`` launch, exactly like the
tree MDIS. A single query is a batch of one.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import types as T
from repro.kernels import ops
from repro.kernels.va_filter import BITS_PER_DIM, pack_codes, DIMS_PER_WORD

# Cells per dimension, derived from the kernel's bit width (paper §2.2.3:
# static b_j = 2 -> 4 cells). The planner's VA cost derives its slack and
# word counts from here too — one constant governs build, kernel, and plan.
CELLS = 1 << BITS_PER_DIM


_next_pow2 = T.next_pow2


@dataclasses.dataclass
class VAFile:
    """A built VA-file instance."""

    data_dev: jax.Array      # (m_pad, n_pad) exact columnar data, storage order
    packed_dev: jax.Array    # (w, n_pad) int32 packed 2-bit approximations
    boundaries: np.ndarray   # (m, CELLS - 1) inner cell boundaries per dim
    tile_n: int
    m: int
    n: int

    # (query, block) pairs the last batch visited
    last_visited_blocks: int = 0

    @property
    def nbytes_index(self) -> int:
        """Approximation storage (the VA-file's memory cost vs a plain scan)."""
        return int(np.prod(self.packed_dev.shape)) * 4

    @property
    def _m_sublane(self) -> int:
        return -(-self.m // 8) * 8

    def query_cells_batch(self, batch: T.QueryBatch, q_pad: int | None = None
                          ) -> tuple[np.ndarray, np.ndarray]:
        """Query-minor (m_s, q_pad or Q) cell bounds for the batched filter.

        Sublane-padded rows — and padding query columns beyond Q — carry
        [0, CELLS-1] match-all bounds (padding queries' rows are dropped by
        the caller). ``searchsorted`` maps -inf to cell 0 and +inf to the
        last cell.
        """
        q_n = len(batch)
        width = q_pad or q_n
        cell_lo = np.zeros((self._m_sublane, width), np.int32)
        cell_hi = np.full((self._m_sublane, width), CELLS - 1, np.int32)
        for d in range(self.m):
            b = self.boundaries[d]
            cell_lo[d, :q_n] = np.searchsorted(b, batch.lower[:, d], side="right")
            cell_hi[d, :q_n] = np.searchsorted(b, batch.upper[:, d], side="right")
        return cell_lo, cell_hi

    def _candidate_blocks_batch(self, batch: T.QueryBatch
                                ) -> tuple[np.ndarray, np.ndarray]:
        """Batched phase 1: one fused filter launch, one small host sync.

        ``multi_va_filter`` evaluates every query's approximation in a single
        launch and reduces the candidate masks to per-
        (query, block) survivor bits on device, so the only device->host
        transfer of the phase is one (Q, n_blocks) bool array — the batch
        counterpart of Q per-query mask readbacks.
        """
        q_n = len(batch)
        q_pad = _next_pow2(q_n)  # pow2 query bucket bounds jit retraces
        cell_lo, cell_hi = self.query_cells_batch(batch, q_pad)
        block_any = ops.multi_va_filter(
            self.packed_dev, jnp.asarray(cell_lo), jnp.asarray(cell_hi),
            m=self.m, tile_n=self.tile_n, block_n=self.tile_n,
        )
        surv = ops.device_get(block_any, stage="launch",
                              path="vafile")[:q_n]  # padding queries drop
        qids, bids = np.nonzero(surv)
        return qids.astype(np.int32), bids.astype(np.int32)

    def query_batch(self, batch: T.QueryBatch, spec: T.ResultSpec = T.IDS,
                    delta=None) -> list:
        """Batched two-phase query: both phases fused, one launch each.

        Phase 1 is a single ``multi_va_filter`` launch for the whole batch
        (one host sync for the (Q, n_blocks) survivor bits); phase 2
        flattens every surviving (query, block) pair into a single
        ``multi_visit_reduce`` call carrying the ResultSpec's on-device
        reducer — reduced shapes (count, top-k, aggregate) ship only their
        payload across the second sync. All per-query dispatch and readback
        taxes amortize over the batch.
        """
        payload, fin = self.launch_batch(batch, spec=spec, delta=delta)
        return fin(ops.device_get(payload) if payload is not None else None)

    def launch_batch(self, batch: T.QueryBatch, spec: T.ResultSpec = T.IDS,
                     delta=None) -> tuple:
        """Device half of the batched two-phase query -> (payload, finalize).

        Phase 1 (the packed filter + its small survivor-bits sync — a
        shape-deciding mid-stage sync, like the tree's prune) and the fused
        visit *launch* run here; the returned ``finalize`` defers the payload
        sync + host finalizers to the caller (the pipelined server's
        finalizer thread). ``payload`` is None when no block survived on a
        frozen dataset.
        """
        from repro.core.blockindex import launch_visits_batch

        spec = T.validate_mode(spec).validate(self.m)
        q_n = len(batch)
        qids, bids = self._candidate_blocks_batch(batch)
        self.last_visited_blocks = int(qids.size)
        return launch_visits_batch(
            self.data_dev, qids, bids, batch, self.tile_n, q_n, spec,
            self.n, perm=None, delta=delta,
        )


def build_vafile(
    dataset: T.Dataset, tile_n: int = 1024, scheme: str = "equal_width"
) -> VAFile:
    """Build a VA-file.

    Args:
      dataset: columnar dataset.
      tile_n: refinement block size.
      scheme: "equal_width" (paper default) or "equal_freq" (quantile cells).
    """
    cols = dataset.cols
    m, n = cols.shape
    if scheme == "equal_width":
        lo = cols.min(axis=1, keepdims=True)
        hi = cols.max(axis=1, keepdims=True)
        steps = np.arange(1, CELLS)[None, :] / CELLS  # (1, 3)
        boundaries = lo + (hi - lo) * steps  # (m, 3)
    elif scheme == "equal_freq":
        qs = np.arange(1, CELLS) / CELLS
        boundaries = np.quantile(cols, qs, axis=1).T  # (m, 3)
    else:
        raise ValueError(scheme)

    codes = np.zeros((m, n), np.uint8)
    for d in range(m):
        codes[d] = np.searchsorted(boundaries[d], cols[d], side="right").astype(np.uint8)
    packed = pack_codes(codes)
    # Pad objects: word 0 of padding must NOT alias cell 0 matches. We pad the
    # exact data with +inf (never matches); approximations may produce false
    # candidates in the padded tail, which the exact refine rejects.
    packed = T.pad_axis(packed, 1, tile_n, 0)
    data_padded, _, _ = ops.prepare_columnar(cols, tile_n=tile_n)
    return VAFile(
        data_dev=jnp.asarray(data_padded),
        packed_dev=jnp.asarray(packed),
        boundaries=boundaries.astype(np.float32),
        tile_n=tile_n,
        m=m,
        n=n,
    )
