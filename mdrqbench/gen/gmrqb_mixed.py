"""GMRQB's query templates 1-8 (arXiv 1801.03644 §6.2, Table 1).

A copy of ``repro.data.gmrqb.template``. Every template constrains
chromosome and location; higher templates add attributes, up to template 8,
a 19-dimension match around one record. ``params["templates"]`` (default
1-8) lists the templates in the mix; each gets an equal share of the queries
(the remainder spread by the seed), in an order drawn from the seed, so that
every seed sends the same mix.
"""
import numpy as np

from mdrqbench.gen.gmrqb import LOC_MAX

INF = np.float32(np.inf)


def _loc_range(rng, frac):
    width = frac * LOC_MAX
    start = rng.random() * (LOC_MAX - width)
    return start, start + width


def _partial(m, preds):
    lo = np.full((m,), -INF, np.float32)
    up = np.full((m,), INF, np.float32)
    for j, (a, b) in preds.items():
        lo[j], up[j] = np.float32(a), np.float32(b)
    return lo, up


def template(k: int, rng: np.random.Generator, cols: np.ndarray):
    """(lower, upper) of one instance of template ``k``."""
    m = cols.shape[0]
    chrom = float(rng.integers(1, 24))
    if k == 1:      # 2 dims, ~10%
        lo, hi = _loc_range(rng, 0.40)
        return _partial(m, {0: (chrom, min(23.0, chrom + 5)), 1: (lo, hi)})
    if k == 2:      # 5 dims, ~2%
        lo, hi = _loc_range(rng, 0.45)
        return _partial(m, {0: (chrom, min(23.0, chrom + 4)), 1: (lo, hi),
                            2: (10.0, 100.0), 3: (10.0, 1000.0), 6: (0.03, 1.0)})
    if k == 3:      # 3 dims, ~5%
        lo, hi = _loc_range(rng, 0.35)
        return _partial(m, {0: (chrom, min(23.0, chrom + 4)), 1: (lo, hi),
                            2: (40.0, 100.0)})
    if k == 4:      # 4 dims, ~0.2%
        lo, hi = _loc_range(rng, 0.15)
        return _partial(m, {0: (chrom, chrom), 1: (lo, hi), 3: (10.0, 1000.0),
                            6: (0.05, 0.9)})
    if k == 5:      # 5 dims, ~0.2%
        lo, hi = _loc_range(rng, 0.25)
        return _partial(m, {0: (chrom, chrom), 1: (lo, hi), 2: (20.0, 95.0),
                            13: (0.0, 0.0), 6: (0.01, 0.8)})
    if k == 6:      # 6 dims, ~0.1%
        lo, hi = _loc_range(rng, 0.3)
        pop = float(rng.integers(0, 26))
        return _partial(m, {0: (chrom, chrom), 1: (lo, hi), 2: (10.0, 100.0),
                            15: (pop, pop + 3), 3: (5.0, 2000.0),
                            18: (20.0, 70.0)})
    if k == 7:      # 7 dims, ~0.05%
        lo, hi = _loc_range(rng, 0.35)
        gt = float(rng.integers(0, 3))
        return _partial(m, {0: (chrom, chrom), 1: (lo, hi), 2: (20.0, 100.0),
                            3: (10.0, 1500.0), 6: (0.02, 0.95),
                            17: (gt, gt), 13: (1.0, 1.0)})
    if k == 8:      # 19 dims, complete match around one record, ~1e-7
        rec = cols[:, rng.integers(cols.shape[1])].astype(np.float64)
        lo, hi = rec.copy(), rec.copy()
        lo[1], hi[1] = max(0.0, rec[1] - 5e4), rec[1] + 5e4
        lo[2], hi[2] = max(0, rec[2] - 5), min(100, rec[2] + 5)
        lo[3], hi[3] = max(1, rec[3] * 0.5), rec[3] * 2.0
        lo[6], hi[6] = max(0, rec[6] - 0.05), min(1, rec[6] + 0.05)
        lo[18], hi[18] = max(1, rec[18] - 10), min(90, rec[18] + 10)
        lo[5], hi[5] = 0.0, float(cols.shape[1])
        return lo.astype(np.float32), hi.astype(np.float32)
    raise ValueError(f"template must be 1..8, got {k}")


def make(cols: np.ndarray, n_queries: int, rng: np.random.Generator,
         params: dict):
    templates = [int(k) for k in params.get("templates", range(1, 9))]
    share, extra = divmod(n_queries, len(templates))
    ks = np.repeat(templates, share)
    ks = np.concatenate([ks, rng.choice(templates, extra, replace=False)])
    rng.shuffle(ks)
    m = cols.shape[0]
    lower = np.empty((n_queries, m), np.float32)
    upper = np.empty((n_queries, m), np.float32)
    for i, k in enumerate(ks):
        lower[i], upper[i] = template(int(k), rng, cols)
    return lower, upper
