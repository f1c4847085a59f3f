"""GMRQB data (arXiv 1801.03644 §6) at hundreds of millions of records.

Draws each attribute from the distribution ``gen/gmrqb.py`` gives it, block
by block: every block of ``BLOCK`` records has a generator of its own, seeded
from a child ``SeedSequence`` of the run's, and writes its records straight
into the (19, n) float32 columns. Blocks run on threads (numpy's generators
release the GIL while they fill an array), and the columns depend on the
seed alone, not on how many threads drew them.

Variation id stays a permutation of 0..n-1: record i gets ``(a*i + b) mod
n`` with ``a`` prime to ``n``, both drawn from the seed, so that each block
computes its own ids. Every other attribute is drawn independently per
record, as ``gen/gmrqb.py`` draws it.
"""
import math
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from mdrqbench.gen.gmrqb import LOC_MAX, M

BLOCK = 1 << 20
CENTERS = np.linspace(0.05, 0.95, 40)


def _fill(cols: np.ndarray, start: int, ss: np.random.SeedSequence,
          perm: tuple) -> None:
    """Records [start, start + BLOCK) of ``cols`` from one block's seed."""
    rng = np.random.default_rng(ss)
    x = cols[:, start:start + BLOCK]
    k = x.shape[1]
    x[0] = rng.integers(1, 24, size=k)                         # chromosome
    hot = rng.random(k) < 0.6                                  # location
    centers = rng.choice(CENTERS, size=k) * LOC_MAX
    x[1] = np.where(
        hot,
        np.clip(centers + rng.normal(0, LOC_MAX * 0.004, size=k), 0, LOC_MAX),
        rng.random(k) * LOC_MAX,
    )
    x[2] = 100.0 * rng.beta(5.0, 1.5, size=k)                  # quality
    x[3] = np.minimum(5000, np.exp(rng.normal(3.5, 1.0, size=k)))  # depth
    x[4] = rng.integers(0, 3, size=k)                          # reference genome
    a, b, n = perm                                             # variation id
    x[5] = (a * np.arange(start, start + k, dtype=np.int64) + b) % n
    x[6] = rng.beta(0.2, 2.0, size=k)                          # allele frequency
    x[7] = np.ceil(x[6] * 5008.0) + 1.0                        # allele count
    x[8] = rng.integers(0, 4, size=k)                          # ref base
    x[9] = rng.integers(0, 4, size=k)                          # alt base
    x[10] = rng.integers(0, 5, size=k)                         # ancestral allele
    x[11] = rng.integers(0, 6, size=k)                         # variant type
    x[12] = rng.integers(0, 2504, size=k)                      # sample id
    x[13] = rng.integers(0, 2, size=k)                         # gender
    x[14] = x[12] // 1.4                                       # family id
    x[15] = x[12] % 26                                         # population
    x[16] = rng.integers(0, 9, size=k)                         # relationship
    x[17] = rng.integers(0, 3, size=k)                         # genotype
    x[18] = np.clip(rng.normal(45, 18, size=k), 1, 90)         # age


def build(cfg: dict, rng: np.random.Generator,
          threads: int | None = None) -> np.ndarray:
    n = int(cfg["rows"])
    if int(cfg.get("dims", M)) != M:
        raise ValueError(f"GMRQB has {M} attributes, config says {cfg['dims']}")
    a = int(rng.integers(1, max(n, 2)))
    while math.gcd(a, n) != 1:
        a = int(rng.integers(1, n))
    perm = (a, int(rng.integers(0, max(n, 1))), max(n, 1))
    starts = range(0, n, BLOCK)
    seeds = np.random.SeedSequence(int(rng.integers(2**63))).spawn(len(starts))
    cols = np.empty((M, n), dtype=np.float32)
    workers = threads or len(os.sched_getaffinity(0))
    with ThreadPoolExecutor(workers) as ex:
        for f in [ex.submit(_fill, cols, s, ss, perm)
                  for s, ss in zip(starts, seeds)]:
            f.result()
    return cols
