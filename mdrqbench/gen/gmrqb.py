"""GMRQB data (arXiv 1801.03644 §6): 19 attributes of genomic variant records.

A copy of ``repro.data.gmrqb.build``. The 1000 Genomes extract the paper uses
is not redistributable, so this is a shape-faithful stand-in: each attribute
follows the published domain and cardinality (chromosome 1-23, location up
to 2.5e8 with variation-rich regions, hashed categoricals, skewed quality and
depth, beta-distributed allele frequencies).
"""
import numpy as np

M = 19
LOC_MAX = 2.5e8


def build(cfg: dict, rng: np.random.Generator) -> np.ndarray:
    n = int(cfg["rows"])
    if int(cfg.get("dims", M)) != M:
        raise ValueError(f"GMRQB has {M} attributes, config says {cfg['dims']}")
    cols = np.empty((M, n), dtype=np.float32)
    cols[0] = rng.integers(1, 24, size=n)                      # chromosome
    hot = rng.random(n) < 0.6                                  # location
    centers = rng.choice(np.linspace(0.05, 0.95, 40), size=n) * LOC_MAX
    cols[1] = np.where(
        hot,
        np.clip(centers + rng.normal(0, LOC_MAX * 0.004, size=n), 0, LOC_MAX),
        rng.random(n) * LOC_MAX,
    )
    cols[2] = 100.0 * rng.beta(5.0, 1.5, size=n)               # quality
    cols[3] = np.minimum(5000, np.exp(rng.normal(3.5, 1.0, size=n)))  # depth
    cols[4] = rng.integers(0, 3, size=n)                       # reference genome
    cols[5] = rng.permutation(n).astype(np.float32)            # variation id
    cols[6] = rng.beta(0.2, 2.0, size=n)                       # allele frequency
    cols[7] = np.ceil(cols[6] * 5008.0) + 1.0                  # allele count
    cols[8] = rng.integers(0, 4, size=n)                       # ref base
    cols[9] = rng.integers(0, 4, size=n)                       # alt base
    cols[10] = rng.integers(0, 5, size=n)                      # ancestral allele
    cols[11] = rng.integers(0, 6, size=n)                      # variant type
    cols[12] = rng.integers(0, 2504, size=n)                   # sample id
    cols[13] = rng.integers(0, 2, size=n)                      # gender
    cols[14] = (cols[12] // 1.4).astype(np.float32)            # family id
    cols[15] = (cols[12] % 26).astype(np.float32)              # population
    cols[16] = rng.integers(0, 9, size=n)                      # relationship
    cols[17] = rng.integers(0, 3, size=n)                      # genotype
    cols[18] = np.clip(rng.normal(45, 18, size=n), 1, 90)      # age
    return cols
