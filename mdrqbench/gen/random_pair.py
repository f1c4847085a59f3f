"""The paper's query generator for its synthetic data (arXiv 1801.03644 §7.2.1).

A copy of ``repro.data.synthetic.random_pair_query``: each query is the box
spanned by two objects drawn from the data, a complete match on every
dimension.
"""
import numpy as np


def make(cols: np.ndarray, n_queries: int, rng: np.random.Generator,
         params: dict):
    n = cols.shape[1]
    i = rng.integers(n, size=n_queries)
    j = rng.integers(n, size=n_queries)
    a, b = cols[:, i].T, cols[:, j].T
    return np.minimum(a, b), np.maximum(a, b)
