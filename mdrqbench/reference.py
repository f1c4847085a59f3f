"""Plain numpy reference for range queries over (m, n) float32 columns.

An object matches a query when ``lower[d] <= x[d] <= upper[d]`` on every
dimension; a dimension whose bounds are -inf and +inf constrains nothing.
Dimensions are tested one after another on the objects that survived the
ones before, which keeps a query over 10M rows to tens of milliseconds.
``dtype`` rounds data and bounds to a lower precision first: that is the
control a sound comparison has to reject.
"""
import numpy as np


def match_ids(cols: np.ndarray, lower: np.ndarray, upper: np.ndarray,
              dtype=np.float32) -> np.ndarray:
    """Sorted int64 ids of the objects that match one query."""
    lower = np.asarray(lower, np.float32).astype(dtype)
    upper = np.asarray(upper, np.float32).astype(dtype)
    ids = None
    for d in range(cols.shape[0]):
        if np.isneginf(lower[d]) and np.isposinf(upper[d]):
            continue
        x = cols[d] if ids is None else cols[d, ids]
        x = x.astype(dtype, copy=False)
        keep = np.flatnonzero((x >= lower[d]) & (x <= upper[d]))
        ids = keep if ids is None else ids[keep]
    if ids is None:
        ids = np.arange(cols.shape[1])
    return ids.astype(np.int64)
