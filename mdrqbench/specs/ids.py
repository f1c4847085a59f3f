"""``Ids()``: the sorted int64 ids of the matching objects."""
import numpy as np


def make():
    from repro.core import Ids
    return Ids()


def answer(ids: np.ndarray, cols: np.ndarray):
    return ids


def same(got, want) -> bool:
    got = np.asarray(got)
    return got.shape == want.shape and bool(np.array_equal(got, want))
