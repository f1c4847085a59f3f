"""``Count()``: the number of matching objects."""
import numpy as np


def make():
    from repro.core import Count
    return Count()


def answer(ids: np.ndarray, cols: np.ndarray):
    return int(ids.size)


def same(got, want) -> bool:
    return isinstance(got, (int, np.integer)) and int(got) == want
