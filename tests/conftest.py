"""Shared fixtures. NOTE: no XLA_FLAGS here — tests see 1 CPU device;
multi-device behaviour is tested via subprocesses (test_distributed.py)."""
import numpy as np
import pytest

from repro import obs
from repro.core import Dataset, PerQueryPath, match_ids_np
from repro.kernels import ops


@pytest.fixture(autouse=True)
def reset_metrics():
    """Zero the launch/host-sync counters and every other registry metric
    before each test — launch-budget assertions and exporter tests never see
    another test's traffic. (``registry().reset()`` keeps the metric objects,
    so references cached in ``kernels.ops`` stay live.)"""
    ops.reset_counters()
    obs.registry().reset()
    yield


@pytest.fixture(scope="session")
def uni5():
    rng = np.random.default_rng(42)
    return Dataset(rng.random((5, 20_000), dtype=np.float32))


@pytest.fixture(scope="session")
def uni19():
    rng = np.random.default_rng(43)
    return Dataset(rng.random((19, 8_192), dtype=np.float32))


class _NumpyRows:
    """Per-query ``query``/``count`` functions over ``cols`` and nothing
    else: what a structure without a batch kernel brings to the registry."""

    def __init__(self, cols):
        self.cols = cols

    def query(self, q):
        return match_ids_np(self.cols, q)

    def count(self, q):
        return int(match_ids_np(self.cols, q).size)


@pytest.fixture(scope="session")
def per_query_path():
    """Factory: ``per_query_path(name, table, **kw)`` -> a ``PerQueryPath``
    (the extension rung) over the numpy oracle of ``table``."""
    def make(name, table, **kw):
        return PerQueryPath(name, _NumpyRows(table), **kw)
    return make


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(1234)
