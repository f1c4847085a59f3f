"""The tree paths' two visit routes (``ResultSpec.visits_on_device``).

Under Count (and Agg) the kd-tree and R*-tree keep their visit list on the
device: the prune mask goes from the prune launch straight into the visit
launch, which compacts it there and visits as many pairs as survived, and
the batch syncs once. Under Ids, Mask and TopK the host builds the list
after a mid-stage sync, as before. Both routes must give the same answers as
each other and as the numpy oracle, frozen and under a live delta, and
count the same visited pairs (``mdrq_visit_pairs_total``).
"""
import dataclasses
from typing import ClassVar

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import (Count, Dataset, Ids, MDRQEngine, QueryBatch,
                        RangeQuery, TopK, match_ids_np)
from repro.core.blockindex import prune_hierarchy_batch
from repro.kernels import multi_scan as ms
from repro.kernels import ops

PATHS = ("kdtree", "rstar")


@dataclasses.dataclass(frozen=True)
class HostCount(Count):
    """Count held to the host route, the reference the device route must
    equal."""
    visits_on_device: ClassVar[bool] = False


def _queries(cols, rng, n_q):
    out = []
    for _ in range(n_q):
        a = cols[:, rng.integers(cols.shape[1])]
        b = cols[:, rng.integers(cols.shape[1])]
        out.append(RangeQuery.complete(np.minimum(a, b), np.maximum(a, b)))
    return out


def _pairs() -> dict:
    """{(path, route): visited pairs} of ``mdrq_visit_pairs_total``."""
    return {(m.labels["path"], m.labels["route"]): int(m.value)
            for m in obs.registry().series("mdrq_visit_pairs_total")
            if m.value}


def _survivors(index, queries) -> int:
    """``np.nonzero`` of the prune mask of the real queries: the pairs the
    host route visits."""
    batch = QueryBatch.from_queries(queries)
    qlo, qhi = batch.bounds_columnar(index.m)
    mask = prune_hierarchy_batch(index.levels_lo, index.levels_hi,
                                 jnp.asarray(qlo), jnp.asarray(qhi),
                                 fanout=index.fanout)
    return int(np.nonzero(np.asarray(mask))[0].size)


@pytest.fixture(scope="module")
def eng_frozen(uni5):
    return MDRQEngine(uni5, tile_n=512, structures=("scan", "kdtree", "rstar"))


@pytest.fixture(scope="module")
def eng_delta(uni5):
    eng = MDRQEngine(uni5, tile_n=512, structures=("scan", "kdtree", "rstar"))
    rng = np.random.default_rng(181)
    new_ids = eng.append(rng.random((150, uni5.m)).astype(np.float32))
    eng.delete(np.concatenate([rng.choice(uni5.n, 90, replace=False),
                               new_ids[:7]]))
    return eng


def _oracle_counts(eng, queries):
    """Counts over base + delta - tombstones, from the Ids host route."""
    return [int(r.size) for r in eng.query_batch(queries, method="scan",
                                                 spec=Ids())]


@pytest.mark.parametrize("context", ["frozen", "delta"])
@pytest.mark.parametrize("path", PATHS)
def test_count_device_route_equals_host_route_and_oracle(
        path, context, eng_frozen, eng_delta, uni5):
    eng = eng_frozen if context == "frozen" else eng_delta
    queries = _queries(uni5.cols, np.random.default_rng(5), 11)
    ops.reset_counters()
    got = eng.query_batch(queries, method=path, spec=Count())
    assert ops.counters() == {"prune_hierarchy_batch": 1,
                              "multi_visit_reduce": 1, "host_sync": 1}
    assert got == eng.query_batch(queries, method=path, spec=HostCount())
    assert got == _oracle_counts(eng, queries)
    if context == "frozen":
        assert got == [int(match_ids_np(uni5.cols, q).size) for q in queries]


@pytest.mark.parametrize("context", ["frozen", "delta"])
@pytest.mark.parametrize("path", PATHS)
def test_nothing_survives_the_prune(path, context, eng_frozen, eng_delta,
                                    uni5):
    """Boxes outside the data: no pair survives, and the device route still
    runs its one visit launch (and the delta's scan) and counts zero."""
    eng = eng_frozen if context == "frozen" else eng_delta
    m = uni5.m
    queries = [RangeQuery.complete(np.full(m, 2.0 + k), np.full(m, 2.5 + k))
               for k in range(3)]
    index = getattr(eng, path)
    assert eng.query_batch(queries, method=path, spec=Count()) == [0, 0, 0]
    assert index.last_visited_blocks == 0
    assert eng.query_batch(queries, method=path, spec=HostCount()) == \
        [0, 0, 0]
    assert _pairs() == {}


@pytest.mark.parametrize("path", PATHS)
def test_device_route_loops_over_visit_chunks(path, monkeypatch):
    """More surviving pairs than one ``VISIT_CHUNK``, reached with a small
    tile: the device route visits them in chunks inside the one launch and
    still counts every pair once."""
    rng = np.random.default_rng(23)
    cols = rng.random((3, 6000), dtype=np.float32)
    eng = MDRQEngine(Dataset(cols), tile_n=128,
                     structures=("scan", "kdtree", "rstar"))
    queries = [RangeQuery.complete(rng.uniform(0.0, 0.3, 3),
                                   rng.uniform(0.6, 1.0, 3)) for _ in range(6)]
    index = getattr(eng, path)
    n_pairs = _survivors(index, queries)
    monkeypatch.setattr(ms, "VISIT_CHUNK", 64)
    jax.clear_caches()  # VISIT_CHUNK is read when the visit op traces
    try:
        got = eng.query_batch(queries, method=path, spec=Count())
        assert n_pairs > 2 * ms.VISIT_CHUNK
        assert index.last_visited_blocks == n_pairs
        assert got == [int(match_ids_np(cols, q).size) for q in queries]
        assert got == eng.query_batch(queries, method=path, spec=HostCount())
    finally:
        monkeypatch.undo()
        jax.clear_caches()


@pytest.mark.parametrize("path", PATHS)
def test_single_query_takes_the_device_route(path, eng_frozen, uni5):
    """Q = 1: ``engine.query`` under Count is one prune, one visit launch and
    one sync."""
    q = _queries(uni5.cols, np.random.default_rng(9), 1)[0]
    ops.reset_counters()
    assert eng_frozen.query(q, path, spec=Count()) == \
        int(match_ids_np(uni5.cols, q).size)
    assert ops.counters() == {"prune_hierarchy_batch": 1,
                              "multi_visit_reduce": 1, "host_sync": 1}
    assert _pairs() == {(path, "device"): _survivors(
        getattr(eng_frozen, path), [q])}


@pytest.mark.parametrize("spec", [Ids(), TopK(k=3, dim=1)],
                         ids=lambda s: s.kind)
@pytest.mark.parametrize("path", PATHS)
def test_ids_and_topk_keep_the_host_route(path, spec, eng_frozen, uni5):
    queries = _queries(uni5.cols, np.random.default_rng(3), 6)
    ops.reset_counters()
    eng_frozen.query_batch(queries, method=path, spec=spec)
    assert ops.counters() == {"prune_hierarchy_batch": 1,
                              "multi_visit_reduce": 1, "host_sync": 2}
    assert set(_pairs()) == {(path, "host")}


@pytest.mark.parametrize("path", PATHS)
def test_visit_pair_counter_equals_prune_survivors(path, eng_frozen, uni5):
    """Both routes count exactly the pairs ``np.nonzero(leaf_mask)`` gives,
    in ``mdrq_visit_pairs_total`` and in ``last_visited_blocks``."""
    queries = _queries(uni5.cols, np.random.default_rng(31), 13)
    index = getattr(eng_frozen, path)
    n_pairs = _survivors(index, queries)
    assert n_pairs > 0
    eng_frozen.query_batch(queries, method=path, spec=Count())
    assert index.last_visited_blocks == n_pairs
    eng_frozen.query_batch(queries, method=path, spec=Ids())
    assert index.last_visited_blocks == n_pairs
    assert _pairs() == {(path, "device"): n_pairs, (path, "host"): n_pairs}


def test_nth_survivor_matches_flatnonzero():
    """The compaction alone: every slot below the count names the survivor
    ``np.flatnonzero`` puts there, over masks sparse, dense, empty and wider
    than one group of ``LANES * LANES`` bits."""
    rng = np.random.default_rng(4)
    for shape, p in [((1, 7), 0.5), ((4, 40), 0.0), ((8, 977), 0.03),
                     ((32, 1100), 0.6), ((2, 9000), 1.0)]:
        mask = rng.random(shape) < p
        slots = jnp.arange(mask.size + 40, dtype=jnp.int32)
        prefix = ms.survivor_prefix(jnp.asarray(mask))
        flat = np.asarray(ms.nth_survivor(prefix, slots))
        want = np.flatnonzero(mask)
        assert int(prefix[2][-1]) == want.size, shape
        np.testing.assert_array_equal(flat[: want.size], want)
