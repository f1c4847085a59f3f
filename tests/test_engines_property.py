"""Property-based tests (hypothesis): every access path returns exactly the
oracle's result set for arbitrary data distributions and query boxes — the
system's core invariant (paper §2.1: result = ids of all matching objects)."""
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import (Dataset, MDRQEngine, QueryBatch, RangeQuery,
                        build_columnar_scan, build_kdtree, build_rstar,
                        build_vafile, match_ids_np)


@st.composite
def dataset_and_query(draw):
    m = draw(st.integers(1, 12))
    n = draw(st.integers(10, 3000))
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    dist = draw(st.sampled_from(["uniform", "clustered", "skewed", "discrete"]))
    if dist == "uniform":
        cols = rng.random((m, n))
    elif dist == "clustered":
        k = draw(st.integers(1, 5))
        centers = rng.random((k, m))
        a = rng.integers(0, k, n)
        cols = (centers[a] + rng.normal(0, 0.05, (n, m))).T
    elif dist == "skewed":
        cols = rng.beta(0.3, 3.0, (m, n))
    else:
        cols = rng.integers(0, 7, (m, n)).astype(np.float32)
    ds = Dataset(cols.astype(np.float32))
    # query: random box, sometimes partial-match, sometimes degenerate
    partial = draw(st.booleans())
    i, j = rng.integers(n), rng.integers(n)
    lo = np.minimum(ds.cols[:, i], ds.cols[:, j])
    hi = np.maximum(ds.cols[:, i], ds.cols[:, j])
    if partial and m > 1:
        keep = rng.random(m) < 0.5
        lo = np.where(keep, lo, -np.inf).astype(np.float32)
        hi = np.where(keep, hi, np.inf).astype(np.float32)
    q = RangeQuery(lo, hi)
    return ds, q


@settings(max_examples=25, deadline=None)
@given(dataset_and_query())
def test_all_paths_equal_oracle(dq):
    ds, q = dq
    oracle = match_ids_np(ds.cols, q)
    tile = 256  # small tiles so indexes have multiple blocks even at small n
    one = QueryBatch.from_queries([q])
    scan = build_columnar_scan(ds, tile_n=tile)
    np.testing.assert_array_equal(scan.query_batch(one)[0], oracle)
    np.testing.assert_array_equal(scan.query_batch(one, partial=True)[0],
                                  oracle)
    for build in (build_kdtree, build_rstar, build_vafile):
        np.testing.assert_array_equal(
            build(ds, tile_n=tile).query_batch(one)[0], oracle)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(2, 8))
def test_structure_invariants(seed, m):
    """kd-tree/STR perms are permutations; leaf MBRs contain their objects;
    VA codes quantize consistently with the boundaries."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(100, 4000))
    ds = Dataset(rng.random((m, n)).astype(np.float32))
    for build in (build_kdtree, build_rstar):
        idx = build(ds, tile_n=256)
        assert np.array_equal(np.sort(idx.perm), np.arange(n))
        leaf_lo = np.asarray(idx.levels_lo[-1])
        leaf_hi = np.asarray(idx.levels_hi[-1])
        perm_cols = ds.cols[:, idx.perm]
        for b in range(idx.n_leaves):
            blk = perm_cols[:, b * 256 : (b + 1) * 256]
            if blk.size == 0:
                continue
            assert (blk >= leaf_lo[:, b : b + 1] - 1e-6).all()
            assert (blk <= leaf_hi[:, b : b + 1] + 1e-6).all()
    va = build_vafile(ds, tile_n=256)
    assert va.boundaries.shape == (m, 3)
    assert (np.diff(va.boundaries, axis=1) >= -1e-6).all()


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_engine_auto_matches_oracle(seed):
    rng = np.random.default_rng(seed)
    ds = Dataset(rng.random((6, 5000)).astype(np.float32))
    eng = MDRQEngine(ds, tile_n=512)
    for _ in range(3):
        i, j = rng.integers(5000), rng.integers(5000)
        q = RangeQuery(np.minimum(ds.cols[:, i], ds.cols[:, j]),
                       np.maximum(ds.cols[:, i], ds.cols[:, j]))
        np.testing.assert_array_equal(eng.query(q, "auto"),
                                      match_ids_np(ds.cols, q))


def test_empty_and_full_results(uni5):
    eng = MDRQEngine(uni5, tile_n=1024)
    q_none = RangeQuery.complete([2.0] * 5, [3.0] * 5)
    q_all = RangeQuery.complete([-1.0] * 5, [2.0] * 5)
    for meth in ("scan", "kdtree", "rstar", "vafile"):
        assert eng.query(q_none, meth).size == 0
        assert eng.query(q_all, meth).size == uni5.n
