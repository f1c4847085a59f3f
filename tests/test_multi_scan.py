"""Batched (multi-query) execution: fused kernels vs the numpy oracle, and
``query_batch`` vs the oracle and vs ``engine.query`` (a batch of one) for
every method.

Kernels run in interpret mode on CPU (the oracle-checked reference path), so
sizes stay small; the XLA refs are checked for exact equality with the
kernels in the same sweep. Masks are discrete — equality is exact."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (Count, Dataset, MDRQEngine, Mask, QueryBatch,
                        RangeQuery, match_ids_np, match_mask_np)
from repro.core.planner import CostModel, Planner, Histograms
from repro.core.vafile import build_vafile
from repro import obs
from repro.kernels import multi_scan as ms
from repro.kernels import ops, ref
from repro.kernels.va_filter import pack_codes


def _mixed_queries(m, cols, rng, n_q):
    """Alternating complete- and partial-match queries around real records."""
    out = []
    for k in range(n_q):
        if k % 2 == 0:
            a = cols[:, rng.integers(cols.shape[1])]
            b = cols[:, rng.integers(cols.shape[1])]
            out.append(RangeQuery.complete(np.minimum(a, b), np.maximum(a, b)))
        else:
            dims = rng.choice(m, size=int(rng.integers(1, m + 1)), replace=False)
            preds = {int(d): tuple(sorted(rng.random(2).tolist())) for d in dims}
            out.append(RangeQuery.partial(m, preds))
    return out


# -- (a) kernel variants vs the numpy oracle ---------------------------------
# Each chunk of 32 query rows compares only the dimension rows some query of
# it bounds, so the cases vary which rows a chunk bounds: random, disjoint
# between chunks, only a row of the last sublane group, a chunk of match-all
# and padding queries, GMRQB's templates 1-7; Q from 1 to 128 and m not a
# multiple of 8. The object padding (+inf) never matches, so a match-all or
# padding query row counts n, not n_pad.

def _case_queries(kind, m, cols, rng, n_q):
    if kind == "mixed":
        return _mixed_queries(m, cols, rng, n_q)
    if kind == "disjoint":  # chunk 0 bounds dims 0-4 only, chunk 1 dims 13+
        out = []
        for k in range(n_q):
            pool = np.arange(5) if k < 32 else np.arange(13, m)
            dims = rng.choice(pool, size=int(rng.integers(1, 4)),
                              replace=False)
            out.append(RangeQuery.partial(m, {
                int(d): tuple(sorted(rng.random(2).tolist())) for d in dims}))
        return out
    if kind == "tail":  # 3 dims each among the first 13: past the head of 8
        return [RangeQuery.partial(m, {
            int(d): tuple(sorted(rng.random(2).tolist()))
            for d in rng.choice(13, size=3, replace=False)})
            for _ in range(n_q)]
    if kind == "last_group":  # a row of the last sublane group, nothing else
        return [RangeQuery.partial(m, {m - 1: tuple(sorted(
            rng.random(2).tolist()))}) for _ in range(n_q)]
    if kind == "match_all":  # a first chunk of 32, then match-all queries
        return (_mixed_queries(m, cols, rng, 32)
                + [RangeQuery.partial(m, {})] * (n_q - 32))
    if kind == "wide":  # chunk 0 bounds 5 of m dims each, chunk 1 2 of 30
        return [RangeQuery.partial(m, {
            int(d): tuple(sorted(rng.random(2).tolist()))
            for d in rng.choice(m if k < 32 else 30, size=5 if k < 32 else 2,
                                replace=False)})
            for k in range(n_q)]
    if kind == "gmrqb":  # templates 1-7 in turn
        from repro.data import gmrqb
        return [gmrqb.template(1 + k % 7, rng) for k in range(n_q)]
    raise AssertionError(kind)


def _case_data(kind, m, n):
    if kind == "gmrqb":
        from repro.data import gmrqb
        return gmrqb.build(n, seed=3).cols
    return np.random.default_rng(m * 7 + n).random((m, n)).astype(np.float32)


def _case_batch(kind, m, n_q, q_pad, n):
    """(cols, batch, padded data, lo, up) of one oracle case."""
    rng = np.random.default_rng(m * 10 + n_q)
    cols = _case_data(kind, m, n)
    batch = QueryBatch.from_queries(_case_queries(kind, m, cols, rng, n_q))
    padded, _, _ = ops.prepare_columnar(cols)
    lo, up = batch.bounds_columnar(padded.shape[0], q_pad)
    return cols, batch, jnp.asarray(padded), jnp.asarray(lo), jnp.asarray(up)


def _check_vs_oracle(out, cols, batch):
    n0 = cols.shape[1]
    for k in range(len(batch)):
        np.testing.assert_array_equal(out[k, :n0].astype(bool),
                                      match_mask_np(cols, batch[k]))
    assert not out[:, n0:].any()           # object padding never matches
    assert (out[len(batch):, :n0] != 0).all()  # padding queries match all


# (m, Q, kind, q_pad, n) of the row-skipping cases; each test's first cases
# keep their historical ids
_ROW_CASES = [
    pytest.param(19, 64, "disjoint", 64, 2000, id="disjoint-chunks-q64"),
    pytest.param(19, 8, "last_group", 8, 2000, id="last-group-only-q8"),
    pytest.param(24, 32, "tail", 32, 2000, id="loop-tail-m24-q32"),
    pytest.param(11, 32, "mixed", 32, 2000, id="m11-q32"),
    pytest.param(19, 40, "match_all", 64, 2000, id="match-all-chunk-q64"),
    pytest.param(19, 128, "gmrqb", 128, 2000, id="gmrqb-t1-7-q128"),
    pytest.param(100, 64, "wide", 64, 2000, id="half-rule-m100-q64"),
]


@pytest.mark.parametrize("m,n_q,kind,q_pad,n", [
    pytest.param(3, 1, "mixed", None, 4096, id="3-1"),
    pytest.param(5, 4, "mixed", None, 4096, id="5-4"),
    pytest.param(19, 6, "mixed", None, 4096, id="19-6"),
    *_ROW_CASES])
def test_multi_scan_tiles_vs_oracle(m, n_q, kind, q_pad, n):
    cols, batch, data, lo, up = _case_batch(kind, m, n_q, q_pad, n)
    out = np.asarray(ops.multi_scan_reduce(data, lo, up, spec=Mask()))
    np.testing.assert_array_equal(out, np.asarray(ref.multi_scan_ref(data, lo, up)))
    _check_vs_oracle(out, cols, batch)


@pytest.mark.parametrize("m,n_q,kind,q_pad,n", [
    pytest.param(5, 3, "mixed", None, 4096, id="5-3"),
    pytest.param(19, 5, "mixed", None, 4096, id="19-5"),
    *_ROW_CASES])
def test_multi_scan_vertical_vs_oracle(m, n_q, kind, q_pad, n):
    cols, batch, data, lo, up = _case_batch(kind, m, n_q, q_pad, n)
    out = np.asarray(ops.multi_scan_vertical_reduce(data, lo, up, spec=Mask()))
    np.testing.assert_array_equal(out, np.asarray(ref.multi_scan_ref(data, lo, up)))
    _check_vs_oracle(out, cols, batch)


@pytest.mark.parametrize("kind,m,n_q,q_pad", [
    ("mixed", 19, 6, 8), ("disjoint", 19, 64, 64), ("last_group", 5, 3, 4),
    ("tail", 24, 32, 32),
    ("match_all", 19, 40, 64), ("gmrqb", 19, 128, 128),
    ("wide", 100, 64, 64)])
def test_row_flags_host_count_matches_kernel_flags(kind, m, n_q, q_pad):
    """The host's chunk flags (from ``dims_mask``, for the row counters) are
    the flags the kernels derive in the jit from the bounds."""
    cols, batch, data, lo, up = _case_batch(kind, m, n_q, q_pad, 1024)
    m_pad = data.shape[0]
    kernel = np.asarray(ms.chunk_flags(ms._bound_rows(lo.T, up.T)))
    bound = np.zeros((q_pad, m_pad), bool)
    bound[:n_q, :m] = batch.dims_mask
    np.testing.assert_array_equal(ms.chunk_flags(bound, xp=np), kernel)
    assert kernel.shape == (-(-q_pad // 32), m_pad)
    assert kernel.any(axis=1).all()    # every chunk compares some row


def _row_counts():
    reg = obs.registry()
    return {outcome: reg.counter_values(f"mdrq_scan_rows_{outcome}_total",
                                        "kernel")
            for outcome in ("compared", "skipped")}


def test_scan_row_counters_gmrqb_templates():
    """One GMRQB batch of 128 through the vertical scan: chunk 0 is template
    1 (dims 0, 1), chunk 1 templates 2-3 (0, 1, 2, 3, 6), chunk 2 templates
    4-5 (0, 1, 2, 3, 6, 13), chunk 3 templates 6-7 (0, 1, 2, 3, 6, 13, 15,
    17, 18): 2 + 5 + 6 + 9 = 22 of the 4 x 24 (chunk, row) pairs compared."""
    from repro.core.scan import build_columnar_scan
    from repro.data import gmrqb
    rng = np.random.default_rng(0)
    ds = gmrqb.build(2000, seed=1)
    order = [1] * 32 + [2, 3] * 16 + [4, 5] * 16 + [6, 7] * 16
    batch = QueryBatch.from_queries([gmrqb.template(k, rng) for k in order])
    scan = build_columnar_scan(ds)
    counts = scan.query_batch(batch, partial=True, spec=Count())
    assert counts == [int(match_mask_np(ds.cols, q).sum())
                      for q in batch.queries]
    assert _row_counts() == {"compared": {"vertical": 22.0},
                             "skipped": {"vertical": 96.0 - 22.0}}


def test_scan_row_counters_complete_match_compares_every_row():
    """A complete-match batch over 19 dims (m_pad 24) would skip only the 5
    padding rows, fewer than a sublane group: each of its two chunks
    compares all 24 rows in straight-line code instead."""
    from repro.core.scan import build_columnar_scan
    rng = np.random.default_rng(1)
    ds = Dataset(rng.random((19, 2000), dtype=np.float32))
    batch = QueryBatch.from_queries(_mixed_queries(19, ds.cols, rng, 80)[::2])
    assert batch.dims_mask.all()
    scan = build_columnar_scan(ds)
    counts = scan.query_batch(batch, spec=Count())
    assert counts == [int(match_mask_np(ds.cols, q).sum())
                      for q in batch.queries]
    assert _row_counts() == {"compared": {"full": 2 * 24.0}, "skipped": {}}


def test_single_query_moves_the_scan_row_counters():
    """A single ``engine.query`` on "scan" is a batch of one through the
    counted scan launch: one chunk of 24 rows, of which a 2-dim GMRQB
    template 1 query compares its 2 and skips the other 22."""
    from repro.data import gmrqb
    ds = gmrqb.build(2000, seed=1)
    q = gmrqb.template(1, np.random.default_rng(0))
    eng = MDRQEngine(ds, structures=("scan",))
    assert eng.query(q, "scan", spec=Count()) == int(
        match_mask_np(ds.cols, q).sum())
    assert _row_counts() == {"compared": {"full": 2.0},
                             "skipped": {"full": 22.0}}


def test_scan_row_counters_half_rule():
    """At m=100 (m_pad 104) a chunk whose loop would run over more than half
    of the 96 rows past its head compares every row: chunk 0 bounds about
    80 rows (5 random dims a query) and compares all 104; chunk 1 bounds at
    most 30 and compares just those."""
    from repro.core.scan import build_columnar_scan
    cols, batch, _, _, _ = _case_batch("wide", 100, 64, 64, 2000)
    union = batch.dims_mask.reshape(2, 32, 100).any(axis=1).sum(axis=1)
    assert union[0] > 8 + 96 // 2 and 2 <= union[1] <= 30
    scan = build_columnar_scan(Dataset(cols))
    counts = scan.query_batch(batch, spec=Count())
    assert counts == [int(match_mask_np(cols, q).sum())
                      for q in batch.queries]
    assert _row_counts() == {"compared": {"full": 104.0 + union[1]},
                             "skipped": {"full": 104.0 - union[1]}}


def test_multi_scan_visit_vs_oracle():
    rng = np.random.default_rng(7)
    m, tile_n = 5, 1024
    cols = rng.random((m, 8192)).astype(np.float32)
    batch = QueryBatch.from_queries(_mixed_queries(m, cols, rng, 3))
    padded, _, n0 = ops.prepare_columnar(cols, tile_n=tile_n)
    data = jnp.asarray(padded)
    n_blocks = padded.shape[1] // tile_n
    # every (query, block) pair, shuffled, plus padding entries
    qids = np.repeat(np.arange(3), n_blocks)
    bids = np.tile(np.arange(n_blocks), 3)
    order = rng.permutation(qids.size)
    qids = np.concatenate([qids[order], [0, 0]]).astype(np.int32)
    bids = np.concatenate([bids[order], [-1, -1]]).astype(np.int32)
    lo, up = batch.bounds_columnar(padded.shape[0])
    lo, up = jnp.asarray(lo), jnp.asarray(up)
    out = np.asarray(ops.multi_visit_reduce(
        data, jnp.asarray(qids), jnp.asarray(bids),
        jnp.asarray((bids >= 0).astype(np.int32)), jnp.zeros((1, 1), jnp.int32),
        lo, up, spec=Mask(), tile_n=tile_n, n_queries=4))
    blocks = data.reshape(data.shape[0], n_blocks, tile_n).transpose(1, 0, 2)
    np.testing.assert_array_equal(out, np.asarray(ref.multi_scan_blocks_ref(
        blocks, jnp.asarray(qids), jnp.asarray(bids), lo, up)))
    for v in range(qids.size - 2):
        k, b = int(qids[v]), int(bids[v])
        full = np.zeros((padded.shape[1],), bool)
        full[:n0] = match_mask_np(cols, batch[k])
        np.testing.assert_array_equal(out[v].astype(bool),
                                      full[b * tile_n:(b + 1) * tile_n])


def test_multi_scan_visit_chunked_vs_oracle(monkeypatch):
    """A visit list longer than one kernel's SMEM share runs as a loop over
    chunks inside the same launch; rows past the list are cut off."""
    from repro.kernels import multi_scan as ms
    monkeypatch.setattr(ms, "VISIT_CHUNK", 64)
    rng = np.random.default_rng(8)
    m, tile_n = 5, 1024
    cols = rng.random((m, 8192)).astype(np.float32)
    batch = QueryBatch.from_queries(_mixed_queries(m, cols, rng, 3))
    padded, _, _ = ops.prepare_columnar(cols, tile_n=tile_n)
    data = jnp.asarray(padded)
    n_blocks = padded.shape[1] // tile_n
    qids = np.tile(np.repeat(np.arange(3), n_blocks), 4)
    bids = np.tile(np.arange(n_blocks), 12)
    qids = np.concatenate([qids, [0, 0]]).astype(np.int32)  # 98 visits
    bids = np.concatenate([bids, [-1, -1]]).astype(np.int32)
    lo, up = batch.bounds_columnar(padded.shape[0])
    lo, up = jnp.asarray(lo), jnp.asarray(up)
    out = np.asarray(ms.multi_scan_visit(
        data, jnp.asarray(qids), jnp.asarray(bids), lo, up, tile_n=tile_n,
        interpret=True))
    blocks = data.reshape(data.shape[0], n_blocks, tile_n).transpose(1, 0, 2)
    np.testing.assert_array_equal(out, np.asarray(ref.multi_scan_blocks_ref(
        blocks, jnp.asarray(qids), jnp.asarray(bids), lo, up)))


@pytest.mark.parametrize("m,n_q", [(5, 3), (19, 6), (33, 4)])
def test_multi_va_filter_vs_single_and_oracle(m, n_q):
    """Batched phase 1: one-launch masks == ref == the numpy oracle over the
    unpacked codes, including point (cell_lo == cell_hi) and match-all
    queries."""
    rng = np.random.default_rng(m * 7 + n_q)
    n, tile_n = 4096, 1024
    codes = rng.integers(0, 4, size=(m, n)).astype(np.uint8)
    packed = jnp.asarray(pack_codes(codes))
    m_s = -(-m // 8) * 8
    qlo = np.zeros((m_s, n_q), np.int32)
    qhi = np.full((m_s, n_q), 3, np.int32)
    qlo[:m] = rng.integers(0, 4, size=(m, n_q))
    qhi[:m] = np.minimum(3, qlo[:m] + rng.integers(0, 3, size=(m, n_q)))
    qlo[:m, 0] = qhi[:m, 0]          # point query in cell space
    qlo[:m, -1], qhi[:m, -1] = 0, 3  # match-all
    out = np.asarray(ops.multi_va_filter(packed, jnp.asarray(qlo),
                                         jnp.asarray(qhi), m, tile_n=tile_n))
    np.testing.assert_array_equal(out, np.asarray(ref.multi_va_filter_packed_ref(
        packed, jnp.asarray(qlo), jnp.asarray(qhi), m)))
    for k in range(n_q):
        oracle = ((codes >= qlo[:m, k, None]) & (codes <= qhi[:m, k, None])
                  ).all(axis=0)
        np.testing.assert_array_equal(out[k].astype(bool), oracle)
    # on-device block reduction == host-side reduction of the full masks
    blocks = np.asarray(ops.multi_va_filter(packed, jnp.asarray(qlo),
                                            jnp.asarray(qhi), m,
                                            tile_n=tile_n, block_n=tile_n))
    np.testing.assert_array_equal(
        blocks, out.reshape(n_q, -1, tile_n).any(axis=2))


def _queries_with_points(cols, rng, n_q):
    """Mixed queries plus point predicates (lb == ub at real records)."""
    m = cols.shape[0]
    out = _mixed_queries(m, cols, rng, n_q)
    rec = cols[:, rng.integers(cols.shape[1])]
    out.append(RangeQuery.complete(rec, rec))                # full point query
    out.append(RangeQuery.partial(m, {1: (float(rec[1]), float(rec[1]))}))
    return out


def test_vafile_batch_one_launch_one_sync(uni5):
    """Tentpole budget: the batched VA path issues exactly one phase-1 launch
    and one phase-1 host sync per batch (plus one fused visit-reduce launch +
    payload readback) and nothing else — results equal the oracle."""
    vf = build_vafile(uni5, tile_n=512)
    rng = np.random.default_rng(17)
    queries = _queries_with_points(uni5.cols, rng, 6)
    singles = [match_ids_np(uni5.cols, q) for q in queries]
    batch = QueryBatch.from_queries(queries)

    ops.reset_counters()
    batched = vf.query_batch(batch)
    assert ops.counters() == {"multi_va_filter": 1,     # one phase-1 launch
                              "multi_visit_reduce": 1,
                              "host_sync": 2}  # survivor bits + visit masks
    for s, b in zip(singles, batched):
        np.testing.assert_array_equal(s, b)

    ops.reset_counters()
    counts = vf.query_batch(batch, spec=Count())
    assert ops.counter("multi_va_filter") == 1
    assert ops.counter("host_sync") == 2
    assert counts == [s.size for s in singles]
    assert all(isinstance(c, int) for c in counts)


def test_vafile_batch_gmrqb_templates():
    """GMRQB-style batches (templates with point predicates) through the
    batched VA path: ids and counts match the oracle, in the batch and one
    query at a time."""
    from repro.data import gmrqb

    ds = gmrqb.build(8192, seed=3)
    vf = build_vafile(ds, tile_n=1024)
    rng = np.random.default_rng(9)
    queries = [gmrqb.template(k, rng, ds) for k in (1, 4, 5, 7, 8)]
    batch = QueryBatch.from_queries(queries)
    batched = vf.query_batch(batch)
    counts = vf.query_batch(batch, spec=Count())
    for k, q in enumerate(queries):
        oracle = match_ids_np(ds.cols, q)
        one = QueryBatch.from_queries([q])
        np.testing.assert_array_equal(batched[k], oracle)
        np.testing.assert_array_equal(vf.query_batch(one)[0], oracle)
        assert counts[k] == oracle.size
        assert vf.query_batch(one, spec=Count()) == [oracle.size]


# -- (b) a query's answer does not depend on its batch, for all methods ------

@pytest.mark.parametrize("method", ["scan", "scan_vertical", "kdtree",
                                    "rstar", "vafile", "auto"])
def test_query_batch_equals_single(method, uni5):
    """The oracle's answer, whether a query rides a batch, the same batch
    reversed, or ``engine.query`` alone (a batch of one)."""
    eng = MDRQEngine(uni5, tile_n=512)
    rng = np.random.default_rng(11)
    queries = _mixed_queries(uni5.m, uni5.cols, rng, 6)
    batched = eng.query_batch(queries, method=method)
    assert eng.last_batch_stats.n_queries == 6
    assert sum(eng.last_batch_stats.method_counts.values()) == 6
    reversed_ = eng.query_batch(queries[::-1], method=method)[::-1]
    for k, q in enumerate(queries):
        oracle = match_ids_np(uni5.cols, q)
        np.testing.assert_array_equal(batched[k], oracle)
        np.testing.assert_array_equal(reversed_[k], oracle)
        np.testing.assert_array_equal(eng.query(q, method), oracle)


# -- count-only result mode --------------------------------------------------

@pytest.fixture(scope="module")
def eng_all(uni5, per_query_path):
    eng = MDRQEngine(uni5, tile_n=512)
    eng.register_path(per_query_path("per_query", uni5.cols, cols=uni5.cols))
    return eng


@pytest.mark.parametrize("method", ["scan", "scan_vertical", "per_query",
                                    "kdtree", "rstar", "vafile", "auto"])
def test_count_mode_equals_ids_sizes(method, eng_all, uni5):
    rng = np.random.default_rng(29)
    queries = _queries_with_points(uni5.cols, rng, 5)
    counts = eng_all.query_batch(queries, method=method, mode="count")
    assert all(isinstance(c, int) for c in counts)
    assert eng_all.last_batch_stats.n_results == sum(counts)
    for k, q in enumerate(queries):
        expected = match_ids_np(uni5.cols, q).size
        assert counts[k] == expected, (method, k)
        assert eng_all.query(q, method, mode="count") == expected
        assert eng_all.last_stats.n_results == expected


def test_count_mode_scan_single_launch_no_mask_readback(eng_all, uni5):
    """Count mode sums masks on device: one fused launch, one O(Q) transfer,
    and no (Q, n) mask ever crosses to the host."""
    rng = np.random.default_rng(31)
    queries = _mixed_queries(uni5.m, uni5.cols, rng, 8)
    ops.reset_counters()
    eng_all.query_batch(queries, method="scan", spec=Count())
    assert ops.counter("multi_scan_reduce") == 1
    assert ops.counter("host_sync") == 1


def test_count_mode_rejects_unknown(eng_all, uni5):
    q = RangeQuery.partial(uni5.m, {0: (0.1, 0.2)})
    with pytest.raises(ValueError):
        eng_all.query(q, mode="top_k")
    with pytest.raises(ValueError):
        eng_all.query_batch([q], mode="top_k")


def test_query_batch_accepts_querybatch_object(uni5):
    eng = MDRQEngine(uni5, structures=("scan",), tile_n=512)
    rng = np.random.default_rng(3)
    queries = _mixed_queries(uni5.m, uni5.cols, rng, 4)
    res_list = eng.query_batch(queries, method="scan")
    res_qb = eng.query_batch(QueryBatch.from_queries(queries), method="scan")
    for a, b in zip(res_list, res_qb):
        np.testing.assert_array_equal(a, b)


# -- (c) edge cases ----------------------------------------------------------

def test_query_batch_empty_and_single(uni5):
    eng = MDRQEngine(uni5, structures=("scan",), tile_n=512)
    assert eng.query_batch([]) == []
    assert eng.last_batch_stats.n_queries == 0
    q = RangeQuery.partial(uni5.m, {0: (0.2, 0.4)})
    res = eng.query_batch([q], method="scan")
    assert len(res) == 1
    np.testing.assert_array_equal(res[0], match_ids_np(uni5.cols, q))


def test_query_batch_match_all_and_match_none(uni5):
    eng = MDRQEngine(uni5, structures=("scan",), tile_n=512)
    q_all = RangeQuery.partial(uni5.m, {})
    q_none = RangeQuery.partial(uni5.m, {0: (2.0, 3.0)})
    res = eng.query_batch([q_all, q_none, q_all], method="scan_vertical")
    assert res[0].size == uni5.n and res[2].size == uni5.n
    assert res[1].size == 0


def test_query_batch_dim_mismatch(uni5):
    eng = MDRQEngine(uni5, structures=("scan",), tile_n=512)
    with pytest.raises(ValueError):
        eng.query_batch([RangeQuery.partial(3, {0: (0.0, 1.0)})])


def test_querybatch_rejects_mixed_dims():
    with pytest.raises(ValueError):
        QueryBatch.from_queries([RangeQuery.partial(3, {}),
                                 RangeQuery.partial(4, {})])


# -- batched planner costs ---------------------------------------------------

def test_batch_amortizes_fixed_taxes(uni5):
    hist = Histograms.build(uni5)
    model = CostModel(n=1_000_000, m=5)
    q = RangeQuery.complete([0.0] * 5, [0.1] * 5)
    sel = hist.selectivity(q)
    assert model.cost_tree(q, sel, batch=128) < model.cost_tree(q, sel)
    assert model.cost_scan(q, batch=128) < model.cost_scan(q)
    # batch=1 must equal the legacy single-query cost structure
    p = Planner(hist, model)
    assert p.explain(q).costs == p.explain(q, batch_size=1).costs


def test_break_even_shifts_with_batch(uni5):
    """The batched break-even differs from single-query — the subsystem's
    paper-relevant planning result (net of sync amortization helping indexes
    and fused-byte amortization helping scans)."""
    hist = Histograms.build(uni5)
    p = Planner(hist, CostModel(n=10_000_000, m=5))
    be1 = p.break_even_selectivity()
    be128 = p.break_even_selectivity(batch_size=128)
    assert be1 > 0
    assert abs(be128 - be1) / be1 > 0.25, (be1, be128)


# -- the serving front end ---------------------------------------------------

def test_mdrq_server_batches_and_agrees(uni5):
    from repro.serve.mdrq_server import MDRQServer

    eng = MDRQEngine(uni5, structures=("scan",), tile_n=512)
    rng = np.random.default_rng(21)
    queries = _mixed_queries(uni5.m, uni5.cols, rng, 10)
    server = MDRQServer(eng, max_batch=4, max_wait_s=float("inf"), method="scan")
    results = server.serve_all(queries)
    for q, ids in zip(queries, results):
        np.testing.assert_array_equal(ids, match_ids_np(uni5.cols, q))
    # 10 queries at window 4 -> batches of 4, 4, 2
    assert server.stats.n_batches == 3
    assert server.stats.n_queries == 10
    assert server.stats.qps > 0


def test_mdrq_server_ticket_forces_flush(uni5):
    from repro.serve.mdrq_server import MDRQServer

    eng = MDRQEngine(uni5, structures=("scan",), tile_n=512)
    server = MDRQServer(eng, max_batch=64, max_wait_s=float("inf"))
    q = RangeQuery.partial(uni5.m, {1: (0.1, 0.3)})
    ticket = server.submit(q)
    assert server.n_pending == 1  # window not full, nothing executed yet
    np.testing.assert_array_equal(ticket.result(), match_ids_np(uni5.cols, q))
    assert server.n_pending == 0
