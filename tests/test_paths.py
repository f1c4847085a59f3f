"""Access-path registry layer + vectorized fixpoint batch planner.

Covers the three acceptance axes of the refactor: (a) the engine routes
everything through the ``AccessPath`` registry and a test-registered toy path
is planned and executed with no engine changes; (b) batched "auto" execution
is element-identical to per-query "auto" in both result modes (random and
GMRQB workloads) while the launch/host-sync budgets hold; (c) ``plan_batch``
is vectorized (>= 10x over Q scalar ``explain`` calls, asserted coarsely) and
its fixpoint amortizes by *realized* bucket sizes, not the whole batch."""
import time

import numpy as np
import pytest

from repro.core import (Dataset, MDRQEngine, PerQueryPath, QueryBatch,
                        RangeQuery, match_ids_np)
from repro.core.planner import BatchPlan, CostModel, Histograms, Planner
from repro.kernels import ops


def _mixed_queries(cols, rng, n_q):
    """Alternating complete- and partial-match queries around real records."""
    m = cols.shape[0]
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


# -- (a) the registry ---------------------------------------------------------

class _ToyNumpyPath:
    """A complete third-party access path: numpy oracle + near-zero cost."""

    name = "toy_numpy"
    plannable = True
    owns_storage = True
    nbytes_index = 123

    def __init__(self, dataset):
        self._cols = dataset.cols
        self.batch_calls = 0

    def query(self, q):
        return match_ids_np(self._cols, q)

    def count(self, q):
        return int(match_ids_np(self._cols, q).size)

    def query_batch(self, batch, mode="ids"):
        self.batch_calls += 1
        if mode == "count":
            return [self.count(batch[k]) for k in range(len(batch))]
        return [self.query(batch[k]) for k in range(len(batch))]

    def cost(self, q, sel, batch, model):
        return 1e-12  # always wins "auto"

    def cost_batch(self, pi, bucket, model):
        return np.full((len(pi),), 1e-12)


def test_toy_path_planned_and_executed_without_engine_changes(uni5):
    """Register a path the engine has never heard of: the planner prices it,
    "auto" routes to it (single and batch), explicit dispatch finds it, and
    the memory report carries it — zero engine edits."""
    eng = MDRQEngine(uni5, structures=("scan",), tile_n=512)
    toy = _ToyNumpyPath(uni5)
    eng.register_path(toy)
    assert "toy_numpy" in eng.paths
    assert "toy_numpy" in eng.planner.available
    assert eng.memory_report()["toy_numpy"] == 123

    rng = np.random.default_rng(5)
    queries = _mixed_queries(uni5.cols, rng, 6)
    # single-query auto: the planner must pick the near-free toy path, and
    # the query rides it as a batch of one
    res = eng.query(queries[0], method="auto")
    assert eng.last_stats.method == "toy_numpy"
    assert toy.batch_calls == 1
    np.testing.assert_array_equal(res, match_ids_np(uni5.cols, queries[0]))
    # batched auto: one bucket, one toy batch call, oracle-equal results
    batched = eng.query_batch(queries, method="auto")
    assert eng.last_batch_stats.method_counts == {"toy_numpy": 6}
    assert toy.batch_calls == 2
    for q, ids in zip(queries, batched):
        np.testing.assert_array_equal(ids, match_ids_np(uni5.cols, q))
    # explicit dispatch + count mode through the same registry entry
    assert eng.query(queries[1], method="toy_numpy", mode="count") == \
        match_ids_np(uni5.cols, queries[1]).size
    counts = eng.query_batch(queries, method="toy_numpy", mode="count")
    assert counts == [match_ids_np(uni5.cols, q).size for q in queries]


def test_register_path_rejects_incomplete_objects(uni5):
    eng = MDRQEngine(uni5, structures=("scan",), tile_n=512)

    class _NotAPath:
        name = "broken"

    with pytest.raises(TypeError):
        eng.register_path(_NotAPath())


def test_engine_has_no_dispatch_chains():
    """The refactor's structural guarantee: routing is the registry, not
    per-method if/elif chains in the engine."""
    import inspect
    from repro.core import engine as engine_mod

    src = inspect.getsource(engine_mod)
    for needle in ("_dispatch_batch", "_dispatch_count",
                   'method == "scan"', 'method == "kdtree"',
                   'method == "vafile"', 'method == "scan_vertical"'):
        assert needle not in src, needle


def test_rowscan_rides_the_per_query_fallback(uni5, per_query_path):
    """A registered structure with only per-query functions rides the
    generic ``PerQueryPath`` adapter — batch results equal the oracle, and
    it never enters "auto" planning (plannable=False)."""
    eng = MDRQEngine(uni5, structures=("scan",), tile_n=512)
    eng.register_path(per_query_path("per_query", uni5.cols))
    assert isinstance(eng.paths["per_query"], PerQueryPath)
    assert "per_query" not in eng.planner.available
    rng = np.random.default_rng(7)
    queries = _mixed_queries(uni5.cols, rng, 4)
    for q, ids in zip(queries, eng.query_batch(queries, method="per_query")):
        np.testing.assert_array_equal(ids, match_ids_np(uni5.cols, q))
    counts = eng.query_batch(queries, method="per_query", mode="count")
    assert counts == [match_ids_np(uni5.cols, q).size for q in queries]


@pytest.mark.parametrize("method", ["scan", "scan_vertical", "kdtree",
                                    "rstar", "vafile", "scan-meshed"])
def test_single_query_launches_what_a_batch_of_one_does(uni5, method):
    """``engine.query`` is a batch of one: it issues exactly the launches and
    host syncs of ``query_batch([q])`` on the same path, and answers the
    same, under Ids and Count."""
    from repro.core import Count
    from repro.core.distributed import make_data_mesh
    if method == "scan-meshed":
        eng = MDRQEngine(uni5, structures=("scan",), tile_n=512,
                         mesh=make_data_mesh())
        method = "scan"
    else:
        eng = MDRQEngine(uni5, tile_n=512)
    rng = np.random.default_rng(19)
    for q in _mixed_queries(uni5.cols, rng, 2):
        for spec in (None, Count()):
            ops.reset_counters()
            batched = eng.query_batch([q], method=method, spec=spec)[0]
            want = ops.counters()
            ops.reset_counters()
            single = eng.query(q, method=method, spec=spec)
            assert ops.counters() == want
            assert want["host_sync"] >= 1 and len(want) >= 2
            np.testing.assert_array_equal(single, batched)


def test_unknown_method_and_unbuilt_structure_raise(uni5):
    eng = MDRQEngine(uni5, structures=("scan",), tile_n=512)
    q = RangeQuery.partial(uni5.m, {0: (0.1, 0.2)})
    with pytest.raises(ValueError, match="unknown method"):
        eng.query(q, method="kdtree")  # built structures only
    with pytest.raises(ValueError, match="unknown method"):
        eng.query_batch([q], method="nope")
    with pytest.raises(ValueError, match="unknown mode"):
        eng.query_batch([q], mode="top_k")


# -- (b) batched auto == per-query auto ---------------------------------------

@pytest.mark.parametrize("mode", ["ids", "count"])
def test_batched_auto_equals_per_query_auto_random(uni5, mode):
    eng = MDRQEngine(uni5, tile_n=512)
    rng = np.random.default_rng(13)
    queries = _mixed_queries(uni5.cols, rng, 8)
    rec = uni5.cols[:, 7]
    queries.append(RangeQuery.complete(rec, rec))     # point query
    queries.append(RangeQuery.partial(uni5.m, {}))    # match-all
    batched = eng.query_batch(queries, method="auto", mode=mode)
    for q, res in zip(queries, batched):
        single = eng.query(q, method="auto", mode=mode)
        if mode == "count":
            assert res == single == match_ids_np(uni5.cols, q).size
        else:
            np.testing.assert_array_equal(res, single)
            np.testing.assert_array_equal(res, match_ids_np(uni5.cols, q))


@pytest.mark.parametrize("mode", ["ids", "count"])
def test_batched_auto_equals_per_query_auto_gmrqb(mode):
    from repro.data import gmrqb

    ds = gmrqb.build(8192, seed=5)
    eng = MDRQEngine(ds, tile_n=1024)
    rng = np.random.default_rng(11)
    queries = [gmrqb.template(k, rng, ds) for k in (1, 2, 4, 5, 7, 8)]
    batched = eng.query_batch(queries, method="auto", mode=mode)
    for q, res in zip(queries, batched):
        single = eng.query(q, method="auto", mode=mode)
        if mode == "count":
            assert res == single == match_ids_np(ds.cols, q).size
        else:
            np.testing.assert_array_equal(res, single)
            np.testing.assert_array_equal(res, match_ids_np(ds.cols, q))


def test_auto_batch_launch_budget_one_per_bucket(uni5):
    """The registry didn't change the launch structure: an auto-planned batch
    that buckets to the fused scan still costs one launch + one host sync."""
    eng = MDRQEngine(uni5, structures=("scan",), tile_n=512)
    rng = np.random.default_rng(3)
    queries = _mixed_queries(uni5.cols, rng, 8)
    ops.reset_counters()
    eng.query_batch(queries, method="auto")
    n_buckets = len(eng.last_batch_stats.method_counts)
    launches = (ops.counter("multi_scan_reduce")
                + ops.counter("multi_scan_vertical_reduce"))
    assert launches == n_buckets
    assert ops.counter("host_sync") == n_buckets


# -- (c) vectorized fixpoint planning -----------------------------------------

def test_plan_batch_vectorized_speedup(uni5):
    """Planning a 128-query batch must beat 128 scalar explain calls by >=
    10x (coarse wall-clock bound; bench_throughput reports the precise
    number via BatchStats.plan_seconds)."""
    hist = Histograms.build(uni5)
    p = Planner(hist, CostModel(n=1_000_000, m=5))
    rng = np.random.default_rng(17)
    queries = _mixed_queries(uni5.cols, rng, 128)
    batch = QueryBatch.from_queries(queries)
    p.plan_batch(batch)  # warm any lazy numpy paths

    t_scalar = min(_timed(lambda: [p.explain(q, batch_size=128)
                                   for q in queries]) for _ in range(3))
    t_vec = min(_timed(lambda: p.plan_batch(batch)) for _ in range(3))
    assert t_scalar > 10 * t_vec, (t_scalar, t_vec)


def _timed(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def test_plan_batch_fixpoint_uses_realized_buckets(uni5):
    """A selective query co-batched with 127 scan-bound queries: under the
    old whole-batch amortization the tree wins it (every fixed tax divided by
    128), but its *realized* tree bucket would hold one query — the fixpoint
    re-prices with that bucket and moves it onto the big scan bucket, whose
    amortization is real. The final plan differs from what ``len(batch)``
    amortization (and from what batch_size=1) would choose.

    Planned under ``Count()`` so the result-payload term is negligible and
    the scenario isolates the amortization effect (under ``Ids()`` the
    scan's n-byte mask readback dominates at n=10M and the tree keeps the
    selective query on output-bytes grounds — that spec-dependent flip is
    covered by test_result_specs.py).
    """
    from repro.core import Count

    hist = Histograms.build(uni5)
    p = Planner(hist, CostModel(n=10_000_000, m=5),
                available=("scan", "kdtree"))
    wide = RangeQuery.complete([0.0] * 5, [0.9] * 5)
    selective = RangeQuery.complete([0.0] * 5, [0.1] * 5)
    batch = QueryBatch.from_queries([wide] * 127 + [selective])

    # whole-batch amortization (the seed's explain_batch semantics): tree
    assert p.explain(selective, batch_size=len(batch),
                     spec=Count()).method == "kdtree"
    assert p.explain_batch(batch.queries, spec=Count())[-1].method == "kdtree"
    # realized-bucket fixpoint: the one-query tree bucket can't pay its own
    # host-sync tax, the 128-query scan bucket amortizes for free -> scan
    bp = p.plan_batch(batch, spec=Count())
    assert isinstance(bp, BatchPlan)
    assert bp.methods[-1] == "scan"
    assert bp.bucket_sizes == {"scan": 128}
    assert bp.converged and 2 <= bp.n_iterations <= 4
    assert bp.est_selectivity.shape == (128,)


def test_plan_batch_matches_engine_buckets(uni5):
    """The buckets the fixpoint priced are the buckets the engine executes,
    and the planning share of the wall time is recorded separately."""
    eng = MDRQEngine(uni5, tile_n=512)
    rng = np.random.default_rng(29)
    queries = _mixed_queries(uni5.cols, rng, 16)
    bp = eng.planner.plan_batch(QueryBatch.from_queries(queries))
    eng.query_batch(queries, method="auto")
    stats = eng.last_batch_stats
    assert stats.method_counts == bp.bucket_sizes
    assert sum(bp.bucket_sizes.values()) == 16
    assert 0.0 < stats.plan_seconds <= stats.seconds


def test_explain_batch_matches_scalar_explain(uni5):
    """The vectorized whole-batch pass must reproduce the scalar cost dicts
    (same paths, same numbers) — the two formulations cannot drift."""
    hist = Histograms.build(uni5)
    p = Planner(hist, CostModel(n=1_000_000, m=5))
    rng = np.random.default_rng(23)
    queries = _mixed_queries(uni5.cols, rng, 9)
    queries.append(RangeQuery.partial(uni5.m, {}))  # match-all edge
    for q, pb in zip(queries, p.explain_batch(queries)):
        ps = p.explain(q, batch_size=len(queries))
        assert set(pb.costs) == set(ps.costs)
        for name in pb.costs:
            assert np.isclose(pb.costs[name], ps.costs[name],
                              rtol=1e-9, atol=0.0), name
        assert pb.method == ps.method
        assert np.isclose(pb.est_selectivity, ps.est_selectivity, rtol=0,
                          atol=0)


def test_plan_batch_single_query_and_empty(uni5):
    hist = Histograms.build(uni5)
    p = Planner(hist, CostModel(n=uni5.n, m=5))
    q = RangeQuery.partial(5, {0: (0.1, 0.3)})
    bp = p.plan_batch(QueryBatch.from_queries([q]))
    assert len(bp.methods) == 1 and bp.methods[0] in p.available
    assert p.explain_batch([]) == []
