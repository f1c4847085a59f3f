"""The ResultSpec layer: every spec x every registered access path vs the
numpy oracle, launch/host-sync budgets, the mode-string back-compat shim,
and spec-dependent planning.

Covers the acceptance axes of the redesign: (a) ``Ids``/``Count``/``Mask``/
``TopK``/``Agg`` agree with the oracle on random and GMRQB batches across
*all* registered paths (``Count() == len(Ids())``, top-k ids are a value-
ordered subset of the id set, aggregates match ``np.min/max/sum`` over it);
(b) reduced shapes run as one fused reduce launch + one host sync per batch
(counter-asserted); (c) the legacy ``mode=`` strings map to specs through
``validate_mode`` with one DeprecationWarning and unknown modes keep the one
canonical error; (d) ``Planner.plan_batch`` produces spec-dependent plans —
a query's chosen path differs between ``Ids()`` and ``Count()``/``Agg``.
"""
import warnings

import numpy as np
import pytest

from repro.core import (Agg, Count, Dataset, Ids, Mask, MDRQEngine,
                        QueryBatch, RangeQuery, TopK, match_ids_np,
                        register_result_spec, resolve_spec, validate_mode)
from repro.core.planner import CostModel, Histograms, Planner
from repro.core.types import RESULT_SPEC_KINDS, ResultSpec
from repro.kernels import ops

SPECS = (Ids(), Count(), Mask(), TopK(k=4, dim=2), TopK(k=3, dim=1, largest=False),
         Agg("sum", 3), Agg("min", 0), Agg("max", 4))


def _mixed_queries(cols, rng, n_q):
    """Complete + partial + point + empty-range + match-all queries."""
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
    out.append(RangeQuery.partial(m, {0: (2.0, 3.0)}))  # empty result set
    out.append(RangeQuery.partial(m, {}))               # match-all
    rec = cols[:, 11]
    out.append(RangeQuery.complete(rec, rec))           # point query
    return out


def _check_spec(spec, res, ids, cols):
    """One query's result under ``spec`` vs the oracle id set."""
    if spec.kind == "ids":
        np.testing.assert_array_equal(res, ids)
    elif spec.kind == "count":
        assert isinstance(res, int) and res == ids.size
    elif spec.kind == "mask":
        assert res.dtype == bool and res.shape == (cols.shape[1],)
        np.testing.assert_array_equal(np.nonzero(res)[0], ids)
    elif spec.kind == "topk":
        # subset of the id set, correct length, and value-ordered; compare
        # value sequences (not raw ids) so attribute ties stay well-defined
        assert set(res.tolist()) <= set(ids.tolist())
        assert res.size == min(spec.k, ids.size)
        got = cols[spec.dim, res]
        vals = cols[spec.dim, ids]
        order = np.argsort(-vals if spec.largest else vals, kind="stable")
        np.testing.assert_allclose(got, vals[order[: spec.k]], rtol=1e-6)
        step = np.diff(got)
        assert np.all(step <= 1e-6) if spec.largest else np.all(step >= -1e-6)
    elif spec.kind == "agg":
        if ids.size == 0:
            assert res == 0.0 if spec.op == "sum" else np.isnan(res)
        else:
            vals = cols[spec.dim, ids]
            exp = {"min": np.min, "max": np.max,
                   "sum": lambda v: np.sum(v, dtype=np.float64)}[spec.op](vals)
            assert np.isclose(res, exp, rtol=1e-4), (res, exp)
    else:
        raise AssertionError(spec.kind)


@pytest.fixture(scope="module")
def eng_all(uni5, per_query_path):
    # the per-query path's reduced specs ride the host from_ids rung
    eng = MDRQEngine(uni5, tile_n=512)
    eng.register_path(per_query_path("per_query", uni5.cols, cols=uni5.cols))
    return eng


@pytest.mark.parametrize("spec", SPECS, ids=lambda s: repr(s))
def test_specs_vs_oracle_all_paths_random(spec, eng_all, uni5):
    """Every registered path serves every spec, matching the oracle — the
    registry loop means a future registered path is covered by adding
    nothing here."""
    rng = np.random.default_rng(5)
    queries = _mixed_queries(uni5.cols, rng, 6)
    for name in eng_all.paths:
        res = eng_all.query_batch(queries, method=name, spec=spec)
        for q, r in zip(queries, res):
            _check_spec(spec, r, match_ids_np(uni5.cols, q), uni5.cols)
        # single-query entry agrees with the batch
        r1 = eng_all.query(queries[0], method=name, spec=spec)
        np.testing.assert_array_equal(np.asarray(r1), np.asarray(res[0]))


@pytest.mark.parametrize("spec", [Ids(), Count(), TopK(k=5, dim=2),
                                  Agg("sum", 1), Agg("max", 17)],
                         ids=lambda s: repr(s))
def test_specs_vs_oracle_gmrqb(spec):
    """GMRQB template batches (19 dims, point/categorical predicates — heavy
    attribute ties) through every plannable path and "auto"."""
    from repro.data import gmrqb

    ds = gmrqb.build(8192, seed=5)
    eng = MDRQEngine(ds, tile_n=1024)
    rng = np.random.default_rng(11)
    queries = [gmrqb.template(k, rng, ds) for k in (1, 2, 4, 5, 7, 8)]
    for name in list(eng.paths) + ["auto"]:
        res = eng.query_batch(queries, method=name, spec=spec)
        for q, r in zip(queries, res):
            _check_spec(spec, r, match_ids_np(ds.cols, q), ds.cols)


def test_count_equals_len_ids_everywhere(eng_all, uni5):
    rng = np.random.default_rng(7)
    queries = _mixed_queries(uni5.cols, rng, 4)
    for name in list(eng_all.paths) + ["auto"]:
        ids = eng_all.query_batch(queries, method=name, spec=Ids())
        counts = eng_all.query_batch(queries, method=name, spec=Count())
        assert counts == [i.size for i in ids], name


# -- property sweep (seeded always; hypothesis-driven when installed) ---------

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def _property_case(seed, k, dim, largest, op, ds, eng):
    """One drawn case: random batch x random spec params, every path."""
    rng = np.random.default_rng(seed)
    queries = _mixed_queries(ds.cols, rng, 3)
    oracle = [match_ids_np(ds.cols, q) for q in queries]
    for spec in (Count(), TopK(k=k, dim=dim, largest=largest), Agg(op, dim)):
        for name in eng.paths:
            res = eng.query_batch(queries, method=name, spec=spec)
            for q, r, ids in zip(queries, res, oracle):
                _check_spec(spec, r, ids, ds.cols)


def test_property_specs_match_oracle_seeded(eng_all, uni5):
    """Deterministic sweep of the property: Count() == len(Ids()), TopK is a
    value-ordered subset, Agg matches the numpy reduction over the id set —
    across all registered paths."""
    rng = np.random.default_rng(99)
    for _ in range(4):
        _property_case(int(rng.integers(2**16)), int(rng.integers(1, 9)),
                       int(rng.integers(5)), bool(rng.integers(2)),
                       ("min", "max", "sum")[int(rng.integers(3))],
                       uni5, eng_all)


if HAVE_HYPOTHESIS:
    @settings(max_examples=12, deadline=None)
    @given(seed=st.integers(0, 2**16), k=st.integers(1, 9),
           dim=st.integers(0, 4), largest=st.booleans(),
           op=st.sampled_from(["min", "max", "sum"]))
    def test_property_specs_match_oracle(seed, k, dim, largest, op, uni5,
                                         eng_all):
        _property_case(seed, k, dim, largest, op, uni5, eng_all)


# -- launch / host-sync budgets ----------------------------------------------

@pytest.mark.parametrize("spec", [TopK(k=4, dim=2), Agg("sum", 1), Count()],
                         ids=lambda s: s.kind)
def test_reduced_specs_one_launch_one_sync_scan_paths(spec, eng_all, uni5):
    """On the scan paths a reduced batch is exactly one device launch (the
    fused kernel + the spec's reducer in one jit) and one host sync — only
    the payload crosses the boundary."""
    rng = np.random.default_rng(13)
    queries = _mixed_queries(uni5.cols, rng, 6)
    ops.reset_counters()
    eng_all.query_batch(queries, method="scan", spec=spec)
    assert ops.counters() == {"multi_scan_reduce": 1, "host_sync": 1}
    ops.reset_counters()
    eng_all.query_batch(queries, method="scan_vertical", spec=spec)
    assert ops.counters() == {"multi_scan_vertical_reduce": 1, "host_sync": 1}


@pytest.mark.parametrize("spec", [TopK(k=4, dim=2), Agg("max", 1)],
                         ids=lambda s: s.kind)
def test_reduced_specs_budget_two_phase_paths(spec, eng_all, uni5):
    """The two-phase paths add exactly one fused visit-reduce launch and one
    payload sync on top of their phase-1 budget: the tree prune is its own
    counted launch + survivor-mask sync, and the VA filter likewise, so both
    land at two launches + two syncs total."""
    rng = np.random.default_rng(17)
    queries = _mixed_queries(uni5.cols, rng, 6)
    ops.reset_counters()
    eng_all.query_batch(queries, method="kdtree", spec=spec)
    assert ops.counters() == {"prune_hierarchy_batch": 1,
                              "multi_visit_reduce": 1, "host_sync": 2}
    ops.reset_counters()
    eng_all.query_batch(queries, method="vafile", spec=spec)
    assert ops.counters() == {"multi_va_filter": 1, "multi_visit_reduce": 1,
                              "host_sync": 2}


def test_ids_budget_unchanged(eng_all, uni5):
    """The identity spec's budget matches the pre-spec protocol: one fused
    launch, one (mask) host sync."""
    rng = np.random.default_rng(19)
    queries = _mixed_queries(uni5.cols, rng, 4)
    ops.reset_counters()
    eng_all.query_batch(queries, method="scan", spec=Ids())
    assert ops.counters() == {"multi_scan_reduce": 1, "host_sync": 1}


# -- back-compat shim ---------------------------------------------------------

def test_mode_strings_map_to_specs_with_one_warning(eng_all, uni5):
    rng = np.random.default_rng(23)
    queries = _mixed_queries(uni5.cols, rng, 4)
    new = eng_all.query_batch(queries, method="scan", spec=Count())
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        old = eng_all.query_batch(queries, method="scan", mode="count")
        deps = [x for x in w if issubclass(x.category, DeprecationWarning)]
        assert len(deps) == 1  # a single warning, at the boundary
    assert old == new
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        old_ids = eng_all.query_batch(queries, method="scan", mode="ids")
        assert sum(issubclass(x.category, DeprecationWarning)
                   for x in w) == 1
    for a, b in zip(old_ids, eng_all.query_batch(queries, method="scan")):
        np.testing.assert_array_equal(a, b)
    # single-query spelling too
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert eng_all.query(queries[0], method="scan", mode="count") \
            == new[0]
        assert sum(issubclass(x.category, DeprecationWarning)
                   for x in w) == 1


def test_unknown_modes_keep_canonical_error(eng_all, uni5):
    q = RangeQuery.partial(uni5.m, {0: (0.1, 0.2)})
    for bad in ("top_k", "nope", 17):
        with pytest.raises(ValueError, match="unknown mode"):
            eng_all.query(q, mode=bad)
        with pytest.raises(ValueError, match="unknown mode"):
            validate_mode(bad)
    with pytest.raises(ValueError, match="not both"):
        resolve_spec(spec=Count(), mode="ids")
    # spec parameter validation has its own canonical errors
    with pytest.raises(ValueError, match="out of range"):
        eng_all.query(q, spec=TopK(k=2, dim=99))
    with pytest.raises(ValueError, match="TopK k"):
        TopK(k=0, dim=1)
    with pytest.raises(ValueError, match="unknown agg op"):
        Agg("median", 0)


def test_spec_registry_is_the_extension_point(per_query_path):
    """New result shapes register like access paths: a subclass lands in the
    kind registry and rides the PerQueryPath host-fallback rung with only
    ``from_ids`` defined — no engine, path, or kernel edits."""
    assert set(RESULT_SPEC_KINDS) >= {"ids", "count", "mask", "topk", "agg"}

    import dataclasses

    @register_result_spec
    @dataclasses.dataclass(frozen=True)
    class Median(ResultSpec):
        kind = "test_median"
        dim: int = 0

        @property
        def value_dim(self):
            return self.dim

        def from_ids(self, ids, cols):
            return float(np.median(cols[self.dim, ids])) if ids.size else float("nan")

        def host_bytes(self, touched, n):
            return 8.0 * np.ones_like(np.asarray(touched, np.float64))

        def result_size(self, res):
            return 1

    try:
        assert RESULT_SPEC_KINDS["test_median"] is Median
        rng = np.random.default_rng(3)
        ds = Dataset(rng.random((4, 2048), dtype=np.float32))
        eng = MDRQEngine(ds, structures=("scan",), tile_n=512)
        eng.register_path(per_query_path("per_query", ds.cols, cols=ds.cols))
        q = RangeQuery.partial(4, {1: (0.2, 0.7)})
        got = eng.query(q, method="per_query", spec=Median(dim=2))
        ids = match_ids_np(ds.cols, q)
        assert np.isclose(got, np.median(ds.cols[2, ids]))
    finally:
        RESULT_SPEC_KINDS.pop("test_median", None)


# -- spec-dependent planning --------------------------------------------------

def test_plan_batch_is_spec_dependent(uni5):
    """The reducer-aware output-bytes term flips a plan: at n=10M a
    moderately selective query reads a 10MB mask back under ``Ids()`` — the
    tree's visited fraction is far cheaper, so kdtree wins — while under
    ``Count()``/``Agg`` every path ships O(1) bytes and the amortized fused
    scan wins (the PR 3/4 cost surface)."""
    hist = Histograms.build(uni5)
    p = Planner(hist, CostModel(n=10_000_000, m=5),
                available=("scan", "kdtree"))
    side = 0.02 ** 0.2  # ~2% selectivity complete-match box
    q = RangeQuery.complete([0.0] * 5, [side] * 5)
    batch = QueryBatch.from_queries([q] * 128)

    ids_plan = p.plan_batch(batch, spec=Ids())
    cnt_plan = p.plan_batch(batch, spec=Count())
    agg_plan = p.plan_batch(batch, spec=Agg("sum", 0))
    assert ids_plan.methods[0] == "kdtree"
    assert cnt_plan.methods[0] == "scan"
    assert agg_plan.methods[0] == "scan"
    # scalar explain agrees with the batch surface
    assert p.explain(q, batch_size=128, spec=Ids()).method == "kdtree"
    assert p.explain(q, batch_size=128, spec=Count()).method == "scan"
    # and the modeled cost orders: Count/Agg batches price cheaper than Ids
    # on the scan path (the mask readback is the whole difference)
    j = ids_plan.path_names.index("scan")
    assert cnt_plan.costs[j, 0] < ids_plan.costs[j, 0]


def test_break_even_shifts_with_spec(uni5):
    """Under ``Ids()`` the scan pays the full mask readback while the index
    reads only its visited fraction, so the index wins a wider selectivity
    band than under the payload-free surface; ``Count()`` sits at the
    kernel-side break-even."""
    hist = Histograms.build(uni5)
    p = Planner(hist, CostModel(n=1_000_000, m=5))
    base = p.break_even_selectivity()                 # spec=None (kernel side)
    be_ids = p.break_even_selectivity(spec=Ids())
    be_cnt = p.break_even_selectivity(spec=Count())
    assert be_ids > base
    assert np.isclose(be_cnt, base, rtol=0.05)


# -- server typing ------------------------------------------------------------

def test_server_typed_by_spec(uni5):
    from repro.serve.mdrq_server import MDRQServer

    eng = MDRQEngine(uni5, structures=("scan",), tile_n=512)
    rng = np.random.default_rng(29)
    queries = _mixed_queries(uni5.cols, rng, 5)
    srv = MDRQServer(eng, max_batch=4, max_wait_s=float("inf"),
                     spec=TopK(k=3, dim=1))
    tickets = [srv.submit(q) for q in queries]
    srv.flush()
    assert all(t.spec == TopK(k=3, dim=1) for t in tickets)
    for q, t in zip(queries, tickets):
        _check_spec(TopK(k=3, dim=1), t.result(), match_ids_np(uni5.cols, q),
                    uni5.cols)
    assert srv.stats.spec_counts == {"topk": len(queries)}

    agg_srv = MDRQServer(eng, max_batch=8, max_wait_s=float("inf"),
                         spec=Agg("max", 2))
    res = agg_srv.serve_all(queries)
    for q, r in zip(queries, res):
        _check_spec(Agg("max", 2), r, match_ids_np(uni5.cols, q), uni5.cols)
    assert agg_srv.stats.spec_counts == {"agg": len(queries)}
    # spec validation happens at construction, before any query is accepted
    with pytest.raises(ValueError, match="out of range"):
        MDRQServer(eng, spec=Agg("sum", 99))
