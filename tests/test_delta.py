"""The versioned dataset: delta segment, tombstones, compaction (DESIGN.md §11).

Covers the acceptance axes of the mutable plane:

  * equivalence — ``query_batch`` over (base + delta − tombstones) matches
    the numpy oracle over the combined live rows for every registered path ×
    every ResultSpec, including the tombstones-only (d=0) corner;
  * budgets — Count/TopK/Agg stay ONE fused launch + ONE host sync per batch
    with a non-empty delta (counter-asserted);
  * compaction — ``compact()`` returns a correct old->new id map, bumps the
    version, empties the delta, and preserves query results through the map;
    the explicit build()/ingest/commit() interleaving folds late writes;
  * planning — ``CostModel.delta_n`` flips a minority index pick to the scan
    as the delta grows, and the engine refreshes it from the snapshot;
  * atomicity — concurrent match-all counts during append/delete/compact only
    ever observe valid cumulative totals (no torn version mix);
  * calibration guards — zero-traffic and all-tombstoned traces produce
    no-op reports, not divide-by-zero.
"""
import threading

import numpy as np
import pytest

from repro.core import (Agg, Compactor, Count, Dataset, Ids, Mask, MDRQEngine,
                        QueryBatch, RangeQuery, TopK)
from repro.core import types as T
from repro.core.planner import CostModel, Histograms, Planner
from repro.kernels import ops
from repro.obs.audit import audit as audit_traces
from repro.obs.audit import calibration_samples

SPECS = (Ids(), Count(), Mask(), TopK(k=4, dim=2),
         TopK(k=3, dim=1, largest=False), Agg("sum", 3), Agg("min", 0),
         Agg("max", 4))


def _mixed_queries(m, rng, n_q):
    """Complete + partial + empty-range + match-all queries over [0, 1)."""
    out = []
    for k in range(n_q):
        if k % 2 == 0:
            a, b = np.sort(rng.random((2, m)).astype(np.float32), axis=0)
            out.append(RangeQuery.complete(a, b))
        else:
            dims = rng.choice(m, size=int(rng.integers(1, m + 1)),
                              replace=False)
            preds = {int(d): tuple(sorted(rng.random(2).tolist()))
                     for d in dims}
            out.append(RangeQuery.partial(m, preds))
    out.append(RangeQuery.partial(m, {0: (2.0, 3.0)}))  # empty result set
    out.append(RangeQuery.partial(m, {}))               # match-all
    return out


class _Oracle:
    """Numpy ground truth over the combined (base + delta − tombstones) rows."""

    def __init__(self, cols, extra_rows, dead_ids):
        self.cols = (np.concatenate([cols, extra_rows.T.astype(np.float32)],
                                    axis=1)
                     if extra_rows is not None and len(extra_rows) else cols)
        self.alive = np.ones((self.cols.shape[1],), bool)
        self.alive[np.asarray(dead_ids, np.int64)] = False

    def ids(self, q):
        return np.nonzero(T.match_mask_np(self.cols, q) & self.alive)[0] \
            .astype(np.int64)

    def check(self, spec, q, res):
        ids = self.ids(q)
        cols = self.cols
        if spec.kind == "ids":
            np.testing.assert_array_equal(res, ids)
        elif spec.kind == "count":
            assert isinstance(res, int) and res == ids.size
        elif spec.kind == "mask":
            assert res.dtype == bool and res.shape == (cols.shape[1],)
            np.testing.assert_array_equal(np.nonzero(res)[0], ids)
        elif spec.kind == "topk":
            vals = cols[spec.dim, ids]
            order = np.lexsort((ids, -vals if spec.largest else vals))
            np.testing.assert_array_equal(res, ids[order[: spec.k]])
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
def eng_delta(uni5, per_query_path):
    """All-paths engine over uni5 with a ~1% delta + mixed tombstones; the
    per-query path sees the frozen base only and merges the delta on the
    host."""
    eng = MDRQEngine(uni5)
    eng.register_path(per_query_path("per_query", uni5.cols, cols=uni5.cols))
    rng = np.random.default_rng(77)
    extra = rng.random((200, uni5.m)).astype(np.float32)   # 1% of n=20k
    new_ids = eng.append(extra)
    dead = np.concatenate([rng.choice(uni5.n, 120, replace=False),
                           new_ids[:10]])
    eng.delete(dead)
    return eng, _Oracle(uni5.cols, extra, dead)


ALL_PATHS = ("scan", "scan_vertical", "kdtree", "rstar", "vafile",
             "per_query")


@pytest.mark.parametrize("method", ALL_PATHS)
def test_delta_equivalence_all_paths_all_specs(method, eng_delta):
    """query_batch over (base + delta − tombstones) == the numpy oracle over
    the combined live rows, for every path × Ids/Count/Mask/TopK/Agg."""
    eng, oracle = eng_delta
    rng = np.random.default_rng(5)
    queries = _mixed_queries(eng.dataset.m, rng, 6)
    for spec in SPECS:
        results = eng.query_batch(queries, method=method, spec=spec)
        for q, res in zip(queries, results):
            oracle.check(spec, q, res)


def test_delta_equivalence_auto_and_singles(eng_delta):
    """The planner route and the single-query entry point agree with the
    oracle too (singles ride the delta-aware batch rung at Q=1)."""
    eng, oracle = eng_delta
    rng = np.random.default_rng(6)
    queries = _mixed_queries(eng.dataset.m, rng, 5)
    for spec in (Ids(), Count(), TopK(k=5, dim=0)):
        for q, res in zip(queries, eng.query_batch(queries, spec=spec)):
            oracle.check(spec, q, res)
        for q in queries[:3]:
            oracle.check(spec, q, eng.query(q, spec=spec))


def test_tombstones_only_delta(uni5):
    """Deletes with no appends (d=0) still fold on device — and stay at the
    frozen-path launch budget (no delta block to scan)."""
    eng = MDRQEngine(uni5, structures=("scan", "kdtree"))
    rng = np.random.default_rng(21)
    dead = rng.choice(uni5.n, 500, replace=False)
    eng.delete(dead)
    oracle = _Oracle(uni5.cols, None, dead)
    queries = _mixed_queries(uni5.m, rng, 4)
    for method in ("scan", "kdtree"):
        for spec in (Ids(), Count(), Agg("sum", 1)):
            for q, res in zip(queries,
                              eng.query_batch(queries, method=method,
                                              spec=spec)):
                oracle.check(spec, q, res)
    ops.reset_counters()
    eng.query_batch(queries, method="scan", spec=Count())
    assert ops.counters() == {"multi_scan_reduce": 1, "host_sync": 1}


@pytest.mark.parametrize("method", ("scan", "scan_vertical"))
def test_tombstoned_delta_rows_never_match_one_dim_chunks(uni5, method):
    """Each chunk of 32 queries here bounds one dimension, with a range that
    holds every value, so the scan kernels compare that one row and skip the
    rest: the +inf-poisoned tombstoned delta rows (and the object padding)
    are rejected by that compare alone. A match-all query compares row 0."""
    eng = MDRQEngine(uni5, structures=("scan",))
    rng = np.random.default_rng(31)
    extra = rng.random((300, uni5.m)).astype(np.float32)
    new_ids = eng.append(extra)
    dead = np.concatenate([new_ids[::3], rng.choice(uni5.n, 50,
                                                    replace=False)])
    eng.delete(dead)
    oracle = _Oracle(uni5.cols, extra, dead)
    queries = [RangeQuery.partial(uni5.m, {(k // 32) % uni5.m: (-1.0, 2.0)})
               for k in range(64)] + [RangeQuery.partial(uni5.m, {})]
    live = uni5.n + len(extra) - len(dead)
    for spec in (Count(), Ids()):
        for q, res in zip(queries, eng.query_batch(queries, method=method,
                                                   spec=spec)):
            oracle.check(spec, q, res)
    assert eng.query_batch(queries, method=method, spec=Count()) == \
        [live] * len(queries)


# -- launch / host-sync budgets under a live delta ----------------------------

@pytest.mark.parametrize("spec", [Count(), TopK(k=4, dim=2), Agg("sum", 1)],
                         ids=lambda s: s.kind)
def test_reduced_specs_budget_unchanged_with_delta(spec, eng_delta):
    """A non-empty delta changes no budget: the delta block scans inside the
    same fused jit and its payload rides the same host sync."""
    eng, _ = eng_delta
    rng = np.random.default_rng(13)
    queries = _mixed_queries(eng.dataset.m, rng, 6)
    ops.reset_counters()
    eng.query_batch(queries, method="scan", spec=spec)
    assert ops.counters() == {"multi_scan_reduce": 1, "host_sync": 1}
    ops.reset_counters()
    eng.query_batch(queries, method="scan_vertical", spec=spec)
    assert ops.counters() == {"multi_scan_vertical_reduce": 1, "host_sync": 1}
    ops.reset_counters()
    eng.query_batch(queries, method="kdtree", spec=spec)
    assert ops.counters() == {"prune_hierarchy_batch": 1,
                              "multi_visit_reduce": 1, "host_sync": 2}
    ops.reset_counters()
    eng.query_batch(queries, method="vafile", spec=spec)
    assert ops.counters() == {"multi_va_filter": 1, "multi_visit_reduce": 1,
                              "host_sync": 2}


def test_memory_report_includes_delta(eng_delta):
    """Satellite: memory_report carries the delta segment + tombstone bytes."""
    eng, _ = eng_delta
    rep = eng.memory_report()
    assert rep["delta"] == eng.delta.nbytes
    # segment rows + delta tombstones + base tombstone vector all counted
    assert rep["delta"] >= 200 * eng.dataset.m * 4 + eng.dataset.n


# -- compaction ---------------------------------------------------------------

def _tiny_engine(seed=11, m=3, n=1024, **kw):
    rng = np.random.default_rng(seed)
    ds = Dataset(rng.random((m, n), dtype=np.float32))
    kw.setdefault("structures", ("scan", "kdtree"))
    return MDRQEngine(ds, tile_n=256, **kw), rng


def test_compact_swaps_version_and_preserves_results():
    eng, rng = _tiny_engine()
    m, n = eng.dataset.m, eng.dataset.n
    extra = rng.random((50, m)).astype(np.float32)
    new_ids = eng.append(extra)
    dead = np.concatenate([rng.choice(n, 30, replace=False), new_ids[:5]])
    eng.delete(dead)
    oracle = _Oracle(eng.dataset.cols, extra, dead)
    queries = _mixed_queries(m, rng, 4)
    before = eng.query_batch(queries, method="scan")

    id_map = eng.compact()
    assert eng.version == 1
    assert eng.delta.d == 0 and eng.delta.n_total == eng.dataset.n
    assert eng.dataset.n == n + 50 - dead.size
    # the map: -1 exactly on tombstoned ids, a bijection onto the rest
    assert id_map.shape == (n + 50,)
    np.testing.assert_array_equal(np.nonzero(id_map < 0)[0], np.sort(dead))
    kept = id_map[id_map >= 0]
    np.testing.assert_array_equal(np.sort(kept), np.arange(eng.dataset.n))
    # every path answers identically, modulo the id renaming
    for method in ("scan", "kdtree"):
        after = eng.query_batch(queries, method=method)
        for res_b, res_a, q in zip(before, after, queries):
            np.testing.assert_array_equal(res_a, np.sort(id_map[res_b]))
            oracle.check(Ids(), q, res_b)
    # rebuilt-from-scratch engine agrees with the compacted one
    fresh = MDRQEngine(Dataset(oracle.cols[:, oracle.alive]), tile_n=256,
                       structures=("scan",))
    for res_a, res_f in zip(eng.query_batch(queries, method="scan"),
                            fresh.query_batch(queries, method="scan")):
        np.testing.assert_array_equal(res_a, res_f)


def test_compactor_folds_ingest_during_build():
    """Writes that land between build() and commit() survive the swap: late
    appends re-enter the new version's delta, late deletes fold through the
    id map (or tombstone the new delta)."""
    eng, rng = _tiny_engine(seed=12, structures=("scan",))
    m, n = eng.dataset.m, eng.dataset.n
    rows0 = rng.random((20, m)).astype(np.float32)
    ids0 = eng.append(rows0)
    eng.delete([0, 1, int(ids0[0])])

    comp = Compactor(eng)
    comp.build()
    # ingest mid-compaction: an append plus deletes hitting (a) a base row
    # kept by the build, (b) a delta row kept by the build, (c) a late row
    rows1 = rng.random((10, m)).astype(np.float32)
    ids1 = eng.append(rows1)
    eng.delete([5, int(ids0[1]), int(ids1[0])])
    id_map = comp.commit()

    assert eng.version == 1
    dead = np.array([0, 1, ids0[0], 5, ids0[1], ids1[0]])
    assert id_map.shape == (n + 30,)
    np.testing.assert_array_equal(np.nonzero(id_map < 0)[0], np.sort(dead))
    # the late rows live in the new version's delta (one already tombstoned)
    assert eng.delta.d == 10
    assert eng.dataset.n == n + 20 - 3  # build snapshot: 2 base + 1 delta dead
    # Oracle in the NEW id space: new base cols + the late rows, with the
    # late tombstones translated into it by hand. Base ids 0/1 died at build,
    # so kept base id 5 -> 5 - 2; ids0[1] is the first surviving snapshot
    # delta row -> n - 2; ids1[0] is the first new-delta row -> n_new.
    dead_new = [5 - 2, n - 2, eng.dataset.n]
    oracle = _Oracle(eng.dataset.cols, rows1, dead_new)
    queries = _mixed_queries(m, rng, 4)
    for q, res in zip(queries, eng.query_batch(queries, method="scan")):
        oracle.check(Ids(), q, res)


def test_compact_rejects_stale_commit():
    eng, rng = _tiny_engine(seed=13, structures=("scan",))
    eng.append(rng.random((4, eng.dataset.m)).astype(np.float32))
    c1, c2 = Compactor(eng), Compactor(eng)
    c1.build(), c2.build()
    c1.commit()
    with pytest.raises(RuntimeError, match="changed during compaction"):
        c2.commit()


def test_non_delta_aware_path_raises_until_compact():
    eng, rng = _tiny_engine(seed=14, structures=("scan",))

    class Frozen:
        nbytes_index = 0

        def query(self, q):
            return np.empty((0,), np.int64)

        def count(self, q):
            return 0

        def query_batch(self, batch, spec=Ids()):
            return [np.empty((0,), np.int64) for _ in range(len(batch))]

    from repro.core.paths import PerQueryPath

    class FrozenPath(PerQueryPath):
        def query_batch(self, batch, spec=Ids()):  # no delta param
            return super(FrozenPath, self).query_batch(batch, spec=spec)

    eng.register_path(FrozenPath("frozen", Frozen()))
    q = RangeQuery.partial(eng.dataset.m, {})
    eng.query_batch([q], method="frozen")  # empty delta: fine
    eng.append(rng.random((2, eng.dataset.m)).astype(np.float32))
    with pytest.raises(ValueError, match="not delta-aware"):
        eng.query_batch([q], method="frozen")


# -- planning -----------------------------------------------------------------

def test_plan_batch_flips_index_pick_as_delta_grows(uni5):
    """The documented flip: a minority-bucket index pick amortizes the delta
    scan over few queries; as delta_n grows its per-query delta share beats
    the index advantage and plan_batch reassigns it to the scan bucket."""
    hist = Histograms.build(uni5)
    model = CostModel(n=4_000_000, m=uni5.m)
    planner = Planner(hist, model, available=("scan", "kdtree"))
    lo = np.full((uni5.m,), 0.4, np.float32)
    tiny = [RangeQuery.complete(lo, lo + 2e-4) for _ in range(8)]
    broad = [RangeQuery.complete(np.zeros(uni5.m, np.float32),
                                 np.full(uni5.m, 0.9, np.float32))
             for _ in range(24)]
    batch = QueryBatch.from_queries(tiny + broad)

    # planned under Count: the Ids spec adds an O(result) host-materialize
    # term that would mask the delta axis for full-scan picks
    model.delta_n = 0
    bp0 = planner.plan_batch(batch, spec=Count())
    assert bp0.methods[:8] == ["kdtree"] * 8
    assert set(bp0.methods[8:]) == {"scan"}

    model.delta_n = 2_000_000
    bp1 = planner.plan_batch(batch, spec=Count())
    assert bp1.methods == ["scan"] * 32


def test_engine_refreshes_delta_cost_axis(uni5):
    eng = MDRQEngine(uni5, structures=("scan",))
    q = RangeQuery.partial(uni5.m, {0: (0.1, 0.2)})
    eng.query_batch([q], method="scan")
    assert eng.planner.model.delta_n == 0
    eng.append(np.random.default_rng(0).random((64, uni5.m))
               .astype(np.float32))
    eng.query_batch([q], method="scan")
    assert eng.planner.model.delta_n == 64


# -- atomicity under concurrent serve traffic ---------------------------------

def test_compact_swap_atomic_under_concurrent_counts():
    """Background match-all counts during append/delete/compact must only
    ever observe valid cumulative totals: a torn swap (new base without its
    delta, double-counted delta, half-applied tombstones) would surface as
    an off-set count."""
    eng, rng = _tiny_engine(seed=15, n=2048)
    n = eng.dataset.n
    q = RangeQuery.partial(eng.dataset.m, {})
    valid = {n}
    observed, errors = [], []
    stop = threading.Event()

    def prober():
        try:
            while not stop.is_set():
                observed.append(
                    eng.query_batch([q], method="scan", spec=Count())[0])
        except Exception as exc:  # pragma: no cover - surfaced by the assert
            errors.append(exc)

    th = threading.Thread(target=prober)
    th.start()
    live = n
    try:
        for _ in range(3):
            ids = eng.append(rng.random((32, eng.dataset.m))
                             .astype(np.float32))
            live += 32
            valid.add(live)
            eng.delete(ids[:8])
            live -= 8
            valid.add(live)
            eng.compact()  # count-invariant: swap must not change totals
    finally:
        stop.set()
        th.join(timeout=60)
    assert not errors, errors
    assert observed and set(observed) <= valid, \
        (sorted(set(observed) - valid), sorted(valid))
    assert eng.version == 3
    assert eng.query_batch([q], method="scan", spec=Count())[0] == live


# -- calibration guards (satellite) -------------------------------------------

def test_calibrate_no_ops_on_empty_samples(uni5):
    eng = MDRQEngine(uni5, structures=("scan",))
    before = eng.planner.model.sec_per_byte
    report = eng.planner.calibrate([])
    assert report.n_samples == 0 and not report.ok
    assert np.isnan(report.rms_rel_err)
    assert eng.planner.model.sec_per_byte == before
    assert calibration_samples([], eng.planner.model) == []


def test_calibration_pipeline_survives_all_tombstoned_traffic():
    """Traces from a fully tombstoned dataset (every query returns nothing)
    still audit and calibrate without dividing by zero."""
    eng, rng = _tiny_engine(seed=16, structures=("scan",))
    eng.delete(np.arange(eng.dataset.n))
    queries = _mixed_queries(eng.dataset.m, rng, 4)
    eng.query_batch(queries, method="scan", trace=True)
    trace = eng.last_trace
    assert all(qt.result_size == 0 for qt in trace.queries)
    rep = audit_traces([trace])
    assert rep is not None
    samples = calibration_samples([trace], eng.planner.model)
    report = eng.planner.calibrate(samples)
    assert report.n_samples == len(samples)
