"""Cross-device batched scan: the distributed equivalence suite.

``DistributedScan.query_batch`` must return exactly what single-device
``ColumnarScan`` returns — under Ids, Count and the reduced specs — while issuing
one fused collective launch and one host sync per batch (counter-asserted;
wall-clock on CPU cannot see launch budgets).

In-process tests run on whatever devices the session has (1 under the tier-1
suite; 8 under ``make test-dist``, which forces
``XLA_FLAGS=--xla_force_host_platform_device_count=8``). True multi-device
equivalence additionally runs in a subprocess with a forced 8-device CPU
platform so the main test process keeps its own device view (XLA locks the
device count at first init)."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.core import (Count, Dataset, DistributedScan, MDRQEngine,
                        QueryBatch, RangeQuery, match_ids_np)
from repro.core.distributed import make_data_mesh
from repro.core.scan import build_columnar_scan
from repro.kernels import ops


def _mixed_queries(ds, rng, n_q):
    """Record-anchored complete matches + partial + point + match-all."""
    out = []
    for _ in range(n_q):
        a = ds.cols[:, rng.integers(ds.n)]
        b = ds.cols[:, rng.integers(ds.n)]
        out.append(RangeQuery.complete(np.minimum(a, b), np.maximum(a, b)))
    out.append(RangeQuery.partial(ds.m, {1: (0.2, 0.6)}))
    rec = ds.cols[:, rng.integers(ds.n)]
    out.append(RangeQuery.complete(rec, rec))     # point query
    out.append(RangeQuery.partial(ds.m, {}))      # match-all
    return out


@pytest.fixture(scope="module")
def dist_pair(uni5):
    return (DistributedScan(uni5, mesh=make_data_mesh()),
            build_columnar_scan(uni5))


def test_distributed_batch_matches_columnar(dist_pair, uni5):
    """Batched ids and counts equal ColumnarScan, one launch + one sync."""
    dsc, cs = dist_pair
    rng = np.random.default_rng(3)
    batch = QueryBatch.from_queries(_mixed_queries(uni5, rng, 5))
    want = cs.query_batch(batch)

    ops.reset_counters()
    got = dsc.query_batch(batch)
    assert ops.counter("distributed_multi_reduce") == 1
    assert ops.counter("host_sync") == 1
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)

    ops.reset_counters()
    counts = dsc.query_batch(batch, spec=Count())
    assert ops.counter("distributed_multi_reduce") == 1
    assert ops.counter("host_sync") == 1
    assert counts == [w.size for w in want]
    assert all(isinstance(c, int) for c in counts)


def test_distributed_batch_accepts_query_list(dist_pair, uni5):
    dsc, cs = dist_pair
    rng = np.random.default_rng(11)
    queries = _mixed_queries(uni5, rng, 2)
    got = dsc.query_batch(queries)  # plain sequence, not a QueryBatch
    for q, ids in zip(queries, got):
        np.testing.assert_array_equal(ids, match_ids_np(uni5.cols, q))
    with pytest.raises(ValueError):
        dsc.query_batch(queries, spec="top_k")


def test_distributed_single_query_is_counted(uni5):
    """A single ``engine.query`` on a meshed engine is a batch of one: one
    collective launch and one host sync, in the launch/host-sync accounting
    (the seed's raw ``np.asarray`` escaped it)."""
    eng = MDRQEngine(uni5, structures=("scan",), tile_n=512,
                     mesh=make_data_mesh())
    q = RangeQuery.partial(uni5.m, {0: (0.1, 0.4)})
    ops.reset_counters()
    ids = eng.query(q, "scan")
    assert ops.counters() == {"distributed_multi_reduce": 1, "host_sync": 1}
    ops.reset_counters()
    cnt = eng.query(q, "scan", spec=Count())
    assert ops.counters() == {"distributed_multi_reduce": 1, "host_sync": 1}
    assert cnt == ids.size == match_ids_np(uni5.cols, q).size


def test_sharded_row_counters_equal_the_full_scans():
    """A sharded launch counts its compared and skipped (chunk, row) pairs
    once, under ``kernel="sharded"``, exactly as the single-device full scan
    counts the same GMRQB batch under ``kernel="full"``."""
    from repro.data import gmrqb
    from repro.obs import metrics
    ds = gmrqb.build(3000, seed=4)
    rng = np.random.default_rng(8)
    batch = QueryBatch.from_queries(
        [gmrqb.template(k, rng, ds) for k in [1, 2, 3, 4] * 10 + [8] * 3])
    reg = metrics.registry()

    def rows():
        return {o: reg.counter_values(f"mdrq_scan_rows_{o}_total", "kernel")
                for o in ("compared", "skipped")}

    reg.reset()
    want = build_columnar_scan(ds).query_batch(batch, spec=Count())
    full = rows()
    reg.reset()
    got = DistributedScan(ds, mesh=make_data_mesh()).query_batch(
        batch, spec=Count())
    sharded = rows()
    assert got == want
    assert sharded == {o: {"sharded": v["full"]} for o, v in full.items()}
    assert full["compared"]["full"] > 0 and full["skipped"]["full"] > 0


def test_meshed_engine_routes_scan_buckets(uni5):
    """``MDRQEngine(mesh=...)`` sends scan buckets through the distributed
    path (counter-asserted) and returns identical results to a plain engine;
    the cost model picks up the mesh's device count."""
    mesh = make_data_mesh()
    eng_d = MDRQEngine(uni5, structures=("scan",), tile_n=512, mesh=mesh)
    eng_s = MDRQEngine(uni5, structures=("scan",), tile_n=512)
    assert eng_d.planner.model.n_devices == mesh.shape["data"]
    assert eng_s.planner.model.n_devices == 1

    rng = np.random.default_rng(23)
    queries = _mixed_queries(uni5, rng, 4)
    ops.reset_counters()
    got = eng_d.query_batch(queries, method="scan")
    assert ops.counter("distributed_multi_reduce") == 1
    assert ops.counter("multi_scan_reduce") == 0  # not the single-device path
    for a, b in zip(got, eng_s.query_batch(queries, method="scan")):
        np.testing.assert_array_equal(a, b)

    counts = eng_d.query_batch(queries, method="scan", spec=Count())
    assert counts == [match_ids_np(uni5.cols, q).size for q in queries]
    # single-query dispatch routes through the mesh as well
    q = queries[0]
    np.testing.assert_array_equal(eng_d.query(q, "scan"),
                                  match_ids_np(uni5.cols, q))
    assert eng_d.query(q, "scan", mode="count") == match_ids_np(uni5.cols, q).size


def test_meshed_engine_never_auto_builds_columnar_copy(uni5):
    """On a meshed engine "auto" must not plan paths that execute on the
    single-device columnar copy: the lazy build would re-place the whole
    dataset on one device next to the sharded copy. Partial-match queries
    plan through the distributed scan instead; scan_vertical stays an
    explicit opt-in."""
    eng = MDRQEngine(uni5, structures=("scan",), tile_n=512,
                     mesh=make_data_mesh())
    assert eng.planner.available == ("scan",)
    assert eng._columnar is None
    q = RangeQuery.partial(uni5.m, {1: (0.2, 0.6)})
    res = eng.query_batch([q], method="auto")
    np.testing.assert_array_equal(res[0], match_ids_np(uni5.cols, q))
    assert eng._columnar is None  # no single-device copy materialized
    # the explicit opt-in still works (and only then builds the copy)
    np.testing.assert_array_equal(
        eng.query(q, method="scan_vertical"), match_ids_np(uni5.cols, q))
    assert eng._columnar is not None


def test_server_unchanged_on_meshed_engine(uni5):
    """The serving front end needs no change for a meshed engine: same API,
    same results, scan batches counted on the distributed path."""
    from repro.serve.mdrq_server import MDRQServer

    eng = MDRQEngine(uni5, structures=("scan",), tile_n=512,
                     mesh=make_data_mesh())
    rng = np.random.default_rng(31)
    queries = _mixed_queries(uni5, rng, 6)
    server = MDRQServer(eng, max_batch=4, max_wait_s=float("inf"),
                        method="scan")
    ops.reset_counters()
    results = server.serve_all(queries)
    # 9 queries at window 4 -> 3 flushes -> 3 fused collective launches
    assert ops.counter("distributed_multi_reduce") == server.stats.n_batches == 3
    for q, ids in zip(queries, results):
        np.testing.assert_array_equal(ids, match_ids_np(uni5.cols, q))

    counts = MDRQServer(eng, max_batch=8, max_wait_s=float("inf"),
                        method="scan", spec=Count()).serve_all(queries)
    assert counts == [match_ids_np(uni5.cols, q).size for q in queries]


# -- forced 8-device subprocess equivalence -----------------------------------

DIST_BATCH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax
    from repro.core import (Agg, Count, Dataset, DistributedScan, MDRQEngine,
                            QueryBatch, RangeQuery, TopK, match_ids_np)
    from repro.core.distributed import make_data_mesh
    from repro.core.scan import build_columnar_scan
    from repro.kernels import ops
    from repro.serve.mdrq_server import MDRQServer
    from repro.data import gmrqb

    assert len(jax.devices()) == 8
    rng = np.random.default_rng(7)

    def check_batch(ds, queries, mesh):
        dsc = DistributedScan(ds, mesh=mesh)
        cs = build_columnar_scan(ds)
        batch = QueryBatch.from_queries(queries)
        want = cs.query_batch(batch)
        ops.reset_counters()
        got = dsc.query_batch(batch)
        assert ops.counter("distributed_multi_reduce") == 1, ops.counters()
        assert ops.counter("host_sync") == 1, ops.counters()
        for k, (a, b) in enumerate(zip(got, want)):
            assert np.array_equal(a, b), k
        ops.reset_counters()
        counts = dsc.query_batch(batch, spec=Count())
        assert ops.counter("distributed_multi_reduce") == 1, ops.counters()
        assert ops.counter("host_sync") == 1, ops.counters()
        assert counts == [w.size for w in want]
        # reduced shapes: shard-local partials + one small collective merge,
        # still one launch + one host sync, oracle-checked against the ids
        for spec in (TopK(k=5, dim=1), Agg("sum", 0), Agg("min", 2)):
            ops.reset_counters()
            red = dsc.query_batch(batch, spec=spec)
            assert ops.counter("distributed_multi_reduce") == 1, ops.counters()
            assert ops.counter("host_sync") == 1, ops.counters()
            for k, ids in enumerate(want):
                vals = ds.cols[spec.dim, ids]
                if spec.kind == "topk":
                    assert set(red[k]) <= set(ids)
                    exp = ids[np.argsort(-vals, kind="stable")[: spec.k]]
                    assert np.allclose(ds.cols[spec.dim, red[k]],
                                       ds.cols[spec.dim, exp]), k
                elif spec.op == "sum":
                    assert np.isclose(red[k], vals.sum(dtype=np.float64),
                                      rtol=1e-4), k
                elif ids.size:
                    assert np.isclose(red[k], vals.min()), k
                else:
                    assert np.isnan(red[k]), k
        return want

    # random 5-dim dataset, record-anchored + partial + match-all queries
    ds = Dataset(rng.random((5, 40000), dtype=np.float32))
    queries = []
    for _ in range(6):
        a = ds.cols[:, rng.integers(ds.n)]; b = ds.cols[:, rng.integers(ds.n)]
        queries.append(RangeQuery.complete(np.minimum(a, b), np.maximum(a, b)))
    queries += [RangeQuery.partial(5, {1: (0.2, 0.6)}), RangeQuery.partial(5, {})]
    mesh = make_data_mesh(8)
    want = check_batch(ds, queries, mesh)

    # GMRQB template batches (19 dims, point predicates)
    gds = gmrqb.build(20000, seed=3)
    grng = np.random.default_rng(9)
    gqueries = [gmrqb.template(k, grng, gds) for k in (1, 4, 5, 7, 8)]
    check_batch(gds, gqueries, mesh)

    # meshed engine + unchanged server on top
    eng = MDRQEngine(ds, structures=("scan",), mesh=mesh)
    assert eng.planner.model.n_devices == 8
    srv = MDRQServer(eng, max_batch=4, max_wait_s=float("inf"), method="scan")
    res = srv.serve_all(queries)
    for a, b in zip(res, want):
        assert np.array_equal(a, b)
    print("DIST_BATCH_OK")
""")


def test_multi_device_batched_subprocess():
    root = Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["PYTHONPATH"] = str(root / "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", DIST_BATCH_SCRIPT],
                       capture_output=True, text=True, timeout=600, env=env,
                       cwd=root)
    assert "DIST_BATCH_OK" in r.stdout, \
        f"stdout={r.stdout}\nstderr={r.stderr[-2000:]}"
