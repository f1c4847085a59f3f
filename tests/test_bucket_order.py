"""Bucket order: each bucket runs its queries grouped by bounded dimensions.

``engine.bucket_order`` sorts a bucket's input positions stably by (number
of bounded dimensions, the bounded-dimension bit pattern), so that a scan
kernel's chunk of 32 queries compares only the rows its own templates
bound. Results scatter back by input position, so every answer stays where
the caller put its query.
"""
import numpy as np
import pytest

from repro import obs
from repro.core import (Count, Ids, MDRQEngine, QueryBatch, make_data_mesh,
                        match_ids_np)
from repro.core.engine import bucket_order
from repro.data import gmrqb
from repro.kernels import multi_scan as ms

# GMRQB's templates in the order bucket_order puts them: by bound-dimension
# count (2, 3, 4, 5, 5, 6, 7, 19), templates 2 and 5 (both 5) by their
# highest bounded dimension (6 against 13)
SORTED_TEMPLATES = [1, 3, 4, 2, 5, 6, 7, 8]


def _reference_order(idxs, mask):
    """Python's stable sort on (count, sum of 2**d over bounded d)."""
    def key(k):
        bits = np.flatnonzero(mask[k])
        return (bits.size, sum(2 ** int(d) for d in bits))
    return sorted(idxs, key=key)


def _signatures(m, rng, n_sigs, q):
    """A (q, m) mask drawn from ``n_sigs`` random signatures."""
    sigs = rng.random((n_sigs, m)) < 0.3
    sigs[:, 0] = True
    return sigs[rng.integers(n_sigs, size=q)]


@pytest.fixture(scope="module")
def ds():
    return gmrqb.build(2000, seed=5)


def _round_robin(ds, per_template=16, seed=9):
    """128 GMRQB queries in round-robin template order 1..8, 1..8, ..."""
    rng = np.random.default_rng(seed)
    return [gmrqb.template(k, rng, ds)
            for k in list(range(1, 9)) * per_template]


@pytest.mark.parametrize("case,m", [
    ("homogeneous", 5), ("homogeneous", 19), ("homogeneous", 100),
    ("ties", 5), ("ties", 19), ("ties", 100),
    ("gmrqb", 19)])
def test_bucket_order(case, m, ds):
    """A bucket of one signature keeps its order; ties keep arrival order;
    GMRQB's eight signatures come out grouped, template 8 last."""
    rng = np.random.default_rng(m)
    if case == "homogeneous":
        idxs = np.sort(rng.choice(200, 128, replace=False))
        mask = np.zeros((200, m), bool)
        mask[:, : m // 2 + 1] = True
    elif case == "ties":
        mask = _signatures(m, rng, 4, 200)
        idxs = np.sort(rng.choice(200, 150, replace=False))
    else:
        mask = QueryBatch.from_queries(_round_robin(ds)).dims_mask
        idxs = np.arange(128)
    got = bucket_order(idxs, mask)
    assert got.dtype == idxs.dtype
    assert list(got) == _reference_order(list(idxs), mask)
    if case == "homogeneous":
        np.testing.assert_array_equal(got, idxs)
    if case == "gmrqb":
        # position k holds template k % 8 + 1
        templates = got % 8 + 1
        assert list(templates) == list(np.repeat(SORTED_TEMPLATES, 16))
        for t in range(8):    # arrival order inside each template
            assert np.all(np.diff(got[16 * t: 16 * (t + 1)]) > 0)


def _engine(ds, path):
    if path == "sharded":
        return MDRQEngine(ds, structures=("scan",), tile_n=512,
                          mesh=make_data_mesh()), "scan"
    return MDRQEngine(ds, structures=("scan",), tile_n=512), path


@pytest.mark.parametrize("kind", ["count", "ids"])
@pytest.mark.parametrize("entry", ["launch", "query_batch"])
@pytest.mark.parametrize("path", ["scan", "scan_vertical", "sharded"])
def test_sorted_buckets_answer_at_every_position(ds, path, entry, kind):
    """128 round-robin GMRQB queries through the launch/finalize split and
    through query_batch: every input position gets the numpy reference's
    answer, although the bucket ran in sorted order."""
    eng, method = _engine(ds, path)
    queries = _round_robin(ds)
    spec = Count() if kind == "count" else Ids()
    if entry == "launch":
        got = eng.launch_batch(queries, method=method, spec=spec).finalize()
    else:
        got = eng.query_batch(queries, method=method, spec=spec)
    want = [match_ids_np(ds.cols, q) for q in queries]
    assert sum(w.size for w in want) > 0
    for k, (g, w) in enumerate(zip(got, want)):
        if kind == "count":
            assert g == w.size, k
        else:
            np.testing.assert_array_equal(g, w, err_msg=str(k))
    assert obs.registry().counter_values(
        "mdrq_bucket_reordered_total", "path") == {method: 1.0}


@pytest.mark.parametrize("path,kernel", [("scan", "full"),
                                         ("sharded", "sharded")])
def test_sorted_bucket_row_counters(ds, path, kernel):
    """The round-robin bucket of 16 queries a template at Q=128: in arrival
    order every chunk of 32 holds all eight templates (19 rows bound, so
    dense: 4 x 24 = 96 pairs compared); sorted, the chunks hold {1, 3}
    (3 rows), {4, 2} (5), {5, 6} (8) and {7, 8} (dense, 24): 40."""
    queries = _round_robin(ds)
    arrival = np.zeros((128, 24), bool)
    arrival[:, :19] = QueryBatch.from_queries(queries).dims_mask
    assert ms.chunk_flags(arrival, xp=np).sum() == 96
    eng, method = _engine(ds, path)
    reg = obs.registry()
    reg.reset()
    eng.launch_batch(queries, method=method, spec=Count()).finalize()
    rows = {o: reg.counter_values(f"mdrq_scan_rows_{o}_total", "kernel")
            for o in ("compared", "skipped")}
    assert rows == {"compared": {kernel: 40.0}, "skipped": {kernel: 56.0}}
    assert reg.counter_values("mdrq_bucket_reordered_total",
                              "path") == {method: 1.0}


def test_one_signature_bucket_is_not_counted(ds):
    """A bucket of template-8 queries only runs in arrival order and
    counts no reordering."""
    rng = np.random.default_rng(3)
    queries = [gmrqb.template(8, rng, ds) for _ in range(40)]
    eng = MDRQEngine(ds, structures=("scan",), tile_n=512)
    got = eng.query_batch(queries, method="scan", spec=Count())
    assert got == [match_ids_np(ds.cols, q).size for q in queries]
    assert obs.registry().counter_values("mdrq_bucket_reordered_total",
                                         "path") == {}
