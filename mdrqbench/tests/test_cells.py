"""Every cell's code end to end at a tiny size on the CPU, and the reference
against the engine on every path and spec."""
import time

import numpy as np
import pytest

from mdrqbench import gen, harness, reference, specs
from mdrqbench.tests.small import ROWS, cell_names, small_cell


@pytest.mark.parametrize("name", cell_names())
def test_cell_runs_and_is_correct(name):
    cell = small_cell(name)
    out = harness.run_cell(cell, 2**31 + 12345, 1.0, False,
                           time.perf_counter())
    assert out["correct"], out["limits"]
    assert out["attempted"] > 0 and out["failed"] == 0
    want = {m["name"] for m in cell.end_to_end}
    assert set(out["metrics"]) == want
    for v in out["metrics"].values():
        assert np.isfinite(v["value"]) and v["value"] > 0
    assert list(out)[-1] == "limits"


@pytest.mark.parametrize("config", ["gmrqb-10m", "synt-uni-10m-d5"])
def test_reference_agrees_with_engine_on_every_path_and_spec(config):
    from repro.core import Count, Dataset, Ids, MDRQEngine, RangeQuery
    name = next(n for n in cell_names()
                if harness.load_cell(n).cfg["name"] == config)
    cell = small_cell(name)
    rng = np.random.default_rng(7)
    cols = gen.load(cell.cfg["generator"]).build(cell.cfg, rng)
    assert cols.shape == (cell.cfg["dims"], ROWS) and cols.dtype == np.float32
    lower, upper = harness.make_pool(cell, cols, rng, 16)
    eng = MDRQEngine(Dataset(cols), structures=tuple(cell.cfg["structures"]),
                     tile_n=int(cell.cfg["tile_n"]))
    queries = [RangeQuery(lo, up) for lo, up in zip(lower, upper)]
    want = [reference.match_ids(cols, lo, up) for lo, up in zip(lower, upper)]
    assert sum(w.size for w in want) > 0
    for method in eng.paths:
        for spec in (Ids(), Count()):
            got = eng.query_batch(queries, method=method, spec=spec)
            for g, w in zip(got, want):
                kind = specs.load(spec.kind)
                assert kind.same(g, kind.answer(w, cols)), (method, spec.kind)


def test_query_generators_follow_the_paper():
    rng = np.random.default_rng(3)
    cols = gen.load("gmrqb").build({"rows": 5000, "dims": 19}, rng)
    lower, upper = gen.load("gmrqb_mixed").make(cols, 80, rng, {})
    dims = (~(np.isneginf(lower) & np.isposinf(upper))).sum(axis=1)
    # templates 1..8 constrain 2, 5, 3, 4, 5, 6, 7 and 19 dimensions, ten each
    assert sorted(np.bincount(dims, minlength=20)[[2, 3, 4, 6, 7, 19]]) \
        == [10] * 6 and np.bincount(dims)[5] == 20
    one = gen.load("gmrqb_mixed").make(cols, 10, rng, {"templates": [8]})
    assert (~np.isinf(one[0])).all()
    uni = gen.load("synt_uni").build({"rows": 1000, "dims": 5}, rng)
    lo, up = gen.load("random_pair").make(uni, 50, rng, {})
    assert (lo <= up).all() and lo.shape == (50, 5)
    # a random-pair box always holds the two objects that span it
    assert all(reference.match_ids(uni, a, b).size >= 1
               for a, b in zip(lo, up))
