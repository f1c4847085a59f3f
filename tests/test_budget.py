"""BUDGET.json: the static launch/sync certificate vs (a) a fresh derivation
from the source and (b) the runtime ``mdrq_launches_total`` counters.

This is the contract that makes the certificate trustworthy in both
directions: ``analysis.budget`` derives the numbers by abstract
interpretation over the project call graph (stdlib ast, no jax), and this
file re-asserts them against what the engine actually does — for every
certified path, frozen and under a live delta, under ``Ids()`` and
``Count()``, through both the synchronous ``query_batch`` and the split
``launch_batch``/``finalize`` protocol (whose device-stage/finalize split
the certificate states explicitly). If either side drifts, exactly one of
the two halves fails and names the path.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from repro.analysis import budget
from repro.analysis.engine import build_project, iter_py_files
from repro.core import Count, Ids, MDRQEngine, RangeQuery
from repro.kernels import ops

REPO = Path(__file__).resolve().parents[1]
CERT_PATH = REPO / "BUDGET.json"

SPECS = (Ids(), Count())


@pytest.fixture(scope="module")
def cert():
    return json.loads(CERT_PATH.read_text())


@pytest.fixture(scope="module")
def graph():
    project, errors = build_project(iter_py_files([REPO / "src"]))
    assert errors == []
    return project.graph


# -- the certificate is fresh and internally consistent -----------------------

def test_checked_in_certificate_matches_source(graph):
    """``make budget-cert`` would be a no-op: re-deriving the certificate
    from the current source produces the checked-in file byte-for-byte."""
    assert budget.check(graph, CERT_PATH) == []


def test_certificate_covers_every_registered_path(cert):
    """Every plannable fused path the engine can build is certified."""
    assert set(cert["paths"]) == {"scan", "scan_vertical", "kdtree",
                                  "rstar", "vafile"}
    for name, ctx in cert["paths"].items():
        assert set(ctx) == {"frozen", "delta"}, name


def test_certificate_internal_consistency(cert):
    """finalize = total - device_stage, launches all happen in the device
    stage, and the engine/serve layers add zero cost of their own. The
    tree paths certify a second budget for specs whose visit list stays on
    the device: the same launches, one sync, and that one in finalize."""
    for name, ctx in cert["paths"].items():
        for key in ("frozen", "delta"):
            for e in (ctx[key], ctx[key].get("visits_on_device", ctx[key])):
                assert e["finalize_host_syncs"] == (
                    e["total"]["host_syncs"]
                    - e["device_stage"]["host_syncs"]), (name, key)
                assert e["total"]["launches"] == \
                    e["device_stage"]["launches"], (name, key)
                assert e["finalize_host_syncs"] >= 1, (name, key)
    for name in ("kdtree", "rstar"):
        for key in ("frozen", "delta"):
            bound = cert["paths"][name][key]
            dev = bound["visits_on_device"]
            assert bound["total"]["host_syncs"] == 2, (name, key)
            assert dev["total"]["host_syncs"] == 1, (name, key)
            assert dev["device_stage"]["host_syncs"] == 0, (name, key)
            assert dev["total"]["launches"] == bound["total"]["launches"]
    zero = {"host_syncs": 0, "launches": {}}
    assert cert["engine"]["MDRQEngine.launch_batch"] == zero
    assert cert["engine"]["MDRQEngine.query_batch"] == zero
    assert cert["engine"]["PendingBatch.finalize"]["per_bucket"] == \
        {"host_syncs": 1, "launches": {}}
    assert cert["serve"]["PipelinedMDRQServer.flush"] == zero
    assert cert["serve"]["PipelinedMDRQServer._finalize_loop"] == zero


# -- the certificate matches the runtime counters ------------------------------

def _mixed_queries(cols, rng, n_q=6):
    m = cols.shape[0]
    out = []
    for k in range(n_q):
        if k % 2 == 0:
            a = cols[:, rng.integers(cols.shape[1])]
            b = cols[:, rng.integers(cols.shape[1])]
            out.append(RangeQuery.complete(np.minimum(a, b),
                                           np.maximum(a, b)))
        else:
            dims = rng.choice(m, size=int(rng.integers(1, m + 1)),
                              replace=False)
            preds = {int(d): tuple(sorted(rng.random(2).tolist()))
                     for d in dims}
            out.append(RangeQuery.partial(m, preds))
    return out


@pytest.fixture(scope="module")
def eng_frozen(uni5):
    return MDRQEngine(uni5, tile_n=512)


@pytest.fixture(scope="module")
def eng_delta(uni5):
    eng = MDRQEngine(uni5, tile_n=512)
    rng = np.random.default_rng(177)
    new_ids = eng.append(rng.random((200, uni5.m)).astype(np.float32))
    eng.delete(np.concatenate([rng.choice(uni5.n, 120, replace=False),
                               new_ids[:10]]))
    return eng


def _expected(entry) -> dict:
    """Certificate entry -> the exact ``ops.counters()`` dict (nonzero only,
    host syncs under the ``host_sync`` pseudo-op of the same family)."""
    exp = dict(entry["launches"])
    if entry["host_syncs"]:
        exp["host_sync"] = entry["host_syncs"]
    return exp


@pytest.mark.parametrize("context", ["frozen", "delta"])
@pytest.mark.parametrize("spec", SPECS, ids=lambda s: s.kind)
def test_query_batch_counters_equal_certificate(cert, eng_frozen, eng_delta,
                                                uni5, context, spec):
    """Warm path x spec x frozen/delta: one synchronous ``query_batch``
    bumps exactly the certified mdrq_launches_total deltas."""
    eng = eng_frozen if context == "frozen" else eng_delta
    rng = np.random.default_rng(7)
    queries = _mixed_queries(uni5.cols, rng)
    for name, ctx in cert["paths"].items():
        e = ctx[context]
        if spec.visits_on_device:
            e = e.get("visits_on_device", e)
        eng.query_batch(queries, method=name, spec=spec)  # warm / trace
        ops.reset_counters()
        eng.query_batch(queries, method=name, spec=spec)
        assert ops.counters() == _expected(e["total"]), \
            (name, context, spec.kind)


@pytest.mark.parametrize("context", ["frozen", "delta"])
def test_split_protocol_stage_split_equals_certificate(cert, eng_frozen,
                                                       eng_delta, uni5,
                                                       context):
    """``launch_batch`` spends exactly the certified device-stage budget;
    ``finalize`` adds exactly the certified finalize syncs (one bucket)."""
    eng = eng_frozen if context == "frozen" else eng_delta
    rng = np.random.default_rng(19)
    queries = _mixed_queries(uni5.cols, rng)
    for name, ctx in cert["paths"].items():
        e = ctx[context]
        eng.query_batch(queries, method=name)  # warm / trace
        ops.reset_counters()
        pending = eng.launch_batch(queries, method=name)
        assert ops.counters() == _expected(e["device_stage"]), \
            (name, context, "device stage")
        pending.finalize()
        assert ops.counters() == _expected(e["total"]), \
            (name, context, "after finalize")
        assert ops.counter("host_sync") - e["device_stage"]["host_syncs"] \
            == e["finalize_host_syncs"], (name, context)


@pytest.mark.parametrize("context", ["frozen", "delta"])
def test_split_protocol_device_route_equals_certificate(cert, eng_frozen,
                                                        eng_delta, uni5,
                                                        context):
    """Under Count the tree paths' ``launch_batch`` syncs nothing and
    ``finalize`` syncs once: the certificate's ``visits_on_device``
    budget."""
    eng = eng_frozen if context == "frozen" else eng_delta
    queries = _mixed_queries(uni5.cols, np.random.default_rng(29))
    for name in ("kdtree", "rstar"):
        e = cert["paths"][name][context]["visits_on_device"]
        eng.query_batch(queries, method=name, spec=Count())  # warm / trace
        ops.reset_counters()
        pending = eng.launch_batch(queries, method=name, spec=Count())
        assert ops.counters() == _expected(e["device_stage"]), (name, context)
        pending.finalize()
        assert ops.counters() == _expected(e["total"]), (name, context)


def test_certificate_drift_is_detected(graph, tmp_path):
    """A tampered certificate fails ``budget.check`` with a leaf-level diff
    naming the changed key — the CI failure mode for an uncommitted budget
    change."""
    cert = budget.certify(graph)
    cert["paths"]["scan"]["frozen"]["total"]["host_syncs"] += 1
    stale = tmp_path / "BUDGET.json"
    stale.write_text(budget.render(cert))
    drift = budget.check(graph, stale)
    assert len(drift) == 1
    assert "paths.scan.frozen.total.host_syncs" in drift[0]


if __name__ == "__main__":
    raise SystemExit(pytest.main([__file__, "-q"]))
