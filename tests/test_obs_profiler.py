"""The span API's profiler sink (DESIGN.md §10): ``obs.to_profiler``.

  * switched off, ``span()`` is the ``NULL_SPAN`` singleton on every thread
    and ``ops.device_get`` opens no span;
  * switched on, a ``jax.profiler`` trace of a few pipelined windows holds
    the server's spans as ``mdrq.*`` events with their attributes: the two
    stages of each window on two host lines, linked by ``window``, and the
    kd-tree's mid-launch sync carrying its leaf mask's bytes;
  * a ``Tracer`` tree is the same with the sink on as with it off.
"""
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import Count, Dataset, MDRQEngine
from repro.data import synthetic
from repro.kernels import ops
from repro.obs import tracing
from repro.serve import serve_pipelined


@pytest.fixture
def sink_off():
    prev = tracing.to_profiler(False)
    yield
    tracing.to_profiler(prev)


@pytest.fixture(scope="module")
def ds():
    rng = np.random.default_rng(17)
    return Dataset(rng.random((4, 6_000), dtype=np.float32))


def _on_thread(fn):
    out = []
    th = threading.Thread(target=lambda: out.append(fn()))
    th.start()
    th.join()
    return out[0]


def test_switch_off_null_span_on_every_thread_and_no_sync_span(
        sink_off, monkeypatch):
    assert not tracing.active()
    assert tracing.span("flush", window=1) is obs.NULL_SPAN
    assert _on_thread(lambda: tracing.span("finalize", window=1)) \
        is obs.NULL_SPAN

    opened = []
    monkeypatch.setattr(tracing, "span",
                        lambda *a, **kw: opened.append(a) or obs.NULL_SPAN)
    got = ops.device_get(jnp.arange(4))
    np.testing.assert_array_equal(got, np.arange(4))
    assert opened == []
    assert ops.counter("host_sync") == 1


def test_switch_is_process_wide_and_returns_previous(sink_off):
    assert tracing.to_profiler(True) is False
    try:
        assert tracing.active()
        for sp in (tracing.span("flush", window=1, reason="size"),
                   _on_thread(lambda: tracing.span("finalize", window=1))):
            assert sp is not obs.NULL_SPAN
            with sp as got:
                assert got.set(x=1) is got
                got.block_on(None)
        assert not tracing.enabled()      # no Tracer was installed
    finally:
        assert tracing.to_profiler(False) is True
    assert tracing.span("flush") is obs.NULL_SPAN


def _mdrq_events(trace_dir):
    from jax.profiler import ProfileData
    files = sorted(trace_dir.rglob("*.xplane.pb"))
    assert files, "the profiler wrote no trace"
    pd = ProfileData.from_file(str(files[-1]))
    out = []
    for plane in pd.planes:
        for j, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("mdrq."):
                    stats = {k: v for k, v in e.stats}
                    out.append((e.name, f"{plane.name}/{j}", stats))
    return out


def test_profiler_trace_of_pipelined_windows(ds, tmp_path, sink_off):
    import jax

    eng = MDRQEngine(ds, structures=("scan", "kdtree"), tile_n=512)
    qs = synthetic.workload(ds, 12, seed=3)
    with serve_pipelined(eng, max_batch=4, max_wait_s=float("inf"),
                         method="kdtree", spec=Count(), warmup=False,
                         latency_budget_s=1e9) as srv:
        for q in qs:                      # one warm pass: compiles
            srv.submit(q)
        srv.drain()
        tracing.to_profiler(True)
        jax.profiler.start_trace(str(tmp_path))
        try:
            tickets = [srv.submit(q) for q in qs]   # three size flushes
            srv.drain()
        finally:
            jax.profiler.stop_trace()
            tracing.to_profiler(False)
        assert all(t.result(timeout=5.0) >= 0 for t in tickets)

    evs = _mdrq_events(tmp_path)
    names = {n for n, _, _ in evs}
    assert {"mdrq.flush", "mdrq.plan", "mdrq.execute", "mdrq.sync",
            "mdrq.backlog_put", "mdrq.finalize"} <= names
    flush = {st["window"]: th for n, th, st in evs if n == "mdrq.flush"}
    fin = {st["window"]: th for n, th, st in evs if n == "mdrq.finalize"}
    assert len(flush) == 3 and set(fin) == set(flush)
    for w, th in fin.items():
        assert th != flush[w], "both stages of a window on one host line"
    flush_st = [st for n, _, st in evs if n == "mdrq.flush"]
    assert all(st["reason"] == "size" and st["n_queries"] == 4
               for st in flush_st)
    assert {st["window"] for n, _, st in evs if n == "mdrq.backlog_put"} \
        == set(flush)

    syncs = [(th, st) for n, th, st in evs if n == "mdrq.sync"]
    launch = [st for th, st in syncs if st.get("stage") == "launch"]
    final = [(th, st) for th, st in syncs if st.get("stage") == "finalize"]
    assert len(launch) == 3 and len(final) == 3
    # the prune's (Q, n_leaves) bool leaf mask, Q padded to the pow2 bucket
    n_leaves = eng.paths["kdtree"]._index.n_leaves
    assert all(st["path"] == "kdtree" and st["bytes"] == 4 * n_leaves
               for st in launch)
    # payload syncs run on the finalizer's line, the prune's on admission's
    assert all(st["path"] == "kdtree" and st["bytes"] > 0 for _, st in final)
    assert {th for th, _ in final} == set(fin.values())
    assert set(flush.values()).isdisjoint(th for th, _ in final)


def _shape(spans):
    return [(s.name, s.attrs, _shape(s.children)) for s in spans]


def test_tracer_tree_is_unchanged_by_the_sink(ds, sink_off):
    eng = MDRQEngine(ds, structures=("scan", "kdtree"), tile_n=512)
    qs = synthetic.workload(ds, 8, seed=5)
    trees = []
    for on in (False, True, False):
        tracing.to_profiler(on)
        eng.query_batch(qs, spec=Count(), trace=True)
        bt = eng.last_trace
        trees.append((_shape(bt.spans),
                      [(t.method, t.launches, t.host_syncs)
                       for t in bt.queries]))
    tracing.to_profiler(False)
    assert trees[0] == trees[1] == trees[2]
    # syncs nest under the bucket that paid them
    ex = [s for s in eng.last_trace.spans if s.name == "execute"]
    assert all(c.name == "sync" for s in ex for c in s.children)
    assert all(s.find("sync") for s in ex)
