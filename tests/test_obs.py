"""§Observability: metrics registry, spans, traces, query log, drift audit.

Covers DESIGN.md §10 end to end:
  * histogram percentiles against a numpy oracle (error bounded by one
    bucket ratio),
  * exporter round-trips (JSONL parse-back; Prometheus text lint),
  * span nesting around jitted calls with launch/host-sync attribution
    (spans wrap the jitted call — no trace-time capture),
  * zero overhead when disabled: ``span()`` returns the shared singleton and
    ``query_batch(trace=False)`` allocates no Span objects at all,
  * ``query_batch(trace=True)`` QueryTrace correctness,
  * the drift audit flagging a skewed-histogram selectivity model,
  * the acceptance loop: corrupt a cost constant -> traced queries ->
    ``Planner.calibrate`` on the audit's observations repairs it,
  * server latency percentiles, flush reasons, the bounded reservoir log,
    and the deadline-flush trace event,
  * tracing stays cheap at B=128: spans per batch are O(buckets), not
    O(queries), and a disabled span allocates nothing.
"""
import json
import math
import re
import time
import tracemalloc

import numpy as np
import pytest

from repro import obs
from repro.core import Count, Dataset, MDRQEngine, RangeQuery
from repro.kernels import ops
from repro.obs import metrics, tracing


@pytest.fixture(scope="module")
def engine():
    rng = np.random.default_rng(7)
    return MDRQEngine(Dataset(rng.random((4, 20_000), dtype=np.float32)))


@pytest.fixture
def xla_backend():
    # ops.set_backend drops the jit caches on switch: the backend is read at
    # trace time, so executables another test traced at a colliding padded
    # shape would otherwise be reused under the wrong backend
    prev = ops.set_backend("xla")
    yield
    ops.set_backend(prev)


def _queries(m, n_q, seed=0, width=0.4):
    rng = np.random.default_rng(seed)
    lo = rng.random((n_q, m)).astype(np.float32) * (1 - width)
    return [RangeQuery.complete(lo[k], lo[k] + width) for k in range(n_q)]


# -- metrics registry ---------------------------------------------------------

def test_counter_gauge_labels_and_families():
    reg = obs.registry()
    a = reg.counter("t_total", op="a")
    b = reg.counter("t_total", op="b")
    assert a is reg.counter("t_total", op="a")  # get-or-create
    a.inc(); a.inc(2); b.inc()
    assert a.value == 3 and b.value == 1
    assert reg.family_total("t_total") == 4
    assert reg.counter_values("t_total", "op") == {"a": 3.0, "b": 1.0}
    g = reg.gauge("t_gauge")
    g.set(2.5)
    assert g.value == 2.5
    with pytest.raises(TypeError):
        reg.gauge("t_total", op="a")  # kind mismatch on one family
    reg.reset()
    assert a.value == 0  # reset zeroes values but keeps objects live


def test_histogram_percentiles_vs_numpy_oracle():
    rng = np.random.default_rng(0)
    xs = np.exp(rng.normal(loc=-7.0, scale=2.0, size=4000))  # latency-ish
    h = metrics.Histogram("lat", {})
    for x in xs:
        h.observe(float(x))
    assert h.count == len(xs)
    assert math.isclose(h.sum, float(xs.sum()), rel_tol=1e-9)
    for p in (50, 90, 95, 99):
        exact = float(np.percentile(xs, p))
        est = h.percentile(p)
        # interpolation is exact to one bucket ratio by construction
        assert exact / metrics.LATENCY_BUCKET_RATIO <= est \
            <= exact * metrics.LATENCY_BUCKET_RATIO
    # clamped to observed extremes
    assert h.percentile(100) == pytest.approx(float(xs.max()))
    assert xs.min() <= h.percentile(0.01) <= np.percentile(xs, 1)
    ps = h.percentiles((50, 95, 99))
    assert set(ps) == {"p50", "p95", "p99"}


def test_histogram_empty_and_validation():
    h = metrics.Histogram("lat", {})
    assert math.isnan(h.percentile(50))
    with pytest.raises(ValueError):
        h.percentile(0)
    with pytest.raises(ValueError):
        metrics.Histogram("bad", {}, bounds=(2.0, 1.0))


def test_jsonl_export_round_trips():
    reg = obs.registry()
    reg.counter("rt_total", help="x", op="scan").inc(5)
    reg.gauge("rt_gauge").set(1.25)
    h = reg.histogram("rt_seconds", kind="ids")
    for v in (1e-4, 2e-4, 3e-3):
        h.observe(v)
    rows = [json.loads(line) for line in reg.to_jsonl().splitlines()]
    by_name = {(r["name"], tuple(sorted(r["labels"].items()))): r
               for r in rows}
    c = by_name[("rt_total", (("op", "scan"),))]
    assert c["type"] == "counter" and c["value"] == 5
    g = by_name[("rt_gauge", ())]
    assert g["type"] == "gauge" and g["value"] == 1.25
    hr = by_name[("rt_seconds", (("kind", "ids"),))]
    assert hr["type"] == "histogram" and hr["count"] == 3
    assert hr["sum"] == pytest.approx(3.3e-3)
    # sparse buckets carry (edge, cumulative count); last cum == count
    assert hr["buckets"][-1][1] == 3
    assert "p50" in hr and "p99" in hr


def test_prometheus_text_lints():
    reg = obs.registry()
    reg.counter("pl_total", help="a counter", op="scan").inc(2)
    reg.counter("pl_total", op="tree").inc(1)
    reg.gauge("pl_gauge").set(3)
    h = reg.histogram("pl_seconds", help="a histogram", kind="ids")
    h.observe(1e-4); h.observe(5.0e-1)
    text = reg.to_prometheus()
    assert text.endswith("\n")
    sample_re = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*'                 # metric name
        r'(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"'         # first label
        r'(,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\})?'    # more labels
        r' (\+Inf|-?[0-9.eE+-]+)$')                  # value
    types = []
    for line in text.splitlines():
        if line.startswith("# HELP "):
            continue
        if line.startswith("# TYPE "):
            types.append(line.split()[2:4])
            continue
        assert sample_re.match(line), f"malformed sample line: {line!r}"
    # one TYPE per family, correct kinds
    fams = dict((n, k) for n, k in types)
    assert len(types) == len(fams)
    assert fams["pl_total"] == "counter"
    assert fams["pl_gauge"] == "gauge"
    assert fams["pl_seconds"] == "histogram"
    # histogram triplet: +Inf bucket cumulative == _count
    inf = re.search(r'pl_seconds_bucket\{kind="ids",le="\+Inf"\} (\d+)', text)
    cnt = re.search(r'pl_seconds_count\{kind="ids"\} (\d+)', text)
    assert inf and cnt and inf.group(1) == cnt.group(1) == "2"
    assert 'pl_seconds_sum{kind="ids"}' in text


# -- spans & launch attribution ----------------------------------------------

def test_ops_counters_are_registry_backed(engine):
    """The launch/host-sync budget counters and the metrics registry are one
    store — budget tests migrated to the registry backend see identical
    numbers through either API."""
    engine.query_batch(_queries(4, 8), method="scan")
    assert ops.counters()  # something launched
    vals = obs.registry().counter_values(tracing.LAUNCH_FAMILY, "op")
    for name, count in ops.counters().items():
        assert vals[name] == count


def test_span_nesting_around_jitted_calls(engine):
    """Spans wrap the jitted call (never the traced body): nested spans
    record the launches and host syncs that completed under them."""
    qs = _queries(4, 8, seed=1)
    engine.query_batch(qs, method="scan")  # warm the jit cache first
    ops.reset_counters()
    with obs.Tracer() as tr:
        with obs.span("outer") as outer:
            with obs.span("inner", path="scan") as inner:
                engine.query_batch(qs, method="scan")
    assert tr.spans == [outer]
    assert outer.children == [inner]
    assert inner.launches >= 1 and inner.host_syncs >= 1
    # the parent's deltas include the child's (snapshots are cumulative)
    assert outer.launches == inner.launches
    assert outer.host_syncs == inner.host_syncs
    assert inner.seconds > 0
    assert [s.attrs for s in tr.find("inner")] == [{"path": "scan"}]


def test_null_span_when_disabled_and_no_allocation(engine, monkeypatch):
    assert not obs.enabled()
    s = obs.span("anything", a=1)
    assert s is obs.NULL_SPAN  # the shared singleton, no allocation
    with s as got:
        got.set(x=2).block_on(None)  # all no-ops

    # the acceptance knife: with tracing disabled, the engine + path layers
    # must not construct a single Span object on the hot path
    def boom(*a, **kw):
        raise AssertionError("Span allocated with tracing disabled")
    monkeypatch.setattr(tracing, "Span", boom)
    res = engine.query_batch(_queries(4, 8, seed=2), trace=False)
    assert len(res) == 8


# -- engine traces ------------------------------------------------------------

def test_query_batch_trace_records(engine):
    qs = _queries(4, 16, seed=3)
    res = engine.query_batch(qs, trace=True)
    bt = engine.last_trace
    assert bt.n_queries == 16 and len(bt.queries) == 16
    assert bt.n == engine.dataset.n
    assert bt.plan_seconds <= bt.seconds
    assert [t.method for t in bt.queries] == engine.last_batch_stats.methods
    for t in bt.queries:
        assert t.bucket_size == engine.last_batch_stats.method_counts[t.method]
        assert t.spec_kind == "ids"
        assert t.mq == 4
        assert t.result_size == len(res[t.index])
        assert t.obs_selectivity == pytest.approx(
            len(res[t.index]) / engine.dataset.n)
        assert math.isfinite(t.est_cost)      # planned run: costs are real
        assert 0 < t.est_selectivity <= 1
        assert t.seconds >= 0 and t.launches > 0
    # span tree: one plan span, and exactly one execute span per realized
    # bucket, carrying its path
    names = [s.name for s in bt.spans]
    assert names.count("plan") == 1
    ex = [s for s in bt.spans if s.name == "execute"]
    assert sorted(s.attrs["path"] for s in ex) == \
        sorted(engine.last_batch_stats.method_counts)

    # explicit-method run: estimates exist, planner cost is honestly NaN
    engine.query_batch(qs, method="scan", trace=True)
    t = engine.last_trace.queries[0]
    assert t.method == "scan" and math.isnan(t.est_cost)
    assert 0 < t.est_selectivity <= 1
    # tracing did not leak an active tracer
    assert not obs.enabled()


def test_trace_disabled_leaves_no_trace(engine):
    engine.last_trace = None
    engine.query_batch(_queries(4, 4, seed=4))
    assert engine.last_trace is None


# -- drift audit + calibration repair -----------------------------------------

def test_audit_flags_skewed_histograms():
    """Perfectly correlated dims break the independence assumption: the
    histogram estimate is ~sel^2 while reality is ~sel — the audit must flag
    the (path x decile) cells, and a well-modeled dataset must stay clean."""
    rng = np.random.default_rng(11)
    col = rng.random(8_192, dtype=np.float32)
    skewed = MDRQEngine(Dataset(np.stack([col, col])),
                        structures=("scan",))
    qs = []
    for k in range(24):
        lo = float(rng.random() * 0.6)
        q = RangeQuery.complete([lo, lo], [lo + 0.25, lo + 0.25])
        qs.append(q)
    skewed.query_batch(qs, method="scan", trace=True)
    report = obs.audit(skewed.last_trace, sel_tolerance=2.0)
    assert not report.ok
    assert all(c.method == "scan" for c in report.drifted)
    # obs sel ~0.25 vs est ~0.0625 -> ratio ~4x, well past tolerance
    assert all(c.sel_ratio > 2.0 for c in report.drifted)
    assert "DRIFT" in report.summary()

    # independent uniform dims: the same workload shape audits clean
    ok_eng = MDRQEngine(Dataset(rng.random((2, 8_192), dtype=np.float32)),
                        structures=("scan",))
    ok_eng.query_batch(qs, method="scan", trace=True)
    assert obs.audit(ok_eng.last_trace, sel_tolerance=2.0).ok


def test_audit_cell_bucketing():
    def qt(method, est, obs_sel, cost=float("nan")):
        return tracing.QueryTrace(
            index=0, method=method, bucket_size=4, est_selectivity=est,
            est_cost=cost, spec_kind="ids", mq=2, result_size=0,
            obs_selectivity=obs_sel, seconds=1e-4, launches=0.25,
            host_syncs=0.25)
    report = obs.audit(
        [qt("scan", 0.05, 0.05), qt("scan", 0.55, 0.54),
         qt("kdtree", 0.01, 0.3)], sel_tolerance=4.0)
    cells = {(c.method, c.decile): c for c in report.cells}
    assert set(cells) == {("scan", 0), ("scan", 5), ("kdtree", 0)}
    assert not cells[("scan", 0)].drifted
    assert cells[("kdtree", 0)].drifted  # 30x past a 4x tolerance
    # unobservable traces (reduced specs) are counted but never flagged
    rep2 = obs.audit([qt("scan", 0.05, None)])
    assert rep2.n_unobserved == 1 and rep2.ok


def test_calibration_repairs_corrupted_cost_constant(xla_backend):
    """Acceptance: corrupt a machine constant, run traced queries, and show
    ``Planner.calibrate`` on the audit's observations repairs it through the
    existing CalibrationReport plumbing (trace -> audit -> calibrate)."""
    # XLA backend for honest timings (interpret mode runs the grid as a
    # Python loop); the fixture cleared the jit caches, so every shape
    # below traces fresh under it
    rng = np.random.default_rng(5)
    eng = MDRQEngine(Dataset(rng.random((4, 50_000), dtype=np.float32)),
                     structures=("scan",))
    model = eng.planner.model
    true_spb = model.sec_per_byte
    model.sec_per_byte = corrupted = true_spb * 1e6

    # traced production traffic at several batch sizes — bucket amortization
    # varies modeled bytes/query, which is what the lstsq fit needs
    samples = []
    for b, seed in ((4, 0), (16, 1), (64, 2)):
        qs = _queries(4, b, seed=seed)
        eng.query_batch(qs, method="scan", spec=Count())  # warm the shape
        eng.query_batch(qs, method="scan", spec=Count(), trace=True)
        samples += obs.calibration_samples(eng.last_trace, model)
    assert len(samples) == 84 and all(m == "scan" for m, _, _ in samples)

    # the corrupted model mispredicts wall time by ~3 orders of magnitude
    worst = max(corrupted * nb / max(sec, 1e-12) for _, nb, sec in samples)
    assert worst > 50

    report = eng.planner.calibrate(samples)
    assert isinstance(report, type(eng.planner.calibrate([])))
    assert report.n_samples == 84 and report.methods == ("scan",)
    assert report.accepted["sec_per_byte"]
    # repaired: the corrupted constant moved back toward reality
    assert model.sec_per_byte < corrupted / 50
    # and the fit explains the measurements far better than the corruption
    resid = [abs(model.sec_per_byte * nb + model.dispatch_overhead - sec)
             / max(sec, 1e-12) for _, nb, sec in samples]
    assert np.median(resid) < 1.0 < worst


# -- server observability -----------------------------------------------------

def test_server_latency_flush_reasons_and_query_log(engine):
    from repro.serve.mdrq_server import MDRQServer

    srv = MDRQServer(engine, max_batch=4, max_wait_s=10.0, spec=Count())
    qs = _queries(4, 9, seed=6)
    tickets = [srv.submit(q) for q in qs[:8]]   # two size-triggered flushes
    assert srv.stats.flush_reasons == {"size": 2}

    srv.max_wait_s = 1e-4
    srv.submit(qs[8])
    time.sleep(2e-3)
    with obs.Tracer() as tr:
        flushed = srv.poll()                    # idle-stream deadline flush
    assert flushed == 1
    assert srv.stats.flush_reasons == {"size": 2, "deadline": 1}
    # the flush trace event carries the trigger
    ev = tr.find("flush")
    assert len(ev) == 1 and ev[0].attrs["reason"] == "deadline"
    assert ev[0].attrs["n_queries"] == 1

    # registry mirror of the reasons
    reasons = obs.registry().counter_values("mdrq_server_flushes_total",
                                            "reason")
    assert reasons == {"size": 2.0, "deadline": 1.0}

    # per-spec-kind latency percentiles
    lat = srv.stats.latency_percentiles("count")
    for stage in ("queue", "execute"):
        assert set(lat[stage]) == {"p50", "p95", "p99"}
        assert 0 < lat[stage]["p50"] <= lat[stage]["p99"]
    assert srv.stats.latency_percentiles("ids") == {"queue": {},
                                                    "execute": {}}
    # queue latency of the deadline-flushed query reflects its wait
    assert srv.query_log.by_reason("deadline")[0].queue_seconds >= 2e-3

    # the query log saw everything, with methods and reasons per entry
    assert len(srv.query_log) == 9
    assert {e.flush_reason for e in srv.query_log.entries} \
        == {"size", "deadline"}
    assert all(e.method in engine.paths for e in srv.query_log.entries)
    assert all(e.spec_kind == "count" for e in srv.query_log.entries)
    lo, up = srv.query_log.bounds()
    assert lo.shape == (9, 4) and up.shape == (9, 4)
    assert all(t.result() == e.result_size
               for t, e in zip(tickets, srv.query_log.entries))


def test_query_log_reservoir_bound():
    log = obs.QueryLog(capacity=16, seed=1)
    e = obs.QueryLogEntry(lower=np.zeros(2), upper=np.ones(2),
                          spec_kind="ids", method="scan", result_size=0,
                          queue_seconds=0.0, execute_seconds=0.0,
                          flush_reason="size", batch_size=1)
    for _ in range(1000):
        log.offer(e)
    assert len(log) == 16 and log.n_seen == 1000
    with pytest.raises(ValueError):
        obs.QueryLog(capacity=0)


def test_reservoir_is_uniform():
    """Retention frequency of early vs late offers stays ~capacity/n."""
    hits = np.zeros(200)
    for seed in range(40):
        log = obs.QueryLog(capacity=20, seed=seed)
        for i in range(200):
            log.offer(i)  # duck-typed payload: the log never inspects it
        for kept in log.entries:
            hits[kept] += 1
    # expected retention 20/200 = 0.1 per slot per trial -> 4 of 40 trials;
    # first and second halves must not differ wildly
    assert abs(hits[:100].mean() - hits[100:].mean()) < 2.0


# -- tracing overhead (perf knob) ---------------------------------------------

def test_tracing_overhead_under_5pct_at_B128(xla_backend, monkeypatch):
    """Why tracing stays within 5% at B=128: the spans ``query_batch(trace=
    True)`` opens per batch are O(buckets), not O(queries) — the same count
    at Q=16 as at Q=128 on the same paths — so their cost amortizes over
    the batch; and a disabled ``span()`` allocates nothing. (The property,
    not a wall-clock ratio: a ratio on a shared CPU measures the CPU's load.)
    """
    rng = np.random.default_rng(9)
    eng = MDRQEngine(Dataset(rng.random((4, 33_000), dtype=np.float32)),
                     structures=("scan",))

    def n_spans(spans):
        return sum(1 + n_spans(s.children) for s in spans)

    per_q = {}
    for q_n in (16, 128):
        eng.query_batch(_queries(4, q_n, seed=10), trace=True)  # planned
        per_q[q_n] = (sorted(eng.last_batch_stats.method_counts),
                      n_spans(eng.last_trace.spans))
    assert per_q[16] == per_q[128]
    assert per_q[128][1] <= 2 + 3 * len(per_q[128][0])

    # disabled: span() hands back the singleton, builds neither a Span nor
    # a profiler annotation, and leaves no memory behind
    def boom(*a, **kw):
        raise AssertionError("span object built with tracing disabled")
    monkeypatch.setattr(tracing, "Span", boom)
    monkeypatch.setattr(tracing, "_annotation", boom)
    assert not tracing.active()
    span = tracing.span
    assert span("execute", path="scan") is obs.NULL_SPAN

    def loop():
        for _ in range(10_000):
            with span("execute", path="scan", bucket=128) as sp:
                sp.block_on(None)

    loop()
    tracemalloc.start()
    try:
        before = tracemalloc.get_traced_memory()[0]
        loop()
        after, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert after - before <= 0 and peak - before < 1024, (before, after, peak)
    assert eng.query_batch(_queries(4, 128, seed=10), trace=False)
