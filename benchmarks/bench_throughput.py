"""Batched-execution throughput: queries/sec over GMRQB template mixes.

Sweeps the serving batch size over {1, 8, 32, 128} with the fused multi-query
kernels underneath (``MDRQEngine.query_batch`` via ``MDRQServer``) — the
inter-query analogue of the paper's intra-query scaling figures. Batch 1 is
the seed engine's per-query regime, so the B{128}/B{1} speedup row is the
amortization headline. On a CPU run it with ``REPRO_KERNEL_BACKEND=xla``
(the Makefile targets do; see common.py); real kernel numbers are TPU.

Result shapes ride the ResultSpec layer: every row carries a ``result_spec``
column, ``--spec {ids,count,mask,topk,agg}`` selects the shape for the mixed
sweep, and ``run_specs`` (the ``--spec topk`` / ``--spec agg`` CI smoke rows)
compares reduced shapes against ids at the largest batch — the reduced
payload (O(k)/O(1) bytes over the device->host boundary instead of a mask)
is the row-to-row delta. ``run_count`` keeps the PR 2 count-only sweep.
"""
import os
import sys
import time

if __name__ == "__main__":
    if "--devices" in sys.argv:
        # the device count locks at first XLA init, so the CPU proxy for the
        # cross-device sweep must be forced before anything imports jax
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np

from benchmarks.common import emit_row, write_bench_json
from repro.core import Agg, Count, Ids, Mask, MDRQEngine, TopK
from repro.data import gmrqb
from repro.serve.mdrq_server import MDRQServer

BATCH_SIZES = (1, 8, 32, 128)

# The --spec vocabulary: one representative instance per registered kind
# (GMRQB dim 0 = the age attribute for top-k/aggregates).
SPEC_CHOICES = {
    "ids": Ids(),
    "count": Count(),
    "mask": Mask(),
    "topk": TopK(k=10, dim=0),
    "agg": Agg("sum", 0),
}


def _throughput(eng, queries, batch: int, method: str = "auto",
                spec=Ids()):
    """(qps, whole-workload ServerStats) through a fresh serving window."""
    server = MDRQServer(eng, max_batch=batch, max_wait_s=float("inf"),
                        method=method, spec=spec)
    server.serve_all(queries[: 2 * batch])  # warmup (jit + retrace buckets)
    server.stats = type(server.stats)()
    server.serve_all(queries)
    return server.stats.qps, server.stats


def _plan_us(stats) -> float:
    """Planning microseconds per query (BatchStats.plan_seconds, aggregated
    by the server) — isolates the vectorized fixpoint planner's cost from
    kernel time in every throughput row."""
    return 1e6 * stats.plan_seconds / max(stats.n_queries, 1)


def _workload(quick: bool, smoke: bool = False):
    if smoke:
        n, n_queries = 20_000, 32
    else:
        n, n_queries = (200_000, 128) if quick else (1_000_000, 256)
    ds = gmrqb.build(n, seed=0)
    eng = MDRQEngine(ds, structures=("scan", "kdtree", "vafile"))
    mixed = [q for _, q in gmrqb.mixed_workload(ds, n_queries, seed=2)]
    return eng, mixed, n_queries


def run(quick: bool = True, spec=Ids()) -> None:
    eng, mixed, n_queries = _workload(quick)
    kind = spec.kind

    # Mixed workload (all 8 templates interleaved) across batch sizes.
    base = None
    for b in BATCH_SIZES:
        r, stats = _throughput(eng, mixed, b, spec=spec)
        base = base or r
        emit_row(f"throughput/mixed/B{b}", 1e6 / r,
                 f"qps={r:.1f};speedup_vs_B1={r / base:.2f}x;"
                 f"plan_us_per_q={_plan_us(stats):.1f}", result_spec=kind)

    # Per-template mixes at the largest batch: which access path carries the
    # throughput for each selectivity band.
    rng = np.random.default_rng(3)
    for k in (1, 4, 8):
        queries = [gmrqb.template(k, rng, eng.dataset) for _ in range(n_queries)]
        r, stats = _throughput(eng, queries, BATCH_SIZES[-1], spec=spec)
        emit_row(f"throughput/T{k}/B{BATCH_SIZES[-1]}", 1e6 / r,
                 f"qps={r:.1f};buckets={'+'.join(sorted(stats.method_counts))};"
                 f"plan_us_per_q={_plan_us(stats):.1f}", result_spec=kind)

    # Fixed-method sweep: isolates the fused-kernel win from planner choices.
    for meth in ("scan", "scan_vertical"):
        r1, _ = _throughput(eng, mixed, 1, method=meth, spec=spec)
        rb, _ = _throughput(eng, mixed, BATCH_SIZES[-1], method=meth,
                            spec=spec)
        emit_row(f"throughput/{meth}/B{BATCH_SIZES[-1]}", 1e6 / rb,
                 f"qps={rb:.1f};speedup_vs_B1={rb / r1:.2f}x",
                 result_spec=kind)


def run_count(quick: bool = True) -> None:
    """Count-only result mode sweep (``--spec count`` / ``make bench-count``)."""
    eng, mixed, _ = _workload(quick)

    base = None
    for b in BATCH_SIZES:
        r, _ = _throughput(eng, mixed, b, spec=Count())
        base = base or r
        emit_row(f"throughput/count/mixed/B{b}", 1e6 / r,
                 f"qps={r:.1f};speedup_vs_B1={r / base:.2f}x",
                 result_spec="count")

    # Count-vs-ids at the largest batch: the id-materialization tax, per path.
    for meth in ("scan", "vafile"):
        r_ids, _ = _throughput(eng, mixed, BATCH_SIZES[-1], method=meth)
        r_cnt, _ = _throughput(eng, mixed, BATCH_SIZES[-1], method=meth,
                               spec=Count())
        emit_row(f"throughput/count/{meth}/B{BATCH_SIZES[-1]}", 1e6 / r_cnt,
                 f"qps={r_cnt:.1f};count_vs_ids={r_cnt / r_ids:.2f}x",
                 result_spec="count")


def run_specs(quick: bool = True, smoke: bool = False,
              kinds=("topk", "agg")) -> None:
    """Reduced-result-shape sweep: one row per spec kind at the largest
    batch, with the spec/ids qps ratio isolating the result-materialization
    tax the on-device reducers remove. ``smoke=True`` runs CI-sized inputs
    so a reducer performance regression surfaces in CI logs (`make
    bench-specs-smoke`)."""
    eng, mixed, _ = _workload(quick, smoke=smoke)
    batch = 32 if smoke else BATCH_SIZES[-1]
    r_ids, _ = _throughput(eng, mixed, batch)
    emit_row(f"throughput/spec/B{batch}", 1e6 / r_ids, f"qps={r_ids:.1f}",
             result_spec="ids")
    for kind in kinds:
        spec = SPEC_CHOICES[kind]
        r, stats = _throughput(eng, mixed, batch, spec=spec)
        emit_row(f"throughput/spec/B{batch}", 1e6 / r,
                 f"qps={r:.1f};vs_ids={r / r_ids:.2f}x;"
                 f"buckets={'+'.join(sorted(stats.method_counts))}",
                 result_spec=kind)


def run_smoke(json_path: str = "BENCH_smoke.json", spec=Ids()) -> None:
    """The CI smoke artifact: per-batch-size qps + p50/p95/p99 queue and
    execute latency over the mixed workload at CI-sized inputs, written to
    ``json_path`` (``make bench-smoke`` -> ``BENCH_smoke.json``).

    ``benchmarks.check_bench`` diffs a fresh run of this against the
    checked-in baseline with a +-30% qps guard band (warn-only), so a
    serving-path throughput regression surfaces in CI logs without making a
    noisy shared runner fail the build.
    """
    eng, mixed, n_queries = _workload(quick=True, smoke=True)
    kind = spec.kind
    batches = []
    for b in BATCH_SIZES:
        server = MDRQServer(eng, max_batch=b, max_wait_s=float("inf"),
                            method="auto", spec=spec)
        server.serve_all(mixed[: 2 * b])  # warmup (jit + retrace buckets)
        server.stats = type(server.stats)()
        server.serve_all(mixed)
        stats = server.stats
        lat = stats.latency_percentiles(kind)
        emit_row(f"smoke/B{b}", 1e6 / stats.qps,
                 f"qps={stats.qps:.1f};"
                 f"p50_exec_us={1e6 * lat['execute'].get('p50', 0):.1f};"
                 f"p99_exec_us={1e6 * lat['execute'].get('p99', 0):.1f}",
                 result_spec=kind)
        batches.append({
            "batch": b,
            "qps": round(stats.qps, 2),
            "mean_batch_size": round(stats.mean_batch_size, 2),
            "plan_us_per_q": round(_plan_us(stats), 2),
            "method_counts": stats.method_counts,
            "flush_reasons": stats.flush_reasons,
            "latency_seconds": lat,
        })
    write_bench_json(
        json_path, "smoke",
        backend=os.environ.get("REPRO_KERNEL_BACKEND", "auto"),
        n=eng.dataset.n, n_queries=n_queries, spec=kind, batches=batches)


def run_ingest(quick: bool = True, smoke: bool = False) -> None:
    """Serve-while-ingest sweep: qps vs delta fraction (``make bench-ingest``).

    Grows the delta segment to {0, 0.5, 1, 2, 5}% of the base dataset (with
    ~10% of each appended slab immediately tombstoned — writes in both
    directions), re-measuring mixed-workload Count qps at the largest batch
    after each step. The ``vs_delta0`` column is the serving tax of the
    un-compacted write path: every batch pays one extra delta-block scan
    inside the same fused launch, so the tax should track the delta's byte
    fraction, not a per-query launch penalty. A final compaction row
    (fresh structures, empty delta) closes the loop — qps recovers to the
    frozen-path rate and the row carries the compact() wall time.

    The ingest ops go through ``MDRQServer.append``/``delete``/``compact``
    so each step also exercises the window-flush interleaving that serving
    traffic sees (flush_reason="ingest").
    """
    eng, mixed, _ = _workload(quick, smoke=smoke)
    batch = 32 if smoke else BATCH_SIZES[-1]
    rng = np.random.default_rng(7)
    n = eng.dataset.n
    ingest = MDRQServer(eng, max_batch=batch, max_wait_s=float("inf"),
                        spec=Count())

    base_qps = None
    for frac in (0.0, 0.005, 0.01, 0.02, 0.05):
        target = int(round(frac * n))
        grow = target - eng.delta.d
        if grow > 0:
            new_ids = ingest.append(
                rng.random((grow, eng.dataset.m)).astype(np.float32))
            if grow >= 10:
                ingest.delete(new_ids[:: 10])
        r, stats = _throughput(eng, mixed, batch, spec=Count())
        base_qps = base_qps or r
        emit_row(f"throughput/ingest/delta{100 * frac:g}pct/B{batch}",
                 1e6 / r,
                 f"qps={r:.1f};vs_delta0={r / base_qps:.2f}x;"
                 f"delta_rows={eng.delta.d};"
                 f"plan_us_per_q={_plan_us(stats):.1f}",
                 result_spec="count")

    t0 = time.perf_counter()
    ingest.compact()
    compact_s = time.perf_counter() - t0
    r, _ = _throughput(eng, mixed, batch, spec=Count())
    emit_row(f"throughput/ingest/compacted/B{batch}", 1e6 / r,
             f"qps={r:.1f};vs_delta0={r / base_qps:.2f}x;"
             f"compact_s={compact_s:.3f};n={eng.dataset.n}",
             result_spec="count")


def _offered_load_pass(srv, queries, offered_qps: float) -> tuple[float, int]:
    """Open-loop driver: Poisson-free fixed-rate arrivals at ``offered_qps``.

    Submits each query at its scheduled arrival instant (polling the server's
    deadline flush while waiting — the real admission-loop shape), then
    drains. Returns (wall seconds, queries shed at admission). Unlike the
    closed-loop ``serve_all``, a saturated server here keeps receiving
    arrivals it cannot absorb — exactly the regime admission control exists
    for."""
    interval = 1.0 / offered_qps
    t0 = time.perf_counter()
    n_shed = 0
    for i, q in enumerate(queries):
        target = t0 + i * interval
        while True:
            now = time.perf_counter()
            if now >= target:
                break
            srv.poll()
            time.sleep(min(target - now, 2e-4))
        if getattr(srv.submit(q), "shed", False):
            n_shed += 1
    srv.drain()
    return time.perf_counter() - t0, n_shed


# Offered load as a fraction of the measured closed-loop pipelined qps —
# machine-independent keys, so check_bench can diff points across runs whose
# absolute qps differ.
OFFERED_FRACS = (0.25, 0.5, 0.75, 1.0, 1.25, 1.5)


def run_pipeline(quick: bool = True, smoke: bool = False,
                 json_path: str = "BENCH_pipeline.json") -> None:
    """Pipelined-serving bench (``--offered-load`` / ``make bench-pipeline-smoke``).

    Two sections, written to ``json_path``:

      * head-to-head: closed-loop qps of the synchronous ``MDRQServer`` vs
        the AOT-warmed ``PipelinedMDRQServer`` at the largest batch — the
        double-buffering win (device stage overlapping host finalize);
      * offered-load sweep: fixed-rate arrivals at fractions of the
        pipelined closed-loop qps, recording achieved qps, shed fraction,
        and p99 queue/execute latency per point. The *saturation knee* is
        the highest offered load the server absorbs (achieved >= 90% of
        offered, sheds < 1%); past it, admission control sheds instead of
        letting queue latency diverge.
    """
    from repro.kernels import ops
    from repro.serve import serve_pipelined

    eng, mixed, n_queries = _workload(quick, smoke=smoke)
    batch = 32 if smoke else BATCH_SIZES[-1]

    sync_qps, _ = _throughput(eng, mixed, batch)
    emit_row(f"pipeline/sync/B{batch}", 1e6 / sync_qps, f"qps={sync_qps:.1f}")

    with serve_pipelined(eng, max_batch=batch, max_wait_s=float("inf"),
                         warmup=True, latency_budget_s=1e9) as srv:
        wrep = srv.last_warmup
        srv.serve_all(mixed[: 2 * batch])   # post-warmup dry pass
        srv.drain()
        srv.reset_stats()
        srv.serve_all(mixed)
        srv.drain()
        pipe_qps = srv.stats.qps
    emit_row(f"pipeline/pipelined/B{batch}", 1e6 / pipe_qps,
             f"qps={pipe_qps:.1f};vs_sync={pipe_qps / sync_qps:.2f}x;"
             f"aot_compiled={wrep.n_compiled};"
             f"warmup_s={wrep.seconds:.2f}")

    # Offered-load sweep on a server with a *real* latency budget (~8
    # windows of drain time) so saturation sheds instead of queueing.
    budget = max(0.05, 8 * batch / pipe_qps)
    points, knee = [], 0.0
    with serve_pipelined(eng, max_batch=batch, max_wait_s=5e-3,
                         warmup=True, backlog=4,
                         latency_budget_s=budget) as srv:
        for frac in OFFERED_FRACS:
            offered = frac * pipe_qps
            srv.reset_stats()
            wall, n_shed = _offered_load_pass(srv, mixed, offered)
            st = srv.stats
            achieved = st.n_queries / wall
            shed_frac = n_shed / len(mixed)
            lat = st.latency_percentiles("ids")
            p99q = lat["queue"].get("p99", 0.0) if lat["queue"] else 0.0
            p99x = lat["execute"].get("p99", 0.0) if lat["execute"] else 0.0
            if shed_frac < 0.01 and achieved >= 0.9 * offered:
                knee = max(knee, offered)
            points.append({
                "frac": frac,
                "offered_qps": round(offered, 2),
                "achieved_qps": round(achieved, 2),
                "shed_frac": round(shed_frac, 4),
                "p99_queue_s": round(p99q, 6),
                "p99_execute_s": round(p99x, 6),
            })
            emit_row(f"pipeline/offered{frac:g}x/B{batch}", 1e6 / achieved,
                     f"qps={achieved:.1f};offered={offered:.1f};"
                     f"shed={100 * shed_frac:.1f}%;"
                     f"p99_queue_us={1e6 * p99q:.0f}")

    write_bench_json(
        json_path, "pipeline",
        backend=os.environ.get("REPRO_KERNEL_BACKEND", "auto"),
        n=eng.dataset.n, n_queries=n_queries, batch=batch,
        head_to_head={"sync_qps": round(sync_qps, 2),
                      "pipelined_qps": round(pipe_qps, 2),
                      "speedup": round(pipe_qps / sync_qps, 3)},
        warmup={"n_runs": wrep.n_runs, "n_compiled": wrep.n_compiled,
                "seconds": round(wrep.seconds, 3),
                "aot_hits": ops.aot_counters().get("hit", 0)},
        latency_budget_s=round(budget, 4),
        knee_qps=round(knee, 2),
        offered=points)


def run_devices(quick: bool = True) -> None:
    """Cross-device batched-scan sweep (``--devices`` / ``make bench-dist``).

    Shards the dataset over 1/2/4/8-device meshes and drives the fixed
    ``scan`` path through ``DistributedScan`` at the largest batch, in both
    result modes. On CPU the devices are ``xla_force_host_platform_device_
    count`` shards of one socket — the honest proxy for *launch structure*
    (one collective per batch), not for bandwidth scaling, which needs a real
    TPU mesh (every CPU "device" shares the same memory bus).
    """
    import jax

    from repro.core.distributed import make_data_mesh

    avail = len(jax.devices())
    if avail < 2:
        print("# run_devices: single-device process; run via "
              "`make bench-dist` (or --devices) for the 8-device CPU proxy",
              flush=True)
    n = 200_000 if quick else 1_000_000
    ds = gmrqb.build(n, seed=0)
    queries = [q for _, q in gmrqb.mixed_workload(ds, 128, seed=2)]
    batch = BATCH_SIZES[-1]
    base: dict = {}
    for d in (1, 2, 4, 8):
        if d > avail:
            continue
        # one engine (one pad + shard placement) per mesh size, both modes
        eng = MDRQEngine(ds, structures=("scan",), mesh=make_data_mesh(d))
        for spec in (Ids(), Count()):
            r, _ = _throughput(eng, queries, batch, method="scan", spec=spec)
            base.setdefault(spec.kind, r)
            emit_row(f"throughput/dist/{spec.kind}/D{d}/B{batch}", 1e6 / r,
                     f"qps={r:.1f};speedup_vs_D1={r / base[spec.kind]:.2f}x",
                     result_spec=spec.kind)


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale sizes")
    ap.add_argument("--spec", choices=tuple(SPEC_CHOICES), default="ids",
                    help="result spec to sweep (reduced kinds run the "
                         "spec-vs-ids comparison section)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized inputs (tiny n, one spec row) — the "
                         "reducer-regression smoke")
    ap.add_argument("--ingest", action="store_true",
                    help="serve-while-ingest sweep: qps vs delta fraction, "
                         "plus the post-compaction recovery row")
    ap.add_argument("--offered-load", action="store_true",
                    help="pipelined serving bench: sync-vs-pipelined "
                         "head-to-head plus the qps-vs-offered-load sweep "
                         "(saturation knee, p99 under load, shed fraction) "
                         "-> BENCH_pipeline.json")
    ap.add_argument("--devices", action="store_true",
                    help="cross-device batched scan sweep (forces an "
                         "8-device CPU platform when XLA_FLAGS is unset)")
    ap.add_argument("--json", default="",
                    help="with --spec ids --smoke: write the per-batch-size "
                         "qps/latency artifact here (BENCH_smoke.json)")
    args = ap.parse_args()
    from benchmarks.common import CSV_HEADER
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    print(CSV_HEADER, flush=True)
    if args.offered_load:
        run_pipeline(quick=not args.full, smoke=args.smoke,
                     json_path=args.json or "BENCH_pipeline.json")
    elif args.devices:
        run_devices(quick=not args.full)
    elif args.ingest:
        run_ingest(quick=not args.full, smoke=args.smoke)
    elif args.spec == "count":
        run_count(quick=not args.full)
    elif args.spec in ("topk", "agg", "mask"):
        run_specs(quick=not args.full, smoke=args.smoke, kinds=(args.spec,))
    elif args.smoke:
        run_smoke(json_path=args.json or "BENCH_smoke.json")
    else:
        run(quick=not args.full)
