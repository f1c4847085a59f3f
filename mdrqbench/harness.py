"""One run of one benchmark cell: data, engine, server, warm pass, window, check.

The cell, its configuration and its traffic are read from ``BENCHMARK.json``
and the files it names; per-layer metrics are the modules under ``layers/``
named after them. Nothing here knows a particular cell.

Order of a run: data and queries from the seed (the benchmark's own
generators), the engine and the pipelined server (its AOT warmup), a warm
pass of the cell's own traffic, then the measured window, then the check of
the answers against the numpy reference. Set-up is everything before the
window. The window runs with the profiler off unless ``--trace 1``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path

import numpy as np

from mdrqbench import check as check_mod
from mdrqbench import gen, loads, roofline, specs

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
GRACE_S = 60.0     # how long past the window an answer may still come


class NoChip(RuntimeError):
    """No accelerator, too few chips, or kernels that would not run on it."""


def log(msg: str) -> None:
    print(msg, flush=True)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    traffic: dict
    end_to_end: list     # BENCHMARK.json metric entries this cell reports
    per_layer: list


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_file: Path = ROOT / "BENCHMARK.json") -> Cell:
    bench = json.loads(bench_file.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfgs = {c["name"]: c for c in bench["configs"]}
    cfg = json.loads((ROOT / cfgs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (BENCH_DIR / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _reports(m, name) and m["moves"] in names]
    return Cell(name, int(w["chips"]), cfg, traffic, e2e, per_layer)


def require_chip(chips: int):
    """The first device, if JAX sees ``chips`` TPUs and Mosaic kernels."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    from repro.kernels import ops
    if ops.use_xla() or ops.default_interpret():
        raise NoChip("the kernels would not run under Mosaic "
                     f"(REPRO_KERNEL_BACKEND={os.environ.get('REPRO_KERNEL_BACKEND')!r})")
    return devs[0]


def use_cache_in_checkout() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    holding every program, however fast it compiled."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class LaunchRecorder:
    """Wraps ``engine.launch_batch`` on the instance to see each window's
    plan: which path the planner gave each query, and when.

    The server launches every query it admits once, in the order it was
    submitted, so the n-th query launched is the n-th submitted."""

    def __init__(self, engine):
        self.windows = []     # (t, queries, methods)
        self.n_launched = 0
        inner = engine.launch_batch

        def launch_batch(queries, *args, **kwargs):
            pb = inner(queries, *args, **kwargs)
            self.windows.append((time.perf_counter(), queries, pb.methods))
            self.n_launched += len(queries)
            return pb
        engine.launch_batch = launch_batch

    def between(self, t0: float, t1: float):
        return [w for w in self.windows if t0 <= w[0] <= t1]

    def methods_since(self, first: int, pool: list, pool_idx: list) -> dict:
        """Log index -> path, for the queries submitted after the first
        ``first`` launched ones; checks that the launches are the log's."""
        out, n = {}, 0
        for _, queries, methods in self.windows:
            for q, meth in zip(queries, methods):
                k = n - first
                n += 1
                if k < 0:
                    continue
                if k >= len(pool_idx) or q is not pool[pool_idx[k]]:
                    raise RuntimeError("launched queries do not follow the "
                                       f"order they were submitted in (#{k})")
                out[k] = meth
        return out


def make_pool(cell: Cell, cols: np.ndarray, rng, n: int):
    q = cell.traffic["queries"]
    lower, upper = gen.load(q["generator"]).make(cols, n, rng, q)
    return lower.astype(np.float32), upper.astype(np.float32)


def _load_layer(name: str):
    path = BENCH_DIR / "layers" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"mdrqbench_layer_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Context:
    """What a per-layer reader may read, after the window has closed."""

    cell: Cell
    n_rows: int
    spec_kind: str
    log: loads.Log
    t0: float
    t_end: float
    stats: object           # the server's ServerStats over the window
    counters: dict          # ops.counters() deltas over the window
    plans: list             # LaunchRecorder windows inside the window
    trace: dict | None      # trace.reduce output of the traced window
    peaks: dict

    def in_window(self) -> np.ndarray:
        """Log indices of the queries due in the window."""
        ts = np.asarray(self.log.due)
        return np.flatnonzero((ts >= self.t0) & (ts <= self.t_end))


def _percentile(values: np.ndarray, p: float) -> float:
    return float(np.percentile(values, p)) if values.size else float("nan")


def end_to_end(cell: Cell, ctx: Context, setup_s: float) -> dict:
    """The cell's end-to-end metrics from the host clock."""
    log_ = ctx.log
    ks = ctx.in_window()
    out = {"setup_s": setup_s}
    done = np.array([log_.t_done[k] if log_.t_done[k] is not None else np.inf
                     for k in ks])
    ok = np.array([log_.error[k] is None for k in ks], bool)
    if cell.traffic["loop"] == "closed":
        completed = int(np.sum(ok & (done <= ctx.t_end)))
        out["qps"] = completed / (ctx.t_end - ctx.t0)
    else:
        due = np.array([log_.due[k] for k in ks])
        lat = np.where(ok, done - due, ctx.t_end + GRACE_S - due)
        out["p50_ms"] = _percentile(lat, 50) * 1e3
        out["p95_ms"] = _percentile(lat, 95) * 1e3
    units = {m["name"]: m["unit"] for m in cell.end_to_end}
    return {k: {"value": v, "unit": units[k]} for k, v in out.items()
            if k in units}


def per_layer(cell: Cell, ctx: Context) -> dict:
    out = {}
    for m in cell.per_layer:
        v = _load_layer(m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


@dataclasses.dataclass
class Setup:
    """A cell's data, query pool, engine and warmed server for one seed."""

    cols: np.ndarray
    lower: np.ndarray
    upper: np.ndarray
    pool: list                   # one RangeQuery per pool entry
    gaps: np.ndarray | None      # open loop: the arrival schedule
    engine: object
    srv: object
    rec: LaunchRecorder
    spec: object
    sample_ss: np.random.SeedSequence

    @property
    def pool_n(self) -> int:
        return len(self.pool)

    def make_query(self, i: int):
        return self.pool[i]


def build(cell: Cell, seed: int, seconds: float, fault=None) -> Setup:
    """Data and queries from the seed, the engine, the warmed-up server.

    ``fault`` (tests only) is called with the engine to break the timed
    path underneath."""
    from repro.core import Dataset, MDRQEngine, RangeQuery
    from repro.serve.pipeline import serve_pipelined

    cfg, traffic = cell.cfg, cell.traffic
    data_ss, query_ss, sched_ss, sample_ss = np.random.SeedSequence(
        seed).spawn(4)
    t = time.perf_counter()
    cols = gen.load(cfg["generator"]).build(cfg, np.random.default_rng(data_ss))
    log(f"setup: {cfg['name']} data {cols.shape[0]} x {cols.shape[1]} in "
        f"{time.perf_counter() - t:.1f} s")
    gaps = None
    if traffic["loop"] == "open":
        n_arrivals = max(1, int(round(traffic["rate_qps"] * seconds)))
        gaps = loads.poisson_gaps(n_arrivals, traffic["rate_qps"],
                                  np.random.default_rng(sched_ss))
        pool_n = n_arrivals
    else:
        pool_n = int(traffic["pool"])
    lower, upper = make_pool(cell, cols, np.random.default_rng(query_ss),
                             pool_n)
    pool = [RangeQuery(lo, up) for lo, up in zip(lower, upper)]
    t = time.perf_counter()
    engine = MDRQEngine(Dataset(cols), structures=tuple(cfg["structures"]),
                        tile_n=int(cfg["tile_n"]))
    log(f"setup: engine {tuple(cfg['structures'])} built in "
        f"{time.perf_counter() - t:.1f} s")
    if fault is not None:
        fault(engine)
    rec = LaunchRecorder(engine)
    server = traffic["server"]
    spec = specs.load(traffic["spec"]["kind"]).make()
    # The server's own AOT warmup compiles every pow2 bucket of every path
    # at the widest bounds, visit lists of up to 2**21 (query, block) pairs
    # that no cell's traffic makes; the warm pass compiles what it does use.
    srv = serve_pipelined(engine, max_batch=int(server["max_batch"]),
                          max_wait_s=float(server["max_wait_s"]),
                          backlog=int(server["backlog"]), method="auto",
                          spec=spec, latency_budget_s=math.inf, warmup=False)
    return Setup(cols, lower, upper, pool, gaps, engine, srv, rec, spec,
                 sample_ss)


def warm(cell: Cell, s: Setup) -> None:
    """A pass of the cell's own traffic over the whole pool, so that the
    data-dependent visit buckets compile here and not in the window."""
    from repro.kernels import ops
    t = time.perf_counter()
    ops.reset_trace_log()
    wait_s = float(cell.traffic["server"]["max_wait_s"])
    drv = loads.Driver(s.srv, s.make_query, s.pool_n)
    traffic = cell.traffic
    for clients in traffic.get("warm_clients", [traffic.get("clients", 1)]):
        loads.run_closed(drv, int(clients), math.inf, wait_s,
                         max_submits=s.pool_n)
        drv.finish(GRACE_S)
    s.srv.drain()
    log(f"setup: warm pass {len(drv.log)} queries in "
        f"{time.perf_counter() - t:.1f} s, {len(ops.trace_log())} traces")


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, dev=None, fault=None) -> dict:
    """One run; returns the result line's object."""
    import jax
    from repro.kernels import ops

    s = build(cell, seed, seconds, fault=fault)
    srv, traffic = s.srv, cell.traffic
    wait_s = float(traffic["server"]["max_wait_s"])
    try:
        warm(cell, s)
        srv.reset_stats()
        ops.reset_trace_log()
        c0 = ops.counters()
        first = s.rec.n_launched
        # Nothing made so far is garbage the window should pay to scan for.
        gc.collect()
        gc.freeze()
        setup_s = time.perf_counter() - t_start

        span = loads.no_span
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0   # keeps bench.* annotations only
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
            span = jax.profiler.TraceAnnotation
        drv = loads.Driver(srv, s.make_query, s.pool_n, span=span)
        try:
            with span("bench.window"):
                if s.gaps is not None:
                    t0, t_end = loads.run_open(drv, s.gaps, wait_s, GRACE_S)
                else:
                    t0, t_end = loads.run_closed(
                        drv, int(traffic["clients"]), seconds, wait_s)
            window_traces = ops.trace_log()
            drv.finish(GRACE_S)
        finally:
            if trace:
                jax.profiler.stop_trace()
        srv.drain()
        stats = srv.stats
        c1 = ops.counters()
    finally:
        srv.close()
    log(f"window: {len(window_traces)} traces or compiles inside "
        f"({sorted(set(window_traces))})")
    log(f"window: paths {stats.method_counts}, flushes {stats.flush_reasons}, "
        f"windows {stats.n_batches}, queries {stats.n_queries}")
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0) if dev else 0
    plans = s.rec.between(t0, t_end)
    method_of = s.rec.methods_since(first, s.pool, drv.log.pool_idx)
    cols, lower, upper, spec = s.cols, s.lower, s.upper, s.spec
    sample_ss = s.sample_ss
    del s, srv
    gc.unfreeze()
    gc.collect()

    counters = {k: c1.get(k, 0) - c0.get(k, 0) for k in c1}
    trace_red = None
    if trace:
        from mdrqbench.trace import reduce as trace_reduce
        trace_red = trace_reduce.reduce_dir(TRACE_DIR)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    kind = dev.device_kind if dev is not None else None
    ctx = Context(cell=cell, n_rows=cols.shape[1], spec_kind=spec.kind,
                  log=drv.log, t0=t0, t_end=t_end, stats=stats,
                  counters=counters, plans=plans, trace=trace_red,
                  peaks=roofline.peaks(kind) if kind else {})
    ks = ctx.in_window()
    failed = sum(1 for k in ks if drv.log.error[k] is not None)
    t = time.perf_counter()
    checked = check_mod.check(
        cols, lower, upper, drv.log, ks, method_of, spec.kind,
        traffic["check"], np.random.default_rng(sample_ss))
    log(f"check: {checked['n_checked']} answers to {checked['n_queries']} "
        f"queries against the reference in "
        f"{time.perf_counter() - t:.1f} s, by path {checked['by_path']}")
    limits = checked["limits"]
    limits["failed_queries"] = {"value": failed, "limit": 0}
    metrics = per_layer(cell, ctx) if trace else end_to_end(cell, ctx,
                                                             setup_s)
    out = {
        "correct": all(v["value"] <= v["limit"] for v in limits.values()),
        "attempted": int(ks.size),
        "failed": int(failed),
        "metrics": metrics,
        "device": {"platform": dev.platform if dev else "none",
                   "kind": kind, "count": jax.device_count(),
                   "memory_peak_bytes": int(mem)},
    }
    if trace_red is not None:
        out["device"]["busy_s"] = trace_red["busy_s"]
        out["device"]["window_s"] = trace_red["window_s"]
        out["breakdown"] = {"device_ops": trace_red["device_ops"],
                            "idle_gaps": trace_red["idle_gaps"]}
    out["limits"] = limits
    return out


def main(argv=None) -> int:
    import argparse
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    cell = load_cell(args.workload)
    try:
        dev = require_chip(cell.chips)
        roofline.peaks(dev.device_kind)
    except (NoChip, roofline.UnknownDevice) as e:
        print(f"mdrqbench: {e}", file=sys.stderr)
        return 2
    use_cache_in_checkout()
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), t_start,
                   dev=dev)
    for name, v in out["limits"].items():
        print(f"check {name} {v['value']} limit {v['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0
