"""On-chip smoke test: the MDRQ engine's main path at GMRQB's published scale.

  python chip_smoke.py             # one TPU chip: every path, spec and server
  python chip_smoke.py --chips 4   # only the sharded scan over a 4-chip mesh

Data is GMRQB at the paper's scale (``gmrqb.build(10_000_000, seed=0)``:
10M records x 19 float32 attributes, 760 MB of base data on the device) and
the queries are one seeded 32-query mixed-workload batch holding all eight
templates. Every result is checked against the numpy oracle
(``core.match_ids_np``) computed on the host from the same data:

  * ids, counts and minima must be exact;
  * a sum may differ from the float64 oracle by f32 summation order only
    (``SUM_RTOL``);
  * top-k values must equal the oracle's k extremes, at positions that match.

One chip: ``query_batch`` for each method x spec, a few ``engine.query``
calls, the synchronous ``MDRQServer``, the pipelined server (AOT warmup, then
traffic that must not retrace), and one live-ingest round (append + delete,
counts over base + delta - tombstones, then ``compact``). ``--chips 4``
builds the meshed engine (``MDRQEngine(mesh=make_data_mesh(4))``) and runs
the batch through the sharded scan under ``Ids`` and ``Count``.

Lines before the last are progress and set-up facts (seconds, compile
counts, peak device bytes), not metrics. The last line of stdout is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU, or with the kernels forced off Mosaic, or on any mismatch or
error, the script exits nonzero and prints no such line.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

N_ROWS = 10_000_000
N_QUERIES = 32
SEED = 0
METHODS = ("scan", "scan_vertical", "kdtree", "rstar", "vafile", "auto")
TOPK_K, TOPK_DIM = 10, 2       # quality: continuous, ties are rare
AGG_DIM = 3                    # depth: positive, so a relative bound holds
# Device sums run ~n/tile_n sequential f32 adds per lane (<= 10^4 at 10M
# rows, tile_n=1024) plus a lane tree; for nonnegative values the f32
# reordering error is then below 1e4 * 2^-24 ~ 6e-4 of the sum.
SUM_RTOL = 1e-3


class SmokeFailure(RuntimeError):
    """A result disagreed with the oracle, or a run fact was wrong."""


def check(ok, what) -> None:
    """Raise ``SmokeFailure(what)`` unless ``ok`` (unlike ``assert``, this
    holds under ``python -O``)."""
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


@contextlib.contextmanager
def phase(name: str):
    """Name a phase in the output; failures propagate (nonzero exit)."""
    t0 = time.perf_counter()
    log(f"phase {name}: start")
    yield
    log(f"phase {name}: pass ({time.perf_counter() - t0:.1f} s)")


def require_tpu():
    """The chip and the Mosaic kernels, or a nonzero exit."""
    import jax
    from repro.kernels import ops
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (jax.devices()[0].platform="
                 f"{dev.platform!r}); refusing to run on another backend")
    if ops.default_interpret() or ops.use_xla():
        backend = os.environ.get("REPRO_KERNEL_BACKEND")
        sys.exit("chip_smoke: kernels would not run as Mosaic "
                 f"(REPRO_KERNEL_BACKEND={backend!r})")
    return dev


class Oracle:
    """Host numpy answers for one query batch over (m, n) columns."""

    def __init__(self, cols: np.ndarray, queries: list):
        from repro.core import match_ids_np
        self.cols = cols
        self.ids = [match_ids_np(cols, q) for q in queries]

    def check(self, spec, got: list, where: str) -> None:
        check(len(got) == len(self.ids), (where, len(got)))
        for k, (ids, res) in enumerate(zip(self.ids, got)):
            tag = f"{where} query {k}"
            kind = spec.kind
            if kind == "ids":
                res = np.asarray(res)
                check(np.array_equal(res, ids),
                      f"{tag}: {res.size} ids vs oracle {ids.size}")
            elif kind == "count":
                check(res == ids.size, f"{tag}: count {res} vs {ids.size}")
            elif kind == "topk":
                self._check_topk(spec, ids, np.asarray(res), tag)
            elif kind == "agg":
                self._check_agg(spec, ids, res, tag)
            else:
                raise SmokeFailure(f"{tag}: unchecked spec {kind}")

    def _check_topk(self, spec, ids, res, tag) -> None:
        want_n = min(spec.k, ids.size)
        check(res.size == want_n, f"{tag}: {res.size} top-k ids vs {want_n}")
        check(np.isin(res, ids).all(), f"{tag}: top-k id outside the matches")
        vals = self.cols[spec.dim, ids]
        want = np.sort(vals)[::-1][:want_n] if spec.largest \
            else np.sort(vals)[:want_n]
        got = self.cols[spec.dim, res]
        check(np.array_equal(np.sort(got), np.sort(want)),
              f"{tag}: top-k values {got} vs {want}")

    def _check_agg(self, spec, ids, res, tag) -> None:
        vals = self.cols[spec.dim, ids]
        if spec.op == "sum":
            want = float(np.sum(vals, dtype=np.float64))
            check(abs(res - want) <= SUM_RTOL * abs(want),
                  f"{tag}: sum {res} vs {want} (rtol {SUM_RTOL})")
        elif ids.size == 0:
            check(np.isnan(res), f"{tag}: {spec.op} of no matches is {res}")
        else:
            want = float(vals.min() if spec.op == "min" else vals.max())
            check(res == want, f"{tag}: {spec.op} {res} vs {want}")


def build_workload(n_rows: int):
    """GMRQB data + the seeded 32-query batch (all eight templates)."""
    from repro.data import gmrqb
    t0 = time.perf_counter()
    ds = gmrqb.build(n_rows, seed=SEED)
    wl = gmrqb.mixed_workload(ds, N_QUERIES, seed=SEED)
    templates = sorted({k for k, _ in wl})
    check(templates == list(range(1, 9)),
          f"batch misses templates: has {templates}; raise N_QUERIES")
    queries = [q for _, q in wl]
    log(f"setup: GMRQB {ds.m} x {ds.n} built in "
        f"{time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    oracle = Oracle(ds.cols, queries)
    log(f"setup: host oracle in {time.perf_counter() - t0:.1f} s; "
        f"matches per query {[i.size for i in oracle.ids]}")
    return ds, queries, oracle


def result_specs():
    from repro.core import Agg, Count, Ids, TopK
    return (Ids(), Count(), TopK(k=TOPK_K, dim=TOPK_DIM),
            Agg("sum", dim=AGG_DIM), Agg("min", dim=AGG_DIM))


def run_one_chip(n_rows: int) -> None:
    from repro.core import Count, Ids, MDRQEngine, match_ids_np
    from repro.kernels import ops
    from repro.serve.mdrq_server import MDRQServer
    from repro.serve.pipeline import serve_pipelined

    ds, queries, oracle = build_workload(n_rows)
    t0 = time.perf_counter()
    eng = MDRQEngine(ds, structures=("scan", "kdtree", "rstar", "vafile"))
    log(f"setup: engine (scan, kdtree, rstar, vafile) built in "
        f"{time.perf_counter() - t0:.1f} s")

    with phase("query_batch methods x specs"):
        for method in METHODS:
            t0 = time.perf_counter()
            for spec in result_specs():
                got = eng.query_batch(queries, method=method, spec=spec)
                oracle.check(spec, got, f"{method}/{spec}")
            log(f"  {method}: all specs match ({time.perf_counter() - t0:.1f}"
                f" s with compiles and checks); last plan "
                f"{eng.last_batch_stats.method_counts}")

    with phase("engine.query"):
        for k in (0, 3, 31):
            for method in METHODS:
                ids = eng.query(queries[k], method=method)
                check(np.array_equal(ids, oracle.ids[k]), (method, k))
                cnt = eng.query(queries[k], method=method, spec=Count())
                check(cnt == oracle.ids[k].size, (method, k, cnt))

    with phase("MDRQServer"):
        srv = MDRQServer(eng, max_batch=16, method="auto", spec=Ids())
        tickets = [srv.submit(q) for q in queries]
        srv.flush()
        oracle.check(Ids(), [t.result() for t in tickets], "MDRQServer")
        log(f"  windows {srv.stats.n_batches}, "
            f"paths {srv.stats.method_counts}")

    with phase("serve_pipelined"):
        with serve_pipelined(eng, max_batch=N_QUERIES, method="scan",
                             spec=Count()) as srv:
            rep = srv.last_warmup
            log(f"setup: warmup compiled {rep.n_compiled} AOT executables "
                f"for buckets {rep.bucket_sizes} in {rep.seconds:.1f} s")
            ops.reset_trace_log()
            tickets = [srv.submit(q) for q in queries + queries[:13]]
            srv.drain()
            got = [t.result() for t in tickets]
            retraced = ops.trace_log()
        # a full window (size flush) and a partial one (drain flush)
        oracle.check(Count(), got[:N_QUERIES], "serve_pipelined")
        check(got[N_QUERIES:] == [i.size for i in oracle.ids[:13]],
              f"serve_pipelined partial window: {got[N_QUERIES:]}")
        check(retraced == (), f"traffic retraced after warmup: {retraced}")

    with phase("live ingest + compact"):
        rng = np.random.default_rng(SEED + 1)
        new_cols = build_rows(4096)
        new_ids = eng.append(new_cols.T)
        check(np.array_equal(new_ids, np.arange(ds.n, ds.n + 4096)),
              f"append assigned ids {new_ids[:3]}...")
        dead = np.unique(np.concatenate(
            [rng.choice(ds.n, 2048, replace=False)]
            + [ids[:64] for ids in oracle.ids]))
        check(eng.delete(dead) == dead.size, "delete missed live rows")
        want = [np.setdiff1d(ids, dead).size + match_ids_np(new_cols, q).size
                for ids, q in zip(oracle.ids, queries)]
        for method in METHODS:
            got = eng.query_batch(queries, method=method, spec=Count())
            check(got == want, (method, got, want))
        t0 = time.perf_counter()
        eng.compact()
        log(f"setup: compact in {time.perf_counter() - t0:.1f} s")
        check(eng.delta.snapshot().is_empty, "delta left after compact")
        got = eng.query_batch(queries, method="auto", spec=Count())
        check(got == want, ("compacted", got, want))


def build_rows(k: int) -> np.ndarray:
    """(19, k) fresh GMRQB rows for the append round (another seed)."""
    from repro.data import gmrqb
    return gmrqb.build(k, seed=SEED + 1).cols


def run_four_chips(n_rows: int) -> None:
    import jax
    from repro.core import Count, Ids, MDRQEngine
    from repro.core.distributed import make_data_mesh

    check(len(jax.devices()) >= 4, f"--chips 4 sees {len(jax.devices())}")
    ds, queries, oracle = build_workload(n_rows)
    t0 = time.perf_counter()
    eng = MDRQEngine(ds, structures=("scan",), mesh=make_data_mesh(4))
    log(f"setup: meshed engine built in {time.perf_counter() - t0:.1f} s")

    with phase("sharded placement"):
        data = eng.dist.data
        shards = data.addressable_shards
        devs = {s.device for s in shards}
        check(len(shards) == 4 and len(devs) == 4, (len(shards), devs))
        for s in shards:
            check(s.data.shape == (data.shape[0], data.shape[1] // 4),
                  (s.device, s.data.shape, data.shape))
        log(f"  {data.shape} over {sorted(str(d) for d in devs)}, "
            f"{shards[0].data.shape} each")

    with phase("sharded scan Ids + Count"):
        for spec in (Ids(), Count()):
            got = eng.query_batch(queries, method="scan", spec=spec)
            oracle.check(spec, got, f"mesh scan/{spec}")
            plan = eng.last_batch_stats.method_counts
            check(plan == {"scan": N_QUERIES}, f"mesh plan {plan}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded scan over a 4-chip data mesh")
    args = ap.parse_args(argv)

    dev = require_tpu()
    from repro.compile_cache import use_compile_cache
    log(f"setup: compile cache {use_compile_cache()}")
    import jax
    log(f"setup: device {dev.device_kind} x {len(jax.devices())}")
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(N_ROWS)
    else:
        run_one_chip(N_ROWS)
    stats = dev.memory_stats() or {}
    log(f"setup: total {time.perf_counter() - t0:.1f} s; device 0 peak bytes "
        f"in use {stats.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
