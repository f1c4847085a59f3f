"""Find an open-loop cell's knee: offered Poisson rates against what is served.

    python3 mdrqbench/sweep.py --workload <open-loop cell> --seed <n> \\
        --seconds <s> --rates 25,50,100,200

One process builds the cell's engine and server once, warms them on the
pool of the highest rate, then offers each rate for ``--seconds`` and prints
one JSON line per rate: offered and achieved rate, p50 and p99 latency from
the due time, and how late the last result came after the last arrival (a
backlog that grows leaves it growing with the rate). The knee is the highest
rate whose achieved rate stays at 0.98 x offered or more with no growing
backlog; the cell offers 0.8 x the knee, written into its traffic file.
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from mdrqbench import harness, loads  # noqa: E402


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(harness.ROOT / "src"))
    rates = [float(r) for r in args.rates.split(",")]
    cell = harness.load_cell(args.workload)
    if cell.traffic["loop"] != "open":
        raise SystemExit(f"{args.workload} is not an open-loop cell")
    try:
        harness.require_chip(cell.chips)
    except harness.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    harness.use_cache_in_checkout()
    cell.traffic = dict(cell.traffic, rate_qps=max(rates))
    s = harness.build(cell, args.seed, args.seconds)
    wait_s = float(cell.traffic["server"]["max_wait_s"])
    with s.srv:
        harness.warm(cell, s)
        harness.log(f"setup: {time.perf_counter() - t_start:.1f} s")
        rng = np.random.default_rng(args.seed)
        for rate in rates:
            n = max(1, int(round(rate * args.seconds)))
            gaps = loads.poisson_gaps(n, rate, rng)
            drv = loads.Driver(s.srv, s.make_query, s.pool_n)
            s.srv.reset_stats()
            t0, t_end = loads.run_open(drv, gaps, wait_s, harness.GRACE_S)
            s.srv.drain()
            log_ = drv.log
            ok = np.array([e is None for e in log_.error])
            done = np.array([t if t is not None else np.inf
                             for t in log_.t_done])
            lat = (done - np.asarray(log_.due))[ok]
            served = int(np.sum(ok & (done <= t_end)))
            print(json.dumps({
                "offered_qps": rate, "achieved_qps": served / (t_end - t0),
                "completed_share": float(ok.mean()),
                "p50_ms": float(np.percentile(lat, 50)) * 1e3,
                "p99_ms": float(np.percentile(lat, 99)) * 1e3,
                "last_result_late_s": float(np.max(done[ok]) - t_end),
                "paths": s.srv.stats.method_counts,
                "flushes": s.srv.stats.flush_reasons}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
