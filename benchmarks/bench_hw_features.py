"""Fig. 4: impact of hardware features (scalar / vectorized / parallel).

Paper contestants -> container analogues:
  scalar single-thread  -> numpy row loop amortized via numpy vector ops on
                           one core (the paper's Listing 1 baseline)
  + SIMD                -> XLA-vectorized columnar scan (kernel proxy)
  + multi-threading     -> shard_map over the process's devices
"""
import time

import jax
import numpy as np

from benchmarks.common import emit_row, qps
from repro.core import MDRQEngine
from repro.core.distributed import make_data_mesh
from repro.data import synthetic


def run(quick: bool = True) -> None:
    n, m = (200_000, 20)
    ds = synthetic.synt_uni(n, m, seed=0)
    rng = np.random.default_rng(1)
    queries = [synthetic.selectivity_targeted_query(ds, 1e-3, rng)
               for _ in range(30)]

    # scalar baseline: single-core numpy (row-major, early-break-free)
    rows = ds.rows()
    for _ in range(2):
        q = queries[0]
        (np.logical_and(rows >= q.lower, rows <= q.upper)).all(1).nonzero()
    t0 = time.perf_counter()
    for q in queries:
        (np.logical_and(rows >= q.lower, rows <= q.upper)).all(1).nonzero()
    dt = (time.perf_counter() - t0) / len(queries)
    emit_row("fig4/scan_scalar_numpy", dt * 1e6, f"qps={1/dt:.1f}")

    eng = MDRQEngine(ds, structures=("scan", "kdtree", "vafile"))
    for meth in ("scan", "scan_vertical", "kdtree", "vafile"):
        r = qps(eng, queries, meth)
        emit_row(f"fig4/{meth}_vectorized", 1e6 / r, f"qps={r:.1f}")

    # multi-device sharded scan over every device this process sees (on a
    # CPU: XLA_FLAGS=--xla_force_host_platform_device_count=8 before launch)
    k = len(jax.devices())
    d = MDRQEngine(ds, structures=("scan",), mesh=make_data_mesh(k))
    for q in queries[:3]:
        d.query(q, "scan")
    t0 = time.perf_counter()
    for q in queries:
        d.query(q, "scan")
    dt = (time.perf_counter() - t0) / len(queries)
    emit_row(f"fig4/scan_vectorized_{k}dev", dt * 1e6, f"qps={1/dt:.1f}")
