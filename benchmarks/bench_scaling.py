"""Fig. 11: scaling vs #parallel units (sharded scan over 1..8 devices).

One process, meshes over the first k of ``jax.devices()``. On a CPU, launch
with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``; a device count
the process does not have is reported as skipped, never dropped.
"""
import time

import jax
import numpy as np

from benchmarks.common import emit_row
from repro.core import MDRQEngine
from repro.core.distributed import make_data_mesh
from repro.data import gmrqb


def run(quick: bool = True) -> None:
    ds = gmrqb.build(200_000, seed=0)
    rng = np.random.default_rng(1)
    qs = [gmrqb.template(int(rng.integers(1, 8)), rng, ds) for _ in range(20)]
    n_dev = len(jax.devices())
    for k in (1, 2, 4, 8):
        if k > n_dev:
            print(f"# fig11/devices{k}/scan skipped: this process sees "
                  f"{n_dev} device(s)", flush=True)
            continue
        d = MDRQEngine(ds, structures=("scan",), mesh=make_data_mesh(k))
        for q in qs[:3]:
            d.query(q, "scan")
        t0 = time.perf_counter()
        for q in qs:
            d.query(q, "scan")
        dt = (time.perf_counter() - t0) / len(qs)
        emit_row(f"fig11/devices{k}/scan", dt * 1e6, f"qps={1/dt:.1f}")
