"""GMRQB: the paper's genomic benchmark end-to-end (paper §6, Fig. 10).

Builds the 19-dimensional shape-faithful GMRQB stand-in, measures Table 1
selectivities, and runs each template through scan / vertical scan / kd-tree /
VA-file with the planner's choice last.

  PYTHONPATH=src python examples/gmrqb_demo.py [n_objects]

On a TPU this runs the Mosaic kernels. On a CPU, prefix
``REPRO_KERNEL_BACKEND=xla`` to run the XLA references instead of the
(slow) interpret-mode kernels.
"""

import sys
import time

import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import MDRQEngine
from repro.data import gmrqb


def main() -> None:
    use_compile_cache()
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 300_000
    print(f"building GMRQB ({n} variation records, 19 attributes) ...")
    ds = gmrqb.build(n, seed=0)
    eng = MDRQEngine(ds, structures=("scan", "kdtree", "vafile"))
    rng = np.random.default_rng(1)

    print(f"\n{'T':>2} {'dims':>5} {'sel (measured)':>15} {'paper':>9}  "
          f"{'scan':>9} {'vertical':>9} {'kdtree':>9} {'vafile':>9}  planner")
    for k in range(1, 9):
        qs = [gmrqb.template(k, rng, ds) for _ in range(5)]
        sel = float(np.mean([ds.selectivity(q) for q in qs]))
        times = {}
        for meth in ("scan", "scan_vertical", "kdtree", "vafile"):
            t0 = time.perf_counter()
            for q in qs:
                eng.query(q, meth)
            times[meth] = (time.perf_counter() - t0) / len(qs) * 1e3
        choice = eng.planner.choose(qs[0])
        paper = gmrqb.PAPER_TABLE1[k - 1].avg_selectivity
        print(f"{k:>2} {qs[0].n_queried_dims:>5} {sel:>14.5%} {paper:>8.4%}  "
              f"{times['scan']:>7.1f}ms {times['scan_vertical']:>7.1f}ms "
              f"{times['kdtree']:>7.1f}ms {times['vafile']:>7.1f}ms  {choice}")

    mixed = [q for _, q in gmrqb.mixed_workload(ds, 20, seed=3)]
    t0 = time.perf_counter()
    for q in mixed:
        eng.query(q, "auto")
    dt = (time.perf_counter() - t0) / len(mixed) * 1e3
    print(f"\nmixed workload via planner: {dt:.1f} ms/query "
          f"({1000/dt:.0f} qps)")


if __name__ == "__main__":
    main()
