"""Quickstart: the paper in five minutes.

Builds a 1M x 5 uniform dataset (the paper's Fig. 6 configuration), runs the
same range query through every access path, shows they agree, and asks the
planner where the scan/index break-even sits — the paper's headline ~1%.

  PYTHONPATH=src python examples/quickstart.py

On a TPU this runs the Mosaic kernels. On a CPU, prefix
``REPRO_KERNEL_BACKEND=xla`` to run the XLA references instead of the
(slow) interpret-mode kernels.
"""

import time

import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import MDRQEngine, RangeQuery
from repro.data import synthetic


def main() -> None:
    use_compile_cache()
    n, m = 300_000, 5
    print(f"building SYNT-UNI {n} x {m} and all access paths ...")
    ds = synthetic.synt_uni(n, m, seed=0)
    eng = MDRQEngine(ds)

    rng = np.random.default_rng(1)
    for target in (0.0001, 0.01, 0.3):
        q = synthetic.selectivity_targeted_query(ds, target, rng)
        sel = ds.selectivity(q)
        print(f"\nquery with measured selectivity {sel:.4%}:")
        results = {}
        for meth in ("scan", "scan_vertical", "kdtree", "rstar", "vafile"):
            t0 = time.perf_counter()
            ids = eng.query(q, meth)
            dt = (time.perf_counter() - t0) * 1e3
            results[meth] = ids
            extra = ""
            if meth in ("kdtree", "rstar"):
                idx = getattr(eng, meth)
                extra = f" (visited {idx.last_visited_blocks}/{idx.n_leaves} blocks)"
            print(f"  {meth:14s} {ids.size:7d} ids in {dt:7.2f} ms{extra}")
        assert all(np.array_equal(v, results["scan"]) for v in results.values())
        plan = eng.planner.explain(q)
        print(f"  planner: est sel {plan.est_selectivity:.4%} -> choose "
              f"{plan.method!r}")

    be = eng.planner.break_even_selectivity()
    print(f"\ncost-model break-even at this scale: {be:.3%}"
          f"  (paper, 1M scale: ~1%; scans win everything below ~1e5 objects)")
    print("memory overhead per structure:", eng.memory_report())


if __name__ == "__main__":
    main()
