"""Benchmark runner — one section per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # quick sizes
  PYTHONPATH=src python -m benchmarks.run --full     # paper-scale sizes
  PYTHONPATH=src python -m benchmarks.run --only fig6,fig10

The kernels run on whatever ``REPRO_KERNEL_BACKEND`` names (see common.py):
Mosaic on a TPU by default; on a CPU set ``REPRO_KERNEL_BACKEND=xla`` (the
Makefile bench targets do). The multi-device sections (fig4's sharded row,
fig11) use the devices this process sees; on a CPU, set
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to give it eight.

Prints ``name,us_per_call,derived`` CSV rows. The roofline section reads the
dry-run artifacts under results/dryrun (run repro.launch.dryrun first).
"""
import argparse
import sys
import time
import traceback

from benchmarks import common
from benchmarks.common import CSV_HEADER
from repro.compile_cache import use_compile_cache

# (section name, module[, entry point — defaults to ``run``])
SECTIONS = [
    ("fig4", "benchmarks.bench_hw_features"),
    ("fig5", "benchmarks.bench_dimensionality"),
    ("fig6", "benchmarks.bench_selectivity"),
    ("fig7", "benchmarks.bench_dataset_size"),
    ("fig8", "benchmarks.bench_clusters"),
    ("fig9", "benchmarks.bench_power"),
    ("fig10", "benchmarks.bench_gmrqb"),
    ("fig11", "benchmarks.bench_scaling"),
    ("throughput", "benchmarks.bench_throughput"),
    ("throughput-count", "benchmarks.bench_throughput", "run_count"),
    # reduced result shapes (top-k / aggregate) vs ids at the largest batch
    ("throughput-specs", "benchmarks.bench_throughput", "run_specs"),
    # serve-while-ingest: qps vs delta fraction + post-compaction recovery
    ("throughput-ingest", "benchmarks.bench_throughput", "run_ingest"),
    # AOT-warmed double-buffered pipeline: sync-vs-pipelined head-to-head
    # plus the offered-load sweep (saturation knee, p99 under load)
    ("throughput-pipeline", "benchmarks.bench_throughput", "run_pipeline"),
    # multi-device sweep: needs XLA_FLAGS=--xla_force_host_platform_device_
    # count=8 in the environment (see `make bench-dist`); degrades to a D1
    # row + a pointer when the process only sees one device.
    ("throughput-dist", "benchmarks.bench_throughput", "run_devices"),
    ("mem", "benchmarks.bench_memory"),
    ("roofline", "benchmarks.bench_rooflines"),
]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale sizes")
    ap.add_argument("--only", default="", help="comma-separated section names")
    ap.add_argument("--json-dir", default="",
                    help="also write one BENCH_<section>.json per section "
                         "(its CSV rows as structured records) into this "
                         "directory")
    args = ap.parse_args()
    use_compile_cache()
    only = set(filter(None, args.only.split(",")))
    if args.json_dir:
        import os as _os
        _os.makedirs(args.json_dir, exist_ok=True)

    print(CSV_HEADER, flush=True)
    failures = 0
    for name, module, *entry in SECTIONS:
        if only and name not in only:
            continue
        t0 = time.time()
        start = common.mark()
        try:
            import importlib
            mod = importlib.import_module(module)
            getattr(mod, entry[0] if entry else "run")(quick=not args.full)
            print(f"# section {name} done in {time.time()-t0:.1f}s", flush=True)
        except Exception:
            failures += 1
            print(f"# section {name} FAILED", flush=True)
            traceback.print_exc()
            continue
        if args.json_dir:
            common.write_bench_json(
                f"{args.json_dir}/BENCH_{name}.json", name,
                rows=common.rows_since(start), full=args.full)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
