"""Shared benchmark utilities.

The kernel backend is whatever REPRO_KERNEL_BACKEND names; nothing here sets
it. On a TPU the default (``auto``) times the Mosaic kernels. On a CPU,
interpret-mode Pallas runs the grid as a Python loop, so the CPU bench
targets set ``REPRO_KERNEL_BACKEND=xla``: the XLA path — semantically
identical to the kernels, validated in tests — is the CPU proxy. A CPU
number is never a device number.
"""
from __future__ import annotations

import json
import time
from typing import Callable, Optional

import numpy as np


def time_workload(fn: Callable[[], object], n_warm: int = 2, n_iter: int = 5
                  ) -> float:
    """Median seconds per call of fn()."""
    for _ in range(n_warm):
        fn()
    ts = []
    for _ in range(n_iter):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def run_queries(engine, queries, method: str) -> float:
    """Total seconds to run all queries with the given method (one pass)."""
    t0 = time.perf_counter()
    for q in queries:
        engine.query(q, method)
    return time.perf_counter() - t0


def qps(engine, queries, method: str, n_warm: int = 3) -> float:
    """Queries/second after warmup (the paper's throughput metric, §7.1.2)."""
    for q in queries[:n_warm]:
        engine.query(q, method)
    dt = run_queries(engine, queries, method)
    return len(queries) / dt


CSV_HEADER = "name,us_per_call,result_spec,derived"

# Every emit_row also lands here as a dict, so any bench section can be
# serialized to a BENCH_<name>.json artifact after the fact (run.py
# --json-dir; bench_throughput --json). Cleared only by mark()/rows_since
# bookkeeping — a process runs few enough rows that the list is free.
ROWS: list[dict] = []


def _parse_derived(derived: str) -> dict:
    """The ``derived`` blob's ``k=v`` pairs as a dict (numbers parsed, a
    trailing x/% unit stripped), so JSON artifacts carry qps etc. as fields
    machines can diff instead of strings they must re-parse."""
    out = {}
    for part in filter(None, derived.split(";")):
        if "=" not in part:
            continue
        k, v = part.split("=", 1)
        num = v[:-1] if v and v[-1] in "x%" else v
        try:
            out[k] = float(num)
        except ValueError:
            out[k] = v
    return out


def emit_row(name: str, us: float, derived: str = "",
             result_spec: str = "ids") -> None:
    """One CSV row. ``result_spec`` is the ResultSpec kind the row measured
    ("ids" unless a benchmark sweeps reduced result shapes) — a first-class
    column so throughput tables distinguish ids/count/top-k runs instead of
    overloading the name or the derived blob."""
    print(f"{name},{us:.2f},{result_spec},{derived}", flush=True)
    ROWS.append({"name": name, "us_per_call": round(us, 2),
                 "result_spec": result_spec, "derived": derived,
                 **_parse_derived(derived)})


def mark() -> int:
    """Bookmark the row stream (pair with ``rows_since``)."""
    return len(ROWS)


def rows_since(start: int) -> list[dict]:
    return ROWS[start:]


def write_bench_json(path: str, bench: str, rows: Optional[list] = None,
                     **extra) -> None:
    """Write one ``BENCH_<name>.json`` artifact: the rows of a bench section
    plus whatever structured payload the bench adds (``extra``), e.g. the
    smoke bench's per-batch-size qps/latency entries that
    ``benchmarks.check_bench`` diffs against the checked-in baseline."""
    doc = {"bench": bench, "rows": ROWS if rows is None else rows, **extra}
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"# wrote {path}", flush=True)
