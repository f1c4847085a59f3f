"""Shrunk copies of the benchmark's cells for CPU tests (interpret mode).

The cells' own config and traffic files are read, then scaled down here:
fewer rows, a smaller pool, fewer clients, a smaller window. Everything else,
the generators, the loops, the check and the metric readers, is the code a
chip run uses. ``OPEN_CELL`` is the open-loop Ids mix that PERF.md keeps for
a later cell (GMRQB under ``traffic/mixed-ids-open.json``), built here so
that the open loop and its readers stay tested.
"""
import json

from mdrqbench import harness

ROWS = 12_000
SMALL_SERVER = {"max_batch": 4, "max_wait_s": 0.002, "backlog": 4}
OPEN_CELL = "gmrqb10m-mixed-ids-open"


def cell_names() -> list:
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    return [w["name"] for w in bench["workloads"]] + [OPEN_CELL]


def _open_cell() -> harness.Cell:
    cfg = json.loads((harness.BENCH_DIR / "configs/gmrqb-10m.json").read_text())
    traffic = json.loads(
        (harness.BENCH_DIR / "traffic/mixed-ids-open.json").read_text())
    ms = {"unit": "ms", "better": "lower", "source": "host_clock"}
    e2e = [{"name": "setup_s", "unit": "s"}, dict(ms, name="p50_ms"),
           dict(ms, name="p95_ms")]
    per_layer = [dict(ms, name="gen_lag_p95_ms.ids", moves="p95_ms"),
                 dict(ms, name="finalize_ms.ids", moves="p95_ms"),
                 {"name": "idle_share.ids", "unit": "%", "moves": "p95_ms"}]
    return harness.Cell(OPEN_CELL, 1, cfg, traffic, e2e, per_layer)


def small_cell(name: str) -> harness.Cell:
    c = _open_cell() if name == OPEN_CELL else harness.load_cell(name)
    c.cfg = dict(c.cfg, rows=ROWS)
    t = dict(c.traffic, server=SMALL_SERVER,
             check={"sample": 64, "per_path": 8})
    if t["loop"] == "closed":
        t.update(clients=8, pool=32)
    else:
        t.update(rate_qps=20)
    c.traffic = t
    return c
