"""Placement: the engine shards a table that one device cannot hold.

With no ``mesh=``, ``MDRQEngine`` reads the first device's memory limit and
shards the scan's table over every local device when one device cannot hold
it beside a full window's Count mask (``distributed.placement_mesh``). The
CPU reports no limit, so these tests patch the reading. Multi-device cases
run in a subprocess with four host devices (XLA fixes the device count at
its first use), through the served path, against the benchmark's numpy
reference.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.core import Dataset, MDRQEngine, distributed

V5E_LIMIT = 16_909_336_064   # bytes_limit a TPU v5 lite reports


def test_placement_rule_at_the_benchmark_sizes():
    """On a v5e the 10M cells stay on one chip and GMRQB at 2e8 rows needs
    four: 50,000,896 rows a chip, 96 B of table and 128 B of mask a row."""
    per = distributed.scan_bytes_per_device
    assert per(19, 10_000_000, 1, 1024) == 10_000_384 * (24 * 4 + 128)
    assert per(19, 10_000_000, 1, 1024) <= V5E_LIMIT
    assert per(5, 10_000_000, 1, 1024) <= V5E_LIMIT
    assert per(19, 200_000_000, 1, 1024) > V5E_LIMIT
    assert per(19, 200_000_000, 4, 1024) == 50_000_896 * 224 <= V5E_LIMIT


def test_no_reported_limit_stays_on_one_device(monkeypatch):
    assert distributed.device_bytes_limit() is None      # the CPU
    assert distributed.placement_mesh(19, 10**9, 1024) is None
    monkeypatch.setattr(distributed, "device_bytes_limit", lambda: V5E_LIMIT)
    assert distributed.placement_mesh(19, 10_000_000, 1024) is None


def test_a_table_too_large_for_every_device_is_refused(monkeypatch):
    monkeypatch.setattr(distributed, "device_bytes_limit", lambda: 10_000)
    cols = np.zeros((19, 4096), np.float32)
    with pytest.raises(ValueError, match=r"needs 917504 B a device on 1 "
                                         r"device\(s\).*holds 10000 B"):
        MDRQEngine(Dataset(cols), structures=("scan",))


SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np, jax
    from jax.sharding import PartitionSpec as P
    from mdrqbench import reference
    from mdrqbench.gen import gmrqb_blocks, gmrqb_mixed
    from repro import obs
    from repro.core import Count, Dataset, Ids, MDRQEngine, RangeQuery
    from repro.core import distributed
    from repro.kernels import ops
    from repro.obs import metrics
    from repro.serve.pipeline import serve_pipelined

    assert len(jax.devices()) == 4
    rng = np.random.default_rng(11)
    n = 200_000
    cols = gmrqb_blocks.build({"rows": n, "dims": 19}, rng)
    lower, upper = gmrqb_mixed.make(cols, 24, rng, {})
    queries = [RangeQuery(lo, up) for lo, up in zip(lower, upper)]
    want = [reference.match_ids(cols, lo, up) for lo, up in zip(lower, upper)]
    assert sum(w.size for w in want) > 0

    # the real reading (none on the CPU) and a v5e's keep it on one device
    assert MDRQEngine(Dataset(cols), structures=("scan",)).dist is None
    distributed.device_bytes_limit = lambda: 16_909_336_064
    assert MDRQEngine(Dataset(cols), structures=("scan",)).dist is None

    # one device holds 200,704 x 224 B, four hold 50,176 x 224 B each
    distributed.device_bytes_limit = lambda: 20_000_000
    try:
        MDRQEngine(Dataset(cols))
        raise AssertionError("indexes over a sharded table were built")
    except ValueError as e:
        assert "kdtree" in str(e), e
    with obs.Tracer() as tr:
        eng = MDRQEngine(Dataset(cols), structures=("scan",))
    place, = tr.find("place")
    assert place.attrs == {"n_devices": 4, "bytes_per_device": 24 * 50176 * 4}
    assert eng.dist is not None and eng.dist.n_devices == 4
    assert eng.planner.model.n_devices == 4
    assert eng.dist.data.sharding.spec == P(None, "data")
    padded, _, _ = ops.prepare_columnar(cols, tile_n=4 * 1024)
    assert np.array_equal(np.asarray(eng.dist.data), padded)
    assert [s.data.shape for s in eng.dist.data.addressable_shards] \\
        == [(24, 50176)] * 4

    metrics.registry().reset()
    with serve_pipelined(eng, max_batch=8, max_wait_s=0.01, method="auto",
                         spec=Count(), warmup=False,
                         latency_budget_s=float("inf")) as srv:
        counts = srv.serve_all(queries)
        assert srv.stats.method_counts == {"scan": 24}, srv.stats.method_counts
    assert counts == [w.size for w in want]
    rows = metrics.registry().counter_values(
        "mdrq_scan_rows_compared_total", "kernel")
    assert set(rows) == {"sharded"} and rows["sharded"] > 0, rows
    # the windows of mixed templates ran sorted, and still answer in order
    reordered = metrics.registry().counter_values(
        "mdrq_bucket_reordered_total", "path")
    assert set(reordered) == {"scan"} and reordered["scan"] > 0, reordered
    with serve_pipelined(eng, max_batch=8, max_wait_s=0.01, method="auto",
                         spec=Ids(), warmup=False,
                         latency_budget_s=float("inf")) as srv:
        ids = srv.serve_all(queries)
    for k, (g, w) in enumerate(zip(ids, want)):
        assert np.array_equal(g, w), k

    eng.query_batch(queries[:4], spec=Count(), trace=True)
    ex, = [s for s in eng.last_trace.spans if s.name == "execute"]
    assert ex.attrs["n_devices"] == 4 and ex.attrs["path"] == "scan"
    print("PLACEMENT_OK")
""")


def test_engine_shards_what_one_device_cannot_hold():
    """A GMRQB table of 200k rows, with the device limit patched small,
    builds sharded over four devices with no ``mesh=``; served Count and
    Ids answers equal the numpy reference's."""
    root = Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join([str(root / "src"), str(root)])
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=900, env=env, cwd=root)
    assert "PLACEMENT_OK" in r.stdout, \
        f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"
