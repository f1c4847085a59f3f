"""The sharded cell's two per-layer readers on a synthetic four-chip trace
reduction, and with no trace."""
import importlib.util
import types

import numpy as np
import pytest

from mdrqbench import harness
from mdrqbench.trace import reduce as R

MS = 1_000_000
KERNEL = "%sharded_scan.3 = s8[128,50000896]{1,0:T(8,128)(4,1)} custom-call(%a)"
PSUM = "%psum.7 = s32[128]{0:T(128)} all-reduce(%b), channel_id=1"


def _reader(name):
    path = harness.BENCH_DIR / "layers" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _trace():
    """Four chips, one 100 ms window: each runs the sharded kernel for 50
    ms, then the all-reduce, 1 ms on three chips and 2 ms on the fourth
    (which waited for the slowest shard)."""
    devices = {f"/device:TPU:{d}": [(10 * MS, 60 * MS, KERNEL),
                                    (60 * MS, (61 + (d == 3)) * MS, PSUM),
                                    (62 * MS, 63 * MS, "%fusion.2 = f32[8]")]
               for d in range(4)}
    spans = [(0, 100 * MS, "bench.window", "main")]
    return R.reduce_events(devices, spans, {"scan": ["^%_multi_scan"]})


def _ctx(trace):
    inf = np.inf
    queries = [types.SimpleNamespace(lower=np.array([0.0, -inf, -inf]),
                                     upper=np.array([1.0, inf, inf])),
               types.SimpleNamespace(lower=np.array([-inf, 0.0, 0.0]),
                                     upper=np.array([inf, 1.0, 1.0]))]
    return types.SimpleNamespace(
        trace=trace, plans=[(0.0, queries, ["scan", "scan"])], n_rows=1000,
        spec_kind="count", peaks={"hbm_bytes_per_s": 819e9},
        stats=types.SimpleNamespace(n_batches=1))


def test_readers_on_a_four_chip_reduction():
    trace = _trace()
    assert trace["n_devices"] == 4 and "scan" not in trace["kernel_s"]
    ctx = _ctx(trace)
    # 3 bounded dims x 1000 rows x 4 B + 2 counts, at four chips' peak, over
    # the kernel's 50 ms on each chip
    want = (3 * 1000 * 4 + 2 * 4) / (4 * 819e9) / 0.05 * 100
    got = _reader("shard_scan_roofline.count").read(ctx)
    assert got == pytest.approx(want)
    # (1 + 1 + 1 + 2) ms over four chips, one window
    assert _reader("allreduce_ms.count").read(ctx) == pytest.approx(1.25)


def test_readers_read_nothing_without_a_trace_or_their_ops():
    for name in ("shard_scan_roofline.count", "allreduce_ms.count"):
        assert _reader(name).read(_ctx(None)) is None
        one_chip = R.reduce_events(
            {"/device:TPU:0": [(0, 5 * MS, "%_multi_scan_reduce_jit.1 = s8[128,1024]")]},
            [(0, 10 * MS, "bench.window", "main")], {"scan": ["^%_multi_scan"]})
        assert _reader(name).read(_ctx(one_chip)) is None
