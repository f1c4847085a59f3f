"""The trace reduction: busy and idle time, kernel time, labelled gaps."""
import pytest

from mdrqbench.trace import reduce as R

TABLE = {"scan": ["multi_scan"], "visit": ["visit"]}


def test_busy_kernels_and_gaps_from_events():
    ms = 1_000_000
    spans = [(0, 100 * ms, "bench.window", "main"),
             (10 * ms, 30 * ms, "bench.wait", "main"),
             (60 * ms, 95 * ms, "bench.submit", "main"),
             (0, 100 * ms, "bench.wait", "collector")]
    devices = {"/device:TPU:0": [
        (-5 * ms, 5 * ms, "multi_scan.1"),      # clipped at the window start
        (2 * ms, 8 * ms, "visit_kernel"),       # overlaps: counted once
        (30 * ms, 60 * ms, "fusion.3"),
        (120 * ms, 130 * ms, "multi_scan.1"),   # after the window
    ]}
    out = R.reduce_events(devices, spans, TABLE)
    assert out["window_s"] == pytest.approx(0.1)
    assert out["busy_s"] == pytest.approx(0.038)
    assert out["kernel_s"] == pytest.approx({"scan": 0.005, "visit": 0.006})
    # gaps: 8-30 (wait), 60-100 (submit); the collector's span is ignored
    assert out["idle_gaps"] == [["bench.submit", pytest.approx(0.04)],
                                ["bench.wait", pytest.approx(0.022)]]
    assert out["device_ops"][0] == ["fusion.3", pytest.approx(0.03)]


def test_no_window_or_no_device_work_is_an_error():
    with pytest.raises(ValueError):
        R.reduce_events({}, [(0, 1, "bench.submit", "main")], TABLE)
    with pytest.raises(ValueError):
        R.reduce_events({"/device:TPU:0": [(5, 6, "x")]},
                        [(0, 1, "bench.window", "main")], TABLE)


def test_recorded_chip_trace():
    """A 0.3 s window of the SYNT-UNI Count cell on one TPU v5 lite, kept as
    the XSpace text of the lines the reduction reads (XLA Ops, bench.*)."""
    import gzip
    from pathlib import Path

    from jax.profiler import ProfileData

    path = Path(__file__).with_name("data") / "synt_count_closed.xplane.txtpb.gz"
    pd = ProfileData.from_text_proto(gzip.decompress(path.read_bytes()).decode())
    out = R.reduce_profile(pd)
    assert out["n_devices"] == 1
    assert out["window_s"] == pytest.approx(0.31518127)
    assert out["busy_s"] == pytest.approx(0.208578569)
    assert out["kernel_s"] == pytest.approx({"visit": 0.098109205,
                                             "scan": 0.067529029})
    assert out["kernel_events"] == {"visit": 13, "scan": 14}
    assert out["device_ops"][0] == ["_multi_visit_reduce_jit.1 s8[32768,1024]",
                                    pytest.approx(0.098109205)]
    assert [g[0] for g in out["idle_gaps"]] == ["bench.submit"] * 10
    assert len(out["device_ops"]) == 10


def test_scan_bytes_count_only_constrained_dimensions():
    import numpy as np

    from mdrqbench import roofline
    inf = np.inf
    lower = np.array([[0.0, -inf, -inf], [-inf, -inf, 1.0]], np.float32)
    upper = np.array([[1.0, inf, inf], [inf, inf, 2.0]], np.float32)
    # dims 0 and 2 bounded by some query: 2 x 1000 rows x 4 B, + 2 counts
    assert roofline.scan_bytes(1000, lower, upper, "count") == 8000 + 8
