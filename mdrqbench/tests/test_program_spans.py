"""The program-span additions to the trace reduction, and their readers."""
import gzip
from pathlib import Path

import pytest

from mdrqbench import harness
from mdrqbench.trace import program as P
from mdrqbench.trace import reduce as R

TABLE = {"scan": ["multi_scan"], "visit": ["visit"]}
MS = 1_000_000
READERS = ("launch_sync_ms.count", "d2h_bytes_per_window.count",
           "finalize_host_ms.count", "idle_in_flush.count")


def _events():
    """A 100 ms window: two windows' device stages on the admission thread
    (``main``), their finalizes on ``fin``, nested as the server nests them.
    """
    spans = [(0, 100 * MS, "bench.window", "main"),
             (10 * MS, 60 * MS, "bench.submit", "main"),
             (60 * MS, 100 * MS, "bench.wait", "main")]
    launch = {"stage": "launch", "path": "kdtree"}
    prog = [
        (-20 * MS, -10 * MS, "mdrq.flush", "main", {"window": 0}),  # before
        (10 * MS, 50 * MS, "mdrq.flush", "main", {"window": 1}),
        (10 * MS, 12 * MS, "mdrq.plan", "main", {"n_queries": 128}),
        (12 * MS, 48 * MS, "mdrq.execute", "main", dict(launch, bucket=128)),
        (14 * MS, 30 * MS, "mdrq.sync", "main", dict(launch, bytes=1_250_000)),
        (50 * MS, 55 * MS, "mdrq.backlog_put", "main", {"window": 1}),
        (70 * MS, 80 * MS, "mdrq.flush", "main", {"window": 2}),
        (-5 * MS, 5 * MS, "mdrq.finalize", "fin", {"window": 0}),  # clipped
        (40 * MS, 70 * MS, "mdrq.finalize", "fin", {"window": 1}),
        (40 * MS, 60 * MS, "mdrq.sync", "fin",
         {"stage": "finalize", "path": "kdtree", "bytes": 512}),
        (85 * MS, 110 * MS, "mdrq.finalize", "fin", {"window": 2}),
        (85 * MS, 90 * MS, "mdrq.sync", "fin",
         {"stage": "finalize", "path": "scan", "bytes": 512}),
    ]
    # idle: 14-30 (inside the prune sync), 40-60, 75-78
    devices = {"/device:TPU:0": [(0, 14 * MS, "multi_scan.1"),
                                 (30 * MS, 40 * MS, "visit_kernel"),
                                 (60 * MS, 75 * MS, "multi_scan.1"),
                                 (78 * MS, 100 * MS, "fusion.3")]}
    return devices, spans, prog


def test_program_spans_idle_in_flush_and_refined_labels():
    devices, spans, prog = _events()
    out = P.reduce_events(devices, spans, prog, TABLE)
    ps = out["program_spans"]
    assert ps["mdrq.flush"] == {"": {"s": pytest.approx(0.05), "n": 2,
                                     "bytes": 0}}
    assert ps["mdrq.execute"]["launch"]["s"] == pytest.approx(0.036)
    assert ps["mdrq.sync"] == {
        "launch": {"s": pytest.approx(0.016), "n": 1, "bytes": 1_250_000},
        "finalize": {"s": pytest.approx(0.025), "n": 2, "bytes": 1024}}
    # window 0's finalize counts its 5 ms inside, not as a span of the window
    assert ps["mdrq.finalize"][""] == {"s": pytest.approx(0.05), "n": 2,
                                       "bytes": 0}
    assert ps["mdrq.backlog_put"][""]["n"] == 1
    # idle inside the admission thread's flushes: 16 + 10 + 3 ms
    assert out["idle_in_flush_s"] == pytest.approx(0.029)
    # longest first; ties go to the shortest span; finalizer spans never label
    assert out["idle_gaps"] == [
        ["bench.submit > mdrq.flush", pytest.approx(0.02)],
        ["bench.submit > mdrq.sync[kdtree]", pytest.approx(0.016)],
        ["bench.wait > mdrq.flush", pytest.approx(0.003)]]


def test_base_keys_read_as_the_reduction_gives_them():
    devices, spans, prog = _events()
    base = R.reduce_events(devices, spans, TABLE)
    out = P.reduce_events(devices, spans, prog, TABLE)
    for key in ("window_s", "busy_s", "n_devices", "device_ops", "kernel_s",
                "kernel_events"):
        assert out[key] == base[key]
    assert [g[1] for g in out["idle_gaps"]] == \
        [g[1] for g in base["idle_gaps"]]
    # no program spans: the base output plus empty additions
    bare = P.reduce_events(devices, spans, [], TABLE)
    assert {k: v for k, v in bare.items()
            if k not in ("program_spans", "idle_in_flush_s")} == base
    assert bare["program_spans"] == {} and bare["idle_in_flush_s"] == 0.0


def test_recorded_chip_trace_has_no_program_spans():
    from jax.profiler import ProfileData

    path = Path(__file__).with_name("data") / \
        "synt_count_closed.xplane.txtpb.gz"
    pd = ProfileData.from_text_proto(
        gzip.decompress(path.read_bytes()).decode())
    base = R.reduce_profile(pd)
    out = P.reduce_profile(pd)
    assert P.read_program_spans(pd) == []
    assert out == dict(base, program_spans={}, idle_in_flush_s=0.0)
    ctx = _ctx(out)
    assert all(harness._load_layer(n).read(ctx) is None for n in READERS)


def _ctx(trace):
    return harness.Context(cell=None, n_rows=0, spec_kind="count", log=None,
                           t0=0.0, t_end=0.1, stats=None, counters={},
                           plans=[], trace=trace, peaks={})


@pytest.mark.parametrize("name,want", [
    ("launch_sync_ms.count", 8.0),            # 16 ms of prune sync / 2
    ("d2h_bytes_per_window.count", 625_512.0),  # (1.25 MB + 1 KiB) / 2
    ("finalize_host_ms.count", 12.5),         # (50 - 25 ms) / 2
    ("idle_in_flush.count", 29.0),            # 29 ms of 100
])
def test_readers(name, want):
    devices, spans, prog = _events()
    reader = harness._load_layer(name)
    assert reader.read(_ctx(P.reduce_events(devices, spans, prog, TABLE))) \
        == pytest.approx(want)
    assert reader.read(_ctx(None)) is None
    # the reduction without the program's spans (a trace with the sink off)
    assert reader.read(_ctx(R.reduce_events(devices, spans, TABLE))) is None
