"""mdrqlint: per-rule positive/negative fixtures, suppression + baseline
round-trips, and the standing assertion that the shipped tree lints clean.

Fixtures are written to tmp dirs whose layout mimics ``repro/...`` because
rules scope themselves by posix-path substring (e.g. uncounted-launch only
fires inside ``repro/kernels/`` and ``repro/core/``).
"""
from __future__ import annotations

import textwrap
from pathlib import Path

import pytest

from repro.analysis import engine
from repro.analysis.__main__ import main as lint_main
from repro.analysis.contracts import (KernelDtypeRule, KernelTileRule,
                                      NoteTraceRule)
from repro.analysis.rules import (ALL_RULES, HostSyncRule, LockDisciplineRule,
                                  RawShardMapRule, RegistryHygieneRule,
                                  SentinelRule, ThreadBoundaryRule,
                                  UncountedLaunchRule)

REPO = Path(__file__).resolve().parents[1]


def lint_one(tmp_path: Path, rel: str, source: str, rule) -> engine.Report:
    """Write ``source`` at tmp/<rel> and run a single rule over it."""
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    return engine.run([path], [rule])


# -- rule 1: host-sync --------------------------------------------------------

def test_host_sync_flags_raw_coercions(tmp_path):
    rep = lint_one(tmp_path, "repro/core/bad_sync.py", """\
        import jax
        import numpy as np
        from repro.kernels import ops

        def leaky(q):
            out = ops.multi_scan_reduce(q)        # device-value source
            jax.device_get(out)                   # raw sync API
            return float(out)                     # raw coercion sink
        """, HostSyncRule())
    rules = [f.rule for f in rep.active]
    assert rules == ["host-sync", "host-sync"]
    assert "jax.device_get" in rep.active[0].message
    assert "float()" in rep.active[1].message


def test_host_sync_accepts_counted_device_get(tmp_path):
    rep = lint_one(tmp_path, "repro/core/good_sync.py", """\
        from repro.kernels import ops

        def clean(q):
            out = ops.multi_scan_reduce(q)
            host = ops.device_get(out)            # the counted sync
            return float(host)                    # host value: not a sync
        """, HostSyncRule())
    assert rep.active == []


def test_host_sync_tracks_taint_through_helpers(tmp_path):
    # _launch returns a device value; the caller's np.asarray is the sync
    rep = lint_one(tmp_path, "repro/core/chained.py", """\
        import numpy as np
        from repro.kernels import ops

        def _launch(q):
            return ops.multi_scan_reduce(q)

        def caller(q):
            return np.asarray(_launch(q))
        """, HostSyncRule())
    assert [f.rule for f in rep.active] == ["host-sync"]
    assert "asarray" in rep.active[0].message


# -- rule 2: uncounted-launch -------------------------------------------------

def test_uncounted_launch_flags_bare_jit(tmp_path):
    rep = lint_one(tmp_path, "repro/kernels/bad_jit.py", """\
        import jax

        @jax.jit
        def fast(x):
            return x + 1

        faster = jax.jit(fast)
        """, UncountedLaunchRule())
    msgs = [f.message for f in rep.active]
    assert len(msgs) == 2
    assert any("'fast'" in m for m in msgs)
    assert any("'faster'" in m for m in msgs)


def test_uncounted_launch_accepts_registered(tmp_path):
    rep = lint_one(tmp_path, "repro/kernels/good_jit.py", """\
        import jax
        from repro.kernels import ops

        @jax.jit
        def _fast_jit(x):
            return x + 1

        fast = ops.counted("fast", "Example counted entry point.")(_fast_jit)
        """, UncountedLaunchRule())
    assert rep.active == []


def test_uncounted_launch_scoped_to_kernels_and_core(tmp_path):
    # a jit in obs/ is not an engine entry point; the rule stays quiet
    rep = lint_one(tmp_path, "repro/obs/free_jit.py", """\
        import jax

        @jax.jit
        def helper(x):
            return x * 2
        """, UncountedLaunchRule())
    assert rep.active == []


# -- rule 3: raw-shard-map ----------------------------------------------------

def test_raw_shard_map_flagged(tmp_path):
    rep = lint_one(tmp_path, "repro/core/bad_dist.py", """\
        from jax.experimental.shard_map import shard_map

        def spread(f, mesh):
            return shard_map(f, mesh=mesh)
        """, RawShardMapRule())
    assert [f.rule for f in rep.active] == ["raw-shard-map"]
    assert "shard_map_compat" in rep.active[0].message


def test_shard_map_compat_accepted(tmp_path):
    rep = lint_one(tmp_path, "repro/core/good_dist.py", """\
        from repro.core.distributed import shard_map_compat

        def spread(f, mesh):
            return shard_map_compat(f, mesh=mesh)
        """, RawShardMapRule())
    assert rep.active == []


# -- rule 4: sentinel ---------------------------------------------------------

def test_sentinel_flags_f32_scale_literals_and_blind_inf_casts(tmp_path):
    rep = lint_one(tmp_path, "repro/models/bad_mask.py", """\
        import jax.numpy as jnp
        import numpy as np

        NEG = -3.0e38                       # rounds to -inf under bf16
        pad = jnp.full((4,), np.inf)        # inf into an unknown dtype
        """, SentinelRule())
    rules = [f.rule for f in rep.active]
    assert rules == ["sentinel", "sentinel"]
    assert "bf16" in rep.active[0].message


def test_sentinel_accepts_numerics_and_explicit_wide_dtypes(tmp_path):
    rep = lint_one(tmp_path, "repro/models/good_mask.py", """\
        import jax.numpy as jnp
        import numpy as np
        from repro import numerics

        NEG = numerics.mask_fill(jnp.bfloat16)
        cost = np.full((4,), np.inf, np.float64)   # f64 inf is exact
        """, SentinelRule())
    assert rep.active == []


# -- rule 5: lock-discipline --------------------------------------------------

def test_lock_discipline_flags_off_lock_write(tmp_path):
    rep = lint_one(tmp_path, "repro/core/bad_lock.py", """\
        import threading

        class Counter:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0              # __init__ is exempt

            def add(self):
                with self._lock:
                    self.count += 1

            def reset(self):
                self.count = 0              # off-lock write to guarded attr
        """, LockDisciplineRule())
    assert [f.rule for f in rep.active] == ["lock-discipline"]
    assert "Counter.count" in rep.active[0].message


def test_lock_discipline_accepts_guarded_writes(tmp_path):
    rep = lint_one(tmp_path, "repro/core/good_lock.py", """\
        import threading

        class Counter:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0

            def add(self):
                with self._lock:
                    self.count += 1

            def reset(self):
                with self._lock:
                    self.count = 0
        """, LockDisciplineRule())
    assert rep.active == []


def test_lock_discipline_flags_state_mutation_and_off_lock_swap(tmp_path):
    rep = lint_one(tmp_path, "repro/core/bad_state.py", """\
        class Engine:
            def patch(self, cols):
                self._state.cols = cols     # in-place mutation

            def swap(self, new):
                self._state = new           # swap outside the ingest lock
        """, LockDisciplineRule())
    msgs = [f.message for f in rep.active]
    assert len(msgs) == 2
    assert any("in-place" in m for m in msgs)
    assert any("ingest lock" in m for m in msgs)


def test_lock_discipline_accepts_single_swap_under_ingest_lock(tmp_path):
    rep = lint_one(tmp_path, "repro/core/good_state.py", """\
        class Engine:
            def swap(self, new):
                with self._ingest_lock:
                    self._state = new
        """, LockDisciplineRule())
    assert rep.active == []


# -- rule 6: registry-hygiene -------------------------------------------------

def test_registry_hygiene_flags_non_frozen_and_mutable_default(tmp_path):
    rep = lint_one(tmp_path, "repro/core/bad_spec.py", """\
        from repro.core.types import register_result_spec

        @register_result_spec
        class Sloppy:
            cache = []
        """, RegistryHygieneRule())
    msgs = [f.message for f in rep.active]
    assert len(msgs) == 2
    assert any("frozen dataclass" in m for m in msgs)
    assert any("mutable class-level default" in m for m in msgs)


def test_registry_hygiene_accepts_frozen_dataclass(tmp_path):
    rep = lint_one(tmp_path, "repro/core/good_spec.py", """\
        import dataclasses

        from repro.core.types import register_result_spec

        @register_result_spec
        @dataclasses.dataclass(frozen=True)
        class Tidy:
            k: int = 4
            dims: tuple = ()
        """, RegistryHygieneRule())
    assert rep.active == []


# -- suppressions and baseline ------------------------------------------------

def test_inline_suppression_moves_finding_out_of_active(tmp_path):
    rep = lint_one(tmp_path, "repro/models/sup.py", """\
        NEG = -3.0e38  # mdrqlint: disable=sentinel
        POS = 3.0e38   # mdrqlint: disable=all
        """, SentinelRule())
    assert rep.active == []
    assert [f.rule for f in rep.suppressed] == ["sentinel", "sentinel"]
    assert rep.exit_code == 0


def test_baseline_round_trip(tmp_path):
    path = tmp_path / "repro" / "models" / "legacy.py"
    path.parent.mkdir(parents=True)
    path.write_text("OLD = -3.0e38\n")

    first = engine.run([path], [SentinelRule()])
    assert first.exit_code == 1 and len(first.active) == 1

    bl = tmp_path / "baseline.json"
    engine.write_baseline(first, bl)
    accepted = engine.load_baseline(bl)
    assert accepted == {first.active[0].baseline_key()}

    second = engine.run([path], [SentinelRule()], baseline=accepted)
    assert second.exit_code == 0
    assert second.active == [] and len(second.baselined) == 1

    # baseline keys carry no line numbers, so entries survive line drift
    path.write_text("# a new leading comment\nOLD = -3.0e38\n")
    third = engine.run([path], [SentinelRule()], baseline=accepted)
    assert third.exit_code == 0 and len(third.baselined) == 1


def test_cli_baseline_flags_round_trip(tmp_path, capsys):
    path = tmp_path / "repro" / "models" / "legacy.py"
    path.parent.mkdir(parents=True)
    path.write_text("OLD = -3.0e38\n")
    bl = tmp_path / "bl.json"

    assert lint_main([str(path), "--baseline", str(bl)]) == 1
    assert lint_main([str(path), "--baseline", str(bl),
                      "--write-baseline"]) == 0
    assert lint_main([str(path), "--baseline", str(bl)]) == 0
    out = capsys.readouterr().out
    assert "1 baselined" in out


def test_report_format_and_json(tmp_path):
    path = tmp_path / "repro" / "models" / "m.py"
    path.parent.mkdir(parents=True)
    path.write_text("NEG = -3.0e38\n")
    rep = engine.run([path], [SentinelRule()])
    line = rep.active[0].format()
    assert line.startswith(path.as_posix() + ":1 sentinel ")
    data = rep.to_json()
    assert data["n_files"] == 1
    assert data["findings"][0]["rule"] == "sentinel"


def test_parse_error_exits_2_not_1(tmp_path):
    """Broken tree != dirty tree: parse errors get their own exit code."""
    path = tmp_path / "repro" / "core" / "broken.py"
    path.parent.mkdir(parents=True)
    path.write_text("def oops(:\n")
    rep = engine.run([path], ALL_RULES)
    assert rep.exit_code == 2
    assert rep.active == []
    assert rep.errors[0].rule == "parse-error"


def test_multi_rule_suppression_comma_separated(tmp_path):
    src = """\
        import numpy as np
        from repro.kernels import ops

        def peek(x):
            return np.asarray(ops.multi_scan_reduce(x)), -3.0e38  # mdrqlint: disable=host-sync,sentinel
        """
    path = tmp_path / "repro" / "core" / "multi.py"
    path.parent.mkdir(parents=True)
    path.write_text(textwrap.dedent(src))
    rep = engine.run([path], ALL_RULES)
    assert rep.active == []
    assert sorted({f.rule for f in rep.suppressed}) == ["host-sync",
                                                        "sentinel"]


def test_stale_baseline_fails_and_prune_drops_it(tmp_path, capsys):
    path = tmp_path / "repro" / "models" / "legacy.py"
    path.parent.mkdir(parents=True)
    path.write_text("OLD = -3.0e38\n")
    bl = tmp_path / "bl.json"
    assert lint_main([str(path), "--baseline", str(bl),
                      "--write-baseline"]) == 0

    # debt paid: the finding is gone, but its waiver lingers -> exit 1
    path.write_text("OLD = 0.0\n")
    assert lint_main([str(path), "--baseline", str(bl)]) == 1
    assert "stale baseline entry" in capsys.readouterr().out

    assert lint_main([str(path), "--baseline", str(bl),
                      "--prune-baseline"]) == 0
    assert engine.load_baseline(bl) == set()
    assert lint_main([str(path), "--baseline", str(bl)]) == 0


# -- rule 7: thread-boundary --------------------------------------------------

def test_thread_boundary_flags_sync_and_parked_payloads(tmp_path):
    rep = lint_one(tmp_path, "repro/serve/bad_pipeline.py", """\
        from repro.kernels import ops
        from repro.serve.pipeline import device_stage

        class BadServer:
            @device_stage
            def flush(self):
                pb = self.engine.launch_batch(self._queries)
                host = ops.device_get(pb)       # sync on the wrong thread
                self._inflight = pb             # parked device value
                return host
        """, ThreadBoundaryRule())
    rules = [f.rule for f in rep.active]
    assert rules == ["thread-boundary", "thread-boundary"]
    assert "device_get" in rep.active[0].message
    assert "_inflight" in rep.active[1].message


def test_thread_boundary_taint_rides_wrappers(tmp_path):
    """A device payload wrapped in a window object is still a device value —
    parking the wrapper on self is the same cross-thread leak."""
    rep = lint_one(tmp_path, "repro/serve/bad_window.py", """\
        from repro.serve.pipeline import device_stage

        class BadServer:
            @device_stage
            def flush(self):
                pb = self.engine.launch_batch(self._queries)
                win = _Window(batch=pb, reason="size")
                self._last_window = win
        """, ThreadBoundaryRule())
    assert [f.rule for f in rep.active] == ["thread-boundary"]
    assert "_last_window" in rep.active[0].message


def test_thread_boundary_accepts_queue_handoff(tmp_path):
    """The sanctioned shape: the payload crosses via the backlog queue, and
    the finalizer stage owns the counted sync."""
    rep = lint_one(tmp_path, "repro/serve/good_pipeline.py", """\
        from repro.kernels import ops
        from repro.serve.pipeline import device_stage, finalizer_stage

        class GoodServer:
            @device_stage
            def flush(self):
                pb = self.engine.launch_batch(self._queries)
                win = _Window(batch=pb, reason="size")
                self._backlog.put(win)          # the one sanctioned crossing
                self.stats.n_flushes = self.stats.n_flushes + 1  # host data

            @finalizer_stage
            def _finalize_loop(self):
                win = self._backlog.get()
                host = ops.device_get(win.batch)  # finalizer owns the sync
                return host
        """, ThreadBoundaryRule())
    assert rep.active == []


# -- rules 8-10: Pallas kernel contracts --------------------------------------

def test_kernel_tile_flags_unasserted_grid_division(tmp_path):
    rep = lint_one(tmp_path, "repro/kernels/tiles.py", """\
        import jax.experimental.pallas as pl

        def launch(x, tile):
            return pl.pallas_call(kern, grid=(x.shape[0] // tile,))(x)
        """, KernelTileRule())
    assert [f.rule for f in rep.active] == ["kernel-tile"]
    assert "x.shape[0] // tile" in rep.active[0].message


def test_kernel_tile_accepts_asserted_grid_and_local_assign(tmp_path):
    rep = lint_one(tmp_path, "repro/kernels/tiles.py", """\
        import jax.experimental.pallas as pl

        def launch(x, tile):
            assert x.shape[0] % tile == 0, "pad first"
            grid = (x.shape[0] // tile,)
            return pl.pallas_call(kern, grid=grid)(x)
        """, KernelTileRule())
    assert rep.active == []


def test_kernel_dtype_flags_defaulted_creator_and_inf_fill(tmp_path):
    rep = lint_one(tmp_path, "repro/kernels/accum.py", """\
        import jax.numpy as jnp
        import jax.experimental.pallas as pl

        def kern(x_ref, o_ref):
            acc = jnp.zeros((8, 8))
            pad = jnp.full((8,), -jnp.inf, dtype=jnp.bfloat16)
            o_ref[...] = acc + pad

        def launch(x):
            return pl.pallas_call(kern, grid=(1,))(x)
        """, KernelDtypeRule())
    assert sorted(f.rule for f in rep.active) == ["kernel-dtype",
                                                  "kernel-dtype"]


def test_kernel_dtype_accepts_explicit_and_outside_kernel(tmp_path):
    rep = lint_one(tmp_path, "repro/kernels/accum.py", """\
        import functools
        import jax.numpy as jnp
        import jax.experimental.pallas as pl

        def kern(x_ref, o_ref, *, tile):
            acc = jnp.zeros((8, 8), jnp.float32)
            pad = jnp.full((8,), -jnp.inf, dtype=jnp.float32)
            o_ref[...] = acc + pad

        def launch(x):
            host_side = jnp.zeros((4,))  # not a kernel body: exempt
            return pl.pallas_call(
                functools.partial(kern, tile=8), grid=(1,))(x)
        """, KernelDtypeRule())
    assert rep.active == []


def test_note_trace_flags_jit_without_probe(tmp_path):
    rep = lint_one(tmp_path, "repro/core/jitted.py", """\
        import jax
        from repro.kernels import ops

        @jax.jit
        def silent(x):
            return x + 1

        def _loud(x):
            ops.note_trace("loud")
            return x + 1

        loud = jax.jit(_loud)
        """, NoteTraceRule())
    assert [f.rule for f in rep.active] == ["note-trace"]
    assert "silent" in rep.active[0].message


def test_note_trace_accepts_probe_after_docstring(tmp_path):
    rep = lint_one(tmp_path, "repro/core/jitted.py", """\
        import jax
        from repro.kernels import ops

        @jax.jit
        def fine(x):
            '''Docstrings don't count as the first statement.'''
            ops.note_trace("fine")
            return x + 1
        """, NoteTraceRule())
    assert rep.active == []


# -- the shipped tree lints clean ---------------------------------------------

def test_shipped_tree_is_clean():
    """src/, tests/, benchmarks/ and examples/ carry no active findings
    under the checked-in baseline — the same invocation CI runs via
    ``make lint-mdrq``."""
    rc = lint_main([str(REPO / p)
                    for p in ("src", "tests", "benchmarks", "examples")])
    assert rc == 0


def test_all_rules_have_ids_and_docs():
    ids = [r.rule_id for r in ALL_RULES]
    assert len(ids) == len(set(ids)) == 10
    assert all(r.doc for r in ALL_RULES)


if __name__ == "__main__":
    raise SystemExit(pytest.main([__file__, "-q"]))
