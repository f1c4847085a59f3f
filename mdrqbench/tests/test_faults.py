"""A run whose timed path is broken underneath has to come out not correct.

Each fault wraps the engine's ``launch_batch`` so that what the server
finalizes is wrong in one way, then drives the rest of a run (everything but
the look for a chip) at a tiny size.
"""
import time

import numpy as np
import pytest

from mdrqbench import harness
from mdrqbench.tests.small import cell_names, small_cell


def _alter(res):
    if isinstance(res, np.ndarray):
        return res[1:] if res.size else np.array([0], np.int64)
    return res + 1


def _wrap_finalize(engine, change):
    inner = engine.launch_batch

    def launch_batch(*args, **kwargs):
        pb = inner(*args, **kwargs)
        fin = pb.finalize
        pb.finalize = lambda: change(fin())
        return pb
    engine.launch_batch = launch_batch


def one_answer_altered(engine):
    """A wrong answer where it is produced: the first of each window."""
    _wrap_finalize(engine, lambda res: [_alter(res[0])] + res[1:])


def half_left_out(engine):
    """Half of each window answered as if nothing matched."""
    def change(res):
        half = len(res) // 2 or 1
        empty = [np.empty((0,), np.int64) if isinstance(r, np.ndarray) else 0
                 for r in res[:half]]
        return empty + res[half:]
    _wrap_finalize(engine, change)


def window_lost(engine):
    """Every other window's answers never come: its finalize raises."""
    state = {"n": 0}

    def change(res):
        state["n"] += 1
        if state["n"] % 2:
            raise RuntimeError("lost window")
        return res
    _wrap_finalize(engine, change)


@pytest.mark.parametrize("fault", [one_answer_altered, half_left_out,
                                   window_lost])
@pytest.mark.parametrize("name", cell_names())
def test_broken_timed_path_is_not_correct(name, fault):
    out = harness.run_cell(small_cell(name), 99, 1.0, False,
                           time.perf_counter(), fault=fault)
    assert out["correct"] is False, out["limits"]
    bad = [k for k, v in out["limits"].items() if v["value"] > v["limit"]]
    assert bad
