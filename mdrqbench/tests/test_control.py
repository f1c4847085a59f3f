"""The control (the reference in bfloat16, in the program's place) has to
fail the comparison that decides ``correct``; the float32 reference has to
pass it."""
import numpy as np
import pytest

from mdrqbench import check, control, gen, harness, loads, reference, specs
from mdrqbench.tests.small import cell_names, small_cell


@pytest.mark.parametrize("name", cell_names())
def test_bfloat16_control_is_rejected(name):
    cell = small_cell(name)
    cell.cfg = dict(cell.cfg, rows=200_000)
    cell.traffic = dict(cell.traffic, pool=256, rate_qps=256,
                        check={"sample": 256, "per_path": 8})
    out = control.control_answers(cell, 2**32 + 5, 1.0)
    w = out["limits"]["wrong_answers"]
    assert w["value"] > w["limit"], out


def test_float32_reference_passes_its_own_check():
    cell = small_cell(cell_names()[0])
    rng = np.random.default_rng(5)
    cols = gen.load(cell.cfg["generator"]).build(cell.cfg, rng)
    lower, upper = harness.make_pool(cell, cols, rng, 40)
    log = loads.Log()
    for i in range(40):
        k = log.add(i, 0.0, 0.0)
        log.t_done[k] = 0.0
        log.result[k] = specs.load("ids").answer(
            reference.match_ids(cols, lower[i], upper[i]), cols)
    out = check.check(cols, lower, upper, log, np.arange(40), {}, "ids",
                      {"sample": 40, "per_path": 4}, rng)
    assert out["limits"]["wrong_answers"]["value"] == 0
    assert out["n_checked"] == 40
