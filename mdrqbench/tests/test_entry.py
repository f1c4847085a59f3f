"""The entry refuses to run without a TPU, and the peaks table refuses an
unknown device."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from mdrqbench import harness, roofline


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "mdrqbench/run.py", "--workload",
         "gmrqb10m-mixed-count-closed", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _no_result_line(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "metrics" in obj:
            return False
    return True


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(harness.ROOT)
    assert p.returncode != 0
    assert _no_result_line(p.stdout)
    assert "no TPU" in p.stderr


def test_bare_benchmark_directory_exits_nonzero(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH_DIR, tmp_path / "mdrqbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert _no_result_line(p.stdout)


def test_unknown_device_kind_is_an_error():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(roofline.UnknownDevice):
        roofline.peaks("TPU v99")
