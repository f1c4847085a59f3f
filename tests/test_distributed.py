"""Distributed MDRQ + gradient compression.

Single-device shard_map equality runs in-process; true multi-device behaviour
(8 host devices) runs in a subprocess so the main test process keeps its
1-device view (XLA locks the device count at first init)."""
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import Count, MDRQEngine, RangeQuery, match_ids_np
from repro.core.distributed import make_data_mesh


def test_distributed_scan_single_device(uni5):
    """``engine.query`` on a meshed engine over this process's devices."""
    eng = MDRQEngine(uni5, structures=("scan",), mesh=make_data_mesh())
    rng = np.random.default_rng(0)
    for _ in range(3):
        i, j = rng.integers(uni5.n), rng.integers(uni5.n)
        q = RangeQuery(np.minimum(uni5.cols[:, i], uni5.cols[:, j]),
                       np.maximum(uni5.cols[:, i], uni5.cols[:, j]))
        oracle = match_ids_np(uni5.cols, q)
        np.testing.assert_array_equal(eng.query(q, "scan"), oracle)
        assert eng.query(q, "scan", spec=Count()) == oracle.size


MULTI_DEVICE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax, jax.numpy as jnp
    from repro.core import (Count, Dataset, MDRQEngine, RangeQuery,
                            match_ids_np)
    from repro.core.distributed import make_data_mesh

    assert len(jax.devices()) == 8
    rng = np.random.default_rng(7)
    ds = Dataset(rng.random((5, 40000), dtype=np.float32))
    eng = MDRQEngine(ds, structures=("scan",), mesh=make_data_mesh(8))
    for t in range(5):
        i, j = rng.integers(ds.n), rng.integers(ds.n)
        q = RangeQuery(np.minimum(ds.cols[:, i], ds.cols[:, j]),
                       np.maximum(ds.cols[:, i], ds.cols[:, j]))
        oracle = match_ids_np(ds.cols, q)
        assert np.array_equal(eng.query(q, "scan"), oracle), t
        assert eng.query(q, "scan", spec=Count()) == oracle.size
    print("MULTI_DEVICE_OK")
""")

COMPRESSION_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.core.distributed import shard_map_compat
    from repro.train import compressed_psum

    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    rng = np.random.default_rng(0)
    g_local = rng.normal(size=(8, 256, 64)).astype(np.float32)

    def body(g):
        return compressed_psum({"w": g[0]}, "data")["w"]

    out = jax.jit(shard_map_compat(body, mesh=mesh, in_specs=P("data"),
                                   out_specs=P()))(jnp.asarray(g_local))
    exact = g_local.mean(axis=0)
    rel = np.abs(np.asarray(out) - exact).max() / np.abs(exact).max()
    assert rel < 0.02, rel   # int8 quantization error bound
    print("COMPRESSION_OK", rel)
""")


@pytest.mark.parametrize("script,marker", [
    (MULTI_DEVICE_SCRIPT, "MULTI_DEVICE_OK"),
    (COMPRESSION_SCRIPT, "COMPRESSION_OK"),
])
def test_multi_device_subprocess(script, marker):
    import os
    from pathlib import Path
    root = Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["PYTHONPATH"] = str(root / "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=600, env=env, cwd=root)
    assert marker in r.stdout, f"stdout={r.stdout}\nstderr={r.stderr[-2000:]}"
