"""End-to-end system tests: the paper's headline experiment in miniature, the
framework integration path (MDRQ filter -> train -> checkpoint -> serve), and
the dry-run machinery on a small subprocess mesh (compile AND execute)."""
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.core import Dataset, MDRQEngine, RangeQuery, match_ids_np
from repro.data import synthetic


def test_paper_headline_selectivity_ordering(uni5):
    """Fig. 6 in miniature: at high selectivity the tree index must visit far
    fewer blocks than a scan touches; at low selectivity nearly all blocks.
    (Timing claims are benchmarks' business; block-visit counts are exact.)"""
    eng = MDRQEngine(uni5, tile_n=512)
    n_blocks = -(-uni5.n // 512)
    rng = np.random.default_rng(0)
    q_hi = synthetic.selectivity_targeted_query(uni5, 0.0005, rng)
    eng.query(q_hi, "kdtree")
    visited_hi = eng.kdtree.last_visited_blocks
    q_lo = synthetic.selectivity_targeted_query(uni5, 0.5, rng)
    eng.query(q_lo, "kdtree")
    visited_lo = eng.kdtree.last_visited_blocks
    assert visited_hi <= n_blocks * 0.35, (visited_hi, n_blocks)
    assert visited_lo >= n_blocks * 0.5, (visited_lo, n_blocks)


def test_vafile_prunes_exact_compares(uni19):
    import jax.numpy as jnp
    from repro.core import QueryBatch
    from repro.kernels import ops
    eng = MDRQEngine(uni19, tile_n=512)
    rng = np.random.default_rng(1)
    q = synthetic.selectivity_targeted_query(uni19, 1e-4, rng)
    ids = eng.query(q, "vafile")
    np.testing.assert_array_equal(ids, match_ids_np(uni19.cols, q))
    # the phase-1 candidates of that query, before the per-block reduction
    va = eng.vafile
    lo, hi = va.query_cells_batch(QueryBatch.from_queries([q]))
    cand = np.asarray(ops.multi_va_filter(va.packed_dev, jnp.asarray(lo),
                                          jnp.asarray(hi), m=va.m,
                                          tile_n=va.tile_n))[0, :va.n]
    assert cand.mean() < 0.05  # 19-dim prefilter bites


def test_cross_dataset_workloads():
    """Paper Table 2 datasets: engines agree with the oracle on all of them."""
    for ds in (synthetic.synt_uni(5000, 5, 0),
               synthetic.synt_clust(5000, 5, 10, 0),
               synthetic.power(5000, 0)):
        eng = MDRQEngine(ds, tile_n=512)
        for q in synthetic.workload(ds, 5, seed=3):
            oracle = match_ids_np(ds.cols, q)
            for meth in ("scan", "kdtree", "rstar", "vafile"):
                np.testing.assert_array_equal(eng.query(q, meth), oracle)


DRYRUN_MINI = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.models.registry import build_model, sharding_rules
    from repro.models.params import sharding_tree
    from repro.train import OptConfig, init_opt_state, make_train_step
    from repro.train.optimizer import opt_state_pspecs

    mesh = jax.make_mesh((2, 4), ("data", "model"))
    cfg = get_config("smollm_360m").reduced().replace(
        d_model=64, n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=512)
    model = build_model(cfg)
    rules = dict(sharding_rules(cfg, tp=4))
    rules.update(heads="model", kv_heads="model")
    params_abs = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    param_sh = sharding_tree(params_abs, mesh, rules)
    opt_abs = jax.eval_shape(init_opt_state, params_abs)
    opt_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                          opt_state_pspecs(params_abs, rules, data_size=2),
                          is_leaf=lambda x: isinstance(x, P))
    batch = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
             "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
    bs = {k: NamedSharding(mesh, P("data", None)) for k in batch}
    step = make_train_step(model, OptConfig())
    low = jax.jit(step, in_shardings=(param_sh, opt_sh, bs),
                  donate_argnums=(0, 1)).lower(params_abs, opt_abs, batch)
    comp = low.compile()
    ca = comp.cost_analysis()
    if isinstance(ca, (list, tuple)):  # older JAX returns one dict per device
        ca = ca[0]
    assert ca.get("flops", 0) > 0
    # ALSO execute it for real on the 8-device mesh (not just compile)
    params = jax.device_put(model.init(jax.random.PRNGKey(0)), param_sh)
    opt = jax.device_put(init_opt_state(params), opt_sh)
    rngn = np.random.default_rng(0)
    real = {k: jax.device_put(jnp.asarray(rngn.integers(0, 512, (8, 64)),
            jnp.int32), bs[k]) for k in batch}
    p2, o2, metrics = comp(params, opt, real)
    assert np.isfinite(float(metrics["loss"]))
    print("DRYRUN_MINI_OK", float(metrics["loss"]))
""")


def test_dryrun_machinery_small_mesh():
    """The dry-run path (shardings, lower, compile, cost analysis) on a 2x4
    subprocess mesh — and the compiled step actually EXECUTES multi-device."""
    import os
    root = Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["PYTHONPATH"] = str(root / "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", DRYRUN_MINI], capture_output=True,
                       text=True, timeout=900, env=env, cwd=root)
    assert "DRYRUN_MINI_OK" in r.stdout, f"{r.stdout}\n{r.stderr[-3000:]}"
