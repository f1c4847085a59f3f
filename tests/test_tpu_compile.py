"""AOT compiles of the main path's jitted ops for a described TPU v5e.

Nothing runs: each op is lowered and compiled by the TPU compiler for a
``v5e:2x2`` topology that is described, not attached, at GMRQB's real width
(m_pad = 24, n_pad = 10M rounded up to the tile), at the smallest and largest
query buckets. Mosaic refuses here what it would refuse on the chip —
misaligned blocks, too much VMEM, lowering gaps — at no chip time. The jitted
bodies are called with ``interpret=False`` explicitly, because
``jax.default_backend()`` is the CPU in this process.

The topology is described inside a module fixture (never at import), which
skips where no TPU compiler is installed. The persistent compilation cache is
off for the module: entries compiled for a described chip cannot be read
back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import Agg, Count, Ids, Mask, TopK
from repro.core import distributed
from repro.kernels import ops

M_PAD = 24                                  # GMRQB: 19 dims -> 3 sublane groups
TILE_N = 1024
N_PAD = -(-10_000_000 // TILE_N) * TILE_N   # 10M objects, tile-aligned
BUCKETS = (1, 128)                          # smallest and largest query bucket
F32, I32, I8 = jnp.float32, jnp.int32, jnp.int8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev_cache = jax.config.jax_enable_compilation_cache
    prev_backend = ops.set_backend("auto")  # the Pallas path, not the refs
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", prev_cache)
    cc.reset_cache()
    ops.set_backend(prev_backend)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(op, *args, **statics):
    """Lower + compile the op's jitted body for the described chip."""
    compiled = op.__wrapped__.lower(*args, interpret=False, tile_n=TILE_N,
                                    **statics).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the kernel is in there
    return compiled


def _scan_args(q_n, sh):
    return (_sds((M_PAD, N_PAD), F32, sh), _sds((M_PAD, q_n), F32, sh),
            _sds((M_PAD, q_n), F32, sh))


@pytest.mark.parametrize("q_n", BUCKETS)
@pytest.mark.parametrize("spec", [Ids(), Mask(), Count(), TopK(k=10, dim=2),
                                  Agg("sum", dim=3), Agg("min", dim=3)],
                         ids=str)
def test_multi_scan_reduce_compiles(one_chip, q_n, spec):
    _compile(ops.multi_scan_reduce, *_scan_args(q_n, one_chip), spec=spec)


def test_multi_scan_reduce_with_delta_compiles(one_chip):
    """The live-ingest variant: delta block + base tombstones, same launch."""
    _compile(ops.multi_scan_reduce, *_scan_args(32, one_chip),
             _sds((M_PAD, 4 * TILE_N), F32, one_chip),
             _sds((N_PAD,), I8, one_chip), spec=Count())


@pytest.mark.parametrize("q_n", BUCKETS)
def test_multi_scan_vertical_reduce_compiles(one_chip, q_n):
    _compile(ops.multi_scan_vertical_reduce, *_scan_args(q_n, one_chip),
             spec=Ids())


@pytest.mark.parametrize("kernel,m_pad,q_n", [
    # GMRQB (m_pad 24) and SYNT-UNI at m=5 (m_pad 8), the benchmark's buckets
    *((k, m_pad, q_n) for k in ("full", "vertical") for m_pad in (24, 8)
      for q_n in (16, 32, 64, 128)),
    # SYNT-UNI at m=50 and m=100 (fig. 5), at the largest bucket
    *((k, m_pad, 128) for k in ("full", "vertical") for m_pad in (56, 104)),
])
def test_row_skipping_scan_compiles(one_chip, kernel, m_pad, q_n):
    """The Count scans at 10M rows, whose query chunks compare only the rows
    they flag (row lists read from SMEM)."""
    data = _sds((m_pad, N_PAD), F32, one_chip)
    lo = up = _sds((m_pad, q_n), F32, one_chip)
    op = ops.multi_scan_reduce if kernel == "full" else \
        ops.multi_scan_vertical_reduce
    _compile(op, data, lo, up, spec=Count())


@pytest.mark.parametrize("q_n", BUCKETS)
def test_multi_visit_reduce_compiles(one_chip, q_n):
    data, lo, up = _scan_args(q_n, one_chip)
    n_visit = q_n * 2048                # pow2 visit bucket
    visits = [_sds((n_visit,), I32, one_chip) for _ in range(3)]
    visit_index = _sds((1, 1), I32, one_chip)
    _compile(ops.multi_visit_reduce, data, *visits, visit_index, lo, up,
             spec=Ids(), n_queries=q_n)


def test_range_scan_vertical_compiles(one_chip):
    """The partial-match scan a single ``engine.query`` runs: Q=1."""
    data, lo, up = _scan_args(1, one_chip)
    _compile(ops.multi_scan_vertical_reduce, data, lo, up, spec=Ids())


def test_range_scan_visit_compiles(one_chip):
    """The two-phase visit launch a single ``engine.query`` runs, at the
    largest pow2 visit bucket one query reaches over 10M rows."""
    data, lo, up = _scan_args(1, one_chip)
    visits = [_sds((16384,), I32, one_chip) for _ in range(3)]
    _compile(ops.multi_visit_reduce, data, *visits,
             _sds((1, 1), I32, one_chip), lo, up, spec=Ids(), n_queries=1)


@pytest.mark.parametrize("q_n", BUCKETS)
def test_multi_va_filter_compiles(one_chip, q_n):
    packed = _sds((2, N_PAD), I32, one_chip)   # 19 dims x 2 bits -> 2 words
    cells = _sds((M_PAD, q_n), I32, one_chip)
    _compile(ops.multi_va_filter, packed, cells, cells, m=19,
             block_n=TILE_N)


@pytest.mark.parametrize("spec", [Ids(), Count()], ids=str)
def test_distributed_multi_reduce_compiles(topo, spec):
    """The sharded scan on a 4-chip data mesh: one kernel per shard; Count
    merges through one all-reduce, Ids masks stay sharded."""
    mesh = Mesh(topo.devices[:4], ("data",))
    n_pad = -(-N_PAD // (4 * TILE_N)) * 4 * TILE_N   # whole tiles per shard
    data = _sds((M_PAD, n_pad), F32, NamedSharding(mesh, P(None, "data")))
    bounds = _sds((M_PAD, 32), F32, NamedSharding(mesh, P()))
    compiled = distributed.distributed_multi_reduce.__wrapped__.lower(
        mesh, data, bounds, bounds, spec=spec, tile_n=TILE_N,
        interpret=False).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert ("all-reduce" in text) == (spec == Count())


@pytest.mark.parametrize("q_n", (16, 32, 64, 128))
def test_sharded_count_compiles_at_200m_rows(topo, q_n):
    """The sharded Count of GMRQB at 2e8 rows on the 2x2 (the
    ``gmrqb-200m-4chip`` cell): 50,000,896 rows a chip, whose (Q, n) int8
    mask passes 2^31 elements at Q=128. Table and mask fit a chip."""
    mesh = Mesh(topo.devices[:4], ("data",))
    n_pad = 200_003_584
    data = _sds((M_PAD, n_pad), F32, NamedSharding(mesh, P(None, "data")))
    bounds = _sds((M_PAD, q_n), F32, NamedSharding(mesh, P()))
    compiled = distributed.distributed_multi_reduce.__wrapped__.lower(
        mesh, data, bounds, bounds, spec=Count(), tile_n=TILE_N,
        interpret=False).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= M_PAD * n_pad // 4 * 4
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        <= distributed.scan_bytes_per_device(19, 200_000_000, 4, TILE_N) \
        + (1 << 20)
