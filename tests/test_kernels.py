"""Per-kernel validation: shape/dtype sweeps against the pure-jnp oracles
(ref.py) and the numpy ground truth. Outputs are discrete masks, so equality
is exact — assert_array_equal, not allclose."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import Count, Mask
from repro.core import types as T
from repro.kernels import ops, ref
from repro.kernels.va_filter import pack_codes


def _mk(m, n, dtype, seed=0):
    rng = np.random.default_rng(seed)
    cols = rng.random((m, n)).astype(np.float32)
    a, b = cols[:, rng.integers(n)], cols[:, rng.integers(n)]
    q = T.RangeQuery.complete(np.minimum(a, b), np.maximum(a, b))
    return cols, q, rng


def _one(q, m_pad, dtype):
    """(m_pad, 1) device bounds of a batch of one query."""
    return ops.batch_bounds_device(T.QueryBatch.from_queries([q]), m_pad,
                                   dtype)


@pytest.mark.parametrize("m", [1, 3, 5, 8, 19, 64, 100])
@pytest.mark.parametrize("n", [1000, 4096])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_range_scan_sweep(m, n, dtype):
    """The fused scan at Q=1 (the kernel behind a single ``engine.query``)."""
    cols, q, _ = _mk(m, n, dtype, seed=m * 1000 + n)
    padded, m0, n0 = ops.prepare_columnar(cols)
    data = jnp.asarray(padded, dtype)
    lo, up = _one(q, padded.shape[0], dtype)
    out = np.asarray(ops.multi_scan_reduce(data, lo, up, spec=Mask()))[0, :n0]
    oracle = np.asarray(ref.multi_scan_ref(data, lo, up))[0, :n0]
    np.testing.assert_array_equal(out, oracle)
    if dtype == jnp.float32:  # numpy ground truth only exact in f32
        np.testing.assert_array_equal(out.astype(bool), T.match_mask_np(cols, q))


@pytest.mark.parametrize("m", [2, 19])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_range_scan_visit_sweep(m, dtype):
    """The two-phase visit launch at Q=1: every visit on query 0."""
    cols, q, rng = _mk(m, 8192, dtype, seed=m)
    padded, _, n0 = ops.prepare_columnar(cols)
    data = jnp.asarray(padded, dtype)
    lo, up = _one(q, padded.shape[0], dtype)
    n_blocks = padded.shape[1] // 1024
    ids = np.concatenate([rng.permutation(n_blocks)[: n_blocks // 2],
                          [-1, -1]]).astype(np.int32)
    qids = jnp.zeros(ids.shape, jnp.int32)
    out = np.asarray(ops.multi_visit_reduce(
        data, qids, jnp.asarray(ids), jnp.asarray((ids >= 0).astype(np.int32)),
        jnp.zeros((1, 1), jnp.int32), lo, up, spec=Mask(), n_queries=1))
    blocks = data.reshape(data.shape[0], n_blocks, 1024).transpose(1, 0, 2)
    oracle = np.asarray(ref.multi_scan_blocks_ref(blocks, qids,
                                                  jnp.asarray(ids), lo, up))
    np.testing.assert_array_equal(out, oracle)


@pytest.mark.parametrize("m,n_q", [(5, 2), (19, 7), (64, 30)])
def test_range_scan_vertical_sweep(m, n_q):
    """The partial-match scan at Q=1, ``n_q`` of ``m`` dims bounded."""
    cols, _, rng = _mk(m, 5000, jnp.float32, seed=m + n_q)
    dims = np.sort(rng.choice(m, size=n_q, replace=False))
    preds = {int(d): tuple(sorted(rng.random(2).tolist())) for d in dims}
    q = T.RangeQuery.partial(m, preds)
    padded, _, n0 = ops.prepare_columnar(cols)
    data = jnp.asarray(padded)
    lo, up = _one(q, padded.shape[0], jnp.float32)
    out = np.asarray(ops.multi_scan_vertical_reduce(data, lo, up,
                                                    spec=Mask()))[0, :n0]
    np.testing.assert_array_equal(out.astype(bool), T.match_mask_np(cols, q))


@pytest.mark.parametrize("m", [1, 16, 19, 33, 48])
def test_va_filter_sweep(m):
    """The packed VA filter at Q=1 against the unpacked-code oracle."""
    rng = np.random.default_rng(m)
    n = 6144
    codes = rng.integers(0, 4, size=(m, n)).astype(np.uint8)
    qlo = rng.integers(0, 4, size=m).astype(np.int32)
    qhi = np.minimum(3, qlo + rng.integers(0, 4, size=m)).astype(np.int32)
    packed = T.pad_axis(pack_codes(codes), 1, 2048, 0)
    m_s = -(-m // 8) * 8
    qlo_p = np.zeros((m_s, 1), np.int32)
    qhi_p = np.full((m_s, 1), 3, np.int32)
    qlo_p[:m, 0], qhi_p[:m, 0] = qlo, qhi
    out = np.asarray(ops.multi_va_filter(jnp.asarray(packed), jnp.asarray(qlo_p),
                                         jnp.asarray(qhi_p), m))[0, :n]
    oracle = np.asarray(ref.va_filter_ref(jnp.asarray(codes), jnp.asarray(qlo),
                                          jnp.asarray(qhi)))
    packed_oracle = np.asarray(ref.multi_va_filter_packed_ref(
        jnp.asarray(pack_codes(codes)), jnp.asarray(qlo_p), jnp.asarray(qhi_p),
        m))[0]
    np.testing.assert_array_equal(out, oracle)
    np.testing.assert_array_equal(oracle, packed_oracle)


def test_match_all_and_match_none():
    cols = np.random.default_rng(0).random((4, 2048)).astype(np.float32)
    padded, _, n0 = ops.prepare_columnar(cols)
    data = jnp.asarray(padded)
    q_all = T.RangeQuery.partial(4, {})
    lo, up = _one(q_all, padded.shape[0], jnp.float32)
    assert np.asarray(ops.multi_scan_reduce(data, lo, up,
                                            spec=Mask()))[0, :n0].all()
    assert int(np.asarray(ops.multi_scan_reduce(data, lo, up,
                                                spec=Count()))[0]) == n0
    q_none = T.RangeQuery.partial(4, {0: (2.0, 3.0)})
    lo, up = _one(q_none, padded.shape[0], jnp.float32)
    assert not np.asarray(ops.multi_scan_reduce(data, lo, up,
                                                spec=Mask()))[0, :n0].any()
    assert int(np.asarray(ops.multi_scan_reduce(data, lo, up,
                                                spec=Count()))[0]) == 0


def test_padding_objects_never_match():
    """+inf sentinel objects must not match even match-all queries' bounds."""
    cols = np.zeros((3, 100), np.float32)
    padded, _, n0 = ops.prepare_columnar(cols)
    q = T.RangeQuery.complete([-1e30] * 3, [1e30] * 3)
    lo, up = _one(q, padded.shape[0], jnp.float32)
    out = np.asarray(ops.multi_scan_reduce(jnp.asarray(padded), lo, up,
                                           spec=Mask()))[0]
    assert out[:n0].all() and not out[n0:].any()


def test_finite_bounds_wider_dtype_stays_finite():
    """A wider comparison dtype (f64 under jax x64) must not overflow the
    float32 carrier arrays back to +-inf — extrema clamp to f32's range."""
    inf = np.full((4, 1), np.inf, np.float32)
    lo, up = T.finite_query_bounds(-inf, inf, dtype=np.float64)
    assert np.isfinite(lo).all() and np.isfinite(up).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_finite_bounds_respect_device_dtype(dtype):
    """Match-all bounds must stay finite *in the comparison dtype*: float32
    extrema round to +-inf under a bfloat16 cast, so the +inf object-padding
    sentinels would match and every padded-axis count reduction (the scan's
    Count, visit segment counts, distributed psum) would overcount."""
    inf = np.full((8, 1), np.inf, np.float32)
    lo, up = T.finite_query_bounds(-inf, inf, dtype=dtype)
    assert np.isfinite(np.asarray(jnp.asarray(lo, dtype), np.float32)).all()
    assert np.isfinite(np.asarray(jnp.asarray(up, dtype), np.float32)).all()

    cols = np.random.default_rng(5).random((3, 100)).astype(np.float32)
    padded, _, n0 = ops.prepare_columnar(cols)
    data = jnp.asarray(padded, dtype)
    q_all = T.RangeQuery.partial(3, {})
    qlo, qhi = _one(q_all, padded.shape[0], dtype)
    mask = np.asarray(ops.multi_scan_reduce(data, qlo, qhi, spec=Mask()))[0]
    count = ops.multi_scan_reduce(data, qlo, qhi, spec=Count())
    # on-device count sees exactly the real objects, never the sentinels
    assert int(np.asarray(count)[0]) == n0
    assert not mask[n0:].any()
