"""The block-parallel GMRQB generator: one seed gives one table, whatever
the thread count, with the attributes ``gen/gmrqb.py`` draws."""
import numpy as np
import pytest

from mdrqbench.gen import gmrqb, gmrqb_blocks

CATEGORICAL = (0, 4, 8, 9, 10, 11, 12, 13, 14, 15, 16, 17)
CONTINUOUS = (1, 2, 3, 6, 18)   # location, quality, depth, frequency, age


def test_one_seed_one_table_at_any_thread_count():
    cfg = {"rows": 2 * gmrqb_blocks.BLOCK + 777, "dims": 19}
    one = gmrqb_blocks.build(cfg, np.random.default_rng(2**40 + 3), threads=1)
    four = gmrqb_blocks.build(cfg, np.random.default_rng(2**40 + 3),
                              threads=4)
    assert one.shape == (19, cfg["rows"]) and one.dtype == np.float32
    assert np.array_equal(one, four)
    other = gmrqb_blocks.build(cfg, np.random.default_rng(2**40 + 4),
                               threads=4)
    assert not np.array_equal(one, other)


@pytest.fixture(scope="module")
def both():
    cfg = {"rows": 1_000_000, "dims": 19}
    return (gmrqb.build(cfg, np.random.default_rng(1)),
            gmrqb_blocks.build(cfg, np.random.default_rng(2)))


def test_domains_and_cardinalities_match_gmrqb(both):
    ref, blk = both
    for d in CATEGORICAL:
        values, n_ref = np.unique(ref[d], return_counts=True)
        values_blk, n_blk = np.unique(blk[d], return_counts=True)
        assert np.array_equal(values, values_blk), d
        # shares of each value agree to within sampling noise
        assert np.abs(n_ref - n_blk).max() / ref.shape[1] < 0.005, d
    assert ref[0].min() == blk[0].min() == 1 and blk[0].max() == 23
    assert blk[12].max() < 2504
    for d in CONTINUOUS:
        span = float(ref[d].max() - ref[d].min())
        q = [0.01, 0.1, 0.5, 0.9, 0.99]
        assert np.abs(np.quantile(ref[d], q) - np.quantile(blk[d], q)).max() \
            < 0.005 * span, d
        assert ref[d].min() >= 0 and blk[d].min() >= 0
    assert blk[1].max() <= gmrqb.LOC_MAX and blk[18].min() >= 1
    assert blk[18].max() <= 90 and blk[3].max() <= 5000 and blk[2].max() <= 100
    # derived attributes follow their source as in gmrqb.py
    assert np.array_equal(blk[7], np.ceil(blk[6] * 5008.0) + 1.0)
    assert np.array_equal(blk[14], blk[12] // 1.4)
    assert np.array_equal(blk[15], blk[12] % 26)


def test_variation_id_is_a_permutation(both):
    _, blk = both
    ids = blk[5].astype(np.int64)
    assert np.array_equal(np.sort(ids), np.arange(blk.shape[1]))
    assert not np.array_equal(ids, np.arange(blk.shape[1]))
