"""Seeded weights agree between the device twin and the host twin to the bit;
traffic is the same work under every seed."""
import numpy as np
import pytest

import seeded
import traffic_gen


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 12345, 2 ** 33 + 5])
def test_table_and_rows_agree_bitwise(seed):
    table = np.asarray(seeded.table_jax(seed, 3, (1000, 128), 1 / 128))
    rows = np.array([0, 5, 999, 17, 5])
    assert table.dtype == np.float32
    assert np.array_equal(table[rows],
                          seeded.rows_np(seed, 3, rows, 128, 1 / 128))
    padded = np.asarray(seeded.table_jax(seed, 4, (1008, 128), 1e-5,
                                         "positive", live_rows=1000))
    assert not padded[1000:].any() and padded[:1000].min() >= 0
    assert np.array_equal(
        padded[:1000],
        seeded.rows_np(seed, 4, np.arange(1000), 128, 1e-5, "positive"))


def test_streams_and_seeds_differ():
    a = seeded.rows_np(1, 0, [3], 8, 1.0)
    assert not np.array_equal(a, seeded.rows_np(1, 1, [3], 8, 1.0))
    assert not np.array_equal(a, seeded.rows_np(2, 0, [3], 8, 1.0))
    assert not np.array_equal(a, seeded.rows_np(1 + 2 ** 32, 0, [3], 8, 1.0))
    assert np.array_equal(a, seeded.rows_np(1, 0, [3], 8, 1.0))


def test_open_loop_offers_the_same_work_under_every_seed():
    d1, s1 = traffic_gen.open_loop_schedule(1, 400, 30, 8, 256)
    d2, s2 = traffic_gen.open_loop_schedule(2 ** 31 + 9, 400, 30, 8, 256)
    assert len(d1) == len(d2) == 12000
    assert sorted(s1) == sorted(s2) and not np.array_equal(s1, s2)
    assert (np.diff(d1) >= 0).all() and 0 <= d1[0] and d1[-1] < 30
    assert s1.min() == 8 and s1.max() == 256 and 65 < s1.mean() < 80
    d3, s3 = traffic_gen.open_loop_schedule(1, 400, 30, 8, 256)
    assert np.array_equal(d1, d3) and np.array_equal(s1, s3)


def test_corpus_and_impressions_repeat_from_the_seed():
    cdf = traffic_gen.zipf_rank_cdf(5000)
    a = traffic_gen.corpus_blocks(3, cdf, 2, 4, 50)
    assert a.shape == (2, 4, 50) and a.dtype == np.int32
    assert a.min() >= 0 and a.max() < 5000
    assert np.array_equal(a, traffic_gen.corpus_blocks(3, cdf, 2, 4, 50))
    assert not np.array_equal(a, traffic_gen.corpus_blocks(4, cdf, 2, 4, 50))
    b = traffic_gen.impression_batches(5, 2, 32, 3, 100, 4, 1.2, 32, 0.25,
                                       1.0, -0.5)
    ids, dense, labels = b[0]
    assert ids.shape == (32, 3) and dense.shape == (32, 4)
    assert set(np.unique(labels)) <= {0.0, 1.0} and ids.max() < 100
    again = traffic_gen.impression_batches(5, 2, 32, 3, 100, 4, 1.2, 32,
                                           0.25, 1.0, -0.5)
    assert all(np.array_equal(x, y) for p, q in zip(b, again)
               for x, y in zip(p, q))
