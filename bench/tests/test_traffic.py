"""The generator is a function of the mix and the seed alone."""

import numpy as np

import traffic

BIG_SEED = 2**40 + 12345

ONLINE = {"loop": "open", "arrivals": "poisson", "rate_per_s": 5000.0}


def test_row_stream_is_deterministic_per_seed_and_covers_the_pool():
    a = traffic.RowStream(100, BIG_SEED).take(250)
    b = traffic.RowStream(100, BIG_SEED)
    assert np.array_equal(a, np.concatenate([b.take(7), b.take(243)]))
    # every pass is a permutation: each seed serves the same rows
    for k in range(2):
        assert sorted(a[100 * k : 100 * (k + 1)]) == list(range(100))
    assert not np.array_equal(a, traffic.RowStream(100, BIG_SEED + 1).take(250))


def test_arrivals_are_deterministic_and_meet_their_rate():
    a = traffic.arrivals(ONLINE, BIG_SEED, 10.0)
    assert np.array_equal(a, traffic.arrivals(ONLINE, BIG_SEED, 10.0))
    assert np.all(np.diff(a) >= 0) and a[0] >= 0 and a[-1] < 10.0
    # Poisson count: mean 50,000, standard deviation about 224
    assert abs(a.size - 50_000) < 5 * 224
    gaps = np.diff(a)
    assert abs(gaps.mean() * 5000.0 - 1.0) < 0.03
    # exponential gaps: the coefficient of variation is 1
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.03
    assert not np.array_equal(a[:100], traffic.arrivals(ONLINE, BIG_SEED + 1, 10.0)[:100])
