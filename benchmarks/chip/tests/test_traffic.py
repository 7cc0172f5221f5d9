"""Seeds of a run's traffic: any whole seed, the same streams each time."""

import numpy as np

from benchmarks.chip import traffic as T


def test_sub_seeds_fit_in_31_bits_for_any_seed():
    for seed in (0, 2 ** 31 + 5, 2 ** 62):
        s = T.sub_seed(seed, 3)
        assert 0 <= s < 2 ** 31
        assert s == T.sub_seed(seed, 3) and s != T.sub_seed(seed, 4)


def test_streams_repeat_per_seed_and_differ_across_purposes():
    a = T.rng_for(2 ** 40 + 7, 21).permutation(10)
    assert np.array_equal(a, T.rng_for(2 ** 40 + 7, 21).permutation(10))
    assert not np.array_equal(a, T.rng_for(2 ** 40 + 7, 22).permutation(10))
