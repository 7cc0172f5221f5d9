"""Operation and byte counts against hand counts at small sizes."""

import pytest

from benchmarks.chip import costs


def test_packed_domination_hand_count():
    # 4 rows, 2 objectives: 16 ordered pairs x (2 "<=" + 2 "<" + 1 cv)
    w = costs.packed_domination(4, 2)
    assert w.ops == 16 * 5
    # rows read: 4 x (2 objectives + violation) x 4 bytes; bits: 16 / 8
    assert w.bytes == 4 * 3 * 4 + 2


def test_domination_counts_hand_count():
    w = costs.domination_counts(4, 2)
    assert w.ops == 16 * 6
    assert w.bytes == 4 * 4 * 4 + 4 * 4


def test_evaluation_hand_count():
    # 10 rows, 2 platforms, 1 link: 10 x (2 x 8 + 1) gathered values
    w = costs.evaluation(10, 2, 1)
    assert w.bytes == 4 * 170 and w.ops == 10 * 170


def test_search_generation_is_its_parts():
    g = costs.search_generation(4, 2, 2, 1)
    want = (costs.packed_domination(8, 2) + costs.Work(0, 8 * 8 / 8)
            + costs.evaluation(4, 2, 1))
    assert (g.ops, g.bytes) == (want.ops, want.bytes)


def test_roofline_picks_the_larger_term():
    peaks = {"ops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert costs.roofline_s(costs.Work(100, 50), peaks) == pytest.approx(5.0)
    assert costs.roofline_s(costs.Work(1000, 5), peaks) == pytest.approx(10.0)
