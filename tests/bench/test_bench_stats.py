"""Percentile and spread arithmetic, against hand counts."""

import pytest

from bench import stats


def test_percentile_exact_ranks_and_interpolation():
    xs = [5.0, 1.0, 4.0, 2.0, 3.0]
    assert stats.percentile(xs, 0) == 1.0
    assert stats.percentile(xs, 50) == 3.0
    assert stats.percentile(xs, 100) == 5.0
    # rank (5 - 1) * 0.9 = 3.6: 4 + 0.6 * (5 - 4)
    assert stats.percentile(xs, 90) == pytest.approx(4.6)
    # 101 values 0..100: the p-th percentile is p itself
    ys = [float(i) for i in range(101)]
    assert stats.percentile(ys, 90) == 90.0
    assert stats.percentile(ys, 50) == 50.0
    assert stats.percentile([7.0], 90) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_spread_is_quartile_distance_over_median():
    # statistics.quantiles(n=4) of 1..7 (exclusive method): 2, 4, 6
    assert stats.spread([1, 2, 3, 4, 5, 6, 7]) == pytest.approx((6 - 2) / 4)
    assert stats.spread([10.0] * 6) == 0.0
