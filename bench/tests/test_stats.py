import statistics

import pytest

import stats


def test_spread_uses_statistics_quartiles():
    vals = [10.0, 11.0, 9.5, 10.2, 10.8, 9.9]
    q1, med, q3 = statistics.quantiles(vals, n=4)
    assert stats.spread(vals) == pytest.approx((q3 - q1) / med)


def test_rate_between_edges_closes_on_a_completion():
    edges = [(1.0, 4.0), (2.0, 4.0), (3.5, 4.0), (5.0, 4.0), (6.5, 4.0)]
    assert stats.rate_between_edges(edges, 1.0, 3.0) == (12.0, 4.0)
    assert stats.rate_between_edges(edges, 1.0, 10.0) is None
    # the opening edge itself is not counted
    assert stats.rate_between_edges(edges, 2.0, 1.0) == (4.0, 1.5)


def test_time_average_of_a_step_function():
    samples = [(0.0, 2.0), (1.0, 4.0), (3.0, 0.0)]
    assert stats.time_average(samples, 0.0, 4.0) == pytest.approx(
        (2 * 1 + 4 * 2 + 0 * 1) / 4)
    assert stats.time_average(samples, 0.5, 2.0) == pytest.approx(
        (2 * 0.5 + 4 * 1.0) / 1.5)
    assert stats.time_average([], 0.0, 1.0) is None
