"""Window arithmetic: a rate closes on a completion, and a stall inside
the window lowers it."""

import math

import pytest

import window


def test_window_closes_on_first_completion_at_or_after_the_length():
    done = [(1.0, 10), (2.0, 10), (2.9, 10), (3.4, 10), (4.0, 10)]
    assert window.close_on_completion(done, 3.0) == (3.4, 40)
    assert window.rate(done, 3.0) == pytest.approx(40 / 3.4)
    # a completion exactly at the length closes it
    assert window.close_on_completion(done, 2.9) == (2.9, 30)


def test_window_that_never_closes_has_no_rate():
    assert window.rate([(1.0, 10)], 3.0) is None


def test_a_stall_inside_the_window_lowers_the_rate():
    steady = [(0.5 * (i + 1), 8) for i in range(20)]
    stalled = [(t + (2.0 if t > 3.0 else 0.0), n) for t, n in steady]
    assert window.rate(stalled, 5.0) < window.rate(steady, 5.0)


def test_latency_tail_counts_a_failed_sample_as_never_done():
    due = [0.0, 1.0, 2.0, 3.0]
    assert window.latency_tail(due, [0.5, 1.5, 2.5, 3.5], 50) == pytest.approx(0.5)
    assert math.isinf(window.latency_tail(due, [0.5, 1.5, 2.5, None], 95))


def test_percentile_matches_linear_interpolation():
    assert window.percentile([1, 2, 3, 4, 5], 50) == 3
    assert window.percentile([0, 10], 95) == pytest.approx(9.5)


def test_spread_is_the_quartile_distance_over_the_median():
    assert window.spread([10, 10, 10, 10]) == 0
    assert window.spread([9, 10, 10, 11]) > 0
