"""Percentile arithmetic over every request of a window."""

from types import SimpleNamespace as R

import pytest

from bench import stats


def test_percentile_interpolates_between_order_statistics():
    assert stats.percentile([1.0, 2.0, 3.0, 4.0, 5.0], 50) == 3.0
    assert stats.percentile(list(range(101)), 95) == 95.0
    assert stats.percentile([0.0, 10.0], 95) == pytest.approx(9.5)
    assert stats.percentile([], 95) is None


def test_ttft_counts_unanswered_requests_at_their_wait_so_far():
    reqs = [
        R(due=0.5, times=[1.0, 1.1]),   # before the window: not counted
        R(due=2.0, times=[2.5, 2.6]),   # answered in the window
        R(due=3.0, times=[]),           # never answered: waits until 10
        R(due=4.0, times=[11.0]),       # answered after the close: waits until 10
        R(due=10.0, times=[10.5]),      # due at the close: outside
    ]
    assert sorted(stats.ttft_samples(reqs, 1.0, 10.0)) == [0.5, 6.0, 7.0]
    assert stats.percentile(stats.ttft_samples(reqs, 1.0, 10.0), 100) == 7.0


def test_itl_takes_gaps_with_both_tokens_in_the_window():
    reqs = [R(due=0.0, times=[0.5, 1.5, 2.0, 2.75]), R(due=1.0, times=[3.0, 9.0, 10.5])]
    assert sorted(stats.itl_samples(reqs, 1.0, 10.0)) == [0.5, 0.75, 6.0]
    assert stats.tokens_in(reqs, 1.0, 10.0) == 5
