"""The knee sweep's reading of one rate: when the queue counts as growing,
and how many requests were held in slots on average."""

from types import SimpleNamespace

import pytest

from bench import sweep


def _row(rate, mid, end):
    return {"rate": rate, "queued_mid": mid, "queued_at_end": end}


def test_queue_grew_only_when_it_rose_past_a_transient():
    assert sweep.queue_grew(_row(2.0, 3, 13), 120)      # rose, above 5% of 240
    assert not sweep.queue_grew(_row(2.0, 3, 10), 120)  # rose, but 10 of 240 is a burst
    assert not sweep.queue_grew(_row(2.0, 30, 20), 120)  # fell
    assert not sweep.queue_grew(_row(2.0, 0, 0), 120)


def test_in_slots_is_the_mean_occupancy_over_the_interval():
    recs = [SimpleNamespace(admitted=0.0, done=5.0),    # 5 s inside [0, 10)
            SimpleNamespace(admitted=8.0, done=None),   # still held at 10: 2 s
            SimpleNamespace(admitted=-4.0, done=1.0),   # 1 s inside
            SimpleNamespace(admitted=None, done=None),  # never admitted
            SimpleNamespace(admitted=-9.0, done=-1.0)]  # done before the interval
    assert sweep.in_slots(recs, 0.0, 10.0) == pytest.approx(0.8)
