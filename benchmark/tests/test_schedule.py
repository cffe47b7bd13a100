"""The schedule: set-up rows first, the backlog handed out in polls of
at most ``poll_rows``, and the input ended at the deadline."""

import pytest

from benchmark.source import Schedule


class Clock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


def test_flood_serves_setup_rows_then_starts_the_window():
    clock = Clock()
    started = []
    s = Schedule(
        [{"rows": 200, "poll_rows": 150}, {"rows": 50, "poll_rows": 100}],
        100,
        5.0,
        clock,
        started.append,
    )
    assert [s.next_range() for _ in range(3)] == [(0, 150), (150, 200), (200, 250)]
    assert s.t0 is None and not started
    clock.now = 103.0
    assert s.next_range() == (250, 350)
    assert s.t0 == 103.0 and started == [103.0]
    assert s.window_rows == 100 and s.served_rows == 350
    assert s.max_gap_s == 3.0


def test_every_poll_is_recorded_with_its_time_and_rows():
    clock = Clock()
    s = Schedule([{"rows": 30, "poll_rows": 20}], 50, 60.0, clock)
    s.next_range(), s.next_range()
    clock.now += 2.0
    s.next_range()
    clock.now += 0.5
    s.next_range()
    assert s.polls == [
        (100.0, 0, 20), (100.0, 20, 30), (102.0, 30, 80), (102.5, 80, 130),
    ]
    assert s.window_polls() == [(0.0, 30, 80), (0.5, 80, 130)]


def test_input_ends_at_the_deadline_and_not_before():
    clock = Clock()
    ended = []
    s = Schedule([], 100, 5.0, clock, None, ended.append)
    s.next_range()
    clock.now += 4.999
    assert s.next_range() == (100, 200)
    clock.now += 0.002
    with pytest.raises(StopIteration):
        s.next_range()
    assert s.ended_at == clock.now and ended == [clock.now]
    assert s.window_rows == 200
