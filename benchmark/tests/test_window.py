"""The whole-request window arithmetic on canned timings."""

import window


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def drive(seconds, walls, gap=0.0):
    clock = FakeClock()
    todo = list(walls)

    def request():
        wall = todo.pop(0)
        clock.t += wall
        return {"wall_s": wall, "units": 10}

    def between():
        clock.t += gap

    return window.run_window(seconds, request, between, clock), todo


def test_only_whole_requests_count_and_no_start_without_room():
    win, left = drive(10.0, [3.0, 3.0, 3.0, 3.0, 3.0])
    # 9 s used after three; a fourth of the last one's length does not fit.
    assert [r["wall_s"] for r in win["counted"]] == [3.0, 3.0, 3.0]
    assert win["attempted"] == 3 and win["dropped"] == 0 and len(left) == 2


def test_first_request_counts_even_past_the_window():
    win, _ = drive(10.0, [46.0, 46.0])
    assert len(win["counted"]) == 1 and win["attempted"] == 1
    assert window.rate_per_s(win["counted"]) == 10 / 46.0


def test_a_request_that_overruns_is_dropped_not_counted():
    win, _ = drive(10.0, [3.0, 3.0, 5.0, 1.0])
    # third started with room for 3 s, took 5: ends at 11 > 10.
    assert [r["wall_s"] for r in win["counted"]] == [3.0, 3.0]
    assert win["attempted"] == 3 and win["dropped"] == 1 and win["failed"] == 0


def test_between_time_is_inside_the_window_but_outside_the_request():
    win, _ = drive(10.0, [2.0] * 10, gap=1.0)
    assert len(win["counted"]) == 3  # 1+2, 1+2, 1+2, then 1 + 2 > 10 - 9
    assert window.median_wall_s(win["counted"]) == 2.0


def test_failed_requests_are_counted_as_failed():
    clock = FakeClock()
    recs = [{"wall_s": 1.0, "failed": True}, {"wall_s": 1.0, "units": 5}]

    def request():
        clock.t += 1.0
        return recs.pop(0) if recs else {"wall_s": 1.0, "units": 5}

    win = window.run_window(3.0, request, None, clock)
    assert win["failed"] == 1 and win["attempted"] == 3 and len(win["counted"]) == 2


def test_median_and_rate():
    recs = [{"wall_s": 1.0, "units": 100}, {"wall_s": 3.0, "units": 100}, {"wall_s": 2.0, "units": 100}]
    assert window.median_wall_s(recs) == 2.0
    assert window.rate_per_s(recs) == 50.0
    assert window.median_wall_s([]) is None and window.rate_per_s([]) is None
