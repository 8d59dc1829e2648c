"""The window opens and closes on dispatch completions, and a stall
inside it lowers the rate."""
import sys
import threading
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchlib.window import (Dispatch, DispatchLog, percentile,  # noqa: E402
                             window_of)


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def rate(w):
    return w.requests / w.seconds


def completions(times, n=8):
    return [Dispatch(i, t, n, 0.1, 0.3) for i, t in enumerate(times)]


def test_window_counts_whole_dispatches_between_completions():
    w = window_of(completions([10, 25, 40, 55, 70, 85]), 48)
    # opens at 10, closes at the first completion at or past 58: 70
    assert (w.t_open, w.t_close) == (10, 70)
    assert w.requests == 4 * 8 and len(w.dispatches) == 4
    assert rate(w) == pytest.approx(32 / 60)


def test_a_stall_inside_the_window_lowers_the_rate():
    steady = window_of(completions([0, 1, 2, 3, 4, 5, 6]), 5)
    stalled = window_of(completions([0, 1, 2, 6, 7, 8, 9]), 5)
    assert rate(steady) == pytest.approx(8.0)
    # the 4-second stall stays in the window: 5 dispatches over 6 s
    assert stalled.t_close == 6 and rate(stalled) == pytest.approx(3 * 8 / 6)
    assert rate(stalled) < rate(steady)


def test_window_needs_a_closing_completion():
    with pytest.raises(ValueError):
        window_of(completions([0, 1]), 5)


def test_dispatch_log_waits_for_the_close_on_a_fake_clock():
    clock = FakeClock()
    log = DispatchLog(clock)
    out = {}
    waiter = threading.Thread(
        target=lambda: out.update(w=log.wait_window(10, stall_s=30)))
    waiter.start()
    for seq, dt in enumerate([0, 4, 4, 4]):
        clock.t += dt
        log.record(Dispatch(seq, clock(), 2 + seq, 0, 0))
    waiter.join(timeout=30)
    w = out["w"]
    assert (w.t_open, w.t_close) == (100, 112)
    assert w.requests == 3 + 4 + 5 and w.seqs == {1, 2, 3}


def test_dispatch_log_reports_a_stall():
    with pytest.raises(TimeoutError):
        DispatchLog(FakeClock()).wait_window(10, stall_s=0.05)


def test_nearest_rank_percentile():
    xs = list(range(1, 101))
    assert percentile(xs, 90) == 90
    assert percentile(xs, 50) == 50
    assert percentile([3.0], 90) == 3.0
    assert percentile([1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11], 90) == 10
