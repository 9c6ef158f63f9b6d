"""The traffic generator and the two traffic loops, on a fake clock."""
import math

import pytest

from bench.traffic import Traffic, Window, drive_closed, drive_open


def test_open_loop_arrivals_are_shuffled_exponential_quantiles():
    mix = {"loop": "open", "arrivals": "poisson", "rate_per_s": 2.0, "rows": 16}
    a = Traffic.from_dict(mix).due_times(50)
    assert len(a) == 100
    gaps = [x - y for x, y in zip(a, [0.0] + a[:-1])]
    n = len(gaps)
    quantiles = [-math.log1p(-(i + 0.5) / n) / 2.0 for i in range(n)]
    assert sorted(gaps) == pytest.approx(quantiles)
    assert gaps != sorted(gaps)
    assert 45 < a[-1] < 50  # mid-quantile gaps of mean 1/rate
    assert Traffic.from_dict(mix).due_times(50) == a


def test_batch_sizes_warm_only_what_the_mix_forms():
    assert Traffic("closed", 16, clients=16).batch_sizes(8) == [8]
    assert Traffic("closed", 16, clients=12).batch_sizes(8) == [4, 8]
    assert Traffic("closed", 16, clients=3).batch_sizes(8) == [3]
    assert Traffic("open", 16, rate_per_s=1.0).batch_sizes(4) == [1, 2, 3, 4]


@pytest.mark.parametrize("bad", [
    {"loop": "closed", "clients": 0, "rows": 1},
    {"loop": "open", "arrivals": "bursty", "rate_per_s": 1, "rows": 1},
    {"loop": "open", "arrivals": "poisson", "rate_per_s": 0, "rows": 1},
    {"loop": "sideways", "rows": 1},
])
def test_bad_mixes_are_refused(bad):
    with pytest.raises(ValueError):
        Traffic.from_dict(bad)


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


def test_closed_loop_window_ends_with_the_first_call_past_the_length():
    clock = FakeClock()
    queued = []

    def serve():
        clock.t += 3.0
        queued.clear()

    w = Window()
    drive_closed(w, Traffic("closed", 4, clients=2), 7.0,
                 lambda due: queued.append(due) or len(queued), serve, clock)
    assert len(w.runs) == 3  # 3, 6, 9 s: the third call is the first past 7 s
    assert w.seconds == pytest.approx(9.0)
    assert len(w.sent) == 6
    assert all(s.run_start == s.due for s in w.sent)
    assert [s.run_end - s.due for s in w.sent] == pytest.approx([3.0] * 6)


def test_open_loop_times_from_due_and_waits_for_the_last_decode():
    clock = FakeClock()

    def serve():
        clock.t += 1.5

    w = Window()
    due = [0.5, 1.0, 1.2, 5.0]
    drive_open(w, Traffic("open", 4, rate_per_s=1.0), due,
               lambda d: d, serve, clock, clock.sleep)
    # 0.5: served at once (0.5-2.0); 1.0 and 1.2 wait for that call, then
    # ride one call (2.0-3.5); 5.0 is served at 5.0-6.5
    assert [s.run_start - s.due for s in w.sent] == pytest.approx([0.0, 1.0, 0.8, 0.0])
    assert [s.run_end - s.due for s in w.sent] == pytest.approx([1.5, 2.5, 2.3, 1.5])
    assert len(w.runs) == 3
    assert w.seconds == pytest.approx(6.5)
    assert w.late_s == pytest.approx([0.0, 0.0])
