"""The arrival schedule: a function of the traffic mix and the seed."""

from collections import Counter

import numpy as np
import pytest

from bench.schedule import schedule, stream_times

MIX = {"streams": 8, "fps": 2.0, "jitter": 0.2}


def test_same_seed_same_schedule_other_seed_other_gaps():
    big = 2 ** 31 + 12345  # the driver's seeds pass 32 signed bits
    assert schedule(MIX, 20.0, big) == schedule(MIX, 20.0, big)
    assert schedule(MIX, 20.0, big) != schedule(MIX, 20.0, big + 1)


@pytest.mark.parametrize("seed", [1, 7, 2 ** 31 + 3])
def test_every_seed_offers_the_same_frames(seed):
    due = schedule(MIX, 30.0, seed)
    per_stream = Counter(d.stream for d in due)
    assert per_stream == {s: 60 for s in range(8)}  # floor(30 s * 2 fps)
    assert all(0 <= d.t_s < 30.0 for d in due)
    assert [d.t_s for d in due] == sorted(d.t_s for d in due)
    for s in range(8):
        assert [d.frame_idx for d in due if d.stream == s] == list(range(60))


def test_without_jitter_frames_are_evenly_spaced():
    # a phase under one gap, then the spacing 1 / fps
    t = stream_times(0, 4.0, 2.0, seed=5)
    assert len(t) == 8 and 0 <= t[0] < 0.25
    assert np.diff(t) == pytest.approx([0.25] * 7)


def test_streams_do_not_line_up_at_the_window_end():
    due = schedule(MIX, 30.0, 4)
    last = [max(d.t_s for d in due if d.stream == s) for s in range(8)]
    assert len({round(x, 6) for x in last}) == 8


def test_rate_trace_is_respected():
    # scale 3 from t = 10 s: three times the frames in the second half,
    # 2 fps x (10 s + 3 x 10 s) = 80 in all, every one inside the window
    t = stream_times(0, 2.0, 20.0, seed=3, rate_trace=[(10.0, 3.0)])
    first = sum(x < 10.0 for x in t)
    second = sum(10.0 <= x < 20.0 for x in t)
    assert first == 20 and second == 60
    assert np.diff([x for x in t if x >= 10.0]) == pytest.approx(
        [1 / 6] * 59)
    with pytest.raises(ValueError):
        stream_times(0, 2.0, 20.0, seed=3, rate_trace=[(1.0, 0.0)])


def test_rate_trace_with_jitter_keeps_the_step():
    t = stream_times(1, 2.0, 40.0, seed=9, jitter=0.2,
                     rate_trace=[(20.0, 2.0)])
    first = sum(x < 20.0 for x in t)
    second = sum(20.0 <= x < 40.0 for x in t)
    assert len(t) == 120 and 1.6 < second / first < 2.5
    # the same work on every seed, in another order
    other = stream_times(1, 2.0, 40.0, seed=10, jitter=0.2,
                         rate_trace=[(20.0, 2.0)])
    assert len(other) == 120 and other != t
