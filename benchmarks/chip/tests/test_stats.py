import numpy as np

from harness import stats


def window(step_s=0.03, steps=20, slots=8, stall_at=None, stall_s=0.0):
    """Token arrival times of ``slots`` requests decoding together; step
    ``stall_at`` takes ``stall_s`` longer, as an admission's prefill between
    two decode steps makes it for every slot."""
    t, times = 0.0, {s: [] for s in range(slots)}
    for i in range(steps):
        t += step_s + (stall_s if i == stall_at else 0.0)
        for s in range(slots):
            times[s].append(t)
    return times


def test_percentile_over_all_samples():
    v = list(range(1, 101))
    assert stats.percentile(v, 95) == np.percentile(v, 95)
    assert stats.percentile([], 95) is None


def test_one_stall_moves_itl_p95():
    base = stats.token_gaps(window(), 0.0, 10.0)
    stalled = stats.token_gaps(window(stall_at=10, stall_s=0.3), 0.0, 10.0)
    assert len(base) == len(stalled) == 8 * 19
    assert abs(stats.percentile(base, 95) - 0.03) < 1e-9
    # the stall delays every slot's next token: 8 of 152 gaps (5.3%) hold
    # it, so the tail over all gaps moves ...
    assert stats.percentile(stalled, 95) > 0.1
    # ... where a median of per-chunk medians would not see it
    chunks = np.array_split(np.array(stalled), 8)
    assert abs(np.median([np.median(c) for c in chunks]) - 0.03) < 1e-9


def test_gaps_inside_window_only():
    times = {0: [0.5, 1.0, 1.5, 2.0, 2.5], 1: [0.9, 1.2]}
    # a gap counts when both of its tokens reached the host in the window
    assert stats.token_gaps(times, 1.0, 2.0) == [0.5, 0.5]


def test_spread():
    assert abs(stats.spread([1, 2, 3, 4, 5]) - (4.5 - 1.5) / 3) < 1e-12
