import statistics

import pytest

from harness import stats


def _boundaries(intervals):
    out = [0.0]
    for d in intervals:
        out.append(out[-1] + d)
    return out


def test_flat_steps_give_the_step_rate():
    iv = [0.346] * 100
    assert stats.window_rate(_boundaries(iv), [8192] * 100) == pytest.approx(8192 / 0.346)


def test_one_350ms_stall_counts_for_what_it_took_and_the_segments_show_where():
    iv = [0.346] * 100
    iv[37] += 0.350
    b = _boundaries(iv)
    flat = 8192 / 0.346
    # all the work over all the time: the stall is 1 % of the window and lowers the rate by 1 %
    assert stats.window_rate(b, [8192] * 100) == pytest.approx(8192 * 100 / (34.6 + 0.35))
    assert stats.window_rate(b, [8192] * 100) == pytest.approx(flat * (1 - 0.35 / 34.95))
    rates = stats.segment_rates(b, [8192] * 100)  # the diagnostic line: one segment is low
    assert [r < flat * 0.999 for r in rates] == [False, True, False, False, False]
    # the naive reading PR 22 was refused on: whole steps over a fixed window
    naive = 8192 * sum(1 for t in b[1:] if t <= 30.0) / 30.0
    assert abs(naive - flat) / flat > 0.005


def test_a_stall_every_fourth_step_counts_in_the_rate_and_in_every_segment():
    iv = [0.346 + (0.1 if i % 4 == 3 else 0.0) for i in range(100)]
    rate = stats.window_rate(_boundaries(iv), [8192] * 100)
    assert rate == pytest.approx(8192 * 100 / sum(iv))
    assert rate < 8192 / 0.346 * 0.95
    assert all(r < 8192 / 0.346 * 0.95 for r in stats.segment_rates(_boundaries(iv), [8192] * 100))


def test_unequal_work_per_dispatch_is_summed_not_averaged():
    b = _boundaries([0.1, 0.3, 0.1, 0.1])
    assert stats.window_rate(b, [128, 16, 128, 128]) == pytest.approx(400 / 0.6)
    with pytest.raises(ValueError):
        stats.window_rate(b, [1, 2, 3])


def test_remainder_steps_are_left_out_and_partial_steps_never_counted():
    b = _boundaries([1.0] * 23)
    assert len(stats.segment_rates(b, [10] * 23)) == 5
    assert stats.segment_rates(b, [10] * 23) == [10.0] * 5
    with pytest.raises(ValueError):
        stats.segment_rates(_boundaries([1.0] * 4), [1] * 4)


def test_percentile_and_spread():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == pytest.approx(50.5)
    assert stats.percentile(xs + [float("inf")], 100) == float("inf")
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.iqr_share(xs) == pytest.approx((q3 - q1) / 50.5)
