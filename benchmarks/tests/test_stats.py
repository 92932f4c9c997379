import pytest

from benchmarks.harness import stats


@pytest.mark.parametrize("stall_at", [0, 45, 89, None])
def test_window_rate_is_all_the_work_over_all_the_time(stall_at):
    # 90 steps of 0.1 s, 256 examples each; a 2 s stall in one of them counts.
    times, t = [5.0], 5.0
    for i in range(90):
        t += 0.1 + (2.0 if i == stall_at else 0.0)
        times.append(t)
    rate, window_s = stats.window_rate(times, 256.0)
    assert window_s == pytest.approx(9.0 if stall_at is None else 11.0)
    assert rate == pytest.approx(90 * 256.0 / window_s)
    with pytest.raises(ValueError):
        stats.window_rate([1.0], 256.0)


def test_tokens_are_counted_on_receipt_not_on_completion():
    # One request's tokens straddle both edges of the window [10, 20).
    times = [9.0, 9.5, 10.0, 15.0, 19.999, 20.0, 21.0]
    assert stats.tokens_in_window(times, 10.0, 20.0) == 3


def test_gaps_are_those_that_end_in_the_window():
    reqs = [[9.0, 10.5, 11.0, 20.5], [1.0, 2.0]]
    assert stats.gaps_ending_in_window(reqs, 10.0, 20.0) == pytest.approx([1.5, 0.5])


def test_percentile_is_linear_interpolation():
    assert stats.percentile([1, 2, 3, 4], 50) == pytest.approx(2.5)
    assert stats.percentile([5], 95) == 5
    assert stats.percentile(list(range(101)), 95) == pytest.approx(95.0)
