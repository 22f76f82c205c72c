import pytest

from grid import stats


def test_a_requests_gap_is_its_mean_gap_not_a_median_of_gaps():
    # gaps of 45, 45, 66 and 45 ms: one cycle carried an admission
    stamps = [(0.000, 1), (0.045, 2), (0.090, 3), (0.156, 4), (0.201, 5)]
    assert stats.mean_gap_ms(stamps, min_tokens=5) == pytest.approx(50.25)
    assert stats.mean_gap_ms(stamps, min_tokens=6) is None
    # the first cycle brings two tokens (prefill, then the decode dispatch)
    two = [(1.0, 2), (1.05, 3), (1.10, 4)]
    assert stats.mean_gap_ms(two, min_tokens=3) == pytest.approx(50.0)


def test_the_metric_is_the_median_over_requests():
    fast = [(0.0, 1), (0.40, 11)]     # 40 ms a token
    slow = [(0.0, 1), (0.60, 11)]     # 60
    slower = [(0.0, 1), (0.90, 11)]   # 90
    short = [(0.0, 1), (0.01, 2)]     # too few tokens: no gap
    gaps = stats.request_gaps_ms([fast, slow, slower, short], min_tokens=8)
    assert sorted(gaps) == pytest.approx([40.0, 60.0, 90.0])
    assert stats.percentile(gaps, 50) == pytest.approx(60.0)
    assert stats.percentile(gaps, 95) == pytest.approx(87.0)


def test_percentile_and_spread():
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    # statistics.quantiles' quartiles, as the contract measures a spread
    assert stats.spread([100, 101, 102, 103, 104, 105]) \
        == pytest.approx((104.25 - 100.75) / 102.5)
