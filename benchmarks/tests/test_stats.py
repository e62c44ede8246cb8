import pytest

from benchmarks.harness import stats


@pytest.mark.parametrize("values,pct,want", [
    ([1.0], 95, 1.0),
    ([3.0, 1.0, 2.0], 95, 3.0),                 # < 20 values: the max
    (list(range(1, 21)), 95, 19),               # rank ceil(19.0) = 19
    (list(range(1, 101)), 95, 95),
    (list(range(1, 101)), 50, 50),
    ([5.0, 5.0, 5.0, 9.0], 75, 5.0),
])
def test_percentile_is_nearest_rank(values, pct, want):
    assert stats.percentile_nearest_rank(values, pct) == want


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile_nearest_rank([], 95)


def test_one_stalled_job_moves_rate_and_tail():
    rows = 1000
    steady = [1.0] * 10
    stalled = [1.0] * 9 + [6.0]
    rate = lambda jobs: stats.window_rate(rows * len(jobs), sum(jobs))
    assert rate(steady) == 1000.0
    assert rate(stalled) == pytest.approx(10000 / 15.0)
    assert stats.percentile_nearest_rank(steady, 95) == 1.0
    assert stats.percentile_nearest_rank(stalled, 95) == 6.0


def test_rate_is_over_the_whole_window():
    with pytest.raises(ValueError):
        stats.window_rate(10, 0.0)
    assert stats.window_rate(0, 2.0) == 0.0


def test_iqr_share_matches_the_contracts_definition():
    import statistics

    v = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q1, _, q3 = statistics.quantiles(v, n=4)
    assert stats.iqr_share(v) == pytest.approx(
        (q3 - q1) / statistics.median(v))
