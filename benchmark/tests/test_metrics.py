"""The end-to-end arithmetic on a recorded list of chunk times."""

import pytest

import metrics

# handel-4096.single-r1's first 22 chunk wall times as a rehearsal printed them
RECORDED = [
    1.0431, 1.0397, 1.0402, 1.0399, 1.0521, 1.0400, 1.0398, 1.0404, 1.0401, 1.0399,
    1.0400, 1.0403, 1.0397, 1.0402, 1.0400, 1.0399, 1.0887, 1.0401, 1.0400, 1.0398,
    1.0402, 1.0399,
]


def test_nearest_rank_p95_is_a_sample():
    # ceil(0.95 * 22) = 21st of 22 in order: the second largest
    assert metrics.nearest_rank(RECORDED, 0.95) == 1.0521


@pytest.mark.parametrize("n", [1, 2, 3, 4, 19, 20])
def test_p95_of_fewer_than_twenty_is_the_slowest(n):
    sample = RECORDED[:n]
    expected = max(sample) if n < 20 else sorted(sample)[-2]
    assert metrics.nearest_rank(sample, 0.95) == expected


def test_nearest_rank_refuses_an_empty_sample():
    with pytest.raises(ValueError):
        metrics.nearest_rank([], 0.95)


def test_sim_ms_per_s_counts_the_overshoot_chunk():
    # a 30-s window with 12.1-s chunks stops at the first boundary at or
    # after 30 s: three chunks in 36.3 s, all of them counted over all of it
    elapsed, chunks = 0.0, 0
    while not metrics.window_is_over(elapsed, 30.0):
        elapsed, chunks = elapsed + 12.1, chunks + 1
    assert chunks == 3
    assert metrics.sim_ms_per_s(8, 10, chunks, elapsed) == pytest.approx(240 / 36.3)


def test_window_stops_exactly_on_a_boundary():
    assert metrics.window_is_over(30.0, 30.0)
    assert not metrics.window_is_over(29.999, 30.0)


def test_iqr_share_is_the_contracts_spread():
    # statistics.quantiles(n=4) of 1..6: Q1 = 1.75, Q3 = 5.25, median 3.5
    assert metrics.iqr_share([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)
