"""Eq. 32 with the half-smoothed success share, and the window arithmetic."""
import math
import statistics

import pytest

from chipbench import stats


def test_no_hit_keeps_tts_finite():
    p = stats.smoothed_success(0, 200)
    assert p == pytest.approx(0.5 / 201)
    t = stats.tts(2.0, p, 8)
    assert math.isfinite(t) and t > 2.0
    # ln(0.01) / ln(1 − P) with P = 1 − (1 − p)^8
    assert t == pytest.approx(2.0 * math.log(0.01) / (8 * math.log1p(-p)))


@pytest.mark.parametrize("hits,trials", [(200, 200), (120, 200), (90, 200)])
def test_solve_that_already_succeeds_costs_one_solve(hits, trials):
    p = stats.smoothed_success(hits, trials)
    assert 1 - (1 - p) ** 8 >= 0.99
    assert stats.tts(0.7, p, 8) == 0.7


def test_replicas_pool_into_one_solve():
    # One replica at p = 0.3 needs ln(0.01)/ln(0.7) runs; eight replicas per
    # solve need eight times fewer solves.
    p = 0.3
    one = stats.tts(1.0, p, 1)
    eight = stats.tts(1.0, p, 8)
    assert one == pytest.approx(math.log(0.01) / math.log(0.7))
    assert eight == pytest.approx(one / 8)
    assert stats.tts(1.0, p, 13) == 1.0        # P = 0.9903 >= 0.99


def test_tts_is_continuous_at_the_target():
    p = 1 - 0.01 ** (1 / 8)
    assert stats.tts(1.0, p * (1 - 1e-9), 8) == pytest.approx(1.0)


@pytest.mark.parametrize("hits,trials", [(-1, 5), (6, 5)])
def test_smoothed_success_rejects_impossible_counts(hits, trials):
    with pytest.raises(ValueError):
        stats.smoothed_success(hits, trials)


def test_p95_is_over_every_solve_not_chunk_medians():
    # 100 solves: 90 fast, 10 slow. The p95 sits among the slow ones; the
    # median of per-chunk (10-solve) medians never sees them.
    solves = [0.1] * 90 + [1.0] * 10
    assert stats.percentile(solves, 95) == pytest.approx(1.0)
    chunks = [solves[i:i + 10] for i in range(0, 100, 10)]
    medians = [statistics.median(c) for c in chunks]
    assert stats.percentile(medians, 95) < 0.6


def test_percentile_interpolates_and_needs_two_samples():
    assert stats.percentile([1.0, 2.0], 50) == pytest.approx(1.5)
    with pytest.raises(ValueError):
        stats.percentile([1.0], 95)


def test_spread_is_iqr_over_median():
    vals = [10.0, 10.0, 11.0, 12.0, 9.0, 10.0]
    q1, med, q3 = statistics.quantiles(vals, n=4)
    assert stats.spread(vals) == pytest.approx((q3 - q1) / med)
