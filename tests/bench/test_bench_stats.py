"""Percentile, time-per-output-token and spread arithmetic."""
import numpy as np
import pytest

from _bench_path import ROOT  # noqa: F401
from bench.stats import percentile, spread, tpot_s


@pytest.mark.parametrize("q", [0, 10, 50, 90, 100])
def test_percentile_matches_numpy_linear(q):
    xs = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0]
    assert percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_by_hand():
    assert percentile([10, 20, 30, 40], 50) == 25
    assert percentile([10, 20, 30, 40], 90) == pytest.approx(37)
    assert percentile([], 50) is None


def test_tpot_is_first_to_last_over_the_tokens_after_the_first():
    assert tpot_s(2.0, 5) == 0.5
    assert tpot_s(1.0, 1) is None and tpot_s(0.0, 0) is None


def test_spread_is_quartile_distance_over_median():
    vals = [100, 102, 98, 101, 99, 103]
    import statistics
    q1, _, q3 = statistics.quantiles(vals, n=4)
    assert spread(vals) == pytest.approx((q3 - q1) / statistics.median(vals))
