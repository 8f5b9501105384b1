"""Percentiles over every request, and unions of intervals."""
import math

import numpy as np
import pytest

from bench.quantile import percentile, union_length


@pytest.mark.parametrize("n", [1, 2, 7, 70, 113])
def test_percentile_matches_numpy_over_all_values(n):
    xs = np.random.default_rng(n).exponential(size=n)
    for q in (50, 90, 95):
        assert percentile(xs.tolist(), q) == pytest.approx(
            float(np.percentile(xs, q)))


def test_every_request_counts_and_a_failed_one_is_infinitely_late():
    xs = [1.0] * 9 + [math.inf]
    assert percentile(xs, 50) == 1.0
    assert percentile(xs, 95) == math.inf
    # 90th of 10 values sits at index 8.1: between 1.0 and inf
    assert percentile(xs, 90) == math.inf
    assert percentile(xs[:9] + [2.0], 90) == pytest.approx(1.1)


def test_union_length_merges_and_clips():
    ivs = [(0, 1), (0.5, 2), (3, 4), (3.5, 3.6)]
    assert union_length(ivs) == pytest.approx(3.0)
    assert union_length(ivs, 1.5, 3.5) == pytest.approx(1.0)
    assert union_length([]) == 0.0
