"""Percentile, spread and interval arithmetic."""

import math

import pytest

from benchmark.stats import (median, merge, percentile, spread, subtract,
                             union_length)


@pytest.mark.parametrize("samples,q,want", [
    ([1, 2, 3, 4, 5], 50, 3),
    ([1, 2, 3, 4], 50, 2),               # nearest rank: ceil(0.5 * 4) = 2nd
    (list(range(1, 101)), 99, 99),
    (list(range(1, 101)), 100, 100),
    (list(range(1, 201)), 99, 198),      # two samples lie beyond it
    ([5], 99, 5),
    ([3, 1, 2], 1, 1),
])
def test_percentile_is_nearest_rank(samples, q, want):
    assert percentile(samples, q) == want


def test_an_unanswered_request_sits_in_the_tail_it_belongs_to():
    lat = [1.0] * 98 + [math.inf, math.inf]
    assert percentile(lat, 50) == 1.0
    assert percentile(lat, 98) == 1.0
    assert percentile(lat, 99) == math.inf


def test_percentile_refuses_nothing_and_bad_q():
    with pytest.raises(ValueError):
        percentile([], 50)
    with pytest.raises(ValueError):
        percentile([1], 0)


def test_median_and_spread():
    assert median([4, 1, 3, 2]) == 2.5
    assert median([3, 1, 2]) == 2
    runs = [100, 101, 102, 103, 104, 105]      # quartiles 101 and 104
    assert spread(runs) == pytest.approx(3 / 102.5)


def test_union_merges_overlaps_once():
    assert merge([(3, 4), (0, 2), (1, 2.5), (5, 5)]) == [(0, 2.5), (3, 4)]
    assert union_length([(0, 2), (1, 3), (10, 11)]) == 4


def test_subtract_leaves_what_no_hole_covers():
    assert subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]
    assert subtract([(0, 1)], [(0, 1)]) == []
    assert subtract([(0, 1)], []) == [(0, 1)]
