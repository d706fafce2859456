import random

import pytest

from stats import (cluster_by_start, completion_rate, quantile, span_self_ms, uncovered_edges,
                   union_length)


def bursty(period, burst, n_bursts, jitter=0.004):
    """Completion instants: ``burst`` plans within a few ms of every harvest."""
    rng = random.Random(5)
    return [k * period + rng.uniform(0, jitter) for k in range(n_bursts) for _ in range(burst)]


def test_rate_is_phase_free_where_count_over_seconds_is_not():
    stream = bursty(period=3.8, burst=5, n_bursts=40)
    window = 51.0
    rates, naive = [], []
    for step in range(38):  # window start swept across one harvest period
        t0 = 20.0 + 0.1 * step
        inside = [t for t in stream if t0 <= t <= t0 + window]
        rates.append(completion_rate(inside))
        naive.append(len(inside) / window)
    assert max(naive) / min(naive) > 1.05  # N / seconds swings by a burst
    assert max(rates) / min(rates) < 1.012  # the estimator does not
    true = 5 / 3.8
    assert all(true <= r <= true * 1.09 for r in rates)  # reads one burst high, always


def test_rate_counts_completions_after_the_first():
    assert completion_rate([10.0, 11.0, 12.0, 14.0]) == pytest.approx(3 / 4.0)
    assert completion_rate([3.0]) is None
    assert completion_rate([]) is None
    assert completion_rate([2.0, 2.0]) is None


def test_a_stall_at_an_edge_of_the_window_shows():
    """The rate and the quantiles are of completed plans: a server that
    completes nothing for the last 15 s of the window reads the same. The
    edges do not."""
    stream = bursty(period=0.95, burst=2, n_bursts=80)
    t0, t1 = 10.3, 61.3
    steady = [t for t in stream if t0 <= t <= t1]
    stalled = [t for t in steady if t <= t1 - 15.0]
    assert completion_rate(stalled) == pytest.approx(completion_rate(steady), rel=0.02)
    edges, widest = uncovered_edges(steady, t0, t1)
    assert widest == pytest.approx(0.95, abs=0.01) and edges <= 2 * widest
    edges, widest = uncovered_edges(stalled, t0, t1)
    assert edges > 15.0 and edges > 2 * widest
    assert uncovered_edges([3.0], 0.0, 10.0) is None


def test_quantile_interpolates_linearly():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert quantile(xs, 0.5) == 30.0
    assert quantile(xs, 0.8) == pytest.approx(42.0)  # nearest-rank would say 40 or 50
    assert quantile(xs, 0.0) == 10.0 and quantile(xs, 1.0) == 50.0
    assert quantile([7.0], 0.8) == 7.0
    assert quantile([], 0.5) is None
    # between two lumps the quantile lies between them, by their weights
    assert quantile([1.0] * 8 + [9.0] * 2, 0.8) == pytest.approx(2.6)
    with pytest.raises(ValueError):
        quantile(xs, 1.5)


def test_union_length_merges_overlaps():
    assert union_length([(0, 2), (1, 3), (5, 6), (6, 6)]) == 4
    assert union_length([]) == 0


def test_span_self_time_subtracts_the_union_of_direct_children():
    spans = [
        {"span_id": "r", "parent_id": None, "start_ms": 0.0, "duration_ms": 100.0},
        {"span_id": "a", "parent_id": "r", "start_ms": 10.0, "duration_ms": 50.0},
        {"span_id": "b", "parent_id": "r", "start_ms": 40.0, "duration_ms": 40.0},  # overlaps a
        {"span_id": "c", "parent_id": "a", "start_ms": 12.0, "duration_ms": 5.0},  # grandchild
        {"span_id": "d", "parent_id": "r", "start_ms": 95.0, "duration_ms": 30.0},  # runs past r
    ]
    assert span_self_ms(spans[0], spans) == pytest.approx(100 - 70 - 5)
    assert span_self_ms(spans[1], spans) == pytest.approx(45.0)
    assert span_self_ms(spans[2], spans) == pytest.approx(40.0)


def test_cluster_by_start():
    groups = cluster_by_start([(0.0, "a"), (0.4, "b"), (3800.0, "c"), (3801.0, "d"), (9000.0, "e")], 20.0)
    assert groups == [["a", "b"], ["c", "d"], ["e"]]
