"""Percentile and due-time latency arithmetic of the benchmark."""

import math

import numpy as np
import pytest

from yardstick import arrivals, stats


def test_percentile_matches_numpy_linear():
    xs = [5.0, 1.0, 4.0, 2.0, 3.0, 10.0, 7.5]
    for q in (0, 1, 50, 90, 99, 100):
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_hand_worked():
    # ranks 0..4 of [10, 20, 30, 40, 50]; p99 sits at rank 3.96
    assert stats.percentile([50, 10, 40, 30, 20], 99) == pytest.approx(49.6)


def test_latency_is_timed_from_due_and_a_lost_request_is_late():
    due = [0.0, 0.010, 0.020, 0.030]
    done = [0.004, 0.030, None, 0.031]
    lat = stats.latencies_from_due(due, done)
    assert lat[:2] == pytest.approx([0.004, 0.020])
    assert math.isinf(lat[2])
    assert lat[3] == pytest.approx(0.001)
    # one lost request of four lies above the 99th percentile's rank
    assert math.isinf(stats.percentile(lat, 99))
    assert stats.percentile(lat, 50) == pytest.approx(0.012)


def test_arrivals_same_gaps_for_every_seed():
    a = arrivals.poisson_offsets(200.0, 5.0, np.random.default_rng(1))
    b = arrivals.poisson_offsets(200.0, 5.0, np.random.default_rng(2))
    assert len(a) == len(b) == 1000
    assert a[0] == b[0] == 0.0 and a[-1] < 5.0
    ga, gb = np.diff(np.append(a, 5.0)), np.diff(np.append(b, 5.0))
    np.testing.assert_allclose(np.sort(ga), np.sort(gb))
    assert not np.allclose(ga, gb)
    offs, step = arrivals.stepped_offsets([100, 300], 4.0,
                                          np.random.default_rng(3))
    assert (step == 0).sum() == 200 and (step == 1).sum() == 600
    assert offs[step == 1].min() >= 2.0
