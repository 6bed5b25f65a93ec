"""The arrival generator: deterministic per seed, an exact count, the shape
of its gaps, every pool item equally often."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import traffic  # noqa: E402

POISSON = {"arrivals": "poisson", "rate_per_s": 37.0, "close": "last_answer"}
BURST = {"arrivals": "gamma", "cv": 2.0, "rate_per_s": 37.0, "close": "last_answer"}


@pytest.mark.parametrize("mix", [POISSON, BURST])
@pytest.mark.parametrize("seed", [0, 2**31 + 12345, -7])
def test_deterministic_and_exact_count(mix, seed):
    a = traffic.schedule(mix, 30.0, 25, seed)
    b = traffic.schedule(mix, 30.0, 25, seed)
    assert len(a.due_s) == round(37.0 * 30.0)
    np.testing.assert_array_equal(a.due_s, b.due_s)
    np.testing.assert_array_equal(a.item, b.item)
    np.testing.assert_array_equal(a.key, b.key)
    assert (np.diff(a.due_s) >= 0).all()
    assert a.due_s[0] >= 0 and a.due_s[-1] < 30.0
    c = traffic.schedule(mix, 30.0, 25, seed + 1)
    assert not np.array_equal(a.due_s, c.due_s)


def test_streams_differ_and_items_are_balanced():
    a = traffic.schedule(POISSON, 30.0, 25, 4, stream=0)
    b = traffic.schedule(POISSON, 30.0, 25, 4, stream=1)
    assert not np.array_equal(a.due_s, b.due_s)
    counts = np.bincount(a.item, minlength=25)
    assert counts.max() - counts.min() <= 1


def test_gap_shape_follows_the_mix():
    rng = np.random.default_rng(0)
    p = np.diff(traffic.due_times(POISSON, 2000.0, rng))
    g = np.diff(traffic.due_times(BURST, 2000.0, rng))
    assert abs(p.std() / p.mean() - 1.0) < 0.1
    assert abs(g.std() / g.mean() - 2.0) < 0.3


@pytest.mark.parametrize("bad", [
    {"arrivals": "uniform", "rate_per_s": 1.0, "close": "seconds"},
    {"arrivals": "poisson", "rate_per_s": 0.0, "close": "seconds"},
    {"arrivals": "gamma", "rate_per_s": 1.0, "close": "seconds"},
    {"arrivals": "poisson", "rate_per_s": 1.0, "close": "never"},
])
def test_bad_mixes_are_refused(bad):
    with pytest.raises(ValueError):
        traffic.validate(bad)
