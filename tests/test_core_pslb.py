"""PSLB 1-D positional balancing: conservation, proportionality, locality."""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import apportion, distribute_stream, owner_of_fraction, pslb_assign


def test_owner_of_fraction_basic():
    lam = np.array([0.0, 0.25, 0.5, 0.75])
    assert owner_of_fraction(lam, np.array([0.0]))[0] == 0
    assert owner_of_fraction(lam, np.array([0.3]))[0] == 1
    assert owner_of_fraction(lam, np.array([0.99]))[0] == 3
    assert owner_of_fraction(lam, np.array([1.0]))[0] == 3  # clipped


def test_owner_skips_zero_power_nodes():
    # middle node has zero power -> empty interval, never selected
    lam = np.array([0.0, 0.5, 0.5])
    got = owner_of_fraction(lam, np.linspace(0, 0.999, 100))
    assert set(np.unique(got)) <= {0, 2}


def test_apportion_sums_and_proportional():
    gamma = np.array([0.5, 0.3, 0.2])
    shares = apportion(1000, gamma)
    assert shares.sum() == 1000
    assert np.array_equal(shares, [500, 300, 200])
    shares = apportion(7, np.array([0.5, 0.5]))
    assert shares.sum() == 7


def test_pslb_unit_tasks_exact_balance():
    powers = np.array([3.0, 4, 5, 2, 1, 5])
    works = np.ones(1000)
    node = np.repeat(np.arange(6), [250, 300, 150, 100, 50, 150])
    res = pslb_assign(works, node, powers)
    assert np.array_equal(res.loads_after, 1000 * powers / powers.sum())
    assert res.loads_after.sum() == 1000


def test_pslb_preserves_locality():
    """Monotone placement: scan-order neighbours stay neighbours (paper:
    'data which are neighbours before are likely to stay neighbours')."""
    rng = np.random.default_rng(1)
    works = rng.uniform(1, 10, size=200)
    node = np.sort(rng.integers(0, 8, size=200))
    res = pslb_assign(works, node, np.ones(8))
    assert (np.diff(res.dest) >= 0).all()


@given(
    st.integers(min_value=1, max_value=40),   # tasks
    st.integers(min_value=1, max_value=8),    # nodes
    st.integers(min_value=0, max_value=10_000),
)
@settings(max_examples=60, deadline=None)
def test_pslb_properties(m, n, seed):
    rng = np.random.default_rng(seed)
    works = rng.integers(1, 20, size=m).astype(float)
    node = rng.integers(0, n, size=m)
    powers = rng.integers(1, 10, size=n).astype(float)
    res = pslb_assign(works, node, powers)
    # conservation
    assert res.loads_after.sum() == pytest.approx(works.sum())
    assert res.dest.min() >= 0 and res.dest.max() < n
    # indivisibility bound: deviation from target < max task size
    targets = works.sum() * powers / powers.sum()
    assert np.abs(res.loads_after - targets).max() <= works.max() + 1e-9


def test_distribute_stream_matches_table5_rule():
    powers = np.array([5.0, 1, 4, 2, 6, 2])  # G3 of the worked example
    works = np.ones(600)
    dest = distribute_stream(works, powers)
    counts = np.bincount(dest, minlength=6)
    assert np.array_equal(counts, [150, 30, 120, 60, 180, 60])
    # unit at stream position 380 (the paper's v26 k=200 example) -> v35
    assert dest[380] == 4


def test_distribute_stream_zero_power_raises():
    with pytest.raises(ValueError):
        distribute_stream(np.ones(3), np.zeros(4))
