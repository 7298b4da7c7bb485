"""Hyper-grid embedding, virtual nodes, optimal dimension (paper sec. 2.1, 4.1)."""

import math

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import HyperGrid, embed, factorize, optimal_dim
from repro.core.cost_model import scan_steps


@pytest.mark.parametrize("n,d", [(2, 1), (3, 2), (4, 2), (8, 3), (16, 4),
                                 (18, 5), (64, 6), (1000, 10)])
def test_optimal_dim(n, d):
    assert optimal_dim(n) == d  # ceil(log2 n)


@given(st.integers(min_value=2, max_value=4096))
@settings(max_examples=80, deadline=None)
def test_factorize_covers_and_is_tight(n):
    d = optimal_dim(n)
    dims = factorize(n, d)
    assert len(dims) == d
    assert math.prod(dims) >= n
    # tight: shrinking any side would lose coverage
    for i in range(d):
        trial = list(dims)
        if trial[i] > 1:
            trial[i] -= 1
            assert math.prod(trial) < n


@given(st.integers(min_value=4, max_value=512))
@settings(max_examples=60, deadline=None)
def test_prop_4_1_optimal_dim_minimises_cost(n):
    """Prop 4.1: d* = ceil(log2 n) has the lowest step cost among dims."""
    best = scan_steps(factorize(n, optimal_dim(n)))
    for d in range(1, optimal_dim(n) + 3):
        assert best <= scan_steps(factorize(n, d))


def test_embed_pads_with_virtual_nodes():
    g = embed([3, 4, 5], d=2)  # 3 nodes into a 2-D grid
    assert g.capacity >= 3
    assert g.n_active == 3
    assert g.powers[3:].sum() == 0
    assert g.total_power == 12


def test_coords_roundtrip():
    g = embed(np.ones(18), d=2)
    for i in range(g.capacity):
        assert g.index(g.coords(i)) == i


def test_slices_partition_powers():
    g = HyperGrid((3, 6), np.arange(18, dtype=float) + 1)
    parts = g.slices()
    assert len(parts) == 3
    assert all(p.dims == (6,) for p in parts)
    assert sum(p.total_power for p in parts) == g.total_power


def test_fail_makes_virtual_node():
    g = embed([2.0, 2.0, 2.0, 2.0], d=2)
    g2 = g.fail(1)
    assert g2.n_active == 3
    assert g2.powers[1] == 0
    assert g.powers[1] == 2.0  # original untouched


def test_virtual_node_power_must_be_zero():
    with pytest.raises(ValueError):
        HyperGrid((2,), np.array([1.0, 2.0]),
                  active=np.array([True, False]))
