"""PSTS recursive balancing: invariants across dimensions and topologies."""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import HyperGrid, embed, psts_schedule


def _random_instance(seed, n_nodes, m, d):
    rng = np.random.default_rng(seed)
    powers = rng.integers(1, 10, size=n_nodes).astype(float)
    grid = embed(powers, d)
    works = rng.integers(1, 20, size=m).astype(float)
    active = np.nonzero(grid.active)[0]
    node = active[rng.integers(0, active.size, size=m)]
    return grid, works, node


@pytest.mark.parametrize("d", [1, 2, 3, 4])
def test_balance_quality_all_dims(d):
    grid, works, node = _random_instance(7, 16, 2000, d)
    res = psts_schedule(works, node, grid)
    # conservation
    assert res.loads_after.sum() == pytest.approx(works.sum())
    # close to power-proportional within a few task sizes
    assert np.abs(res.loads_after - res.targets).max() <= 4 * works.max()


def test_unit_tasks_converge_to_exact_targets():
    grid, works, node = _random_instance(3, 8, 5000, 3)
    works = np.ones(5000)
    res = psts_schedule(works, node, grid)
    assert np.abs(res.loads_after - res.targets).max() <= 2.0


def test_nothing_moves_when_already_balanced():
    powers = np.array([2.0, 2.0, 2.0, 2.0])
    grid = HyperGrid((2, 2), powers)
    # perfectly balanced unit tasks
    node = np.repeat(np.arange(4), 25)
    works = np.ones(100)
    res = psts_schedule(works, node, grid)
    assert res.moved_tasks == 0
    assert np.array_equal(res.loads_after, res.loads_before)


def test_virtual_nodes_receive_nothing():
    grid = embed([1.0, 2.0, 3.0], d=2)  # capacity 4, one virtual slot
    rng = np.random.default_rng(0)
    node = rng.integers(0, 3, size=500)
    works = np.ones(500)
    res = psts_schedule(works, node, grid)
    assert res.loads_after[~grid.active].sum() == 0


def test_failed_node_drains():
    """Paper sec 4.1 / elasticity: tau=0 node gives all its work away."""
    grid = HyperGrid((2, 2), np.array([1.0, 1, 1, 1]))
    failed = grid.fail(2)
    node = np.repeat(np.arange(4), 100)
    works = np.ones(400)
    res = psts_schedule(works, node, failed)
    assert res.loads_after[2] == 0
    assert np.abs(res.loads_after[failed.active] -
                  400 / 3).max() <= 1.5


@given(
    st.integers(min_value=2, max_value=24),
    st.integers(min_value=1, max_value=400),
    st.integers(min_value=1, max_value=4),
    st.integers(min_value=0, max_value=10_000),
)
@settings(max_examples=40, deadline=None)
def test_psts_invariants(n_nodes, m, d, seed):
    grid, works, node = _random_instance(seed, n_nodes, m, d)
    res = psts_schedule(works, node, grid)
    # every task placed on an active node
    assert grid.active[res.dest].all()
    # conservation of work
    assert res.loads_after.sum() == pytest.approx(works.sum())
    # indivisibility bound: residual within a few max-task sizes per level
    slack = (grid.ndim + 1) * works.max()
    assert np.abs(res.loads_after - res.targets).max() <= slack + 1e-9


def test_dimension_reduces_boundary_traffic_bookkeeping():
    grid, works, node = _random_instance(11, 16, 3000, 4)
    res = psts_schedule(works, node, grid)
    assert res.inter_grid_units.shape == (3,)
    assert (res.inter_grid_units >= 0).all()
