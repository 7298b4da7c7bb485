"""A heterogeneous explicit cluster through the normal path: the Google
2011 machine table (Reiss et al., SoCC 2012, Table 1; 11,659 machines of
power 0.5, 798 of 1.0, 126 of 0.25) cut to 64 machines in its
proportions, each class keeping one or more (59 / 4 / 1), permuted by a
seed as a trace's machine IDs are. ``lab.sweep(backend="batched")`` runs
it in float32 against the float64 reference ``simulate_scalar`` on the
same slots, works and powers.

Powers other than 1 reach the power-weighted fair share and deficit, each
task's owner power (``pw_own``) and the trigger's ``queue / pw`` ratio;
with every power 1 those reduce to counting. So the sweep is also run on
all-ones and on re-permuted powers, which must read differently.
"""

import numpy as np
import pytest

from repro import lab
from repro.runtime.vector_backend import simulate_scalar

TABLE = ((59, 0.5), (4, 1.0), (1, 0.25))
SEEDS = range(2**31 + 100, 2**31 + 108)
SLOTS = 32
TRIGGER = {"floor": 0.05, "p": 0.001, "q": 0.0001, "t_task": 0.0001,
           "packets_per_step": 64.0}

# Tolerances, relative to the reference, with their reasons:
# * ``completed`` and ``arrived`` exact: counts stay exact in float32.
# * ``mean_response``, ``p99_response``, ``moved_units`` within 1e-6:
#   float32 rounds each operation by at most 6e-8, and each metric is a
#   mean, a sum or one task's value over a few roundings a task (measured
#   here: <= 4.4e-7). A task whose owner flipped to the neighbouring
#   interval would move the mean by ~1e-2 at 64 nodes, so this also
#   asserts that no owner flipped on these seeds.
# * ``trigger_fires`` exact: one flipped fire decision would move the
#   queues by a slot's excess, far past the tolerances above.
RTOL = 1e-6


def _powers(seed: int) -> tuple:
    table = np.repeat([p for _, p in TABLE], [c for c, _ in TABLE])
    return tuple(np.random.default_rng(seed).permutation(table).tolist())


def _scenarios(powers: tuple) -> list:
    base = lab.Scenario(
        cluster=lab.ClusterSpec(powers=powers),
        workload=lab.WorkloadSpec(
            process="poisson", horizon=float(SLOTS), work_mean=6.0,
            packet_mean=8.0, params={"rate": 0.8 * sum(powers) / 6.0}),
        policy=lab.PolicySpec(name="psts", params=TRIGGER))
    return lab.expand_grid(base, {"seed": SEEDS})


def _sweep(powers: tuple) -> list[dict]:
    results = lab.sweep(_scenarios(powers), backend="batched", dt=1.0)
    return [dict(r.metrics) for r in results]


@pytest.fixture(scope="module")
def google64():
    powers = _powers(3)
    assert sum(powers) == 59 * 0.5 + 4 * 1.0 + 0.25
    return powers, _sweep(powers)


def test_heterogeneous_sweep_matches_the_reference(google64):
    powers, got = google64
    slot, works, pw, cfg, scale = lab.get_backend("batched").compile(
        _scenarios(powers), 1.0)
    assert np.array_equal(pw, powers) and scale is None
    for i, m in enumerate(got):
        ref = simulate_scalar(slot[i], works[i], pw, cfg)
        assert m["completed"] == m["arrived"] == ref["completed"]
        assert m["trigger_fires"] == ref["trigger_fires"]
        for k in ("mean_response", "p99_response", "moved_units"):
            assert m[k] == pytest.approx(ref[k], rel=RTOL), k
    assert any(m["trigger_fires"] > 0 for m in got)


@pytest.mark.parametrize("other", ["all_ones", "re_permuted"])
def test_other_powers_read_differently(google64, other):
    """The same seeds on other powers: the arrivals are the same, the
    placements are not. A program that ignored the powers, or their
    order, would read the same here."""
    powers, got = google64
    others = (tuple([1.0] * len(powers)) if other == "all_ones"
              else _powers(4))
    assert others != powers
    theirs = _sweep(others)
    if other == "re_permuted":   # same capacity: the same arrivals
        assert [m["completed"] for m in theirs] == \
            [m["completed"] for m in got]
    gaps = [abs(a["mean_response"] - b["mean_response"]) / b["mean_response"]
            for a, b in zip(theirs, got)]
    assert max(gaps) > 1e3 * RTOL
