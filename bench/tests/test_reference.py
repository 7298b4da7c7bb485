"""The benchmark's copies of the generators and of the float64 engine
agree with the program's at a small size, seed for seed."""

import numpy as np
import pytest

from bench import cells
from bench.reference import engine
from bench.reference import workload as ref

SEEDS = [0, 7, 2**31 + 5, cells.POOL_BASE + 777]
PROCESSES = [("poisson", {"rate": 40.0}),
             ("bursty", {"rate_lo": 8.0, "rate_hi": 200.0,
                         "sojourn_lo": 20.0, "sojourn_hi": 4.0}),
             ("diurnal", {"rate_mean": 40.0, "amplitude": 0.8,
                          "period": 10.0})]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("process,params", PROCESSES)
def test_generators_match_program(process, params, seed):
    from repro.runtime.workload import make_workload, to_slots
    prog = make_workload(process, horizon=32.0, work_dist="uniform",
                         work_mean=6.0, packet_mean=8.0, seed=seed, **params)
    t, w = ref.make_workload(process, horizon=32.0, work_dist="uniform",
                             work_mean=6.0, packet_mean=8.0, seed=seed,
                             **params)
    assert np.array_equal(t, prog.t_arrive) and np.array_equal(w, prog.works)
    s, _, count = to_slots(prog, 1.0, 32)
    assert np.array_equal(ref.to_slots(t, 1.0, 32), s[:count])
    assert np.array_equal(
        ref.slot_counts(process, horizon=32.0, seed=seed, dt=1.0,
                        n_slots=32, **params),
        np.bincount(s[:count], minlength=32))


def test_powers_match_program():
    from repro import lab
    spec = lab.ClusterSpec(n_nodes=300, power_low=1, power_high=10,
                           power_seed=3)
    assert np.array_equal(ref.cluster_powers(300, 1, 10, 3),
                          spec.resolve_powers())


@pytest.mark.parametrize("seed", SEEDS)
def test_engine_matches_program_reference(seed):
    from repro.runtime.vector_backend import VectorConfig, simulate_scalar
    n, T = 48, 24
    powers = ref.cluster_powers(n, 1, 10, 0)
    t, w = ref.make_workload("bursty", horizon=float(T), work_dist="uniform",
                             work_mean=6.0, packet_mean=8.0, seed=seed,
                             rate_lo=powers.sum() / 30,
                             rate_hi=powers.sum() * 0.7)
    slot = ref.to_slots(t, 1.0, T)
    kw = dict(rebalance=True, floor=0.05, p=1e-3, q=1e-4, t_task=1e-4,
              packets_per_step=64.0, packets_per_unit=1.5)
    want = simulate_scalar(slot, w[:slot.size], powers,
                           VectorConfig(n_nodes=n, n_slots=T, **kw))
    got = engine.simulate(slot, w[:slot.size], powers, n_slots=T, dt=1.0,
                          **kw)
    assert got == pytest.approx(want, rel=1e-12, abs=0)
