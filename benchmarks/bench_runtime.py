"""Event-driven cluster-runtime benchmarks, declared through ``repro.lab``.

* ``policy_grid`` — policies x arrival processes x failure on/off as
  Scenarios executed on the events backend, reporting mean/P99/wait
  response, migration volume and trigger fires; asserts the headline shape:
  PSTS-with-trigger achieves lower mean response time than
  place-on-arrival-only under bursty arrivals.

Timing note for trajectory diffs: since the repro.lab migration every
``us_per_call`` here is END-TO-END (scenario lowering + workload
materialization + engine + result assembly), where pre-lab emissions timed
the bare engine call only — expect a one-off level shift, not a regression.
* ``vector_sweep`` — a 128-seed sweep auto-dispatched by ``lab.sweep`` to
  the batched backend (ONE lax.scan call), asserting per-seed agreement
  with the scalar reference engine to float tolerance, and reporting the
  batched-vs-Python-loop speed.
"""

from __future__ import annotations

import time

import numpy as np

from repro import lab

N_NODES = 16
POWERS = tuple(
    np.random.default_rng(0).integers(1, 10, size=N_NODES).astype(float))

# heavy-burst regime: offered load during bursts exceeds cluster power, so
# queues build and rebalancing has something to do
PROCESSES = {
    "poisson": {"rate": 8.0},
    "bursty": {"rate_lo": 0.5, "rate_hi": 18.0, "sojourn_lo": 25.0,
               "sojourn_hi": 6.0},
    "diurnal": {"rate_mean": 8.0, "amplitude": 0.9, "period": 80.0},
}
WORK_MEAN = 6.0
POLICIES = ("jsq", "arrival_only", "psts")
HORIZON = 200.0
SEEDS = (0, 1)
FAULTS = lab.FaultSpec(failures=((40.0, 2), (90.0, 11)),
                       joins=((130.0, 2),))


def _scenario(policy: str, process: str, fail: bool, seed: int
              ) -> lab.Scenario:
    if policy == "psts":
        pol = lab.PolicySpec("psts", trigger_period=1.0,
                             params={"floor": 0.05})
        bandwidth = 256.0
    else:
        pol = lab.PolicySpec(policy)
        bandwidth = 64.0
    return lab.Scenario(
        name=f"{process}{'+fail' if fail else ''}/{policy}",
        cluster=lab.ClusterSpec(powers=POWERS, bandwidth=bandwidth),
        workload=lab.WorkloadSpec(process=process, horizon=HORIZON,
                                  work_mean=WORK_MEAN,
                                  params=PROCESSES[process]),
        policy=pol,
        faults=FAULTS if fail else lab.FaultSpec(),
        seed=seed, engine_seed=7)


def _run(policy: str, process: str, fail: bool, seed: int):
    t0 = time.perf_counter()
    r = lab.run(_scenario(policy, process, fail, seed), backend="events")
    us = (time.perf_counter() - t0) * 1e6
    assert r["completed"] == r["arrived"], (policy, process, fail, seed)
    return r, us


def policy_grid() -> list[tuple[str, float, str]]:
    rows = []
    means: dict[tuple, float] = {}
    for process in PROCESSES:
        for fail in (False, True):
            for policy in POLICIES:
                rs, us = [], 0.0
                for seed in SEEDS:
                    r, dt = _run(policy, process, fail, seed)
                    rs.append(r)
                    us += dt
                mean = float(np.mean([r["mean_response"] for r in rs]))
                p99 = float(np.mean([r["p99_response"] for r in rs]))
                wait = float(np.mean([r["mean_wait"] for r in rs]))
                means[(process, fail, policy)] = mean
                tag = f"{process}{'+fail' if fail else ''}"
                rows.append((
                    f"runtime/{tag}/{policy}", us / len(SEEDS),
                    f"mean_resp={mean:.3f};p99_resp={p99:.3f};"
                    f"mean_wait={wait:.3f};"
                    f"migrations={sum(r['migrations'] for r in rs)};"
                    f"fires={sum(r['trigger_fires'] for r in rs)};"
                    f"restarts={sum(r['restarts'] for r in rs)}"))
    # acceptance shape: the trigger pays under bursts, with and without
    # failures in play
    for fail in (False, True):
        psts = means[("bursty", fail, "psts")]
        arr = means[("bursty", fail, "arrival_only")]
        assert psts < arr, (
            f"PSTS {psts:.3f} must beat arrival-only {arr:.3f} "
            f"under bursty arrivals (fail={fail})")
    return rows


def vector_sweep() -> list[tuple[str, float, str]]:
    from repro.runtime.vector_backend import reference_gaps, simulate_scalar

    n_seeds = 128
    base = lab.Scenario(
        cluster=lab.ClusterSpec(powers=POWERS),
        workload=lab.WorkloadSpec(process="poisson", horizon=HORIZON,
                                  work_mean=WORK_MEAN,
                                  params=PROCESSES["poisson"]),
        policy=lab.PolicySpec("psts", params={"floor": 0.1}))
    scenarios = lab.expand_grid(base, {"seed": range(n_seeds)})

    lab.sweep(scenarios, backend="batched")  # compile at the timed shape
    t0 = time.perf_counter()
    results = lab.sweep(scenarios, backend="auto")
    us_sweep = (time.perf_counter() - t0) * 1e6
    assert all(r.backend == "batched" for r in results), \
        "a uniform 128-seed sweep must auto-dispatch to the batched backend"

    # scalar reference over a sample of seeds: per-seed agreement with the
    # batched results, and the cost of the equivalent Python loop. Both
    # sides are timed end-to-end (scenario lowering + engine) so the
    # per-seed comparison is like-for-like.
    backend = lab.get_backend("batched")
    sample = list(range(0, n_seeds, 8))
    max_err = 0.0
    t0 = time.perf_counter()
    for i in sample:
        slot, works, powers, cfg, _ = backend.compile([scenarios[i]],
                                                      backend.default_dt)
        sm = simulate_scalar(slot[0], works[0], powers, cfg)
        # raises outside the float32 engine's stated tolerance
        gaps = reference_gaps(results[i].metrics, sm, cfg.n_slots)
        max_err = max(max_err, *gaps.values())
    us_scalar = (time.perf_counter() - t0) / len(sample) * 1e6

    mean_resp = float(np.mean([r["mean_response"] for r in results]))
    return [
        (f"runtime/vector_sweep/seeds={n_seeds}", us_sweep,
         f"sweep_e2e_us_per_seed={us_sweep / n_seeds:.1f};"
         f"scalar_e2e_us_per_seed={us_scalar:.1f};"
         f"max_rel_err={max_err:.2e};"
         f"mean_resp={mean_resp:.3f}"),
    ]


ALL = [policy_grid, vector_sweep]
