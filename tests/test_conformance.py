"""Cross-backend conformance: the fidelity envelope and conservation laws,
enforced over randomized inputs (PR 5 satellite).

Two families of invariants:

* **Fidelity envelope** — a uniform synthetic Scenario run on the events
  and batched backends must agree on makespan within the documented
  envelope (ROADMAP: the fluid model reads ~1-3% off on makespan, with
  rare light-load outliers; we enforce <= 15% + two slot widths) and must
  realize the identical workload (same arrived count from the same seed).
* **Conservation** — under arbitrary fault + eviction + resize churn the
  event engine must neither leak nor duplicate work: at *any* cut instant
  ``admitted == completed + in_flight`` (work units), every task
  eventually completes, and wasted service is exactly the progress churn
  destroyed. The same holds federation-wide with WAN exchange on top.

Property-based tests run under hypothesis with a bounded, derandomized
profile so CI wall time stays flat; deterministic companions pin the same
invariants on fixed examples.
"""

from __future__ import annotations

import numpy as np
import pytest

from repro import lab
from repro.runtime import ClusterRuntime
from repro.traces import Evictions, TraceSchema

from hypothesis import given, settings
from hypothesis import strategies as st

# bounded, derandomized: identical examples on every CI run, ~seconds of
# wall time (the batched backend recompiles per workload shape)
FAST_PROFILE = dict(max_examples=6, deadline=None, derandomize=True)
CHEAP_PROFILE = dict(max_examples=20, deadline=None, derandomize=True)

# the enforced fidelity envelope (see module docstring)
MAKESPAN_REL_TOL = 0.15
DT = 1.0


# ---------------------------------------------------------------------------
# events vs batched: the fidelity envelope
# ---------------------------------------------------------------------------

def _uniform_scenario(seed: int) -> lab.Scenario:
    """A random *subcritical* uniform scenario, derived deterministically
    from one integer so hypothesis shrinking stays meaningful. The fluid
    model's timeline ends at the horizon, so the documented envelope only
    covers stable regimes — the offered load is kept at 30-75% of the
    cluster's capacity."""
    rng = np.random.default_rng(seed)
    cluster = lab.ClusterSpec(n_nodes=int(rng.integers(2, 9)),
                              power_seed=int(rng.integers(0, 16)),
                              bandwidth=256.0)
    work_mean = float(rng.uniform(2.0, 6.0))
    utilization = float(rng.uniform(0.3, 0.75))
    rate = utilization * float(cluster.resolve_powers().sum()) / work_mean
    return lab.Scenario(
        cluster=cluster,
        workload=lab.WorkloadSpec(
            process="poisson", horizon=50.0, work_dist="uniform",
            work_mean=work_mean, params={"rate": rate}),
        policy=lab.PolicySpec(
            "psts" if rng.integers(0, 2) else "arrival_only",
            trigger_period=1.0),
        seed=int(rng.integers(0, 1 << 31)))


def _assert_envelope(sc: lab.Scenario) -> None:
    e = lab.run(sc, backend="events")
    b = lab.run(sc, backend="batched", dt=DT)
    # identical realization: the same seed must produce the same workload
    assert e["arrived"] == b["arrived"]
    assert e["completed"] == e["arrived"]
    assert b["completed"] == b["arrived"]
    if e["completed"] == 0:
        return
    gap = abs(e["makespan"] - b["makespan"])
    assert gap <= MAKESPAN_REL_TOL * e["makespan"] + 2 * DT, (
        f"makespan fidelity envelope violated: events {e['makespan']:.3f} "
        f"vs batched {b['makespan']:.3f} (seed {sc.seed})")
    # the fluid model has no head-of-line blocking: it may read optimistic
    # on mean response, but a catastrophic divergence is a bug
    assert b["mean_response"] <= 2.0 * e["mean_response"] + 2 * DT


@pytest.mark.parametrize("seed", [3, 11, 42, 1234])
def test_events_vs_batched_makespan_examples(seed):
    _assert_envelope(_uniform_scenario(seed))


@settings(**FAST_PROFILE)
@given(st.integers(min_value=0, max_value=2**31 - 1))
def test_events_vs_batched_makespan_property(seed):
    _assert_envelope(_uniform_scenario(seed))


# ---------------------------------------------------------------------------
# conservation under fault + eviction + resize churn
# ---------------------------------------------------------------------------

POWERS = (3.0, 1.0, 4.0, 2.0)


def _churn_inputs(seed: int):
    """Random trace + fault schedule, derived from one integer."""
    rng = np.random.default_rng(seed)
    m = int(rng.integers(5, 60))
    k = int(rng.integers(0, m))
    trace = TraceSchema(
        t_arrive=np.sort(rng.uniform(0.0, 30.0, m)),
        works=rng.uniform(0.5, 4.0, m),
        packets=rng.uniform(1.0, 8.0, m),
        priority=rng.integers(0, 3, m).astype(np.int32),
        evictions=Evictions(rng.integers(0, m, k),
                            rng.uniform(0.0, 40.0, k)),
        ends_evicted=rng.random(m) < 0.1)
    # up to two fail->join pairs on distinct nodes (never all four), plus
    # up to two resizes anywhere in [0.3x, 2x]
    nodes = rng.permutation(len(POWERS))[:int(rng.integers(0, 3))]
    failures, joins = [], []
    for nd in nodes:
        t_fail = float(rng.uniform(0.0, 25.0))
        failures.append((t_fail, int(nd)))
        joins.append((t_fail + float(rng.uniform(1.0, 15.0)), int(nd)))
    resizes = [(float(rng.uniform(0.0, 35.0)),
                int(rng.integers(0, len(POWERS))),
                float(rng.uniform(0.3, 2.0)))
               for _ in range(int(rng.integers(0, 3)))]
    return trace, failures, joins, resizes


def _assert_conserved(seed: int) -> None:
    trace, failures, joins, resizes = _churn_inputs(seed)
    rt = ClusterRuntime(POWERS, "psts", trigger_period=1.0, seed=0,
                        policy_kwargs={"floor": 0.05})
    rt.schedule_workload(trace, failures=failures, joins=joins,
                         resizes=resizes)
    # conservation must hold at ANY cut instant, not just at the end
    for cut in (5.0, 12.0, 21.0, 33.0):
        rt.advance(until=cut)
        c = rt.work_census(cut)
        assert c["conservation_gap"] <= 1e-6 * max(c["admitted"], 1.0), (
            f"work leaked mid-run at t={cut} (seed {seed}): {c}")
    rt.advance(until=1e9)  # drain
    m = rt.metrics
    assert m.completed == m.arrived == trace.m, (seed, m.completed)
    end = rt.work_census()
    assert end["in_flight"] == pytest.approx(0.0, abs=1e-9)
    assert end["admitted"] == pytest.approx(float(trace.works.sum()))
    assert end["completed"] == pytest.approx(end["admitted"])
    assert m.wasted_work >= -1e-12
    # task-level audit: every eviction/restart the metrics counted is
    # visible on some task, and vice versa
    assert sum(t.evictions for t in rt.tasks.values()) == m.evictions
    assert sum(t.restarts for t in rt.tasks.values()) == m.restarts
    if m.evictions == 0 and m.restarts == 0:
        assert m.wasted_work == pytest.approx(0.0)


@pytest.mark.parametrize("seed", [0, 7, 19, 101, 555])
def test_conservation_under_churn_examples(seed):
    _assert_conserved(seed)


@settings(**CHEAP_PROFILE)
@given(st.integers(min_value=0, max_value=2**31 - 1))
def test_conservation_under_churn_property(seed):
    _assert_conserved(seed)


def test_eviction_requeues_and_wastes_progress():
    """One task, one mid-service eviction: the attempt's progress is
    wasted, the task restarts through admission, and work still conserves
    exactly."""
    trace = TraceSchema(t_arrive=[0.0], works=[4.0], packets=[1.0],
                        evictions=Evictions([0], [2.0]))
    rt = ClusterRuntime((1.0,), "jsq", trigger_period=0.0)
    m = rt.run(trace)
    assert m.completed == 1 and m.evictions == 1
    assert m.wasted_work == pytest.approx(2.0)  # 2 time units at power 1
    assert m.makespan == pytest.approx(6.0)     # restart from scratch
    assert rt.tasks[0].evictions == 1
    c = rt.work_census()
    assert c["admitted"] == c["completed"] == pytest.approx(4.0)


def test_eviction_of_finished_task_is_noop():
    trace = TraceSchema(t_arrive=[0.0], works=[1.0], packets=[1.0],
                        evictions=Evictions([0], [5.0]))
    m = ClusterRuntime((1.0,), "jsq", trigger_period=0.0).run(trace)
    assert m.completed == 1 and m.evictions == 0
    assert m.wasted_work == pytest.approx(0.0)
    assert m.makespan == pytest.approx(1.0)


def test_completion_beats_eviction_on_timestamp_tie():
    trace = TraceSchema(t_arrive=[0.0], works=[2.0], packets=[1.0],
                        evictions=Evictions([0], [2.0]))
    m = ClusterRuntime((1.0,), "jsq", trigger_period=0.0).run(trace)
    assert m.completed == 1 and m.evictions == 0
    assert m.makespan == pytest.approx(2.0)


def test_end_mode_eviction_outcomes_counted_apart_from_completions():
    """Satellite fix: an eviction-truncated task still 'completes' its
    truncated service in the replay, but the eviction is counted so
    throughput analyses can subtract it."""
    trace = TraceSchema(t_arrive=[0.0, 0.0], works=[1.0, 1.0],
                        packets=[1.0, 1.0],
                        ends_evicted=np.array([True, False]))
    m = ClusterRuntime((1.0, 1.0), "jsq", trigger_period=0.0).run(trace)
    assert m.completed == 2
    assert m.evictions == 1
    assert m.wasted_work == pytest.approx(0.0)  # nothing was interrupted


def test_resize_banks_progress_and_reshapes_completion():
    """A resize mid-service continues the task at the new rate from its
    banked progress — no restart, no waste."""
    trace = TraceSchema(t_arrive=[0.0], works=[8.0], packets=[1.0])
    rt = ClusterRuntime((2.0,), "jsq", trigger_period=0.0)
    m = rt.run(trace, resizes=[(2.0, 0, 0.5)])
    # 4 units done by t=2 at power 2; remaining 4 at power 1 -> t=6
    assert m.makespan == pytest.approx(6.0)
    assert m.resizes == 1 and m.restarts == 0
    assert m.wasted_work == pytest.approx(0.0)
    # the task entered service at t=0: its wait is 0, not the garbage
    # "response - work/current-power" would yield after the rate change
    assert m.mean_wait == pytest.approx(0.0)
    # resize to zero is a removal: the node fails, the task restarts later
    rt2 = ClusterRuntime((2.0,), "jsq", trigger_period=0.0)
    m2 = rt2.run(TraceSchema(t_arrive=[0.0], works=[8.0], packets=[1.0]),
                 resizes=[(2.0, 0, 0.0)], joins=[(3.0, 0)])
    assert m2.failures == 1 and m2.restarts == 1
    assert m2.makespan == pytest.approx(7.0)  # rejoin at 3 + 8/2


def test_zero_resize_is_a_failure_on_every_backend():
    """A resize to fraction 0 is a removal in disguise: schedule
    resolution normalizes it into a failure, so the events engine and the
    batched power-scale lowering agree that the node is down until its
    join — which restores the pre-zero power on both."""
    sc = lab.Scenario(
        cluster=lab.ClusterSpec(powers=(2.0, 2.0)),
        workload=lab.WorkloadSpec(process="poisson", horizon=8.0,
                                  params={"rate": 1.0}),
        policy=lab.PolicySpec("arrival_only"),
        faults=lab.FaultSpec(failures=((1.0, 1),),
                             joins=((2.0, 1), (4.0, 1)),
                             resizes=((3.0, 1, 0.0),)))
    failures, joins, resizes = lab.resolve_fault_schedule(sc)
    assert (3.0, 1) in failures and resizes == ()
    backend = lab.get_backend("batched")
    assert backend.eligible(sc) is None
    scale = backend._power_scale(sc, n_slots=8, n=2, dt=1.0)
    np.testing.assert_allclose(scale[3, 1], 0.0)  # down after the zero
    np.testing.assert_allclose(scale[4:, 1], 1.0)  # the join restores it
    e = lab.run(sc, backend="events")
    assert e["completed"] == e["arrived"]
    assert e["failures"] == 2 and e["joins"] == 2  # zero-resize = failure


def _churn_members(tmp_path) -> list:
    """Two skewed members, each replaying an eviction stream from a
    normalized CSV + sidecar (the PR 5 churn scenarios)."""
    members = []
    rng = np.random.default_rng(5)
    for i, rate in enumerate((18, 2)):  # skewed: WAN exchange happens
        m = 40 * rate // 10
        t = np.sort(rng.uniform(0.0, 20.0, m))
        k = m // 3
        trace = TraceSchema(
            t_arrive=t, works=rng.uniform(1.0, 3.0, m),
            packets=rng.uniform(1.0, 4.0, m),
            evictions=Evictions(rng.integers(0, m, k),
                                rng.uniform(0.0, 30.0, k)))
        csv = tmp_path / f"member{i}.csv"
        side = tmp_path / f"member{i}.json"
        from repro.traces import write_normalized_csv
        write_normalized_csv(trace, csv, constraints_path=side)
        members.append(lab.Scenario(
            name=f"m{i}",
            cluster=lab.ClusterSpec(powers=(2.0, 1.0, 3.0),
                                    bandwidth=256.0),
            workload=lab.WorkloadSpec(
                trace=lab.TraceRef(
                    path=str(csv), format="csv",
                    params={"constraints_path": str(side)}),
                horizon=None),
            policy=lab.PolicySpec("psts", trigger_period=1.0,
                                  params={"floor": 0.05})))
    return members


@pytest.mark.parametrize("mode", ["lockstep", "async"])
def test_federated_members_replay_eviction_streams(tmp_path, mode):
    """Churn replay conserves tasks AND work units federation-wide while
    WAN exchange is live — in both stepping modes (the async engine must
    not lose in-flight work or eviction rows to its event heap)."""
    from repro.federation import Federation, TopologySpec
    members = _churn_members(tmp_path)
    fed = Federation(members=tuple(members),
                     topology=TopologySpec(kind="full", bandwidth=16.0,
                                           latency=1.0),
                     exchange_period=2.0, mode=mode)
    from repro.federation.runtime import FederatedRuntime
    frt = FederatedRuntime(fed)
    report = frt.run()
    total = sum(sc.workload.materialize(sc.seed).m for sc in members)
    assert report.aggregate.completed == total
    assert report.aggregate.evictions > 0
    # waste only accrues when an eviction catches a task mid-service;
    # what must ALWAYS hold is that it never goes negative and that the
    # federation-wide work books balance (below)
    assert report.aggregate.wasted_work >= 0.0
    end = frt.work_census(1e9)
    assert end["conservation_gap"] <= 1e-6 * max(end["admitted"], 1.0)
    assert end["admitted"] == pytest.approx(end["completed"])


def test_lockstep_and_async_agree_on_link_free_churn(tmp_path):
    """With no WAN links there is nothing for the stepping modes to
    disagree about: every member runs its own trace to completion, so the
    lockstep and async engines must produce identical ``Metrics.summary()``
    dictionaries on the PR 5 churn members."""
    from repro.federation import Federation, TopologySpec
    members = tuple(_churn_members(tmp_path))
    topo = TopologySpec(kind="isolated")
    summaries = {}
    for mode in ("lockstep", "async"):
        from repro.federation.runtime import FederatedRuntime
        frt = FederatedRuntime(Federation(members=members, topology=topo,
                                          exchange_period=2.0, mode=mode))
        summaries[mode] = frt.run().aggregate.summary()
    assert summaries["lockstep"] == summaries["async"]


def test_batched_rejects_eviction_traces_with_reason(tmp_path):
    """Eligibility satellite: the fluid backend cannot requeue individual
    tasks — a preempted trace is rejected with a readable reason, and the
    events backend takes it."""
    trace = TraceSchema(t_arrive=[0.0, 1.0], works=[2.0, 2.0],
                        packets=[1.0, 1.0],
                        evictions=Evictions([0], [0.5]))
    csv = tmp_path / "t.csv"
    side = tmp_path / "t.json"
    from repro.traces import write_normalized_csv
    write_normalized_csv(trace, csv, constraints_path=side)
    sc = lab.Scenario(
        cluster=lab.ClusterSpec(powers=(1.0, 2.0)),
        workload=lab.WorkloadSpec(
            trace=lab.TraceRef(path=str(csv),
                               params={"constraints_path": str(side)}),
            horizon=None),
        policy=lab.PolicySpec("arrival_only"))
    reason = lab.get_backend("batched").eligible(sc)
    assert reason is not None and "eviction" in reason
    assert lab.get_backend("events").eligible(sc) is None
    r = lab.run(sc, backend="events")
    assert r["completed"] == 2 and r["evictions"] == 1
    assert r.extras["work_census"]["conservation_gap"] <= 1e-9


def test_hypothesis_profile_is_bounded():
    """The CI fast subset includes this file: the property profiles must
    stay small enough to keep wall time ~flat."""
    assert FAST_PROFILE["max_examples"] <= 10
    assert CHEAP_PROFILE["max_examples"] <= 25
    assert FAST_PROFILE["derandomize"] and CHEAP_PROFILE["derandomize"]
