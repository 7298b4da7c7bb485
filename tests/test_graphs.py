"""DAG workloads (ISSUE 7 tentpole): DagSpec validation and topology,
the event engine's release frontier (no child starts before all parents
complete, including under eviction/failure churn), data-locality
placement and transfer accounting, critical-path metrics, and DAG
content in Scenario fingerprints."""

import json

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st
from repro.graphs import DAG_KINDS, DagSpec, make_dag
from repro.obs import Tracer
from repro.runtime.runtime import ClusterRuntime
from repro.traces import Evictions, trace_scale, write_normalized_csv
from repro.traces.schema import TraceSchema


def _trace(m, dag, work=2.0, t_arrive=None, evictions=None):
    return TraceSchema(
        t_arrive=np.zeros(m) if t_arrive is None else np.asarray(t_arrive),
        works=np.full(m, float(work)), packets=np.full(m, 4.0), dag=dag,
        evictions=evictions if evictions is not None else Evictions())


def _service_starts(tracer):
    """tid -> earliest service-attempt start, from the lifecycle trace
    (every attempt emits a 'service' span, including interrupted ones)."""
    starts = {}
    ev = tracer._events
    for i in range(0, len(ev), 8):
        if ev[i + 1] == "service":
            tid = ev[i + 5]
            t0 = ev[i + 2]
            starts[tid] = min(starts.get(tid, t0), t0)
    return starts


def _assert_parents_first(rt, dag, tracer=None):
    """No task's first service attempt precedes any parent's completion."""
    starts = _service_starts(tracer) if tracer is not None else {
        tid: task.t_attempt_start for tid, task in rt.tasks.items()}
    parents = dag.parents_of()
    for tid, ps in enumerate(parents):
        for p in ps:
            assert rt.tasks[p].t_finish <= starts[tid] + 1e-9, (
                f"task {tid} started at {starts[tid]} before parent {p} "
                f"finished at {rt.tasks[p].t_finish}")


# ---------------------------------------------------------------------------
# DagSpec: validation, diagnostics, topology utilities
# ---------------------------------------------------------------------------

def test_empty_dag():
    dag = DagSpec()
    assert dag.empty and dag.k == 0 and dag.m == 0
    assert dag.depth() == 0 and dag.width() == 0
    assert dag.critical_path() == 0.0


def test_edgeless_but_declared_is_not_empty():
    dag = DagSpec(m=4)
    assert not dag.empty and dag.k == 0
    assert dag.depth() == 1 and dag.width() == 4
    assert dag.critical_path() == 1.0


def test_chain_topology():
    dag = make_dag({"kind": "chain"}, 5, 0)
    assert dag.k == 4 and dag.depth() == 5 and dag.width() == 1
    assert dag.critical_path() == 5.0
    assert dag.critical_path(np.array([1.0, 2.0, 3.0, 4.0, 5.0])) == 15.0
    assert list(dag.topo) == [0, 1, 2, 3, 4]


def test_diamond_topology():
    dag = make_dag({"kind": "diamond"}, 6, 0)
    # 1 source -> 4 middles -> 1 sink
    assert dag.depth() == 3 and dag.width() == 4
    assert dag.critical_path() == 3.0
    assert dag.parents_of()[5] == [1, 2, 3, 4]
    assert dag.children_of()[0] == [1, 2, 3, 4]


def test_self_loop_diagnostic():
    with pytest.raises(ValueError, match=r"self-loop: task 1 -> 1"):
        DagSpec(child=np.array([1]), parent=np.array([1]), m=3)


def test_cycle_diagnostic_names_the_cycle():
    with pytest.raises(ValueError, match=r"cycle: \d+( -> \d+)+"):
        DagSpec(child=np.array([2, 3, 1]), parent=np.array([1, 2, 3]), m=4)


def test_duplicate_edge_rejected():
    with pytest.raises(ValueError, match="duplicate"):
        DagSpec(child=np.array([1, 1]), parent=np.array([0, 0]), m=2)


def test_edge_out_of_range_rejected():
    with pytest.raises(ValueError, match="references task 5"):
        DagSpec(child=np.array([5]), parent=np.array([0]), m=3)


def test_bad_out_size_rejected():
    with pytest.raises(ValueError, match="out_size"):
        DagSpec(child=np.array([1]), parent=np.array([0]),
                out_size=np.array([-1.0, 0.0]), m=2)


def test_json_round_trip():
    dag = make_dag({"kind": "random", "out_size": 2.0}, 12, 7)
    back = DagSpec.from_dict(json.loads(json.dumps(dag.to_dict())))
    assert back.m == dag.m
    assert np.array_equal(back.child, dag.child)
    assert np.array_equal(back.parent, dag.parent)
    assert np.allclose(back.out_size, dag.out_size)


def test_select_reindexes_and_drops_cut_edges():
    dag = make_dag({"kind": "diamond"}, 6, 0)
    sub = dag.select(np.array([0, 1, 5]))
    assert sub.m == 3
    # 0->1 and 1->5 survive (re-indexed); edges through dropped middles go
    pairs = set(zip(sub.child.tolist(), sub.parent.tolist()))
    assert pairs == {(1, 0), (2, 1)}


@pytest.mark.parametrize("kind", sorted(DAG_KINDS))
def test_generators_produce_valid_dags(kind):
    for m in (1, 2, 7, 24):
        dag = make_dag({"kind": kind, "out_size": 4.0}, m, 3)
        assert dag.m == m
        # construction validates acyclicity; generators are topological
        assert (dag.parent < dag.child).all()
        assert dag.depth() >= 1 and dag.width() >= 1


def test_make_dag_explicit_edges_m_mismatch():
    with pytest.raises(ValueError, match="declares 5 tasks"):
        make_dag({"edges": [[1, 0]], "m": 5}, 3, 0)


# ---------------------------------------------------------------------------
# Release frontier: engine semantics
# ---------------------------------------------------------------------------

def test_child_waits_for_parent():
    dag = make_dag({"kind": "chain"}, 2, 0)
    tr = Tracer()
    rt = ClusterRuntime(np.array([1.0, 1.0]), "round_robin", tracer=tr)
    m = rt.run(_trace(2, dag, work=4.0))
    assert m.completed == 2
    parent, child = rt.tasks[0], rt.tasks[1]
    assert child.t_attempt_start >= parent.t_finish - 1e-9
    # the wait in the frontier is a first-class lifecycle phase
    names = [tr._events[i + 1] for i in range(0, len(tr._events), 8)]
    assert "blocked-on-parents" in names


def test_blocked_census_while_gated():
    dag = make_dag({"kind": "chain"}, 2, 0)
    rt = ClusterRuntime(np.array([1.0]), "round_robin")
    rt.schedule_workload(_trace(2, dag, work=4.0))
    rt.advance(until=1.0)  # parent running, child arrived but gated
    c = rt.census()
    assert c["blocked"] == 1 and c["running"] == 1
    wc = rt.work_census(1.0)
    assert wc["blocked"] == 4.0
    assert wc["conservation_gap"] < 1e-9
    rt.advance(until=100.0)
    assert rt.census()["blocked"] == 0
    assert rt.metrics.completed == 2


def test_eviction_of_parent_keeps_child_gated():
    # parent evicted mid-service: its attempt is wasted, the child must
    # still wait for the parent's (second) completion, and work units stay
    # conserved throughout
    dag = make_dag({"kind": "chain", "out_size": 8.0}, 2, 0)
    ev = Evictions(task=np.array([0]), time=np.array([2.0]))
    tr = Tracer()
    rt = ClusterRuntime(np.array([1.0, 1.0]), "locality", tracer=tr)
    m = rt.run(_trace(2, dag, work=4.0, evictions=ev))
    assert m.completed == 2
    assert m.evictions == 1 and m.wasted_work > 0
    _assert_parents_first(rt, dag, tr)
    wc = rt.work_census()
    assert wc["conservation_gap"] < 1e-9


def test_probe_reports_frontier_size():
    from repro.obs import ProbeSeries
    dag = make_dag({"kind": "chain"}, 3, 0)
    probe = ProbeSeries(every=0.5)
    rt = ClusterRuntime(np.array([1.0]), "psts", probe=probe)
    rt.run(_trace(3, dag, work=2.0))
    assert max(probe.blocked_tasks) >= 1
    assert probe.to_dict()["blocked_tasks"] == probe.blocked_tasks


# ---------------------------------------------------------------------------
# Data locality: transfer accounting and placement
# ---------------------------------------------------------------------------

def test_transfer_charged_on_remote_fetch():
    # round_robin forces parent -> node 0, child -> node 1: the child's
    # service is delayed by out_size / link_bandwidth and the fetch is
    # booked as a locality miss
    dag = DagSpec(child=np.array([1]), parent=np.array([0]),
                  out_size=np.array([10.0, 0.0]), m=2)
    rt = ClusterRuntime(np.array([1.0, 1.0]), "round_robin",
                        link_bandwidth=5.0)
    m = rt.run(_trace(2, dag, work=4.0))
    # parent: [0, 4] on node 0; child fetch [4, 6], service [6, 10]
    assert m.makespan == pytest.approx(10.0)
    assert m.dag_bytes_moved == pytest.approx(10.0)
    assert m.locality_misses == 1 and m.locality_hits == 0
    assert m.locality_hit_ratio == 0.0


def test_locality_policy_prefers_producer_node():
    dag = DagSpec(child=np.array([1]), parent=np.array([0]),
                  out_size=np.array([10.0, 0.0]), m=2)
    rt = ClusterRuntime(np.array([1.0, 1.0]), "locality",
                        link_bandwidth=5.0)
    m = rt.run(_trace(2, dag, work=4.0))
    # child lands where the parent's output already lives: no fetch
    assert m.makespan == pytest.approx(8.0)
    assert m.dag_bytes_moved == 0.0
    assert m.locality_hits == 1 and m.locality_misses == 0


def test_locality_beats_psts_on_fanin_fanout():
    # the acceptance shape: heavy intermediate outputs over a slow link
    dag = make_dag({"kind": "fanin_fanout", "out_size": 64.0}, 32, 1)
    wl = _trace(32, dag, work=2.0)
    out = {}
    for pol in ("psts", "locality"):
        rt = ClusterRuntime(np.array([2.0, 3.0, 1.0, 4.0]), pol,
                            link_bandwidth=16.0, seed=7)
        out[pol] = rt.run(wl)
    assert out["locality"].cp_stretch < out["psts"].cp_stretch
    assert (out["locality"].locality_hit_ratio
            > out["psts"].locality_hit_ratio)


def test_cp_lower_bound_and_stretch():
    dag = make_dag({"kind": "chain"}, 3, 0)
    rt = ClusterRuntime(np.array([2.0, 1.0]), "psts")
    m = rt.run(_trace(3, dag, work=4.0))
    # chain of 3 x 4 work units on p_max=2: bound 6; makespan 6 exactly
    # (each link runs back-to-back on the fast node)
    assert m.cp_lower_bound == pytest.approx(6.0)
    assert m.cp_stretch >= 1.0 - 1e-9
    assert m.makespan == pytest.approx(m.cp_stretch * m.cp_lower_bound)


def test_arrival_aware_bound_uses_release_times():
    dag = DagSpec(m=2)  # independent, declared
    wl = _trace(2, dag, work=4.0, t_arrive=[0.0, 10.0])
    rt = ClusterRuntime(np.array([1.0]), "psts")
    m = rt.run(wl)
    # the late task cannot finish before 10 + 4; the area bound alone
    # (0 + 8/1) would undershoot
    assert m.cp_lower_bound == pytest.approx(14.0)


# ---------------------------------------------------------------------------
# Conformance under churn (example-based + property-based)
# ---------------------------------------------------------------------------

def _churn_run(seed, policy="locality"):
    rng = np.random.default_rng(seed)
    m = int(rng.integers(8, 40))
    dag = make_dag({"kind": "random", "p": 0.3, "out_size": 16.0}, m,
                   int(rng.integers(0, 1 << 16)))
    t_arrive = np.sort(rng.uniform(0.0, 5.0, m))
    n_ev = int(rng.integers(1, 6))
    ev = Evictions(task=rng.integers(0, m, n_ev),
                   time=rng.uniform(0.5, 20.0, n_ev))
    wl = TraceSchema(t_arrive=t_arrive,
                     works=rng.uniform(0.5, 4.0, m),
                     packets=np.full(m, 4.0), dag=dag, evictions=ev)
    tr = Tracer()
    rt = ClusterRuntime(np.array([2.0, 1.0, 3.0]), policy,
                        link_bandwidth=8.0, seed=seed, tracer=tr)
    failures = [(float(rng.uniform(1.0, 10.0)), 1)]
    joins = [(failures[0][0] + 5.0, 1)]
    mt = rt.run(wl, failures=failures, joins=joins)
    assert mt.completed == m
    _assert_parents_first(rt, dag, tr)
    wc = rt.work_census()
    assert wc["conservation_gap"] < 1e-6
    assert wc["admitted"] == pytest.approx(wc["completed"])


@pytest.mark.parametrize("seed", range(6))
def test_no_child_starts_before_parents_under_churn(seed):
    _churn_run(seed)
    _churn_run(seed + 100, policy="psts")


@settings(max_examples=25, deadline=None)
@given(st.integers(min_value=0, max_value=10_000))
def test_property_release_frontier_conformance(seed):
    _churn_run(seed)


# ---------------------------------------------------------------------------
# Fingerprints fold in DAG content (satellite: sidecar collision class)
# ---------------------------------------------------------------------------

def _sidecar_scenario(tmp_path, dag, tag):
    from repro.lab import ClusterSpec, Scenario, TraceRef, WorkloadSpec
    trace = _trace(dag.m, dag, t_arrive=np.arange(dag.m) * 0.1)
    csv = tmp_path / f"{tag}.csv"
    side = tmp_path / "side.json"  # same path both times — the collision
    write_normalized_csv(trace, str(csv), constraints_path=str(side))
    return Scenario(
        cluster=ClusterSpec(powers=(1.0, 2.0)),
        workload=WorkloadSpec(
            horizon=None,
            trace=TraceRef(path=str(csv), format="csv",
                           params={"constraints_path": str(side)})))


def test_fingerprint_folds_dag_sidecar_content(tmp_path):
    dag_a = make_dag({"kind": "chain", "out_size": 1.0}, 4, 0)
    dag_b = make_dag({"kind": "diamond", "out_size": 1.0}, 4, 0)
    sc_a = _sidecar_scenario(tmp_path, dag_a, "t")
    fp_a = sc_a.fingerprint()
    # overwrite the sidecar at the SAME path with different edges; the
    # scenario JSON is unchanged, only sidecar content differs
    sc_b = _sidecar_scenario(tmp_path, dag_b, "t")
    assert sc_b.to_json() == sc_a.to_json()
    assert sc_b.fingerprint() != fp_a


def test_fingerprint_folds_inline_dag():
    from repro.lab import ClusterSpec, Scenario, WorkloadSpec
    base = dict(cluster=ClusterSpec(powers=(1.0, 2.0)))
    plain = Scenario(workload=WorkloadSpec(), **base)
    chain = Scenario(workload=WorkloadSpec(dag={"kind": "chain"}), **base)
    diamond = Scenario(workload=WorkloadSpec(dag={"kind": "diamond"}),
                       **base)
    fps = {plain.fingerprint(), chain.fingerprint(), diamond.fingerprint()}
    assert len(fps) == 3


# ---------------------------------------------------------------------------
# Spec/backend integration
# ---------------------------------------------------------------------------

def test_workload_spec_realizes_dag():
    from repro.lab import WorkloadSpec
    spec = WorkloadSpec(horizon=20.0, dag={"kind": "random", "p": 0.2})
    wl = spec.materialize(3)
    assert isinstance(wl, TraceSchema) and wl.has_dag
    assert wl.dag.m == wl.m
    # generator draws from the scenario seed: different seeds, different
    # realizations (task counts differ too — compare shapes first)
    wl2 = spec.materialize(4)
    same = (wl.dag.k == wl2.dag.k
            and np.array_equal(wl.dag.child, wl2.dag.child))
    assert not same


def test_workload_spec_rejects_bad_dag():
    from repro.lab import WorkloadSpec
    with pytest.raises(ValueError, match="dag"):
        WorkloadSpec(dag={"kind": "nope"})
    with pytest.raises(ValueError, match="mapping"):
        WorkloadSpec(dag=[["a", "b"]])


def test_batched_and_legacy_reject_dags():
    from repro.lab import ClusterSpec, Scenario, WorkloadSpec
    from repro.lab.backends import get_backend
    sc = Scenario(cluster=ClusterSpec(powers=(1.0, 2.0)),
                  workload=WorkloadSpec(dag={"kind": "chain"}))
    assert get_backend("events").eligible(sc) is None
    for name in ("batched", "legacy"):
        reason = get_backend(name).eligible(sc)
        assert reason is not None and "events backend" in reason


def test_events_backend_runs_dag_scenario():
    from repro.lab import ClusterSpec, Scenario, WorkloadSpec
    from repro.lab.backends import get_backend
    sc = Scenario(
        cluster=ClusterSpec(powers=(2.0, 1.0, 3.0), link_bandwidth=8.0),
        workload=WorkloadSpec(horizon=10.0,
                              dag={"kind": "fanin_fanout",
                                   "out_size": 16.0}))
    r = get_backend("events").run(sc)
    assert r.metrics["cp_lower_bound"] > 0
    assert r.metrics["cp_stretch"] >= 1.0 - 1e-9
    assert (r.metrics["locality_hits"] + r.metrics["locality_misses"]) > 0


def test_unrealizable_dag_is_an_eligibility_reason():
    from repro.lab import ClusterSpec, Scenario, WorkloadSpec
    from repro.lab.backends import get_backend
    sc = Scenario(cluster=ClusterSpec(powers=(1.0,)),
                  workload=WorkloadSpec(
                      horizon=5.0,
                      dag={"edges": [[1, 0]], "m": 9999}))
    reason = get_backend("events").eligible(sc)
    assert reason is not None and "unrealizable" in reason


def test_trace_scale_rejects_dag_traces():
    dag = make_dag({"kind": "chain"}, 3, 0)
    with pytest.raises(ValueError, match="resample"):
        trace_scale(_trace(3, dag), 2.0, seed=0)


def test_google_job_chains_flag():
    from repro.traces import load_google_task_events
    path = "tests/data/google_tiny_events.csv"
    plain = load_google_task_events(path)
    assert not plain.has_dag
    chained = load_google_task_events(path, job_chains=True)
    assert chained.has_dag
    # 4 tasks across 2 jobs -> one chain edge per job with >= 2 tasks,
    # and edges never cross jobs (chains are within-job by construction)
    assert 1 <= chained.dag.k <= chained.dag.m - 1
    assert (chained.dag.parent < chained.dag.child).all()
