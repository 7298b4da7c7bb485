"""Scheduler-as-a-service (PR 8): session lifecycle, streaming/offline
equivalence, the decision stream, task sources, the ``online`` lab
backend, the CLI ``serve`` verb, unified driving verbs across layers, and
the deprecation shims.

The load-bearing property: streaming a trace through
:class:`~repro.serve.SchedulerService` one admission at a time yields a
``Metrics.summary()`` and ``work_census()`` *identical* to offline replay
of the same trace — including under PR 5 eviction/machine-event churn —
because arrivals are queued before the clock passes them and the event
queue orders by (time, kind, seq) regardless of when events were pushed.
"""

from __future__ import annotations

import io
import json
import warnings

import numpy as np
import pytest

from repro import lab
from repro.lab.cli import main as lab_cli
from repro.runtime import ClusterRuntime, Workload, make_workload, run_policy
from repro.runtime.runtime import Task
from repro.serve import (
    Decision,
    DecisionLog,
    IterableSource,
    JsonlSource,
    SchedulerService,
    Session,
    TaskSubmit,
    WorkloadSource,
)

from hypothesis import given, settings
from hypothesis import strategies as st
from test_conformance import POWERS, _churn_inputs

STREAM_PROFILE = dict(max_examples=12, deadline=None, derandomize=True)


def _psts() -> ClusterRuntime:
    """The conformance-suite reference runtime (same ctor as offline)."""
    return ClusterRuntime(POWERS, "psts", trigger_period=1.0, seed=0,
                          policy_kwargs={"floor": 0.05})


def _offline(trace, failures=(), joins=(), resizes=()) -> ClusterRuntime:
    rt = _psts()
    rt.run(trace, failures=failures, joins=joins, resizes=resizes)
    return rt


def _online(trace, failures=(), joins=(), resizes=(), *,
            step: float | None = None) -> SchedulerService:
    """Stream the same trace through a service: arrival-paced micro-steps
    by default (one admission batch per step), or fixed-width steps."""
    svc = SchedulerService(_psts())
    svc.rt.schedule_faults(failures=failures, joins=joins, resizes=resizes)
    src = svc.attach(WorkloadSource(trace))
    if step is None:
        while not src.exhausted:
            svc.advance(until=src.next_time)
    else:
        while svc.session.pending_sources:
            svc.advance(until=svc.now + step)
    svc.drain()
    svc.close()
    return svc


def _assert_identical(off: ClusterRuntime, on: ClusterRuntime) -> None:
    assert on.metrics.summary() == off.metrics.summary()
    assert on.work_census() == off.work_census()


# ---------------------------------------------------------------------------
# session lifecycle: open / feed / submit / advance / drain / close
# ---------------------------------------------------------------------------

def test_open_session_lifecycle():
    wl = make_workload("poisson", horizon=20.0, seed=0, rate=2.0)
    rt = ClusterRuntime((3.0, 1.0, 7.0, 2.0), "jsq")
    s = rt.open_session()
    assert isinstance(s, Session)
    s.feed(WorkloadSource(wl))
    n = s.advance(until=10.0)
    assert n > 0
    assert 0 < rt.metrics.arrived < wl.m, "micro-step admits only up to t"
    # live admission between steps, at a time after the current clock
    s.submit(TaskSubmit(t=10.5, work=2.0, packets=1.0))
    m = s.drain()
    assert m.completed == m.arrived == wl.m + 1
    assert s.close() is rt.metrics
    s.close()  # idempotent
    with pytest.raises(RuntimeError, match="closed"):
        s.advance(until=1e9)
    with pytest.raises(RuntimeError, match="closed"):
        s.submit(TaskSubmit(t=99.0, work=1.0))


def test_session_context_manager_and_auto_tids():
    rt = ClusterRuntime((1.0, 1.0), "jsq")
    with rt.open_session() as s:
        a = s.submit({"t": 0.0, "work": 1.0})
        b = s.submit(TaskSubmit(t=0.5, work=1.0))
        c = s.submit(Task(tid=7, t_arrive=1.0, work=1.0, packets=1.0), 1.0)
        d = s.submit({"t": 1.5, "work": 1.0})
        s.drain()
    assert s.closed
    assert [x.tid for x in (a, b, c)] == [0, 1, 7]
    assert d.tid == 8, "counter jumps past explicitly-named tids"
    assert rt.metrics.completed == 4


def test_live_tids_never_collide_with_streaming_source():
    """A trace source pre-assigns ids 0..m-1 but streams them in lazily;
    live auto-id submissions between steps must not squat on ids the
    source has not emitted yet (the serve --feed path)."""
    wl = make_workload("poisson", horizon=30.0, seed=5, rate=2.0)
    rt = ClusterRuntime((2.0, 1.0), "jsq")
    with rt.open_session() as s:
        s.feed(WorkloadSource(wl))
        s.advance(until=3.0)
        live = [s.submit({"t": 4.0 + i, "work": 1.0}) for i in range(3)]
        m = s.drain()
    assert m.completed == wl.m + 3
    assert all(t.tid >= wl.m for t in live)


def test_submit_guards():
    rt = ClusterRuntime((1.0,), "jsq")
    rt.submit(Task(tid=0, t_arrive=0.0, work=1.0, packets=1.0), 0.0)
    rt.advance(until=0.5)
    with pytest.raises(ValueError):  # tid already known to this runtime
        rt.submit(Task(tid=0, t_arrive=0.6, work=1.0, packets=1.0), 0.6)
    rt.advance(until=5.0)
    with pytest.raises(ValueError):  # the clock never goes backwards
        rt.submit(Task(tid=1, t_arrive=1.0, work=1.0, packets=1.0), 1.0)


def test_advance_event_budget_and_strict():
    wl = make_workload("poisson", horizon=15.0, seed=2, rate=3.0)
    rt = ClusterRuntime(POWERS, "jsq")
    rt.schedule_workload(wl)
    assert rt.advance(max_events=3) == 3
    assert rt.advance(max_events=10**9) > 0  # runs dry within budget
    assert rt.metrics.completed == wl.m
    rt2 = ClusterRuntime(POWERS, "jsq")
    rt2.schedule_workload(wl)
    with pytest.raises(RuntimeError, match="budget"):
        rt2.advance(max_events=3, strict=True)


def test_run_is_session_composition():
    """The monolithic run() is exactly feed + drain on a twin runtime."""
    wl = make_workload("bursty", horizon=40.0, seed=3, rate_lo=0.5,
                       rate_hi=8.0, work_mean=4.0)
    ref = ClusterRuntime(POWERS, "psts", trigger_period=1.0, seed=1,
                         policy_kwargs={"floor": 0.05})
    ref.run(wl)
    twin = ClusterRuntime(POWERS, "psts", trigger_period=1.0, seed=1,
                          policy_kwargs={"floor": 0.05})
    with twin.open_session() as s:
        s.feed(WorkloadSource(wl))
        s.drain()
    _assert_identical(ref, twin)


# ---------------------------------------------------------------------------
# the equivalence property: streaming == offline replay, under churn
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 7, 19, 101, 555])
def test_streaming_matches_offline_under_churn(seed):
    trace, failures, joins, resizes = _churn_inputs(seed)
    off = _offline(trace, failures, joins, resizes)
    svc = _online(trace, failures, joins, resizes)
    _assert_identical(off, svc.rt)
    assert svc.log.counts["complete"] == trace.m


@pytest.mark.parametrize("seed", [7, 101])
@pytest.mark.parametrize("step", [0.3, 1.7])
def test_fixed_step_pacing_matches_offline(seed, step):
    trace, failures, joins, resizes = _churn_inputs(seed)
    off = _offline(trace, failures, joins, resizes)
    svc = _online(trace, failures, joins, resizes, step=step)
    _assert_identical(off, svc.rt)


@settings(**STREAM_PROFILE)
@given(st.integers(min_value=0, max_value=2**31 - 1))
def test_streaming_matches_offline_property(seed):
    trace, failures, joins, resizes = _churn_inputs(seed)
    off = _offline(trace, failures, joins, resizes)
    svc = _online(trace, failures, joins, resizes)
    _assert_identical(off, svc.rt)


def test_bounded_microsteps_compose(seed=19):
    """Tiny event budgets + tiny time steps — however the advance() calls
    are sliced, the composed run is the same run."""
    trace, failures, joins, resizes = _churn_inputs(seed)
    off = _offline(trace, failures, joins, resizes)
    svc = SchedulerService(_psts())
    svc.rt.schedule_faults(failures=failures, joins=joins, resizes=resizes)
    svc.attach(WorkloadSource(trace))
    while svc.session.pending_sources or svc.rt.pending_work():
        svc.advance(until=svc.now + 0.9, max_events=5)
    svc.drain()
    _assert_identical(off, svc.rt)


# ---------------------------------------------------------------------------
# the online lab backend: byte-identical RunResult
# ---------------------------------------------------------------------------

def _churn_scenario() -> lab.Scenario:
    return lab.Scenario(
        cluster=lab.ClusterSpec(n_nodes=6, power_seed=3, bandwidth=128.0),
        workload=lab.WorkloadSpec(process="bursty", horizon=40.0,
                                  work_mean=4.0,
                                  params={"rate_lo": 0.5, "rate_hi": 8.0}),
        policy=lab.PolicySpec("psts", trigger_period=1.0,
                              params={"floor": 0.05}),
        faults=lab.FaultSpec(failures=((10.0, 1),), joins=((22.0, 1),),
                             resizes=((15.0, 2, 0.5),)),
        seed=11)


def test_online_backend_matches_events():
    sc = _churn_scenario()
    e = lab.run(sc, backend="events")
    o = lab.run(sc, backend="online")
    assert o.backend == "online"
    assert o.backend_options["model"] == "incremental-service"
    assert o.backend_options["pacing"] == "arrivals"
    assert o.backend_options["micro_steps"] > 0
    assert o.metrics == e.metrics
    assert o.extras.get("work_census") == e.extras.get("work_census")
    d = o.backend_options["decisions"]
    assert d["complete"] == o["completed"]
    assert d["trigger"] == o["trigger_evals"]


def test_online_backend_fixed_step_and_option_validation():
    sc = _churn_scenario()
    e = lab.run(sc, backend="events")
    o = lab.run(sc, backend="online", step=0.5)
    assert o.metrics == e.metrics
    assert o.backend_options["pacing"] == 0.5
    with pytest.raises(ValueError, match="step"):
        lab.run(sc, backend="online", step=0.0)
    with pytest.raises(TypeError, match="step only"):
        lab.run(sc, backend="online", nonsense=1)


def test_online_backend_dag_workload():
    sc = lab.Scenario(
        cluster=lab.ClusterSpec(powers=(2.0, 1.0, 3.0), bandwidth=64.0),
        workload=lab.WorkloadSpec(process="poisson", horizon=25.0,
                                  work_mean=3.0, params={"rate": 2.0},
                                  dag={"kind": "random", "p": 0.3}),
        policy=lab.PolicySpec("psts", trigger_period=1.0,
                              params={"floor": 0.05}),
        seed=5)
    e = lab.run(sc, backend="events")
    o = lab.run(sc, backend="online")
    assert o.metrics == e.metrics
    assert o["cp_lower_bound"] > 0
    assert o.extras.get("work_census") == e.extras.get("work_census")


def test_online_backend_registered_lazily():
    b = lab.get_backend("online")
    assert b.name == "online" and "online" in lab.BACKENDS
    # streams single scenarios only; federations route elsewhere
    member = lab.Scenario(cluster=lab.ClusterSpec(n_nodes=2))
    fed = lab.Federation(members=(member, member),
                         topology=lab.TopologySpec(kind="isolated"))
    assert b.eligible(fed) is not None


# ---------------------------------------------------------------------------
# the decision stream
# ---------------------------------------------------------------------------

def test_decision_stream_is_ordered_and_counted():
    trace, failures, joins, resizes = _churn_inputs(7)
    svc = _online(trace, failures, joins, resizes)
    log = svc.log
    assert len(log) == sum(log.counts.values()) > 0
    assert [d.seq for d in log] == list(range(len(log)))
    ts = [d.t for d in log]
    assert ts == sorted(ts), "decisions emit in event order"
    m = svc.metrics
    assert log.counts["complete"] == m.completed
    assert log.counts["trigger"] == m.trigger_evals
    fired = sum(1 for d in log if d.kind == "trigger" and d.info["fired"])
    assert fired == m.trigger_fires
    # m.evictions also counts traces that *end* in eviction (those emit a
    # complete decision); evict decisions cover the mid-run requeues
    assert log.counts["evict"] <= m.evictions
    # every completed task was placed at least once first
    assert log.counts["place"] >= m.completed


def test_requeue_eviction_emits_evict_decision():
    rt = ClusterRuntime((1.0,), "jsq")
    svc = SchedulerService(rt)
    svc.submit({"t": 0.0, "work": 10.0}, evictions=(2.0,))
    m = svc.drain()
    assert m.completed == 1 and m.evictions == 1
    assert svc.log.counts["evict"] == 1
    [d] = [d for d in svc.log if d.kind == "evict"]
    assert d.t == 2.0 and d.info["running"] is True and d.node == 0


def test_decision_to_dict_round_trips_as_json():
    p = Decision(0, 1.5, "place", tid=3, node=2)
    g = Decision(1, 2.0, "migrate", tid=3, src=2, dst=0)
    t = Decision(2, 3.0, "trigger", info={"fired": True})
    assert p.to_dict() == {"seq": 0, "t": 1.5, "kind": "place",
                           "tid": 3, "node": 2}
    assert g.to_dict() == {"seq": 1, "t": 2.0, "kind": "migrate",
                           "tid": 3, "src": 2, "dst": 0}
    d = json.loads(json.dumps(t.to_dict()))
    assert d["kind"] == "trigger" and d["fired"] is True
    assert "tid" not in d and "node" not in d


def test_decision_log_streaming_and_drain():
    got = []
    log = DecisionLog(keep=False, on_decision=got.append)
    wl = make_workload("poisson", horizon=10.0, seed=4, rate=2.0)
    rt = ClusterRuntime((2.0, 1.0), "jsq")
    svc = SchedulerService(rt, log=log)
    svc.attach(WorkloadSource(wl))
    svc.drain()
    assert len(log) == 0, "keep=False retains nothing"
    assert len(got) == sum(log.counts.values()) > 0
    # keep=True accumulates; drain() pops
    rt2 = ClusterRuntime((2.0, 1.0), "jsq")
    svc2 = SchedulerService(rt2)
    svc2.attach(WorkloadSource(wl))
    svc2.drain()
    popped = svc2.log.drain()
    assert len(popped) == len(got) and len(svc2.log.decisions) == 0


def test_advance_returns_only_new_decisions():
    wl = make_workload("poisson", horizon=20.0, seed=1, rate=2.0)
    svc = SchedulerService(ClusterRuntime((2.0, 1.0), "jsq"))
    svc.attach(WorkloadSource(wl))
    first = svc.advance(until=10.0)
    second = svc.advance(until=1e9)
    assert first and second
    assert {d.seq for d in first}.isdisjoint({d.seq for d in second})
    assert len(first) + len(second) == len(svc.log.decisions)


# ---------------------------------------------------------------------------
# task sources
# ---------------------------------------------------------------------------

def test_tasksubmit_from_dict_and_to_task():
    ts = TaskSubmit.from_dict({"t_arrive": 2.0, "work": 3.0, "packets": 2,
                               "parents": [1, 2], "evictions": [5.0],
                               "user": "alice"})
    assert ts.t == 2.0 and ts.parents == (1, 2) and ts.evictions == (5.0,)
    assert ts.info == {"user": "alice"}, "unknown keys ride along as info"
    task = ts.to_task(9)
    assert task.tid == 9 and task.t_arrive == 2.0 and task.parents == (1, 2)
    # feasible as node indices needs the cluster capacity to become a mask
    con = TaskSubmit(t=0.0, work=1.0, feasible=[0, 2])
    with pytest.raises(ValueError, match="capacity"):
        con.to_task(0)
    mask = con.to_task(0, capacity=4).feasible
    assert mask.dtype == np.bool_ and list(mask) == [True, False, True,
                                                     False]


def test_iterable_source_pull_boundary():
    src = IterableSource([TaskSubmit(t=1.0, work=1.0),
                          {"t": 2.0, "work": 1.0},
                          TaskSubmit(t=3.0, work=1.0)])
    assert [ts.t for ts in src.pull(1.5)] == [1.0]
    assert not src.exhausted, "lookahead buffers the t=2 item"
    assert [ts.t for ts in src.pull(3.0)] == [2.0, 3.0]
    assert src.pull(99.0) == []
    assert src.exhausted


def test_jsonl_source_from_file_like_and_path(tmp_path):
    text = ('{"t": 0.5, "work": 2.0}\n'
            '\n'
            '{"t": 1.0, "work": 1.0, "packets": 3}\n')
    src = JsonlSource(io.StringIO(text))
    got = src.pull(10.0)
    assert [ts.t for ts in got] == [0.5, 1.0] and got[1].packets == 3
    assert src.exhausted
    path = tmp_path / "feed.jsonl"
    path.write_text(text)
    rt = ClusterRuntime((1.0, 1.0), "jsq")
    with rt.open_session() as s:
        s.feed(JsonlSource(str(path)))
        m = s.drain()
    assert m.completed == 2


def test_workload_source_streams_in_admission_order():
    # same-instant arrivals admit best tier first, as schedule_workload does
    from repro.traces import TraceSchema
    trace = TraceSchema(t_arrive=np.array([0.5, 1.0, 1.0]),
                        works=np.ones(3), packets=np.ones(3),
                        priority=np.array([1, 2, 0], dtype=np.int32))
    src = WorkloadSource(trace)
    got = src.pull(5.0)
    assert [ts.tid for ts in got] == [0, 2, 1]
    assert src.next_time is None and src.exhausted


def test_workload_source_guards_unprepared_state():
    trace, *_ = _churn_inputs(0)  # carries evictions
    src = WorkloadSource(trace)
    with pytest.raises(RuntimeError, match="prepare"):
        src.pull(1e9)


# ---------------------------------------------------------------------------
# CLI: python -m repro.lab serve
# ---------------------------------------------------------------------------

def _scenario_file(tmp_path) -> str:
    sc = lab.Scenario(
        cluster=lab.ClusterSpec(n_nodes=3, power_seed=0),
        workload=lab.WorkloadSpec(process="poisson", horizon=10.0,
                                  params={"rate": 1.0}),
        policy=lab.PolicySpec("psts", trigger_period=1.0,
                              params={"floor": 0.05}),
        seed=2, name="serve-smoke")
    path = tmp_path / "scenario.json"
    path.write_text(sc.to_json())
    return str(path)


def test_cli_serve_streams_decisions(tmp_path, capsys):
    feed = tmp_path / "tasks.jsonl"
    feed.write_text('{"t": 1.0, "work": 2.0}\n{"t": 4.0, "work": 1.0}\n')
    dec = tmp_path / "decisions.jsonl"
    out = tmp_path / "result.json"
    assert lab_cli(["serve", _scenario_file(tmp_path),
                    "--feed", str(feed), "--decisions-out", str(dec),
                    "--out", str(out)]) == 0
    assert "served" in capsys.readouterr().err
    lines = [json.loads(x) for x in dec.read_text().splitlines() if x]
    assert lines and all({"seq", "t", "kind"} <= set(d) for d in lines)
    payload = json.loads(out.read_text())
    m = payload["metrics"]
    assert m["completed"] == m["arrived"] > 2  # workload + both feed tasks
    assert payload["decisions"]["complete"] == m["completed"]
    assert sum(1 for d in lines if d["kind"] == "complete") == m["completed"]


def test_cli_serve_feed_only_fixed_step(tmp_path, capsys):
    feed = tmp_path / "tasks.jsonl"
    feed.write_text('{"t": 0.5, "work": 1.0}\n{"t": 1.5, "work": 2.0}\n')
    out = tmp_path / "result.json"
    assert lab_cli(["serve", _scenario_file(tmp_path), "--no-workload",
                    "--feed", str(feed), "--step", "0.5",
                    "--out", str(out)]) == 0
    capsys.readouterr()
    m = json.loads(out.read_text())["metrics"]
    assert m["arrived"] == m["completed"] == 2


# ---------------------------------------------------------------------------
# unified verbs across layers + deprecation shims
# ---------------------------------------------------------------------------

def test_service_operator_verbs_fail_join_resize():
    svc = SchedulerService(ClusterRuntime((1.0, 1.0), "jsq"))
    for i in range(4):
        svc.submit({"t": 0.0, "work": 4.0})
    svc.advance(until=0.5)
    svc.fail(1)               # t defaults to now
    svc.join(1, 6.0)
    svc.resize(0, 2.0, 8.0)
    m = svc.drain()
    svc.close()
    assert m.completed == 4
    assert m.failures == 1 and m.joins == 1


def test_federated_runtime_shares_the_session_verbs():
    from repro.federation import FederatedRuntime, TopologySpec
    fed = lab.Federation(
        members=tuple(
            lab.Scenario(
                name=f"dc{i}",
                cluster=lab.ClusterSpec(n_nodes=3, power_seed=i,
                                        bandwidth=128.0),
                workload=lab.WorkloadSpec(process="poisson", horizon=30.0,
                                          work_mean=5.0,
                                          params={"rate": r}),
                policy=lab.PolicySpec("psts", trigger_period=1.0,
                                      params={"floor": 0.05}),
                seed=i)
            for i, r in enumerate((6.0, 2.0))),
        topology=TopologySpec(kind="full", bandwidth=8.0, latency=2.0),
        exchange_period=4.0)
    ref = FederatedRuntime(fed).run()
    fr = FederatedRuntime(fed)
    n = fr.advance(until=12.0)          # partial: whole epochs only
    assert 0 < n <= 3
    report = fr.drain()
    assert report.aggregate.summary() == ref.aggregate.summary()
    assert report.epochs == ref.epochs
    # live admission into a chosen member is conserved in the audit
    fr2 = FederatedRuntime(fed)
    fr2.advance(until=8.0)
    fr2.submit(Task(tid=90_000, t_arrive=8.0, work=3.0, packets=1.0),
               member=1)
    r2 = fr2.drain()
    assert r2.aggregate.completed == ref.aggregate.completed + 1


def test_deprecated_inject_and_step_until_still_work():
    rt = ClusterRuntime((2.0, 2.0), "jsq")
    with pytest.warns(DeprecationWarning, match="inject"):
        rt.inject(Task(tid=0, t_arrive=1.0, work=2.0, packets=1.0), 1.0)
    with pytest.warns(DeprecationWarning, match="step_until"):
        rt.step_until(1e9)
    assert rt.metrics.completed == 1


def test_run_policy_shim_warns_and_matches_session_api():
    wl = make_workload("poisson", horizon=15.0, seed=6, rate=2.0)
    with pytest.warns(DeprecationWarning, match="run_policy"):
        m = run_policy("psts", wl, POWERS, trigger_period=1.0, seed=0,
                       policy_kwargs={"floor": 0.05})
    rt = _psts()
    with rt.open_session() as s:
        s.feed(WorkloadSource(wl))
        s.drain()
    assert m.summary() == rt.metrics.summary()


def test_stable_public_api_surface():
    import repro
    import repro.serve as serve
    assert repro.Scenario is lab.Scenario
    assert repro.run is lab.run
    assert repro.sweep is lab.sweep
    assert repro.RunResult is lab.RunResult
    assert repro.SchedulerService is SchedulerService
    assert set(repro.__all__) >= {"Scenario", "run", "sweep", "RunResult",
                                  "SchedulerService", "__version__"}
    assert {"SchedulerService", "Session", "TaskSubmit", "WorkloadSource",
            "JsonlSource", "DecisionLog", "Decision"} <= set(serve.__all__)
    assert {"Scenario", "run", "sweep", "RunResult"} <= set(lab.__all__)
    with pytest.raises(AttributeError):
        repro.nonsense
