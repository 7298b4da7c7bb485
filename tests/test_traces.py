"""repro.traces: parsers, schema, synthesizer, engine + lab integration."""

from __future__ import annotations

import contextlib
import gzip
import json
import tempfile
import time
import warnings
from pathlib import Path

import numpy as np
import pytest

from repro import lab
from repro.runtime import ClusterRuntime
from repro.traces import (
    OPS,
    Constraints,
    Evictions,
    InfeasibleTaskError,
    TraceSchema,
    dense_tiers,
    load_azure_packing,
    load_google_machine_events,
    load_google_task_events,
    load_normalized_csv,
    load_trace,
    trace_scale,
    write_normalized_csv,
)

from hypothesis import given, settings
from hypothesis import strategies as st

DATA = Path(__file__).parent / "data"
G_EVENTS = DATA / "google_tiny_events.csv"
G_CONSTRAINTS = DATA / "google_tiny_constraints.csv"
A_VM = DATA / "azure_tiny_vm.csv"
A_VMTYPES = DATA / "azure_tiny_vmtypes.csv"


def _google_tiny():
    with pytest.warns(UserWarning):  # fallback duration + dropped row
        return load_google_task_events(str(G_EVENTS),
                                       constraints_path=str(G_CONSTRAINTS))


@contextlib.contextmanager
def _quiet():
    """Tolerate (don't assert) parser warnings: lab materialization is
    memoized, so whether a load warns depends on cache state — the
    warning contracts themselves are covered by the direct parser tests."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        yield


# ---------------------------------------------------------------------------
# schema
# ---------------------------------------------------------------------------

def test_dense_tiers_orderings():
    raw = np.array([11, 9, 0, 4, 9])
    up = dense_tiers(raw, higher_is_more_important=True)
    assert up.tolist() == [0, 1, 3, 2, 1]
    down = dense_tiers(raw, higher_is_more_important=False)
    assert down.tolist() == [3, 2, 0, 1, 2]


def test_trace_schema_defaults_and_validation():
    tr = TraceSchema(t_arrive=[0.0, 1.0], works=[1.0, 2.0],
                     packets=[1.0, 1.0])
    assert tr.priority.tolist() == [0, 0]
    assert tr.n_tiers == 1 and not tr.constrained
    with pytest.raises(ValueError, match="priority"):
        TraceSchema(t_arrive=[0.0], works=[1.0], packets=[1.0],
                    priority=[0, 1])
    with pytest.raises(ValueError, match="outside the trace"):
        TraceSchema(t_arrive=[0.0], works=[1.0], packets=[1.0],
                    constraints=Constraints(("a",), [3], [0],
                                            [OPS["=="]], [1.0]))


def test_constraints_node_mask_and_select():
    c = Constraints(("mc", "ssd"),
                    task=[0, 0, 2], attr=[0, 1, 0],
                    op=[OPS[">="], OPS["=="], OPS["<"]],
                    value=[2.0, 1.0, 1.0])
    attrs = np.array([[0.0, 1.0], [2.0, 0.0], [3.0, 1.0]])  # 3 nodes
    mask = c.node_mask(3, ("mc", "ssd"), attrs)
    assert mask.tolist() == [
        [False, False, True],   # mc>=2 AND ssd==1 -> node 2 only
        [True, True, True],     # unconstrained
        [True, False, False],   # mc<1 -> node 0 only
    ]
    sel = c.select(np.array([2, 2, 0]))
    assert sel.k == 4  # task 2's one row twice, task 0's two rows once
    assert sorted(sel.task.tolist()) == [0, 1, 2, 2]
    # unknown attribute is loud
    with pytest.raises(InfeasibleTaskError, match="ssd"):
        c.node_mask(3, ("mc",), attrs[:, :1])


def test_feasibility_diagnostic_names_task_and_predicates():
    c = Constraints(("mc",), [1], [0], [OPS[">"]], [99.0])
    tr = TraceSchema(t_arrive=[0.0, 1.0], works=[1.0, 1.0],
                     packets=[1.0, 1.0], constraints=c)
    with pytest.raises(InfeasibleTaskError, match=r"task 1.*mc > 99"):
        tr.feasibility(("mc",), np.array([[1.0], [2.0]]))


# ---------------------------------------------------------------------------
# google parser
# ---------------------------------------------------------------------------

def test_google_column_semantics():
    tr = _google_tiny()
    assert tr.m == 4
    # arrival order: (500,0) t=0, (600,1) t=0.5, (500,1) t=1, (600,0) t=2
    np.testing.assert_allclose(tr.t_arrive, [0.0, 0.5, 1.0, 2.0])
    # requeue mode (default): work = final FINISH interval * cpu; the
    # EVICT-ended (600,1) and interval-less (500,1) fall back to the
    # median *finished* duration 5s; median cpu fill 0.5 for (600,0)
    np.testing.assert_allclose(tr.works, [3.0, 4.0, 1.25, 2.0])
    np.testing.assert_allclose(tr.packets,
                               np.array([0.4, 0.3, 0.2, 0.1]) * 64.0)
    # native 11/4/9/0 -> dense tiers, bigger = more important
    assert tr.priority.tolist() == [0, 2, 1, 3]
    assert tr.n_tiers == 4
    # (600,1)'s trace life ended at its EVICT row; no *mid-life* eviction
    # exists, so no requeue events are emitted
    assert tr.ends_evicted.tolist() == [False, True, False, False]
    assert tr.evictions.empty
    # constraints joined on (job, task idx); absent-task row dropped
    assert tr.constraints.k == 3
    assert tr.constraints.describe_task(0) == "machine_class > 1 AND ssd == 1"
    assert tr.constraints.describe_task(1) == "machine_class < 2"
    assert tr.constraints.describe_task(2) == "(unconstrained)"


def test_google_end_mode_is_backward_compatible():
    """eviction_mode='end' reproduces the PR 4 numbers: EVICT rows end the
    service interval (work spans first SCHEDULE -> last terminal), no
    requeue events — but eviction-truncated tasks are still flagged."""
    with pytest.warns(UserWarning):
        tr = load_google_task_events(str(G_EVENTS), eviction_mode="end")
    np.testing.assert_allclose(tr.works, [3.0, 3.2, 1.0, 2.0])
    assert tr.evictions.empty
    assert tr.ends_evicted.tolist() == [False, True, False, False]
    with pytest.raises(ValueError, match="eviction_mode"):
        load_google_task_events(str(G_EVENTS), eviction_mode="restart")


def test_google_requeue_mode_emits_midlife_evictions(tmp_path):
    """A SCHED->EVICT->SCHED->FINISH lifetime: the mid-life EVICT becomes a
    requeue event, and the useful work is the *final* run only."""
    p = tmp_path / "events.csv"
    p.write_text(
        "1000000,,7,0,,0,u,0,9,0.5,0.2,\n"    # SUBMIT t=1
        "2000000,,7,0,,1,u,0,9,0.5,0.2,\n"    # SCHEDULE t=2
        "5000000,,7,0,,2,u,0,9,0.5,0.2,\n"    # EVICT t=5 (mid-life)
        "6000000,,7,0,,1,u,0,9,0.5,0.2,\n"    # SCHEDULE t=6
        "10000000,,7,0,,4,u,0,9,0.5,0.2,\n")  # FINISH t=10
    tr = load_google_task_events(str(p))
    assert tr.m == 1 and not tr.ends_evicted[0]
    np.testing.assert_allclose(tr.works, [2.0])  # (10-6) * 0.5 cpu
    assert tr.evictions.k == 1
    assert tr.evictions.task.tolist() == [0]
    np.testing.assert_allclose(tr.evictions.time, [4.0])  # 5s - submit 1s
    # end mode spans the whole lifetime instead and replays nothing
    tr_end = load_google_task_events(str(p), eviction_mode="end")
    np.testing.assert_allclose(tr_end.works, [4.0])  # (10-2) * 0.5
    assert tr_end.evictions.empty and not tr_end.ends_evicted[0]


def test_google_out_of_order_rows_match_sorted(tmp_path):
    """Shard-shuffled rows must parse identically to time-sorted rows."""
    lines = [ln for ln in G_EVENTS.read_text().splitlines()
             if ln and not ln.startswith("#")]
    srt = sorted(lines, key=lambda ln: int(ln.split(",")[0]))
    p = tmp_path / "sorted.csv"
    p.write_text("\n".join(srt) + "\n")
    with pytest.warns(UserWarning):
        a = load_google_task_events(str(p),
                                    constraints_path=str(G_CONSTRAINTS))
    b = _google_tiny()
    np.testing.assert_allclose(a.t_arrive, b.t_arrive)
    np.testing.assert_allclose(a.works, b.works)
    assert a.priority.tolist() == b.priority.tolist()
    assert a.constraints.k == b.constraints.k


def test_google_gzip_round_trip(tmp_path):
    gz = tmp_path / "events.csv.gz"
    with gzip.open(gz, "wt") as fh:
        fh.write(G_EVENTS.read_text())
    with pytest.warns(UserWarning):
        a = load_google_task_events(str(gz))
    with pytest.warns(UserWarning):
        b = load_google_task_events(str(G_EVENTS))
    np.testing.assert_allclose(a.t_arrive, b.t_arrive)
    np.testing.assert_allclose(a.works, b.works)
    np.testing.assert_allclose(a.packets, b.packets)


def test_google_no_submit_rows_is_loud(tmp_path):
    p = tmp_path / "bad.csv"
    p.write_text("1000,,5,0,,4,u,0,9,0.5,0.2,\n")
    with pytest.raises(ValueError, match="no SUBMIT rows"):
        load_google_task_events(str(p))


# ---------------------------------------------------------------------------
# azure parser
# ---------------------------------------------------------------------------

def test_azure_column_semantics():
    with pytest.warns(UserWarning):  # open-ended VM + missing vmTypeId
        tr = load_azure_packing(str(A_VM), vmtypes_path=str(A_VMTYPES))
    assert tr.m == 4
    np.testing.assert_allclose(tr.t_arrive, [0.0, 3.0, 6.0, 12.0])
    np.testing.assert_allclose(tr.works, [24.0, 12.0, 12.0, 6.0])
    np.testing.assert_allclose(tr.packets, [128.0, 512.0, 128.0, 16.0])
    assert tr.priority.tolist() == [0, 1, 0, 1]  # azure 1=high -> tier 0
    # every VM constrained cores >= its type's core count
    assert tr.constraints.describe_task(0) == "cores >= 2"
    assert tr.constraints.describe_task(1) == "cores >= 4"
    assert tr.constraints.describe_task(3) == "cores >= 1"


def test_azure_unknown_priority_tiers_warn_and_map(tmp_path):
    p = tmp_path / "vm.csv"
    p.write_text("0,1,1,7,0.0,0.5\n1,1,1,0,0.1,0.3\n2,1,1,1,0.2,0.4\n")
    with pytest.warns(UserWarning, match=r"unknown priority value\(s\) \[7\]"):
        tr = load_azure_packing(str(p))
    # relative order preserved: 7 -> tier 0, 1 -> tier 1, 0 -> tier 2
    assert tr.priority.tolist() == [0, 2, 1]


def test_azure_without_vmtypes_is_unconstrained():
    with pytest.warns(UserWarning):  # open-ended VM
        tr = load_azure_packing(str(A_VM))
    assert not tr.constrained
    np.testing.assert_allclose(tr.works, [12.0, 3.0, 6.0, 6.0])


# ---------------------------------------------------------------------------
# normalized CSV + round trip
# ---------------------------------------------------------------------------

def test_normalized_round_trip(tmp_path):
    tr = _google_tiny()
    csv = tmp_path / "norm.csv"
    sidecar = tmp_path / "norm_constraints.json"
    write_normalized_csv(tr, csv, constraints_path=sidecar)
    back = load_normalized_csv(str(csv), constraints_path=str(sidecar))
    np.testing.assert_allclose(back.t_arrive, tr.t_arrive)
    np.testing.assert_allclose(back.works, tr.works)
    assert back.priority.tolist() == tr.priority.tolist()
    assert back.constraints.k == tr.constraints.k
    assert back.constraints.describe_task(0) == tr.constraints.describe_task(0)


def test_normalized_three_column_form_still_loads():
    tr = load_normalized_csv(str(DATA / "tiny_trace.csv"))
    assert tr.m == 8 and tr.n_tiers == 1 and not tr.constrained
    assert (np.diff(tr.t_arrive) >= 0).all()


def test_normalized_empty_and_bad_columns(tmp_path):
    empty = tmp_path / "empty.csv"
    empty.write_text("# nothing\n")
    assert load_normalized_csv(str(empty)).m == 0
    bad = tmp_path / "bad.csv"
    bad.write_text("1,2\n")
    with pytest.raises(ValueError, match="expected 3 columns"):
        load_normalized_csv(str(bad))


def _random_schema(seed: int) -> TraceSchema:
    """Arbitrary small TraceSchema — every axis populated at random."""
    rng = np.random.default_rng(seed)
    m = int(rng.integers(1, 25))
    k_con = int(rng.integers(0, 2 * m))
    k_ev = int(rng.integers(0, 2 * m))
    names = ("machine_class", "ssd")[:int(rng.integers(1, 3))]
    constraints = Constraints(
        names, rng.integers(0, m, k_con),
        rng.integers(0, len(names), k_con).astype(np.int32),
        rng.choice(list(OPS.values()), k_con).astype(np.int8),
        np.round(rng.uniform(0, 4, k_con), 6))
    return TraceSchema(
        t_arrive=np.sort(np.round(rng.uniform(0, 50, m), 6)),
        works=np.round(rng.uniform(0.5, 9, m), 6),
        packets=np.round(rng.uniform(0.5, 9, m), 6),
        priority=rng.integers(0, 4, m).astype(np.int32),
        constraints=constraints,
        evictions=Evictions(rng.integers(0, m, k_ev),
                            np.round(rng.uniform(0, 60, k_ev), 6)),
        ends_evicted=rng.random(m) < 0.25)


def _assert_round_trips(trace: TraceSchema, tmp_path, gz: bool) -> None:
    suffix = ".gz" if gz else ""
    csv = tmp_path / f"rt.csv{suffix}"
    side = tmp_path / f"rt.json{suffix}"
    write_normalized_csv(trace, csv, constraints_path=side)
    back = load_normalized_csv(str(csv), constraints_path=str(side)
                               if side.exists() else None)
    assert back.m == trace.m
    np.testing.assert_allclose(back.t_arrive, trace.t_arrive, rtol=1e-6)
    np.testing.assert_allclose(back.works, trace.works, rtol=1e-6)
    np.testing.assert_allclose(back.packets, trace.packets, rtol=1e-6)
    assert back.priority.tolist() == trace.priority.tolist()
    assert back.ends_evicted.tolist() == trace.ends_evicted.tolist()
    # sparse rows may legally be re-ordered by (task, …): compare as sets
    assert back.evictions.k == trace.evictions.k
    assert sorted(zip(back.evictions.task.tolist(),
                      back.evictions.time.tolist())) == pytest.approx(
        sorted(zip(trace.evictions.task.tolist(),
                   trace.evictions.time.tolist())))
    assert back.constraints.k == trace.constraints.k
    for tid in range(trace.m):
        assert (back.constraints.describe_task(tid)
                == trace.constraints.describe_task(tid))


@pytest.mark.parametrize("gz", [False, True])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_normalized_round_trip_examples(tmp_path, seed, gz):
    _assert_round_trips(_random_schema(seed), tmp_path, gz)


@settings(max_examples=15, deadline=None, derandomize=True)
@given(st.integers(min_value=0, max_value=2**31 - 1),
       st.booleans())
def test_normalized_round_trip_property(seed, gz):
    # a fresh directory per generated example — function-scoped pytest
    # fixtures and @given don't mix (hypothesis health check, and a stale
    # sidecar from one example would bleed into the next)
    with tempfile.TemporaryDirectory() as d:
        _assert_round_trips(_random_schema(seed), Path(d), gz)


def test_sidecar_without_evictions_still_loads(tmp_path):
    """PR 4 sidecars (constraints only, no eviction keys) stay loadable."""
    side = tmp_path / "old.json"
    side.write_text(json.dumps({
        "attr_names": ["mc"], "rows": [[0, "mc", ">=", 1.0]]}))
    csv = tmp_path / "t.csv"
    csv.write_text("0.0,1.0,1.0,0\n")
    tr = load_normalized_csv(str(csv), constraints_path=str(side))
    assert tr.constraints.k == 1
    assert tr.evictions.empty and not tr.ends_evicted.any()
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"ends_evicted": [5]}))
    with pytest.raises(ValueError, match="ends_evicted index 5"):
        load_normalized_csv(str(csv), constraints_path=str(bad))


# ---------------------------------------------------------------------------
# machine_events parser
# ---------------------------------------------------------------------------

def _machine_events(tmp_path, text: str):
    p = tmp_path / "machines.csv"
    p.write_text(text)
    return load_google_machine_events(str(p), time_scale=1e-6)


def test_machine_events_remove_add_update_mapping(tmp_path):
    sched = _machine_events(tmp_path, "\n".join([
        "0,10,0,,1.0,0.5",          # ADD machine 10 (census)
        "0,11,0,,0.5,0.5",          # ADD machine 11 (census)
        "4000000,10,1,,,",          # REMOVE 10 at t=4
        "9000000,10,0,,1.0,0.5",    # ADD 10 back at t=9
        "6000000,11,2,,0.25,0.5",   # UPDATE 11 to half capacity at t=6
    ]) + "\n")
    assert sched.n_machines == 2
    assert sched.machine_ids == (10, 11)
    assert sched.failures == ((4.0, 0),)
    assert sched.joins == ((9.0, 0),)
    assert sched.resizes == ((6.0, 1, 0.5),)  # 0.25 / first-seen 0.5


def test_machine_events_born_mid_trace_and_zero_capacity(tmp_path):
    sched = _machine_events(tmp_path, "\n".join([
        "0,5,0,,1.0,0.5",
        "3000000,6,0,,1.0,0.5",     # machine 6 first appears at t=3
        "7000000,5,2,,0.0,0.5",     # UPDATE to zero capacity = removal
    ]) + "\n")
    assert (0.0, 1) in sched.failures     # 6 absent before its ADD
    assert sched.joins == ((3.0, 1),)
    assert (7.0, 0) in sched.failures     # zero-capacity UPDATE
    assert sched.resizes == ()


def test_machine_events_rejoin_keeps_resized_capacity(tmp_path):
    """A machine that resized, failed, and rejoined is still resized; no
    spurious reconciling event is emitted at the rejoin."""
    sched = _machine_events(tmp_path, "\n".join([
        "0,1,0,,1.0,0.5",
        "2000000,1,2,,0.5,0.5",     # resize to half
        "4000000,1,1,,,",           # remove
        "8000000,1,0,,0.5,0.5",     # rejoin at the same (halved) capacity
    ]) + "\n")
    assert sched.resizes == ((2.0, 0, 0.5),)
    assert sched.failures == ((4.0, 0),)
    assert sched.joins == ((8.0, 0),)


def test_machine_events_zero_capacity_rejoin_stays_down(tmp_path):
    """An ADD of a machine whose desired capacity is zero must not raise
    it: a same-instant failure+join pair would resolve as node-up under
    the engine's tie-break (NODE_FAIL before NODE_JOIN)."""
    sched = _machine_events(tmp_path, "\n".join([
        "0,1,0,,1.0,0.5",
        "10000000,1,1,,,",          # REMOVE at t=10
        "15000000,1,2,,0.0,0.5",    # UPDATE to zero capacity while down
        "20000000,1,0,,,",          # ADD back, capacity still zero
    ]) + "\n")
    assert sched.failures == ((10.0, 0),)
    assert sched.joins == ()            # never resurrected
    assert sched.resizes == ()
    # a later UPDATE restoring capacity brings it back up via ADD
    sched2 = _machine_events(tmp_path, "\n".join([
        "0,1,0,,1.0,0.5",
        "10000000,1,1,,,",
        "15000000,1,2,,0.0,0.5",
        "20000000,1,2,,1.0,0.5",    # capacity restored while down
        "25000000,1,0,,,",          # the ADD raises it
    ]) + "\n")
    assert sched2.joins == ((25.0, 0),)


def test_machine_events_zero_update_recovers_via_update(tmp_path):
    """A machine downed by a zero-capacity UPDATE (never REMOVEd) comes
    straight back when an UPDATE restores its capacity — only REMOVEd
    machines wait for an ADD."""
    sched = _machine_events(tmp_path, "\n".join([
        "0,1,0,,1.0,0.5",
        "100000000,1,2,,0.0,0.5",   # UPDATE to zero at t=100
        "200000000,1,2,,1.0,0.5",   # capacity restored at t=200
    ]) + "\n")
    assert sched.failures == ((100.0, 0),)
    assert sched.joins == ((200.0, 0),)
    assert sched.resizes == ()


def test_machine_events_same_stamp_reboot_blips(tmp_path):
    """REMOVE+ADD recorded at one timestamp is a reboot: the fold orders
    REMOVE first, so the engine's NODE_FAIL-before-NODE_JOIN tie-break
    leaves the machine up — not permanently dead."""
    sched = _machine_events(tmp_path, "\n".join([
        "0,1,0,,1.0,0.5",
        "100000000,1,1,,,",         # REMOVE at t=100...
        "100000000,1,0,,1.0,0.5",   # ...and ADD at the same stamp
    ]) + "\n")
    assert sched.failures == ((100.0, 0),)
    assert sched.joins == ((100.0, 0),)


def test_machine_events_first_row_remove_counts(tmp_path):
    """An excerpt cut mid-trace may open with a REMOVE: the machine
    existed before the cut, so the removal must fail the node instead of
    being dropped (silently overstating capacity)."""
    sched = _machine_events(tmp_path, "\n".join([
        "0,1,0,,1.0,0.5",
        "5000000,2,1,,,",           # machine 2's first row is its REMOVE
    ]) + "\n")
    assert sched.n_machines == 2
    assert sched.failures == ((5.0, 1),)


def test_machine_events_validation(tmp_path):
    p = tmp_path / "bad.csv"
    p.write_text("0,1,7,,1.0,0.5\n")
    with pytest.raises(ValueError, match="unknown event type"):
        load_google_machine_events(str(p))
    empty = tmp_path / "empty.csv"
    empty.write_text("# nothing\n")
    assert load_google_machine_events(str(empty)).empty


def test_machine_events_align_with_the_workload_clock(tmp_path):
    """The public Google trace starts at raw 600s; t_arrive is re-zeroed
    to the first SUBMIT, so the machine schedule must be re-zeroed against
    the same origin or every capacity event fires 600s late."""
    events = tmp_path / "events.csv"
    events.write_text(
        "600000000,,7,0,,0,u,0,9,0.5,0.2,\n"    # SUBMIT at raw 600s
        "601000000,,7,0,,1,u,0,9,0.5,0.2,\n"
        "605000000,,7,0,,4,u,0,9,0.5,0.2,\n")
    mach = tmp_path / "machines.csv"
    mach.write_text("0,1,0,,1.0,0.5\n"
                    "0,2,0,,1.0,0.5\n"
                    "610000000,1,1,,,\n")        # REMOVE 10s in
    tr = load_google_task_events(str(events))
    assert tr.t_zero_raw == pytest.approx(600e6)
    np.testing.assert_allclose(tr.t_arrive, [0.0])
    sc = lab.Scenario(
        cluster=lab.ClusterSpec(powers=(1.0, 1.0)),
        workload=lab.WorkloadSpec(
            trace=lab.TraceRef(path=str(events), format="google",
                               machine_events=str(mach)),
            horizon=None),
        policy=lab.PolicySpec("arrival_only"))
    failures, _, _ = lab.resolve_fault_schedule(sc)
    assert failures == ((10.0, 0),)  # on the workload clock, not 610s


def test_machine_events_replay_through_runtime(tmp_path):
    """End-to-end: REMOVE strands work, ADD restores it, UPDATE reshapes a
    running task's completion — all from one machine_events file."""
    p = tmp_path / "machines.csv"
    p.write_text("0,0,0,,1.0,0.5\n"
                 "2000000,0,2,,0.5,0.5\n")   # halve node 0 at t=2
    sched = load_google_machine_events(str(p), time_scale=1e-6)
    tr = TraceSchema(t_arrive=[0.0], works=[8.0], packets=[1.0])
    rt = ClusterRuntime((2.0,), "jsq", trigger_period=0.0)
    m = rt.run(tr, failures=sched.failures, joins=sched.joins,
               resizes=sched.resizes)
    assert m.makespan == pytest.approx(6.0)  # 4 done by t=2, then power 1
    assert m.resizes == 1


# ---------------------------------------------------------------------------
# trace_scale synthesizer
# ---------------------------------------------------------------------------

def test_trace_scale_preserves_mix_and_burstiness():
    rng = np.random.default_rng(0)
    m = 2000
    # two bursts with distinct priority mixes
    t = np.sort(np.concatenate([rng.uniform(0, 10, m // 2),
                                rng.uniform(50, 60, m // 2)]))
    pri = np.where(t < 30, 0, 1).astype(np.int32)
    con_idx = np.flatnonzero(pri == 0)
    c = Constraints(("mc",), con_idx, np.zeros(con_idx.size, np.int32),
                    np.full(con_idx.size, OPS[">="], np.int8),
                    np.full(con_idx.size, 1.0))
    tr = TraceSchema(t_arrive=t, works=np.full(m, 2.0),
                     packets=np.full(m, 4.0), priority=pri, constraints=c)
    big = trace_scale(tr, 3.0, seed=7)
    assert abs(big.m - 3 * m) / (3 * m) < 0.1
    assert (np.diff(big.t_arrive) >= 0).all()
    # the gap between the bursts stays (burstiness preserved)
    in_gap = ((big.t_arrive > 15) & (big.t_arrive < 45)).mean()
    assert in_gap < 0.01
    # tier mix preserved and constraints travel with their tasks
    frac0 = (big.priority == 0).mean()
    assert abs(frac0 - 0.5) < 0.05
    assert big.constraints.k == int((big.priority == 0).sum())
    # deterministic in the seed
    again = trace_scale(tr, 3.0, seed=7)
    np.testing.assert_array_equal(big.t_arrive, again.t_arrive)
    assert trace_scale(tr, 3.0, seed=8).m != big.m or not np.allclose(
        trace_scale(tr, 3.0, seed=8).t_arrive[:10], big.t_arrive[:10])


def test_trace_scale_carries_evictions_and_outcomes():
    rng = np.random.default_rng(2)
    m = 500
    t = np.sort(rng.uniform(0, 100, m))
    # every task is evicted 1.5 time units after its arrival
    tr = TraceSchema(t_arrive=t, works=np.full(m, 2.0),
                     packets=np.full(m, 4.0),
                     evictions=Evictions(np.arange(m), t + 1.5),
                     ends_evicted=np.arange(m) % 3 == 0)
    big = trace_scale(tr, 2.0, seed=9)
    assert big.preempted
    # one eviction row per resampled task, dragged along with its arrival:
    # the evict-minus-arrive offset is preserved for every instance
    assert big.evictions.k == big.m
    order = np.argsort(big.evictions.task, kind="stable")
    np.testing.assert_allclose(
        big.evictions.time[order] - big.t_arrive[big.evictions.task[order]],
        1.5, rtol=1e-9)
    assert 0.2 < big.ends_evicted.mean() < 0.45  # mix preserved


def test_trace_scale_thinning_and_validation():
    tr = TraceSchema(t_arrive=np.linspace(0, 100, 1000),
                     works=np.ones(1000), packets=np.ones(1000))
    small = trace_scale(tr, 0.25, seed=1)
    assert 150 < small.m < 350
    with pytest.raises(ValueError, match="factor"):
        trace_scale(tr, 0.0)


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

POWERS = (4.0, 3.0, 5.0, 2.0)
ATTRS = {"machine_class": (0.0, 1.0, 2.0, 3.0)}


def _constrained_trace(m=200, seed=0):
    rng = np.random.default_rng(seed)
    t = np.sort(rng.uniform(0, 30, m))
    pri = rng.integers(0, 2, m).astype(np.int32)
    idx = np.flatnonzero(pri == 0)
    c = Constraints(("machine_class",), idx,
                    np.zeros(idx.size, np.int32),
                    np.full(idx.size, OPS[">="], np.int8),
                    np.full(idx.size, 2.0))
    return TraceSchema(t_arrive=t, works=rng.uniform(1, 4, m),
                       packets=rng.uniform(2, 8, m), priority=pri,
                       constraints=c)


@pytest.mark.parametrize("policy", ["psts", "arrival_only", "jsq",
                                    "random", "round_robin"])
def test_constraints_enforced_under_every_policy(policy):
    tr = _constrained_trace()
    rt = ClusterRuntime(POWERS, policy, node_attrs=ATTRS,
                        trigger_period=1.0,
                        policy_kwargs={"floor": 0.05}
                        if policy == "psts" else None)
    metrics = rt.run(tr)
    assert metrics.completed == tr.m
    for task in rt.tasks.values():
        if task.feasible is not None:
            assert all(task.feasible[nd] for _, nd in task.placements), \
                (policy, task.tid)


def test_constraint_blind_still_enforces():
    tr = _constrained_trace()
    rt = ClusterRuntime(POWERS, "psts", node_attrs=ATTRS,
                        constraint_blind=True, trigger_period=1.0)
    rt.run(tr)
    for task in rt.tasks.values():
        if task.feasible is not None:
            assert all(task.feasible[nd] for _, nd in task.placements)


def test_priority_orders_batch_admission_and_queue_service():
    # all tasks arrive at t=0 on a single node: service order must be
    # tier 0 first (FIFO within tier), nonpreemptively
    tr = TraceSchema(t_arrive=np.zeros(4), works=np.ones(4),
                     packets=np.ones(4),
                     priority=np.array([2, 0, 1, 0], np.int32))
    rt = ClusterRuntime((1.0,), "round_robin", trigger_period=0.0)
    rt.run(tr)
    finish = sorted((task.t_finish, tid) for tid, task in rt.tasks.items())
    assert [tid for _, tid in finish] == [1, 3, 2, 0]
    waits = rt.metrics.wait_by_tier()
    assert waits[0]["completed"] == 2
    assert waits[0]["mean_wait"] < waits[2]["mean_wait"]


def test_infeasible_task_is_loud_not_a_hang():
    c = Constraints(("machine_class",), [0], [0], [OPS[">"]], [50.0])
    tr = TraceSchema(t_arrive=[0.0], works=[1.0], packets=[1.0],
                     constraints=c)
    rt = ClusterRuntime(POWERS, "psts", node_attrs=ATTRS)
    with pytest.raises(InfeasibleTaskError, match="no node"):
        rt.run(tr)


def test_constrained_task_parks_through_feasible_outage():
    # only node 3 (class 3) is feasible; it fails before the arrival and
    # rejoins later — the task must wait for it, not run elsewhere
    c = Constraints(("machine_class",), [0], [0], [OPS[">="]], [3.0])
    tr = TraceSchema(t_arrive=[5.0], works=[2.0], packets=[1.0],
                     constraints=c)
    rt = ClusterRuntime(POWERS, "jsq", node_attrs=ATTRS)
    m = rt.run(tr, failures=[(1.0, 3)], joins=[(20.0, 3)])
    assert m.completed == 1
    task = rt.tasks[0]
    assert all(nd == 3 for _, nd in task.placements)
    assert task.t_finish == pytest.approx(21.0)  # join + work/power


def test_rebalance_respects_feasibility_groups():
    tr = _constrained_trace(m=400, seed=3)
    rt = ClusterRuntime(POWERS, "psts", node_attrs=ATTRS,
                        trigger_period=0.5, bandwidth=256.0,
                        policy_kwargs={"floor": 0.01})
    metrics = rt.run(tr)
    assert metrics.migrations > 0  # rebalancing actually happened
    for task in rt.tasks.values():
        if task.feasible is not None:
            assert all(task.feasible[nd] for _, nd in task.placements)


# ---------------------------------------------------------------------------
# lab integration
# ---------------------------------------------------------------------------

def _lab_scenario(**overrides):
    sc = lab.Scenario(
        name="google-tiny",
        cluster=lab.ClusterSpec(powers=POWERS,
                                attrs={"machine_class": (0, 1, 2, 3),
                                       "ssd": (0, 1, 0, 1)}),
        workload=lab.WorkloadSpec(
            trace=lab.TraceRef(
                path=str(G_EVENTS), format="google",
                params={"constraints_path": str(G_CONSTRAINTS)}),
            horizon=None),
        policy=lab.PolicySpec("psts", trigger_period=1.0,
                              params={"floor": 0.05}),
    )
    return sc.updated(overrides) if overrides else sc


def test_traceref_json_round_trip_and_grid_paths():
    sc = _lab_scenario()
    back = lab.Scenario.from_json(sc.to_json())
    assert back == sc
    assert back.fingerprint() == sc.fingerprint()
    scaled = sc.updated({"workload.trace.scale": 2.0})
    assert scaled.workload.trace.scale == 2.0


def test_traceref_rejects_typo_params_and_formats():
    with pytest.raises(ValueError, match="unknown trace format"):
        lab.TraceRef(path="x.csv", format="slurm")
    with pytest.raises(ValueError, match="constraintz"):
        lab.TraceRef(path="x.csv", format="google",
                     params={"constraintz_path": "y"})


def test_fingerprint_covers_trace_contents(tmp_path):
    p = tmp_path / "t.csv"
    p.write_text("0.0,1.0,1.0\n")
    sc = lab.Scenario(cluster=lab.ClusterSpec(powers=POWERS),
                      workload=lab.WorkloadSpec(trace_path=str(p),
                                                horizon=None))
    fp1 = sc.fingerprint()
    time.sleep(0.01)
    p.write_text("0.0,2.0,1.0\n")
    fp2 = sc.fingerprint()
    assert fp1 != fp2, "same path, different contents must not collide"
    # declaration changes still matter too
    assert sc.replace(seed=1).fingerprint() != fp2


def test_events_backend_reports_per_tier_waits():
    with _quiet():
        r = lab.run(_lab_scenario())
    assert r["completed"] == 4
    wbt = r.extras["wait_by_tier"]
    assert set(wbt) == {"0", "1", "2", "3"}
    assert sum(v["completed"] for v in wbt.values()) == 4
    assert r.extras["tier_counts"] == {"0": 1, "1": 1, "2": 1, "3": 1}


def test_batched_rejects_constrained_trace_with_reason():
    sc = _lab_scenario()
    with _quiet():
        reason = lab.get_backend("batched").eligible(sc)
        assert reason is not None and "constraint" in reason
        assert lab.get_backend("events").eligible(sc) is None
        assert lab.get_backend("legacy").eligible(sc) is not None


def test_eligibility_surfaces_missing_attrs():
    sc = _lab_scenario()
    bare = sc.replace(cluster=lab.ClusterSpec(powers=POWERS))
    with _quiet():
        reason = lab.get_backend("events").eligible(bare)
    assert reason is not None and "attrs" in reason


def test_unconstrained_trace_runs_on_batched(tmp_path):
    p = tmp_path / "plain.csv"
    p.write_text("0.0,2.0,4.0,1\n1.0,3.0,4.0,0\n2.0,2.0,4.0,1\n")
    sc = lab.Scenario(
        cluster=lab.ClusterSpec(powers=POWERS),
        workload=lab.WorkloadSpec(trace=lab.TraceRef(path=str(p)),
                                  horizon=None),
        policy=lab.PolicySpec("arrival_only"))
    r = lab.run(sc, backend="batched")
    assert r["completed"] == 3
    # the fluid model cannot see tiers: flagged in provenance
    assert "workload trace priorities" in r.backend_options["ignored"]


def test_scaled_trace_seed_sweep_is_an_ensemble():
    sc = _lab_scenario(**{"workload.trace.scale": 25.0})
    results = lab.sweep(base=sc, grid={"seed": range(3)}, backend="events")
    arrived = {r["arrived"] for r in results}
    assert len(arrived) > 1, "scaled replays must differ across seeds"


def _plain_trace_and_machines(tmp_path):
    """A 2-node csv trace plus a machine_events companion in the same
    (plain) time units: node 1 halves capacity at t=2."""
    csv = tmp_path / "plain.csv"
    csv.write_text("0.0,2.0,4.0\n0.5,2.0,4.0\n1.0,2.0,4.0\n")
    mach = tmp_path / "machines.csv"
    mach.write_text("0,0,0,,1.0,0.5\n"
                    "0,1,0,,1.0,0.5\n"
                    "2,1,2,,0.5,0.5\n")
    return csv, mach


def test_traceref_machine_events_merges_into_fault_schedule(tmp_path):
    csv, mach = _plain_trace_and_machines(tmp_path)
    sc = lab.Scenario(
        cluster=lab.ClusterSpec(powers=(2.0, 2.0)),
        workload=lab.WorkloadSpec(
            trace=lab.TraceRef(path=str(csv), machine_events=str(mach)),
            horizon=None),
        policy=lab.PolicySpec("arrival_only"),
        faults=lab.FaultSpec(failures=((30.0, 0),)))
    failures, joins, resizes = lab.resolve_fault_schedule(sc)
    assert (30.0, 0) in failures          # declared faults survive
    assert resizes == ((2.0, 1, 0.5),)    # trace churn merged in
    assert lab.Scenario.from_json(sc.to_json()) == sc
    r = lab.run(sc, backend="events")
    assert r["completed"] == 3 and r["resizes"] == 1
    # the machine_events file contents are part of the identity
    fp = sc.fingerprint()
    mach.write_text(mach.read_text() + "3,0,1,,,\n")
    assert sc.fingerprint() != fp


def test_traceref_machine_events_eligibility(tmp_path):
    csv, mach = _plain_trace_and_machines(tmp_path)
    small = lab.Scenario(
        cluster=lab.ClusterSpec(powers=(2.0,)),  # fewer nodes than machines
        workload=lab.WorkloadSpec(
            trace=lab.TraceRef(path=str(csv), machine_events=str(mach)),
            horizon=None),
        policy=lab.PolicySpec("arrival_only"))
    reason = lab.get_backend("events").eligible(small)
    assert reason is not None and "2 machines" in reason
    missing = small.updated({
        "cluster": {"powers": [2.0, 2.0]},
        "workload.trace.machine_events": str(tmp_path / "nope.csv")})
    reason = lab.get_backend("events").eligible(missing)
    assert reason is not None and "unreadable" in reason


def test_traceref_machine_events_on_batched_power_scale(tmp_path):
    """The fluid backend expresses machine churn as its power up/down
    schedule — resizes become fractional scales."""
    csv, mach = _plain_trace_and_machines(tmp_path)
    sc = lab.Scenario(
        cluster=lab.ClusterSpec(powers=(2.0, 2.0)),
        workload=lab.WorkloadSpec(
            trace=lab.TraceRef(path=str(csv), machine_events=str(mach)),
            horizon=None),
        policy=lab.PolicySpec("arrival_only"))
    assert lab.get_backend("batched").eligible(sc) is None
    backend = lab.get_backend("batched")
    scale = backend._power_scale(sc, n_slots=6, n=2, dt=1.0)
    np.testing.assert_allclose(scale[:, 0], 1.0)
    np.testing.assert_allclose(scale[:2, 1], 1.0)
    np.testing.assert_allclose(scale[2:, 1], 0.5)
    r = lab.run(sc, backend="batched")
    assert r["completed"] == 3 and r["resizes"] == 1


def test_blind_mode_round_trips_and_changes_nothing_unconstrained():
    sc = _lab_scenario(**{"policy.constraint_mode": "blind"})
    assert lab.Scenario.from_json(sc.to_json()) == sc
    with pytest.raises(ValueError, match="constraint_mode"):
        lab.PolicySpec("psts", constraint_mode="ignore")


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_trace_info_and_convert(tmp_path, capsys):
    from repro.lab.cli import main
    out_csv = tmp_path / "norm.csv"
    out_side = tmp_path / "norm.json"
    with pytest.warns(UserWarning):
        rc = main(["trace", str(G_EVENTS), "--format", "google",
                   "--param", f"constraints_path={G_CONSTRAINTS}",
                   "--out", str(out_csv),
                   "--out-constraints", str(out_side)])
    assert rc == 0
    text = capsys.readouterr().out
    assert "tasks        4" in text
    assert "constraints  3 row(s)" in text
    back = load_normalized_csv(str(out_csv),
                               constraints_path=str(out_side))
    assert back.m == 4 and back.constraints.k == 3


def test_cli_trace_eviction_mode_and_machine_events(tmp_path, capsys):
    from repro.lab.cli import main
    events = tmp_path / "events.csv"
    events.write_text(
        "1000000,,7,0,,0,u,0,9,0.5,0.2,\n"
        "2000000,,7,0,,1,u,0,9,0.5,0.2,\n"
        "5000000,,7,0,,2,u,0,9,0.5,0.2,\n"
        "6000000,,7,0,,1,u,0,9,0.5,0.2,\n"
        "10000000,,7,0,,4,u,0,9,0.5,0.2,\n")
    mach = tmp_path / "machines.csv"
    mach.write_text("0,1,0,,1.0,0.5\n4000000,1,2,,0.5,0.5\n")
    rc = main(["trace", str(events), "--format", "google",
               "--machine-events", str(mach)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "evictions    1 requeue event(s), 0 task(s) end evicted" in out
    assert "machines     1: 0 failure(s), 0 join(s), 1 resize(s)" in out
    # the escape hatch: end mode replays nothing
    rc = main(["trace", str(events), "--format", "google",
               "--eviction-mode", "end"])
    assert rc == 0
    assert "evictions    0 requeue event(s)" in capsys.readouterr().out
    with pytest.raises(SystemExit, match="google"):
        main(["trace", str(events), "--eviction-mode", "end"])


def test_cli_run_on_trace_scenario(tmp_path, capsys):
    from repro.lab.cli import main
    sc = _lab_scenario()
    f = tmp_path / "sc.json"
    f.write_text(sc.to_json())
    with _quiet():
        rc = main(["run", str(f), "--out", str(tmp_path / "r.json")])
    assert rc == 0
    payload = json.loads((tmp_path / "r.json").read_text())
    assert payload[0]["extras"]["wait_by_tier"]["0"]["completed"] == 1


# ---------------------------------------------------------------------------
# scale / performance
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_million_row_file_ingests_fast(tmp_path):
    rng = np.random.default_rng(0)
    n = 1_000_000
    arr = np.stack([np.sort(rng.uniform(0, 1000, n)),
                    rng.uniform(1, 5, n), rng.uniform(1, 9, n),
                    rng.integers(0, 3, n)], axis=1)
    p = tmp_path / "big.csv"
    np.savetxt(p, arr, delimiter=",", fmt="%.6g")
    t0 = time.perf_counter()
    tr = load_normalized_csv(str(p))
    elapsed = time.perf_counter() - t0
    assert tr.m == n
    assert elapsed < 10.0, f"1M-row ingest took {elapsed:.1f}s"


def test_load_trace_dispatch_and_unknown_format():
    tr = load_trace(str(DATA / "tiny_trace.csv"))
    assert tr.m == 8
    with pytest.raises(ValueError, match="unknown trace format"):
        load_trace(str(DATA / "tiny_trace.csv"), format="nope")


# ---------------------------------------------------------------------------
# attribute-value hashing (stable codes for non-numeric constraint values)
# ---------------------------------------------------------------------------

def test_hash_attr_value_numeric_passthrough():
    from repro.traces import hash_attr_value

    assert hash_attr_value(3) == 3.0
    assert hash_attr_value(2.5) == 2.5
    assert hash_attr_value("7") == 7.0      # numeric-looking strings too
    assert hash_attr_value("1e3") == 1000.0
    assert hash_attr_value(True) == 1.0


def test_hash_attr_value_opaque_strings_are_stable_48_bit_codes():
    from repro.traces import hash_attr_value

    code = hash_attr_value("platform-aB3/xyz")
    # deterministic across calls (unlike hash(), which is salted per
    # process) and an exact float64 integer under 2**48
    assert code == hash_attr_value("platform-aB3/xyz")
    assert code == float(int(code))
    assert 0 <= code < 2.0 ** 48
    assert hash_attr_value("platform-aB3/xyz") != hash_attr_value(
        "platform-aB3/xyzz")
    # pinned value: the codec is part of the on-disk spec format, so a
    # silent change would break recorded fingerprints and spec files
    assert hash_attr_value("machine_class") == 66852076972125.0


def test_hash_attr_value_round_trips_through_cluster_spec():
    from repro.traces import hash_attr_value

    spec = lab.ClusterSpec(
        powers=(1.0, 2.0),
        attrs={"platform": ("alpha", "beta"), "cpus": (2, 4)})
    resolved = spec.resolve_attrs()
    assert resolved["platform"] == (hash_attr_value("alpha"),
                                    hash_attr_value("beta"))
    assert resolved["cpus"] == (2.0, 4.0)
    # a string-valued constraint compares exactly against the hashed
    # node attribute: == selects exactly the matching node
    tr = TraceSchema(
        t_arrive=np.array([0.0]), works=np.array([2.0]),
        packets=np.array([1.0]),
        constraints=Constraints(
            attr_names=("platform",),
            task=np.array([0]),
            attr=np.array([0]),
            op=np.array([OPS["=="]]),
            value=np.array([hash_attr_value("beta")])))
    rt = ClusterRuntime(spec.resolve_powers(), "jsq",
                        node_attrs=resolved)
    rt.run(tr)
    (task,) = rt.tasks.values()
    assert task.node == 1  # only "beta" is feasible


def test_hash_attr_value_round_trips_through_spec_json():
    spec = lab.ClusterSpec(powers=(1.0,), attrs={"platform": ("alpha",)})
    sc = lab.Scenario(
        name="hashed-attrs",
        cluster=spec,
        workload=lab.WorkloadSpec(process="poisson", horizon=5.0,
                                  params={"rate": 1.0}),
        policy=lab.PolicySpec("jsq"))
    back = lab.Scenario.from_json(sc.to_json())
    assert back.cluster.attrs == spec.attrs
    assert back.fingerprint() == sc.fingerprint()
