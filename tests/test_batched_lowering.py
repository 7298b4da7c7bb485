"""The batched sweep's host lowering: synthetic scenarios go straight from
each seed's arrivals and works (``draw_tasks``) to the (B, M) slot rows
(``slot_rows``), without the packet counts that ``make_workload`` draws
after them. The rows must be the ones the lowering through whole
``Workload`` objects gives, bit for bit, and ``make_workload`` must draw
the same stream as before the split.
"""

import jax
import numpy as np
import pytest

from repro import lab
from repro.runtime.workload import (
    batch_slots,
    draw_tasks,
    make_workload,
    slot_rows,
    to_slots,
)


def _stacked_rows(workloads, dt, n_slots):
    """Each workload quantised on its own, padded and stacked: the
    lowering through whole workloads, spelled out."""
    ends = [wl.t_arrive < dt * n_slots for wl in workloads]
    cap = max((int(keep.sum()) for keep in ends), default=0)
    slots, works, counts = [], [], []
    for wl, keep in zip(workloads, ends):
        s = np.full(cap, n_slots, dtype=np.int32)
        w = np.zeros(cap, dtype=np.float64)
        c = int(keep.sum())
        s[:c] = np.floor(wl.t_arrive[keep] / dt).astype(np.int32)
        w[:c] = wl.works[keep]
        slots.append(s)
        works.append(w)
        counts.append(c)
    return (np.stack(slots), np.stack(works),
            np.asarray(counts, dtype=np.int64))


def _assert_same(got, want):
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert np.array_equal(g, w)


# (process, params, horizon, dt, n_slots, seeds)
CASES = {
    "poisson-dt1": ("poisson", {"rate": 3.0}, 20.0, 1.0, 20, [0, 1, 2]),
    "bursty-dt0.5": ("bursty", {"rate_lo": 0.5, "rate_hi": 6.0,
                                "sojourn_lo": 5.0, "sojourn_hi": 2.0},
                     30.0, 0.5, 60, [0, 1, 2, 3]),
    "diurnal-dt0.7": ("diurnal", {"rate_mean": 2.0, "period": 10.0},
                      25.0, 0.7, 36, [0, 1, 2]),
    # the grid ends at 9.8, short of the horizon: later arrivals are cut
    "poisson-grid-short-of-horizon": ("poisson", {"rate": 4.0}, 10.3, 0.7,
                                      14, [5, 6]),
    "bursty-dt0.7": ("bursty", {"rate_lo": 1.0, "rate_hi": 5.0}, 40.0, 0.7,
                     58, [7, 8]),
    # seed 3 has no arrival; seeds 2 and 4 have 1 and 3
    "zero-arrivals": ("poisson", {"rate": 0.5}, 4.0, 1.0, 4, [2, 3, 4]),
    "no-arrivals-at-all": ("poisson", {"rate": 0.5}, 4.0, 1.0, 4, [3, 11]),
    # an arrival exactly at the grid's end is cut; two share one slot
    "trace-at-grid-end": ("trace", {"times": (0.5, 1.5, 1.5, 2.99, 3.0)},
                          4.0, 1.0, 3, [0, 1]),
    # floor(t / dt), not t // dt: 40.0 / 0.1 rounds to 400.0, while
    # 40.0 // 0.1 is 399.0
    "trace-dt0.1": ("trace", {"times": (0.7, 40.0, 40.0)}, 50.0, 0.1, 500,
                    [0]),
}


@pytest.mark.parametrize("case", list(CASES))
def test_slot_rows_equal_the_workload_lowering(case):
    process, params, horizon, dt, n_slots, seeds = CASES[case]
    wls = [make_workload(process, horizon=horizon, seed=s, **params)
           for s in seeds]
    want = _stacked_rows(wls, dt, n_slots)
    draws = [draw_tasks(process, horizon=horizon, seed=s, **params)[1:]
             for s in seeds]
    _assert_same(slot_rows(draws, dt, n_slots), want)
    _assert_same(batch_slots(wls, dt, n_slots), want)
    for b, wl in enumerate(wls):
        s, w, c = to_slots(wl, dt, n_slots)
        assert c == want[2][b]
        _assert_same((s, w), (want[0][b, :c], want[1][b, :c]))


def test_cases_reach_the_edges():
    """The cases above hold what they are named for."""
    def counts(case):
        process, params, horizon, dt, n_slots, seeds = CASES[case]
        return [int((make_workload(process, horizon=horizon, seed=s,
                                   **params).t_arrive < dt * n_slots).sum())
                for s in seeds]

    assert counts("zero-arrivals") == [1, 0, 3]
    assert counts("no-arrivals-at-all") == [0, 0]
    assert counts("trace-at-grid-end") == [4, 4]
    assert len(set(counts("poisson-dt1"))) == 3
    assert 40.0 // 0.1 != np.floor(40.0 / 0.1)
    process, params, horizon, dt, n_slots, seeds = \
        CASES["poisson-grid-short-of-horizon"]
    for s in seeds:
        t = make_workload(process, horizon=horizon, seed=s,
                          **params).t_arrive
        assert (t >= dt * n_slots).any()


# make_workload's arrays as the generator drew them before draw_tasks was
# split out of it: (process, kwargs, t_arrive, works, packets)
SAVED = {
    3: ("poisson", {"horizon": 4.0, "rate": 1.5},
        [0.6389556585483143, 1.7325077609458952, 1.916205192563336,
         2.938308605636858],
        [1.6820321195284205, 3.3473691429739723, 4.100441095728182,
         3.5837681224850666],
        [8.0, 10.0, 9.0, 12.0]),
    5: ("bursty", {"horizon": 4.0, "work_dist": "poisson", "rate_lo": 1.0,
                   "rate_hi": 3.0, "sojourn_lo": 1.0, "sojourn_hi": 1.0},
        [0.10714250720689965, 0.7616274451940832, 2.642394552244947,
         2.75664842968413, 2.9825280619620296, 3.292233405044088,
         3.4269362130320538, 3.6350166293254107, 3.6454395309687198,
         3.780751071323668],
        [2.0, 3.0, 4.0, 5.0, 4.0, 1.0, 4.0, 5.0, 5.0, 3.0],
        [14.0, 9.0, 11.0, 10.0, 3.0, 13.0, 5.0, 4.0, 12.0, 9.0]),
}


@pytest.mark.parametrize("seed", sorted(SAVED))
def test_make_workload_draws_the_saved_stream(seed):
    process, kw, t, works, packets = SAVED[seed]
    wl = make_workload(process, seed=seed, **kw)
    for got, want in ((wl.t_arrive, t), (wl.works, works),
                      (wl.packets, packets)):
        assert got.dtype == np.float64
        assert np.array_equal(got, np.array(want))
    _, t2, w2 = draw_tasks(process, seed=seed, **kw)
    assert np.array_equal(t2, wl.t_arrive) and np.array_equal(w2, wl.works)


# ---------------------------------------------------------------------------
# The batched backend's lowering
# ---------------------------------------------------------------------------

def _scenarios(workload, seeds):
    base = lab.Scenario(cluster=lab.ClusterSpec(n_nodes=8),
                        workload=workload,
                        policy=lab.PolicySpec(name="psts"))
    return lab.expand_grid(base, {"seed": seeds})


class _Span:
    def __init__(self, args):
        self.args = args

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **kw):
        self.args.update(kw)


class _Spans:
    """Stands in for ``jax.profiler.TraceAnnotation``: keeps each span's
    name and metadata."""

    def __init__(self):
        self.seen = []

    def __call__(self, name, **kw):
        self.seen.append((name, dict(kw)))
        return _Span(self.seen[-1][1])

    def args(self, name):
        return [kw for n, kw in self.seen if n == name]


@pytest.fixture
def spans(monkeypatch):
    rec = _Spans()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec)
    return rec


def test_run_many_equals_the_materialized_lowering(monkeypatch):
    scs = _scenarios(lab.WorkloadSpec(
        process="bursty", horizon=30.0,
        params={"rate_lo": 1.0, "rate_hi": 12.0}), [0, 1, 2, 3])
    backend = lab.get_backend("batched")
    got = [r.to_dict() for r in backend.run_many(scs, dt=0.5)]

    compile_ = type(backend).compile

    def materialized(self, scenarios, dt, fifo_dispatch=False):
        slot, works, powers, cfg, scale = compile_(self, scenarios, dt,
                                                   fifo_dispatch)
        rows = _stacked_rows([sc.workload.materialize(sc.seed)
                              for sc in scenarios], dt, cfg.n_slots)
        _assert_same((slot, works), rows[:2])
        return rows[0], rows[1], powers, cfg, scale

    monkeypatch.setattr(type(backend), "compile", materialized)
    want = [r.to_dict() for r in backend.run_many(scs, dt=0.5)]
    assert got == want
    assert sum(r["metrics"]["completed"] for r in got) > 0


def test_synthetic_sweep_draws_no_packets(spans):
    spec = lab.WorkloadSpec(process="poisson", horizon=12.0,
                            packet_mean=5.0, work_mean=3.0,
                            params={"rate": 4.0})
    scs = _scenarios(spec, [1, 2])
    slot, works, powers, cfg, scale = lab.get_backend("batched").compile(
        scs, 1.0)
    assert spans.args("repro.batched.generate") == [
        {"tasks": sum(spec.materialize(s).m for s in (1, 2)), "packets": 0}]
    assert cfg.packets_per_unit == (1.0 + 5.0) / 3.0


def test_trace_sweep_reads_the_traces_packets(tmp_path, spans):
    rows = np.array([[0.2, 2.0, 9.0], [1.4, 4.0, 1.0], [1.7, 1.0, 5.0],
                     [3.1, 3.0, 2.0]])
    csv = tmp_path / "trace.csv"
    np.savetxt(csv, rows, delimiter=",")
    scs = _scenarios(lab.WorkloadSpec(trace_path=str(csv), horizon=None),
                     [0])
    backend = lab.get_backend("batched")
    slot, works, powers, cfg, scale = backend.compile(scs, 1.0)
    assert spans.args("repro.batched.generate") == [
        {"tasks": 4, "packets": 4}]
    assert cfg.packets_per_unit == rows[:, 2].sum() / rows[:, 1].sum()
    assert cfg.n_slots == 5      # the last arrival, 3.1, plus one dt
    assert slot.tolist() == [[0, 1, 1, 3]]
    assert works.tolist() == [[2.0, 4.0, 1.0, 3.0]]
    res, = backend.run_many(scs)
    m = res.metrics
    assert m["completed"] == 4
    assert m["moved_packets"] == pytest.approx(
        m["moved_units"] * cfg.packets_per_unit)
