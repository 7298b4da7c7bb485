"""The reduction of the program's spans and scopes (``spans.py``) and its
readers, on a trace recorded on a TPU v5e: a rehearsal-size window of
``alibaba4k.poisson`` (``trace_sweeps.py --rehearsal``: 64 nodes, 16
slots, 4 seeds a sweep) with the optimized HLO text of its program; and
``xplane.reduce`` on the older ``small_fifo`` trace, as before. The
recorded window also carries the harness's former ``bench.lower``,
``bench.layout`` and ``bench.simulate`` spans."""

import importlib
from collections import defaultdict
from pathlib import Path

import numpy as np
import pytest

from bench import cells, check, run, spans, xplane

DATA = Path(__file__).parent / "data"
TRACE = DATA / "small_spans.xplane.pb"
HLO = DATA / "small_spans.hlo.txt"
CELL, SEED = "alibaba4k.poisson", 2147483659
PHASES = ("repro.batched.generate", "repro.batched.quantize",
          "repro.vector.layout", "repro.vector.transfer", "repro.vector.run",
          "repro.vector.fetch", "repro.batched.results")
NEW = ("generate_ms", "quantize_ms", "layout_ms", "owner_lookup_ms",
       "layout_fill")


@pytest.fixture(scope="module")
def data():
    return xplane.load(TRACE)


@pytest.fixture(scope="module")
def reduced(data):
    return spans.reduce(data, [HLO.read_text()])


@pytest.fixture(scope="module")
def trace(data):
    return xplane.reduce(data)


@pytest.fixture(scope="module")
def raw():
    """Window, host spans ``(start, end, name, args)`` and device ops."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(TRACE))
    host = [(e.start_ns, e.end_ns, e.name, dict(e.stats))
            for p in data.planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events]
    (lo, hi), = [(s, e) for s, e, n, _ in host if n == xplane.WINDOW]
    ops = [(e.start_ns, e.end_ns, e.name) for p in data.planes
           if p.name == "/device:TPU:0" for ln in p.lines
           if ln.name == "XLA Ops" for e in ln.events
           if e.end_ns > lo and e.start_ns < hi]
    return lo, hi, [h for h in host if lo < h[0] and h[1] < hi], ops


def _window(reduced, sweeps):
    return run.Window(sweeps=sweeps, tasks=0, compiles=0,
                      device_kind="TPU v5 lite", spans=reduced)


def test_span_sums(reduced, raw):
    _, _, host, _ = raw
    sweeps = reduced.span_n["repro.sweep"]
    assert sweeps >= 2
    for name in PHASES:
        mine = [h for h in host if h[2] == name]
        assert reduced.span_n[name] == len(mine) == sweeps
        assert reduced.span_s[name] == pytest.approx(
            sum(e - s for s, e, _, _ in mine) * 1e-9)
    args = defaultdict(float)
    for _, _, name, stats in host:
        if name == "repro.vector.layout":
            for k, v in stats.items():
                args[k] += v
    assert reduced.span_args["repro.vector.layout"] == dict(args)
    assert reduced.span_args["repro.sweep"] == {"scenarios": 4.0 * sweeps}
    assert reduced.span_n["bench.sweep"] == sweeps


# idle seconds by innermost span, as the program's spans and the
# harness's read them before idle gaps were named by both
IDLE_BY_SPAN = {
    "host: bench.layout": 9.699e-05, "host: bench.lower": 0.00181266,
    "host: bench.simulate": 0.0001853, "host: bench.sweep": 8.63e-05,
    "host: no bench span": 0.0006423,
    "host: repro.batched.generate": 0.00168784,
    "host: repro.batched.quantize": 0.00050336,
    "host: repro.batched.results": 0.00211238,
    "host: repro.sweep": 0.001698919,
    "host: repro.vector.fetch": 0.017644733,
    "host: repro.vector.layout": 0.00037679,
    "host: repro.vector.transfer": 0.003113437}


def test_idle_agrees_with_the_harness_and_falls_under_program_spans(trace):
    idle = sum(s for _, s in trace.gaps)
    assert idle == pytest.approx(trace.window_s - trace.busy_s)
    by_span = trace.idle_by_span()
    assert sum(by_span.values()) == pytest.approx(idle)
    assert list(by_span.values()) == sorted(by_span.values(), reverse=True)
    assert by_span == pytest.approx(IDLE_BY_SPAN, rel=1e-9)
    under = sum(s for label, s in by_span.items()
                if label.startswith("host: repro."))
    assert under / idle > 0.9


# the harness's readers on this trace as they read before the program's
# spans named its idle gaps (3 sweeps and 12,345 tasks stand in for a run)
BEFORE = {"device_idle_share": 84.70889423048378,
          "program_ms": 1.8032113333333333,
          "prefix_scan_roofline": 0.6014268358408009,
          "window_compiles": 0.0}


def test_harness_readers_read_as_before(trace):
    win = run.Window(sweeps=3, tasks=12345, compiles=0,
                     device_kind="TPU v5 lite", trace=trace)
    got = {m: importlib.import_module(f"bench.metrics.{m}").read(win)
           for m in BEFORE}
    assert got == pytest.approx(BEFORE, rel=1e-12)


def test_scope_map(reduced, raw):
    _, _, _, ops = raw
    scopes = spans.scope_map(HLO.read_text())
    assert set(scopes.values()) <= set(spans.SCOPES)
    assert {"owner_lookup", "owner_gather", "scatter_add", "p99_sort",
            "prefix_scan", "trigger"} <= set(reduced.scope_s)
    # every op's own time lands in one scope, or in (unscoped)
    own = sum(s for _, s in xplane._self_times(ops)) * 1e-9
    assert sum(reduced.scope_s.values()) == pytest.approx(own)
    lookup = [op for op in ops
              if scopes.get(xplane.op_name(op[2])) == "owner_lookup"]
    assert lookup
    assert reduced.scope_s[spans.UNSCOPED] < 0.5 * own


def test_readers(reduced):
    sweeps = reduced.span_n["repro.sweep"]
    win = _window(reduced, sweeps)
    got = {m: importlib.import_module(f"bench.metrics.{m}").read(win)
           for m in NEW}
    for m, span in (("generate_ms", "repro.batched.generate"),
                    ("quantize_ms", "repro.batched.quantize"),
                    ("layout_ms", "repro.vector.layout")):
        assert got[m] == pytest.approx(1e3 * reduced.span_s[span] / sweeps)
    assert got["owner_lookup_ms"] == pytest.approx(
        1e3 * reduced.scope_s["owner_lookup"] / sweeps)
    layout = reduced.span_args["repro.vector.layout"]
    assert got["layout_fill"] == pytest.approx(
        100 * layout["tasks"] / layout["lanes"])
    assert all(v > 0 for v in got.values())
    for mod in NEW:   # the harness's window has no spans: nothing to read
        bare = run.Window(sweeps=sweeps, tasks=1, compiles=0,
                          device_kind="TPU v5 lite")
        assert importlib.import_module(f"bench.metrics.{mod}").read(
            bare) is None


def test_layout_fill_is_the_bench_lane_reckoning(reduced):
    """From the reference's arrivals: each sweep's tasks over B * T * K,
    with K its busiest slot's arrivals rounded up to 128."""
    cell = cells.find(CELL).shrunk()
    pool = cells.pool_seeds(cell)
    counts = np.stack([check.reference_counts(cell, s) for s in pool])
    groups = cells.pool_groups(counts.sum(axis=1), cell.pool_sweeps)
    order = cells.pool_order(SEED, cell.pool_sweeps)
    window = [counts[groups[g]] for g in order][:reduced.span_n["repro.sweep"]]
    tasks = sum(int(c.sum()) for c in window)
    lanes = sum(c.size * (-(-int(c.max()) // 128) * 128) for c in window)
    win = _window(reduced, len(window))
    got = importlib.import_module("bench.metrics.layout_fill").read(win)
    assert got == pytest.approx(100.0 * tasks / lanes, rel=1e-12)
    assert reduced.span_args["repro.vector.layout"]["tasks"] == tasks


def test_reduce_of_small_fifo_reads_as_before():
    """``xplane.reduce`` gives every field it gave before the program had
    spans; ``spans.reduce`` finds no program span or scope in it."""
    data = xplane.load(DATA / "small_fifo.xplane.pb")
    tr = xplane.reduce(data, window="bench.sweep")
    assert tr.window_s == pytest.approx(0.011553639, abs=1e-12)
    assert tr.busy_s == pytest.approx(0.001230366, abs=1e-12)
    assert tr.chips == 1 and tr.modules == 1
    assert tr.module_s == pytest.approx(0.001230691, abs=1e-12)
    assert len(tr.op_self_s) == len(tr.op_label) == 89
    assert sum(tr.op_self_s.values()) == pytest.approx(0.001230366)
    assert len(tr.kernel_s) == 31
    assert sum(tr.kernel_s.values()) == pytest.approx(0.003146633)
    assert tr.kernel_s["prefix_scan_pallas"] == pytest.approx(9.673e-06)
    assert tr.kernel_s["dispatch_work_prefix_pallas"] == pytest.approx(
        3.486e-05)
    assert len(tr.gaps) == 33
    assert {name for name, _ in tr.gaps} == {"host: no bench span"}
    assert sum(s for _, s in tr.gaps) == pytest.approx(0.010323273)
    sp = spans.reduce(data, window="bench.sweep")
    assert sp.span_s == {} and sp.scope_s == {}


HLO_TEXT = """\
HloModule m

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  %x = f32[4]{0} add(%param_0, %param_0), metadata={op_name="jit(f)/while/body/scatter_add/add"}
  ROOT %scatter.1 = f32[4]{0} negate(%x)
}

ENTRY %main (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %fusion.7 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation
  %sort.2 = f32[4]{0} sort(%fusion.7), metadata={op_name="jit(f)/p99_sort/jit(sort)/sort"}
  ROOT %reduce-window.1 = f32[4]{0} negate(%sort.2), metadata={op_name="reduce_window_sum"}
}
"""


def test_scope_of_an_instruction():
    assert spans.scope_of("jit(f)/while/body/closed_call/owner_lookup/"
                          "vmap(jit(searchsorted))/gather") == "owner_lookup"
    assert spans.scope_of("jit(f)/while/body/add") is None
    got = spans.scope_map(HLO_TEXT)
    assert got["fusion.7"] == "scatter_add"       # from its fused computation
    assert got["sort.2"] == "p99_sort"
    assert "reduce-window.1" not in got and "p" not in got


def test_two_shapes_that_disagree_are_told_apart_by_type():
    other = HLO_TEXT.replace("f32[4]", "f32[8]").replace(
        "p99_sort", "summary")
    pick = spans._maps_by_module([HLO_TEXT, other])
    op4 = [(0, 1, "%sort.2 = f32[4]{0} sort(f32[4]{0} %fusion.7)")]
    op8 = [(0, 1, "%sort.2 = f32[8]{0} sort(f32[8]{0} %fusion.7)")]
    assert pick(op4)["sort.2"] == "p99_sort"
    assert pick(op8)["sort.2"] == "summary"
    same = spans._maps_by_module([HLO_TEXT, HLO_TEXT])
    assert same(op8)["sort.2"] == "p99_sort"
