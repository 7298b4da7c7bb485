"""The trace reduction, on a trace recorded on a TPU v5e: one FIFO-dispatch
sweep (128 nodes, 8 seeds, 16 slots) inside a ``bench.sweep`` span."""

from pathlib import Path

import pytest

from bench import xplane

DATA = Path(__file__).parent / "data" / "small_fifo.xplane.pb"
SPAN = "bench.sweep"


@pytest.fixture(scope="module")
def trace():
    return xplane.reduce(DATA, window=SPAN)


@pytest.fixture(scope="module")
def raw():
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(DATA))
    host = [(e.start_ns, e.end_ns) for p in data.planes
            if p.name.startswith("/host:") for ln in p.lines
            for e in ln.events if e.name == SPAN]
    (lo, hi), = host
    ops = [(e.start_ns, e.end_ns, e.name) for p in data.planes
           if p.name == "/device:TPU:0" for ln in p.lines
           if ln.name == "XLA Ops" for e in ln.events]
    return lo, hi, ops


def _busy_by_sweep_line(ops, lo, hi):
    """Union length by counting open intervals across sorted endpoints."""
    points = sorted([(max(s, lo), 1) for s, e, _ in ops if e > lo and s < hi]
                    + [(min(e, hi), -1) for s, e, _ in ops
                       if e > lo and s < hi])
    busy, depth, last = 0.0, 0, None
    for x, d in points:
        if depth > 0:
            busy += x - last
        depth += d
        last = x
    return busy * 1e-9


def test_window_and_busy(trace, raw):
    lo, hi, ops = raw
    assert trace.window_s == pytest.approx((hi - lo) * 1e-9)
    assert trace.chips == 1
    assert trace.busy_s == pytest.approx(_busy_by_sweep_line(ops, lo, hi))
    assert 0.0 < trace.busy_s < trace.window_s


def test_idle_gaps_fill_the_rest(trace):
    idle = sum(s for _, s in trace.gaps)
    assert idle + trace.busy_s == pytest.approx(trace.window_s)
    assert all(name.startswith("host: ") for name, _ in trace.gaps)
    longest = trace.longest_gaps(10)
    assert len(longest) <= 10
    assert [s for _, s in longest] == sorted((s for _, s in longest),
                                             reverse=True)


def test_own_times_add_up_to_busy(trace):
    # nested ops (a while's body) subtract from their parent, so the own
    # times add up to the top-level intervals, which do not overlap here
    assert sum(trace.op_self_s.values()) == pytest.approx(trace.busy_s)
    top = trace.top_ops(10)
    assert len(top) == 10
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)


def test_kernel_time(trace, raw):
    _, _, ops = raw
    for kernel in ("prefix_scan_pallas", "dispatch_work_prefix_pallas"):
        want = sum(e - s for s, e, n in ops
                   if n.startswith(f"%{kernel}.")) * 1e-9
        assert want > 0
        assert trace.kernel_s[kernel] == pytest.approx(want)


def test_program_runs(trace):
    assert trace.modules == 1
    assert trace.module_s == pytest.approx(1230691e-9, rel=1e-6)


def test_names():
    name = "%fusion.83 = f32[106496]{0} fusion(f32[32,4096]{1,0} %x)"
    assert xplane.op_name(name) == "fusion.83"
    assert xplane.kernel_of("%prefix_scan_pallas.1 = f32[8]") == \
        "prefix_scan_pallas"
    assert xplane.kernel_of("%copy-start = (f32[8])") == "copy-start"
