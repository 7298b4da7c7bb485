"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

The device's timeline is the ``XLA Ops`` line of each ``/device:TPU:<i>``
plane; control flow (a ``while``) is an op whose interval holds its body's
ops, so busy time is the union of the intervals and an op's own time is
its interval less what nested ops cover. The ``XLA Modules`` line has one
event per program run. The host's timeline is the ``/host:CPU`` plane,
where the benchmark's ``bench.*`` annotations mark the window and each
sweep, and the program's ``repro.*`` annotations its host phases, on the
same clock as the device. Each idle stretch of the device goes to the
innermost of those spans that covers it.
"""

from __future__ import annotations

import glob
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

WINDOW = "bench.window"
PREFIXES = ("bench.", "repro.")   # the harness's spans and the program's


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def op_name(event_name: str) -> str:
    """HLO instruction of an op event: ``%fusion.83 = f32[...] ...`` ->
    ``fusion.83``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def kernel_of(event_name: str) -> str:
    """Instruction name without its numeric suffix: a Pallas kernel's
    custom call is named after the kernel (``prefix_scan_pallas.1``)."""
    name = op_name(event_name)
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def _self_times(events):
    """Own time of each op: its interval less its direct children's."""
    evs = sorted(events, key=lambda e: (e[0], -e[1]))
    own = [[name, e - s] for s, e, name in evs]
    stack = []   # (end, index) of the ops that hold the current one
    for k, (s, e, _) in enumerate(evs):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            own[stack[-1][1]][1] -= e - s
        stack.append((e, k))
    return own


@dataclass
class Trace:
    """Times in seconds, over the traced window."""

    window_s: float
    busy_s: float                      # averaged over the chips with ops
    chips: int
    op_self_s: dict = field(default_factory=dict)     # op -> own seconds
    op_label: dict = field(default_factory=dict)      # op -> readable text
    kernel_s: dict = field(default_factory=dict)      # kernel -> seconds
    module_s: float = 0.0              # program runs on the device
    modules: int = 0
    gaps: list = field(default_factory=list)          # [[host span, s]]

    def top_ops(self, k: int = 10):
        ranked = sorted(self.op_self_s.items(), key=lambda kv: -kv[1])[:k]
        return [[self.op_label[n], s] for n, s in ranked]

    def longest_gaps(self, k: int = 10):
        return [list(g) for g in sorted(self.gaps, key=lambda g: -g[1])[:k]]

    def idle_by_span(self) -> dict:
        """Idle seconds per innermost host span, longest first."""
        out = defaultdict(float)
        for label, s in self.gaps:
            out[label] += s
        return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def find_xplane(log_dir) -> Path:
    files = glob.glob(str(Path(log_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {files}")
    return Path(files[0])


def load(path):
    """The profiler's data in ``path``, read once for every reduction."""
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def host_spans(data, window: str = WINDOW):
    """``(lo, hi, inside)``: the bounds of the host span named ``window``
    and the ``bench.*`` and ``repro.*`` host spans that overlap it, each
    ``(start, end, name, {arg: value})``."""
    spans = [(ev.start_ns, ev.end_ns, ev.name, dict(ev.stats))
             for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith(PREFIXES)]
    win = [(s, e) for s, e, n, _ in spans if n == window]
    if len(win) != 1:
        raise RuntimeError(f"expected one {window!r} span, found {len(win)}")
    lo, hi = win[0]
    return lo, hi, [sp for sp in spans
                    if sp[2] != window and sp[1] > lo and sp[0] < hi]


def reduce(data, window: str = WINDOW) -> Trace:
    """Reduce loaded profiler data (:func:`load`) to a :class:`Trace` over
    the host span named ``window``."""
    lo, hi, inside = host_spans(data, window)
    phases = [(s, e, n) for s, e, n, _ in inside]

    busy_total, chips = 0.0, 0
    busy_union = []
    op_self, op_label = defaultdict(float), {}
    kernel = defaultdict(float)
    module_s, modules = 0.0, 0
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        ops = []
        for line in plane.lines:
            if line.name == "XLA Ops":
                for ev in line.events:
                    if ev.end_ns > lo and ev.start_ns < hi:
                        ops.append((ev.start_ns, ev.end_ns, ev.name))
            elif line.name == "XLA Modules":
                for ev in line.events:
                    if ev.end_ns > lo and ev.start_ns < hi:
                        module_s += (min(ev.end_ns, hi)
                                     - max(ev.start_ns, lo)) * 1e-9
                        modules += 1
        if not ops:
            continue
        chips += 1
        plane_busy = _union(_clip([(s, e) for s, e, _ in ops], lo, hi))
        busy_total += sum(e - s for s, e in plane_busy) * 1e-9
        busy_union = _union(busy_union + plane_busy)
        for name, own in _self_times(ops):
            key = op_name(name)
            op_self[key] += own * 1e-9
            op_label.setdefault(key, name[:160])
        for s, e, name in ops:
            kernel[kernel_of(name)] += (e - s) * 1e-9

    gaps = []
    edge = lo
    for s, e in busy_union + [[hi, hi]]:
        if s > edge:
            gaps += _attribute(edge, s, phases)
        edge = max(edge, e)
    return Trace(window_s=(hi - lo) * 1e-9,
                 busy_s=busy_total / max(chips, 1), chips=chips,
                 op_self_s=dict(op_self), op_label=op_label,
                 kernel_s=dict(kernel), module_s=module_s, modules=modules,
                 gaps=gaps)


def _attribute(s, e, phases):
    """The gap [s, e) in pieces, each ``[host span, seconds]`` named after
    the innermost host span that covers it; adjacent pieces of one name
    merge."""
    cuts = sorted({s, e} | {x for ps, pe, _ in phases for x in (ps, pe)
                            if s < x < e})
    pieces = []
    for a, b in zip(cuts, cuts[1:]):
        inner = min(((pe - ps, name) for ps, pe, name in phases
                     if ps <= a and pe >= b), default=None)
        label = f"host: {inner[1] if inner else 'no bench span'}"
        if pieces and pieces[-1][0] == label:
            pieces[-1][1] += (b - a) * 1e-9
        else:
            pieces.append([label, (b - a) * 1e-9])
    return pieces
