"""Reduction of the program's own spans and device scopes in a profiler
trace, from the data that ``xplane.load`` read, beside ``xplane.reduce``.

* Host spans: the program marks its phases with profiler annotations
  named ``repro.*`` (``lab.sweep``, the batched backend's generation,
  quantisation and result assembly, ``vector_backend``'s layout,
  transfer, dispatch and fetch). Per name, the harness's ``bench.*``
  spans included, this keeps the seconds inside the window, the count,
  and the sums of the spans' numeric arguments (``tasks``, ``lanes``,
  ``K``, ``scenarios``).
* Device scopes: ``_simulate_batch_jax`` names its parts with
  ``jax.named_scope``, which reaches the optimized HLO as each
  instruction's ``metadata={op_name=...}``; the trace names ops by
  instruction only. So an op's own time goes to the scope its instruction
  has in the optimized HLO text of the program that ran, and to
  ``(unscoped)`` where it has none.

Idle stretches of the device, by innermost span, are ``xplane.reduce``'s.
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

from bench import xplane

# the named scopes of ``_simulate_batch_jax``
SCOPES = ("prefix_scan", "deficit", "owner_lookup", "owner_gather",
          "dispatch", "scatter_add", "trigger", "service", "p99_sort",
          "summary")
UNSCOPED = "(unscoped)"

_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
_INSTRUCTION = re.compile(r"^\s+(ROOT )?%(\S+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([^,\s]+)")


def scope_of(op_name: str) -> str | None:
    """The first component of an ``op_name`` that names a scope."""
    return next((c for c in op_name.split("/") if c in SCOPES), None)


def scope_map(hlo_text: str) -> dict:
    """Instruction name -> scope, from an optimized HLO text. An
    instruction takes the scope of its own ``op_name``; a fusion without
    one takes that of its fused computation's root, else of the first of
    its instructions that has one. Instructions with none are left out."""
    own, calls, roots, members = {}, {}, {}, defaultdict(list)
    comp = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if m is None or comp is None:
            continue
        name = m.group(2)
        op = _OP_NAME.search(line)
        scope = scope_of(op.group(1)) if op else None
        if scope:
            own[name] = scope
        members[comp].append(name)
        if m.group(1):
            roots[comp] = name
        called = _CALLS.search(line)
        if called:
            calls[name] = called.group(1)
    out = dict(own)
    for name, comp in calls.items():
        if name in out:
            continue
        scoped = [roots.get(comp)] + members.get(comp, [])
        scope = next((own[i] for i in scoped if i in own), None)
        if scope:
            out[name] = scope
    return out


def _head(text: str) -> str:
    """``%name = type`` of an HLO instruction line or of an op event."""
    return " ".join(text.strip().removeprefix("ROOT ").split(None, 3)[:3])


def _maps_by_module(hlo_texts):
    """``pick(ops)``: the instruction -> scope map for the ops of one
    program run. One map serves every run where the texts of the shapes
    the window ran agree on every instruction they share; otherwise each
    run takes the map of the text that holds most of its ops' ``%name =
    type`` heads (the type tells the shapes apart)."""
    maps = [scope_map(t) for t in hlo_texts]
    merged = {}
    if all(merged.setdefault(name, scope) == scope
           for m in maps for name, scope in m.items()):
        return lambda ops: merged
    heads = [{_head(line) for line in t.splitlines()
              if _INSTRUCTION.match(line)} for t in hlo_texts]

    def pick(ops):
        mine = {_head(name) for _, _, name in ops}
        return maps[max(range(len(maps)),
                        key=lambda i: len(mine & heads[i]))]
    return pick


@dataclass
class Spans:
    """Seconds over the traced window."""

    span_s: dict = field(default_factory=dict)      # name -> seconds
    span_n: dict = field(default_factory=dict)      # name -> count
    span_args: dict = field(default_factory=dict)   # name -> {arg: sum}
    scope_s: dict = field(default_factory=dict)     # scope -> own seconds


def reduce(data, hlo_texts=(), window: str = xplane.WINDOW) -> Spans:
    """Reduce loaded profiler data (``xplane.load``) to the program's spans
    and scopes over the host span named ``window``; ``hlo_texts`` are the
    optimized HLO texts of the programs the window ran (no scopes without
    them)."""
    lo, hi, inside = xplane.host_spans(data, window)
    span_s, span_n = defaultdict(float), defaultdict(int)
    span_args = defaultdict(lambda: defaultdict(float))
    for s, e, name, stats in inside:
        span_s[name] += (min(e, hi) - max(s, lo)) * 1e-9
        span_n[name] += 1
        for k, v in stats.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                span_args[name][k] += v

    scope_s = defaultdict(float)
    if hlo_texts:
        pick = _maps_by_module(hlo_texts)
        for plane in data.planes:
            if not plane.name.startswith("/device:TPU:"):
                continue
            ops, runs = [], []
            for line in plane.lines:
                events = [(ev.start_ns, ev.end_ns, ev.name)
                          for ev in line.events
                          if ev.end_ns > lo and ev.start_ns < hi]
                if line.name == "XLA Ops":
                    ops += events
                elif line.name == "XLA Modules":
                    runs += events
            for rs, re_, _ in runs:
                mine = [op for op in ops if op[0] >= rs and op[1] <= re_]
                scopes = pick(mine)
                for name, own in xplane._self_times(mine):
                    scope_s[scopes.get(xplane.op_name(name), UNSCOPED)] += \
                        own * 1e-9
    return Spans(span_s=dict(span_s), span_n=dict(span_n),
                 span_args={k: dict(v) for k, v in span_args.items()},
                 scope_s=dict(scope_s))


def span_ms_per_sweep(run, name: str) -> float | None:
    """Milliseconds a sweep of the window spent in the host span ``name``."""
    sp = run.spans
    if sp is None or run.sweeps == 0 or name not in sp.span_s:
        return None
    return 1e3 * sp.span_s[name] / run.sweeps
