"""Benchmark-trajectory regression gate (ISSUE 3 satellite).

Compares a freshly produced ``benchmarks/run.py --json`` artifact against a
committed baseline (``BENCH_*.json``) and exits nonzero when a key metric
regresses by more than ``--threshold`` (default 10%). This is what turns
the committed ``BENCH_*.json`` trajectory into an enforced contract: PR 1-2
performance claims (and this PR's federation claims) fail CI when broken.

Key metrics are *quality* numbers (mean/P99 response, error bounds,
speedup ratios) — stable across machines. Raw ``us_per_call`` timings are
noisy on shared CI runners and are only checked with ``--include-timing``
(useful locally, with a generous threshold).

Usage::

    python benchmarks/run.py --json BENCH_new.json
    python benchmarks/compare.py BENCH_PR3.json BENCH_new.json
    python benchmarks/compare.py --baseline-glob 'BENCH_*.json' BENCH_new.json
"""

from __future__ import annotations

import argparse
import glob
import json
import re
import sys

# derived metrics that gate, with their good direction
LOWER_IS_BETTER = (
    "mean_resp",
    "p99_resp",
    "mean_wait",
    "overhead",
    "tier0_wait",      # constrained-trace priority-0 wait (PR 4)
    "tier0_p99",
    "worst_tier_wait",
    "wasted_work",     # service burned by eviction/failure churn (PR 5)
    "cp_stretch",      # makespan over the DAG critical-path bound (PR 7)
    "dag_bytes_moved",
    "steady_overhead",  # post-warmup fifo-dispatch cost vs plain (PR 9)
    "us_per_call",  # only with --include-timing
)
HIGHER_IS_BETTER = (
    "speedup",
    "isolated_over_full",
    "tier0_improvement",  # constrained PSTS vs blind dispatch margin
    "waste_improvement",  # PSTS vs arrival-only wasted work margin (PR 5)
    "locality_hit_ratio",  # DAG children placed with their input (PR 7)
    "cp_stretch_improvement",  # locality vs locality-blind margin (PR 7)
    "tasks_per_second",
    "decisions_per_second",  # streaming-service throughput (PR 8)
    "online_matches_events",  # 1 while the equivalence property holds
    "steal_over_push",  # pull vs push mean completion under skew (PR 10)
    "async_speedup",    # async engine vs lockstep wall-clock (PR 10)
)
# absolute ceilings enforced on the fresh run alone, no baseline needed:
# wall-clock ratios drift run-to-run (relative gating would be noise) but
# must stay under a hard bar. Keys match by exact name or prefix.
ABS_CEILINGS = {
    "telemetry_overhead_frac": 0.05,  # obs enabled-vs-disabled delta (PR 6)
    "serve_p99_ms": 1.0,  # per-decision p99 through the service (PR 8)
    "scrape_overhead_frac": 0.05,  # metrics registry + scrape delta (PR 9)
}
# wall-clock ratios whose *level* is machine-dependent (vectorized vs
# event-loop wall time moves with the host's python/XLA speed balance, so
# the same code scores 15x on one box and 23x on another): relative gating
# across artifacts from different machines is noise. These (record-name
# prefix, metric) pairs are exempt from relative gating and instead must
# stay above an absolute floor — the structural claim (the fast path IS
# an order of magnitude faster) holds on any machine.
ABS_FLOORS = {
    ("federation/fastpath", "speedup"): 5.0,
    # the PR 10 acceptance claim: stealing matches or beats positional
    # push on mean completion under 4-cluster skew (ratio ~1.0; floored
    # with headroom for engine tweaks, never below "matching")
    ("federation/steal", "steal_over_push"): 0.95,
    # the async engine must stay in lockstep's wall-clock ballpark (the
    # ratio hovers around 1.0 and moves with host scheduling noise)
    ("federation/async", "async_speedup"): 0.7,
}
# below this absolute scale, relative comparison is meaningless noise
ABS_FLOOR = 1e-9


def _floor_for(name: str, metric: str):
    for (name_prefix, m), floor in ABS_FLOORS.items():
        if m == metric and name.startswith(name_prefix):
            return floor
    return None


def _load(path: str) -> dict:
    with open(path) as fh:
        records = json.load(fh)
    return {(r["suite"], r["name"]): r for r in records}


def _direction(metric: str) -> int:
    """+1 lower-is-better, -1 higher-is-better, 0 not a key metric."""
    for key in LOWER_IS_BETTER:
        if metric == key or metric.startswith(key):
            return 1
    for key in HIGHER_IS_BETTER:
        if metric == key or metric.startswith(key):
            return -1
    return 0


def _as_number(value):
    if isinstance(value, bool) or not isinstance(value, (int, float)):
        return None
    return float(value)


def compare(baseline: dict, fresh: dict, threshold: float,
            include_timing: bool,
            timing_threshold: float | None = None
            ) -> tuple[list[str], list[str]]:
    """Returns (regressions, notes). ``timing_threshold`` lets raw
    ``us_per_call`` gates run with a budget of their own (dedicated
    runners are quiet, but never shared-runner quiet)."""
    regressions, notes = [], []
    if timing_threshold is None:
        timing_threshold = threshold
    for key, old in sorted(baseline.items()):
        new = fresh.get(key)
        if new is None:
            notes.append(f"MISSING  {key[0]}/{key[1]} (in baseline, not in "
                         f"fresh run)")
            continue
        pairs = [(m, old["derived"].get(m), new["derived"].get(m))
                 for m in old["derived"]]
        if include_timing:
            pairs.append(("us_per_call", old.get("us_per_call"),
                          new.get("us_per_call")))
        for metric, ov, nv in pairs:
            sign = _direction(metric)
            if sign == 0 or (metric == "us_per_call"
                             and not include_timing):
                continue
            if _floor_for(key[1], metric) is not None:
                continue  # machine-dependent level: absolute floor below
            ov, nv = _as_number(ov), _as_number(nv)
            if ov is None or nv is None:
                continue
            if isinstance(ov, float) and abs(ov) < ABS_FLOOR:
                continue  # zero/noise baseline: nothing to regress from
            budget = (timing_threshold if metric == "us_per_call"
                      else threshold)
            ratio = (nv - ov) / abs(ov) * sign
            if ratio > budget:
                regressions.append(
                    f"REGRESSED {key[0]}/{key[1]} {metric}: "
                    f"{ov:g} -> {nv:g} "
                    f"({ratio * 100.0:+.1f}% vs {budget * 100.0:.0f}% "
                    f"budget)")
    # absolute ceilings: checked on every fresh record (baseline-less
    # records included — a brand-new suite is gated from its first run)
    for key, rec in sorted(fresh.items()):
        for metric, value in rec["derived"].items():
            value = _as_number(value)
            if value is None:
                continue
            for name, ceiling in ABS_CEILINGS.items():
                if (metric == name or metric.startswith(name)) \
                        and value > ceiling:
                    regressions.append(
                        f"EXCEEDED {key[0]}/{key[1]} {metric}: "
                        f"{value:g} > {ceiling:g} absolute ceiling")
            floor = _floor_for(key[1], metric)
            if floor is not None and value < floor:
                regressions.append(
                    f"BELOW    {key[0]}/{key[1]} {metric}: "
                    f"{value:g} < {floor:g} absolute floor")
    new_only = sorted(set(fresh) - set(baseline))
    if new_only:
        notes.append(f"NEW      {len(new_only)} record(s) without baseline "
                     f"(first: {new_only[0][0]}/{new_only[0][1]})")
    return regressions, notes


def _natural_key(name: str) -> list:
    """Digit runs compare numerically, so BENCH_PR10 sorts after BENCH_PR9
    (plain lexicographic sort would pick PR9 as 'newest' forever)."""
    return [int(tok) if tok.isdigit() else tok
            for tok in re.split(r"(\d+)", name)]


def newest_baseline(pattern: str, exclude: str) -> str:
    """Newest committed trajectory file by natural name sort."""
    candidates = sorted((p for p in glob.glob(pattern) if p != exclude),
                        key=_natural_key)
    if not candidates:
        raise SystemExit(f"no baseline matches {pattern!r}")
    return candidates[-1]


def main() -> int:
    parser = argparse.ArgumentParser(
        description="fail when fresh benchmark results regress >threshold "
                    "against a committed BENCH_*.json baseline")
    parser.add_argument("baseline", nargs="?", default=None,
                        help="baseline JSON (omit with --baseline-glob)")
    parser.add_argument("fresh", help="freshly produced benchmark JSON")
    parser.add_argument("--baseline-glob", default=None, metavar="GLOB",
                        help="pick the newest (name-sorted) match instead "
                             "of naming the baseline explicitly")
    parser.add_argument("--threshold", type=float, default=0.10,
                        help="allowed relative regression (default 0.10)")
    parser.add_argument("--include-timing", action="store_true",
                        help="also gate raw us_per_call timings (noisy on "
                             "shared runners; CI enables this only behind "
                             "the dedicated-runner label)")
    parser.add_argument("--timing-threshold", type=float, default=None,
                        help="separate budget for us_per_call (default: "
                             "--threshold)")
    args = parser.parse_args()

    if (args.baseline is None) == (args.baseline_glob is None):
        parser.error("give exactly one of BASELINE or --baseline-glob")
    baseline_path = (args.baseline if args.baseline is not None
                     else newest_baseline(args.baseline_glob, args.fresh))
    print(f"baseline: {baseline_path}")
    print(f"fresh:    {args.fresh}")
    regressions, notes = compare(_load(baseline_path), _load(args.fresh),
                                 args.threshold, args.include_timing,
                                 args.timing_threshold)
    for line in notes:
        print(line)
    for line in regressions:
        print(line)
    if regressions:
        print(f"FAIL: {len(regressions)} metric(s) regressed beyond "
              f"{args.threshold * 100.0:.0f}%")
        return 1
    print("OK: no key metric regressed beyond "
          f"{args.threshold * 100.0:.0f}%")
    return 0


if __name__ == "__main__":
    sys.exit(main())
