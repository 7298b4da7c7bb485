"""Benchmark driver. One function per paper table/figure, plus framework
benchmarks (dispatch, kernels, data balance, runtime). Prints ``name,
us_per_call,derived`` CSV; ``--json PATH`` additionally writes the same
results machine-readable (derived ``k=v;k=v`` strings parsed into dicts) so
perf trajectories can be tracked as ``BENCH_*.json`` artifacts.

Run: ``PYTHONPATH=src python -m benchmarks.run [--only SUBSTR] [--json PATH]``
"""

from __future__ import annotations

import argparse
import importlib
import json
import math
import sys
import traceback


SUITES = ("paper", "dispatch", "kernels", "balance", "ablation", "runtime",
          "federation", "traces", "fidelity", "evictions", "obs", "dag",
          "serve")


def _suites():
    """``(name, benchmark functions, import error)`` per suite. A suite that
    fails to import comes back with its error, and the run counts it as a
    failed suite."""
    suites = []
    for name in SUITES:
        try:
            mod = importlib.import_module(f".bench_{name}", __package__)
        except Exception as exc:  # noqa: BLE001 — reported, counted failed
            suites.append((name, [], exc))
        else:
            suites.append((name, mod.ALL, None))
    return suites


def _finite(v):
    """Strict-JSON guard: non-finite floats become None (bare ``NaN``
    literals would make the artifact unparseable by jq/JSON.parse)."""
    if isinstance(v, float) and not math.isfinite(v):
        return None
    return v


def _parse_derived(derived: str) -> dict:
    """``k=v;k=v`` -> dict with numbers parsed where possible."""
    out: dict = {}
    for part in derived.split(";"):
        if "=" not in part:
            if part:
                out[part] = True
            continue
        k, v = part.split("=", 1)
        try:
            out[k] = int(v)
        except ValueError:
            try:
                out[k] = _finite(float(v))
            except ValueError:
                out[k] = v
    return out


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--only", default="", help="substring filter on name")
    parser.add_argument("--json", default="", metavar="PATH",
                        help="also write results as a JSON list of "
                             "{name, us_per_call, derived} records")
    args = parser.parse_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    records = []
    failures = 0
    for suite_name, fns, import_error in _suites():
        if import_error is not None:
            failures += 1
            print(f"{suite_name},NaN,IMPORT ERROR", file=sys.stderr)
            traceback.print_exception(import_error)
            continue
        for fn in fns:
            if args.only and args.only not in f"{suite_name}/{fn.__name__}":
                continue
            try:
                for name, us, derived in fn():
                    print(f"{name},{us:.1f},{derived}")
                    records.append({
                        "suite": suite_name,
                        "name": name,
                        "us_per_call": _finite(round(float(us), 1)),
                        "derived": _parse_derived(derived),
                    })
            except Exception:
                failures += 1
                print(f"{suite_name}/{fn.__name__},NaN,ERROR",
                      file=sys.stderr)
                traceback.print_exc()
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(records, fh, indent=2, sort_keys=True,
                      allow_nan=False)
            fh.write("\n")
        print(f"# wrote {len(records)} records to {args.json}",
              file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
