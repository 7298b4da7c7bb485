"""Whether the timed sweeps were correct: what they produced, compared
with the benchmark's own float64 reference.

Two comparisons, each a number with a limit of its own in
``cells/<cell>.json``; a cell compares the numbers its file gives a limit,
and ``count_gap`` always:

* ``count_gap``, over every scenario the window swept: the largest
  difference between the ``completed`` and ``arrived`` the program reports
  and the arrivals the reference generator draws from that seed. Exact
  (limit 0): it checks workload materialisation and slot quantisation.
* over a sample of the window's scenarios, drawn from the run's seed and
  always holding the one with the window's busiest slot, the reference
  engine is run on the reference workload and the worst gap taken of
  ``mean_rel``, ``p99_rel`` (relative gaps of the mean and nearest-rank
  p99 response), ``moved_rel`` (relative gap of the units moved per
  trigger fire), ``fires_gap`` (difference in trigger fires, as a share of
  the slots) and ``makespan_gap`` (difference in slots).
"""

from __future__ import annotations

import math

import numpy as np

from .reference import engine
from .reference import workload as ref_workload

SAMPLED = ("mean_rel", "p99_rel", "moved_rel", "fires_gap", "makespan_gap")
NUMBERS = ("count_gap",) + SAMPLED


def reference_counts(cell, seed: int) -> np.ndarray:
    """(slots,) arrivals per slot of one scenario, by the reference."""
    kw = cell.workload_kwargs()
    return ref_workload.slot_counts(
        kw["process"], horizon=kw["horizon"], seed=seed,
        dt=cell.config["dt"], n_slots=cell.config["slots"], **kw["params"])


def reference_metrics(cell, seed: int, dtype=np.float64) -> dict:
    """The reference engine's metrics of one scenario, in ``dtype``."""
    kw = cell.workload_kwargs()
    params = kw.pop("params")
    t, works = ref_workload.make_workload(seed=seed, **kw, **params)
    slot = ref_workload.to_slots(t, cell.config["dt"], cell.config["slots"])
    return engine.simulate(slot, works[:slot.size], cell.powers(),
                           dtype=dtype, **cell.engine_kwargs())


def _rel(a: float, b: float) -> float:
    gap = abs(a - b) / max(abs(b), 1e-30)
    return gap if math.isfinite(gap) else math.inf


def gaps(got: dict, ref: dict, n_slots: int, dt: float) -> dict:
    """The sampled numbers of one scenario."""
    per_fire = [d["moved_units"] / max(d["trigger_fires"], 1.0)
                for d in (got, ref)]
    out = {
        "mean_rel": _rel(got["mean_response"], ref["mean_response"]),
        "p99_rel": _rel(got["p99_response"], ref["p99_response"]),
        "moved_rel": _rel(*per_fire),
        "fires_gap": abs(got["trigger_fires"] - ref["trigger_fires"])
        / n_slots,
        "makespan_gap": abs(got["makespan"] - ref["makespan"]) / dt,
    }
    return {k: (v if math.isfinite(v) else math.inf) for k, v in out.items()}


def sample(seed: int, n: int, k: int, must: int) -> list[int]:
    """``k`` indices of ``n`` drawn from ``seed``, with ``must`` among them."""
    rng = np.random.default_rng([seed % 2**63, 0xC4EC])
    pick = set(rng.choice(n, size=min(k, n), replace=False).tolist())
    pick.add(must)
    return sorted(pick)


def judge(cell, results: list[dict], seeds: list[int], counts: np.ndarray,
          run_seed: int, produce=None) -> tuple[dict, int]:
    """Readings and failures of a window.

    ``results``: the program's metrics per scenario, in window order;
    ``seeds``: their scenario seeds; ``counts``: (scenarios, slots)
    reference arrivals per slot. ``produce(i)`` gives the metrics to
    judge for sampled scenario ``i`` (default: the program's own); the
    control passes the reference in a lower precision here.

    Returns ``({number: reading}, failed scenarios)``.
    """
    limits = cell.limits
    totals = counts.sum(axis=1)
    count_gaps = np.array([
        max(abs(r["completed"] - c), abs(r["arrived"] - c))
        for r, c in zip(results, totals)], dtype=np.float64)
    bad = set(np.flatnonzero(count_gaps > limits["count_gap"]).tolist())
    readings = {"count_gap": float(count_gaps.max(initial=0.0))}
    busiest = int(np.argmax(counts.max(axis=1)))
    produce = produce or (lambda i: results[i])
    worst = dict.fromkeys(SAMPLED, 0.0)
    for i in sample(run_seed, len(results), cell.settings["check_sample"],
                    busiest):
        ref = reference_metrics(cell, seeds[i])
        g = gaps(produce(i), ref, cell.config["slots"], cell.config["dt"])
        if any(g[k] > limits[k] for k in SAMPLED if k in limits):
            bad.add(i)
        for k in SAMPLED:
            worst[k] = max(worst[k], g[k])
    readings.update(worst)
    return readings, len(bad)


def verdict(readings: dict, limits: dict) -> dict:
    """``{number: {"value", "limit"}}`` of the numbers the cell compares,
    in a fixed order."""
    return {k: {"value": readings[k], "limit": limits[k]} for k in NUMBERS
            if k in limits}


def passed(readings: dict, limits: dict) -> bool:
    return all(readings[k] <= limits[k] for k in NUMBERS if k in limits)
