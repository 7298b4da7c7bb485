"""A cell, found by name: its ``workloads`` entry in ``BENCHMARK.json``,
its deployment under ``configs/``, its traffic mix under ``traffic/`` and
its own settings and limits under ``cells/``. Everything here is read
from those files, so a later cell is a set of new files and entries.

The traffic generator is general: a mix names an arrival process of the
reference generators, an offered load as a share of the cluster's
capacity, arrival rates as multiples of the base rate
``lambda = load * sum(powers) / work_mean`` and fixed process parameters.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from .reference import workload as ref_workload

ROOT = Path(__file__).resolve().parents[1]


def benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _load(path: Path) -> dict:
    return json.loads(path.read_text())


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    settings: dict

    @property
    def seeds_per_sweep(self) -> int:
        return int(self.settings.get("seeds_per_sweep",
                                     self.config["seeds_per_sweep"]))

    @property
    def pool_sweeps(self) -> int:
        """Sweeps in the cell's fixed pool of scenario seeds."""
        return int(self.settings["pool_sweeps"])

    @property
    def limits(self) -> dict:
        return self.settings["limits"]

    def powers(self) -> np.ndarray:
        c = self.config
        return ref_workload.cluster_powers(c["nodes"], c["power_low"],
                                           c["power_high"], c["power_seed"])

    def workload_kwargs(self) -> dict:
        """Keyword arguments of the reference ``make_workload`` (and of
        the program's ``WorkloadSpec``), but for the seed."""
        tr, c = self.traffic, self.config
        lam = tr["load"] * float(self.powers().sum()) / tr["work_mean"]
        params = {k: float(v) * lam for k, v in tr["rates"].items()}
        params.update(tr.get("params", {}))
        return dict(process=tr["process"], horizon=c["slots"] * c["dt"],
                    work_dist=tr["work_dist"], work_mean=tr["work_mean"],
                    packet_mean=tr["packet_mean"], params=params)

    def engine_kwargs(self) -> dict:
        """Keyword arguments of the reference engine."""
        c, tr = self.config, self.traffic
        return dict(n_slots=c["slots"], dt=c["dt"],
                    rebalance=c["policy"] == "psts",
                    # the program's packets draw 1 + Poisson(packet_mean)
                    packets_per_unit=(1.0 + tr["packet_mean"])
                    / tr["work_mean"],
                    **c["trigger"])

    def shrunk(self) -> "Cell":
        """The same cell at a size the CPU rehearsal can run."""
        config = dict(self.config, nodes=min(self.config["nodes"], 64),
                      slots=16)
        settings = dict(self.settings, seeds_per_sweep=4,
                        pool_sweeps=min(self.pool_sweeps, 6),
                        check_sample=min(self.settings["check_sample"], 2))
        return Cell(self.name, self.chips, config, self.traffic, settings)


def find(name: str, root: Path = ROOT) -> Cell:
    """The cell of that name in ``BENCHMARK.json``, with its files."""
    spec = benchmark(root)
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; have "
                         f"{[w['name'] for w in spec['workloads']]}")
    conf = next(c for c in spec["configs"] if c["name"] == entry["config"])
    bench = root / "bench"
    return Cell(name=name, chips=int(entry["chips"]),
                config=_load(root / conf["file"]),
                traffic=_load(bench / "traffic" / f"{entry['traffic']}.json"),
                settings=_load(bench / "cells" / f"{name}.json"))


POOL_BASE = 10_000   # scenario seeds of a cell's pool start here


def warm_seeds(cell: Cell) -> list[int]:
    """Scenario seeds of the warm-up sweep, which no window sweeps."""
    return list(range(POOL_BASE, POOL_BASE + cell.seeds_per_sweep))


def pool_seeds(cell: Cell) -> list[int]:
    """Scenario seeds of the cell's fixed pool: ``pool_sweeps`` sweeps of
    ``seeds_per_sweep`` each."""
    B = cell.seeds_per_sweep
    return list(range(POOL_BASE + B, POOL_BASE + B * (cell.pool_sweeps + 1)))


def pool_groups(tasks: np.ndarray, groups: int) -> list[list[int]]:
    """The pool's seeds (by index; ``tasks`` holds each one's task count)
    dealt into ``groups`` sweeps of equal size whose task totals are near
    alike: largest first, each to the lightest sweep with room. So the
    window's work does not depend on which sweeps it reaches."""
    size = len(tasks) // groups
    load = np.zeros(groups)
    out: list[list[int]] = [[] for _ in range(groups)]
    for i in np.argsort(-np.asarray(tasks), kind="stable"):
        g = min((g for g in range(groups) if len(out[g]) < size),
                key=lambda g: load[g])
        out[g].append(int(i))
        load[g] += tasks[i]
    return [sorted(g) for g in out]


def pool_order(seed: int, groups: int) -> list[int]:
    """The order in which the run with ``seed`` sweeps the pool's groups:
    every run meets the same sizes and arrivals, in an order of its own,
    and no group repeats within a run."""
    rng = np.random.default_rng(np.random.SeedSequence(seed % 2**64))
    return rng.permutation(groups).tolist()
