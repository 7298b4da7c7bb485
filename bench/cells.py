"""A cell, found by name: its ``workloads`` entry in ``BENCHMARK.json``,
its deployment under ``configs/``, its traffic mix under ``traffic/`` and
its own settings and limits under ``cells/``. Everything here is read
from those files, so a later cell is a set of new files and entries.

The traffic generator is general: a mix names an arrival process of the
reference generators, an offered load as a share of the cluster's
capacity, arrival rates as multiples of the base rate
``lambda = load * sum(powers) / work_mean`` and fixed process parameters.

A deployment gives its cluster in one of two forms:

* ``power_low``, ``power_high``, ``power_seed``: integer powers drawn
  uniformly from ``power_seed``, as the program's ``ClusterSpec`` draws
  them from ``n_nodes``;
* ``machines``, a table of ``{"count", "power"}`` classes (a trace's
  machine table). It is expanded in table order and then permuted by
  ``power_seed``, because a trace's machine IDs are not grouped by
  platform; the program is handed exactly these powers.

``nodes`` is the cluster's size in both forms, so a table's counts add up
to it.
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
        """(nodes,) float64 node powers, in node order."""
        c = self.config
        if "machines" in c:
            table = np.repeat([float(m["power"]) for m in c["machines"]],
                              [int(m["count"]) for m in c["machines"]])
            return np.random.default_rng(c["power_seed"]).permutation(table)
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
        nodes = min(self.config["nodes"], 64)
        config = dict(self.config, nodes=nodes, slots=16)
        if "machines" in config:
            config["machines"] = shrink_table(config["machines"], nodes)
        settings = dict(self.settings, seeds_per_sweep=4,
                        pool_sweeps=min(self.pool_sweeps, 6),
                        check_sample=min(self.settings["check_sample"], 2))
        return Cell(self.name, self.chips, config, self.traffic, settings)


def shrink_table(machines: list[dict], nodes: int) -> list[dict]:
    """A machine table cut to ``nodes`` machines: every class keeps at
    least one, and the rest are shared out in the table's proportions by
    largest remainder."""
    counts = np.array([int(m["count"]) for m in machines])
    if nodes >= counts.sum():
        return list(machines)
    if nodes < len(machines):
        raise ValueError(f"{nodes} machines cannot hold {len(machines)} "
                         f"classes")
    quota = nodes * counts / counts.sum()
    kept = np.maximum(np.floor(quota).astype(int), 1)
    by_remainder = np.argsort(-(quota - np.floor(quota)), kind="stable")
    for i in by_remainder[:max(nodes - int(kept.sum()), 0)]:
        kept[i] += 1
    while kept.sum() > nodes:   # the minimums took seats past the quotas
        i = min(np.flatnonzero(kept > 1), key=lambda i: quota[i] - kept[i])
        kept[i] -= 1
    return [dict(m, count=int(k)) for m, k in zip(machines, kept)]


def check_cluster(config: dict, file: str) -> None:
    """Refuse a deployment whose cluster is not given in exactly one form,
    or whose machine table does not add up to ``nodes``."""
    uniform = {"power_low", "power_high"} & set(config)
    if "power_seed" not in config:
        raise ValueError(f"{file}: the cluster needs a power_seed")
    if "machines" in config:
        if uniform:
            raise ValueError(f"{file}: give the cluster as a machine table "
                             f"or by {sorted(uniform)}, not both")
        rows = config["machines"]
        if not rows or any(int(m["count"]) < 1 or float(m["power"]) <= 0
                           for m in rows):
            raise ValueError(f"{file}: every machine class needs a count of "
                             f"1 or more and a power above 0")
        total = sum(int(m["count"]) for m in rows)
        if total != config["nodes"]:
            raise ValueError(f"{file}: the machine table holds {total} "
                             f"machines, nodes says {config['nodes']}")
    elif len(uniform) != 2:
        raise ValueError(f"{file}: give the cluster as a machine table or "
                         f"by power_low and power_high")


def find(name: str, root: Path = ROOT) -> Cell:
    """The cell of that name in ``BENCHMARK.json``, with its files."""
    spec = benchmark(root)
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; have "
                         f"{[w['name'] for w in spec['workloads']]}")
    conf = next(c for c in spec["configs"] if c["name"] == entry["config"])
    bench = root / "bench"
    config = _load(root / conf["file"])
    check_cluster(config, conf["file"])
    return Cell(name=name, chips=int(entry["chips"]), config=config,
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
