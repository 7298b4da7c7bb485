#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, at a cell's size.

    python3 bench/calibrate.py --workload alibaba4k.poisson --seeds 12 --control 3

For each of ``--seeds`` sweeps of the cell's pool (groups 0, 1, ..., as
a run groups them) it sweeps their scenarios through ``lab.sweep`` (the timed path, at the timed
sizes) and judges them as a run with its own run seed does: the sound
readings. For ``--control``
of those seeds it judges the benchmark's own reference computed in
bfloat16, put in the program's place, on the same sampled scenarios: the
control's readings. One JSON line per judged seed goes to standard output.
The limits in ``cells/<cell>.json`` lie between the largest sound reading
and the smallest control reading of each number (see ``PERF.md``).
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)
if str(ROOT / "src") not in sys.path:
    sys.path.insert(1, str(ROOT / "src"))

import ml_dtypes  # noqa: E402
import numpy as np  # noqa: E402

from bench import cells, check, run  # noqa: E402

FIRST_SEED = 1_000_003   # run seeds FIRST_SEED, FIRST_SEED + 1, ...


def no_limits(cell: cells.Cell) -> cells.Cell:
    """The cell with every limit open, so that every scenario is read."""
    settings = dict(cell.settings,
                    limits=dict.fromkeys(check.NUMBERS, float("inf")))
    return cells.Cell(cell.name, cell.chips, cell.config, cell.traffic,
                      settings)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--control", type=int, default=3)
    p.add_argument("--rehearsal", action="store_true")
    args = p.parse_args(argv)
    cell = cells.find(args.workload)
    if args.rehearsal:
        cell = cell.shrunk()
    cell = no_limits(cell)

    run.enable_cache()
    import jax
    from repro import lab
    dev = jax.devices()[0]
    pool = cells.pool_seeds(cell)
    tasks = [check.reference_counts(cell, s).sum() for s in pool]
    groups = cells.pool_groups(np.array(tasks), cell.pool_sweeps)
    for k in range(args.seeds):
        seed = FIRST_SEED + k
        seeds = [pool[i] for i in groups[k]]
        results = run.sweep(lab, cell, run.scenarios(lab, cell, seeds))
        counts = np.stack([check.reference_counts(cell, s) for s in seeds])
        sound, _ = check.judge(cell, results, seeds, counts, seed)
        line = {"workload": cell.name, "seed": seed, "group": k,
                "device": dev.device_kind,
                "K": int(-(-counts.max() // 128) * 128), "sound": sound}
        if k < args.control:
            def bf16(i, _seeds=seeds):
                return check.reference_metrics(cell, _seeds[i],
                                               dtype=ml_dtypes.bfloat16)
            line["control"], _ = check.judge(cell, results, seeds, counts,
                                             seed, produce=bf16)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
