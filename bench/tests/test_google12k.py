"""The ``google12k`` deployment as committed: the Google cluster-data 2011
machine table (Reiss et al., SoCC 2012, Table 1) merged into three CPU
classes, found by name, and its cell's traced rehearsal through the
command line, which reads the program's spec-handling span."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import cells

ROOT = Path(__file__).resolve().parents[2]
NAME = "google12k.poisson"


def test_committed_table_expands_to_the_cell():
    cell = cells.find(NAME)
    assert cell.chips == 1 and cell.seeds_per_sweep == 32
    assert cell.pool_sweeps == 64
    powers = cell.powers()
    assert powers.shape == (12583,) and powers.sum() == 6659.0
    for power, count in ((0.5, 11659), (1.0, 798), (0.25, 126)):
        assert int((powers == power).sum()) == count
    assert np.array_equal(powers, cell.powers())
    assert cell.workload_kwargs()["params"]["rate"] == pytest.approx(
        0.8 * 6659 / 6.0)


def test_traced_rehearsal_is_correct_and_reads_spec_ms():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", NAME, "--seed",
         str(2**31 + 41), "--seconds", "1", "--trace", "1", "--rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 1, r.stderr[-2000:]   # a rehearsal is no result
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["rehearsal"] == "not a device result"
    assert out["correct"] is True and out["failed"] == 0
    assert out["metrics"]["spec_ms"]["value"] > 0
    assert out["metrics"]["spec_ms"]["unit"] == "ms"
