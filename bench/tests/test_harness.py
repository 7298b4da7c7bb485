"""The harness on the CPU: cells found by name, no result without a chip,
a sound rehearsal judged correct, and the timed path broken underneath
judged not correct."""

import dataclasses
import importlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import ml_dtypes
import numpy as np
import pytest

from bench import cells, check, run

ROOT = Path(__file__).resolve().parents[2]
SPEC = cells.benchmark()
NAMES = [w["name"] for w in SPEC["workloads"]]


def test_cells_are_found_by_name():
    for w in SPEC["workloads"]:
        cell = cells.find(w["name"])
        conf = next(c for c in SPEC["configs"] if c["name"] == w["config"])
        assert cell.config["name"] == conf["name"]
        assert cell.config["source"] == conf["source"]
        traffic = ROOT / "bench" / "traffic" / f"{w['traffic']}.json"
        assert cell.traffic == json.loads(traffic.read_text())
        assert "count_gap" in cell.limits
        assert set(cell.limits) <= set(check.NUMBERS)
    with pytest.raises(SystemExit):
        cells.find("no-such-cell")


def test_metric_modules_declare_what_benchmark_says():
    for m in SPEC["per_layer"]:
        mod = importlib.import_module(f"bench.metrics.{m['name']}")
        assert (mod.LAYER, mod.UNIT, mod.MOVES) == \
            (m["layer"], m["unit"], m["moves"])


@pytest.mark.parametrize("name", NAMES)
def test_pool_is_fixed_and_runs_differ_only_in_order(name):
    cell = cells.find(name)
    pool = cells.warm_seeds(cell) + cells.pool_seeds(cell)
    assert len(set(pool)) == len(pool)
    assert len(pool) == cell.seeds_per_sweep * (cell.pool_sweeps + 1)
    for seed in (2**31 + 9, 3, -3):
        order = cells.pool_order(seed, cell.pool_sweeps)
        assert sorted(order) == list(range(cell.pool_sweeps))
        assert order == cells.pool_order(seed, cell.pool_sweeps)
    assert cells.pool_order(-3, 64) != cells.pool_order(3, 64)


def test_pool_groups_even_out_the_sweeps():
    rng = np.random.default_rng(0)
    tasks = rng.uniform(0.5, 1.5, size=8 * 40)
    groups = cells.pool_groups(tasks, 40)
    assert sorted(i for g in groups for i in g) == list(range(tasks.size))
    assert {len(g) for g in groups} == {8}
    totals = np.array([tasks[g].sum() for g in groups])
    naive = tasks.reshape(40, 8).sum(axis=1)
    assert totals.std() < naive.std() / 5


def _cli(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=600)


def test_no_chip_no_result():
    r = _cli(["--workload", NAMES[0], "--seed", "1", "--seconds", "1"], ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_without_the_program_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _cli(["--workload", NAMES[0], "--seed", "1", "--seconds", "1",
              "--rehearsal"], tmp_path)
    assert r.returncode != 0
    assert "{" not in r.stdout


def _run(name, seed=2**31 + 17):
    import jax
    jax.clear_caches()
    cell = cells.find(name).shrunk()
    return run.run(cell, seed, 0.5, False, SPEC, jax.devices())


@pytest.mark.parametrize("name", NAMES)
def test_sound_rehearsal_is_correct(name):
    out = _run(name)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"sim_tasks_per_s", "setup_s"}


def test_warm_up_by_shape_unavailable_is_no_result(monkeypatch):
    from repro import lab
    from repro.runtime import vector_backend as vb
    cell = cells.find(NAMES[0]).shrunk()
    plans = [np.stack([check.reference_counts(cell, s) for s in seeds])
             for seeds in (cells.warm_seeds(cell), cells.pool_seeds(cell))]
    monkeypatch.delattr(vb, "_simulate_batch_jax")
    with pytest.raises(run.Unmeasured, match="warm-up by shape"):
        run.warm_shapes(lab, cell, plans)


def test_compile_inside_the_window_is_no_result(monkeypatch):
    import jax

    def forget(*_):
        jax.clear_caches()
        return 0
    monkeypatch.setattr(run, "warm_shapes", forget)
    with pytest.raises(run.Unmeasured, match="compiled inside the window"):
        _run(NAMES[0])


def test_unmeasured_run_prints_nothing(monkeypatch, capsys):
    def unmeasured(*_a, **_k):
        raise run.Unmeasured("no warm-up")
    monkeypatch.setattr(run, "run", unmeasured)
    rc = run.main(["--workload", NAMES[0], "--seed", "1", "--seconds", "1",
                   "--rehearsal"])
    assert rc == 3 and capsys.readouterr().out == ""


class _Proxy:
    """A module with some attributes replaced."""

    def __init__(self, target, **over):
        self._target, self._over = target, over

    def __getattr__(self, name):
        return self._over.get(name) or getattr(self._target, name)


def _state_unchanged(monkeypatch, vb):
    import jax
    real = jax.lax.scan

    def scan(f, init, xs, *a, **k):
        return real(lambda c, x: (c, f(c, x)[1]), init, xs, *a, **k)
    monkeypatch.setattr(vb, "jax", _Proxy(jax, lax=_Proxy(jax.lax,
                                                           scan=scan)))


def _half_batch(monkeypatch, vb):
    real = vb._per_slot

    def per_slot(slot, works, n_slots):
        w, cnt = real(slot, works, n_slots)
        keep = cnt // 2
        lanes = np.arange(w.shape[2])[None, None, :]
        return (np.where(lanes < keep[:, :, None], w, 0.0).astype(w.dtype),
                keep.astype(cnt.dtype))
    monkeypatch.setattr(vb, "_per_slot", per_slot)


def _answer_altered(monkeypatch, vb):
    real = vb.simulate_batch

    def simulate_batch(*a, **k):
        bm = real(*a, **k)
        return dataclasses.replace(bm, mean_response=bm.mean_response * 1.25)
    monkeypatch.setattr(vb, "simulate_batch", simulate_batch)


def _count_altered(monkeypatch, vb):
    real = vb.simulate_batch

    def simulate_batch(*a, **k):
        bm = real(*a, **k)
        completed = bm.completed.copy()
        completed[-1] += 1
        return dataclasses.replace(bm, completed=completed)
    monkeypatch.setattr(vb, "simulate_batch", simulate_batch)


# one chip: no exchange between chips to leave out
FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered, "count_altered": _count_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", NAMES)
def test_broken_timed_path_is_not_correct(monkeypatch, name, fault):
    from repro.runtime import vector_backend as vb
    FAULTS[fault](monkeypatch, vb)
    try:
        out = _run(name)
    finally:
        monkeypatch.undo()
        import jax
        jax.clear_caches()
    assert out["correct"] is False and out["failed"] > 0


@pytest.mark.parametrize("name", NAMES)
def test_control_in_bfloat16_is_not_correct(name):
    """The reference in bfloat16 put in the program's place fails the
    limits; the float64 reference in its place passes them. The cell
    keeps its nodes and its traffic; its horizon is cut to 32 slots."""
    full = cells.find(name)
    cell = cells.Cell(full.name, full.chips, dict(full.config, slots=32),
                      full.traffic, dict(full.settings, seeds_per_sweep=2,
                                         check_sample=2))
    seeds = cells.pool_seeds(cell)[2:4]
    counts = np.stack([check.reference_counts(cell, s) for s in seeds])

    def results(dtype):
        out = [check.reference_metrics(cell, s, dtype=dtype) for s in seeds]
        return [dict(m, arrived=m["completed"]) for m in out]

    control, failed = check.judge(cell, results(ml_dtypes.bfloat16), seeds,
                                  counts, 5)
    assert not check.passed(control, cell.limits) and failed > 0
    sound, failed = check.judge(cell, results(np.float64), seeds, counts, 5)
    assert check.passed(sound, cell.limits) and failed == 0
