"""A deployment whose cluster is a machine table: found from its files,
expanded and permuted once, handed to the program and to the reference
alike, and cut for the rehearsal. The table is the Google cluster-data
2011 one (Reiss et al., SoCC 2012, Table 1), in a configuration written
to a temporary checkout; the cell's traffic and limits are those of
``alibaba4k.poisson``."""

import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from bench import cells, run

ROOT = Path(__file__).resolve().parents[2]
SPEC = cells.benchmark()

GOOGLE = [{"count": 11659, "power": 0.5}, {"count": 798, "power": 1.0},
          {"count": 126, "power": 0.25}]
NAME = "google12k.poisson"


def _root(tmp_path, **changes):
    """A checkout whose only cell runs the Google table, with ``changes``
    made to its configuration."""
    base = json.loads((ROOT / "bench/configs/alibaba4k.json").read_text())
    config = {k: v for k, v in base.items()
              if k not in ("power_low", "power_high")}
    config.update(name="google12k", nodes=12583, machines=GOOGLE,
                  power_seed=3)
    config.update(changes)
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "cells"):
        (bench / sub).mkdir(parents=True)
    (bench / "configs/google12k.json").write_text(json.dumps(config))
    shutil.copy(ROOT / "bench/traffic/poisson.json", bench / "traffic")
    shutil.copy(ROOT / "bench/cells/alibaba4k.poisson.json",
                bench / "cells" / f"{NAME}.json")
    spec = dict(SPEC, configs=[dict(SPEC["configs"][0], name="google12k",
                                    file="bench/configs/google12k.json")],
                workloads=[{"name": NAME, "config": "google12k",
                            "traffic": "poisson", "chips": 1, "why": "test"}])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path


@pytest.fixture
def google(tmp_path):
    return cells.find(NAME, _root(tmp_path))


def test_table_at_full_size(google):
    powers = google.powers()
    assert powers.shape == (12583,) and powers.sum() == 6659.0
    for row in GOOGLE:
        assert int((powers == row["power"]).sum()) == row["count"]
    # permuted by the seed, not grouped by class; the same each time
    assert np.array_equal(powers, google.powers())
    assert (np.diff(powers) != 0).sum() > 1000
    assert google.workload_kwargs()["params"]["rate"] == pytest.approx(
        0.8 * 6659 / 6.0)


@pytest.mark.parametrize("changes", [
    {"nodes": 12584},
    {"machines": GOOGLE[:2]},
    {"power_low": 1, "power_high": 1},
    {"machines": GOOGLE + [{"count": 0, "power": 2.0}]},
    {"machines": GOOGLE[:2] + [{"count": 126, "power": 0.0}]},
], ids=["nodes_off", "table_short", "both_forms", "empty_class",
        "zero_power"])
def test_a_table_that_does_not_add_up_is_refused(tmp_path, changes):
    root = _root(tmp_path, **changes)
    with pytest.raises(ValueError):
        cells.find(NAME, root)


def test_scenarios_hand_the_program_the_table(google):
    from repro import lab
    for cell in (google, google.shrunk()):
        scs = run.scenarios(lab, cell, [11, 2**31 + 9])
        for sc in scs:
            assert np.array_equal(sc.cluster.resolve_powers(), cell.powers())
        _, _, powers, _, _ = lab.get_backend("batched").compile(
            scs, cell.config["dt"])
        assert np.array_equal(powers, cell.powers())


def test_shrunk_table_keeps_every_class(google):
    small = google.shrunk()
    assert small.config["nodes"] == 64
    assert [m["count"] for m in small.config["machines"]] == [59, 4, 1]
    assert small.powers().sum() == 59 * 0.5 + 4 + 0.25
    assert cells.shrink_table(GOOGLE, 20000) == GOOGLE
    got = cells.shrink_table([{"count": 1000, "power": 1.0}]
                             + [{"count": 1, "power": 2.0}] * 10, 12)
    assert [m["count"] for m in got] == [2] + [1] * 10


def _rehearse(cell):
    import jax
    jax.clear_caches()
    return run.run(cell.shrunk(), 2**31 + 21, 0.5, False, SPEC,
                   jax.devices())


def test_table_rehearsal_is_correct(google):
    out = _rehearse(google)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0


# powers the program could be handed in place of the table's
OTHER_POWERS = {
    "unpermuted": lambda cell: np.repeat(
        [m["power"] for m in cell.config["machines"]],
        [m["count"] for m in cell.config["machines"]]),
    "all_ones": lambda cell: np.ones(cell.config["nodes"]),
}


@pytest.mark.parametrize("other", sorted(OTHER_POWERS))
def test_program_given_other_powers_is_not_correct(google, monkeypatch,
                                                   other):
    real = run.scenarios

    def scenarios(lab, cell, seeds):
        powers = tuple(OTHER_POWERS[other](cell))
        return [sc.replace(cluster=sc.cluster.replace(powers=powers))
                for sc in real(lab, cell, seeds)]
    monkeypatch.setattr(run, "scenarios", scenarios)
    out = _rehearse(google)
    assert out["correct"] is False and out["failed"] > 0


# fingerprints of the scenarios run.scenarios built before clusters could
# be machine tables: the uniform form is handed to the program unchanged
FINGERPRINTS = {
    "alibaba4k.poisson": ["411c671fd8860dde", "5315ea0a5c0963c8",
                          "4d6cd10702368deb"],
    "alibaba4k.bursty": ["37e205b5a2126095", "f768b721da7e45df",
                         "67b76feb1f4fb7a0"],
}


@pytest.mark.parametrize("name", sorted(FINGERPRINTS))
def test_uniform_scenarios_are_as_before(name):
    from repro import lab
    scs = run.scenarios(lab, cells.find(name), [0, 10_040, 2**31 + 5])
    assert [sc.fingerprint() for sc in scs] == FINGERPRINTS[name]
