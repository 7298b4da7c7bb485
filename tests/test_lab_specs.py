"""The scenario-spec layer at deployment size: fingerprints, the
uniformity check of a batched sweep, grid copies, and what a sweep of an
explicit cluster of 10^4 powers serialises.

A spec is frozen, so its canonical JSON is built once and kept; a seed
copy shares the base's sections; the uniformity check compares fields, a
shared section by identity. The fingerprints and verdicts pinned here are
those of the JSON round trip that these replace, so stored results still
match their scenarios.
"""

import json

import numpy as np
import pytest

from repro import lab
from repro.federation import Federation
from repro.lab import specs
from repro.lab.backends import uniform_but_for_seed

# the Google 2011 machine table (Reiss et al., SoCC 2012, Table 1), merged
# by normalised CPU: (machines, power)
GOOGLE = ((11659, 0.5), (798, 1.0), (126, 0.25))
TRIGGER = {"floor": 0.05, "p": 0.001, "q": 0.0001, "t_task": 0.0001,
           "packets_per_step": 64.0}


def _google_powers() -> tuple:
    table = np.repeat([p for _, p in GOOGLE], [c for c, _ in GOOGLE])
    return tuple(np.random.default_rng(3).permutation(table).tolist())


def _scenario(cluster, **overrides) -> lab.Scenario:
    fields = dict(
        cluster=cluster,
        workload=lab.WorkloadSpec(process="poisson", horizon=256.0,
                                  work_mean=6.0, packet_mean=8.0,
                                  params={"rate": 0.8 * 6659 / 6.0}),
        policy=lab.PolicySpec(name="psts", params=TRIGGER), seed=7)
    fields.update(overrides)
    return lab.Scenario(**fields)


def _uniform():
    return _scenario(lab.ClusterSpec(n_nodes=4000, power_low=1,
                                     power_high=1))


def _explicit():
    return _scenario(lab.ClusterSpec(powers=_google_powers()))


def _observed():
    return _scenario(lab.ClusterSpec(powers=(1.0, 2.0, 0.5, 4.0), d=1),
                     obs=lab.ObsSpec(probe_every=1.0),
                     faults=lab.FaultSpec(failures=((3.0, 1),),
                                          joins=((9.0, 1),)),
                     name="observed", engine_seed=5)


# computed with the JSON round trip of ``to_dict`` that fingerprints were
# taken from before the canonical JSON was kept on the specs
FINGERPRINTS = {"uniform": (_uniform, "8918be489cbe454f"),
                "explicit": (_explicit, "1d6308b77131d8b5"),
                "observed": (_observed, "6626a98f88c150f7")}


@pytest.mark.parametrize("name", sorted(FINGERPRINTS))
def test_fingerprints_are_pinned(name):
    build, want = FINGERPRINTS[name]
    sc = build()
    assert sc.fingerprint() == want
    assert sc.fingerprint() == want          # kept, not rebuilt differently
    assert lab.Scenario.from_json(sc.to_json()).fingerprint() == want


def test_canonical_json_is_the_sorted_compact_dict():
    for build, _ in FINGERPRINTS.values():
        sc = build()
        assert sc.canonical_json == json.dumps(
            sc.to_dict(), sort_keys=True, separators=(",", ":"))
        assert sc.cluster.canonical_json == json.dumps(
            sc.cluster.to_dict(), sort_keys=True, separators=(",", ":"))
        assert hash(sc) == hash(lab.Scenario.from_json(sc.to_json()))


# ---------------------------------------------------------------------------
# uniformity: the verdicts of the JSON comparison it replaces
# ---------------------------------------------------------------------------

def _small():
    return _scenario(lab.ClusterSpec(powers=(3.0, 1.0, 7.0, 2.0, 0.5),
                                     bandwidth=256.0))


def _one_power_off(sc):
    powers = list(sc.cluster.powers)
    powers[6001] = 0.25 if powers[6001] != 0.25 else 0.5
    return sc.replace(cluster=sc.cluster.replace(powers=tuple(powers)))


# (base, other scenarios): the verdict on [base, *others]
PAIRS = {
    "same_object": (_small, lambda b: [b], True),
    "seed": (_small, lambda b: [b.updated({"seed": 1})], True),
    "name": (_small, lambda b: [b.updated({"name": "other"})], True),
    "seed_and_name": (_small, lambda b: [b.updated({"seed": 2,
                                                    "name": "x"})], True),
    "rebuilt_from_json": (_small, lambda b: [
        lab.Scenario.from_json(b.to_json()).updated({"seed": 3})], True),
    "engine_seed": (_small, lambda b: [b.updated({"engine_seed": 1})],
                    False),
    "cluster.d": (_small, lambda b: [b.updated({"cluster.d": 2})], False),
    "cluster.bandwidth": (_small, lambda b: [
        b.updated({"cluster.bandwidth": 64.0})], False),
    "cluster.link_bandwidth": (_small, lambda b: [
        b.updated({"cluster.link_bandwidth": 8.0})], False),
    "cluster.n_nodes_form": (_small, lambda b: [b.replace(
        cluster=lab.ClusterSpec(n_nodes=5, bandwidth=256.0))], False),
    "cluster.one_power_of_12583": (_explicit, lambda b: [_one_power_off(b)],
                                   False),
    "cluster.equal_copy_of_12583": (_explicit, lambda b: [b.replace(
        cluster=lab.ClusterSpec(powers=_google_powers()), seed=8)], True),
    "workload.horizon": (_small, lambda b: [
        b.updated({"workload.horizon": 128.0})], False),
    "workload.params.rate": (_small, lambda b: [
        b.updated({"workload.params.rate": 2.0})], False),
    "workload.work_mean": (_small, lambda b: [
        b.updated({"workload.work_mean": 5.0})], False),
    "workload.packet_mean": (_small, lambda b: [
        b.updated({"workload.packet_mean": 4.0})], False),
    "policy.name": (_small, lambda b: [
        b.updated({"policy.name": "arrival_only"})], False),
    "policy.trigger_period": (_small, lambda b: [
        b.updated({"policy.trigger_period": 1.0})], False),
    "policy.params.floor": (_small, lambda b: [
        b.updated({"policy.params.floor": 0.1})], False),
    "faults.failures": (_small, lambda b: [
        b.updated({"faults.failures": [[4.0, 1]]})], False),
    "obs.none_against_set": (_small, lambda b: [
        b.replace(obs=lab.ObsSpec())], False),
    "obs.probe_every": (lambda: _small().replace(obs=lab.ObsSpec(
        probe_every=1.0)), lambda b: [b.updated({"obs.probe_every": 2.0})],
        False),
    "obs.equal_copies": (lambda: _small().replace(obs=lab.ObsSpec()),
                         lambda b: [b.replace(obs=lab.ObsSpec(), seed=4)],
                         True),
    "third_differs": (_small, lambda b: [
        b.updated({"seed": 1}), b.updated({"workload.horizon": 99.0})],
        False),
    "a_federation": (_small, lambda b: [Federation(members=(b,))], False),
}


@pytest.mark.parametrize("case", sorted(PAIRS))
def test_uniformity_gives_the_json_verdicts(case):
    base, others, want = PAIRS[case]
    b = base()
    assert uniform_but_for_seed([b, *others(b)]) is want


def test_numerically_equal_params_share_a_batch():
    """Where field equality and the JSON comparison part, the check keeps
    field equality: a ``floor`` of ``1`` and one of ``1.0`` are read as
    ``float(...)`` by the batched backend and run the same batch (the JSON
    comparison called them different). Each result keeps its own
    scenario's fingerprint."""
    a = _small().updated({"policy.params.floor": 1})
    b = a.updated({"policy.params.floor": 1.0, "seed": 1})
    assert a.fingerprint() != b.updated({"seed": a.seed}).fingerprint()
    assert uniform_but_for_seed([a, b]) is True


# ---------------------------------------------------------------------------
# grid copies
# ---------------------------------------------------------------------------

def _round_trip(sc, assignments):
    """The copy as the full JSON round trip builds it."""
    d = sc.to_dict()
    for path, value in assignments.items():
        node = d
        *parents, leaf = path.split(".")
        for p in parents:
            if not isinstance(node.get(p), dict):
                raise KeyError(path)
            node = node[p]
        node[leaf] = specs._thaw(value)
    return lab.Scenario.from_dict(d)


def test_seed_copies_share_the_base_sections():
    base = _explicit()
    scs = lab.expand_grid(base, {"seed": range(32)})
    assert [sc.seed for sc in scs] == list(range(32))
    for sc in scs:
        for section in ("cluster", "workload", "policy", "faults"):
            assert getattr(sc, section) is getattr(base, section)
    assert scs[5] == _round_trip(base, {"seed": 5})
    assert scs[5].fingerprint() == _round_trip(base, {"seed": 5}).fingerprint()


@pytest.mark.parametrize("assignments", [
    {"cluster.d": 2},
    {"policy.params.floor": 0.2},
    {"seed": 3, "workload.params.rate": 2.0},
    {"name": "n", "engine_seed": 4, "cluster.bandwidth": 8.0},
    {"faults": {"failures": [[1.0, 0]]}},
    {"obs": {"probe_every": 2.0}},
    {"obs.probe_every": 3.0},
    {"workload.trace_path": None, "policy.name": "arrival_only"},
], ids=lambda a: ",".join(a))
def test_dotted_paths_equal_the_round_trip(assignments):
    base = _observed()
    got = base.updated(assignments)
    want = _round_trip(base, assignments)
    assert got == want
    assert got.fingerprint() == want.fingerprint()
    assert got.to_json() == want.to_json()
    if any(path in specs._SECTIONS for path in assignments):
        return          # a whole section: the full round trip
    touched = {path.split(".")[0] for path in assignments}
    for section in ("cluster", "workload", "policy", "faults"):
        if section not in touched:
            assert getattr(got, section) is getattr(base, section)


@pytest.mark.parametrize("assignments, error", [
    ({"obs.ring": 4}, KeyError),          # no obs section to reach into
    ({"cluster.powers.3": 1.0}, KeyError),
    ({"workload.trace.scale": 2.0}, KeyError),
    ({"nonsense": 1}, ValueError),
    ({"cluster.nonsense": 1}, ValueError),
    ({"seed.x": 1}, KeyError),
])
def test_bad_paths_fail_as_the_round_trip_does(assignments, error):
    base = _small()
    with pytest.raises(error):
        _round_trip(base, assignments)
    with pytest.raises(error):
        base.updated(assignments)


# ---------------------------------------------------------------------------
# what a sweep serialises
# ---------------------------------------------------------------------------

def test_sweep_of_an_explicit_cluster_serialises_it_once(monkeypatch):
    """A 32-seed batched sweep over an explicit cluster of 512 powers
    builds the cluster's canonical JSON at most once: the uniformity
    checks compare the shared cluster by identity, and the results'
    fingerprints reuse the kept JSON."""
    powers = tuple(np.random.default_rng(0).choice([0.25, 0.5, 1.0], 512))
    base = lab.Scenario(
        cluster=lab.ClusterSpec(powers=powers),
        workload=lab.WorkloadSpec(process="poisson", horizon=8.0,
                                  work_mean=6.0, params={"rate": 30.0}),
        policy=lab.PolicySpec(name="psts"))
    scs = lab.expand_grid(base, {"seed": range(32)})
    real, built = specs._thaw, []

    def thaw(value):
        if isinstance(value, lab.ClusterSpec):
            built.append(value)
        return real(value)
    monkeypatch.setattr(specs, "_thaw", thaw)
    results = lab.sweep(scs, backend="batched")
    assert len(built) <= 1
    assert [r.fingerprint for r in results] == [sc.fingerprint()
                                                for sc in scs]
    assert len(built) <= 1
