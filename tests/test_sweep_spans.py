"""The batched sweep's profiler spans and device scopes.

Host phases are ``jax.profiler.TraceAnnotation`` spans named ``repro.*``,
recorded only while the profiler runs, with counters as their
arguments; the device program's parts are ``jax.named_scope`` blocks,
which reach the optimized HLO as ``metadata={op_name=...}`` and nothing
else.
"""

import contextlib
import glob
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import lab
from repro.runtime import vector_backend as vb

# the phases of one batched sweep, in the order they run; all inside
# ``repro.sweep``. ``repro.lab.specs`` is the scenario-spec handling: the
# uniformity check of the dispatch and of the compile, and the results'
# fingerprints
PHASES = ("repro.lab.specs", "repro.lab.specs", "repro.batched.generate",
          "repro.batched.quantize", "repro.vector.layout",
          "repro.vector.transfer", "repro.vector.run", "repro.vector.fetch",
          "repro.lab.specs", "repro.batched.results")
SCOPES = ("prefix_scan", "deficit", "owner_lookup", "owner_gather",
          "dispatch", "scatter_add", "trigger", "service", "p99_sort",
          "summary")
SEEDS = ([1, 2, 3], [4, 5, 6])


def _base():
    return lab.Scenario(
        cluster=lab.ClusterSpec(n_nodes=16),
        workload=lab.WorkloadSpec(process="poisson", horizon=24.0,
                                  params={"rate": 12.0}),
        policy=lab.PolicySpec(name="psts"))


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """Two profiled sweeps (after an unprofiled one that compiles):
    their results and the ``repro.*`` host spans ``(start, end, name,
    args)`` in start order."""
    base = _base()
    lab.sweep(lab.expand_grid(base, {"seed": SEEDS[0]}), backend="batched")
    out = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(out))
    try:
        results = [lab.sweep(lab.expand_grid(base, {"seed": s}),
                             backend="batched") for s in SEEDS]
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True)
    spans = sorted(((ev.start_ns, ev.end_ns, ev.name, dict(ev.stats))
                    for plane in ProfileData.from_file(path).planes
                    if plane.name.startswith("/host:")
                    for line in plane.lines for ev in line.events
                    if ev.name.startswith("repro.")),
                   key=lambda sp: sp[:2])
    return results, spans


def test_every_span_once_per_sweep_nested(profiled):
    results, spans = profiled
    sweeps = [sp for sp in spans if sp[2] == "repro.sweep"]
    assert len(sweeps) == len(SEEDS)
    for (lo, hi, _, args), seeds in zip(sweeps, SEEDS):
        assert args == {"scenarios": len(seeds)}
        inner = [sp for sp in spans if sp[2] != "repro.sweep"
                 and lo <= sp[0] and sp[1] <= hi]
        assert tuple(name for _, _, name, _ in inner) == PHASES
        for (_, end, _, _), (start, _, _, _) in zip(inner, inner[1:]):
            assert end <= start          # one after the other, none nested
        for _, _, name, args in inner:
            if name == "repro.lab.specs":
                assert args == {"scenarios": len(seeds), "nodes": 16}
    assert len(spans) == len(SEEDS) * (1 + len(PHASES))


def test_span_counters_match_the_sweep(profiled):
    results, spans = profiled
    generate = [sp[3] for sp in spans if sp[2] == "repro.batched.generate"]
    layout = [sp[3] for sp in spans if sp[2] == "repro.vector.layout"]
    base = _base()
    for res, seeds, gen, lay in zip(results, SEEDS, generate, layout):
        completed = sum(int(r.metrics["completed"]) for r in res)
        # a synthetic sweep draws no packet counts: nothing reads them
        assert gen == {"tasks": sum(base.workload.materialize(s).m
                                    for s in seeds), "packets": 0}
        assert lay["tasks"] == completed
        slot, works, powers, cfg, scale = lab.get_backend(
            "batched").compile(lab.expand_grid(base, {"seed": seeds}), 1.0)
        args = vb.device_args(slot, works, powers, cfg, scale)
        assert lay["lanes"] == args[0].size
        assert lay["K"] == args[0].shape[2]


@pytest.fixture
def uncached():
    """No persistent compilation cache: its key leaves out ``op_name``
    metadata, so a program cached with scopes would come back for one
    compiled without them."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _optimized_hlo(fifo: bool) -> str:
    cfg = vb.VectorConfig(n_nodes=64, n_slots=16, fifo_dispatch=fifo)
    shapes = (((4, 16, 128), np.float32), ((4, 16), np.int32),
              ((4, 64), np.float32), ((16, 64), np.float32))
    args = [jax.ShapeDtypeStruct(s, d) for s, d in shapes]
    jax.clear_caches()
    return vb._simulate_batch_jax.lower(*args, cfg).compile().as_text()


def _without_metadata(text: str) -> str:
    """The text less its source tables and every ``metadata={...}``."""
    out, table = [], False
    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            table = True
        elif table:
            table = bool(line.strip())
        else:
            out.append(re.sub(r", metadata=\{[^}]*\}", "", line))
    return "\n".join(out)


@pytest.mark.parametrize("fifo", [False, True])
def test_optimized_hlo_names_every_scope(uncached, fifo):
    names = set()
    for op_name in re.findall(r'op_name="([^"]*)"', _optimized_hlo(fifo)):
        names.update(op_name.split("/"))
    want = {s for s in SCOPES if fifo or s != "dispatch"}
    assert want <= names


@pytest.mark.parametrize("fifo", [False, True])
def test_scopes_change_only_metadata(uncached, monkeypatch, fifo):
    scoped = _without_metadata(_optimized_hlo(fifo))
    monkeypatch.setattr(jax, "named_scope",
                        lambda _name: contextlib.nullcontext())
    plain = _optimized_hlo(fifo)
    monkeypatch.undo()
    jax.clear_caches()
    assert not any(s in plain for s in ("owner_lookup", "p99_sort"))
    assert scoped == _without_metadata(plain)
