#!/usr/bin/env python3
"""Benchmark of the batched seed sweep on the chip: one cell per run.

    python3 bench/run.py --workload alibaba4k.poisson --seed 7 --seconds 30 --trace 0

A cell of ``BENCHMARK.json`` is one cluster deployment (``configs/``)
under one traffic mix (``traffic/``). A run sweeps scenario seeds through
``repro.lab.sweep(..., backend="batched")``, the call users make, back to
back. The scenario seeds form a fixed pool of sweeps, the same in every
run (``pool_sweeps`` in ``cells/<cell>.json``), grouped so that the sweeps
hold near the same number of tasks; ``--seed`` sets the order in which a
run sweeps them, so every run meets the same sizes and arrivals, and no
seed repeats within a run. It prints, as its last line
of standard output, one JSON object: ``correct``, ``attempted`` and
``failed`` (in scenarios), ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics read from a profiler trace of the
window), ``device``, with ``--trace 1`` a ``breakdown``, and last the
numbers compared for ``correct``, each beside its limit (also the last
lines of standard error).

* Set-up (``setup_s``, from the start of the process to the start of the
  window): JAX and the chip, one warm-up sweep on seeds of its own, then an
  ahead-of-time compile of every further program shape the pool's sweeps
  need, so that nothing compiles inside the window.
* Window: sweeps start while less than ``--seconds`` have passed; the
  window ends with the last sweep. ``sim_tasks_per_s`` is all the
  scenario-tasks of those sweeps over the window's wall time.
* Check (after the window, not timed): every scenario's task count against
  the benchmark's own generators, and a sample of scenarios against its
  own float64 engine (``check.py``).

Without a TPU, or with fewer chips than the cell asks for, it exits with
code 2 and prints no result; where it cannot warm the pool's shapes, or a
program compiled inside the window, with code 3 and no result.
``--rehearsal`` runs the cell at a tiny size on whatever JAX finds
(``JAX_PLATFORMS=cpu``: kernels interpreted), marks the line as not a
device result and exits with code 1.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)          # the checkout, not bench/, heads the path
if str(ROOT / "src") not in sys.path:
    sys.path.insert(1, str(ROOT / "src"))

import numpy as np  # noqa: E402

from bench import cells, check, xplane  # noqa: E402

# JAX's persistent compilation cache, at a fixed path inside the checkout
CACHE_DIR = ROOT / ".jax_cache"
# timed around every compile, a load from the persistent cache included
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
METRIC_KEYS = ("mean_response", "p99_response", "makespan", "trigger_fires",
               "moved_units", "completed", "arrived")


def enable_cache() -> None:
    """JAX's persistent compilation cache, in the checkout whatever the
    environment says."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))


class Unmeasured(RuntimeError):
    """The window could not be kept free of compiles: no result."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclass
class Window:
    """What one window measured; the per-layer readers take it."""

    sweeps: int
    tasks: int
    compiles: int
    device_kind: str
    trace: xplane.Trace | None = None


class CompileCounter:
    """Counts compilations and cache loads, from ``jax.monitoring``."""

    def __enter__(self):
        import jax.monitoring
        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        return self

    def __exit__(self, *exc):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._duration)

    def _duration(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += secs


# ---------------------------------------------------------------------------
# The program under test
# ---------------------------------------------------------------------------

def scenarios(lab, cell: cells.Cell, seeds: list[int]):
    c, kw = cell.config, cell.workload_kwargs()
    base = lab.Scenario(
        cluster=lab.ClusterSpec(n_nodes=c["nodes"], power_low=c["power_low"],
                                power_high=c["power_high"],
                                power_seed=c["power_seed"]),
        workload=lab.WorkloadSpec(
            process=kw["process"], horizon=kw["horizon"],
            work_dist=kw["work_dist"], work_mean=kw["work_mean"],
            packet_mean=kw["packet_mean"], params=kw["params"]),
        policy=lab.PolicySpec(name=c["policy"], params=c["trigger"]))
    return lab.expand_grid(base, {"seed": seeds})


def sweep(lab, cell: cells.Cell, scs) -> list[dict]:
    """One sweep through the users' call; each scenario's metrics."""
    results = lab.sweep(scs, backend="batched", dt=cell.config["dt"])
    return [{k: float(r.metrics[k]) for k in METRIC_KEYS} for r in results]


def warm_shapes(lab, cell: cells.Cell, plans: list[np.ndarray]) -> int:
    """Compile ahead of time the sweep program for every argument shape
    the pool's sweeps need; ``plans`` holds each sweep's (seeds, slots)
    arrivals per slot, the warm-up sweep's first. The shapes come from the
    program's own layout (``vector_backend.device_args``) of a batch whose
    rows hold their busiest slot's arrivals. Returns the programs compiled
    here; raises :class:`Unmeasured` where the program no longer has what
    this needs, as a window would then compile."""
    try:
        from repro.runtime import vector_backend as vb
        one = scenarios(lab, cell, [0])
        _, _, powers, cfg, scale = lab.get_backend("batched").compile(
            one, cell.config["dt"])
        program = vb._simulate_batch_jax
        seen, compiled = set(), 0
        for j, counts in enumerate(plans):
            busiest = counts.max(axis=1)
            slot = np.full((counts.shape[0], max(int(busiest.max()), 1)),
                           counts.shape[1], np.int32)
            for b, k in enumerate(busiest):
                slot[b, :k] = 0
            args = vb.device_args(slot, np.ones(slot.shape), powers, cfg,
                                  scale)
            key = tuple((a.shape, str(a.dtype)) for a in args)
            if j and key not in seen:
                t0 = time.perf_counter()
                program.lower(*args, cfg).compile()
                compiled += 1
                log(f"  compiled shape {[k[0] for k in key]} in "
                    f"{time.perf_counter() - t0:.3f} s")
            seen.add(key)
            del args
    except (ImportError, AttributeError, TypeError, ValueError) as e:
        raise Unmeasured(f"warm-up by shape unavailable: {e!r}") from e
    return compiled


@contextlib.contextmanager
def host_spans(lab):
    """Profiler annotations around the program's host phases, for the
    traced run only: ``bench.lower`` (workload materialisation and slot
    quantisation), ``bench.layout`` (per-slot layout and transfer) and
    ``bench.simulate`` (the device program and the results' return).
    A phase the program no longer has is left out."""
    import jax
    targets = []
    with contextlib.suppress(ImportError, AttributeError):
        targets.append((type(lab.get_backend("batched")), "compile",
                        "bench.lower"))
    with contextlib.suppress(ImportError):
        from repro.runtime import vector_backend as vb
        targets += [(vb, "device_args", "bench.layout"),
                    (vb, "simulate_batch", "bench.simulate")]
    saved = []
    for owner, attr, span in targets:
        fn = owner.__dict__.get(attr) if isinstance(owner, type) \
            else getattr(owner, attr, None)
        if fn is None:
            continue

        def wrapped(*a, _fn=fn, _span=span, **k):
            with jax.profiler.TraceAnnotation(_span):
                return _fn(*a, **k)
        saved.append((owner, attr, fn))
        setattr(owner, attr, wrapped)
    try:
        yield
    finally:
        for owner, attr, fn in saved:
            setattr(owner, attr, fn)


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def per_layer(cell_name: str, run: Window, spec: dict) -> dict:
    """The cell's per-layer metrics, each read by ``metrics/<name>.py``."""
    out = {}
    for m in spec["per_layer"]:
        if cell_name not in m.get("workloads", [cell_name]):
            continue
        value = importlib.import_module(f"bench.metrics.{m['name']}").read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(cell: cells.Cell, seed: int, seconds: float, trace: bool,
        spec: dict, devices) -> dict:
    """Set-up, window and check of one run; returns the result line."""
    import jax
    from repro import lab
    dev = devices[0]
    B = cell.seeds_per_sweep

    log(f"cell {cell.name}: {cell.config['nodes']} nodes, "
        f"{cell.config['slots']} slots, {B} seeds per sweep, "
        f"traffic {cell.traffic['process']}")
    warm_seeds = cells.warm_seeds(cell)
    t0 = time.perf_counter()
    with CompileCounter() as warm_compiles:
        sweep(lab, cell, scenarios(lab, cell, warm_seeds))
    t_warm = time.perf_counter() - t0
    t0 = time.perf_counter()
    pool = cells.pool_seeds(cell)
    pool_counts = np.stack([check.reference_counts(cell, s) for s in pool])
    groups = cells.pool_groups(pool_counts.sum(axis=1), cell.pool_sweeps)
    plan_idx = [groups[g] for g in cells.pool_order(seed, cell.pool_sweeps)]
    plan_seeds = [[pool[i] for i in idx] for idx in plan_idx]
    counts = [pool_counts[idx] for idx in plan_idx]
    warm_counts = np.stack([check.reference_counts(cell, s)
                            for s in warm_seeds])
    t_counts = time.perf_counter() - t0
    t0 = time.perf_counter()
    n_warmed = warm_shapes(lab, cell, [warm_counts] + counts)
    log(f"  warm-up sweep {t_warm:.3f} s, {warm_compiles.seconds:.3f} s of "
        f"it compiling; the pool's {len(plan_idx)} sweeps' arrivals counted "
        f"and grouped in {t_counts:.3f} s; {n_warmed} further shapes "
        f"compiled in {time.perf_counter() - t0:.3f} s")
    plan = [scenarios(lab, cell, s) for s in plan_seeds]

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    spans = host_spans(lab) if trace else contextlib.nullcontext()
    note = (jax.profiler.TraceAnnotation if trace
            else lambda _name: contextlib.nullcontext())
    results = []
    with spans, CompileCounter() as counter:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        t_start = time.perf_counter()
        with note(xplane.WINDOW):
            for scs in plan:
                if time.perf_counter() - t_start >= seconds:
                    break
                t0 = time.perf_counter()
                with note("bench.sweep"):
                    results.append(sweep(lab, cell, scs))
                log(f"  sweep {len(results)}: {time.perf_counter() - t0:.6f} s")
        t_end = time.perf_counter()
        compiles = counter.count
        if trace:
            jax.profiler.stop_trace()
    setup_s = t_start - _T0
    window_s = t_end - t_start
    done = len(results)
    if done == len(plan) and window_s < seconds:
        log(f"  the pool of {len(plan)} sweeps ran out at {window_s:.3f} s")
    if compiles:
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        raise Unmeasured(f"{compiles} programs compiled inside the window")
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")

    counts = np.concatenate(counts[:done])
    tasks = int(counts.sum())
    win = Window(sweeps=done, tasks=tasks,
                 compiles=compiles, device_kind=dev.device_kind)
    if trace:
        t0 = time.perf_counter()
        win.trace = xplane.reduce(xplane.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"  trace read in {time.perf_counter() - t0:.3f} s")
    log(f"  window {window_s:.6f} s: {done} sweeps, {done * B} scenarios, "
        f"{tasks} tasks, {compiles} compiles; set-up {setup_s:.6f} s; "
        f"peak {peak} bytes")

    t0 = time.perf_counter()
    flat = [m for res in results for m in res]
    seeds = [x for ss in plan_seeds[:done] for x in ss]
    readings, failed = check.judge(cell, flat, seeds, counts, seed)
    log(f"  check took {time.perf_counter() - t0:.3f} s")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if trace:
        metrics = per_layer(cell.name, win, spec)
        device.update(busy_s=win.trace.busy_s, window_s=win.trace.window_s)
    else:
        metrics = {"sim_tasks_per_s": {"value": tasks / window_s,
                                       "unit": "tasks/s"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
    out = {"correct": check.passed(readings, cell.limits) and failed == 0,
           "attempted": len(flat), "failed": failed, "metrics": metrics,
           "device": device}
    if trace:
        out["breakdown"] = {"device_ops": win.trace.top_ops(10),
                            "idle_gaps": win.trace.longest_gaps(10)}
    out["checks"] = check.verdict(readings, cell.limits)
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearsal", action="store_true",
                   help="tiny size on any device; not a device result")
    args = p.parse_args(argv)
    spec = cells.benchmark()
    cell = cells.find(args.workload)

    enable_cache()
    import jax
    devices = jax.devices()
    if args.rehearsal:
        cell = cell.shrunk()
    elif devices[0].platform != "tpu" or len(devices) < cell.chips:
        log(f"no chip to measure: JAX finds {len(devices)} "
            f"{devices[0].platform} device(s), the cell needs {cell.chips} "
            f"TPU chip(s)")
        return 2
    try:
        out = run(cell, args.seed, args.seconds, bool(args.trace), spec,
                  devices[:cell.chips])
    except Unmeasured as e:
        log(f"no result: {e}")
        return 3
    if args.rehearsal:
        out = {"rehearsal": "not a device result", **out}
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 1 if args.rehearsal else 0


if __name__ == "__main__":
    sys.exit(main())
