#!/usr/bin/env python3
"""Smoke test of the batched sweep on one TPU, at deployment size.

Drives ``repro.lab.sweep`` on the batched backend, the path users call for
seed sweeps:

1. psts over 32 seeds on a 4,096-node heterogeneous cluster (integer powers
   1-10, about the machine count of Alibaba cluster-trace-v2018), Poisson
   arrivals at 0.8 of the cluster's capacity with mean work 6, 256 slots:
   about 7.7e5 tasks per seed;
2. the same with ``fifo_dispatch=True`` on 128 nodes, the dispatch kernel's
   lane limit.

For each it compiles the sweep program ahead of time (compile seconds, the
kernels in it, ``memory_analysis()``), runs one warm-up and one timed sweep,
times the compiled program alone on device-resident inputs, and checks every
seed for ``completed == arrived`` and some seeds against the float64
reference ``simulate_scalar`` within ``vector_backend.reference_gaps``, the
tolerance ``tests/test_runtime_vector.py`` holds the engine to.
Any failed phase raises. The last line of standard output is one JSON
object naming the device; it says ``"ok": true`` only on a TPU.

    python chip_smoke.py                  # needs a TPU; fails without one
    python chip_smoke.py --cpu-rehearsal  # tiny sizes on the CPU, no "ok"
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from importlib import metadata
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

WORK_MEAN = 6.0
LOAD = 0.8        # offered work over cluster capacity
SLOTS = 256
SEEDS = 32


def log(msg: str) -> None:
    print(msg, flush=True)


def _version(dist: str) -> str:
    try:
        return metadata.version(dist)
    except metadata.PackageNotFoundError:
        return "not installed"


def device_check(rehearsal: bool):
    import jax
    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__} "
        f"libtpu={_version('libtpu')}")
    if dev.platform != "tpu" and not rehearsal:
        raise SystemExit("no TPU: this smoke test measures the chip and "
                         "has no CPU fallback (see --cpu-rehearsal)")
    return devices


def scenarios(n_nodes: int, seeds: int, slots: int):
    from repro import lab
    cluster = lab.ClusterSpec(n_nodes=n_nodes, power_low=1, power_high=10)
    rate = LOAD * float(cluster.resolve_powers().sum()) / WORK_MEAN
    base = lab.Scenario(
        cluster=cluster,
        workload=lab.WorkloadSpec(process="poisson", horizon=float(slots),
                                  work_mean=WORK_MEAN,
                                  params={"rate": rate}),
        policy=lab.PolicySpec(name="psts"))
    return lab.expand_grid(base, {"seed": range(seeds)})


def compile_sweep(scs, fifo: bool, dev):
    """Lower the scenarios as the batched backend does and compile its
    program ahead of time; returns (compiled, device arguments, cfg)."""
    from repro import lab
    from repro.runtime.vector_backend import _simulate_batch_jax, device_args
    slot, works, powers, cfg, scale = lab.get_backend("batched").compile(
        scs, 1.0, fifo_dispatch=fifo)
    # uncommitted, like the arrays the sweep itself passes, so the sweep's
    # own call finds this compile instead of compiling again
    args = device_args(slot, works, powers, cfg, scale)
    t0 = time.perf_counter()
    compiled = _simulate_batch_jax.lower(*args, cfg).compile()
    secs = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    kernels = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    B, T, K = args[0].shape
    log(f"  program: seeds={B} nodes={cfg.n_nodes} slots={T} "
        f"tasks={int((slot < cfg.n_slots).sum())} "
        f"max_tasks_per_seed={works.shape[1]} max_tasks_per_slot_padded={K} "
        f"compile_s={secs} tpu_custom_calls={kernels} "
        f"arg_bytes={mem.argument_size_in_bytes} "
        f"temp_bytes={mem.temp_size_in_bytes} "
        f"out_bytes={mem.output_size_in_bytes}")
    stats = dev.memory_stats() or {}
    limit = stats.get("bytes_limit")
    if limit is not None and need > limit:
        raise RuntimeError(f"program needs {need} bytes, device has {limit}")
    want = 2 if fifo else 1   # prefix scan (+ dispatch kernel)
    if dev.platform == "tpu" and kernels < want:
        raise RuntimeError(f"{kernels} compiled Pallas kernels in the "
                           f"program, expected at least {want}")
    return compiled, args, cfg


def run_phase(name: str, n_nodes: int, seeds: int, slots: int, fifo: bool,
              ref_seeds, dev) -> None:
    import jax
    from repro import lab
    from repro.runtime.vector_backend import reference_gaps, simulate_scalar
    log(f"phase {name}: psts, {n_nodes} nodes, {seeds} seeds, {slots} "
        f"slots, fifo_dispatch={fifo}")
    scs = scenarios(n_nodes, seeds, slots)
    compiled, args, cfg = compile_sweep(scs, fifo, dev)

    t0 = time.perf_counter()
    lab.sweep(scs, backend="batched", fifo_dispatch=fifo)
    warm = time.perf_counter() - t0
    t0 = time.perf_counter()
    results = lab.sweep(scs, backend="batched", fifo_dispatch=fifo)
    timed = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(compiled(*args))
    device = time.perf_counter() - t0
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    log(f"  sweep: warmup_s={warm} timed_s={timed} program_s={device} "
        f"peak_bytes_in_use={peak}")

    for sc, res in zip(scs, results):
        m = res.metrics
        wl = sc.workload.materialize(sc.seed)
        arrived = int((wl.t_arrive < sc.workload.horizon).sum())
        if res.backend != "batched" or not (
                m["completed"] == m["arrived"] == arrived):
            raise AssertionError(f"seed {sc.seed}: backend={res.backend} "
                                 f"completed={m['completed']} arrived="
                                 f"{m['arrived']} generated={arrived}")
        if not all(math.isfinite(m[k]) for k in
                   ("mean_response", "p99_response", "makespan")):
            raise AssertionError(f"seed {sc.seed}: non-finite metrics {m}")
    log(f"  completed == arrived on all {len(results)} seeds "
        f"({sum(r.metrics['completed'] for r in results)} tasks)")

    batched = lab.get_backend("batched")
    for i in ref_seeds:
        slot, works, powers, cfg, scale = batched.compile(
            [scs[i]], 1.0, fifo_dispatch=fifo)
        ref = simulate_scalar(slot[0], works[0], powers, cfg,
                              power_scale=scale)
        gaps = reference_gaps(results[i].metrics, ref, cfg.n_slots)
        log(f"  seed {i} vs simulate_scalar: "
            + " ".join(f"{k}={v:.3e}" for k, v in gaps.items())
            + f" trigger_fires={results[i].metrics['trigger_fires']}/"
              f"{ref['trigger_fires']}")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--cpu-rehearsal", action="store_true",
                        help="tiny sizes on any device; never reports ok")
    args = parser.parse_args(argv)
    devices = device_check(args.cpu_rehearsal)
    dev = devices[0]

    from repro.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    if args.cpu_rehearsal:
        run_phase("sweep", 64, 4, 16, False, [0, 1], dev)
        run_phase("fifo_dispatch", 16, 4, 16, True, [0], dev)
        log("cpu rehearsal done; no device result")
        return 1
    run_phase("sweep", 4096, SEEDS, SLOTS, False, [0, 1], dev)
    run_phase("fifo_dispatch", 128, SEEDS, SLOTS, True, [0], dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
