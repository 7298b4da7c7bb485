#!/usr/bin/env python3
"""A traced window of one cell, read through the program's own spans and
device scopes (``spans.py``) as well as the harness's reduction.

    python3 bench/trace_sweeps.py --workload alibaba4k.poisson --seed 7 \\
        --seconds 50 --out "$TMPDIR/trace-poisson"

Set-up, window and sweep order are ``run.py``'s (its functions, the same
pool and ``--seed``), and the window carries the harness's own ``bench.*``
spans, as a ``--trace 1`` run of ``run.py`` does. After the window, not
timed, it compiles (a persistent-cache hit) each program shape the window
ran for its optimized HLO text, keeps the trace and those texts under
``--out``, and prints one JSON object: the per-layer metrics of the
program's spans and scopes (``metrics/``, read from a :class:`run.Window`
that also holds them), the idle seconds per innermost host span, the
device seconds per scope, the seconds of every host span per sweep and
what a span costs with no profiler running. It checks no answer:
``run.py`` does that. Without a TPU it exits with code 2; ``--rehearsal``
runs the cell at a tiny size on whatever JAX finds and exits with code 1.
"""

from __future__ import annotations

import argparse
import importlib
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)
if str(ROOT / "src") not in sys.path:
    sys.path.insert(1, str(ROOT / "src"))

import numpy as np  # noqa: E402

from bench import cells, check, run, spans, xplane  # noqa: E402

METRICS = ("generate_ms", "quantize_ms", "layout_ms", "owner_lookup_ms",
           "layout_fill", "device_idle_share", "program_ms",
           "prefix_scan_roofline", "window_compiles")


def span_cost_us(n: int = 20000) -> float:
    """Microseconds one span with arguments costs, no profiler running."""
    import jax
    t0 = time.perf_counter()
    for _ in range(n):
        with jax.profiler.TraceAnnotation("repro.cost") as span:
            span.set_metadata(tasks=1, lanes=2, K=3)
    return (time.perf_counter() - t0) / n * 1e6


def hlo_texts(lab, cell, counts) -> list[str]:
    """Optimized HLO of the sweep program at each shape that ``counts``
    (each sweep's (seeds, slots) arrivals) lay out to, as ``run.py``'s
    warm-up builds them."""
    from repro.runtime import vector_backend as vb
    _, _, powers, cfg, scale = lab.get_backend("batched").compile(
        run.scenarios(lab, cell, [0]), cell.config["dt"])
    texts, seen = [], set()
    for c in counts:
        busiest = c.max(axis=1)
        slot = np.full((c.shape[0], max(int(busiest.max()), 1)),
                       c.shape[1], np.int32)
        for b, k in enumerate(busiest):
            slot[b, :k] = 0
        args = vb.device_args(slot, np.ones(slot.shape), powers, cfg, scale)
        key = tuple(a.shape for a in args)
        if key not in seen:
            seen.add(key)
            texts.append(vb._simulate_batch_jax.lower(*args, cfg)
                         .compile().as_text())
    return texts


def traced(cell: cells.Cell, seed: int, seconds: float, out: Path,
           device_kind: str) -> dict:
    import jax
    from repro import lab
    B = cell.seeds_per_sweep
    cost = span_cost_us()
    warm = cells.warm_seeds(cell)
    run.sweep(lab, cell, run.scenarios(lab, cell, warm))
    pool = cells.pool_seeds(cell)
    pool_counts = np.stack([check.reference_counts(cell, s) for s in pool])
    groups = cells.pool_groups(pool_counts.sum(axis=1), cell.pool_sweeps)
    plan_idx = [groups[g] for g in cells.pool_order(seed, cell.pool_sweeps)]
    counts = [pool_counts[idx] for idx in plan_idx]
    warm_counts = np.stack([check.reference_counts(cell, s) for s in warm])
    run.warm_shapes(lab, cell, [warm_counts] + counts)
    plan = [run.scenarios(lab, cell, [pool[i] for i in idx])
            for idx in plan_idx]

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    walls = []
    with run.host_spans(lab), run.CompileCounter() as counter:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        t_start = time.perf_counter()
        with jax.profiler.TraceAnnotation(xplane.WINDOW):
            for scs in plan:
                if time.perf_counter() - t_start >= seconds:
                    break
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.sweep"):
                    run.sweep(lab, cell, scs)
                walls.append(time.perf_counter() - t0)
                run.log(f"  sweep {len(walls)}: {walls[-1]:.6f} s")
        jax.profiler.stop_trace()
    done = len(walls)
    texts = hlo_texts(lab, cell, counts[:done])
    path = xplane.find_xplane(trace_dir)
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(path, out / "window.xplane.pb")
    for i, text in enumerate(texts):
        (out / f"program.{i}.hlo.txt").write_text(text)
    shutil.rmtree(trace_dir, ignore_errors=True)

    win = run.Window(sweeps=done, tasks=int(np.concatenate(
        counts[:done]).sum()), compiles=counter.count,
        device_kind=device_kind, trace=xplane.reduce(out / "window.xplane.pb"))
    win.spans = spans.reduce(out / "window.xplane.pb", texts)
    lanes = sum(B * c.shape[1] * (-(-int(c.max()) // 128) * 128)
                for c in counts[:done])
    metrics = {}
    for name in METRICS:
        value = importlib.import_module(f"bench.metrics.{name}").read(win)
        if value is not None:
            metrics[name] = value
    sp = win.spans
    return {
        "device_kind": device_kind, "sweeps": done, "tasks": win.tasks,
        "window_s": sp.window_s, "busy_s": win.trace.busy_s,
        "sweep_wall_s": walls, "metrics": metrics,
        "bench_layout_fill": 100.0 * win.tasks / lanes,
        "idle_by_span": sp.idle_by_span(),
        "idle_share_under_repro": sp.idle_share_under(),
        "scope_s": dict(sorted(sp.scope_s.items(), key=lambda kv: -kv[1])),
        "span_s": sp.span_s, "span_n": sp.span_n, "span_args": sp.span_args,
        "per_sweep": sp.sweeps, "span_cost_us": cost,
        "top_ops": win.trace.top_ops(10)}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--out", type=Path, required=True)
    p.add_argument("--rehearsal", action="store_true",
                   help="tiny size on any device; not a device result")
    args = p.parse_args(argv)
    cell = cells.find(args.workload)
    run.enable_cache()
    import jax
    dev = jax.devices()[0]
    if args.rehearsal:
        cell = cell.shrunk()
    elif dev.platform != "tpu":
        run.log(f"no chip to measure: JAX finds {dev.platform}")
        return 2
    out = traced(cell, args.seed, args.seconds, args.out, dev.device_kind)
    if args.rehearsal:
        out = {"rehearsal": "not a device result", **out}
    (args.out / "summary.json").write_text(json.dumps(out, indent=1))
    print(json.dumps(out), flush=True)
    return 1 if args.rehearsal else 0


if __name__ == "__main__":
    sys.exit(main())
