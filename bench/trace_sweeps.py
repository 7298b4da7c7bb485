#!/usr/bin/env python3
"""A traced window of one cell, kept on disk, with what ``run.py --trace
1`` reads from it but does not print.

    python3 bench/trace_sweeps.py --workload alibaba4k.poisson --seed 7 \\
        --seconds 50 --out "$TMPDIR/trace-poisson"

Set-up, window, program texts and trace reading are ``run.py``'s
(``prepare``, ``window``, ``hlo_texts``, ``read_trace``), with the same
pool and ``--seed``. ``--out``, a new directory, keeps the profiler's
trace and the optimized HLO text of each program shape the window ran
(``program.<i>.hlo.txt``). It prints one JSON object: the idle seconds of
the window per innermost host span, and the device's own seconds per
named scope of the sweep program. It checks no answer: ``run.py`` does
that. Without a TPU it exits with code 2; ``--rehearsal`` runs the cell
at a tiny size on whatever JAX finds and exits with code 1.
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

from bench import cells, run  # noqa: E402


def traced(cell: cells.Cell, seed: int, seconds: float, out: Path) -> dict:
    from repro import lab
    plan = run.prepare(lab, cell, seed)
    out.mkdir(parents=True)
    results, *_ = run.window(lab, cell, plan, seconds, trace_dir=out)
    texts = run.hlo_texts(lab, cell, plan.counts[:len(results)])
    for i, text in enumerate(texts):
        (out / f"program.{i}.hlo.txt").write_text(text)
    trace, spans = run.read_trace(out, texts)
    return {"sweeps": len(results), "idle_by_span": trace.idle_by_span(),
            "scope_s": dict(sorted(spans.scope_s.items(),
                                   key=lambda kv: -kv[1]))}


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
    out = {"device_kind": dev.device_kind,
           **traced(cell, args.seed, args.seconds, args.out)}
    if args.rehearsal:
        out = {"rehearsal": "not a device result", **out}
    (args.out / "summary.json").write_text(json.dumps(out, indent=1))
    print(json.dumps(out), flush=True)
    return 1 if args.rehearsal else 0


if __name__ == "__main__":
    sys.exit(main())
