"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell against the production mesh, with no real allocation
(ShapeDtypeStruct inputs), and record memory/cost/collective analysis.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch olmo-1b \
      --shape train_4k --mesh single --out experiments/dryrun
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

This is how the distribution config is proven coherent without hardware:
a sharding mismatch, an OOM-at-compile or an unsupported collective fails
the cell. Results feed EXPERIMENTS.md sections Dry-run and Roofline.
"""

# The 512 placeholder devices MUST be configured before jax initialises —
# keep these as the very first two lines (before any repro/jax import).
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, arch_shape_cells, get_config
from repro.configs.base import ModelConfig, ShapeSpec
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import collective_stats, roofline_report
from repro.launch.shardings import (
    activation_rules,
    batch_pspecs,
    cache_pspecs,
    named,
    param_pspecs,
    state_pspecs,
)
from repro.models import LM
from repro.models.common import dtype_of, logical_axis_rules
from repro.optim import AdamW, warmup_cosine
from repro.train import init_state, make_train_step

__all__ = ["input_specs", "lower_cell", "main"]


def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> dict:
    """Weak-type-correct, shardable ShapeDtypeStruct stand-ins for every
    model input of this cell (no device allocation)."""
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if shape.kind == "train":
        specs = {"tokens": jax.ShapeDtypeStruct((b, s), i32),
                 "labels": jax.ShapeDtypeStruct((b, s), i32)}
        if cfg.prefix_len:
            specs["prefix_embed"] = jax.ShapeDtypeStruct(
                (b, cfg.prefix_len, cfg.prefix_dim), dtype_of(cfg.dtype))
        return specs
    if shape.kind == "prefill":
        return {"tokens": jax.ShapeDtypeStruct((b, s), i32),
                "lengths": jax.ShapeDtypeStruct((b,), i32)}
    # decode: one new token against a seq_len cache
    return {"tokens": jax.ShapeDtypeStruct((b, 1), i32),
            "lengths": jax.ShapeDtypeStruct((b,), i32)}


def _serve_params_shapes(lm: LM):
    """Serving holds bf16 params (no optimizer state)."""
    shapes = jax.eval_shape(lm.init, jax.random.key(0))
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, jnp.bfloat16 if x.dtype == jnp.float32 else x.dtype),
        shapes)


def _lower_one(cfg: ModelConfig, shape: ShapeSpec, multi_pod: bool,
               unroll: bool = False, ep: int | None = None):
    """Lower + compile one configuration; returns (record, lowered,
    compiled). ``unroll=True`` is the analysis variant: every loop
    straight-lined so XLA's cost model sees each FLOP exactly once."""
    mesh = make_production_mesh(multi_pod=multi_pod, ep=ep)
    n_dev = mesh.devices.size
    lm = LM(cfg, unroll=unroll)
    rules = activation_rules(cfg, mesh, shape)
    specs = input_specs(cfg, shape)
    t0 = time.time()

    with jax.set_mesh(mesh), logical_axis_rules(rules):
        if shape.kind == "train":
            opt = AdamW(moments_dtype=dtype_of(cfg.moments_dtype))
            sch = warmup_cosine(3e-4, 100, 10_000)
            state_shapes = jax.eval_shape(
                lambda: init_state(lm, opt, jax.random.key(0)))
            st_sh = named(mesh, state_pspecs(state_shapes, cfg, mesh))
            b_sh = named(mesh, batch_pspecs(cfg, mesh, shape))
            step = make_train_step(lm, opt, sch, remat=True)
            lowered = jax.jit(
                step, in_shardings=(st_sh, b_sh),
                out_shardings=(st_sh, None)).lower(state_shapes, specs)
        else:
            params_shapes = _serve_params_shapes(lm)
            p_sh = named(mesh, param_pspecs(params_shapes, cfg, mesh))
            cache_shapes = jax.eval_shape(
                lambda: lm.init_cache(shape.global_batch, shape.seq_len))
            c_sh = named(mesh, cache_pspecs(cache_shapes, cfg, mesh, shape))
            b = rules["batch"]
            tok_sh = named(mesh, jax.tree.map(
                lambda _: __import__("jax").sharding.PartitionSpec(b, None),
                specs["tokens"]))
            len_sh = named(mesh, jax.sharding.PartitionSpec(b))
            fn = lm.prefill if shape.kind == "prefill" else lm.decode_step
            lowered = jax.jit(
                fn, in_shardings=(p_sh, c_sh, tok_sh, len_sh),
                out_shardings=(None, c_sh)).lower(
                    params_shapes, cache_shapes, specs["tokens"],
                    specs["lengths"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    coll = collective_stats(compiled.as_text())
    record = {
        "arch": cfg.name,
        "shape": shape.name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": n_dev,
        "kind": shape.kind,
        "n_stages": lm.n_stages,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "args_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        "cost": {"flops": cost.get("flops", 0.0),
                 "bytes_accessed": cost.get("bytes accessed", 0.0)},
        "collectives": coll,
    }
    return record, lowered, compiled


def _analysis_counts(cfg: ModelConfig, shape: ShapeSpec, multi_pod: bool,
                     ep: int | None = None) -> dict:
    """Loop-corrected HLO counts for the full depth.

    XLA's cost model counts while-loop bodies once, so the scan-over-stages
    (and inner attention/SSM scans) under-report. We lower *unrolled*
    variants at 1 and 2 stages, fit counts = base + per_stage * n, and
    extrapolate to the full depth. (The unrolled variant also runs attention
    at a single KV block, so its in-layer FLOPs are exact.)
    """
    period = cfg.attn_every if cfg.family == "hybrid" else 1
    full_stages = (cfg.n_layers // period if cfg.family == "hybrid"
                   else cfg.n_layers)
    points = {}
    for k in (1, 2):
        cfg_k = dataclasses.replace(cfg, n_layers=period * k)
        rec, _, _ = _lower_one(cfg_k, shape, multi_pod, unroll=True, ep=ep)
        points[k] = rec
    out = {}
    for name, get in (
        ("flops", lambda r: float(r["cost"]["flops"] or 0.0)),
        ("bytes_accessed", lambda r: float(r["cost"]["bytes_accessed"]
                                           or 0.0)),
        ("collective_bytes",
         lambda r: float(r["collectives"]["total_bytes"])),
        ("collective_count",
         lambda r: float(r["collectives"]["total_count"])),
    ):
        per_stage = get(points[2]) - get(points[1])
        base = get(points[1]) - per_stage
        if base < 0 or per_stage < 0:
            # partitioner decisions changed between depths — the 2-point
            # fit is unreliable; fall back to slope-through-origin
            out[name] = get(points[2]) / 2.0 * full_stages
            out[name + "_per_stage"] = get(points[2]) / 2.0
        else:
            out[name] = base + per_stage * full_stages
            out[name + "_per_stage"] = per_stage
    return out


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               cfg: ModelConfig | None = None,
               return_artifacts: bool = False,
               analysis: bool = True,
               ep: int | None = None):
    """Full dry-run record for one cell: real compile (sharding proof,
    memory, collective schedule) + loop-corrected analysis counts."""
    cfg = cfg or get_config(arch)
    shape = SHAPES[shape_name]
    record, lowered, compiled = _lower_one(cfg, shape, multi_pod, ep=ep)
    if ep:
        record["mesh"] += f"+ep{ep}"
    if analysis:
        record["corrected"] = _analysis_counts(cfg, shape, multi_pod, ep=ep)
    record["roofline"] = roofline_report(record, cfg, shape)
    if return_artifacts:
        return record, lowered, compiled
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    cells = []
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    if args.all:
        for arch, shape_name, skipped in arch_shape_cells():
            for mp in meshes:
                cells.append((arch, shape_name, mp))
    else:
        for mp in meshes:
            cells.append((args.arch, args.shape, mp))

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch, shape_name, mp in cells:
        tag = f"{arch}__{shape_name}__{'multi' if mp else 'single'}"
        path = os.path.join(args.out, tag + ".json")
        if os.path.exists(path):
            print(f"[skip] {tag} (cached)")
            continue
        print(f"[run ] {tag}", flush=True)
        try:
            rec = lower_cell(arch, shape_name, mp)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            r = rec["roofline"]
            state_gib = rec["memory"]["args_bytes"] / 2 ** 30
            print(f"      ok: compile={rec['compile_s']}s "
                  f"state/dev={state_gib:.2f}GiB "
                  f"dominant={r['dominant']} "
                  f"t_compute={r['compute_s']:.4f}s "
                  f"t_mem={r['memory_s']:.4f}s "
                  f"t_coll={r['collective_s']:.4f}s "
                  f"roofline={r['roofline_fraction']:.3f}", flush=True)
        except Exception:
            failures += 1
            print(f"      FAILED {tag}", flush=True)
            traceback.print_exc()
        finally:
            jax.clear_caches()
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
