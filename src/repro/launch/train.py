"""Training CLI.

CPU-scale (smoke config):
  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --smoke \
      --steps 50 --rows 2 --seq-len 128 --ckpt-dir /tmp/ckpt

Cluster-scale (production mesh; run on real TPU slices):
  python -m repro.launch.train --arch grok-1-314b --mesh multi ...
"""

from __future__ import annotations

import argparse
import json

import jax

from repro.configs import get_config
from repro.data import DocStream, Pipeline
from repro.launch.mesh import make_production_mesh
from repro.launch.shardings import activation_rules
from repro.models import LM
from repro.models.common import dtype_of, logical_axis_rules
from repro.optim import AdamW, warmup_cosine
from repro.sched.straggler import StragglerMonitor
from repro.train import LoopConfig, train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--rows", type=int, default=2,
                    help="batch rows per data shard")
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--shards", type=int, default=2,
                    help="data shards for the pipeline")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", choices=["none", "single", "multi"],
                    default="none")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    lm = LM(cfg)
    stream = DocStream(vocab_size=cfg.vocab_size,
                       mean_len=max(args.seq_len // 2, 16),
                       max_len=args.seq_len, seed=args.seed)
    monitor = StragglerMonitor(n_hosts=args.shards)
    pipe = Pipeline(stream, shard_dims=(args.shards,),
                    rows_per_shard=args.rows, seq_len=args.seq_len,
                    monitor=monitor)
    opt = AdamW(moments_dtype=dtype_of(cfg.moments_dtype))
    sch = warmup_cosine(args.lr, args.warmup, args.steps)
    loop = LoopConfig(
        steps=args.steps, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, seed=args.seed,
        microbatches=args.microbatches, log_every=args.log_every,
        metrics_hook=lambda step, row: print(
            f"step {step:5d} loss {row['loss']:.4f} "
            f"lr {row['lr']:.2e} dt {row['dt']*1e3:.0f}ms", flush=True))

    if args.mesh != "none":
        mesh = make_production_mesh(multi_pod=args.mesh == "multi")
        rules = activation_rules(cfg, mesh)
        with jax.set_mesh(mesh), logical_axis_rules(rules):
            state, history = train(lm, opt, sch, pipe, loop, monitor=monitor)
    else:
        state, history = train(lm, opt, sch, pipe, loop, monitor=monitor)

    print(json.dumps({"final_step": int(state.opt.step),
                      "first_loss": history[0]["loss"],
                      "final_loss": history[-1]["loss"]}))


if __name__ == "__main__":
    main()
