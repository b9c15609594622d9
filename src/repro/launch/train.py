"""Training launcher: ``python -m repro.launch.train --arch rubicall
--steps 200``.

Builds the best mesh for the attached devices, wires the data pipeline
for the arch family (synthetic squiggles for basecallers, synthetic token
streams for LMs), runs the fault-tolerant loop (checkpoint/resume,
optional int8 grad compression), and prints metric history.

On a real cluster this process runs per host under
``jax.distributed.initialize`` (args --coordinator/--num-hosts kept
explicit below); the mesh/sharding code is identical — GSPMD handles the
host boundary. Failure handling: the watchdog + elastic reshard path in
``training/elastic.py`` (see DESIGN.md §5).
"""
from __future__ import annotations

import argparse
import json

import jax

from repro.config import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.training.optimizer import AdamWConfig
from repro.training.train_loop import TrainLoopConfig, run


def data_for(cfg, batch: int, seq: int):
    if cfg.family == "basecaller":
        from repro.data.squiggle import SquiggleConfig, batches
        import jax.numpy as jnp
        for b in batches(SquiggleConfig(chunk_len=seq), batch):
            yield {k: jnp.asarray(v) for k, v in b.items()}
    else:
        from repro.data.tokens import token_batches
        yield from token_batches(cfg, batch, seq)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="rubicall")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--lr", type=float, default=2e-3)
    ap.add_argument("--grad-compress-bits", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--coordinator", default="",
                    help="host:port for multi-host jax.distributed")
    ap.add_argument("--num-hosts", type=int, default=1)
    ap.add_argument("--host-id", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    if args.coordinator:
        jax.distributed.initialize(args.coordinator, args.num_hosts,
                                   args.host_id)

    cfg = get_config(args.arch + ("-smoke" if args.smoke else ""))
    mesh = make_host_mesh(args.model_parallel)
    opt_cfg = AdamWConfig(lr=args.lr, total_steps=args.steps)
    loop = TrainLoopConfig(steps=args.steps, ckpt_dir=args.ckpt_dir,
                           ckpt_every=args.ckpt_every,
                           n_micro=args.n_micro,
                           grad_compress_bits=args.grad_compress_bits)
    out = run(cfg, opt_cfg, loop, data_for(cfg, args.batch, args.seq),
              mesh=mesh)
    for row in out["history"]:
        print(json.dumps(row))


if __name__ == "__main__":
    main()
