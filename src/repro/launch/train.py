"""Training driver: data -> sharded train steps -> checkpoints -> heartbeats.

Single-host CPU here (mesh (1,1) or whatever the device count allows), but
the loop is the production shape: deterministic resume from the latest
checkpoint, async checkpointing, heartbeat emission, straggler monitoring,
and elastic remesh on restart (the mesh shape is an argument; restore
re-shards).

  PYTHONPATH=src python -m repro.launch.train --arch smollm_360m \\
      --steps 200 --batch 8 --seq 128 --workdir /tmp/run1
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs.base import ParallelConfig, ShapeConfig, get_config, reduced
from repro.data import SyntheticLM, make_device_batch
from repro.distributed import step as step_mod
from repro.distributed.ft import Heartbeat, check_workers
from repro.distributed.sharding import current, use_mesh
from repro.launch.mesh import make_mesh
from repro.models import init_params
from repro.optim import adamw_init
from repro.runtime import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm_360m")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--workdir", default="/tmp/repro_train")
    ap.add_argument("--mesh", default="1x1", help="DATAxMODEL, e.g. 4x2")
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced config (CPU-sized)")
    ap.add_argument("--full-size", dest="reduced", action="store_false")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--host-id", type=int, default=0)
    ap.set_defaults(reduced=True)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    cfg = dataclasses.replace(cfg, remat="none" if args.reduced else cfg.remat)
    shape = ShapeConfig("train", args.seq, args.batch, "train")
    d, m = (int(x) for x in args.mesh.split("x"))
    mesh = make_mesh((d, m), ("data", "model"))

    os.makedirs(args.workdir, exist_ok=True)
    mgr = CheckpointManager(os.path.join(args.workdir, "ckpt"), keep=3)
    hb = Heartbeat(args.workdir, args.host_id)
    ds = SyntheticLM(cfg, shape, seed=0)

    with use_mesh(mesh):
        mc = current()
        jitted, (param_sh, opt_sh, batch_sh) = step_mod.make_train_step(
            cfg, ParallelConfig(), mc, peak_lr=args.lr, warmup=20,
            total_steps=args.steps)
        params = jax.jit(lambda k: init_params(k, cfg),
                         out_shardings=param_sh)(jax.random.key(0))
        opt = adamw_init(params, cfg.optim_state_dtype, cfg.optim_second_dtype)

        start = 0
        try:
            state_tpl = {"params": params, "opt": opt}
            state, start = mgr.restore(state_tpl, shardings={
                "params": param_sh, "opt": opt_sh})
            params, opt = state["params"], state["opt"]
            print(f"resumed from step {start} (elastic mesh {args.mesh})")
        except FileNotFoundError:
            print("fresh start")

        t0 = time.time()
        for step in range(start, args.steps):
            batch = make_device_batch(ds.batch_at(step), batch_sh)
            params, opt, metrics = jitted(params, opt, batch)
            if step % args.log_every == 0 or step == args.steps - 1:
                loss = float(metrics["loss"])
                dt = time.time() - t0
                print(f"step {step:5d}  loss {loss:.4f}  "
                      f"gnorm {float(metrics['grad_norm']):.3f}  "
                      f"lr {float(metrics['lr']):.2e}  {dt:.1f}s")
                hb.beat(step)
                stragglers = [w for w in check_workers(args.workdir)
                              if w.state != "healthy"]
                if stragglers:
                    print(f"  [ft] degraded workers: "
                          f"{[(w.host, w.state) for w in stragglers]}")
            if step and step % args.ckpt_every == 0:
                mgr.save({"params": params, "opt": opt}, step)
        mgr.save({"params": params, "opt": opt}, args.steps, block=True)
        print(f"done: {args.steps} steps, final loss "
              f"{float(metrics['loss']):.4f}")
        with open(os.path.join(args.workdir, "result.json"), "w") as f:
            json.dump({"final_loss": float(metrics["loss"]),
                       "steps": args.steps}, f)


if __name__ == "__main__":
    main()
