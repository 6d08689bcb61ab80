"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b \
        --smoke --steps 50 --batch 8 --seq 256 --ckpt-dir /tmp/ckpt

Fault-tolerance behavior (the restart drill in tests/test_failover.py):
  * checkpoints every ``--ckpt-every`` steps (params, optimizer, data
    cursor) via repro.ckpt — atomic renames, latest-k retention;
  * on start, resumes from the newest checkpoint automatically; the data
    pipeline's batch(step) is pure, so the token stream replays exactly;
  * ``--simulate-failure-at K`` kills the process at step K (exercised by
    the failover test to prove restart equivalence).

Scale-out notes (how this maps to thousands of nodes):
  * this launcher is per-host; under multi-host JAX the same code runs on
    every host with jax.distributed.initialize() and the mesh from
    launch/mesh.py (the multi-pod dry-run proves those shardings compile);
  * stragglers: training is synchronous SPMD; mitigation is (a) the
    checkpoint/restart path above for fail-stop nodes, and (b) elastic
    restart — restore() re-shards onto whatever mesh is alive (see
    --elastic-remesh smoke flag which restores onto a different mesh
    shape to prove the path);
  * gradient compression: --compress-bits N switches to the shard_map
    step with the INTAC integer all-reduce + error feedback.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import checkpoint as ckpt
from repro.configs import get_config, get_smoke_config
from repro.data.pipeline import DataCfg, make_source
from repro.distributed.collectives import (init_residuals,
                                           make_shardmap_train_step)
from repro.launch.mesh import make_mesh
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.optim import adamw
from repro.train.steps import make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data", default="", help="packed token file (optional)")
    ap.add_argument("--moe-impl", default="dense",
                    choices=("dense", "capacity"))
    ap.add_argument("--compress-bits", type=int, default=0,
                    help=">0: shard_map step with INTAC compressed "
                         "all-reduce at this bit width")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--simulate-failure-at", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    key = jax.random.PRNGKey(args.seed)
    params = init_params(key, cfg)
    opt_state = adamw.init(params)
    lr_fn = adamw.cosine_schedule(args.lr, args.warmup, args.steps)

    dcfg = DataCfg(vocab=cfg.vocab, seq_len=args.seq,
                   global_batch=args.batch, seed=args.seed)
    source = make_source(dcfg, args.data or None)

    use_shardmap = args.compress_bits > 0 or args.microbatches > 1
    residuals = init_residuals(params) if use_shardmap else None
    if use_shardmap:
        mesh = make_mesh((jax.device_count(),), ("data",))
        step_fn = make_shardmap_train_step(
            cfg, mesh, lr_fn=lr_fn,
            num_microbatches=args.microbatches,
            compress_bits=args.compress_bits or None,
            moe_impl=args.moe_impl)
        step_fn = jax.jit(step_fn)
    else:
        step_fn = jax.jit(make_train_step(cfg, lr_fn=lr_fn, remat=False,
                                          moe_impl=args.moe_impl))

    start = 0
    if args.ckpt_dir:
        state = {"params": params, "opt": opt_state}
        if residuals is not None:
            state["residuals"] = residuals
        # newest *valid* snapshot: a crash mid-save leaves a .tmp dir (no
        # manifest) and a flipped bit fails the CRC sidecar — both fall
        # back to the previous verified step instead of crashing or
        # silently resuming from garbage
        restored = ckpt.restore_latest_valid(args.ckpt_dir, state)
        if restored is not None:
            state, manifest, latest = restored
            params, opt_state = state["params"], state["opt"]
            residuals = state.get("residuals", residuals)
            start = manifest["extra"]["next_step"]
            print(f"[restore] resumed from step {latest} -> next {start}",
                  flush=True)

    t0 = time.time()
    for step in range(start, args.steps):
        batch = {k: jnp.asarray(v) for k, v in source.batch(step).items()}
        if use_shardmap:
            params, opt_state, residuals, metrics = step_fn(
                params, opt_state, residuals, batch)
        else:
            params, opt_state, metrics = step_fn(params, opt_state, batch)
        if step % args.log_every == 0 or step == args.steps - 1:
            loss = float(metrics["loss"])
            dt = time.time() - t0
            print(f"step {step:5d} loss {loss:.4f} "
                  f"lr {float(metrics['lr']):.2e} ({dt:.1f}s)", flush=True)
        if args.ckpt_dir and ckpt.save_every(step, args.ckpt_every):
            state = {"params": params, "opt": opt_state}
            if residuals is not None:
                state["residuals"] = residuals
            ckpt.save(args.ckpt_dir, step, state,
                      extra={"next_step": step + 1, "arch": args.arch})
            print(f"[ckpt] saved step {step}", flush=True)
        if args.simulate_failure_at and step == args.simulate_failure_at:
            print(f"[failure] simulated crash at step {step}", flush=True)
            os._exit(17)

    print(f"done: {args.steps - start} steps, "
          f"final loss {float(metrics['loss']):.4f}")
    return float(metrics["loss"])


if __name__ == "__main__":
    main()
