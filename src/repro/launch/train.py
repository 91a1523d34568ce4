"""Training launcher (XLA plane): jit-compiled data-parallel/TP training
of any registered architecture on the active device set.

    # CPU sanity run (reduced config):
    PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b \
        --reduced --steps 20 --batch 8 --seq 64

    # on a real TPU slice the same entry point trains the full config
    # against the production mesh:
    PYTHONPATH=src python -m repro.launch.train --arch qwen2-moe-a2.7b \
        --shape train_4k --mesh 16x16

Checkpoints are written every --ckpt-every steps; --resume restarts
from the newest one (the stop/restart baseline the TrainMover runtime
benchmarks compare against).
"""
from __future__ import annotations

import argparse
import glob
import os
import time

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPES, ShapeCfg
from repro.launch import compile_cache
from repro.launch.mesh import make_production_mesh
from repro.models import registry
from repro.train import checkpoint as ckpt_mod
from repro.train import data as data_mod
from repro.train import step as step_mod
from repro.train.optimizer import AdamCfg


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b",
                    choices=list(registry.ARCH_IDS) + ["gpt-medium"])
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mesh", default="none",
                    choices=["none", "16x16", "2x16x16"])
    ap.add_argument("--ckpt-dir", default="results/ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    cfg = (registry.reduced_config(args.arch) if args.reduced
           else registry.get_config(args.arch))
    if args.shape:
        shape = SHAPES[args.shape]
    else:
        shape = ShapeCfg("custom", "train", args.seq, args.batch)
    mesh = None
    if args.mesh != "none":
        mesh = make_production_mesh(multi_pod=args.mesh == "2x16x16")

    run = step_mod.RunCfg(adam=AdamCfg(lr=args.lr, warmup_steps=20),
                          grad_accum=int(os.environ.get(
                              "REPRO_GRAD_ACCUM", "1")))
    stream = data_mod.stream_for(cfg, shape)

    t0 = time.time()
    start_step = 0
    if args.resume:
        hits = sorted(glob.glob(f"{args.ckpt_dir}/{cfg.name}-*.pkl"))
        if hits:
            state, start_step = ckpt_mod.load(hits[-1])
            state = jax.tree.map(jnp.asarray, state)
            print(f"resumed from {hits[-1]} @ step {start_step}")
    if start_step == 0:
        state = step_mod.init_state(cfg, run, jax.random.PRNGKey(run.seed),
                                    mesh)
    train_step = step_mod.make_train_step(cfg, run, mesh)
    if mesh is not None:
        sh = step_mod.state_shardings(cfg, mesh)
        train_step = jax.jit(train_step, in_shardings=(sh, None),
                             out_shardings=(sh, None),
                             donate_argnums=(0,))
    else:
        train_step = jax.jit(train_step, donate_argnums=(0,))
    print(f"arch={cfg.name} params={registry.count_params(cfg):,} "
          f"batch={shape.global_batch} seq={shape.seq_len} "
          f"devices={len(jax.devices())}")

    for step in range(start_step, args.steps):
        batch = {k: jnp.asarray(v) for k, v in
                 stream.batch(step).items()}
        state, stats = train_step(state, batch)
        if (step + 1) % args.log_every == 0 or step == start_step:
            print(f"step {step + 1:>5d}  loss {float(stats['loss']):.4f}"
                  f"  gnorm {float(stats['grad_norm']):.3f}"
                  f"  lr {float(stats['lr']):.2e}"
                  f"  {time.time() - t0:.0f}s")
        if (step + 1) % args.ckpt_every == 0:
            path = f"{args.ckpt_dir}/{cfg.name}-{step + 1:07d}.pkl"
            nbytes = ckpt_mod.save(path, jax.tree.map(lambda x: x, state),
                                   step + 1)
            print(f"checkpoint -> {path} ({nbytes / 2 ** 20:.1f} MiB)")
    print("TRAINING DONE")


if __name__ == "__main__":
    main()
