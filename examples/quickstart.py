"""Quickstart: train a ~100M-parameter GPT on the TrainMover runtime,
survive an expected migration AND an unexpected failure mid-run, and
verify the loss trajectory is exactly the one an uninterrupted run
produces.

    PYTHONPATH=src python examples/quickstart.py [--steps 300] [--small]

The cluster is simulated (8 machines, dp=2 x pp=2 + spares) but the
training math, collective ring-reduces, XLA compiles and state copies
are real; only network/bootstrap *timing* comes from the calibrated
cost model.
"""
from __future__ import annotations

import argparse
import sys
import time

sys.path.insert(0, "src")

import numpy as np

from repro.cluster.node import Cluster
from repro.cluster.simclock import SimClock
from repro.configs.gpt import tiny_gpt
from repro.core.controller import Controller
from repro.core.engine import PipelineEngine
from repro.core.sandbox import CommHooks
from repro.launch import compile_cache


def build(cfg, dp, pp, batch, seq, standby=1):
    cluster = Cluster(dp * pp + 2 + standby, device_capacity=32 * 2 ** 30)
    clock = SimClock()
    comm = CommHooks(clock)
    eng = PipelineEngine(cfg, dp=dp, pp=pp, global_batch=batch,
                         seq_len=seq, cluster=cluster, clock=clock,
                         comm=comm, micro_batches=2)
    return Controller(eng, standby_count=standby)


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--small", action="store_true",
                    help="2-layer d=128 model (fast CI mode)")
    args = ap.parse_args()

    if args.small:
        cfg = tiny_gpt(layers=2, d=128, heads=4, vocab=512)
        batch, seq = 8, 64
    else:
        # ~100M params: 12 layers x d=768 (GPT-2 small class)
        cfg = tiny_gpt(layers=12, d=768, heads=12, vocab=32768)
        batch, seq = 8, 256

    t0 = time.time()
    print(f"model={cfg.name}  steps={args.steps}")

    # --- reference run (no interruptions) --------------------------
    ref = build(cfg, 2, 2, batch, seq)
    ref.bootstrap_job(list(range(4)))
    third = max(args.steps // 3, 1)
    ref_losses = ref.train(3 * third)

    # --- interrupted run -------------------------------------------
    ctl = build(cfg, 2, 2, batch, seq)
    ctl.bootstrap_job(list(range(4)))
    losses = ctl.train(third)

    print(f"\n[{third}] expected migration (maintenance) ...")
    rep = ctl.expected_migration([ctl.engine.grid[(1, 1)]])
    print(f"  downtime={rep.downtime:.2f}s  overlapped={rep.overlap:.2f}s"
          f"  qps: +{rep.qps_added}/~{rep.qps_inherited} inherited"
          f"  mem_overhead={rep.mem_overhead_bytes:.0f}B")
    losses += ctl.train(third)

    print(f"\n[{2*third}] unexpected failure (GPU down) ...")
    rep2 = ctl.unexpected_failure(ctl.engine.grid[(0, 0)])
    print(f"  downtime={rep2.downtime:.2f}s  state via {rep2.state_path}"
          f"  promote={rep2.promote_s:.2f}s"
          f"  lost_iterations={rep2.lost_iterations}")
    losses += ctl.train(third)

    same = np.allclose(ref_losses, losses, rtol=0, atol=0)
    print(f"\nloss[0]={losses[0]:.4f} -> loss[-1]={losses[-1]:.4f}")
    print(f"trajectory bitwise-identical to uninterrupted run: {same}")
    print(f"downtime total={ctl.clock.lane_total('downtime'):.2f}s "
          f"(sim)  wall={time.time()-t0:.0f}s")
    assert same, "migration transparency violated!"
    assert losses[-1] < losses[0], "model did not learn"
    print("QUICKSTART OK")


if __name__ == "__main__":
    main()
