"""Chip smoke test: train gpt-medium at its published width on the
TrainMover runtime through an expected migration and an unexpected
failure, on one TPU, and check the trajectory against an uninterrupted
run.

    python3 chip_smoke.py

The path is the one examples/quickstart.py drives (PipelineEngine +
Controller, dp=2 x pp=2 with one general standby, every simulated
machine on the one chip) at gpt-medium's width (d_model 1024, 16 heads,
d_ff 4096, vocab 50304, seq 2048); only the depth is cut. Weights are
random from the engine's seed and the data is the seeded synthetic
stream. Downtimes are SimClock seconds from the cost model, not chip
time.

The script exits non-zero, with no result line, when JAX finds no TPU.
Its last line is the JSON result
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
The phase functions take the config and sizes, so tests run them on
the CPU at a tiny width.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.cluster.node import Cluster  # noqa: E402
from repro.cluster.simclock import SimClock  # noqa: E402
from repro.configs.gpt import GPT_MEDIUM  # noqa: E402
from repro.core.controller import Controller  # noqa: E402
from repro.core.engine import PipelineEngine  # noqa: E402
from repro.core.sandbox import CommHooks  # noqa: E402
from repro.launch import compile_cache  # noqa: E402

DP, PP, MICRO_BATCHES, GLOBAL_BATCH, STANDBY = 2, 2, 2, 4, 1
SEQ_LEN = 2048          # GPT-3 context length (arXiv:2005.14165)
LAYERS = 4              # of gpt-medium's 24: the most that fits one chip
ITERS = 6               # per run; the interrupted run trains 2 + 2 + 2
MAX_PEAK_FRACTION = 0.8  # of the device's bytes_limit


def device_check() -> dict:
    """The attached devices as JAX reports them; raises unless a TPU."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RuntimeError(f"no TPU attached: JAX found {devs[0].platform} "
                           f"devices")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def smoke_config(layers: int = LAYERS):
    return dataclasses.replace(GPT_MEDIUM, num_layers=layers)


def make_engine(cfg, seq_len: int, global_batch: int = GLOBAL_BATCH
                ) -> PipelineEngine:
    """The smoke's engine, laid out as examples/quickstart.build; it
    holds no device arrays until setup."""
    cluster = Cluster(DP * PP + 2 + STANDBY, device_capacity=32 * 2 ** 30)
    clock = SimClock()
    return PipelineEngine(cfg, dp=DP, pp=PP, global_batch=global_batch,
                          seq_len=seq_len, cluster=cluster, clock=clock,
                          comm=CommHooks(clock),
                          micro_batches=MICRO_BATCHES)


def build(cfg, seq_len: int, global_batch: int = GLOBAL_BATCH
          ) -> Controller:
    """A bootstrapped job on the smoke's engine."""
    ctl = Controller(make_engine(cfg, seq_len, global_batch),
                     standby_count=STANDBY)
    ctl.bootstrap_job(list(range(DP * PP)))
    return ctl


def train(ctl: Controller, iters: int):
    """(losses, wall seconds per iteration), each iteration timed up to
    the updated payloads being ready on the device."""
    losses, walls = [], []
    for _ in range(iters):
        t0 = time.perf_counter()
        losses += ctl.train(1)
        jax.block_until_ready(
            [(ctl.cluster[mid].payload["param_segs"],
              ctl.cluster[mid].payload["opt"])
             for mid in ctl.engine.grid.values()])
        walls.append(time.perf_counter() - t0)
    return losses, walls


def compile_seconds(ctl: Controller) -> dict:
    """Measured compile seconds of every role the job compiled: the
    engine's stage roles and each standby's warm roles."""
    out = {f"stage{s}": ctl.engine.compile_role(s).compile_seconds
           for s in range(ctl.engine.pp)}
    for mid in ctl.standbys:
        for rk, role in ctl.cluster[mid].warm_roles.items():
            out[f"standby{mid}:{rk}"] = role.compile_seconds
    return out


def live_bytes() -> int:
    return sum(a.nbytes for a in jax.live_arrays())


def reference_run(cfg, seq_len: int, iters: int = ITERS,
                  global_batch: int = GLOBAL_BATCH) -> dict:
    """Phase (b): the uninterrupted run. Its controller is released
    before returning; `live_bytes_after` is what the device still holds
    afterwards."""
    ctl = build(cfg, seq_len, global_batch)
    losses, walls = train(ctl, iters)
    compiled = compile_seconds(ctl)
    del ctl
    gc.collect()
    return {"losses": losses, "walls": walls, "compile_s": compiled,
            "live_bytes_after": live_bytes()}


def interrupted_run(cfg, seq_len: int, iters: int = ITERS,
                    global_batch: int = GLOBAL_BATCH) -> dict:
    """Phase (c): the same job, with an expected migration of grid slot
    (1, 1) after a third of the iterations and an unexpected failure of
    slot (0, 0) after two thirds."""
    third = iters // 3
    ctl = build(cfg, seq_len, global_batch)
    losses, walls = train(ctl, third)
    mig = ctl.expected_migration([ctl.engine.grid[(1, 1)]])
    more, w = train(ctl, third)
    losses, walls = losses + more, walls + w
    fail = ctl.unexpected_failure(ctl.engine.grid[(0, 0)])
    more, w = train(ctl, iters - 2 * third)
    return {"losses": losses + more, "walls": walls + w,
            "compile_s": compile_seconds(ctl),
            "migration_downtime": mig.downtime,
            "failure_downtime": fail.downtime,
            "failure_state_path": fail.state_path}


def parity(ref, got) -> dict:
    """Phase (d): bitwise comparison of two loss trajectories."""
    ref, got = np.asarray(ref), np.asarray(got)
    same_len = ref.shape == got.shape
    diff = np.flatnonzero(ref != got) if same_len else np.array([0])
    return {"identical": same_len and diff.size == 0,
            "max_abs_diff": (float(np.max(np.abs(ref - got)))
                             if same_len else float("inf")),
            "first_divergence": int(diff[0]) if diff.size else None,
            "falling": bool(got[-1] < got[0])}


def memory_facts(device) -> dict:
    """Phase (e): the device's peak memory since the process began, and
    its limit."""
    stats = device.memory_stats()
    return {k: stats[k] for k in ("peak_bytes_in_use", "bytes_limit")}


def _fmt_walls(walls) -> str:
    return " ".join(repr(w) for w in walls)


def main() -> None:
    cache = compile_cache.enable()
    cache_held = cache.is_dir() and any(cache.iterdir())
    dev = device_check()
    print(f"(a) device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}")
    print(f"    compile cache {cache}: "
          f"{'held entries at start' if cache_held else 'empty at start'}")
    cfg = smoke_config()
    print(f"config: {cfg.name} d_model={cfg.d_model} heads={cfg.num_heads}"
          f" d_ff={cfg.d_ff} vocab={cfg.vocab_size} seq_len={SEQ_LEN} "
          f"dp={DP} pp={PP} micro_batches={MICRO_BATCHES} "
          f"global_batch={GLOBAL_BATCH} standby={STANDBY} dtype=float32")
    print(f"reduced: num_layers {GPT_MEDIUM.num_layers} -> {cfg.num_layers}")

    ref = reference_run(cfg, SEQ_LEN)
    print(f"(b) reference losses: {ref['losses']}")
    print(f"    live device bytes after release: {ref['live_bytes_after']}")

    got = interrupted_run(cfg, SEQ_LEN)
    print(f"(c) interrupted losses: {got['losses']}")
    print(f"    expected migration of (1,1): downtime "
          f"{got['migration_downtime']!r} SimClock s (modelled)")
    print(f"    unexpected failure of (0,0): downtime "
          f"{got['failure_downtime']!r} SimClock s (modelled), state via "
          f"{got['failure_state_path']}")

    par = parity(ref["losses"], got["losses"])
    print(f"(d) parity: bitwise_identical={par['identical']} "
          f"max_abs_diff={par['max_abs_diff']!r} "
          f"first_divergence={par['first_divergence']} "
          f"loss {got['losses'][0]!r} -> {got['losses'][-1]!r} "
          f"falling={par['falling']}")

    mem = memory_facts(jax.devices()[0])
    frac = mem["peak_bytes_in_use"] / mem["bytes_limit"]
    print(f"(e) iteration s (wall, smoke only, not a benchmark): "
          f"reference {_fmt_walls(ref['walls'])}; "
          f"interrupted {_fmt_walls(got['walls'])}")
    for run, cs in (("reference", ref["compile_s"]),
                    ("interrupted", got["compile_s"])):
        print(f"    compile_seconds {run}: " + " ".join(
            f"{k}={v!r}" for k, v in cs.items()))
    print(f"    peak_bytes_in_use={mem['peak_bytes_in_use']} "
          f"bytes_limit={mem['bytes_limit']} ({frac:.3f} of the limit)")

    failed = [name for name, bad in (
        ("parity", not par["identical"]),
        ("loss not falling", not par["falling"]),
        ("device arrays outlived the reference controller",
         ref["live_bytes_after"] != 0),
        (f"peak above {MAX_PEAK_FRACTION} of the limit",
         frac > MAX_PEAK_FRACTION)) if bad]
    if failed:
        raise SystemExit(f"chip smoke FAILED: {', '.join(failed)}")
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
