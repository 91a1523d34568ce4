"""Readings that the limits of `correct` are set from (PERF.md).

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds <n> [<n> ...] [--out <file.jsonl>]

For each seed, in one process: the program's numbers, from the cell's
own set-up and one window cycle (as a run makes them), and the numbers
that these stand-ins give against the same reference:

- `control`: the reference in the configuration's next precision down;
- `half_batch`: half of the batch left out, the mean over the rest;
- `no_exchange`: the gradient exchange between DP replicas left out,
  each replica stepping on its own rows.

A step that returns its state unchanged reads 1 on `grad_gap` and
`update_gap` by their definition and needs no run. One JSON line per
seed goes to standard output (and is appended to `--out`).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(HERE / ".jax_cache")
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

from chipbench import compare, main, reference, spec  # noqa: E402


def readings(parts, cfg, ref):
    """A stand-in's numbers: `parts` are follows of its replicas."""
    got = [compare.follow_readings(p, cfg, ref["master0"]) for p in parts]
    merged = {"losses": [sum(g["losses"][t] for g in got) / len(got)
                         for t in range(len(got[0]["losses"]))]}
    for key in ("grad_norms", "grads", "update_norms"):
        merged[key] = {k: [g[key][k] for g in got] for k in got[0][key]}
    return compare.gaps(merged, compare.follow_readings(ref, cfg))


def stand_ins(cfg, seed, steps):
    ref = reference.follow(cfg, seed, steps)
    per_d = cfg["global_batch"] // cfg["dp"]
    halves = [range(d * per_d, (d + 1) * per_d) for d in range(cfg["dp"])]
    follow = lambda **kw: reference.follow(cfg, seed, steps, **kw)
    return {
        "control": readings([follow(precision="control")], cfg, ref),
        "half_batch": readings([follow(rows=halves[0])], cfg, ref),
        "no_exchange": readings([follow(rows=h) for h in halves], cfg,
                                ref),
    }


def main_(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--out")
    args = p.parse_args(argv)
    cell = spec.cell(args.workload)
    main.device_check(cell.chips)
    from repro.launch import compile_cache
    compile_cache.enable()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    steps = 1 + cell.traffic["warmup"].count("train")
    for seed in args.seeds:
        t0 = time.perf_counter()
        run = main.run_cell(cell, seed, 0.0, False, t0)
        t1 = time.perf_counter()
        line = {"workload": cell.name, "seed": seed,
                "program": {k: v["value"] for k, v in run["checks"].items()},
                **stand_ins(cell.config, seed, steps),
                "program_s": t1 - t0, "stand_ins_s": time.perf_counter() - t1}
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main_()
