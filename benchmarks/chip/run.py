"""Chip benchmark of the TrainMover runtime: one run of one cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the cell's TPU chips;
see benchmarks/chip/README.md. JAX's persistent compilation cache is
kept at the fixed benchmarks/chip/.jax_cache of the checkout.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(HERE / ".jax_cache")
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

from chipbench import main  # noqa: E402

if __name__ == "__main__":
    main.main(t_start=T_START)
